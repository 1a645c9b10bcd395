"""Pallas flash-attention kernel parity in INTERPRET mode (CPU-executable).

The kernel only ever executes on the real chip; interpret mode runs the
same kernel logic through the Pallas interpreter so fwd/bwd numerics —
including the in-kernel ALiBi bias and the lse ring path — are validated in
every CPU test run. Oracle: ``xla_attention`` / ``xla_chunk_attention``.
On the chip (real Mosaic lowering) the kernel is held inside the model:
every training cell of ``BENCHMARK.json`` compares the program's losses and
updated leaves with ``benchmark/reference/`` (plain fp32, no kernel) before
it reports ``correct``, and ``chip_smoke.py`` holds served logits to an XLA
forward at 2e-2.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.ops.attention import xla_attention
from photon_tpu.ops.flash_attention import flash_attention, flash_attention_with_lse
from photon_tpu.ops.ring_attention import xla_chunk_attention

B, S, H, D = 2, 256, 4, 64
BLOCK = 128


def _qkv(d=D, s=S, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(key, (B, s, H, d), dtype) for key in ks)


def _rel(a, ref):
    a = np.asarray(a, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.linalg.norm(a - ref) / (np.linalg.norm(ref) + 1e-12))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("alibi", [False, True])
def test_forward_parity(causal, alibi):
    q, k, v = _qkv()
    o_k = flash_attention(q, k, v, causal=causal, alibi=alibi,
                          block_q=BLOCK, block_k=BLOCK, interpret=True)
    o_x = xla_attention(q, k, v, causal=causal, alibi=alibi)
    assert _rel(o_k, o_x) < 2e-5, (causal, alibi)


@pytest.mark.parametrize("alibi", [False, True])
def test_backward_parity(alibi):
    q, k, v = _qkv()
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

    def loss(fn):
        return jax.grad(
            lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum(), argnums=(0, 1, 2)
        )

    gk = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, alibi=alibi, block_q=BLOCK, block_k=BLOCK, interpret=True
    ))(q, k, v)
    gx = loss(lambda q, k, v: xla_attention(q, k, v, causal=True, alibi=alibi))(q, k, v)
    for name, a, ref in zip(("dq", "dk", "dv"), gk, gx):
        assert _rel(a, ref) < 5e-5, (name, alibi)


def test_lane_padded_d_head():
    """d_head 80 < 128: zero-pad path must not perturb outputs."""
    q, k, v = _qkv(d=80)
    o_k = flash_attention(q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK, interpret=True)
    o_x = xla_attention(q, k, v, causal=True)
    assert _rel(o_k, o_x) < 2e-5


def test_d_head_128_1b_shape():
    q, k, v = _qkv(d=128)
    o_k = flash_attention(q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK, interpret=True)
    o_x = xla_attention(q, k, v, causal=True)
    assert _rel(o_k, o_x) < 2e-5


def test_bf16_inputs():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    o_k = flash_attention(q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK, interpret=True)
    o_x = xla_attention(q, k, v, causal=True)
    assert _rel(o_k, o_x) < 2e-2  # bf16 tolerance


def test_lse_path_parity():
    """The ring inner kernel: (o, lse) vs the XLA chunk oracle off-diagonal."""
    q, k, v = _qkv(s=128)
    o_k, lse_k = flash_attention_with_lse(
        q, k, v, causal=True, q_start=128, k_start=0,
        block_q=BLOCK, block_k=BLOCK, interpret=True,
    )
    o_x, lse_x = xla_chunk_attention(q, k, v, q_start=128, k_start=0, causal=True)
    assert _rel(o_k, o_x) < 2e-5
    assert _rel(lse_k, lse_x) < 2e-5


def test_alibi_long_range_decay():
    """Behavioral: with ALiBi, attention to distant keys decays — the last
    query's effective context is shorter than without ALiBi."""
    q, k, v = _qkv(seed=3)
    o_plain = flash_attention(q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK, interpret=True)
    o_alibi = flash_attention(q, k, v, causal=True, alibi=True,
                              block_q=BLOCK, block_k=BLOCK, interpret=True)
    # must actually differ (bias applied), and both be finite
    assert _rel(o_alibi, o_plain) > 1e-3
    assert np.isfinite(np.asarray(o_alibi)).all()


@pytest.mark.parametrize("block", [256, 512])
def test_large_tile_parity(block):
    """The 512-wide tiles ``pick_tiles`` may choose on the chip must be
    numerically correct BEFORE their first on-chip execution — fwd + bwd
    at a sequence long enough (1024) that multiple 512 tiles and the
    causal off-diagonal both exercise."""
    q, k, v = _qkv(s=1024, seed=7)
    o_k = flash_attention(q, k, v, causal=True, block_q=block, block_k=block,
                          interpret=True)
    o_x = xla_attention(q, k, v, causal=True)
    assert _rel(o_k, o_x) < 2e-5, block

    w = jax.random.normal(jax.random.PRNGKey(8), o_x.shape)

    def loss(fn):
        return jax.grad(
            lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum(),
            argnums=(0, 1, 2),
        )

    gk = loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=True
    ))(q, k, v)
    gx = loss(lambda q, k, v: xla_attention(q, k, v, causal=True))(q, k, v)
    for a, ref in zip(gk, gx):
        assert _rel(a, ref) < 2e-4, block


@pytest.mark.parametrize("group", [2, 4])
@pytest.mark.parametrize("alibi", [False, True])
def test_gqa_native_parity(group, alibi):
    """Grouped-query attention runs NATIVELY in the kernel (k/v at h_kv
    width, q heads index-mapped onto kv group rows — no repeated-kv tensor;
    the dkv backward accumulates each kv row over its whole q-head group).
    Oracle: kv replicated to full width + the XLA path; jax.grad through
    the replication sums group members, so dk/dv shapes and values must
    match the kernel's kv-row-major outputs exactly."""
    q, _, _ = _qkv(s=256)
    h_kv = H // group
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    k = jax.random.normal(ks[0], (B, 256, h_kv, D))
    v = jax.random.normal(ks[1], (B, 256, h_kv, D))

    def rep(x):
        return jnp.repeat(x, group, axis=2)

    o_k = flash_attention(q, k, v, causal=True, alibi=alibi,
                          block_q=128, block_k=128, interpret=True)
    o_x = xla_attention(q, rep(k), rep(v), causal=True, alibi=alibi)
    assert _rel(o_k, o_x) < 2e-5

    w = jax.random.normal(jax.random.PRNGKey(12), o_x.shape)
    gk = jax.grad(
        lambda q, k, v: (flash_attention(
            q, k, v, causal=True, alibi=alibi, block_q=128, block_k=128,
            interpret=True) * w).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gx = jax.grad(
        lambda q, k, v: (xla_attention(
            q, rep(k), rep(v), causal=True, alibi=alibi) * w).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, ref in zip(gk, gx):
        assert a.shape == ref.shape
        assert _rel(a, ref) < 2e-4


def test_lse_path_gqa_parity():
    """The ring inner kernel with grouped kv (_flash_lse h_q plumbing):
    (o, lse) forward AND gradients through BOTH outputs vs the
    replicated-kv chunk oracle — covers the _flash_lse_bwd group-reshape
    recompute, which no TPU is needed to regress."""
    group = 2
    q, _, _ = _qkv(s=128)
    h_kv = H // group
    ks = jax.random.split(jax.random.PRNGKey(21), 2)
    k = jax.random.normal(ks[0], (B, 128, h_kv, D))
    v = jax.random.normal(ks[1], (B, 128, h_kv, D))

    def rep(x):
        return jnp.repeat(x, group, axis=2)

    o_k, lse_k = flash_attention_with_lse(
        q, k, v, causal=True, q_start=128, k_start=0,
        block_q=BLOCK, block_k=BLOCK, interpret=True,
    )
    o_x, lse_x = xla_chunk_attention(q, rep(k), rep(v), q_start=128, k_start=0,
                                     causal=True)
    assert _rel(o_k, o_x) < 2e-5
    assert _rel(lse_k, lse_x) < 2e-5

    wo = jax.random.normal(jax.random.PRNGKey(22), o_x.shape)
    wl = jax.random.normal(jax.random.PRNGKey(23), lse_x.shape)

    def loss_kernel(q, k, v):
        o, lse = flash_attention_with_lse(
            q, k, v, causal=True, q_start=128, k_start=0,
            block_q=BLOCK, block_k=BLOCK, interpret=True)
        return (o * wo).sum() + (lse * wl).sum()

    def loss_ref(q, k, v):
        o, lse = xla_chunk_attention(q, rep(k), rep(v), q_start=128, k_start=0,
                                     causal=True)
        return (o * wo).sum() + (lse * wl).sum()

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, ref in zip(gk, gx):
        assert a.shape == ref.shape
        assert _rel(a, ref) < 2e-4


# ---------------------------------------------------------------------------
# clamped index maps (PR 28): dead causal tiles repeat a live block's index
# ---------------------------------------------------------------------------


def _clamp_case(case):
    """q, k, v and the oracle's kv for one case at seq 512, tile 128: a 4 x 4
    grid with 6 dead tiles whose fetches the clamped maps drop."""
    s_k = 512
    s_q = 256 if case == "s_q!=s_k" else 512
    h_kv = H // 2 if case == "gqa2" else H
    ks = jax.random.split(jax.random.PRNGKey(31), 4)
    q = jax.random.normal(ks[0], (B, s_q, H, D))
    k = jax.random.normal(ks[1], (B, s_k, h_kv, D))
    v = jax.random.normal(ks[2], (B, s_k, h_kv, D))
    w = jax.random.normal(ks[3], (B, s_q, H, D))
    rep = (lambda x: jnp.repeat(x, H // h_kv, axis=2)) if h_kv != H else (lambda x: x)
    return q, k, v, w, rep, case == "alibi"


@pytest.mark.parametrize("case", ["plain", "alibi", "gqa2", "s_q!=s_k"])
def test_clamped_maps_forward_and_lse(case):
    q, k, v, _, rep, alibi = _clamp_case(case)
    o_k = flash_attention(q, k, v, causal=True, alibi=alibi,
                          block_q=128, block_k=128, interpret=True)
    o_x = xla_attention(q, rep(k), rep(v), causal=True, alibi=alibi)
    assert _rel(o_k, o_x) < 2e-5, case
    if not alibi:  # the lse variant takes no bias
        s_q, s_k = q.shape[1], k.shape[1]
        _, lse_k = flash_attention_with_lse(
            q, k, v, causal=True, q_start=s_k - s_q, k_start=0,
            block_q=128, block_k=128, interpret=True)
        _, lse_x = xla_chunk_attention(q, rep(k), rep(v), q_start=s_k - s_q,
                                       k_start=0, causal=True)
        assert _rel(lse_k, lse_x) < 2e-5, case


@pytest.mark.parametrize("case", ["plain", "alibi", "gqa2", "s_q!=s_k"])
def test_clamped_maps_gradients(case):
    q, k, v, w, rep, alibi = _clamp_case(case)
    gk = jax.grad(
        lambda q, k, v: (flash_attention(
            q, k, v, causal=True, alibi=alibi, block_q=128, block_k=128,
            interpret=True) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(
        lambda q, k, v: (xla_attention(
            q, rep(k), rep(v), causal=True, alibi=alibi) * w).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, ref in zip(("dq", "dk", "dv"), gk, gx):
        assert a.shape == ref.shape
        assert _rel(a, ref) < 2e-4, (case, name)


def test_asymmetric_tiles_parity():
    """The three launches may run different, non-square tiles: forward and
    gradients at q 256 x k 128 and q 128 x k 256 on seq 512."""
    q, k, v, w, _, _ = _clamp_case("plain")
    gx = jax.grad(lambda q, k, v: (xla_attention(q, k, v, causal=True) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for bq, bk in ((256, 128), (128, 256)):
        gk = jax.grad(
            lambda q, k, v: (flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk,
                interpret=True) * w).sum(), argnums=(0, 1, 2))(q, k, v)
        for a, ref in zip(gk, gx):
            assert _rel(a, ref) < 2e-4, (bq, bk)


def test_derived_tiles_parity():
    """``block_q=None`` (what the models pass): the tiles come from
    ``pick_tiles`` and the result is the oracle's."""
    from photon_tpu.ops.flash_attention import LANE, pick_tiles

    q, k, v, w, _, _ = _clamp_case("plain")
    assert pick_tiles(512, 512, LANE, 4).blocks == ((512, 512),) * 3
    o_k = flash_attention(q, k, v, causal=True, interpret=True)
    assert _rel(o_k, xla_attention(q, k, v, causal=True)) < 2e-5
    gk = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, causal=True, interpret=True) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(lambda q, k, v: (xla_attention(q, k, v, causal=True) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, ref in zip(gk, gx):
        assert _rel(a, ref) < 2e-4


def test_non_causal_call_keeps_the_identity_maps():
    """Without a mask every tile is live: the clamped maps hand every grid
    index back untouched, and the 4 x 4 grid gives the oracle's output and
    gradients."""
    from photon_tpu.ops.flash_attention import _kv_block, _q_block

    kw = dict(causal=False, block_q=128, block_k=128, offset=0)
    assert all(_kv_block(i, j, n_k=4, **kw) == j and _q_block(i, j, n_q=4, **kw) == i
               for i in range(4) for j in range(4))
    q, k, v, w, _, _ = _clamp_case("plain")
    gk = jax.grad(
        lambda q, k, v: (flash_attention(
            q, k, v, causal=False, block_q=128, block_k=128,
            interpret=True) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(lambda q, k, v: (xla_attention(q, k, v, causal=False) * w).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, ref in zip(gk, gx):
        assert _rel(a, ref) < 2e-4


# ---------------------------------------------------------------------------
# strips (PR 39): a tile on the diagonal multiplies each strip's live extent
# ---------------------------------------------------------------------------

# s_q, s_k, tile, STRIP_ROWS for all three launches, kv heads, ALiBi
STRIP_CASES = {
    "one-tile": (512, 512, 512, 128, H, False),          # the whole sequence, 4 strips
    "beside-interior": (512, 512, 256, 128, H, False),   # 2 x 2 tiles, one under the diagonal
    "one-strip": (512, 512, 256, 256, H, False),         # sub = tile
    "alibi": (512, 512, 512, 128, H, True),
    "gqa2": (512, 512, 256, 128, H // 2, False),
    "whole-tile-offset": (256, 512, 256, 128, H, False),  # s_q != s_k, offset one tile
}


def _strip_case(case, monkeypatch):
    from photon_tpu.ops import flash_attention as fa

    s_q, s_k, tile, sub, h_kv, alibi = STRIP_CASES[case]
    monkeypatch.setattr(fa, "STRIP_ROWS", dict.fromkeys(("fwd", "dq", "dkv"), sub))
    for launch in ("fwd", "dq", "dkv"):  # the strips engage, and skip work
        assert fa.strip_rows(launch, tile, tile, causal=True, offset=s_k - s_q) == sub
        live, _ = fa.live_tiles(s_q, s_k, tile, tile)
        executed = fa.executed_pairs(launch, s_q, s_k, tile, tile)
        assert (executed < live * tile * tile) == (sub < tile)
    ks = jax.random.split(jax.random.PRNGKey(39), 4)
    q = jax.random.normal(ks[0], (B, s_q, H, D))
    k = jax.random.normal(ks[1], (B, s_k, h_kv, D))
    v = jax.random.normal(ks[2], (B, s_k, h_kv, D))
    w = jax.random.normal(ks[3], (B, s_q, H, D))
    rep = (lambda x: jnp.repeat(x, H // h_kv, axis=2)) if h_kv != H else (lambda x: x)
    return q, k, v, w, rep, tile, alibi


@pytest.mark.parametrize("case", list(STRIP_CASES))
def test_strips_forward_and_lse(case, monkeypatch):
    q, k, v, _, rep, tile, alibi = _strip_case(case, monkeypatch)
    o_k = flash_attention(q, k, v, causal=True, alibi=alibi,
                          block_q=tile, block_k=tile, interpret=True)
    o_x = xla_attention(q, rep(k), rep(v), causal=True, alibi=alibi)
    assert _rel(o_k, o_x) < 2e-5, case
    if not alibi:  # the lse variant takes no bias
        s_q, s_k = q.shape[1], k.shape[1]
        o_l, lse_k = flash_attention_with_lse(
            q, k, v, causal=True, q_start=s_k - s_q, k_start=0,
            block_q=tile, block_k=tile, interpret=True)
        _, lse_x = xla_chunk_attention(q, rep(k), rep(v), q_start=s_k - s_q,
                                       k_start=0, causal=True)
        assert _rel(o_l, o_x) < 2e-5, case
        assert _rel(lse_k, lse_x) < 2e-5, case


@pytest.mark.parametrize("case", list(STRIP_CASES))
def test_strips_gradients(case, monkeypatch):
    q, k, v, w, rep, tile, alibi = _strip_case(case, monkeypatch)
    gk = jax.grad(
        lambda q, k, v: (flash_attention(
            q, k, v, causal=True, alibi=alibi, block_q=tile, block_k=tile,
            interpret=True) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(
        lambda q, k, v: (xla_attention(
            q, rep(k), rep(v), causal=True, alibi=alibi) * w).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, ref in zip(("dq", "dk", "dv"), gk, gx):
        assert a.shape == ref.shape
        assert _rel(a, ref) < 2e-4, (case, name)


def test_odd_offset_keeps_the_whole_tile_body():
    """A chunk whose offset is no whole number of tiles (ring attention's
    kernel at an uneven start) has no static diagonal inside a tile: the
    strips stand down and the masked whole-tile body gives the oracle's
    ``(o, lse)``."""
    from photon_tpu.ops import flash_attention as fa

    assert fa.strip_rows("fwd", BLOCK, BLOCK, causal=True, offset=192) == 0
    assert fa.strip_rows("fwd", BLOCK, BLOCK, causal=True, offset=256) == BLOCK
    assert fa.strip_rows("fwd", 256, 128, causal=True, offset=0) == 0  # not square
    assert fa.strip_rows("fwd", BLOCK, BLOCK, causal=False, offset=0) == 0
    q, k, v = _qkv(s=256, seed=5)
    o_k, lse_k = flash_attention_with_lse(
        q, k, v, causal=True, q_start=192, k_start=0,
        block_q=BLOCK, block_k=BLOCK, interpret=True)
    o_x, lse_x = xla_chunk_attention(q, k, v, q_start=192, k_start=0, causal=True)
    assert _rel(o_k, o_x) < 2e-5
    assert _rel(lse_k, lse_x) < 2e-5


@pytest.mark.parametrize("alibi", [False, True])
def test_strip_body_is_the_whole_tile_body(alibi, monkeypatch):
    """At one pinned tile, strips of 128 against ``STRIP_ROWS`` 0 (every live
    tile whole under the mask, as before PR 39): the same pairs summed in
    another order, so equal to rounding, output and all three gradients."""
    from photon_tpu.ops import flash_attention as fa

    q, k, v, w, _, _ = _clamp_case("plain")

    def run(sub):
        monkeypatch.setattr(fa, "STRIP_ROWS", dict.fromkeys(("fwd", "dq", "dkv"), sub))

        def f(q, k, v):
            return flash_attention(q, k, v, causal=True, alibi=alibi, block_q=256,
                                   block_k=256, interpret=True)

        return (f(q, k, v), *jax.grad(lambda q, k, v: (f(q, k, v) * w).sum(),
                                      argnums=(0, 1, 2))(q, k, v))

    for name, a, ref in zip(("o", "dq", "dk", "dv"), run(128), run(0)):
        assert _rel(a, ref) < 2e-6, (alibi, name)


@pytest.mark.parametrize("alibi", [False, True])
def test_lone_tile_forward_is_the_carried_one(alibi, monkeypatch):
    """A forward whose one tile holds the whole sequence takes no scratch and
    writes each strip's output and log-sum-exp directly; with that turned off
    the same strips go through the running state and the finalize step. The
    two agree to the last bit but for the order of one addition."""
    from photon_tpu.ops import flash_attention as fa

    assert fa._lone_tile(128, 512, 512, 512, 0)
    assert not fa._lone_tile(0, 512, 512, 512, 0)       # no strips: the masked body
    assert not fa._lone_tile(128, 1024, 1024, 512, 0)   # more tiles than one
    assert not fa._lone_tile(128, 512, 512, 512, 512)   # a chunk wholly in the past
    monkeypatch.setattr(fa, "STRIP_ROWS", dict.fromkeys(("fwd", "dq", "dkv"), 128))
    q, k, v, _, _, _ = _clamp_case("plain")

    def run():
        o = flash_attention(q, k, v, causal=True, alibi=alibi, block_q=512, block_k=512,
                            interpret=True)
        _, lse = flash_attention_with_lse(q, k, v, causal=True, block_q=512, block_k=512,
                                          interpret=True)
        return o, lse

    lone = run()
    monkeypatch.setattr(fa, "_lone_tile", lambda *a: False)
    for name, a, ref in zip(("o", "lse"), lone, run()):
        assert _rel(a, ref) < 1e-6, (alibi, name)
    assert _rel(lone[0], xla_attention(q, k, v, causal=True, alibi=alibi)) < 2e-5


# ---------------------------------------------------------------------------
# the launches read the projections' own arrays (PR 45): in place, head pairs
# ---------------------------------------------------------------------------

# case -> (heads, kv heads, d, d_v, s_q, s_k, tile, strip rows, alibi, layout)
LAYOUT_CASES = {
    "256/256 MHA": (2, 2, 256, 256, 256, 256, 128, 0, False, "in_place"),
    "128/128 GQA 4": (4, 1, 128, 128, 256, 256, 128, 0, False, "in_place"),
    "128/256 v wider": (2, 2, 128, 256, 256, 256, 128, 0, False, "in_place"),
    "256/128 alibi": (2, 1, 256, 128, 256, 256, 128, 0, True, "in_place"),
    "64/64 MHA pairs": (4, 4, 64, 64, 256, 256, 128, 0, False, "head_pairs"),
    "64/64 MHA pairs + alibi": (4, 4, 64, 64, 256, 256, 128, 0, True, "head_pairs"),
    "s_q != s_k": (2, 2, 128, 128, 128, 384, 128, 0, False, "in_place"),
    "pairs, s_q != s_k": (2, 2, 64, 64, 128, 384, 128, 0, True, "head_pairs"),
    # one tile holds the sequence: the forward's strips write straight out
    "strip tile": (2, 2, 128, 128, 512, 512, 512, 128, False, "in_place"),
    "pairs, strip tile": (2, 2, 64, 64, 512, 512, 512, 128, True, "head_pairs"),
    # tiles under the diagonal: the running state is carried in the scratch
    "carried tile": (2, 2, 128, 128, 512, 512, 256, 128, False, "in_place"),
    "pairs, carried tile": (6, 6, 64, 64, 512, 512, 256, 128, False, "head_pairs"),
    "pairs, carried masked tile": (2, 2, 64, 64, 512, 512, 256, 0, True, "head_pairs"),
}


def _layout_run(case, monkeypatch, forced=None):
    """``(o, lse, dq, dk, dv)`` of one case, and the layout its launches took;
    ``forced`` makes the call take that layout whatever its widths."""
    from photon_tpu.ops import flash_attention as fa

    h, h_kv, d, d_v, s_q, s_k, tile, sub, alibi, _ = LAYOUT_CASES[case]
    monkeypatch.setattr(fa, "STRIP_ROWS", dict.fromkeys(("fwd", "dq", "dkv"), sub))
    if forced:
        monkeypatch.setattr(fa, "flash_layout", lambda *a: forced)
    seen = {}
    real_fwd = fa._fwd

    def spy(q, k, v, **kw):
        o, lse = real_fwd(q, k, v, **kw)
        heads = kw.get("heads")
        seen["layout"] = ("head_major" if heads is None
                          else "head_pairs" if heads.pair else "in_place")
        seen.setdefault("lse", lse)
        return o, lse

    monkeypatch.setattr(fa, "_fwd", spy)
    ks = jax.random.split(jax.random.PRNGKey(45), 4)
    q = jax.random.normal(ks[0], (B, s_q, h, d))
    k = jax.random.normal(ks[1], (B, s_k, h_kv, d))
    v = jax.random.normal(ks[2], (B, s_k, h_kv, d_v))
    w = jax.random.normal(ks[3], (B, s_q, h, d_v))

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, alibi=alibi, block_q=tile,
                               block_k=tile, interpret=True)

    o, pull = jax.vjp(attend, q, k, v)
    return (o, seen["lse"], *pull(w)), seen["layout"]


@pytest.mark.parametrize("case", list(LAYOUT_CASES))
def test_layouts_equal_the_head_major_path(case, monkeypatch):
    """The launches over the projections' own ``[B, S, H·D]`` arrays give what
    they give over ``to_bh``'s transposed and padded copies: the output, the
    log-sum-exp and all three gradients. A pair's body does at its masked
    lanes what the padded body does at its pad, so at equal tiles the two are
    equal bit for bit; in place the bodies are the same and only ``delta``'s
    sum runs over another view."""
    want = LAYOUT_CASES[case][-1]
    new, took = _layout_run(case, monkeypatch)
    assert took == want
    old, took = _layout_run(case, monkeypatch, forced="head_major")
    assert took == "head_major"
    for name, a, ref in zip(("o", "lse", "dq", "dk", "dv"), new, old):
        assert a.shape == ref.shape and a.dtype == ref.dtype, (case, name)
        if want == "head_pairs":
            np.testing.assert_array_equal(np.asarray(a), np.asarray(ref), err_msg=f"{case} {name}")
        else:
            assert _rel(a, ref) < 2e-5, (case, name)


# what each benchmark configuration hands the kernel, and shapes beside them:
# (q heads, kv heads, d, d_v) -> the layout
@pytest.mark.parametrize("heads,layout", [
    pytest.param((12, 12, 64, 64), "head_pairs", id="mpt-125m"),
    pytest.param((20, 20, 256, 256), "in_place", id="glm-4.7-flash-ep8"),
    pytest.param((32, 32, 192, 128), "head_major", id="xing4.0-29b-a4b-ep8"),
    pytest.param((32, 8, 64, 64), "head_major", id="lfm2-8b-a1b-ep4"),
    pytest.param((40, 8, 64, 64), "head_major", id="granite-4.0-h-micro-stage1"),
    pytest.param((16, 16, 128, 128), "in_place", id="mpt-1b"),
    pytest.param((16, 4, 128, 128), "in_place", id="gqa-128"),
    pytest.param((3, 3, 64, 64), "head_major", id="odd-heads-64"),
    pytest.param((6, 6, 64, 128), "head_major", id="64-with-v-128"),
    pytest.param((4, 4, 32, 32), "head_major", id="d32"),
])
def test_layout_follows_the_head_widths(heads, layout):
    from photon_tpu.ops import flash_attention as fa

    assert fa.flash_layout(*heads) == layout


def test_benchmark_presets_take_their_layouts():
    """The rule applied to the presets the benchmark's cells run."""
    from photon_tpu.config import load_preset
    from photon_tpu.ops import flash_attention as fa

    took = {}
    for preset in ("mpt-125m", "glm-4.7-flash-ep8", "xing4.0-29b-a4b-ep8",
                   "lfm2-8b-a1b-ep4", "granite-4.0-h-micro-stage1"):
        m = load_preset(preset).model
        d_v = m.v_head_dim if m.latent_attention else m.d_head
        took[preset] = fa.flash_layout(m.n_heads, m.n_kv_heads or m.n_heads, m.d_head, d_v)
    assert took == {"mpt-125m": "head_pairs", "glm-4.7-flash-ep8": "in_place",
                    "xing4.0-29b-a4b-ep8": "head_major", "lfm2-8b-a1b-ep4": "head_major",
                    "granite-4.0-h-micro-stage1": "head_major"}


def test_lse_variant_stays_head_major(monkeypatch):
    """The ring's inner kernel keeps ``to_bh``: its launch takes no
    ``heads``, at a width that ``flash_attention`` reads in place."""
    from photon_tpu.ops import flash_attention as fa

    seen = []
    real_fwd = fa._fwd
    monkeypatch.setattr(fa, "_fwd", lambda *a, **kw: (seen.append(kw.get("heads")),
                                                      real_fwd(*a, **kw))[1])
    q, k, v = _qkv(d=128)
    flash_attention_with_lse(q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK,
                             interpret=True)
    flash_attention(q, k, v, causal=True, block_q=BLOCK, block_k=BLOCK, interpret=True)
    assert seen[0] is None and seen[1] == fa._Heads(H, H)
