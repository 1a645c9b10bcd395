"""Laguna-XS.2 (``laguna``) on the training path, at a tiny size with the
published structure: a leading dense full-attention layer, then one period
``sliding, sliding, sliding, full``; more query heads in a sliding layer than
in a full one over the same key-value heads; a window; a headwise gate on
attention's output; a full layer's head half turned by YaRN's frequencies with
a factor on cos and sin, a sliding layer's wholly by plain ones; sigmoid top-4
of 16 experts, 4 held, and a shared expert, in two expert stacks.

The plain reference is ``benchmark/reference/laguna_swa_moe.py`` (float32,
``Precision.HIGHEST``, the band a second mask, the experts a masked loop); on
the CPU the program runs ``attn_impl: xla`` in float32, so the two differ by
the order of summation alone and every tolerance below is a float32 one. The
windowed Pallas launches run under the interpreter against ``xla_attention``'s
mask, which a position-by-position loop holds.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # `benchmark` is a sibling of `tests`, not installed
    sys.path.insert(0, str(ROOT))

from benchmark.reference import laguna_swa_moe as ref  # noqa: E402
from photon_tpu.config import load_preset  # noqa: E402
from photon_tpu.config.schema import Config  # noqa: E402
from photon_tpu.models import MPTModel, init_params, mpt  # noqa: E402
from photon_tpu.ops import flash_attention as fa  # noqa: E402
from photon_tpu.ops import head_gate as hg  # noqa: E402
from photon_tpu.ops import moe  # noqa: E402
from photon_tpu.ops.attention import multihead_attention, xla_attention  # noqa: E402
from photon_tpu.train.train_step import make_loss_fn  # noqa: E402
from photon_tpu.utils.profiling import ATTN_GATE_SCOPE, ATTN_PROJ_SCOPE  # noqa: E402
from tests._helpers import TINY_PRESETS, tiny_preset  # noqa: E402

PRESET = "laguna-xs.2-ep8"
FULL, SLIDING = "full_attention", "sliding_attention"
STACKS = [("blocks_0", FULL, True, 1), ("blocks_1", SLIDING, False, 3),
          ("blocks_2", FULL, False, 1)]


def tiny_cfg(**model):
    """The preset with every size shrunk and nothing of its structure changed
    (``tests/_helpers.TINY_PRESETS``): ``F S S S F`` with layer 0 dense, 4 / 6
    query heads over 2 key-value heads of 8, a window of 8 in rows of 32, half
    of a full layer's head turned, 4 of 16 experts held."""
    return tiny_preset(PRESET, **model)


def dims_of(cfg) -> dict:
    return ref.dims_of(dataclasses.asdict(cfg.model))


def leaf_names(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


TOKENS = np.random.default_rng(3).integers(0, 96, size=(2, 32)).astype(np.int32)


def _qkv(seed: int, s: int, h: int, h_kv: int, d: int, batch: int = 1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (batch, s, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (batch, s, h_kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (batch, s, h_kv, d), jnp.float32)
    w = jax.random.normal(keys[3], (batch, s, h, d), jnp.float32)
    return q, k, v, w


# ---------------------------------------------------------------------------
# the band: the XLA mask against a loop, the Pallas launches against the mask
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("window", [1, 5, 16, 40])
def test_xla_attention_masks_the_band_position_by_position(window):
    """Query ``i`` sees keys ``i - window < j <= i``, its own among them."""
    s, h, d = 24, 2, 4
    q, k, v, _ = _qkv(window, s, h, h, d)
    got = np.asarray(xla_attention(q, k, v, window=window))
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    for i in range(s):
        lo = max(i - window + 1, 0)
        for head in range(h):
            scores = k[0, lo:i + 1, head] @ q[0, i, head] / math.sqrt(d)
            p = np.exp(scores - scores.max())
            want = (p / p.sum()) @ v[0, lo:i + 1, head]
            np.testing.assert_allclose(got[0, i, head], want, atol=1e-5)
    if window >= s:  # every causal pair is inside: the causal call's numbers
        causal = xla_attention(*(jnp.asarray(a, jnp.float32) for a in (q, k, v)))
        assert np.array_equal(got, np.asarray(causal))


def _against_the_mask(s, h, h_kv, d, window, tile, seed=0):
    """Output and all three gradients of the interpreted launches against
    ``xla_attention`` under the same window: the largest relative error."""
    q, k, v, w = _qkv(seed + s + window + tile, s, h, h_kv, d)
    rep = lambda x: jnp.repeat(x, h // h_kv, axis=2)  # noqa: E731

    def mask(q, k, v):
        return xla_attention(q, rep(k), rep(v), window=window)

    def kernel(q, k, v):
        return fa.flash_attention(q, k, v, window=window, block_q=tile, block_k=tile,
                                  interpret=True)

    want = (mask(q, k, v), *jax.grad(lambda *a: (mask(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v))
    got = (kernel(q, k, v),
           *jax.grad(lambda *a: (kernel(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v))
    return [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) for a, b in zip(got, want)]


@pytest.mark.parametrize("s, h, h_kv, d, window, tile", [
    (512, 4, 2, 128, 64, 128),    # a window smaller than a tile
    (512, 4, 2, 128, 128, 128),   # equal to it: the diagonal's strips and the lower edge
    (512, 2, 1, 128, 200, 128),   # larger, and not a whole number of tiles
    (640, 2, 1, 128, 256, 128),   # a sequence that is no whole number of windows
    (512, 2, 1, 128, 128, 256),   # a tile two windows wide: both bounds cut it
    (384, 4, 4, 64, 100, 128),    # pairs of 64-wide heads
    (256, 4, 2, 64, 96, 128),     # grouped heads at 64: head-major copies
    (256, 64, 8, 128, 128, 128),  # a sliding layer's heads
    (256, 48, 8, 128, 128, 128),  # a full layer's, under a window all the same
], ids=lambda x: str(x))
def test_the_banded_launches_match_the_mask(s, h, h_kv, d, window, tile):
    """Values, dq, dk and dv under the Pallas interpreter, float32: the order
    of summation alone differs (2e-4, ``tests/test_flash_kernel_interpret.py``'s)."""
    assert max(_against_the_mask(s, h, h_kv, d, window, tile)) < 2e-4


@pytest.mark.parametrize("window, sub", [(256, 128), (512, 128), (256, 64), (256, 0)])
def test_the_bands_lower_edge_runs_as_the_diagonals_strips_mirrored(window, sub, monkeypatch):
    """A window that is a whole number of (square, aligned) tiles: the tile on
    the band's lower edge runs as strips against the keys each strip's queries
    can see, in all three launches (a strip height of their own,
    ``BAND_STRIP_ROWS``; 0: whole tiles under the mask), and a query that sees
    no key of that tile takes nothing from it."""
    monkeypatch.setattr(fa, "BAND_STRIP_ROWS", dict.fromkeys(("fwd", "dq", "dkv"), sub))
    assert fa._edge_as_strips(sub, 256, window) == bool(sub)
    assert not fa._edge_as_strips(sub, 256, 300) and not fa._edge_as_strips(128, 256, None)
    assert max(_against_the_mask(1024, 2, 1, 128, window, 256)) < 2e-4
    if sub:  # fewer pairs multiplied than by whole tiles, the same number seen
        whole = fa.executed_pairs("fwd", 1024, 1024, 256, 256, window=window)
        monkeypatch.setattr(fa, "BAND_STRIP_ROWS", dict.fromkeys(("fwd", "dq", "dkv"), 0))
        assert whole < fa.executed_pairs("fwd", 1024, 1024, 256, 256, window=window)


@pytest.mark.parametrize("window", [256, 257, 4096])
def test_a_window_that_holds_the_row_is_the_causal_kernel_bit_for_bit(window):
    q, k, v, w = _qkv(1, 256, 2, 1, 128)
    run = lambda **kw: fa.flash_attention(  # noqa: E731
        q, k, v, block_q=128, block_k=128, interpret=True, **kw)
    grads = lambda **kw: jax.grad(  # noqa: E731
        lambda q, k, v: (fa.flash_attention(q, k, v, block_q=128, block_k=128,
                                            interpret=True, **kw) * w).sum(),
        argnums=(0, 1, 2))(q, k, v)
    assert np.array_equal(run(window=window), run())
    for a, b in zip(grads(window=window), grads()):
        assert np.array_equal(a, b)
    # and it lowers to the causal call's own program
    lowered = lambda **kw: jax.jit(lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, block_q=128, block_k=128, interpret=True, **kw)).lower(q, k, v).as_text()
    assert lowered(window=window) == lowered()
    assert lowered(window=128) != lowered()


def test_a_query_sees_its_own_key_and_the_511_before_it():
    """The window's edge at the published 512: moving key ``j`` changes query
    ``j + 511`` and leaves ``j + 512`` (and every later one, and every earlier
    query) bit-equal; through the dispatch's XLA path and the interpreted
    launches alike."""
    s, w, j = 1024, 512, 300
    q, k, v, _ = _qkv(2, s, 1, 1, 128)
    k2 = k.at[0, j].add(1.0)
    v2 = v.at[0, j].add(1.0)
    for attend in (
            lambda k, v: multihead_attention(q, k, v, impl="xla", window=w),
            lambda k, v: fa.flash_attention(q, k, v, window=w, block_q=256, block_k=256,
                                            interpret=True)):
        a, b = np.asarray(attend(k, v)), np.asarray(attend(k2, v2))
        assert np.array_equal(a[0, :j], b[0, :j])
        assert not np.array_equal(a[0, j], b[0, j])
        assert not np.array_equal(a[0, j + w - 1], b[0, j + w - 1])
        assert np.array_equal(a[0, j + w:], b[0, j + w:])


def _launch_grids(fn, *args) -> dict[str, tuple[int, ...]]:
    """``{kernel name: grid}`` of every Pallas launch in ``fn``'s jaxpr."""
    grids = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                scope = str(eqn.source_info.name_stack)
                name = re.search(r"(flash_\w+)/multihead_attention", scope).group(1)
                grids[name] = tuple(eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


def test_the_windowed_grid_walks_the_band_at_the_cells_shapes():
    """64 / 8 heads of 128, window 512, 16,384 positions: the inner axis of the
    forward's and dq's grid has at most ``ceil((511 + block_q) / block_k) + 1``
    steps (63 live tiles of 64 paid: not the square's 1,024 a head), dk/dv's as
    many for each member of the group, and the launches carry the band's names."""
    s, h, h_kv, d, w = 16384, 64, 8, 128, 512
    q = jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, s, h_kv, d), jnp.bfloat16)
    grids = _launch_grids(
        jax.grad(lambda q, k, v: fa.flash_attention(q, k, v, window=w).astype(
            jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)
    assert set(grids) == {"flash_swa_fwd", "flash_swa_dq", "flash_swa_dkv"}
    plan = fa.pick_tiles(s, s, d, 2, h // h_kv, layout=fa.IN_PLACE, window=w)
    for name, tiles in (("flash_swa_fwd", plan.fwd), ("flash_swa_dq", plan.dq)):
        bound = -(-(w - 1 + tiles.block_q) // tiles.block_k) + 1
        assert grids[name][:2] == (h, s // tiles.block_q)
        assert grids[name][2] <= bound < s // tiles.block_k
        assert tiles.grid_tiles == grids[name][1] * grids[name][2]
    group = h // h_kv
    bound = -(-(w - 1 + plan.dkv.block_k) // plan.dkv.block_q) + 1
    assert grids["flash_swa_dkv"][:2] == (h_kv, s // plan.dkv.block_k)
    assert grids["flash_swa_dkv"][2] <= group * bound
    # the causal call keeps the square and its names
    causal = _launch_grids(lambda q, k, v: fa.flash_attention(q, k, v), q, kv, kv)
    assert causal == {"flash_fwd": (h, 8, 8)}


def test_the_dispatch_refuses_a_window_it_cannot_walk():
    q, k, v, _ = _qkv(0, 16, 2, 2, 8)
    for kw in (dict(impl="ring"), dict(alibi=True), dict(causal=False), dict(window=0)):
        with pytest.raises((NotImplementedError, ValueError), match="window"):
            multihead_attention(q, k, v, **{"impl": "xla", "window": 4, **kw})
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention(q, k, v, window=4, causal=False, interpret=True)


# ---------------------------------------------------------------------------
# the rotation and the gate
# ---------------------------------------------------------------------------


def test_a_half_turned_head_turns_its_first_half_and_passes_the_rest():
    """Dims 0-3 of 8 turn (dim ``i`` with ``i + 2``) by the given frequencies
    with the factor on cos and sin; dims 4-7 pass; the defaults are the whole
    rotation as it was."""
    q, k, _, _ = _qkv(3, 6, 2, 1, 8)
    inv = (0.5, 0.125)
    q2, k2 = mpt.apply_rope(q, k, 10000.0, inv, rotary_dim=4, factor=1.5)
    for x, y in ((q, q2), (k, k2)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        assert np.array_equal(y[..., 4:], x[..., 4:])
        for t in range(6):
            for i, f in enumerate(inv):
                c, s = 1.5 * math.cos(t * f), 1.5 * math.sin(t * f)
                np.testing.assert_allclose(y[0, t, :, i], x[0, t, :, i] * c - x[0, t, :, i + 2] * s,
                                           atol=1e-5)
                np.testing.assert_allclose(y[0, t, :, i + 2],
                                           x[0, t, :, i + 2] * c + x[0, t, :, i] * s, atol=1e-5)
    # the turned part of a score carries the factor's square, the passed part 1
    plain = mpt.apply_rope(q, k, 10000.0, inv, rotary_dim=4)
    dot = lambda a, b, sl: float(jnp.sum(a[0, 5, 0, sl] * b[0, 2, 0, sl]))  # noqa: E731
    assert dot(q2, k2, slice(0, 4)) == pytest.approx(2.25 * dot(*plain, slice(0, 4)), rel=1e-5)
    assert dot(q2, k2, slice(4, 8)) == dot(*plain, slice(4, 8))
    whole = mpt.apply_rope(q, k, 10000.0)
    for a, b in zip(whole, mpt.apply_rope(q, k, 10000.0, rotary_dim=8, factor=1.0)):
        assert np.array_equal(a, b)


def test_each_kind_reads_its_own_heads_window_and_rotation():
    m = load_preset(PRESET).model
    full, sliding = m.attention_kind(FULL), m.attention_kind(SLIDING)
    assert (full.n_heads, full.window, full.rope_theta, full.rotary_dim) == (48, None, 5e5, 64)
    assert full.rope_factor == pytest.approx(0.1 * math.log(64) + 1)
    assert len(full.inv_freq) == 32 and full.inv_freq == m.rope_inv_freq(64)
    np.testing.assert_allclose(full.inv_freq, ref.yarn_inv_freq(dims_of(load_preset(PRESET)), 64),
                               rtol=1e-6)
    # the low frequencies turn 64 times slower, the high ones as they were
    assert full.inv_freq[0] == 1.0 and full.inv_freq[-1] == pytest.approx(
        5e5 ** (-62 / 64) / 64)
    assert sliding == (64, 512, 1e4, 128, None, 1.0)
    assert m.attention_kind("attention") == full  # the model-wide kind is a full layer
    assert m.softmax_scale is None  # the factor is on cos and sin, not on the softmax
    # one kind a model: what every other preset reads
    glm = load_preset("glm-4.7-flash-ep8").model
    assert glm.attention_kind()[:3] == (glm.n_heads, None, glm.rope_theta)
    keye = load_preset("keye-vl-2.0-30b-a3b-ep8").model.attention_kind()
    assert (keye.rotary_dim, keye.inv_freq, keye.rope_factor) == (128, None, 1.0)


def test_the_gate_is_one_sigmoid_a_head_and_token():
    """With ``W_g = 0`` every gate is 1/2: the attention branch halves, and
    nothing else moves; the gate's parameter is ``[layers, D, heads of the
    kind]``."""
    cfg = tiny_cfg()
    params = ref.make_params(dims_of(cfg), 3)
    assert params["blocks_1"]["block"]["attn_gate"]["kernel"].shape == (3, 32, 6)
    assert params["blocks_2"]["block"]["attn_gate"]["kernel"].shape == (1, 32, 4)
    dims = dims_of(cfg)
    mm = ref.MATMULS["float32"]
    h = jnp.asarray(np.random.default_rng(0).normal(size=(2, 32, 32)), jnp.float32)
    layer = jax.tree.map(lambda a: a[0], params["blocks_1"]["block"])
    half = {**layer, "attn_gate": {"kernel": jnp.zeros_like(layer["attn_gate"]["kernel"])}}
    ungated = ref.attention(h, layer, SLIDING, {**dims, "gated": False}, mm)
    np.testing.assert_allclose(ref.attention(h, half, SLIDING, dims, mm), 0.5 * ungated,
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(ref.attention(h, layer, SLIDING, dims, mm) - 0.5 * ungated))) > 1e-4


# ---------------------------------------------------------------------------
# the blocks and the stack against the reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights in the program's layout, and loss + gradients of one
    batch from the program (float32 compute) and from the reference."""
    cfg = tiny_cfg()
    dims = dims_of(cfg)
    params = ref.make_params(dims, 7)
    n = TOKENS.shape[0] * (TOKENS.shape[1] - 1)
    got = jax.value_and_grad(make_loss_fn(MPTModel(cfg.model), 16))(params, TOKENS)
    want = jax.value_and_grad(lambda p: ref.ce_sum(p, TOKENS, dims) / n)(params)
    return cfg, dims, params, got, want


def test_the_stacks_are_runs_of_equal_kind_and_mlp():
    assert tiny_cfg().model.stacks == STACKS == ref.stacks(dims_of(tiny_cfg()))
    model = load_preset(PRESET).model
    assert model.stacks == STACKS
    assert (model.swa_layers, model.full_attention_layers) == (3, 2)
    # the other families' stacks and kinds keep their names
    assert Config().model.stacks == [("blocks", "attention", False, 12)]
    assert (Config().model.swa_layers, Config().model.full_attention_layers) == (0, 12)
    assert load_preset("lfm2-8b-a1b-ep4").model.stacks[1] == ("blocks_1", "attention", False, 1)
    assert load_preset("granite-4.0-h-micro-stage1").model.full_attention_layers == 1


@pytest.mark.parametrize("kind, dense", [(FULL, True), (SLIDING, False), (FULL, False)])
def test_one_block_of_each_kind_matches_the_reference(seeded, kind, dense):
    cfg, dims, params, _, _ = seeded
    stack = {(FULL, True): "blocks_0", (SLIDING, False): "blocks_1",
             (FULL, False): "blocks_2"}[kind, dense]
    layer = jax.tree.map(lambda a: a[0], params[stack]["block"])
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 32, 32)), jnp.float32)
    got = mpt.MPTBlock(cfg.model, dense, kind).apply({"params": layer}, x)
    want, _ = ref.block(x, layer, kind, dense, dims, ref.MATMULS["float32"])
    assert float(jnp.max(jnp.abs(want - x))) > 1e-3
    # float32 on both sides: only the order of summation differs
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_init_gives_the_reference_tree():
    cfg = tiny_cfg()
    mine = init_params(cfg.model, seed=0)
    theirs = ref.make_params(dims_of(cfg), 0)
    assert leaf_names(mine) == leaf_names(theirs)
    assert jax.tree.map(jnp.shape, mine) == jax.tree.map(jnp.shape, theirs)
    assert sorted(mine) == ["blocks_0", "blocks_1", "blocks_2", "lm_head", "ln_f", "wte"]
    assert mine["blocks_1"]["block"]["q_proj"]["kernel"].shape == (3, 32, 6 * 8)
    assert mine["blocks_2"]["block"]["q_proj"]["kernel"].shape == (1, 32, 4 * 8)
    assert mine["blocks_1"]["block"]["k_proj"]["kernel"].shape == (3, 32, 2 * 8)
    assert "router" not in mine["blocks_0"]["block"]  # the leading layer is dense


def test_forward_logits_match_reference(seeded):
    cfg, dims, params, _, _ = seeded
    logits = MPTModel(cfg.model).apply({"params": params}, TOKENS)
    want = ref.forward(params, TOKENS, dims)
    assert float(jnp.max(jnp.abs(want))) > 0.05
    # float32 on both sides: only the order of summation differs
    np.testing.assert_allclose(logits, want, atol=2e-5)


def test_loss_matches_reference(seeded):
    *_, (loss, _), (want, _) = seeded
    # float32 on both sides, chunked against whole log-softmax
    assert abs(float(loss) - float(want)) < 1e-5


LEAVES = leaf_names(jax.eval_shape(lambda: ref.make_params(ref.dims_of({
    **dataclasses.asdict(load_preset(PRESET).model), **TINY_PRESETS[PRESET]}), 0)))


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(seeded, leaf):
    *_, (_, got), (_, want) = seeded
    got = dict(zip(leaf_names(got), jax.tree.leaves(got)))[leaf]
    want = dict(zip(leaf_names(want), jax.tree.leaves(want)))[leaf]
    scale = float(jnp.max(jnp.abs(want)))
    if leaf.endswith("router_bias"):  # selects only: no gradient at all
        assert scale == 0 and not np.any(got)
        return
    # float32 on both sides; a leaf's largest entry runs over five decades
    # (a norm's scale to the embedding), so the tolerance is relative to it
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=1e-4 * scale, rtol=1e-3)


def test_the_interpreted_kernels_carry_the_whole_model(seeded):
    """The Pallas launches (banded in the sliding stack, causal in the full
    ones) in the model's own step, under the interpreter: the loss and every
    gradient leaf stay the XLA path's."""
    cfg, _, params, (loss, grads), _ = seeded
    kern = tiny_cfg(attn_impl="pallas", attn_interpret=True)
    got, g = jax.value_and_grad(make_loss_fn(MPTModel(kern.model), 16))(params, TOKENS)
    assert abs(float(got) - float(loss)) < 1e-5
    for name, a, b in zip(leaf_names(g), jax.tree.leaves(g), jax.tree.leaves(grads)):
        np.testing.assert_allclose(a, b, atol=1e-4 * max(float(jnp.max(jnp.abs(b))), 1e-8),
                                   rtol=1e-3, err_msg=name)


def test_bfloat16_compute_stays_near_the_reference(seeded):
    cfg, dims, params, _, (want, _) = seeded
    low = tiny_cfg(compute_dtype="bfloat16")
    loss = make_loss_fn(MPTModel(low.model), 16)(params, TOKENS)
    assert abs(float(loss) - float(want)) < 2e-2


@pytest.mark.parametrize("t", [1, 9, 17, 31])
def test_changing_a_token_leaves_every_earlier_output_bit_equal(seeded, t):
    cfg, _, params, _, _ = seeded
    model = MPTModel(cfg.model)
    changed = TOKENS.copy()
    changed[:, t] = (changed[:, t] + 1) % 96
    a = np.asarray(model.apply({"params": params}, TOKENS))
    b = np.asarray(model.apply({"params": params}, changed))
    assert np.array_equal(a[:, :t], b[:, :t])
    assert not np.array_equal(a[:, t], b[:, t])


def test_a_sliding_layer_alone_forgets_a_token_after_its_window():
    """One sliding block (window 8): position ``t``'s input reaches outputs
    ``t .. t + 7`` and no later one."""
    cfg = tiny_cfg()
    params = ref.make_params(dims_of(cfg), 7)
    layer = jax.tree.map(lambda a: a[1], params["blocks_1"]["block"])
    x = jnp.asarray(np.random.default_rng(6).normal(size=(1, 32, 32)), jnp.float32)
    block = mpt.MPTBlock(cfg.model, False, SLIDING)
    a = np.asarray(block.apply({"params": layer}, x))
    b = np.asarray(block.apply({"params": layer}, x.at[0, 5].add(1.0)))
    assert np.array_equal(a[0, :5], b[0, :5]) and np.array_equal(a[0, 13:], b[0, 13:])
    assert not np.array_equal(a[0, 12], b[0, 12])


@pytest.mark.parametrize("microbatches", [2])
def test_three_adopt_steps_follow_the_reference(microbatches):
    """Three optimizer steps through ``Trainer`` (two microbatches a step: the
    rows by expert are summed over them) and through the reference's ``Grad``
    + ``adopt_step`` (gradient and moments on the host): every leaf, both
    expert stacks' selection biases among them."""
    from benchmark.program import optimizer_settings
    from photon_tpu.train.trainer import Trainer

    cfg = tiny_cfg(moe_bias_update_speed=0.2)
    cfg.scheduler.t_warmup = 1  # a learning rate from the second step on
    cfg.train.global_batch_size = 4
    cfg.train.device_microbatch_size = 4 // microbatches
    dims = dims_of(cfg)
    params0 = ref.make_params(dims, 11)
    rows = np.concatenate([TOKENS, np.roll(TOKENS, 5, axis=1)])
    batches = [np.roll(rows, i, axis=1) for i in range(3)]
    trainer = Trainer(cfg, params=jax.tree.map(jnp.array, params0))
    losses = [trainer.fit([b], duration_steps=1)["loss"] for b in batches]
    got = trainer.state.params

    opt = optimizer_settings(cfg)
    grad = ref.Grad(dims, rows=2)
    want, state = params0, ref.adopt_init(params0)
    for batch, loss in zip(batches, losses):
        ref_loss, g = grad(want, batch)
        assert abs(float(loss) - float(ref_loss)) < 1e-5
        # the balancing steps ride the gradient tree and are no part of the gradient
        clipped = ref.clip_by_global_norm(g, 1.0).tree()
        assert not any(np.any(clipped[s]["block"]["router_bias"]) for s in ("blocks_1", "blocks_2"))
        want, state = ref.adopt_step(want, state, g, opt)

    for stack in ("blocks_1", "blocks_2"):
        bias0 = np.asarray(params0[stack]["block"]["router_bias"])
        bias = np.asarray(got[stack]["block"]["router_bias"])
        assert np.max(np.abs(bias - bias0)) > 0.05  # it moved, by up to 3 x 0.2
        np.testing.assert_allclose(bias, want[stack]["block"]["router_bias"], atol=1e-6)
    for name, a, b in zip(leaf_names(got), jax.tree.leaves(got), jax.tree.leaves(want)):
        # float32 on both sides; three steps of lr 6e-4 move a weight by ~1e-3
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    change = lambda p: ref.leaf_norms(jax.tree.map(jnp.subtract, p, params0))  # noqa: E731
    assert ref.worst_leaf_gap(change(got), change(want)) < 1e-3


# ---------------------------------------------------------------------------
# the share: what expert parallelism asks of the layer
# ---------------------------------------------------------------------------


def _layer_weights(seed: int, n_experts: int = 256, d: int = 32, hidden: int = 16):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape, scale=0.2: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * scale, jnp.float32)
    return {"router": f32(d, n_experts, scale=0.5), "router_bias": f32(n_experts, scale=0.05),
            "moe_gate": f32(n_experts, d, hidden), "moe_up": f32(n_experts, d, hidden),
            "moe_down": f32(n_experts, hidden, d),
            "shared_gate_proj": {"kernel": f32(d, hidden)},
            "shared_up_proj": {"kernel": f32(d, hidden)},
            "shared_down_proj": {"kernel": f32(hidden, d)}}


@pytest.mark.parametrize("held", [256, 32])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """Sigmoid top-8 of 256 experts scaled 2.5, the published counts: the
    routed parts of the eight shares of 32, with the shared expert (what every
    chip computes alike) counted once, add up to the layer with every expert
    held, which the reference computes as a masked loop."""
    p = _layer_weights(1)
    u = jnp.asarray(np.random.default_rng(2).normal(size=(2, 24, 32)), jnp.float32)
    uncut = dict(top_k=8, routed_scale=2.5, experts_held=256, first_expert=0, n_shared=1,
                 n_experts=256)
    want, want_rows = ref.sparse_mlp(u, p, uncut, ref.MATMULS["float32"])
    parts, rows, by_expert = [], 0.0, 0.0
    for first in range(0, 256, held):
        sl = slice(first, first + held)
        out, counters = moe.dropless_moe_mlp(
            u, p["router"], p["router_bias"], p["moe_gate"][sl], p["moe_up"][sl],
            p["moe_down"][sl], top_k=8, first_expert=first, routed_scale=2.5,
            compute_dtype=jnp.float32)
        parts.append(out)
        rows += float(counters["rows_held"])
        by_expert = counters["expert_rows"]  # every share routes over all 256
    shared = ref._glm.shared_expert(u, p, ref.MATMULS["float32"])
    assert rows == 2 * 24 * 8  # every assignment is some share's, once
    np.testing.assert_array_equal(by_expert, want_rows)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5)
    if held < 256:  # and one share alone is not the layer
        assert float(jnp.max(jnp.abs(parts[0] + shared - want))) > 1e-3


# ---------------------------------------------------------------------------
# the headwise gate: one function with a pull-back of its own
# ---------------------------------------------------------------------------


def _two_line_gate(o, logits, **_):
    """The gate as the block wrote it before ``ops/head_gate``, its pull-back
    left to autodiff: what ``head_gate`` has to equal."""
    attn_out = o.reshape(*o.shape[:-1], logits.shape[-1], -1)
    gate = jax.nn.sigmoid(logits.astype(jnp.float32))
    return (attn_out.astype(jnp.float32) * gate[..., None]).astype(o.dtype).reshape(o.shape)


def _gate_readings(gate_fn, s, heads, d, dtype):
    """``gated``, and from one cotangent ``d_o`` and ``d_logits``, then the
    gradients of the gate's own ``[D, H]`` kernel and of the block's normed
    input ``h`` through the gate's ``Dense``."""
    keys = jax.random.split(jax.random.PRNGKey(s + heads + d), 4)
    h = jax.random.normal(keys[0], (2, s, 16), dtype)
    kernel = jax.random.normal(keys[1], (16, heads), jnp.float32)
    o = jax.random.normal(keys[2], (2, s, heads * d), dtype)
    dy = jax.random.normal(keys[3], (2, s, heads * d), dtype)
    dense = lambda h, kernel: h @ kernel.astype(dtype)  # noqa: E731 — nn.Dense(dtype=compute)
    gated, pull = jax.vjp(gate_fn, o, dense(h, kernel))
    d_o, d_logits = pull(dy)
    d_h, d_kernel = jax.grad(
        lambda h, kernel: jnp.vdot(gate_fn(o, dense(h, kernel)).astype(jnp.float32),
                                   dy.astype(jnp.float32)), argnums=(0, 1))(h, kernel)
    assert gated.dtype == d_o.dtype == d_logits.dtype == d_h.dtype == dtype
    return {k: np.asarray(v, np.float32) for k, v in dict(
        gated=gated, d_o=d_o, d_logits=d_logits, d_kernel=d_kernel, d_h=d_h).items()}


def _assert_the_gate_is_the_two_lines(s, heads, d, dtype, interpret):
    gate_fn = lambda o, logits: hg.head_gate(  # noqa: E731
        o, logits, impl="pallas", interpret=interpret)
    o = jnp.zeros((2, s, heads * d), dtype)
    launches = str(jax.make_jaxpr(gate_fn)(o, jnp.zeros((2, s, heads), dtype))).count(
        "pallas_call")
    assert launches == int(hg.uses_kernel("pallas", interpret, s, d)) == int(interpret)
    got = _gate_readings(gate_fn, s, heads, d, dtype)
    want = _gate_readings(_two_line_gate, s, heads, d, dtype)
    for name in ("gated", "d_o"):  # the same roundings at the same places
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    if not interpret:  # and the same float32 sum over D
        for name in ("d_logits", "d_kernel", "d_h"):
            np.testing.assert_array_equal(got[name], want[name], err_msg=name)
        return
    # a launch sums a head's D products in its own order: one ulp of the dtype
    ulp = float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(got["d_logits"], want["d_logits"], rtol=ulp, atol=1e-30)
    for name in ("d_kernel", "d_h"):  # sums of those over tokens / heads
        scale = np.abs(want[name]).max()
        np.testing.assert_allclose(got[name], want[name], rtol=0, atol=4 * ulp * scale,
                                   err_msg=name)


GATE_CASES = [  # the ``jax.numpy`` expression: a head of whole lanes or not, a decode step
    pytest.param(s, heads, d, dtype, False, id=f"s{s}-h{heads}-d{d}-{dtype}")
    for s in (1, 32) for heads in (4, 6) for d in (128, 24) for dtype in ("bfloat16", "float32")
] + [  # the Pallas launches under the interpreter, at a block-aligned size
    pytest.param(hg.ROW_BLOCK, heads, 128, dtype, True, id=f"interpret-h{heads}-{dtype}")
    for heads in (4, 6) for dtype in ("bfloat16", "float32")]


@pytest.mark.parametrize("s,heads,d,dtype,interpret", GATE_CASES)
def test_head_gate_is_the_two_lines_and_their_autodiff(s, heads, d, dtype, interpret):
    _assert_the_gate_is_the_two_lines(s, heads, d, jnp.dtype(dtype), interpret)


@pytest.mark.parametrize("interpret", [False, True], ids=["jnp", "interpret"])
def test_a_gate_without_the_sigmoids_slope_fails_the_comparison(monkeypatch, interpret):
    """The planted fault: ``g * (1 - g)`` dropped from the pull-back."""
    monkeypatch.setattr(hg, "_gate_slope", jnp.ones_like)
    with pytest.raises(AssertionError, match="d_logits|Not equal to tolerance"):
        _assert_the_gate_is_the_two_lines(hg.ROW_BLOCK, 4, 128, jnp.dtype("float32"), interpret)


def test_on_a_mesh_every_shard_of_rows_and_heads_runs_its_own_launches():
    """Rows over ``fsdp``, heads over ``tensor``: the launches under
    ``shard_map`` (a Mosaic call is not GSPMD's to partition) against the two
    lines on one device."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from photon_tpu.config.schema import MeshConfig
    from photon_tpu.parallel.context import use_mesh
    from photon_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(MeshConfig(fsdp=2, tensor=2), devices=jax.devices()[:4])
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    o, logits, dy = (jax.random.normal(k, (2, hg.ROW_BLOCK, w), jnp.bfloat16)
                     for k, w in zip(keys, (4 * 128, 4, 4 * 128)))

    def readings(gate_fn, *arrays):
        gated, pull = jax.vjp(gate_fn, *arrays[:2])
        return gated, *pull(arrays[2])

    with use_mesh(mesh):
        sharding = NamedSharding(mesh, P("fsdp", None, "tensor"))
        sharded = jax.jit(lambda *a: readings(
            lambda o, logits: hg.head_gate(o, logits, impl="pallas", interpret=True), *a))
        text = sharded.lower(*(jax.device_put(a, sharding) for a in (o, logits, dy))).as_text()
        assert "shard_map" in text or "manual" in text
        got = sharded(*(jax.device_put(a, sharding) for a in (o, logits, dy)))
    assert got[0].sharding.spec == P("fsdp", None, "tensor")
    for name, a, b in zip(("gated", "d_o", "d_logits"), got, readings(_two_line_gate, o, logits, dy)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32),
                                      err_msg=name)


def test_the_gated_models_gradient_is_the_two_lines(monkeypatch):
    """Through the blocks: the tiny model's loss and every leaf of its
    gradient (``attn_gate/kernel`` and, through ``h``, all that lies under
    it) with ``head_gate`` and with the two lines in its place."""
    cfg = tiny_cfg()
    params = init_params(cfg.model, seed=0)
    grad = lambda: jax.value_and_grad(  # noqa: E731
        make_loss_fn(MPTModel(cfg.model), 16))(params, TOKENS)
    loss, got = grad()
    traced = []  # (the two lines did take its place: one trace a stack, at least)
    monkeypatch.setattr(
        mpt, "head_gate", lambda *a, **kw: traced.append(a) or _two_line_gate(*a, **kw))
    want_loss, want = grad()
    assert len(traced) >= 3
    assert float(loss) == float(want_loss)
    names = leaf_names(want)
    assert sum("attn_gate/kernel" in n for n in names) == 3
    for name, a, b in zip(names, jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


# ---------------------------------------------------------------------------
# scopes, span attributes, counts
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def step_op_names():
    """The ``op_name``s of the tiny model's whole compiled train step."""
    from photon_tpu.optim import build_optimizer
    from photon_tpu.train import init_train_state
    from photon_tpu.train.train_step import make_train_step

    cfg = tiny_cfg()
    model = MPTModel(cfg.model)
    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    state = init_train_state(model, tx, init_params(cfg.model, seed=0))
    compiled = jax.jit(make_train_step(model, tx, loss_chunk_tokens=16)).lower(
        state, jnp.asarray(TOKENS)).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', compiled)


def test_the_gates_operations_are_under_its_own_scope(step_op_names):
    gate = re.compile(rf"\b{ATTN_GATE_SCOPE}\b")
    proj = re.compile(rf"\b{ATTN_PROJ_SCOPE}\b")
    hits = [n for n in step_op_names if gate.search(n)]
    assert any("attn_gate/dot_general" in n for n in hits)
    assert any(re.search(rf"{ATTN_GATE_SCOPE}/(logistic|mul)", n) for n in hits)
    assert not [n for n in hits if proj.search(n)]  # no operation carries two readers' scopes
    assert not [n for n in step_op_names if "attn_gate/" in n and not gate.search(n)]
    assert any("transpose(jvp(" in n for n in hits) and any("rematted" in n for n in hits)
    for stack in ("blocks_0", "blocks_1", "blocks_2"):
        assert any(f"/{stack}/" in n for n in hits), stack
    # step_parts' row for attention's projections does not take them
    assert not re.search(r"\battn/proj\b", "jit(train_step)/.../block/attn/gate/mul:")


def test_every_operation_of_the_step_carries_a_stage_and_the_experts_their_scopes(
        step_op_names):
    own = [n for n in step_op_names if n.startswith("jit(train_step)/")]
    assert len(own) > 300
    hoisted = [n for n in own if re.match(r"jit\(train_step\)/blocks_\d/block/", n)]
    assert not sorted({n for n in own if "train_step/" not in n} - set(hoisted))
    for stack in ("blocks_1", "blocks_2"):
        for scope in ("moe/router", "moe/dispatch", "moe/experts", "moe/shared_expert"):
            assert any(f"/{stack}/" in n and scope in n for n in own), (stack, scope)
    assert any("/blocks_0/" in n and "block/mlp" in n for n in own)  # the dense layer


def test_the_windowed_launches_carry_their_own_names():
    """``flash_fwd_ms_train`` / ``flash_bwd_ms_train`` keep reading the full
    layers' launches, the new readers the band's."""
    from benchmark.layer_metrics import flash_bwd_ms_train, flash_fwd_ms_train, swa_flash_ms_train

    cfg = tiny_cfg(attn_impl="pallas", attn_interpret=True)
    text = jax.jit(jax.grad(make_loss_fn(MPTModel(cfg.model), 16))).lower(
        init_params(cfg.model, seed=0), TOKENS).as_text(debug_info=True)
    names = set(re.findall(r"(flash_\w+)/multihead_attention", text))
    assert names == {"flash_fwd", "flash_dq", "flash_dkv",
                     "flash_swa_fwd", "flash_swa_dq", "flash_swa_dkv"}
    swa, fwd, bwd = (re.compile(m.KERNEL)
                     for m in (swa_flash_ms_train, flash_fwd_ms_train, flash_bwd_ms_train))
    for kernel in ("fwd", "dq", "dkv"):
        band = f"jit(f)/blocks_1/flash_swa_{kernel}/multihead_attention/pallas_call"
        full = f"jit(f)/blocks_2/flash_{kernel}/multihead_attention/pallas_call"
        assert swa.search(band) and not swa.search(full)
        assert not fwd.search(band) and not bwd.search(band)
        assert bool(fwd.search(full)) != bool(bwd.search(full))


def test_trainer_tells_the_sliding_layers_and_the_bands_plan_on_its_span():
    from photon_tpu.models.step import step_attrs

    told = lambda model: step_attrs(model, batch_rows=1).steps  # noqa: E731
    # (no kernel in a step on the CPU backend: the flash plans add no key)
    # (nor does the gate take its launches there: ``head_gate_layers`` 0)
    assert told(load_preset(PRESET).model) == {
        "swa_layers": 3, "sliding_window": 512, "head_gate_layers": 0}
    assert told(tiny_cfg().model) == {
        "swa_layers": 3, "sliding_window": 8, "head_gate_layers": 0}
    lfm2 = told(load_preset("lfm2-8b-a1b-ep4").model)
    assert "swa_layers" not in lfm2 and "head_gate_layers" not in lfm2
    # with the kernel in the step: the full layers' plan and the band's, each
    # by its kind's heads, the executed share from ``TilePlan.attrs()``
    model = dataclasses.replace(load_preset(PRESET).model, attn_interpret=True)
    attrs = told(model)
    assert attrs["flash_layout"] == "in_place"
    assert attrs["head_gate_layers"] == 5  # heads of 128 lanes in rows of 16,384
    assert told(tiny_cfg(attn_impl="pallas", attn_interpret=True).model)[
        "head_gate_layers"] == 0  # heads of 8: the ``jax.numpy`` expression
    assert attrs["flash_tiles"] == "fwd=2048x2048 dq=2048x2048 dkv=2048x2048"
    plan = fa.pick_tiles(16384, 16384, 128, 2, 8, layout=fa.IN_PLACE, window=512)
    assert attrs["swa_tiles"] == " ".join(
        f"{n}={t.block_q}x{t.block_k}" for n, t in zip(plan._fields, plan))
    assert attrs["swa_executed_share"] == " ".join(
        f"{n}={t.executed_share:.3f}" for n, t in zip(plan._fields, plan))
    shares = [float(x.split("=")[1]) for x in attrs["swa_executed_share"].split()]
    assert all(1.0 <= x < 2.0 for x in shares)
    assert [float(x.split("=")[1]) for x in attrs["flash_executed_share"].split()] == [1.016] * 3
    assert set(told(dataclasses.replace(load_preset("mpt-125m").model, attn_interpret=True))) == {
        "flash_layout", "flash_tiles", "flash_live_tiles", "flash_executed_share"}


def test_the_published_width_cut_counts_its_parameters():
    """``jax.eval_shape`` of the preset's own tree: ISSUE 49's table, to the
    parameter."""
    model = load_preset(PRESET).model
    shapes = jax.eval_shape(lambda: init_params(model, seed=0))
    count = lambda t: sum(math.prod(a.shape) for a in jax.tree.leaves(t))  # noqa: E731
    full = 2 * 2048 * 48 * 128 + 2 * 2048 * 8 * 128 + 2048 * 48
    sliding = 2 * 2048 * 64 * 128 + 2 * 2048 * 8 * 128 + 2048 * 64
    experts = 2048 * 256 + 256 + 32 * 3 * 2048 * 512 + 3 * 2048 * 512
    assert (full, sliding, experts) == (29_458_432, 37_879_808, 104_333_568)
    assert count(shapes["blocks_0"]) == full + 4096 + 3 * 2048 * 8192 == 79_794_176
    assert count(shapes["blocks_1"]) == 3 * (sliding + 4096 + experts) == 3 * 142_217_472
    assert count(shapes["blocks_2"]) == full + 4096 + experts == 133_796_096
    assert count(shapes["wte"]) + count(shapes["lm_head"]) + count(shapes["ln_f"]) == 51_382_272
    assert count(shapes) == 691_624_960
    assert round(count(shapes) * 16 / 1e9, 2) == 11.07
    block = shapes["blocks_1"]["block"]
    assert block["q_proj"]["kernel"].shape == (3, 2048, 8192)
    assert block["k_proj"]["kernel"].shape == (3, 2048, 1024)
    assert block["attn_gate"]["kernel"].shape == (3, 2048, 64)
    assert block["moe_gate"].shape == (3, 32, 2048, 512)
    assert block["router"].shape == (3, 2048, 256) and block["router_bias"].shape == (3, 256)
    assert shapes["blocks_2"]["block"]["out_proj"]["kernel"].shape == (1, 6144, 2048)
    # the whole model at these sizes is the published 33.4 B (headwise gates)
    whole = (2 * 100_352 * 2048 + 2048 + count(shapes["blocks_0"])
             + 30 * (sliding + 4096 + experts + 224 * 3 * 2048 * 512)
             + 9 * (full + 4096 + experts + 224 * 3 * 2048 * 512))
    assert round(whole / 1e9, 2) == 33.44
    theirs = jax.eval_shape(lambda: ref.make_params(ref.dims_of(dataclasses.asdict(model)), 0))
    assert jax.tree.map(lambda a: a.shape, theirs) == jax.tree.map(lambda a: a.shape, shapes)


def test_the_preset_is_what_the_benchmark_configuration_states():
    from benchmark.program import build_config

    config = json.loads((ROOT / "benchmark/configs" / f"{PRESET}.json").read_text())
    traffic = json.loads((ROOT / "benchmark/traffic/ep8-share-swa-1x16384.json").read_text())
    cfg = build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=2**31 + 5)
    assert (cfg.train.global_batch_size, cfg.train.device_microbatch_size) == (1, 1)
    assert cfg.model.stacks == STACKS and cfg.model.swa_layers == 3
    assert cfg.model.d_head == 128 and cfg.model.training_path_only
    assert config["parameters"] == 691_624_960
    # a preset edited under the benchmark is refused
    config["model"]["sliding_window"] = 256
    with pytest.raises(ValueError, match="sliding_window"):
        build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=1)


def test_model_flops_per_token_counts_the_family():
    """The program's own estimate is the benchmark's cost file at the expected
    rows: a full layer's causal half at 48 heads, a sliding layer's band at 64,
    the gate's product, the experts at this chip's share. A token's forward
    pass is 0.55 GFLOP of products, 0.40 of full-layer flash and 0.05 of
    banded flash; were the window a mask the sliding layers would cost 0.81."""
    from benchmark.costs import laguna_swa_moe_train as cost
    from photon_tpu.utils.profiling import model_flops_per_token

    model = load_preset(PRESET).model
    m = json.loads((ROOT / "benchmark/configs" / f"{PRESET}.json").read_text())["model"]
    parts = cost.parts_per_token(m, cost.expected_routed_rows_per_token(m))
    assert model_flops_per_token(model) == pytest.approx(sum(parts.values()), rel=1e-9)
    forward = {k: v / 3e9 for k, v in parts.items()}
    assert round(forward["flash_full"], 2) == 0.40 and round(forward["flash_band"], 2) == 0.05
    assert round(sum(forward.values()) - forward["flash_full"] - forward["flash_band"], 2) == 0.55
    masked = 3 * 64 * 8192.5 * 4 * 128 / 1e9
    assert round(masked, 2) == 0.81
    assert round((sum(forward.values()) - forward["flash_band"] + masked)
                 / sum(forward.values()), 2) == 1.75


def test_every_parameter_has_a_sharding_rule():
    from jax.sharding import PartitionSpec as P

    from photon_tpu.config.schema import MeshConfig
    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.parallel.sharding import _RULES, param_specs

    params = init_params(tiny_cfg().model, seed=0)
    names = leaf_names(params)
    assert not [n for n in names if not any(re.search(p, n) for p, _ in _RULES)]
    mesh = make_mesh(MeshConfig(fsdp=2, tensor=2), devices=jax.devices()[:4])
    specs = param_specs(params, mesh)
    for stack in ("blocks_0", "blocks_1", "blocks_2"):  # a column a head, like q's
        assert specs[stack]["block"]["attn_gate"]["kernel"] == P("pipe", "fsdp", "tensor")
        assert specs[stack]["block"]["q_proj"]["kernel"] == P("pipe", "fsdp", "tensor")
    assert specs["blocks_1"]["block"]["router_bias"] == P("pipe", None)


# ---------------------------------------------------------------------------
# who refuses the family, and what the schema refuses of it
# ---------------------------------------------------------------------------


def _refuse_serving():
    from photon_tpu.serve.engine import PagedEngine

    PagedEngine(tiny_cfg(), params={})


def _refuse_decode():
    from photon_tpu.models.decode import prefill

    prefill({}, jnp.zeros((1, 4), jnp.int32), jnp.array([4]), tiny_cfg().model)


def _refuse_hf_export():
    from photon_tpu.checkpoint.hf_export import llama_state_dict

    llama_state_dict({}, tiny_cfg().model)


def _refuse_hf_import():
    from photon_tpu.checkpoint.hf_import import llama_params_from_hf

    llama_params_from_hf({}, tiny_cfg().model)


@pytest.mark.parametrize("call", [_refuse_serving, _refuse_decode,
                                  _refuse_hf_export, _refuse_hf_import],
                         ids=lambda f: f.__name__.removeprefix("_refuse_"))
def test_serving_decode_and_hf_interop_refuse_the_family(call):
    with pytest.raises(NotImplementedError, match="training path only") as err:
        call()
    assert "sliding-window layers" in str(err.value) and "attn_gate" in str(err.value)


@pytest.mark.parametrize("field, value", [("partial_rotary_factor", 0.5),
                                          ("attn_gate", "headwise")])
def test_a_new_field_alone_is_training_path_only(field, value):
    from photon_tpu.config.schema import refuse_training_only_family
    from tests._helpers import tiny_llama_config

    model = tiny_llama_config().model
    assert not model.training_path_only
    refuse_training_only_family(model, "serving")
    setattr(model, field, value)
    assert model.training_path_only
    with pytest.raises(NotImplementedError, match=field):
        refuse_training_only_family(model, "serving")


def _with(cfg, **paths):
    for dotted, value in paths.items():
        obj = cfg
        *parents, leaf = dotted.split("__")
        for name in parents:
            obj = getattr(obj, name)
        setattr(obj, leaf, value)
    return cfg


_LATENT = dict(model__kv_lora_rank=8, model__q_lora_rank=8, model__qk_nope_head_dim=4,
               model__qk_rope_head_dim=4, model__v_head_dim=8, model__n_kv_heads=0,
               model__head_dim=0, model__swa_n_heads=0)


@pytest.mark.parametrize("change, message", [
    (dict(model__layer_types="full_attention,sliding_attention"), "needs n_layers=5"),
    (dict(model__layer_types="full_attention,linear_attention,conv,conv,conv"),
     "'full_attention' or 'sliding_attention'"),
    (dict(model__sliding_window=0), "sliding_window >= 1"),
    (dict(model__layer_types=",".join([FULL] * 5)), "belong to 'sliding_attention' layers"),
    (dict(model__swa_n_heads=5), "a multiple of the 2 key-value heads"),
    (dict(model__head_dim=0), "head_dim"),
    (dict(model__attn_gate="elementwise"), "only 'headwise'"),
    (dict(model__partial_rotary_factor=0.0), "partial_rotary_factor must lie in"),
    (dict(model__partial_rotary_factor=0.3), "even whole number"),
    (dict(model__rope_scaling_attention_factor=-1.0), "rope_scaling_attention_factor be >= 0"),
    (dict(model__rope_scaling_type=""), "belong to rope_scaling_type='yarn'"),
    (dict(model__rope_scaling_attention_factor=0.0), "differs from rope_scaling_mscale_all_dim"),
    (dict(model__rope_scaling_attention_factor=0.0, model__rope_scaling_mscale_all_dim=1.0),
     "need rope_scaling_attention_factor"),
    (dict(model__rope_scaling_mscale_all_dim=1.0), "both scale the scores"),
    (dict(model__rope=False, model__rope_scaling_type="", model__rope_scaling_factor=1.0,
          model__rope_scaling_original_max_position=0, model__rope_scaling_attention_factor=0.0,
          model__partial_rotary_factor=1.0), "need rope=true"),
    (_LATENT, "does not combine with latent attention"),
    (dict(model__dsa_topk=4, model__dsa_index_heads=2, model__dsa_index_head_dim=4,
          model__dsa_chunk=8), "no alibi, latent attention or layer_types"),
    (dict(model__hc_mult=4), "hc_mult > 1 does not combine with layer_types"),
    (dict(model__attn_impl="ring"), "not supported with ring attention"),
    (dict(mesh__sequence=2), "mesh.sequence > 1"),
    (dict(mesh__tensor=2), "a mesh axis above 1 other than data"),
    (dict(mesh__fsdp=2), "a mesh axis above 1 other than data"),
    (dict(mesh__expert=2), "mesh.expert > 1 with moe_router='sigmoid'"),
    (dict(mesh__pipe=5), "mesh.pipe > 1"),
    (dict(model__lora_rank=4), "LoRA adapters"),
    (dict(photon__adapters__enabled=True), "LoRA adapters"),
    (dict(photon__serve__enabled=True), "photon.serve"),
    (dict(photon__serve__prefix_cache=True), "photon.serve"),
], ids=lambda x: None if isinstance(x, dict) else str(x)[:40])
def test_schema_refuses_what_the_family_cannot_do_yet(change, message):
    cfg = tiny_cfg()
    with pytest.raises(ValueError, match=message):
        _with(cfg, **change).validate()


def test_a_data_parallel_mesh_is_not_refused():
    assert _with(tiny_cfg(), mesh__data=2).validate().mesh.data == 2


def test_alibi_refuses_a_window():
    """The schema keeps ALiBi from every model with rotary positions, and the
    windowed family's own rule names it too."""
    with pytest.raises(ValueError, match="rope excludes alibi"):
        _with(tiny_cfg(), model__alibi=True).validate()
    cfg = _with(tiny_cfg(), model__alibi=True)
    with pytest.raises(ValueError, match="do not combine with alibi"):
        cfg._validate_windowed_family()


def test_the_new_fields_survive_yaml_and_json(tmp_path):
    cfg = tiny_cfg()
    cfg.to_yaml(tmp_path / "resolved.yaml")
    back = Config.from_yaml(tmp_path / "resolved.yaml").validate()
    assert back.model.layer_kinds == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert (back.model.sliding_window, back.model.swa_n_heads, back.model.swa_rope_theta,
            back.model.partial_rotary_factor, back.model.attn_gate) == (8, 6, 1e4, 0.5, "headwise")
    assert back.model.rope_scaling_attention_factor == pytest.approx(1.4158883083359672)
    assert Config.from_json(cfg.to_json()).model.stacks == STACKS
    d = Config().model
    assert (d.sliding_window, d.swa_n_heads, d.swa_rope_theta, d.partial_rotary_factor,
            d.rope_scaling_attention_factor, d.attn_gate) == (0, 0, 0.0, 1.0, 0.0, "")
    assert not d.training_path_only


# ---------------------------------------------------------------------------
# the new fields at their defaults: every other preset as it was
# ---------------------------------------------------------------------------

#: each other benchmark preset at its tiny size (``tests/_helpers.TINY_PRESETS``):
#: the leaves of its parameter tree and its loss on ``TOKENS``-like rows with
#: seed-0 weights, read on the commit before the window, the kinds and the gate
#: existed (PR 48's tree; five of them are ``tests/test_xing_mhc.py``'s numbers
#: of the commit before PR 44); the lowered train steps of the six presets were
#: equal text for text on parent and change there too (PERF.md section 6, PR 49)
UNCHANGED = {
    "mpt-125m": (9, 4.5944647789001465),
    "glm-4.7-flash-ep8": (32, 4.5490946769714355),
    "granite-4.0-h-micro-stage1": (37, 4.566521644592285),
    "keye-vl-2.0-30b-a3b-ep8": (20, 4.790014743804932),
    "lfm2-8b-a1b-ep4": (33, 4.588274002075195),
    "xing4.0-29b-a4b-ep8": (44, 4.586148738861084),
}
#: float32 sums of a few thousand terms whose order XLA:CPU may change with
#: the threads it is given (a loss near 4.6 has an ulp of 4.8e-7): ten ulps
LOSS_ATOL = 5e-6


def _old_apply_rope(q, k, theta, inv_freq=None):
    """``apply_rope`` as it was before a head could be partly turned."""
    half = q.shape[-1] // 2
    if inv_freq is None:
        inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(q.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]

    def rot(x):
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:].astype(jnp.float32)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
        return out.astype(x.dtype)

    return rot(q), rot(k)


@pytest.mark.parametrize("preset", list(UNCHANGED))
def test_every_other_preset_keeps_its_tree_its_loss_and_its_step(preset, monkeypatch):
    """No sliding layer, a wholly turned head, no factor and no gate: no new
    leaf, the loss of the commit before, and a train step that lowers to the
    same text whether its blocks read their heads, window and rotation from
    ``attention_kind`` and turn through the new ``apply_rope``, or from the
    model's own fields through the old one (``tests/test_xing_mhc.py``'s guard
    of the same name, for this PR's fields)."""
    from photon_tpu.config.schema import AttentionKind, ModelConfig
    from photon_tpu.optim import build_optimizer
    from photon_tpu.train import init_train_state
    from photon_tpu.train.train_step import make_train_step

    cfg = tiny_preset(preset)
    m = cfg.model
    assert not m.swa_layers and not m.attn_gate and m.partial_rotary_factor == 1.0
    params = init_params(m, seed=0)
    names = leaf_names(params)
    assert len(names) == UNCHANGED[preset][0] and not [n for n in names if "attn_gate" in n]
    tokens = np.random.default_rng(3).integers(0, 96, size=(2, m.max_seq_len)).astype(np.int32)
    model = MPTModel(m)
    loss = float(make_loss_fn(model, 16)(params, jnp.asarray(tokens)))
    assert loss == pytest.approx(UNCHANGED[preset][1], abs=LOSS_ATOL)

    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    state = init_train_state(model, tx, params)

    def lowered() -> str:
        return jax.jit(make_train_step(MPTModel(cfg.model), tx, loss_chunk_tokens=16)).lower(
            state, jnp.asarray(tokens)).as_text()

    with_kinds = lowered()

    def one_kind(self, kind="attention"):
        assert kind == "attention"
        return AttentionKind(self.n_heads, None, self.rope_theta, self.d_head,
                             self.rope_inv_freq(self.d_head), 1.0)

    def old_rope(q, k, theta, inv_freq=None, rotary_dim=None, factor=1.0):
        assert rotary_dim in (None, q.shape[-1]) and factor == 1.0
        return _old_apply_rope(q, k, theta, inv_freq)

    monkeypatch.setattr(ModelConfig, "attention_kind", one_kind)
    monkeypatch.setattr(mpt, "apply_rope", old_rope)
    assert lowered() == with_kinds
    assert "attn_gate" not in with_kinds and "attn/gate" not in with_kinds
