"""Flash attention under a mask by (query, key) that all heads share, in Pallas.

The sibling of ``ops/flash_attention.py`` for learned sparse attention
(``ops/dsa.py``): which keys a query sees is DATA, a ``[batch, s_q, s_k]``
int8 mask the indexer's selection made, not the causal rule. Same online
softmax, same FlashAttention-2 backward (a dq launch, a dk/dv launch), same
grouped-query row maps and the same kernel names in a trace (``flash_fwd``,
``flash_dq``, ``flash_dkv`` under ``multihead_attention``), so the readers
that find the dense kernel's launches find these.

What differs:

- Every launch reads the mask's ``[block_q, block_k]`` tile beside its q and
  k/v tiles; a masked score is ``NEG_INF`` before the running maximum. The
  mask holds causality too (the selection only picks earlier keys), so there
  is no ``causal`` flag, no ALiBi and no position offset here.
- Which tiles hold a picked pair is data as well: two small int32 tables a
  launch (:func:`tile_tables`), prefetched to SMEM, say for every grid step
  whether its tile is live and which k/v (forward, dq) or q (dk/dv) block to
  hold. A tile with no picked pair names the row's nearest live block, so
  the pipeline sees a repeated index and fetches nothing, and its body is
  predicated off: it costs its grid step and no more, as a tile above the
  diagonal does in the dense kernel. The mask is read ONCE for all three
  launches' tables (:func:`tile_counts` at the tiles' common divisor,
  :func:`live_tables` from the counts); the backward takes them as kept.
- The forward also returns the log-sum-exp: the indexer's alignment loss
  forms each head's probabilities from it (``ops/dsa.index_loss``). No
  gradient flows into it; its cotangent is dropped.

The tiles are the dense kernel's ladder tops cut to what divides the
sequence (:data:`TILE_CAPS`); the mask's tile and its int32 upcast are added
to ``flash_attention.launch_vmem_bytes``'s estimate.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_tpu.ops.flash_attention import (
    LANE,
    NEG_INF,
    SUBLANE,
    _kernel_scope,
    _kv_row,
    _vmem_params,
    lane_padded,
    launch_vmem_bytes,
)

#: the largest (block_q, block_k) a launch takes: the dense kernel's ladder
#: tops (``flash_attention.TILE_LADDER_TOP``), the forward's cut to 1,024 so
#: that its score temporaries and the mask's tile stay well inside VMEM
TILE_CAPS = {"fwd": (1024, 1024), "dq": (1024, 1024), "dkv": (512, 512)}
#: the launches, in the order of a tile plan
LAUNCHES = ("fwd", "dq", "dkv")


def pick_block(s: int, cap: int) -> int:
    """The largest whole-lane-width divisor of ``s`` under ``cap``; ``s``
    itself where there is none (a short test row is one tile)."""
    return next((t for t in range(min(cap, s) // LANE * LANE, 0, -LANE) if s % t == 0), s)


def plan_tiles(s_q: int, s_k: int) -> tuple[tuple[int, int], ...]:
    """``(block_q, block_k)`` of the forward, dq and dk/dv launches."""
    return tuple((pick_block(s_q, TILE_CAPS[n][0]), pick_block(s_k, TILE_CAPS[n][1]))
                 for n in LAUNCHES)


def base_tile(tiles) -> tuple[int, int]:
    """The largest ``(block_q, block_k)`` that divides every launch's tile."""
    return (math.gcd(*(bq for bq, _ in tiles)), math.gcd(*(bk for _, bk in tiles)))


def tile_counts(mask: jax.Array, block_q: int, block_k: int) -> jax.Array:
    """``[B, n_q, n_k]`` int32: the picked pairs in every tile of ``mask [B,
    s_q, s_k]``, an int8 of 0 and 1. The one pass over the mask outside the
    launches, as a product with the key blocks' indicator (int8 operands,
    int32 sums: exact): on the chip that reads the mask at the memory's rate,
    where upcasting and adding the tiles took 17 times as long (PERF.md
    section 6, PR 35)."""
    b, s_q, s_k = mask.shape
    n_k = s_k // block_k
    key_block = (jnp.arange(s_k)[:, None] // block_k == jnp.arange(n_k)[None, :])
    by_row = jnp.einsum("bqk,kn->bqn", mask, key_block.astype(jnp.int8),
                        preferred_element_type=jnp.int32)
    return jnp.sum(by_row.reshape(b, s_q // block_q, block_q, n_k), axis=2)


def live_tables(counts: jax.Array, tiles) -> tuple[jax.Array, ...]:
    """For each launch of ``tiles`` the ``[B, n_q, n_k]`` bool table of its
    tiles that hold a picked pair, from :func:`tile_counts` at
    :func:`base_tile`."""
    b, n_q, n_k = counts.shape
    bq0, bk0 = base_tile(tiles)
    return tuple(
        jnp.any(counts.reshape(b, n_q * bq0 // bq, bq // bq0, n_k * bk0 // bk, bk // bk0) > 0,
                axis=(2, 4))
        for bq, bk in tiles)


def tile_tables(live: jax.Array) -> tuple[jax.Array, jax.Array]:
    """``(live, fetch)`` as flat int32 tables for a launch that sweeps the
    LAST axis of ``live [..., n]`` innermost: ``fetch`` is the step's own
    index where its tile is live, else the nearest live index before it in
    the sweep, else the first live one after (0 where the sweep has none), so
    that a dead step names a block the pipeline already holds or will need."""
    n = live.shape[-1]
    idx = jnp.arange(n, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, idx, -1), axis=live.ndim - 1)
    after = jax.lax.cummin(jnp.where(live, idx, n), axis=live.ndim - 1, reverse=True)
    fetch = jnp.where(before >= 0, before, jnp.where(after < n, after, 0))
    return live.astype(jnp.int32).reshape(-1), fetch.astype(jnp.int32).reshape(-1)


def _vmem(launch: str, block_q: int, block_k: int, d: int, itemsize: int) -> dict:
    # the mask's int8 tile twice (the pipeline's two buffers) and its upcast
    extra = 2 * block_q * block_k + 4 * block_q * block_k
    return _vmem_params(launch_vmem_bytes(launch, block_q, block_k, d, itemsize) + extra)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(live_ref, fetch_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_s, l_s, acc_s, *, scale, heads, n_q):
    del fetch_ref  # the index maps' table
    q_blk, k_blk, n_k = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(k_blk == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    @pl.when(live_ref[((pl.program_id(0) // heads) * n_q + q_blk) * n_k + k_blk] != 0)
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [block_q, block_k]
        s = jnp.where(mask_ref[0].astype(jnp.int32) != 0, s, NEG_INF)
        m_prev = m_s[:, 0][:, None]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a row with no picked key so far keeps m == NEG_INF; exp(s - m)
        # would be 1 there, so force p to 0
        p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_s[:, 0][:, None] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_s[:] = acc_s[:] * alpha + pv
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(k_blk == n_k - 1)
    def _finalize():
        l = l_s[:, 0][:, None]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_s[:] / l_safe).astype(o_ref.dtype)
        lse = m_s[:, 0] + jnp.log(l_safe[:, 0])
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (SUBLANE, lse.shape[0]))


def _fwd(q, k, v, mask, live, *, scale, block_q, block_k, h_q, interpret):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    n_q, n_k = s_q // block_q, s_k // block_k
    kv = _kv_row(h_q, h_q * k.shape[0] // bh)
    live, fetch = tile_tables(live)

    def kj(b, i, j, fetch_ref):
        return fetch_ref[((b // h_q) * n_q + i) * n_k + j]

    launch = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, heads=h_q, n_q=n_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j, lv, ft: (b, i, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, i, j, lv, ft: (kv(b), kj(b, i, j, ft), 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda b, i, j, lv, ft: (kv(b), kj(b, i, j, ft), 0)),
                pl.BlockSpec((1, block_q, block_k),
                             lambda b, i, j, lv, ft: (b // h_q, i, kj(b, i, j, ft))),
            ],
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j, lv, ft: (b, i, 0)),
                pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j, lv, ft: (b, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, LANE), jnp.float32),  # running max
                pltpu.VMEM((block_q, LANE), jnp.float32),  # running denominator
                pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, SUBLANE, s_q), jnp.float32),
        ],
        interpret=interpret,
        **_vmem("fwd", block_q, block_k, d, q.dtype.itemsize),
    )
    with _kernel_scope("flash_fwd"):
        o, lse = launch(live, fetch, q, k, v, mask)
    return o, lse[:, 0, :]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _probabilities(q_ref, k_ref, mask_ref, lse_ref, scale):
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask_ref[0].astype(jnp.int32) != 0, s, NEG_INF)
    lse = lse_ref[0, 0][:, None]
    return jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)


def _bwd_dq_kernel(live_ref, fetch_ref, q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_s, *, scale, heads, n_q):
    del fetch_ref
    q_blk, k_blk, n_k = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(k_blk == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    @pl.when(live_ref[((pl.program_id(0) // heads) * n_q + q_blk) * n_k + k_blk] != 0)
    def _compute():
        p = _probabilities(q_ref, k_ref, mask_ref, lse_ref, scale)
        dp = jax.lax.dot_general(
            do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        k = k_ref[0]
        dq_s[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k_blk == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(live_ref, fetch_ref, q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_s, dv_s, *, scale, kv_heads, n_q):
    """The inner grid dimension sweeps ``group * n_q`` steps: a kv row takes
    its gradient from every q head of its group (``t // n_q``) and q block
    (``t % n_q``) in one pass over its VMEM accumulators."""
    del fetch_ref
    k_blk, t, n_t = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    n_k = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    @pl.when(live_ref[((pl.program_id(0) // kv_heads) * n_k + k_blk) * n_q + t % n_q] != 0)
    def _compute():
        p = _probabilities(q_ref, k_ref, mask_ref, lse_ref, scale)
        do = do_ref[0].astype(jnp.float32)
        dv_s[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dk_s[:] += jax.lax.dot_general(
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == n_t - 1)
    def _finalize():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _bwd(scale, dq_tile, dkv_tile, res, do, *, h_q, interpret):
    q, k, v, mask, (_, live_dq, live_dkv), o, lse = res
    bh, s_q, d = q.shape
    bh_k, s_k = k.shape[0], k.shape[1]
    itemsize = q.dtype.itemsize
    h_kv = h_q * bh_k // bh
    group = h_q // h_kv
    kv = _kv_row(h_q, h_kv)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse_b = jnp.broadcast_to(lse[:, None, :], (bh, SUBLANE, s_q))
    delta_b = jnp.broadcast_to(delta[:, None, :], (bh, SUBLANE, s_q))

    block_q, block_k = dq_tile
    n_q, n_k = s_q // block_q, s_k // block_k
    live, fetch = tile_tables(live_dq)

    def kj(b, i, j, fetch_ref):
        return fetch_ref[((b // h_q) * n_q + i) * n_k + j]

    launch_dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, heads=h_q, n_q=n_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i, j, lv, ft: (b, i, 0)),  # q
                pl.BlockSpec((1, block_k, d),
                             lambda b, i, j, lv, ft: (kv(b), kj(b, i, j, ft), 0)),  # k
                pl.BlockSpec((1, block_k, d),
                             lambda b, i, j, lv, ft: (kv(b), kj(b, i, j, ft), 0)),  # v
                pl.BlockSpec((1, block_q, block_k),
                             lambda b, i, j, lv, ft: (b // h_q, i, kj(b, i, j, ft))),
                pl.BlockSpec((1, block_q, d), lambda b, i, j, lv, ft: (b, i, 0)),  # do
                pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j, lv, ft: (b, 0, i)),
                pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j, lv, ft: (b, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j, lv, ft: (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
        interpret=interpret,
        **_vmem("dq", block_q, block_k, d, itemsize),
    )
    with _kernel_scope("flash_dq"):
        dq = launch_dq(live, fetch, q, k, v, mask, do, lse_b, delta_b)

    # dk/dv: grid rows are the kv STORAGE rows, the inner dimension sweeps the
    # group's q heads x q blocks; its tables run over (batch, k block, q block)
    block_q, block_k = dkv_tile
    n_q, n_k = s_q // block_q, s_k // block_k
    live, fetch = tile_tables(live_dkv.swapaxes(1, 2))

    def qrow(b, t):
        if group == 1:
            return b
        return (b // h_kv) * h_q + (b % h_kv) * group + t // n_q

    def qi(b, j, t, fetch_ref):
        return fetch_ref[((b // h_kv) * n_k + j) * n_q + t % n_q]

    launch_dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, kv_heads=h_kv, n_q=n_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh_k, n_k, group * n_q),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda b, j, t, lv, ft: (qrow(b, t), qi(b, j, t, ft), 0)),  # q
                pl.BlockSpec((1, block_k, d), lambda b, j, t, lv, ft: (b, j, 0)),  # k
                pl.BlockSpec((1, block_k, d), lambda b, j, t, lv, ft: (b, j, 0)),  # v
                pl.BlockSpec((1, block_q, block_k),
                             lambda b, j, t, lv, ft: (b // h_kv, qi(b, j, t, ft), j)),
                pl.BlockSpec((1, block_q, d),
                             lambda b, j, t, lv, ft: (qrow(b, t), qi(b, j, t, ft), 0)),  # do
                pl.BlockSpec((1, SUBLANE, block_q),
                             lambda b, j, t, lv, ft: (qrow(b, t), 0, qi(b, j, t, ft))),
                pl.BlockSpec((1, SUBLANE, block_q),
                             lambda b, j, t, lv, ft: (qrow(b, t), 0, qi(b, j, t, ft))),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, d), lambda b, j, t, lv, ft: (b, j, 0)),
                pl.BlockSpec((1, block_k, d), lambda b, j, t, lv, ft: (b, j, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh_k, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh_k, s_k, d), v.dtype),
        ],
        interpret=interpret,
        **_vmem("dkv", block_q, block_k, d, itemsize),
    )
    with _kernel_scope("flash_dkv"):
        dk, dv = launch_dkv(live, fetch, q, k, v, mask, do, lse_b, delta_b)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


# ``live``: the launches' live tiles (:func:`live_tables`); ``tiles`` (static):
# ((block_q, block_k),) * 3 for the forward, dq and dk/dv launches; ``h_q``
# (static): the q heads a batch row has (rows are ``b * h_q + head``); the mask
# and the tables are operands without a cotangent
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _masked_flash(q, k, v, mask, live, scale, tiles, h_q, interpret):
    return _fwd(q, k, v, mask, live[0], scale=scale, block_q=tiles[0][0],
                block_k=tiles[0][1], h_q=h_q, interpret=interpret)


def _masked_flash_fwd(q, k, v, mask, live, scale, tiles, h_q, interpret):
    o, lse = _masked_flash(q, k, v, mask, live, scale, tiles, h_q, interpret)
    return (o, lse), (q, k, v, mask, live, o, lse)


def _masked_flash_bwd(scale, tiles, h_q, interpret, res, cots):
    do, _ = cots  # the log-sum-exp feeds detached statistics only
    dq, dk, dv = _bwd(scale, tiles[1], tiles[2], res, do, h_q=h_q, interpret=interpret)
    return dq, dk, dv, None, (None, None, None)


_masked_flash.defvjp(_masked_flash_fwd, _masked_flash_bwd)


def masked_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array, *,
                           scale: float | None = None, interpret: bool = False,
                           tiles: tuple[tuple[int, int], ...] | None = None,
                           live: tuple[jax.Array, ...] | None = None):
    """Attention of ``q [B, S_q, H, D]`` over the keys ``mask [B, S_q, S_k]``
    (int8, non-zero = picked; one mask for all heads) allows, ``k`` / ``v``
    ``[B, S_k, H_kv, D]`` with ``H % H_kv == 0``. Returns ``(o [B, S_q, H, D],
    lse [B, H, S_q] float32)``; a query with no picked key gives zeros. The
    gradient reaches q, k and v; the log-sum-exp carries none. ``live``: the
    launches' live tiles where the caller has them (:func:`live_tables` for
    ``tiles``), read from the mask here otherwise."""
    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    if h % h_kv or v.shape[2] != h_kv:
        raise ValueError(f"bad grouped head split: q {h}, k {h_kv}, v {v.shape[2]}")
    if mask.shape != (b, s_q, s_k):
        raise ValueError(f"mask {mask.shape} is not [batch, s_q, s_k] = {(b, s_q, s_k)}")
    scale = 1.0 / (d ** 0.5) if scale is None else float(scale)
    d_pad = lane_padded(d)

    def to_bh(x, s, heads):
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * heads, s, d)
        return x if d_pad == d else jnp.pad(x, ((0, 0), (0, 0), (0, d_pad - d)))

    tiles = tuple(tiles or plan_tiles(s_q, s_k))
    if live is None:
        live = live_tables(
            tile_counts((mask != 0).astype(jnp.int8), *base_tile(tiles)), tiles)
    ob, lse = _masked_flash(
        to_bh(q, s_q, h), to_bh(k, s_k, h_kv), to_bh(v, s_k, h_kv),
        mask.astype(jnp.int8), tuple(live), scale, tiles, h, interpret)
    o = jnp.transpose(ob[..., :d].reshape(b, h, s_q, d), (0, 2, 1, 3))
    return o, lse.reshape(b, h, s_q)


def masked_xla_attention(q: jax.Array, k: jax.Array, v: jax.Array, mask: jax.Array, *,
                         scale: float | None = None):
    """The same function in plain XLA, all ``[B, H, S_q, S_k]`` scores at
    once: the kernel's oracle, and what the CPU backend steps down to."""
    h, h_kv, d = q.shape[2], k.shape[2], q.shape[3]
    scale = 1.0 / (d ** 0.5) if scale is None else float(scale)
    if h != h_kv:
        k, v = jnp.repeat(k, h // h_kv, axis=2), jnp.repeat(v, h // h_kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    picked = (mask != 0)[:, None]
    scores = jnp.where(picked, scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    e = jnp.where(picked, jnp.exp(scores - top), 0.0)
    total = jnp.sum(e, axis=-1, keepdims=True)
    probs = e / jnp.where(total == 0.0, 1.0, total)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    lse = jnp.where(total[..., 0] == 0.0, NEG_INF, top[..., 0] + jnp.log(
        jnp.where(total[..., 0] == 0.0, 1.0, total[..., 0])))
    return out, jax.lax.stop_gradient(lse)


@functools.partial(jax.named_call, name="multihead_attention")
def masked_multihead_attention(q, k, v, mask, *, impl: str = "pallas",
                               interpret: bool = False, live=None):
    """``ops/attention.multihead_attention``'s dispatch for the masked
    kernel, under the same name in a trace: ``pallas`` runs the kernel on a
    TPU (or anywhere under ``interpret``) and steps down to XLA on the CPU
    backend, ``xla`` is the plain path. ``live``: :func:`plan_tiles`' live
    tiles where the caller has them (the kernel's only)."""
    # looked up at the call, as ``ops/moe.grouped_matmul`` does: the offline
    # compile check swaps the module's function to force the kernel
    from photon_tpu.ops import flash_attention

    if impl == "pallas" and (interpret or flash_attention.pallas_supported(q)):
        return masked_flash_attention(q, k, v, mask, interpret=interpret, live=live)
    if impl not in ("pallas", "xla"):
        raise ValueError(f"the masked attention has no impl {impl!r}")
    return masked_xla_attention(q, k, v, mask)
