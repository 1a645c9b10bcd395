"""Ragged paged attention for the serving plane, in Pallas.

The PR 5 serving step resolves each slot's block table with a dense
gather at FULL padded width: every decode step reads ``max_blocks``
blocks per slot no matter how short the sequence, so attention cost
scales with pool capacity instead of live tokens. This module is the
kernel-shaped fix ("Ragged Paged Attention: A High-Performance and
Flexible LLM Inference Kernel for TPU", PAPERS.md): attention walks each
slot's block table only over its LIVE blocks, with an online-softmax
accumulation over the block walk, and handles prefill-chunk rows and
decode rows in one ragged batch.

Shape contract (one transformer layer; the serving step scans layers):

- ``q``            ``[B, T, H, Dh]`` — ``B`` slots x ``T`` query tokens.
  Decode rows carry one real token (``T`` pads to the step's chunk
  bucket); a prompt chunk carries up to ``T`` consecutive tokens.
- ``k_pool/v_pool``  ``[NB, bs, H_kv, Dh]`` — the layer's paged pool
  including the trash block (grouped-query: ``H_kv <= H``).
- ``rows``         ``[B, n_ctx]`` int32 — each slot's block-table slice.
  ``n_ctx`` is the step's LIVE width (the pow2 bucket covering the
  longest live slot), not the table's full width: this slice is the
  ragged walk. Dead entries point at the trash block and are masked.
- ``positions``    ``[B, T]`` int32 — each query token's absolute
  position. Causality and raggedness are one mask: key position ``p``
  is visible to a query at position ``pos`` iff ``p <= pos``, which
  simultaneously hides same-chunk future tokens, other slots' recycled
  bytes behind stale table entries, and everything past the slot's true
  length (the per-slot true length is exactly ``positions`` + 1 at each
  slot's last real row).

Returns ``[B, T, H, Dh]`` attention outputs.

Two implementations share this contract:

- :func:`ragged_paged_attention` — the fused Pallas kernel. The block
  walk is the innermost (sequential) grid dimension, so the online
  softmax state ``(m, l, acc)`` lives in VMEM scratch and persists
  across blocks, exactly the ``ops/flash_attention.py`` idiom —
  including ``interpret=`` so the CPU sandbox executes the same kernel
  logic through the Pallas interpreter. Numerics: EPSILON-tier vs the
  dense softmax (the online rescaling reorders the fp32 accumulation);
  the pinned thresholds live in ``tests/test_ragged_attention.py``.
- :func:`ragged_reference_attention` — the XLA reference over the same
  live view: one dense softmax over ``n_ctx * bs`` masked scores.
  BIT-EXACT with the contiguous ``models/decode.py`` math (masked
  positions contribute exactly-zero probability either way), which is
  why ``serve/cache.py`` uses this math for its gather path and the
  parity harness keeps ``assert_array_equal`` there.

The table indirection itself is resolved by :func:`live_view` — a
gather indexed ONLY by the ``[B, n_ctx]`` row slice, so the work (and
the HBM traffic it models) is proportional to live blocks, never to the
pool. On a real chip the natural next step is folding that gather into
the kernel via scalar-prefetched index maps (the RPA paper's layout);
the block-walk structure here is already the one that move needs.

TPU sizing notes: the kernel's k-tile is one pool block, so
``serve.block_size`` should be a sublane multiple (>= 8) on hardware;
``Dh`` is zero-padded to the 128-lane width as in flash_attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_tpu.ops.flash_attention import LANE, NEG_INF, SUBLANE


def live_view(k_pool: jax.Array, v_pool: jax.Array,
              rows: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Gather the live blocks behind ``rows [B, n_ctx]`` into contiguous
    per-slot views ``[B, n_ctx * bs, H_kv, Dh]``. O(live blocks): the
    pool is indexed only through the row slice — this is the block-table
    walk, and the only place the pool is touched."""
    b, n_ctx = rows.shape
    bs = k_pool.shape[1]
    kb = k_pool[rows].reshape(b, n_ctx * bs, *k_pool.shape[2:])
    vb = v_pool[rows].reshape(b, n_ctx * bs, *v_pool.shape[2:])
    return kb, vb


def ragged_reference_attention(q: jax.Array, kb: jax.Array, vb: jax.Array,
                               positions: jax.Array, *,
                               scale: float | None = None,
                               slopes: jax.Array | None = None) -> jax.Array:
    """Dense-math oracle over an already-gathered live view: the exact
    grouped-query einsum formulation of ``models/decode.py:decode_step``
    with a token axis. Bit-exact with the contiguous path (the unit
    tests pin it); ``serve/cache.py`` inlines this same math as its
    gather attention so the serving parity bar stays assert_array_equal.

    ``q [B, T, H, Dh]``, ``kb/vb [B, S, H_kv, Dh]``, ``positions
    [B, T]``; ``slopes [H]`` arms the ALiBi distance bias."""
    b, t, h, d = q.shape
    s = kb.shape[1]
    n_kv = kb.shape[2]
    group = h // n_kv
    scale = (1.0 / d ** 0.5) if scale is None else scale
    k_pos = jnp.arange(s)[None, None, :]  # [1, 1, S]
    valid = k_pos <= positions[:, :, None]  # [B, T, S]
    qg = q.reshape(b, t, n_kv, group, d)
    scores = jnp.einsum("btkgd,bskd->btkgs", qg, kb,
                        preferred_element_type=jnp.float32) * scale
    if slopes is not None:
        dist = (positions[:, :, None] - k_pos).astype(jnp.float32)  # [B, T, S]
        sl = slopes.astype(jnp.float32).reshape(n_kv, group)
        scores = scores - sl[None, None, :, :, None] * dist[:, :, None, None, :]
    scores = jnp.where(valid[:, :, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("btkgs,bskd->btkgd", probs.astype(vb.dtype), vb)
    return out.reshape(b, t, h, d)


# ---------------------------------------------------------------------------
# The fused kernel
# ---------------------------------------------------------------------------


def _rpa_kernel(q_ref, k_ref, v_ref, pos_ref, *rest, scale, bs, use_alibi):
    """One (slot x kv-head, block) grid point: score the q tile against
    pool block ``j`` of this row's walk and fold it into the online
    softmax state. Rows are head-major ``t * group + g`` (grouped-query:
    every kv head serves its ``group`` q heads from one k/v tile)."""
    if use_alibi:
        slope_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        slope_ref = None
        o_ref, m_s, l_s, acc_s = rest
    j = pl.program_id(1)
    n_j = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    q = q_ref[0]  # [Tg, d]
    k = k_ref[0]  # [bs, d]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [Tg, bs]
    q_pos = pos_ref[0, 0, :][:, None]  # [Tg, 1] absolute query positions
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * bs
    if use_alibi:
        slope = slope_ref[0, 0, :][:, None]  # [Tg, 1] per-row head slope
        s = s - slope * (q_pos - k_pos).astype(jnp.float32)
    # the ragged mask: causality, same-chunk future tokens, recycled
    # bytes behind stale/trash table entries — all one comparison
    s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_s[:, 0][:, None]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # fully-masked tiles keep m == NEG_INF; exp(s - m) would be exp(0)=1
    # there, so force p to 0 (their l and acc contributions stay 0)
    p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_s[:, 0][:, None] + jnp.sum(p, axis=-1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [Tg, d]
    acc_s[:] = acc_s[:] * alpha + pv
    m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(j == n_j - 1)
    def _finalize():
        l = l_s[:, 0][:, None]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_s[:] / l_safe).astype(o_ref.dtype)


def ragged_paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           rows: jax.Array, positions: jax.Array, *,
                           scale: float | None = None,
                           slopes: jax.Array | None = None,
                           interpret: bool = False) -> jax.Array:
    """The fused ragged-paged-attention kernel (module docstring has the
    full shape contract). ``slopes [H]`` arms in-kernel ALiBi;
    ``interpret`` runs through the Pallas interpreter (CPU sandbox)."""
    b, t, h, d = q.shape
    n_kv = k_pool.shape[2]
    if h % n_kv:
        raise ValueError(f"q heads ({h}) must be a multiple of kv heads ({n_kv})")
    if v_pool.shape != k_pool.shape:
        raise ValueError(f"k pool {k_pool.shape} != v pool {v_pool.shape}")
    bs = k_pool.shape[1]
    group = h // n_kv
    tg = t * group
    n_ctx = rows.shape[1]
    scale = (1.0 / d ** 0.5) if scale is None else scale
    d_pad = max(LANE, ((d + LANE - 1) // LANE) * LANE)

    kb, vb = live_view(k_pool, v_pool, rows)  # [B, S, H_kv, Dh]

    def pad_d(x):
        if d_pad != d:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, d_pad - d)])
        return x

    # head-major row layout: grid row b*n_kv + kv serves rows t*group + g
    # (q head kv*group + g), all scoring against ONE k/v tile per block
    qb = pad_d(
        q.reshape(b, t, n_kv, group, d).transpose(0, 2, 1, 3, 4)
        .reshape(b * n_kv, tg, d)
    )
    kb = pad_d(kb.transpose(0, 2, 1, 3).reshape(b * n_kv, n_ctx * bs, d))
    vb = pad_d(vb.transpose(0, 2, 1, 3).reshape(b * n_kv, n_ctx * bs, d))
    # positions replicated per group row, SUBLANE-replicated for tiling
    # (the flash lse idiom: callers of the (1, SUBLANE, Tg) tile use row 0)
    pos_rep = jnp.repeat(positions.astype(jnp.int32), group, axis=1)  # [B, Tg]
    pos_b = jnp.broadcast_to(
        pos_rep[:, None, None, :], (b, n_kv, SUBLANE, tg)
    ).reshape(b * n_kv, SUBLANE, tg)

    inputs = [qb, kb, vb, pos_b]
    in_specs = [
        pl.BlockSpec((1, tg, d_pad), lambda r, j: (r, 0, 0)),
        pl.BlockSpec((1, bs, d_pad), lambda r, j: (r, j, 0)),
        pl.BlockSpec((1, bs, d_pad), lambda r, j: (r, j, 0)),
        pl.BlockSpec((1, SUBLANE, tg), lambda r, j: (r, 0, 0)),
    ]
    if slopes is not None:
        # per-ROW slope (rows mix q heads): row t*group + g of grid row
        # (b, kv) biases with the GLOBAL head kv*group + g
        slope_rows = jnp.tile(
            slopes.astype(jnp.float32).reshape(n_kv, 1, group), (1, t, 1)
        ).reshape(n_kv, tg)
        slope_b = jnp.broadcast_to(
            slope_rows[None, :, None, :], (b, n_kv, SUBLANE, tg)
        ).reshape(b * n_kv, SUBLANE, tg)
        inputs.append(slope_b)
        in_specs.append(pl.BlockSpec((1, SUBLANE, tg), lambda r, j: (r, 0, 0)))

    out = pl.pallas_call(
        functools.partial(_rpa_kernel, scale=scale, bs=bs,
                          use_alibi=slopes is not None),
        grid=(b * n_kv, n_ctx),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tg, d_pad), lambda r, j: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((tg, LANE), jnp.float32),  # running max
            pltpu.VMEM((tg, LANE), jnp.float32),  # running denom
            pltpu.VMEM((tg, d_pad), jnp.float32),  # output accumulator
        ],
        out_shape=jax.ShapeDtypeStruct((b * n_kv, tg, d_pad), q.dtype),
        interpret=interpret,
        name="ragged_paged_attention",
    )(*inputs)

    out = out[..., :d].reshape(b, n_kv, t, group, d).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, t, h, d)
