"""Blockwise flash attention for TPU, in Pallas.

Replaces the reference's CUDA flash-attention dependency
(``attn_impl: flash``, ``conf/llm_config/mpt-125m.yaml:27-28``,
``README.md:96-100``) with an MXU-tiled, online-softmax kernel.

Design notes (TPU-first):
- Grid is ``(batch*heads, q_blocks, k_blocks)``; the innermost k dimension is
  executed sequentially per core, so the online-softmax running state
  ``(m, l, acc)`` lives in VMEM scratch and persists across k iterations.
- Scores accumulate in fp32 on the MXU (``preferred_element_type``); inputs
  are bf16. The log-sum-exp is saved for the backward pass.
- Blockwise structure means a ring/context-parallel extension only has to
  rotate k/v blocks between chips — the inner kernel is unchanged
  (SURVEY.md §5 long-context note).
- ``d_head`` is zero-padded to the 128-lane width when smaller (padding
  columns contribute nothing to scores or outputs).

Backward follows FlashAttention-2: a precomputed ``delta = rowsum(dO·O)``,
one kernel accumulating dq over k blocks, one accumulating dk/dv over q
blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8  # fp32 sublane height; lse/delta carry 8 redundant rows for tiling


def _kernel_scope(kernel: str):
    """Names one kernel's launches in a profiler trace: ``kernel`` goes into
    the operation's ``op_name`` metadata. The TPU compiler names a custom
    call's instruction after the innermost scope around it, and the
    benchmark's ``flash_attention_roofline`` finds this kernel by the
    instruction name ``multihead_attention`` (``ops/attention.py``'s
    ``named_call``), so that stays innermost. Once that reader looks for
    these names, ``pl.pallas_call(name=kernel)`` alone does both."""
    return jax.named_scope(f"{kernel}/multihead_attention")
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
NEG_INF = -1.0e30


def pallas_supported(x: jax.Array | None) -> bool:
    """Whether the Pallas TPU kernels can run where ``x`` lives (``None``, or
    a tracer: the default backend). True on a TPU. False on the CPU backend —
    the one the tests use — where callers step down to their XLA reference
    path in silence. Any other backend raises: a quiet step down there would
    hide that the kernel is not in the program. ``chip_smoke.py`` is what
    proves the kernels are in the programs that ran on the chip."""
    try:
        platform = x.devices().pop().platform if hasattr(x, "devices") else None
    except Exception:
        platform = None
    if platform is None:
        platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise NotImplementedError(
            f"Pallas TPU kernels cannot run on the {platform!r} backend, and "
            "only the CPU backend steps down to the XLA path; ask for "
            "attn_impl='xla' (serve.attention_impl='gather') explicitly"
        )
    return platform == "tpu"


def _tile_ids(q_blk: int, k_blk: int, block_q: int, block_k: int, offset: int):
    """Global (query, key) position iotas for the (q_blk, k_blk) tile.

    ``offset = s_k - s_q`` aligns query positions to the end of the key
    sequence (matches ``xla_attention``; matters when s_q != s_k).
    """
    q_ids = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_blk * block_q + offset
    k_ids = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1) + k_blk * block_k
    return q_ids, k_ids


def _causal_mask(q_blk: int, k_blk: int, block_q: int, block_k: int, offset: int) -> jax.Array:
    """Boolean [block_q, block_k] mask for the (q_blk, k_blk) tile."""
    q_ids, k_ids = _tile_ids(q_blk, k_blk, block_q, block_k, offset)
    return q_ids >= k_ids


def _alibi_bias(slope, q_blk, k_blk, block_q, block_k, offset) -> jax.Array:
    """Per-head ALiBi bias ``-slope * (q_pos - k_pos)`` for one tile
    (reference: llm-foundry MPT ``attn_config.alibi``; oracle:
    ``ops/attention.py:xla_attention``)."""
    q_ids, k_ids = _tile_ids(q_blk, k_blk, block_q, block_k, offset)
    return -slope * (q_ids - k_ids).astype(jnp.float32)


def _bh_slopes(h_slopes: jax.Array, bh: int) -> jax.Array:
    """[bh, SUBLANE, LANE] per-(batch*head) slope array (replicated across
    the tile so each grid row DMAs one full fp32 tile). ``h_slopes`` is the
    per-head slope vector [h] — by default ``attention.alibi_slopes(h)``,
    but a caller under a head-sharded (tensor-parallel) mesh passes its
    LOCAL slice of the global slope table so every shard biases with its
    true global head index."""
    h = h_slopes.shape[0]
    slopes = jnp.tile(h_slopes, bh // h)  # head-major order
    return jnp.broadcast_to(slopes[:, None, None], (bh, SUBLANE, LANE))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, block_q, block_k, causal, offset, use_alibi):
    if use_alibi:
        slopes_ref, o_ref, lse_ref, m_s, l_s, acc_s = rest
    else:
        slopes_ref = None
        o_ref, lse_ref, m_s, l_s, acc_s = rest
    q_blk = pl.program_id(1)
    k_blk = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(k_blk == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    # for causal attention, tiles strictly above the diagonal are dead
    live = (not causal) or (k_blk * block_k <= q_blk * block_q + (block_q - 1) + offset)

    def _compute():
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [block_q, block_k]
        s = s * scale
        if use_alibi:
            s = s + _alibi_bias(slopes_ref[0, 0, 0], q_blk, k_blk, block_q, block_k, offset)
        if causal:
            s = jnp.where(_causal_mask(q_blk, k_blk, block_q, block_k, offset), s, NEG_INF)

        m_prev = m_s[:, 0][:, None]  # [block_q, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # fully-masked rows keep m == NEG_INF; exp(s - m) would be exp(0)=1
        # there, so force p to 0 (their output stays 0, l stays 0)
        p = jnp.where(m_new > NEG_INF / 2, jnp.exp(s - m_new), 0.0)  # [block_q, block_k]
        alpha = jnp.exp(m_prev - m_new)  # rescale of old state
        l_new = alpha * l_s[:, 0][:, None] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, d]
        acc_s[:] = acc_s[:] * alpha + pv
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    if causal:
        # static skip only possible when grid point is fully dead; the grid is
        # dense so we predicate instead (dead tiles cost only the DMA)
        @pl.when(live)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(k_blk == n_k - 1)
    def _finalize():
        l = l_s[:, 0][:, None]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_s[:] / l_safe).astype(o_ref.dtype)
        lse = m_s[:, 0] + jnp.log(l_safe[:, 0])  # [block_q]
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (SUBLANE, lse.shape[0]))


def _kv_row(h_q: int, h_kv: int):
    """bh_q-major grid row → k/v storage row for grouped-query attention.

    Arrays are head-major flattened (``b*h + h_idx``); q head ``hq`` reads
    kv head ``hq // group``. With ``h_q == h_kv`` (MHA) this is identity.
    """
    group = h_q // h_kv

    def row(bh):
        if group == 1:
            return bh
        return (bh // h_q) * h_kv + (bh % h_q) // group

    return row


def _fwd(q, k, v, *, scale, causal, block_q, block_k, offset=None, slopes=None,
         h_q=0, interpret=False):
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    n_q = pl.cdiv(s_q, block_q)
    n_k = pl.cdiv(s_k, block_k)
    grid = (bh, n_q, n_k)
    h_q = h_q or 1  # 0 → MHA (kv row == q row; exact head split irrelevant)
    kv = _kv_row(h_q, h_q * k.shape[0] // bh)

    # offset generalizes the causal mask to chunked/global positions:
    # visible iff q_id + offset >= k_id (ring attention passes
    # q_start - k_start; default aligns q to the end of k)
    offset = s_k - s_q if offset is None else offset
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k, causal=causal,
        offset=offset, use_alibi=slopes is not None,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), j, 0)),
    ]
    inputs = [q, k, v]
    if slopes is not None:
        in_specs.append(pl.BlockSpec((1, SUBLANE, LANE), lambda b, i, j: (b, 0, 0)))
        inputs.append(slopes)
    # lse carries SUBLANE redundant rows so its (1, 8, block_q) blocks are
    # exactly one fp32 tile; callers use row 0
    out_shape = [
        jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
        jax.ShapeDtypeStruct((bh, SUBLANE, s_q), jnp.float32),
    ]
    launch = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j: (b, 0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANE), jnp.float32),  # running max
            pltpu.VMEM((block_q, LANE), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        out_shape=out_shape,
        interpret=interpret,
    )
    with _kernel_scope("flash_fwd"):
        o, lse = launch(*inputs)
    return o, lse[:, 0, :]


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest, scale, block_q, block_k, causal, offset, use_alibi):
    if use_alibi:
        slopes_ref, dq_ref, dq_s = rest
    else:
        slopes_ref = None
        dq_ref, dq_s = rest
    q_blk = pl.program_id(1)
    k_blk = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(k_blk == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    live = (not causal) or (k_blk * block_k <= q_blk * block_q + (block_q - 1) + offset)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if use_alibi:
            s = s + _alibi_bias(slopes_ref[0, 0, 0], q_blk, k_blk, block_q, block_k, offset)
        if causal:
            s = jnp.where(_causal_mask(q_blk, k_blk, block_q, block_k, offset), s, NEG_INF)
        lse = lse_ref[0, 0][:, None]
        # guard fully-masked rows (lse == NEG_INF): exp(s - lse) would be 1
        p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)  # [block_q, block_k]
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dq_s[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        @pl.when(live)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(k_blk == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest, scale, block_q, block_k, causal, offset, use_alibi, n_q):
    """Inner grid dim sweeps ``group * n_q`` steps: for grouped-query
    attention every kv row accumulates dk/dv over ALL q heads of its group
    (t // n_q picks the group member, t % n_q the q block); MHA is the
    group == 1 degenerate case."""
    if use_alibi:
        slopes_ref, dk_ref, dv_ref, dk_s, dv_s = rest
    else:
        slopes_ref = None
        dk_ref, dv_ref, dk_s, dv_s = rest
    k_blk = pl.program_id(1)
    t = pl.program_id(2)
    n_t = pl.num_programs(2)
    q_blk = t % n_q

    @pl.when(t == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    live = (not causal) or (k_blk * block_k <= q_blk * block_q + (block_q - 1) + offset)

    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
        if use_alibi:
            s = s + _alibi_bias(slopes_ref[0, 0, 0], q_blk, k_blk, block_q, block_k, offset)
        if causal:
            s = jnp.where(_causal_mask(q_blk, k_blk, block_q, block_k, offset), s, NEG_INF)
        lse = lse_ref[0, 0][:, None]
        # guard fully-masked rows (lse == NEG_INF): exp(s - lse) would be 1
        p = jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)  # [block_q, block_k]
        do = do_ref[0].astype(jnp.float32)
        # dv += p^T @ do
        dv_s[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale  # [block_q, block_k]
        # dk += ds^T @ q
        dk_s[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        @pl.when(live)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(t == n_t - 1)
    def _finalize():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, res, do, *, slopes=None, h_q=0,
         interpret=False):
    q, k, v, o, lse = res
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    n_q = pl.cdiv(s_q, block_q)
    n_k = pl.cdiv(s_k, block_k)
    bh_k = k.shape[0]
    h_q = h_q or 1
    h_kv = h_q * bh_k // bh
    group = h_q // h_kv
    kv = _kv_row(h_q, h_kv)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [bh, s_q]
    # SUBLANE-replicated rows for TPU tiling (see _fwd)
    lse_b = jnp.broadcast_to(lse[:, None, :], (bh, SUBLANE, s_q))
    delta_b = jnp.broadcast_to(delta[:, None, :], (bh, SUBLANE, s_q))

    use_alibi = slopes is not None
    extra_inputs = [slopes] if use_alibi else []
    slope_spec = (
        [pl.BlockSpec((1, SUBLANE, LANE), lambda b, i, j: (b, 0, 0))] if use_alibi else []
    )

    launch_dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
                          causal=causal, offset=s_k - s_q, use_alibi=use_alibi),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), j, 0)),  # k
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), j, 0)),  # v
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),  # do
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j: (b, 0, i)),  # lse
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j: (b, 0, i)),  # delta
        ] + slope_spec,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
        interpret=interpret,
    )
    with _kernel_scope("flash_dq"):
        dq = launch_dq(q, k, v, do, lse_b, delta_b, *extra_inputs)

    # dkv grid rows are the kv STORAGE rows; the inner dim sweeps the
    # group's q heads × q blocks so each kv row accumulates its whole
    # gradient in one VMEM scratch pass (GQA-native: no repeated kv, no
    # cross-row reduction)
    def qrow(b, t):
        if group == 1:
            return b
        return (b // h_kv) * h_q + (b % h_kv) * group + t // n_q

    launch_dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
                          causal=causal, offset=s_k - s_q, use_alibi=use_alibi, n_q=n_q),
        grid=(bh_k, n_k, group * n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, t: (qrow(b, t), t % n_q, 0)),  # q
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),  # k
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),  # v
            pl.BlockSpec((1, block_q, d), lambda b, j, t: (qrow(b, t), t % n_q, 0)),  # do
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, j, t: (qrow(b, t), 0, t % n_q)),  # lse
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, j, t: (qrow(b, t), 0, t % n_q)),  # delta
        ] + (
            [pl.BlockSpec((1, SUBLANE, LANE), lambda b, j, t: (qrow(b, t), 0, 0))]
            if use_alibi else []
        ),
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh_k, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh_k, s_k, d), v.dtype),
        ],
        interpret=interpret,
    )
    with _kernel_scope("flash_dkv"):
        dk, dv = launch_dkv(q, k, v, do, lse_b, delta_b, *extra_inputs)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------


# slopes rides as a real operand (index 3) so a tensor-parallel caller can
# pass per-shard slope slices (traced values — a static head count cannot
# express a shard-dependent offset); its cotangent is zero (slopes are
# non-learned constants). ``h_q`` (static) carries the q-head count for
# grouped-query attention, where k/v hold fewer rows than q; 0 = MHA.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, slopes, scale, causal, block_q, block_k, interpret, h_q=0):
    o, _ = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                slopes=slopes, h_q=h_q, interpret=interpret)
    return o


def _flash_fwd(q, k, v, slopes, scale, causal, block_q, block_k, interpret, h_q=0):
    o, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                  slopes=slopes, h_q=h_q, interpret=interpret)
    return o, (q, k, v, o, lse, slopes)


def _flash_bwd(scale, causal, block_q, block_k, interpret, h_q, res, do):
    q, k, v, o, lse, slopes = res
    dq, dk, dv = _bwd(scale, causal, block_q, block_k, (q, k, v, o, lse), do,
                      slopes=slopes, h_q=h_q, interpret=interpret)
    return dq, dk, dv, jax.tree.map(jnp.zeros_like, slopes)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    alibi: bool = False,
    alibi_slopes: jax.Array | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention over ``[batch, seq, heads, d_head]`` inputs.

    Grouped-query attention is native: ``k``/``v`` may carry fewer heads
    than ``q`` (``h_q % h_kv == 0``) — the kernel index-maps each q head
    onto its kv group row, so the repeated-kv tensor is never materialized
    in HBM (fwd reads and bwd dk/dv are kv-row-major).

    ``alibi`` adds the per-head linear distance bias in-kernel. Slopes
    default to ``ops/attention.py:alibi_slopes(h)``; a head-sharded
    (tensor-parallel) caller passes ``alibi_slopes`` — its LOCAL [h] slice
    of the global slope table — so each shard biases with its true global
    head index (the in-kernel default would restart the slope sequence per
    shard). ``interpret`` runs the kernel in the Pallas interpreter
    (CPU-testable)."""
    b, s_q, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads ({h}) must be a multiple of kv heads ({h_kv})")
    if v.shape[2] != h_kv:
        # the kv row map is derived from k's width and applied to v — a
        # mismatch would silently read the wrong heads
        raise ValueError(f"k has {h_kv} heads but v has {v.shape[2]}")
    s_k = k.shape[1]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    if s_q % block_q or s_k % block_k:
        raise ValueError(f"seq lengths ({s_q},{s_k}) must divide blocks ({block_q},{block_k})")
    scale = 1.0 / (d**0.5)

    d_pad = max(LANE, ((d + LANE - 1) // LANE) * LANE)

    def to_bh(x, s, heads):
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * heads, s, d)
        if d_pad != d:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, d_pad - d)))
        return x

    qb, kb, vb = to_bh(q, s_q, h), to_bh(k, s_k, h_kv), to_bh(v, s_k, h_kv)
    slopes = None
    if alibi:
        from photon_tpu.ops.attention import alibi_slopes as default_slopes

        h_slopes = alibi_slopes if alibi_slopes is not None else default_slopes(h)
        slopes = _bh_slopes(h_slopes.astype(jnp.float32), b * h)
    ob = _flash(qb, kb, vb, slopes, scale, causal, block_q, block_k, interpret,
                h if h_kv != h else 0)
    o = ob[..., :d].reshape(b, h, s_q, d)
    return jnp.transpose(o, (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# LSE-returning variant (ring attention inner kernel)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_lse(q, k, v, scale, causal, offset, block_q, block_k, interpret=False,
               h_q=0):
    return _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                offset=offset, h_q=h_q, interpret=interpret)


def _flash_lse_fwd(q, k, v, scale, causal, offset, block_q, block_k, interpret=False,
                   h_q=0):
    o, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                  offset=offset, h_q=h_q, interpret=interpret)
    return (o, lse), (q, k, v)


def _flash_lse_bwd(scale, causal, offset, block_q, block_k, interpret, h_q, res, cots):
    """Exact backward for BOTH outputs (o, lse) by recomputing the chunk with
    the differentiable XLA path. Ring attention's online-softmax merge takes
    real gradients through lse, which the FlashAttention-2 backward (defined
    only for the final normalized output) does not model — recompute does."""
    q, k, v = res
    from photon_tpu.ops.ring_attention import xla_chunk_attention

    bh_q = q.shape[0]
    group = bh_q // k.shape[0]

    def chunk(q3, k3, v3):
        # flat rows → the [b, s, h, d] chunk oracle: each kv row becomes a
        # "batch" entry holding its GROUP of q heads (group == 1 for MHA);
        # pass the kernel's scale explicitly (inputs are lane-padded, so
        # 1/sqrt(padded_d) would be wrong)
        s_q, d = q3.shape[1:]
        q4 = q3.reshape(bh_q // group, group, s_q, d).transpose(0, 2, 1, 3)
        o4, lse3 = xla_chunk_attention(
            q4, k3[:, :, None, :], v3[:, :, None, :],
            q_start=offset, k_start=0, causal=causal, scale=scale,
        )
        o3 = o4.transpose(0, 2, 1, 3).reshape(bh_q, s_q, d)
        lse_o = lse3.transpose(0, 2, 1).reshape(bh_q, s_q)
        return o3, lse_o

    _, vjp = jax.vjp(chunk, q, k, v)
    return vjp(cots)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    q_start: int = 0,
    k_start: int = 0,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Like :func:`flash_attention` but over global positions
    (``q_start``/``k_start`` are the chunks' sequence offsets) and returning
    ``(o [b,s,h,d], lse [b,s,h])`` for online-softmax merging across chunks.
    Grouped-query attention: ``k``/``v`` may carry fewer heads than ``q``
    (consumed natively, same as :func:`flash_attention`)."""
    b, s_q, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv or v.shape[2] != h_kv:
        raise ValueError(f"bad GQA head split: q {h}, k {h_kv}, v {v.shape[2]}")
    s_k = k.shape[1]
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    if s_q % block_q or s_k % block_k:
        raise ValueError(f"seq lengths ({s_q},{s_k}) must divide blocks ({block_q},{block_k})")
    scale = 1.0 / (d**0.5)
    d_pad = max(LANE, ((d + LANE - 1) // LANE) * LANE)

    def to_bh(x, s, heads):
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * heads, s, d)
        if d_pad != d:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, d_pad - d)))
        return x

    qb, kb, vb = to_bh(q, s_q, h), to_bh(k, s_k, h_kv), to_bh(v, s_k, h_kv)
    ob, lse = _flash_lse(qb, kb, vb, scale, causal, q_start - k_start, block_q,
                         block_k, interpret, h if h_kv != h else 0)
    o = jnp.transpose(ob[..., :d].reshape(b, h, s_q, d), (0, 2, 1, 3))
    return o, jnp.transpose(lse.reshape(b, h, s_q), (0, 2, 1))
