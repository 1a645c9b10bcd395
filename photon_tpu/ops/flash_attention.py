"""Blockwise flash attention for TPU, in Pallas.

Replaces the reference's CUDA flash-attention dependency
(``attn_impl: flash``, ``conf/llm_config/mpt-125m.yaml:27-28``,
``README.md:96-100``) with an MXU-tiled, online-softmax kernel.

Design notes (TPU-first):
- Grid is ``(batch*heads, q_blocks, k_blocks)``; the innermost k dimension is
  executed sequentially per core, so the online-softmax running state
  ``(m, l, acc)`` lives in VMEM scratch and persists across k iterations.
- The tile is a function of the shapes, not a default: ``pick_tiles`` gives
  each of the three launches the largest ``(block_q, block_k)`` that divides
  the sequences, fits the VMEM budget by ``launch_vmem_bytes`` (an estimate
  from the kernel's own buffers) and the ladder on the chip showed no slower
  than the next smaller; a launch whose estimate passes what Mosaic gives
  unasked asks for it (``vmem_limit_bytes``). Callers pass ``block_q`` /
  ``block_k`` only to pin a tile (tests, the ladder).
- The grid is dense over the causal square, so a tile strictly above the
  diagonal still costs its grid step, but nothing else: its body is
  predicated off (``pl.when(live)``) and its k/v (forward, dq) or
  q/dO/lse/delta (dk/dv) block index is clamped to the nearest live one
  (``_kv_block``, ``_q_block``), so the pipeline sees a repeated index and
  issues no copy.
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
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
SUBLANE = 8  # fp32 sublane height; lse/delta carry 8 redundant rows for tiling


def lane_padded(d: int) -> int:
    """``d_head`` as the kernels see it: zero-padded up to whole lane widths."""
    return -(-d // LANE) * LANE


def _kernel_scope(kernel: str):
    """Names one kernel's launches in a profiler trace: ``kernel`` goes into
    the operation's ``op_name`` metadata. The TPU compiler names a custom
    call's instruction after the innermost scope around it, and the
    benchmark's ``flash_attention_roofline`` finds this kernel by the
    instruction name ``multihead_attention`` (``ops/attention.py``'s
    ``named_call``), so that stays innermost. Once that reader looks for
    these names, ``pl.pallas_call(name=kernel)`` alone does both."""
    return jax.named_scope(f"{kernel}/multihead_attention")


NEG_INF = -1.0e30
# VMEM, in bytes. A Mosaic kernel that asks for nothing gets 16 MiB of a v5e
# core's 128 MiB; a launch whose estimate is larger asks for its estimate
# (``vmem_limit_bytes``), and ``pick_tiles`` keeps every launch under the budget.
VMEM_SCOPED_DEFAULT = 16 * 2**20
VMEM_BUDGET = 48 * 2**20
VMEM_SLACK = 2**20  # the compiler's own scratch, and rounding to its tiles
SCORE_TEMPS = 1.5  # [block_q, block_k] fp32 temporaries alive at once
# The largest (block_q, block_k) at which each launch was still no slower than
# at the next smaller tile, on the ladder ``scripts/flash_tile_ladder.py`` runs
# on the chip (v5e, seq 2,048, d_head 64 and 128, PERF.md PR 28). The forward
# is fastest with the whole 2,048 square in one step (its cost is per k step:
# the running max / denominator stores and the accumulator rescale), dq peaks
# at 1,024, and dk/dv, whose fp32-operand products grow with the masked part
# of a diagonal tile, at 512.
TILE_LADDER_TOP = {"fwd": (2048, 2048), "dq": (1024, 1024), "dkv": (512, 512)}


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
# Tiling the causal square: which tiles a launch fetches, how large they are
# ---------------------------------------------------------------------------


def _kv_block(i, j, *, causal, block_q, block_k, offset, n_k):
    """k/v block that grid step ``(i, j)`` of the forward and dq launches
    holds in VMEM. Row ``i``'s tiles past the diagonal are dead (the body is
    predicated off): their index is clamped to the row's last live block, so
    consecutive steps name the same block and the pipeline issues no copy for
    them. A live step maps to ``j`` itself; non-causal, every step does."""
    if not causal:
        return j
    last_live = jnp.maximum(i * block_q + (block_q - 1) + offset, 0) // block_k
    return jnp.minimum(j, jnp.minimum(last_live, n_k - 1))


def _q_block(i, j, *, causal, block_q, block_k, offset, n_q):
    """q/dO/lse/delta block that step ``(j, i)`` of the dk/dv launch holds: that
    launch sweeps q innermost, so k block ``j``'s dead tiles come first and
    are clamped forward to its first live q block (fetched once, ahead)."""
    if not causal:
        return i
    first_live = jnp.maximum(j * block_k - offset, 0) // block_q
    return jnp.maximum(i, jnp.minimum(first_live, n_q - 1))


def launch_vmem_bytes(launch: str, block_q: int, block_k: int, d: int, itemsize: int) -> int:
    """VMEM one launch (``fwd``, ``dq`` or ``dkv``) needs at a tile, from the
    kernel's own buffers: every BlockSpec'd operand and result twice (the
    pipeline double-buffers them), the scratch accumulators, the fp32
    temporaries as large as an operand (the forward's ``pv`` and rescaled
    accumulator, the backward bodies' upcasts), and the ``[block_q, block_k]``
    score temporaries. The body names five or six of those (s, the mask, p,
    dp, ds); the compiler keeps ``SCORE_TEMPS`` of them alive at once, read
    off the smallest ``vmem_limit_bytes`` it accepts per tile (PERF.md,
    PR 28: over 90 readings of launch, tile, width and dtype this estimate is
    1.05 to 2.1 times that, never under it;
    ``tests/test_tpu_compile.py::test_flash_vmem_estimate_is_enough``)."""
    q_rows = block_q * d * itemsize  # one q-shaped block: q, o, do, dq
    k_rows = block_k * d * itemsize  # one k-shaped block: k, v, dk, dv
    row_stats = SUBLANE * block_q * 4  # one lse / delta block
    slopes = SUBLANE * LANE * 4
    if launch == "fwd":
        piped = 2 * q_rows + 2 * k_rows + row_stats  # q, o; k, v; lse
        scratch = 2 * block_q * LANE * 4 + block_q * d * 4  # m, l; acc
        upcast = 2 * block_q * d * 4  # pv, acc * alpha
    elif launch == "dq":
        piped = 3 * q_rows + 2 * k_rows + 2 * row_stats  # q, do, dq; k, v
        scratch = block_q * d * 4
        upcast = (block_q + block_k) * d * 4  # do, v
    elif launch == "dkv":
        piped = 2 * q_rows + 4 * k_rows + 2 * row_stats  # q, do; k, v, dk, dv
        scratch = 2 * block_k * d * 4
        upcast = (2 * block_q + block_k) * d * 4  # do, q, v
    else:
        raise ValueError(f"unknown launch {launch!r}")
    scores = int(SCORE_TEMPS * block_q * block_k * 4)
    return 2 * (piped + slopes) + scratch + upcast + scores + VMEM_SLACK


def _vmem_params(need: int) -> dict:
    """The launch's compiler parameters: nothing while the estimate fits what
    Mosaic gives a kernel unasked, else the estimate as its limit."""
    if need <= VMEM_SCOPED_DEFAULT:
        return {}
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=need)}


class LaunchTiles(NamedTuple):
    """One launch's tile, what it needs and what it skips."""

    block_q: int
    block_k: int
    vmem_bytes: int  # launch_vmem_bytes at this tile
    live_tiles: int  # tiles whose body runs, per (batch, q head)
    grid_tiles: int  # grid steps paid, per (batch, q head)


class TilePlan(NamedTuple):
    fwd: LaunchTiles
    dq: LaunchTiles
    dkv: LaunchTiles

    @property
    def blocks(self) -> tuple[tuple[int, int], ...]:
        return tuple((t.block_q, t.block_k) for t in self)

    def attrs(self) -> dict[str, str]:
        """The plan as span attributes (``trainer/steps`` carries them)."""
        return {
            "flash_tiles": " ".join(
                f"{n}={t.block_q}x{t.block_k}" for n, t in zip(self._fields, self)),
            "flash_live_tiles": " ".join(
                f"{n}={t.live_tiles}/{t.grid_tiles}" for n, t in zip(self._fields, self)),
        }


def _tile_sizes(s: int) -> list[int]:
    """Block sizes a length-``s`` axis can take: its divisors that are whole
    lane widths (a block's rows are the lanes of the lse/delta blocks and of
    the score tile), and ``s`` itself, which is always allowed."""
    return sorted({t for t in range(LANE, s + 1, LANE) if s % t == 0} | {s})


def pick_tiles(s_q: int, s_k: int, d_pad: int, itemsize: int, n_kv_group: int = 1, *,
               causal: bool = True, offset: int | None = None,
               block_q: int | None = None, block_k: int | None = None,
               vmem_budget: int = VMEM_BUDGET) -> TilePlan:
    """``(block_q, block_k)`` of the forward, dq and dk/dv launches, from the
    shapes alone: for each launch the largest tile (by area) that divides
    both sequences, stays inside ``vmem_budget`` by :func:`launch_vmem_bytes`,
    and does not pass ``TILE_LADDER_TOP``, the size past which the ladder on the chip
    stopped paying (PERF.md, PR 28). A sequence no candidate fits gets its
    smallest candidate: there is always an answer, and it always divides.

    An explicit ``block_q`` / ``block_k`` pins that side for all three
    launches, as given (``min(block, s)``; it must divide, or ``ValueError``).
    ``n_kv_group`` is the grouped-query group: dk/dv sweeps it too, so its
    tile counts are per kv head times the group."""
    qs = _tile_sizes(s_q) if block_q is None else [min(block_q, s_q)]
    ks = _tile_sizes(s_k) if block_k is None else [min(block_k, s_k)]
    if s_q % qs[0] or s_k % ks[0]:  # only a pinned block can fail to divide
        raise ValueError(
            f"seq lengths ({s_q},{s_k}) must divide blocks ({block_q},{block_k})")
    plan = []
    for launch in TilePlan._fields:
        top_q, top_k = TILE_LADDER_TOP[launch]
        sized = [(launch_vmem_bytes(launch, bq, bk, d_pad, itemsize), bq, bk)
                 for bq in qs for bk in ks]
        # largest area first; of two equal areas the longer k block (fewer
        # steps of the forward's and dq's inner sweep)
        fits = [(bq * bk, bk, bq, need) for need, bq, bk in sized
                if need <= vmem_budget
                and (bq <= top_q or block_q is not None)
                and (bk <= top_k or block_k is not None)]
        if fits:
            _, bk, bq, need = max(fits)
        else:
            need, bq, bk = min(sized)
        live, grid = live_tiles(s_q, s_k, bq, bk, causal=causal, offset=offset)
        group = n_kv_group if launch == "dkv" else 1
        plan.append(LaunchTiles(bq, bk, need, live * group, grid * group))
    return TilePlan(*plan)


def live_tiles(s_q: int, s_k: int, block_q: int, block_k: int, *,
               causal: bool = True, offset: int | None = None) -> tuple[int, int]:
    """``(live, grid)`` tiles of one (batch, head): the tiles whose body runs
    and the grid steps paid. Closed form of the kernels' ``live`` predicate."""
    offset = s_k - s_q if offset is None else offset
    n_q, n_k = s_q // block_q, s_k // block_k
    if not causal:
        return n_q * n_k, n_q * n_k
    live = sum(
        min(max((i * block_q + block_q - 1 + offset) // block_k + 1, 0), n_k)
        for i in range(n_q))
    return live, n_q * n_k


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
        # the grid is dense, so a dead tile is predicated off, not skipped; it
        # costs its grid step and no copy (_kv_block repeats a live index)
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
    kj = functools.partial(_kv_block, causal=causal, block_q=block_q,
                           block_k=block_k, offset=offset, n_k=n_k)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k, causal=causal,
        offset=offset, use_alibi=slopes is not None,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), kj(i, j), 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), kj(i, j), 0)),
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
        **_vmem_params(launch_vmem_bytes("fwd", block_q, block_k, d, q.dtype.itemsize)),
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


def _bwd(scale, causal, dq_tile, dkv_tile, res, do, *, slopes=None, h_q=0,
         interpret=False):
    """``dq_tile`` / ``dkv_tile``: each launch's own ``(block_q, block_k)``."""
    q, k, v, o, lse = res
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    offset = s_k - s_q
    itemsize = q.dtype.itemsize
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

    block_q, block_k = dq_tile
    n_q = pl.cdiv(s_q, block_q)
    n_k = pl.cdiv(s_k, block_k)
    kj = functools.partial(_kv_block, causal=causal, block_q=block_q,
                           block_k=block_k, offset=offset, n_k=n_k)
    launch_dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
                          causal=causal, offset=offset, use_alibi=use_alibi),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),  # q
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), kj(i, j), 0)),  # k
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (kv(b), kj(i, j), 0)),  # v
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),  # do
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j: (b, 0, i)),  # lse
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j: (b, 0, i)),  # delta
        ] + slope_spec,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct((bh, s_q, d), q.dtype),
        interpret=interpret,
        **_vmem_params(launch_vmem_bytes("dq", block_q, block_k, d, itemsize)),
    )
    with _kernel_scope("flash_dq"):
        dq = launch_dq(q, k, v, do, lse_b, delta_b, *extra_inputs)

    # dkv grid rows are the kv STORAGE rows; the inner dim sweeps the
    # group's q heads × q blocks so each kv row accumulates its whole
    # gradient in one VMEM scratch pass (GQA-native: no repeated kv, no
    # cross-row reduction)
    block_q, block_k = dkv_tile
    n_q = pl.cdiv(s_q, block_q)
    n_k = pl.cdiv(s_k, block_k)

    def qrow(b, t):
        if group == 1:
            return b
        return (b // h_kv) * h_q + (b % h_kv) * group + t // n_q

    def qi(j, t):
        return _q_block(t % n_q, j, causal=causal, block_q=block_q,
                        block_k=block_k, offset=offset, n_q=n_q)

    launch_dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
                          causal=causal, offset=offset, use_alibi=use_alibi, n_q=n_q),
        grid=(bh_k, n_k, group * n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, t: (qrow(b, t), qi(j, t), 0)),  # q
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),  # k
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),  # v
            pl.BlockSpec((1, block_q, d), lambda b, j, t: (qrow(b, t), qi(j, t), 0)),  # do
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, j, t: (qrow(b, t), 0, qi(j, t))),  # lse
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, j, t: (qrow(b, t), 0, qi(j, t))),  # delta
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
        **_vmem_params(launch_vmem_bytes("dkv", block_q, block_k, d, itemsize)),
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
# ``tiles`` (static) is ``((block_q, block_k),) * 3`` for the forward, dq and
# dk/dv launches: ``TilePlan.blocks``.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, slopes, scale, causal, tiles, interpret, h_q=0):
    o, _ = _fwd(q, k, v, scale=scale, causal=causal, block_q=tiles[0][0],
                block_k=tiles[0][1], slopes=slopes, h_q=h_q, interpret=interpret)
    return o


def _flash_fwd(q, k, v, slopes, scale, causal, tiles, interpret, h_q=0):
    o, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=tiles[0][0],
                  block_k=tiles[0][1], slopes=slopes, h_q=h_q, interpret=interpret)
    return o, (q, k, v, o, lse, slopes)


def _flash_bwd(scale, causal, tiles, interpret, h_q, res, do):
    q, k, v, o, lse, slopes = res
    dq, dk, dv = _bwd(scale, causal, tiles[1], tiles[2], (q, k, v, o, lse), do,
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
    block_q: int | None = None, block_k: int | None = None,
    interpret: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Flash attention over ``[batch, seq, heads, d_head]`` inputs.

    ``block_q`` / ``block_k`` ``None`` (what the models pass) derives each
    launch's tile from the shapes (:func:`pick_tiles`); an explicit value is
    used as given for all three launches and must divide the sequence.

    Grouped-query attention is native: ``k``/``v`` may carry fewer heads
    than ``q`` (``h_q % h_kv == 0``) — the kernel index-maps each q head
    onto its kv group row, so the repeated-kv tensor is never materialized
    in HBM (fwd reads and bwd dk/dv are kv-row-major).

    ``alibi`` adds the per-head linear distance bias in-kernel. Slopes
    default to ``ops/attention.py:alibi_slopes(h)``; a head-sharded
    (tensor-parallel) caller passes ``alibi_slopes`` — its LOCAL [h] slice
    of the global slope table — so each shard biases with its true global
    head index (the in-kernel default would restart the slope sequence per
    shard). ``interpret`` runs the kernel in the Pallas interpreter (CPU);
    ``scale`` multiplies the scores before the softmax (``None``: 1/sqrt(d))."""
    b, s_q, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads ({h}) must be a multiple of kv heads ({h_kv})")
    if v.shape[2] != h_kv:
        # the kv row map is derived from k's width and applied to v — a
        # mismatch would silently read the wrong heads
        raise ValueError(f"k has {h_kv} heads but v has {v.shape[2]}")
    s_k = k.shape[1]
    scale = 1.0 / (d**0.5) if scale is None else float(scale)

    d_pad = lane_padded(d)
    tiles = pick_tiles(s_q, s_k, d_pad, q.dtype.itemsize, h // h_kv, causal=causal,
                       block_q=block_q, block_k=block_k).blocks

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
    ob = _flash(qb, kb, vb, slopes, scale, causal, tiles, interpret,
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
    block_q: int | None = None,
    block_k: int | None = None,
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
    scale = 1.0 / (d**0.5)
    d_pad = lane_padded(d)
    # only the forward is a kernel here (the backward recomputes through XLA)
    block_q, block_k = pick_tiles(
        s_q, s_k, d_pad, q.dtype.itemsize, causal=causal, offset=q_start - k_start,
        block_q=block_q, block_k=block_k).blocks[0]

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
