"""Mamba-2's state-space recurrence in its chunked form (SSD), and the causal
depthwise convolution that feeds it (and that a ``conv`` layer's gated short
convolution takes its taps from). Training only. The caller names the scopes
(``models/mpt.py``: ``mamba/conv``, ``mamba/scan``; ``shortconv/mix``).

Per head ``h`` (width ``P``) and position ``t``, with ``G`` groups of ``B_t``,
``C_t`` (width ``N`` each; head ``h`` reads group ``h // (H / G)``; one group
is shared by all heads), ``A_h = -exp(A_log_h)`` and ``dt`` already through
its softplus:

    H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T        (P x N, H_0 = 0)
    y_t = H_t C_t + D x_t

``B`` and ``C`` arrive as they lie behind the convolution, ``[B, S, G·N]``
with a group a block of ``N`` columns.

The program never walks positions. A row is cut into chunks of ``Q``
positions, walked in order with the state ``H`` at each chunk's start
carried in float32. Inside a chunk, with ``cs_t`` the running sum of ``dt A``
from the chunk's first position:

    y_intra = ((C B^T) o L) (dt x)        L_ts = exp(cs_t - cs_s), s <= t
    y_inter = exp(cs_t) (C_t H_start)
    H_end   = exp(cs_Q) H_start + sum_s exp(cs_Q - cs_s) dt_s x_s B_s^T

``dt``, the running sums, every decay and the carried state are float32; the
products take ``compute_dtype`` operands and accumulate in float32. A decay
is the exponential of a DIFFERENCE of running sums, held at or under zero
before the exponential, never a quotient of two exponentials.

:func:`ssd_scan` is that in two forms, chosen by what it can see
(:func:`uses_kernel`: the head's width, whole chunks of whole lanes, and
``ops/attention.py``'s rule for where a kernel can run).

**The launches** (a TPU, or anywhere under ``interpret``): a ``custom_vjp``
whose forward and backward are one Pallas launch each over a grid of (row,
block of ``HEAD_BLOCK`` heads, chunk; a block's heads all of one group, so
no more than a group has, and its ``B`` / ``C`` that group's columns), the
chunk axis innermost and in
order, the block's state ``[N, heads·P]`` float32 in VMEM scratch from chunk
to chunk. ``x`` and ``y`` are read and written where they lie, ``[B, S,
H·P]`` with a head a block of ``P`` columns (two heads a lane block of 128 at
``P`` 64). A step forms ``C B^T`` once for its heads, then walks its pairs of
heads in a loop whose body is traced once (a pair's columns a 128-lane slice
at a dynamic offset; unrolled, the body's eight copies cost every run 17 s of
tracing and lowering: PERF.md section 6, PR 51): each head's ``[Q, Q]`` decay
and masked square in VMEM (as strips of 128 rows against the columns at or
under them: the quarter over the diagonal is never made), and the three
products; nothing ``[Q, Q]`` reaches HBM. A pair's running sums and ``dt``
arrive as rows with the positions on lanes and are turned in the kernel where
a product wants them along sublanes. The forward keeps the chunks' start
states (``[chunks, N, H·P]`` float32, 67 MB a layer at 8,192 positions of 64
heads of 64 x 128) and ``y``; the backward walks the chunks from the last to
the first carrying ``dH``, rebuilds a head's decays transposed (``[s, t]``)
once and uses them twice (``dx`` through ``M^T dy``; ``d(C B^T)`` through
``(dt x) dy^T o L^T``), and gives ``dx``, one partial of ``dB`` and ``dC`` a
head block, and per position and head the two inner products the running
sums' gradient is made of:

    r_s    = <x_s, dU_s>            dU = d(dt x), through y_intra and H_end
    d cs_t = <dy_t, y_t - D x_t> - <dt_t x_t, du_t> - <w_t, dw_t>
    d dt_t = r_t + A (d cs summed from the chunk's end back to t
                      + cs_Q's own share: sum_s <w_s, dw_s> + exp(cs_Q) <H_start, dH_end>)

with ``du`` the part of ``dU`` through ``y_intra`` and ``w = dt x exp(cs_Q -
cs)`` what a position hands the end state (the row sums of ``dM o M`` are
``<dy_t, y_intra_t>`` and its column sums ``<dt_s x_s, du_s>``: no ``[Q, Q]``
reduction is made; every inner product takes the operand its matmul took,
rounded as it was, because ``d cs`` is a difference of these sums). The ``[B,
S, H]`` float32 arithmetic around the launches — ``dt A``, the running sum and
its transpose, the reverse running sum — stays ``jax.numpy`` (2 MB a layer).

**The walk** (every other shape: the tiny presets, ``init_params``' row of 8
tokens, the CPU backend without the interpreter): ``lax.scan`` over the
chunks, the body :func:`_chunk` in ``jax.numpy`` under ``jax.checkpoint``,
autodiff's backward. It is also the tests' second reference for the launches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_tpu.ops.flash_attention import LANE, VMEM_SLACK


def causal_conv1d(x: jax.Array, kernel: jax.Array,
                  bias: jax.Array | None = None) -> jax.Array:
    """Depthwise causal convolution over ``x [B, S, C]``: ``y_t = bias +
    sum_k kernel[k] * x_(t - W + 1 + k)`` with zeros before the row's start
    (``kernel [W, C]``; no ``bias``: the sum alone), as ``W`` shifted products
    in float32."""
    width, s = kernel.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    kernel = kernel.astype(jnp.float32)
    y = None if bias is None else bias.astype(jnp.float32)
    for k in range(width):
        tap = kernel[k] * padded[:, k:k + s]
        y = tap if y is None else y + tap
    return y


def _chunk(a_neg: jax.Array, compute_dtype, state: jax.Array, inputs):
    """One chunk: ``(H_start [B, H, P, N], (x [B, Q, H, P], dt [B, Q, H],
    b [B, Q, N], c [B, Q, N]))`` -> ``(H_end, y [B, H, Q, P] float32)``. ``y``
    leaves head-major, as the products make it: the scan then stacks whole
    slabs, and the caller turns the row once (a position-major ``y`` made the
    compiler lay the stack out with the chunk index inside, and every chunk's
    write a strided one: 85 ms of a 589 ms step on a v5e; PERF.md, PR 33)."""
    x, dt, b, c = inputs
    q = x.shape[1]
    f32 = jnp.float32
    cs = jnp.cumsum(dt * a_neg, axis=1)  # [B, Q, H], <= 0 and falling
    cs_h = jnp.swapaxes(cs, 1, 2)  # [B, H, Q]
    causal = jnp.tril(jnp.ones((q, q), bool))
    # masked before the exponential: above the diagonal the difference is
    # positive and may overflow
    decay = jnp.exp(jnp.where(causal, cs_h[..., :, None] - cs_h[..., None, :], -jnp.inf))
    scores = jnp.einsum("btn,bsn->bts", c, b, preferred_element_type=f32)
    x32 = x.astype(f32)
    x_dt = (x32 * dt[..., None]).astype(compute_dtype)
    y = jnp.einsum("bhts,bshp->bhtp", (scores[:, None] * decay).astype(compute_dtype),
                   x_dt, preferred_element_type=f32)
    y = y + jnp.exp(cs_h)[..., None] * jnp.einsum(
        "btn,bhpn->bhtp", c, state.astype(compute_dtype), preferred_element_type=f32)
    to_end = jnp.exp(cs[:, -1:] - cs)  # [B, Q, H]
    grown = jnp.einsum("bshp,bsn->bhpn",
                       (x32 * (dt * to_end)[..., None]).astype(compute_dtype), b,
                       preferred_element_type=f32)
    return state * jnp.exp(cs_h[..., -1])[..., None, None] + grown, y


def _walk(x, dt, a_log, b, c, d, chunk: int, compute_dtype, groups: int = 1) -> jax.Array:
    """:func:`ssd_scan` as ``lax.scan`` over the chunks, ``jax.numpy`` inside."""
    bsz, s, h, p = x.shape
    if groups > 1:  # a group with its run of heads is a scan of its own
        def group_first(t, axis):  # [.., groups * k, ..] at ``axis`` -> [groups, .., k, ..]
            return jnp.moveaxis(t.reshape(*t.shape[:axis], groups, -1, *t.shape[axis + 1:]), axis, 0)

        y = jax.vmap(lambda *of_group: _walk(*of_group, chunk, compute_dtype))(
            group_first(x, 2), group_first(dt, 2), group_first(a_log, 0),
            group_first(b, 2), group_first(c, 2), group_first(d, 0))
        return jnp.moveaxis(y, 0, 2).reshape(x.shape)
    n = b.shape[-1]
    a_neg = -jnp.exp(a_log.astype(jnp.float32))

    def by_chunk(t):  # [B, S, ...] -> [chunks, B, Q, ...]
        return jnp.moveaxis(t.reshape(bsz, s // chunk, chunk, *t.shape[2:]), 1, 0)

    step = jax.checkpoint(functools.partial(_chunk, a_neg, compute_dtype))
    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, n), jnp.float32),
        (by_chunk(x.astype(compute_dtype)), by_chunk(dt),
         by_chunk(b.astype(compute_dtype)), by_chunk(c.astype(compute_dtype))))
    y = jnp.transpose(y, (1, 0, 3, 2, 4)).reshape(bsz, s, h, p)  # [chunks, B, H, Q, P]
    return y + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)


# ---------------------------------------------------------------------------
# the launches
# ---------------------------------------------------------------------------

#: heads of a launch's block: ``[Q, HEAD_BLOCK·P]`` of ``x`` beside the
#: block's state ``[N, HEAD_BLOCK·P]`` float32. On the chip
#: (``scripts/ssd_scan_ladder.py`` at the cell's shapes; PERF.md section 6,
#: PR 51) 8 / 16 / 32 heads took 0.892 / 0.834 / 0.814 ms forward and 2.145 /
#: 2.049 / 1.996 forward + backward (the walk 3.103 / 6.405): 32 is 2.6 % faster
#: alone and doubles what a launch holds in VMEM
HEAD_BLOCK = 16
#: rows of a strip of a head's ``[Q, Q]`` square: a strip meets the columns at
#: or under its last row (over its first, transposed), so a chunk of 256 makes
#: three quarters of the square, as ``[STRIP, LANE]`` tiles
STRIP = LANE
#: the rows of a pair of heads in the ``[B, blocks, chunks, pairs, 8, Q]``
#: float32 arrays the launches read and write beside ``x``, the positions on
#: lanes (eight rows: a whole sublane tile, so a pair is a leading index).
#: Read: either head's running sum ``cs``, either head's ``dt``. Written by
#: the backward: either head's ``<x, d(dt x)>``, either head's ``d cs``
_ROWS = 8


def _head_block(heads: int, groups: int = 1) -> int:
    """Heads a block: ``HEAD_BLOCK``, or the most that divides the heads of
    one group (all ``heads`` where there is one): a block reads one group's
    ``B`` and ``C``."""
    heads //= groups
    block = min(HEAD_BLOCK, heads)
    while heads % block:
        block -= 2
    return block


def uses_kernel(impl: str, interpret: bool, seq: int, chunk: int, heads: int, d_head: int,
                d_state: int, x: jax.Array | None = None, groups: int = 1) -> bool:
    """Whether a scan over rows of ``seq`` positions takes the Pallas launches:
    by the shape (a group's heads in pairs that fill a lane block, chunks and
    states of whole lane blocks) and by ``ops/attention.py``'s rule for where a
    kernel can run (``pallas`` on a TPU or anywhere under ``interpret``)."""
    # looked up at the call: the offline compile check swaps the function
    from photon_tpu.ops import flash_attention

    return (impl == "pallas" and 2 * d_head == LANE and heads % (2 * groups) == 0
            and chunk % STRIP == 0 and seq % chunk == 0 and d_state % LANE == 0
            and (interpret or flash_attention.pallas_supported(x)))


def _nt(a, b):
    """``a [m, k] b[n, k]^T``, float32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    """``a[k, m]^T b [k, n]``, float32."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def _lanes(pair):
    """A pair of heads' columns of a ``[.., heads·P]`` block: the first head's
    ``P`` lanes, then the second's."""
    return pl.ds(pl.multiple_of(pair * LANE, LANE), LANE)


class _Strip:
    """What both launches make of a strip of ``STRIP`` positions of a pair of
    heads: the ``[STRIP, LANE]`` float32 tiles a position's numbers take when
    every lane of a head holds them. ``numbers`` are the pair's ``[8, Q]`` rows
    (positions on lanes); a tile is a row along sublanes, turned."""

    def __init__(self, numbers, last_row, r0: int, p: int):
        def down(row):  # one of the rows on every sublane: [LANE, STRIP], to be turned
            return jnp.broadcast_to(numbers[row:row + 1, r0:r0 + STRIP], (LANE, STRIP))

        first_rows = jax.lax.broadcasted_iota(jnp.int32, (LANE, STRIP), 0) < p
        #: the pair's first head, by lane
        self.first = jax.lax.broadcasted_iota(jnp.int32, (STRIP, LANE), 1) < p
        #: ``cs`` of either head on every lane: a column of its square's differences
        self.cs_of = [down(0).T, down(1).T]
        self.cs = jnp.where(self.first, *self.cs_of)
        self.dt = jnp.where(first_rows, down(2), down(3)).T
        self.to_end = jnp.exp(last_row - self.cs)  # exp(cs_Q - cs)

    def halves(self, t):
        """``t`` with the other head's lanes zeroed, for either head."""
        zero = jnp.zeros_like(t)
        return jnp.where(self.first, t, zero), jnp.where(self.first, zero, t)


def _forward_kernel(x_ref, pairs_ref, b_ref, c_ref, last_ref, d_ref, y_ref, *rest,
                    heads: int, p: int, keep_states: bool):
    """One chunk of one block of heads: ``y``, and the state carried on."""
    states_ref, state, masked, read_out, grown, x_dt_of = (
        rest if keep_states else (None, *rest))
    q = x_ref.shape[1]
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if keep_states:
        states_ref[0, 0] = state[...]  # the chunk's start state, for the backward
    cb, bb = c_ref[0], b_ref[0]
    compute = cb.dtype
    t_pos = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    s_pos = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    masked[...] = jnp.where(s_pos <= t_pos, _nt(cb, bb), 0.0)  # (C B^T)_ts, s <= t
    read_out[...] = _nn(cb, state[...].astype(compute))  # C H_start, every head of the block

    def pair_of_heads(pair, carry):
        lanes = _lanes(pair)
        numbers = pairs_ref[0, 0, 0, pair]
        for r0 in range(0, q, STRIP):
            rows = slice(r0, r0 + STRIP)
            at = _Strip(numbers, last_ref[0, 0, :, lanes], r0, p)
            x32 = x_ref[0, rows, lanes].astype(f32)
            for i, half in enumerate(at.halves((x32 * at.dt).astype(compute))):
                x_dt_of[i, rows] = half
            squares, operands = [], []
            for i in range(2):
                for c0 in range(0, r0 + STRIP, LANE):  # the columns at or under the strip
                    # cs_t - cs_s, at or under zero where s <= t; held there
                    # above the diagonal too, where `masked` is zero
                    diff = at.cs_of[i] - numbers[i:i + 1, c0:c0 + LANE]
                    squares.append((masked[rows, c0:c0 + LANE]
                                    * jnp.exp(jnp.minimum(diff, 0.0))).astype(compute))
                    operands.append(x_dt_of[i, c0:c0 + LANE])
            y_ref[0, rows, lanes] = (
                _nn(jnp.concatenate(squares, axis=1), jnp.concatenate(operands, axis=0))
                + jnp.exp(at.cs) * read_out[rows, lanes] + d_ref[:, lanes] * x32)
            grown[rows, lanes] = (x32 * (at.dt * at.to_end)).astype(compute)
        return carry

    jax.lax.fori_loop(0, heads // 2, pair_of_heads, 0)
    state[...] = jnp.exp(last_ref[0, 0]) * state[...] + _tn(bb, grown[...])


def _backward_kernel(x_ref, dy_ref, y_ref, pairs_ref, b_ref, c_ref, last_ref, d_ref,
                     states_ref, dx_ref, db_ref, dc_ref, dots_ref, dd_ref, ends_ref,
                     d_state, masked, d_scores, d_grown, grown, read_out, dy_of,
                     *, heads: int, p: int):
    """The same chunk, walked from the row's last: ``d_state`` arrives as the
    gradient of the chunk's END state and leaves as that of its start."""
    q = x_ref.shape[1]
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state[...] = jnp.zeros_like(d_state)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    cb, bb = c_ref[0], b_ref[0]
    compute = cb.dtype
    start = states_ref[0, 0]
    d_end = d_state[...].astype(compute)
    s_pos = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    t_pos = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    live = s_pos <= t_pos
    masked[...] = jnp.where(live, _nt(bb, cb), 0.0)  # (C B^T)_ts at [s, t], s <= t
    d_scores[...] = jnp.zeros_like(d_scores)
    d_grown[...] = _nn(bb, d_end)  # B_s dH_end: the gradient of dt x exp(cs_Q - cs)
    ends_ref[...] = jnp.zeros_like(ends_ref)
    dots_ref[...] = jnp.zeros_like(dots_ref)

    def dy_of_a_pair(pair, carry):  # dy in the products' dtype: a head's lanes, and whole
        lanes = _lanes(pair)
        dy_c = dy_ref[0, :, lanes].astype(compute)
        zero = jnp.zeros_like(dy_c)
        first = jax.lax.broadcasted_iota(jnp.int32, dy_c.shape, 1) < p
        dy_of[0, :, lanes] = jnp.where(first, dy_c, zero)
        dy_of[1, :, lanes] = jnp.where(first, zero, dy_c)
        dy_of[2, :, lanes] = dy_c
        return carry

    jax.lax.fori_loop(0, heads // 2, dy_of_a_pair, 0)

    def pair_of_heads(pair, carry):
        lanes = _lanes(pair)
        numbers = pairs_ref[0, 0, 0, pair]
        for r0 in range(0, q, STRIP):
            rows = slice(r0, r0 + STRIP)
            at = _Strip(numbers, last_ref[0, 0, :, lanes], r0, p)
            x32 = x_ref[0, rows, lanes].astype(f32)
            dy = dy_ref[0, rows, lanes]
            x_dt = (x32 * at.dt).astype(compute)
            squares, operands = [], []
            for i, x_dt_half in enumerate(at.halves(x_dt)):
                for c0 in range(r0, q, LANE):  # the t at or after the strip's first s
                    diff = numbers[i:i + 1, c0:c0 + LANE] - at.cs_of[i]  # cs_t - cs_s
                    decay = jnp.exp(jnp.minimum(diff, 0.0))
                    squares.append((masked[rows, c0:c0 + LANE] * decay).astype(compute))
                    operands.append(dy_of[i, c0:c0 + LANE, lanes])
                    d_scores[rows, c0:c0 + LANE] += _nt(
                        x_dt_half, dy_of[2, c0:c0 + LANE, lanes]) * decay
            # d(dt x): through y_intra, and through the chunk's end state
            d_intra = _nn(jnp.concatenate(squares, axis=1), jnp.concatenate(operands, axis=0))
            d_w = d_grown[rows, lanes]
            d_u = d_intra + at.to_end * d_w
            skip = d_ref[:, lanes]
            dx_ref[0, rows, lanes] = (at.dt * d_u + skip * dy).astype(dx_ref.dtype)
            grown_c = (x32 * (at.dt * at.to_end)).astype(compute)
            handed_on = grown_c.astype(f32) * d_w
            # every product against the operand its matmul took, rounded as it
            # was: the running sums' gradient is a difference of these sums
            sums = (x32 * d_u,
                    dy_of[2, rows, lanes].astype(f32) * (y_ref[0, rows, lanes] - skip * x32)
                    - x_dt.astype(f32) * d_intra - handed_on)
            for k, by_lane in enumerate(sums):  # a head's lanes summed: turned, then by sublane
                turned = by_lane.T
                for i in range(2):
                    dots_ref[0, 0, 0, pair, 2 * k + i:2 * k + i + 1, rows] = jnp.sum(
                        turned[i * p:(i + 1) * p], axis=0, keepdims=True)
            dd_ref[0, :, lanes] += jnp.sum(dy * x32, axis=0, keepdims=True)
            ends_ref[0, 0, :, lanes] += jnp.sum(handed_on, axis=0, keepdims=True)
            grown[rows, lanes] = grown_c
            read_out[rows, lanes] = (dy * jnp.exp(at.cs)).astype(compute)  # d(C H_start)
        return carry

    jax.lax.fori_loop(0, heads // 2, pair_of_heads, 0)
    d_sc = jnp.where(live, d_scores[...], 0.0).astype(compute)  # d(C B^T)_ts at [s, t]
    db_ref[0, 0] = _nn(d_sc, cb) + _nt(grown[...], d_end)
    dc_ref[0, 0] = _tn(d_sc, bb) + _nt(read_out[...], start.astype(compute))
    carried = jnp.exp(last_ref[0, 0]) * d_state[...]
    # cs_Q's own share, a lane a (head, p): what the positions hand the end
    # state (each takes it back at its own position: ``dots``), and
    # exp(cs_Q) <H_start, dH_end>
    ends_ref[0, 0] += jnp.sum(start * carried, axis=0, keepdims=True)
    d_state[...] = carried + _tn(cb, read_out[...])


def _by_pair(cs: jax.Array, dt: jax.Array, block: int) -> jax.Array:
    """Two ``[B, chunks, Q, H]`` arrays -> ``[B, H / block, chunks, block / 2,
    8, Q]``: a pair of heads' rows, either head's ``cs``, then either head's
    ``dt``, then zeros."""
    bsz, chunks, q, h = cs.shape

    def pairs(t):  # -> [B, blocks, chunks, pairs, 2, Q]
        t = jnp.swapaxes(t, 2, 3).reshape(bsz, chunks, h // block, block // 2, 2, q)
        return jnp.swapaxes(t, 1, 2)

    both = jnp.concatenate([pairs(cs), pairs(dt)], axis=4)
    return jnp.pad(both, [(0, 0)] * 4 + [(0, _ROWS - 4), (0, 0)])


def _beside(dt: jax.Array, a_neg: jax.Array, d: jax.Array, chunk: int, block: int, p: int):
    """What the launches read beside ``x``, ``B`` and ``C``, from the float32
    ``[B, S, H]`` arithmetic before them: ``(pairs [B, blocks, chunks, block /
    2, 8, Q], last [B, chunks, 1, H·P], skip [1, H·P])``: a pair of heads' rows
    of the running sum of ``dt A`` within a chunk and of ``dt``; ``cs_Q`` a
    chunk, and ``D``, on every lane of the head."""
    bsz, s, h = dt.shape
    by_chunk = dt.reshape(bsz, s // chunk, chunk, h)
    cs = jnp.cumsum(by_chunk * a_neg, axis=2)
    return (_by_pair(cs, by_chunk, block), jnp.repeat(cs[:, :, -1:], p, axis=-1),
            jnp.repeat(d, p)[None])


def _specs(bsz: int, s: int, h: int, p: int, n: int, chunk: int, block: int, backward: bool,
           groups: int = 1):
    """Block specs by name over the grid (row, head block, chunk); the
    backward's chunk index counts from the row's last. A head block reads its
    group's ``n`` columns of ``B`` and ``C`` ``[B, S, G·n]``."""
    chunks = s // chunk
    at = (lambda c: chunks - 1 - c) if backward else (lambda c: c)
    width = block * p
    per_group = h // groups // block  # head blocks a group
    group_of = (lambda j: 0) if groups == 1 else (lambda j: j // per_group)
    return {
        "wide": pl.BlockSpec((1, chunk, width), lambda i, j, c: (i, at(c), j)),
        "pairs": pl.BlockSpec((1, 1, 1, block // 2, _ROWS, chunk),
                              lambda i, j, c: (i, j, at(c), 0, 0, 0)),
        "bc": pl.BlockSpec((1, chunk, n), lambda i, j, c: (i, at(c), group_of(j))),
        "lanes": pl.BlockSpec((1, 1, 1, width), lambda i, j, c: (i, at(c), 0, j)),
        "skip": pl.BlockSpec((1, width), lambda i, j, c: (0, j)),
        "states": pl.BlockSpec((1, 1, n, width), lambda i, j, c: (i, at(c), 0, j)),
        "partial": pl.BlockSpec((1, 1, chunk, n), lambda i, j, c: (i, j, at(c), 0)),
        "dd": pl.BlockSpec((1, 1, width), lambda i, j, c: (i, 0, j)),
    }


def _params(piped: int, scratch: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        # every piped block twice (the pipeline's two buffers) and the
        # scratch; the compiler's own stack is under 2 MiB at the cell's
        # shapes (the backward compiles at + 2 and not at + 1). No more than
        # it needs: what a launch reserves, XLA cannot prefetch into around it
        vmem_limit_bytes=2 * piped + scratch + 4 * VMEM_SLACK)


def _forward(x, dt, a_neg, b, c, d, chunk: int, keep_states: bool, interpret: bool,
             groups: int = 1):
    """``y [B, S, H·P]`` float32 and, where kept, the chunks' start states
    ``[B, chunks, N, H·P]`` float32, from ``x [B, S, H·P]``."""
    bsz, s, h = dt.shape
    p, n = x.shape[-1] // h, b.shape[-1] // groups
    block = _head_block(h, groups)
    width, chunks = block * p, s // chunk
    spec = _specs(bsz, s, h, p, n, chunk, block, backward=False, groups=groups)
    pairs, last, skip = _beside(dt, a_neg, d, chunk, block, p)
    item = x.dtype.itemsize
    piped = (chunk * width * (item + 4) + block // 2 * _ROWS * chunk * 4
             + 2 * chunk * n * item + (n * width * 4 if keep_states else 0))
    scratch = (n * width * 4 + chunk * chunk * 4 + chunk * width * (4 + item)
               + 2 * chunk * LANE * item)
    out = pl.pallas_call(
        functools.partial(_forward_kernel, heads=block, p=p, keep_states=keep_states),
        grid=(bsz, h // block, chunks),
        in_specs=[spec[k] for k in ("wide", "pairs", "bc", "bc", "lanes", "skip")],
        out_specs=[spec["wide"]] + ([spec["states"]] if keep_states else []),
        out_shape=[jax.ShapeDtypeStruct((bsz, s, h * p), jnp.float32)] + (
            [jax.ShapeDtypeStruct((bsz, chunks, n, h * p), jnp.float32)] if keep_states else []),
        scratch_shapes=[pltpu.VMEM((n, width), jnp.float32),  # the state
                        pltpu.VMEM((chunk, chunk), jnp.float32),  # C B^T under the diagonal
                        pltpu.VMEM((chunk, width), jnp.float32),  # C H_start
                        pltpu.VMEM((chunk, width), x.dtype),  # dt x exp(cs_Q - cs)
                        pltpu.VMEM((2, chunk, LANE), x.dtype)],  # a pair's dt x, a head each
        compiler_params=_params(piped, scratch),
        interpret=interpret,
        name="ssd_scan_fwd",
    )(x, pairs, b, c, last, skip)
    return out if keep_states else out[0]


def _backward(x, dt, a_neg, b, c, d, y, states, dy, chunk: int, interpret: bool,
              groups: int = 1):
    """The gradients of ``x`` (in its dtype), ``dt``, ``a_neg``, ``b`` and ``c``
    (float32) and ``d`` from ``dy [B, S, H·P]`` float32."""
    bsz, s, h = dt.shape
    p, n = x.shape[-1] // h, b.shape[-1] // groups
    block = _head_block(h, groups)
    width, chunks, blocks = block * p, s // chunk, h // block
    spec = _specs(bsz, s, h, p, n, chunk, block, backward=True, groups=groups)
    pairs, last, skip = _beside(dt, a_neg, d, chunk, block, p)
    item = x.dtype.itemsize
    f32 = jnp.float32
    piped = (chunk * width * (2 * item + 8) + 2 * block // 2 * _ROWS * chunk * 4
             + 2 * chunk * n * (item + 4) + n * width * 4 + 3 * width * 4)
    scratch = n * width * 4 + 2 * chunk * chunk * 4 + chunk * width * (4 + 5 * item)
    dx, db, dc, dots, dd, ends = pl.pallas_call(
        functools.partial(_backward_kernel, heads=block, p=p),
        grid=(bsz, blocks, chunks),
        in_specs=[spec[k] for k in ("wide", "wide", "wide", "pairs", "bc", "bc", "lanes",
                                    "skip", "states")],
        out_specs=[spec[k] for k in ("wide", "partial", "partial", "pairs", "dd", "lanes")],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, blocks, s, n), f32),
                   jax.ShapeDtypeStruct((bsz, blocks, s, n), f32),
                   jax.ShapeDtypeStruct(pairs.shape, f32),
                   jax.ShapeDtypeStruct((bsz, 1, h * p), f32),
                   jax.ShapeDtypeStruct((bsz, chunks, 1, h * p), f32)],
        scratch_shapes=[pltpu.VMEM((n, width), f32),  # dH
                        pltpu.VMEM((chunk, chunk), f32),  # C B^T at [s, t], s <= t
                        pltpu.VMEM((chunk, chunk), f32),  # its gradient, every head's
                        pltpu.VMEM((chunk, width), f32),  # B dH_end
                        pltpu.VMEM((chunk, width), x.dtype),  # dt x exp(cs_Q - cs)
                        pltpu.VMEM((chunk, width), x.dtype),  # d(C H_start)
                        pltpu.VMEM((3, chunk, width), x.dtype)],  # dy: a head each, whole
        compiler_params=_params(piped, scratch),
        interpret=interpret,
        name="ssd_scan_bwd",
    )(x, dy, y, pairs, b, c, last, skip, states)

    def by_head(first: int):  # two of a pair's rows -> [B, chunks, Q, H]
        # -> [B, chunks, blocks, pairs, 2, Q]
        t = jnp.swapaxes(dots[:, :, :, :, first:first + 2], 1, 2)
        return jnp.swapaxes(t.reshape(bsz, chunks, h, chunk), 2, 3)

    by_x, d_cs = by_head(0), by_head(2)
    # dt_s A reaches the decays from a position before s to one at or after it
    # (the running sums' gradient summed from the chunk's end back to s), and
    # through cs_Q what the chunk's end state is handed
    d_a = (jnp.flip(jnp.cumsum(jnp.flip(d_cs, 2), axis=2), 2)
           + jnp.sum(ends.reshape(bsz, chunks, 1, h, p), axis=-1)).reshape(bsz, s, h)
    by_x = by_x.reshape(bsz, s, h)

    def of_groups(partial):  # a head block's partial [B, blocks, S, N] -> [B, S, G·N]
        if groups == 1:
            return jnp.sum(partial, axis=1)
        by_group = jnp.sum(partial.reshape(bsz, groups, blocks // groups, s, n), axis=2)
        return jnp.swapaxes(by_group, 1, 2).reshape(bsz, s, groups * n)

    return (dx, by_x + a_neg * d_a, jnp.sum(dt * d_a, axis=(0, 1)), of_groups(db),
            of_groups(dc), jnp.sum(dd.reshape(bsz, h, p), axis=(0, 2)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _launches(x, dt, a_log, b, c, d, chunk: int, interpret: bool, groups: int = 1):
    return _forward(x, dt, -jnp.exp(a_log), b, c, d, chunk, False, interpret, groups)


def _launches_fwd(x, dt, a_log, b, c, d, chunk: int, interpret: bool, groups: int):
    y, states = _forward(x, dt, -jnp.exp(a_log), b, c, d, chunk, True, interpret, groups)
    return y, (x, dt, a_log, b, c, d, y, states)


def _launches_bwd(chunk: int, interpret: bool, groups: int, residuals, dy):
    # (traced under the scopes of the call it pulls back: the caller's
    # ``mamba/scan`` holds these operations too)
    x, dt, a_log, b, c, d, y, states = residuals
    a_neg = -jnp.exp(a_log)
    dx, d_dt, d_a_neg, db, dc, dd = _backward(x, dt, a_neg, b, c, d, y, states, dy, chunk,
                                              interpret, groups)
    return dx, d_dt, d_a_neg * a_neg, db.astype(b.dtype), dc.astype(c.dtype), dd


_launches.defvjp(_launches_fwd, _launches_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, a_log: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, *, chunk: int,
             compute_dtype=jnp.bfloat16, impl: str = "xla",
             interpret: bool = False, groups: int = 1) -> jax.Array:
    """``y [B, S, H, P]`` float32 of the recurrence above over ``x [B, S, H,
    P]``, ``dt [B, S, H]`` (positive, float32), ``a_log [H]``, ``b``, ``c``
    ``[B, S, groups·N]`` (a group a block of ``N`` columns, for its ``H /
    groups`` heads) and the skip weight ``d [H]``. ``S`` is a multiple of
    ``chunk``; every row starts from a zero state. ``impl`` / ``interpret``
    are ``ops/attention.py``'s: with ``pallas`` the shapes :func:`uses_kernel`
    admits take the launches."""
    bsz, s, h, p = x.shape
    if s % chunk:
        raise ValueError(f"sequence {s} is not a multiple of the scan's chunk {chunk}")
    dt = dt.astype(jnp.float32)
    if h % groups or b.shape[-1] % groups:
        raise ValueError(f"{groups} groups do not divide {h} heads and B's {b.shape[-1]} columns")
    if not uses_kernel(impl, interpret, s, chunk, h, p, b.shape[-1] // groups, x, groups):
        return _walk(x, dt, a_log, b, c, d, chunk, compute_dtype, groups)

    def scan(x, dt, a_log, b, c, d):
        f32 = jnp.float32
        y = _launches(x.astype(compute_dtype).reshape(*x.shape[:2], h * p), dt, a_log.astype(f32),
                      b.astype(compute_dtype), c.astype(compute_dtype), d.astype(f32),
                      chunk, interpret, groups)
        return y.reshape(x.shape)

    # a Mosaic launch cannot be partitioned by GSPMD: on a mesh each shard of
    # rows (data, fsdp, expert) runs its own, which is exact for a scan along
    # the row (``schema.py`` refuses `mamba` layers under tensor or sequence)
    from photon_tpu.parallel.context import current_mesh

    mesh = current_mesh()
    if mesh is not None and any(mesh.shape.get(a, 1) > 1 for a in ("data", "fsdp", "expert")):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        rows, whole = P(("data", "fsdp", "expert")), P()
        scan = shard_map(scan, mesh=mesh, in_specs=(rows, rows, whole, rows, rows, whole),
                         out_specs=rows, check_vma=False)
    return scan(x, dt, a_log, b, c, d)
