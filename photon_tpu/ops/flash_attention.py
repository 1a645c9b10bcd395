"""Blockwise flash attention for TPU, in Pallas.

Replaces the reference's CUDA flash-attention dependency
(``attn_impl: flash``, ``conf/llm_config/mpt-125m.yaml:27-28``,
``README.md:96-100``) with an MXU-tiled, online-softmax kernel.

Design notes (TPU-first):
- Grid is ``(batch*heads, q_blocks, k_blocks)``; the innermost k dimension is
  executed sequentially per core, so the online-softmax running state
  ``(m, l, acc)`` lives in VMEM scratch and persists across k iterations.
- The launches read the projections' own arrays where the head widths let
  them (``flash_layout``, a rule by widths alone; no knob). ``[B, S, H, D] ->
  [B, S, H·D]`` moves no data, and a head is a COLUMN BLOCK of that array:
  ``(1, block, D)`` at ``(b, i, h)`` for q, o, dO, dq and at ``(b, j, h //
  group)`` for k, v, dk, dv, legal for Mosaic where D is whole lanes
  (``IN_PLACE``). The grid's first axis is still ``batch*heads``; the index
  maps decode it (``_block_at``). At D 64 a 128-lane column block is two
  neighbouring heads, and with as many k / v heads as q heads the grid's
  first axis is ``batch * heads / 2`` and each step runs both heads of its
  PAIR (``HEAD_PAIRS``): a head's strip of q (dO; in dk/dv, of k and v) with
  the other head's 64 lanes at zero (``_own_lanes``) stands where the zero
  pad stood, so a contraction over the 128 lanes is the lone padded head's
  bit for bit, and a product whose outputs are the 128 lanes has the head's
  own columns in its own lanes, which the body keeps (``_by_head``). The MXU
  does the work it did on a padded head; HBM reads of q, k, v, dO halve.
  What the backward saves is then q, k, v and o as the projections and
  ``out_proj`` hold them: no transposed, padded copy. Everything else (a
  width that is not whole lanes, as 192; grouped heads at 64, where a q pair
  and its kv head sit in different halves of different blocks;
  ``flash_attention_with_lse``) keeps ``HEAD_MAJOR``: ``to_bh``'s
  ``[B·H, S, lane_padded(D)]`` copies, blocks ``(1, block, D_pad)`` at
  ``(bh, i, 0)``. lse and delta are small ``[rows, 8, S]`` float32 arrays in
  every layout (a pair's two rows in the halves of the eight sublanes).
- The tile is a function of the shapes, not a default: ``pick_tiles`` gives
  each of the three launches the largest ``(block_q, block_k)`` that divides
  the sequences, fits the VMEM budget by ``launch_vmem_bytes`` (an estimate
  from the kernel's own buffers) and the ladder on the chip showed no slower
  than the next smaller; a launch whose estimate passes what Mosaic gives
  unasked asks for it (``vmem_limit_bytes``). Callers pass ``block_q`` /
  ``block_k`` only to pin a tile (tests, the ladder).
- A causal launch's grid is dense over the causal square, so a tile strictly
  above the diagonal still costs its grid step, but nothing else: its body is
  predicated off (``_run_tile``) and its k/v (forward, dq) or
  q/dO/lse/delta (dk/dv) block index is clamped to the nearest live one
  (``_kv_block``, ``_q_block``), so the pipeline sees a repeated index and
  issues no copy.
- A windowed launch (``window``: key ``j`` is live for query ``i`` iff ``i -
  window < j <= i``) walks the BAND and not the square (``_Band``): the inner
  axis of the forward's and dq's grid has only as many steps as k tiles can
  meet one q tile's band, and the index maps place step ``j`` at the band's
  first tile for that q tile (clamped at 0; a step past the diagonal repeats
  the last live index and is predicated off); the dk/dv launch walks the q
  tiles of one k tile's band likewise. A tile wholly inside the band runs
  unmasked, the tile on the diagonal as the causal launch's strips (where a
  tile is no wider than the window), the tile on the band's lower edge under
  the second bound alone (as the diagonal's strips mirrored, ``_edge_strips``,
  where the window is a whole number of tiles), and only a tile that both
  bounds cut under both.
  Which of those bodies a launch holds is static (``_Band.cases``). The
  windowed launches carry their own names (``flash_swa_fwd``,
  ``flash_swa_dq``, ``flash_swa_dkv``); ``window=None`` is the causal
  program, instruction for instruction, and so is a window that holds the
  whole sequence.
- A live tile multiplies no block of scores that lies wholly above the
  diagonal. The tiles are large because a grid step and an online-softmax
  update cost more than a small tile's products, so on the diagonal half of
  a tile is dead; but the whole tile is in VMEM and which part is dead is
  static. Where the geometry is (``strip_rows``: causal, a square tile, an
  offset of whole tiles: every training shape), a tile under the diagonal
  runs whole with no mask, and the tile on it runs as statically unrolled
  strips of ``STRIP_ROWS`` queries (forward, dq) or keys (dk/dv), each
  against the live extent of the other axis and masked in its last square
  block alone. A forward strip is one plain softmax over its extent, merged
  into the scratch once; where that tile holds the whole sequence
  (``_lone_tile``: seq 2,048) nothing is carried from step to step, so the
  forward takes no scratch and each strip writes its output rows and
  log-sum-exp (the running max / denominator's column reads and broadcast
  stores were a quarter of that launch's schedule). Anything else (non-causal, a pinned tile that is
  not square, an offset that is no whole number of tiles) runs every live
  tile whole under the mask. ``STRIP_ROWS`` is read off the same ladder as
  the tiles; ``TilePlan.attrs()`` tells ``flash_executed_share``, the pairs
  multiplied over the pairs visible.
- Scores accumulate in fp32 on the MXU (``preferred_element_type``); inputs
  are bf16. The log-sum-exp is saved for the backward pass.
- Blockwise structure means a ring/context-parallel extension only has to
  rotate k/v blocks between chips — the inner kernel is unchanged
  (SURVEY.md §5 long-context note).
- Under ``HEAD_MAJOR`` ``d_head`` is zero-padded to the 128-lane width when
  smaller (padding columns contribute nothing to scores or outputs).
- v may be narrower (or wider) than q and k (latent attention: q/k 192, v
  128): v, o, dO, dv and the output accumulator then take v's own padded
  width, q, k, dq and dk theirs, and ``launch_vmem_bytes`` / ``pick_tiles``
  reckon both; nothing is padded from one width to the other in HBM.

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

from photon_tpu.utils.profiling import (
    FLASH_SWA_DKV_KERNEL,
    FLASH_SWA_DQ_KERNEL,
    FLASH_SWA_FWD_KERNEL,
)

LANE = 128
SUBLANE = 8  # fp32 sublane height; lse/delta carry 8 redundant rows for tiling


def lane_padded(d: int) -> int:
    """``d_head`` as the kernels see it: zero-padded up to whole lane widths."""
    return -(-d // LANE) * LANE


# Where the launches find a head (``flash_layout``; the span attribute
# ``flash_layout`` on ``trainer/steps`` says which one a step's launches took).
IN_PLACE = "in_place"  # [B, S, H·D] as the projections wrote it, a head a column block
HEAD_PAIRS = "head_pairs"  # the same arrays at D 64: a 128-lane column block is two heads
HEAD_MAJOR = "head_major"  # [B·H, S, lane_padded(D)] copies made by ``to_bh``
PAIR_WIDTH = LANE // 2  # the head width two of which fill a column block


def flash_layout(h_q: int, h_kv: int, d: int, d_v: int) -> str:
    """The layout ``flash_attention``'s launches read for these heads, from
    the widths alone. ``[B, S, H, D] -> [B, S, H·D]`` moves no data, and a
    ``(block, D)`` column block of that array is a legal Mosaic block where D
    is whole lanes: such heads are read and written ``IN_PLACE``. At D 64 a
    128-lane column block holds two neighbouring heads; where q, k and v all
    have the same even number of such heads (so that a q pair's k and v are
    one block too) the launches take ``HEAD_PAIRS``. Every other shape (a
    width that is not whole lanes, grouped heads at 64) keeps ``HEAD_MAJOR``:
    transposed and padded copies."""
    if d % LANE == 0 and d_v % LANE == 0:
        return IN_PLACE
    if d == d_v == PAIR_WIDTH and h_q == h_kv and h_q % 2 == 0:
        return HEAD_PAIRS
    return HEAD_MAJOR


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
BAND_SCORE_TEMPS = 0.5  # more of them in a windowed launch: the second bound's select
# The largest (block_q, block_k) at which each launch was still no slower than
# at the next smaller tile, on the ladder ``scripts/flash_tile_ladder.py`` runs
# on the chip (v5e; PERF.md PR 28, read again with the strip bodies in PR 39 at
# seq 2,048 / d_head 64 and seq 4,096 / d_head 256). With the dead part of a
# diagonal tile gone every launch is fastest with the whole 2,048 square in
# one step at the width it was read at (``TILE_LADDER_WIDTH``: the forward up
# to 256 lanes, dq and dk/dv at 128); a wider head gets proportionally fewer
# rows (at 256 lanes dq and dk/dv peak at 1,024 and 2,048 does not fit).
TILE_LADDER_TOP = {"fwd": (2048, 2048), "dq": (2048, 2048), "dkv": (2048, 2048)}
TILE_LADDER_WIDTH = {"fwd": 256, "dq": 128, "dkv": 128}
# Rows of a strip: inside a tile that straddles the diagonal a launch runs
# strips of this many queries (forward, dq) or keys (dk/dv), each against the
# live extent of the other axis only (``strip_rows``, ``_run_tile``). 0 keeps
# the whole-tile masked body. Read off the same ladder (``--sub``).
STRIP_ROWS = {"fwd": 256, "dq": 256, "dkv": 256}
# The same for a windowed launch, whose tiles are smaller (a strip is a larger
# part of one) and whose tile on the band's lower edge runs as strips too: read
# off the ladder at window 512 (``--window 512 --sub``; PERF.md, PR 49).
BAND_STRIP_ROWS = {"fwd": 128, "dq": 256, "dkv": 256}
# A windowed launch's grid step (its fixed cost: the pipeline's turn, the
# online-softmax merge or the accumulator's read and write) in score pairs, by
# which ``pick_tiles`` weighs a band's tile: small tiles multiply few pairs no
# query sees and pay many steps, large ones the reverse. From the ladder at 64 /
# 8 heads of 128, window 512, seq 16,384 (PERF.md, PR 49): a launch's time at
# 256- and 512-square tiles is ``a x executed pairs + b x steps`` with ``b / a``
# these (1.06 / 0.56 / 1.49 us a step against 6.0 / 4.1 / 8.1 ps a pair); the
# 512 square they pick is the ladder's fastest for every launch.
BAND_STEP_PAIRS = {"fwd": 176_000, "dq": 135_000, "dkv": 184_000}


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


def _pos_diff(q0, k0, rows: int, cols: int) -> jax.Array:
    """``q_pos - k_pos`` as int32 ``[rows, cols]`` for a block of scores whose
    first query sits at global position ``q0`` and whose first key at ``k0``.

    ``q0`` carries ``offset = s_k - s_q``, which aligns query positions to the
    end of the key sequence (matches ``xla_attention``; matters when
    s_q != s_k). A pair is visible iff the difference is >= 0, and ALiBi's
    bias is ``-slope`` times it.
    """
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)) + (q0 - k0)


# Which bounds a block of scores is masked by (``masked``: bit flags, so that
# the causal launch's ``False`` / ``True`` read as before): the diagonal
# (``q_pos - k_pos >= 0``), and a windowed launch's second bound (``q_pos -
# k_pos < window``).
CAUSAL_BOUND = 1
WINDOW_BOUND = 2


def _scores(q, k, q0, k0, *, scale, slope, masked: int, window: int | None = None) -> jax.Array:
    """fp32 scores ``[rows, cols]`` of one block: ``q @ k^T * scale``, plus the
    per-head ALiBi bias ``-slope * (q_pos - k_pos)`` when ``slope`` is given
    (reference: llm-foundry MPT ``attn_config.alibi``; oracle:
    ``ops/attention.py:xla_attention``), with pairs above the diagonal at
    ``NEG_INF`` when ``masked`` has ``CAUSAL_BOUND`` and pairs ``window`` or
    more apart when it has ``WINDOW_BOUND``. A block that lies wholly inside
    what is visible is not ``masked`` and pays for no select."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale
    if slope is None and not masked:
        return s
    diff = _pos_diff(q0, k0, q.shape[0], k.shape[0])
    if slope is not None:
        s = s + -slope * diff.astype(jnp.float32)
    if masked & CAUSAL_BOUND:
        s = jnp.where(diff >= 0, s, NEG_INF)
    if masked & WINDOW_BOUND:
        s = jnp.where(diff < window, s, NEG_INF)
    return s


def _bh_slopes(h_slopes: jax.Array, bh: int, pair: bool = False) -> jax.Array:
    """[bh, SUBLANE, LANE] per-(batch*head) slope array (replicated across
    the tile so each grid row DMAs one full fp32 tile). ``h_slopes`` is the
    per-head slope vector [h] — by default ``attention.alibi_slopes(h)``,
    but a caller under a head-sharded (tensor-parallel) mesh passes its
    LOCAL slice of the global slope table so every shard biases with its
    true global head index. ``pair``: a grid row is two heads, and the tile
    holds the first one's slope in its upper half (``_stat_row``), the
    second's in its lower: ``[bh / 2, SUBLANE, LANE]``."""
    h = h_slopes.shape[0]
    slopes = jnp.tile(h_slopes, bh // h)  # head-major order
    if pair:
        return _stats_to_blocks(jnp.broadcast_to(slopes[:, None], (bh, LANE)), pair)
    return jnp.broadcast_to(slopes[:, None, None], (bh, SUBLANE, LANE))


# ---------------------------------------------------------------------------
# Where a launch finds a head: the block's index, and a pair's two halves
# ---------------------------------------------------------------------------


def _block_at(cols: int):
    """``(row, block) -> block index`` of a ``(1, rows, width)`` block.
    ``cols == 0``: the array is head-major ``[B·H, S, D]`` and ``row`` is its
    own. Otherwise it is ``[B, S, cols · width]`` and ``row = b · cols + c``
    is decoded: the head (or pair of heads) is column block ``c`` of batch
    row ``b``."""
    if not cols:
        return lambda row, blk: (row, blk, 0)
    return lambda row, blk: (row // cols, blk, row % cols)


def _own_lanes(x: jax.Array, e: int, pair: bool) -> jax.Array:
    """``x [rows, 128]`` with the other head's 64 lanes at zero, for head ``e``
    of a pair: what the zero pad is to a lone 64-wide head. A contraction over
    the 128 lanes then sums head ``e``'s products and exact zeros, and a
    product whose 128 lanes are outputs has head ``e``'s columns and zeros.
    Not a pair: ``x`` itself."""
    if not pair:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    keep = lane < PAIR_WIDTH if e == 0 else lane >= PAIR_WIDTH
    return jnp.where(keep, x, jnp.zeros_like(x))


def _pair_lanes(first: jax.Array, second: jax.Array) -> jax.Array:
    """``[rows, 128]``: the first head's lanes of ``first``, the second's of
    ``second``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, first.shape, first.ndim - 1)
    return jnp.where(lane < PAIR_WIDTH, first, second)


def _by_head(xs: list[jax.Array]) -> jax.Array:
    """One block's ``[rows, width]`` result from its heads': the lone head's
    own, or a pair's two halves side by side."""
    return xs[0] if len(xs) == 1 else _pair_lanes(*xs)


def _stat_row(e: int, pair: bool) -> int:
    """The sublane row of a lse / delta / slope block that head ``e`` of the
    block reads: a lone head's statistic fills all ``SUBLANE`` rows, a pair's
    two fill half each."""
    return e * (SUBLANE // 2) if pair else 0


def _stat_block(rows: list[jax.Array]) -> jax.Array:
    """``[SUBLANE, n]`` block of a per-row statistic from its ``[n]`` vector:
    a lone head's over all the sublanes, a pair's two over half of them each
    (``_stat_row``)."""
    shape = (SUBLANE, rows[0].shape[0])
    if len(rows) == 1:
        return jnp.broadcast_to(rows[0][None, :], shape)
    upper = jax.lax.broadcasted_iota(jnp.int32, shape, 0) < _stat_row(1, True)
    return jnp.where(upper, rows[0][None, :], rows[1][None, :])


def _stats_to_blocks(x: jax.Array, pair: bool) -> jax.Array:
    """``[B·H, S]`` per-head rows (lse, delta) as the launches' ``[rows,
    SUBLANE, S]`` blocks: a lone head's row replicated over the sublanes (one
    fp32 tile a block), a pair's two rows over half of them each."""
    bh, s = x.shape
    if not pair:
        return jnp.broadcast_to(x[:, None, :], (bh, SUBLANE, s))
    half = SUBLANE // 2
    return jnp.broadcast_to(
        x.reshape(bh // 2, 2, 1, s), (bh // 2, 2, half, s)).reshape(bh // 2, SUBLANE, s)


def _stats_from_blocks(x: jax.Array, pair: bool) -> jax.Array:
    """The inverse: ``[rows, SUBLANE, S]`` blocks to ``[B·H, S]``."""
    if not pair:
        return x[:, 0, :]
    rows, _, s = x.shape
    return x[:, ::SUBLANE // 2, :].reshape(rows * 2, s)


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


def _clip(x, lo: int, hi: int):
    """``x`` held to ``[lo, hi]``: a Python int stays one (the static counts),
    a traced index stays traced (the index maps, the kernels)."""
    if isinstance(x, int):
        return min(max(x, lo), hi)
    return jnp.minimum(jnp.maximum(x, lo), hi)


class _Band(NamedTuple):
    """A windowed causal launch's geometry in tiles: query ``r`` (at position
    ``r + offset`` among the keys) sees keys ``(r + offset - window, r +
    offset]``. Every method takes a Python int (the static counts: grid
    sizes, ``live_tiles``, ``executed_pairs``) or a traced index (the index
    maps and the kernels' own ``program_id``s) and is the same arithmetic."""

    block_q: int
    block_k: int
    offset: int
    window: int
    n_q: int
    n_k: int

    def k_span(self, i):
        """``(first, last)`` k tile that q tile ``i``'s band meets."""
        q_lo = i * self.block_q + self.offset
        first = _clip((q_lo - self.window + 1) // self.block_k, 0, self.n_k - 1)
        last = _clip((q_lo + self.block_q - 1) // self.block_k, 0, self.n_k - 1)
        return first, last

    def q_span(self, j):
        """``(first, last)`` q tile whose band meets k tile ``j``."""
        k_lo = j * self.block_k - self.offset
        first = _clip(k_lo // self.block_q, 0, self.n_q - 1)
        last = _clip((k_lo + self.block_k - 1 + self.window - 1) // self.block_q,
                     0, self.n_q - 1)
        return first, last

    def kv_block(self, i, step):
        """k/v block that step ``step`` of q tile ``i``'s sweep holds (forward,
        dq): the band's first tile plus ``step``; a step past the band's last
        tile repeats it, so the pipeline issues no copy (as ``_kv_block``)."""
        first, last = self.k_span(i)
        return jnp.minimum(first + step, last)

    def q_block(self, j, step):
        """q/dO/lse/delta block of step ``step`` of k tile ``j``'s sweep (dk/dv)."""
        first, last = self.q_span(j)
        return jnp.minimum(first + step, last)

    def cuts(self, i, j):
        """``(live, causal, window)`` of tile ``(i, j)``: whether a pair of it is
        visible, and whether the diagonal / the band's lower edge passes
        through it (so that its scores need that bound's mask)."""
        q_lo = i * self.block_q + self.offset
        q_hi = q_lo + self.block_q - 1
        k_lo = j * self.block_k
        k_hi = k_lo + self.block_k - 1
        live = (k_lo <= q_hi) & (k_hi > q_lo - self.window)
        return live, k_hi > q_lo, q_hi - k_lo >= self.window

    def tiles(self, by: str):
        """Every live ``(i, j, causal, window)`` (static), swept as the launch
        ``by`` (``"q"``: forward, dq; ``"k"``: dk/dv) sweeps them."""
        span, n = (self.k_span, self.n_q) if by == "q" else (self.q_span, self.n_k)
        for outer in range(n):
            first, last = span(outer)
            for inner in range(first, last + 1):
                i, j = (outer, inner) if by == "q" else (inner, outer)
                live, causal, window = self.cuts(i, j)
                if live:
                    yield i, j, causal, window

    def steps(self, by: str) -> int:
        """Steps of the inner sweep: the most tiles one q tile's (``"q"``) or
        one k tile's (``"k"``) band meets."""
        if by == "q":
            return max(hi - lo + 1 for lo, hi in map(self.k_span, range(self.n_q)))
        return max(hi - lo + 1 for lo, hi in map(self.q_span, range(self.n_k)))

    def cases(self, by: str) -> frozenset[tuple[bool, bool]]:
        """The ``(causal, window)`` kinds of live tile this launch meets: the
        bodies its kernel holds."""
        return frozenset((c, w) for _, _, c, w in self.tiles(by))


def strip_rows(launch: str, block_q: int, block_k: int, *, causal: bool, offset,
               window: int | None = None) -> int:
    """Rows of a strip for ``launch`` at this tile, or 0 where a live tile
    keeps the whole-tile masked body. The strips need a diagonal tile's
    geometry to be static: a causal call, a square tile, and ``offset`` a
    known whole number of tiles, so that every live tile lies wholly under the
    diagonal or has it as its own diagonal. A tile the strip does not divide
    is one strip. A windowed launch has strip heights of its own
    (``BAND_STRIP_ROWS``)."""
    sub = (STRIP_ROWS if window is None else BAND_STRIP_ROWS)[launch]
    if not (causal and sub and block_q == block_k
            and isinstance(offset, int) and offset % block_q == 0):
        return 0
    return block_q if block_q % sub else sub


def _lone_tile(sub: int, s_q: int, s_k: int, block: int, offset) -> bool:
    """Whether one tile on the diagonal is the forward's whole sweep (the
    strips engage and the tile holds both sequences): nothing is carried from
    step to step then, so the launch takes no scratch and each strip writes the
    outputs. (dq and dk/dv add into a scratch that the compiler already
    forwards when there is one step: the same program either way.)"""
    return bool(sub) and s_q == s_k == block and offset == 0


def _strips(block: int, sub: int, by: str) -> list[tuple[slice, list[tuple[slice, bool]]]]:
    """A diagonal tile as strips: ``(strip, [(extent, masked), ...])``, all in
    the tile's own coordinates. ``by == "q"`` (forward, dq): query strip ``r``
    against keys ``[0, r * sub)``, wholly visible, and its own ``sub x sub``
    block on the diagonal, the only one masked. ``by == "k"`` (dk/dv): key
    strip ``c`` against its diagonal block of queries and queries
    ``[(c + 1) * sub, block)``."""
    strips = []
    for lo in range(0, block, sub):
        own = (slice(lo, lo + sub), True)
        if by == "q":
            rest = [(slice(0, lo), False)] if lo else []
            strips.append((own[0], rest + [own]))
        else:
            rest = [(slice(lo + sub, block), False)] if lo + sub < block else []
            strips.append((own[0], [own] + rest))
    return strips


def _edge_strips(block: int, sub: int, by: str) -> list[tuple[slice, list[tuple[slice, int]]]]:
    """The tile on a band's lower edge as strips, where the window is a whole
    number of (square, aligned) tiles: in the tile's own coordinates key ``c``
    is visible to query ``r`` iff ``c > r``, the mirror of the diagonal
    tile's triangle. ``by == "q"``: query strip ``r`` against its own ``sub x
    sub`` block, the only one masked (by the window's bound), and keys ``[(r +
    1) * sub, block)``, wholly visible. ``by == "k"``: key strip ``c`` against
    queries ``[0, c * sub)`` and its own block."""
    # the diagonal tile's strips as the other axis has them, under the other bound
    mirrored = _strips(block, sub, "k" if by == "q" else "q")
    return [(rows, [(extent, WINDOW_BOUND if masked else False) for extent, masked in parts])
            for rows, parts in mirrored]


def _edge_as_strips(sub: int, block: int, window: int | None) -> bool:
    """Whether a windowed launch's lower-edge tile has the static triangle
    ``_edge_strips`` runs: the strips engage (``sub``: a square tile, aligned)
    and the window is a whole number of tiles."""
    return bool(sub) and window is not None and window % block == 0


def _run_tile(compute, by: str, q_blk, k_blk, block_q: int, block_k: int, *,
              causal: bool, offset, sub: int, band: _Band | None = None) -> None:
    """Runs ``compute(strips, guard)`` in the form grid step ``(q_blk, k_blk)``
    needs. The grid is dense, so a dead tile is predicated off, not skipped
    (it costs its grid step and no copy: ``_kv_block`` / ``_q_block`` repeat a
    live index). ``sub`` (``strip_rows``) says the geometry is static: a tile
    under the diagonal then runs whole and unmasked, the tile on it as strips
    against their live extents. Otherwise every live tile runs whole under the
    mask, and ``guard`` tells the body that a row may have no visible key.

    ``band`` (a windowed launch): the tile is one of the band's sweep, or a
    step past its end (not live). A live tile runs by what cuts it
    (``_Band.cuts``): neither bound, whole and unmasked; the diagonal alone,
    as strips where ``sub`` says so (a tile no wider than the window), else
    whole under the causal mask; the band's lower edge alone, as the mirrored
    strips where the window is a whole number of such tiles
    (``_edge_strips``; a query of that tile may see none of its keys, so the
    body guards), else whole under the second bound; both, whole under both.
    Only the kinds the launch meets (``_Band.cases``) are in its kernel."""
    rows, cols = (block_q, block_k) if by == "q" else (block_k, block_q)

    def whole(masked):
        return [(slice(0, rows), [(slice(0, cols), masked)])]

    if band is not None:
        live, cut_c, cut_w = band.cuts(q_blk, k_blk)
        live &= (q_blk < band.n_q) & (k_blk < band.n_k)
        for on_c, on_w in sorted(band.cases(by)):
            @pl.when(live & (cut_c == on_c) & (cut_w == on_w))
            def _(on_c=on_c, on_w=on_w):
                if on_c and not on_w and sub:
                    compute(_strips(block_q, sub, by), guard=False)
                elif on_w and not on_c and _edge_as_strips(sub, block_q, band.window):
                    compute(_edge_strips(block_q, sub, by), guard=True)
                else:
                    compute(whole(CAUSAL_BOUND * on_c + WINDOW_BOUND * on_w),
                            guard=on_c or on_w)
    elif not causal:
        compute(whole(False), guard=False)
    elif not sub:
        @pl.when(k_blk * block_k <= q_blk * block_q + (block_q - 1) + offset)
        def _():
            compute(whole(True), guard=True)
    else:
        diagonal = q_blk + offset // block_q  # the k block that straddles

        @pl.when(k_blk < diagonal)
        def _():
            compute(whole(False), guard=False)

        @pl.when(k_blk == diagonal)
        def _():
            compute(_strips(block_q, sub, by), guard=False)


def launch_vmem_bytes(launch: str, block_q: int, block_k: int, d: int, itemsize: int,
                      d_v: int | None = None, layout: str = HEAD_MAJOR,
                      window: int | None = None) -> int:
    """VMEM one launch (``fwd``, ``dq`` or ``dkv``) needs at a tile, from the
    kernel's own buffers (``d`` the padded width of q and k, ``d_v`` of v,
    ``None`` = the same): every BlockSpec'd operand and result twice (the
    pipeline double-buffers them), the scratch accumulators, the fp32
    temporaries as large as an operand (the forward's ``pv`` and rescaled
    accumulator, the backward bodies' upcasts), and the ``[block_q, block_k]``
    score temporaries. The body names five or six of those (s, the mask, p,
    dp, ds); the compiler keeps ``SCORE_TEMPS`` of them alive at once, read
    off the smallest ``vmem_limit_bytes`` it accepts per tile (PERF.md,
    PR 28: over 90 readings of launch, tile, width and dtype this estimate is
    1.05 to 2.1 times that, never under it;
    ``tests/test_tpu_compile.py::test_flash_vmem_estimate_is_enough``).

    The estimate is the whole-tile body's, which every launch still holds
    (a tile under the diagonal runs it). A strip's temporaries are smaller
    and come one strip after another: its scores are ``[sub, extent]``, at
    most a ``sub / block`` part of the tile's, its ``pv`` / ``dq`` parts
    ``[sub, d]``; the upcasts are made once a tile and sliced, as large as
    the whole-tile body's.

    ``layout`` is what the operands are read in (``flash_layout``), and the
    numbers above are ``HEAD_MAJOR``'s. Read in place (``IN_PLACE``,
    ``HEAD_PAIRS``), a column block of a wider array, dq and dk/dv want four
    more q-shaped blocks than over head-major rows, whatever the tile (2.0,
    4.3 and 8.4 MB at three tiles, widths and dtypes: 4 x ``block_q`` x ``d``
    x ``itemsize`` to 3 %; the forward wants none), and ``IN_PLACE``'s dq
    holds the forward's o block, from which it makes delta. ``HEAD_PAIRS`` (``d`` is
    then the pair's 128 lanes) adds the second head's running max,
    denominator and accumulator to the forward's scratch (dq and dk/dv add
    both heads into one accumulator, each in its own lanes), and one more
    set of score temporaries: the two heads' bodies are unrolled side by
    side, and the compiler starts the second's scores while the first's are
    alive (at a 1,024 square it wanted 1.0 to 1.7 score tiles more than for
    a lone padded head: 15.4 / 14.0 / 17.3 MB for the three launches, where
    this gives 20.0 / 19.0 / 20.6). Both read off the smallest limit the
    compiler accepts, as the rest was (PERF.md, PR 45).

    ``window``: a banded launch's bodies hold the second bound's select
    beside the first's, ``BAND_SCORE_TEMPS`` more score temporaries."""
    d_v = d if d_v is None else d_v
    pair = layout == HEAD_PAIRS
    q_rows = block_q * d * itemsize  # one q-shaped block: q, dq
    o_rows = block_q * d_v * itemsize  # one o-shaped block: o, do
    k_rows = block_k * d * itemsize  # one k-shaped block: k, dk
    v_rows = block_k * d_v * itemsize  # one v-shaped block: v, dv
    row_stats = SUBLANE * block_q * 4  # one lse / delta block
    slopes = SUBLANE * LANE * 4
    if launch == "fwd":
        piped = q_rows + o_rows + k_rows + v_rows + row_stats  # q, o; k, v; lse
        scratch = (2 if pair else 1) * (2 * block_q * LANE * 4 + block_q * d_v * 4)  # m, l; acc
        upcast = 2 * block_q * d_v * 4  # pv, acc * alpha
    elif launch == "dq":
        piped = 2 * q_rows + o_rows + k_rows + v_rows + 2 * row_stats  # q, dq, do; k, v
        if layout == IN_PLACE:
            piped += o_rows  # o comes in, delta goes out where it came in
        scratch = block_q * d * 4
        upcast = (block_q + block_k) * d_v * 4  # do, v
    elif launch == "dkv":
        piped = q_rows + o_rows + 2 * k_rows + 2 * v_rows + 2 * row_stats  # q, do; k, dk, v, dv
        scratch = block_k * (d + d_v) * 4
        upcast = block_q * (d + d_v) * 4 + block_k * d_v * 4  # q, do, v
    else:
        raise ValueError(f"unknown launch {launch!r}")
    temps = SCORE_TEMPS + (BAND_SCORE_TEMPS if window is not None else 0.0)
    scores = (2 if pair else 1) * int(temps * block_q * block_k * 4)
    in_place = 4 * block_q * max(d, d_v) * itemsize if (
        layout != HEAD_MAJOR and launch != "fwd") else 0
    return 2 * (piped + slopes) + scratch + upcast + scores + in_place + VMEM_SLACK


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
    executed_share: float  # score pairs the bodies multiply over visible pairs


class TilePlan(NamedTuple):
    fwd: LaunchTiles
    dq: LaunchTiles
    dkv: LaunchTiles

    @property
    def blocks(self) -> tuple[tuple[int, int], ...]:
        return tuple((t.block_q, t.block_k) for t in self)

    def attrs(self, layout: str = HEAD_MAJOR, banded: bool = False) -> dict[str, str]:
        """The plan as span attributes (``trainer/steps`` carries them), with
        the ``layout`` its launches read (``flash_layout``). ``banded``: the
        plan of a model's windowed layers, told as ``swa_*`` beside the full
        layers' ``flash_*`` (one layout for both: it follows the head widths)."""
        told = {
            "tiles": " ".join(
                f"{n}={t.block_q}x{t.block_k}" for n, t in zip(self._fields, self)),
            "live_tiles": " ".join(
                f"{n}={t.live_tiles}/{t.grid_tiles}" for n, t in zip(self._fields, self)),
            "executed_share": " ".join(
                f"{n}={t.executed_share:.3f}" for n, t in zip(self._fields, self)),
        }
        if banded:
            return {f"swa_{key}": value for key, value in told.items()}
        return {"flash_layout": layout, **{f"flash_{key}": v for key, v in told.items()}}


def _tile_sizes(s: int) -> list[int]:
    """Block sizes a length-``s`` axis can take: its divisors that are whole
    lane widths (a block's rows are the lanes of the lse/delta blocks and of
    the score tile), and ``s`` itself, which is always allowed."""
    return sorted({t for t in range(LANE, s + 1, LANE) if s % t == 0} | {s})


def pick_tiles(s_q: int, s_k: int, d_pad: int, itemsize: int, n_kv_group: int = 1, *,
               causal: bool = True, offset: int | None = None,
               block_q: int | None = None, block_k: int | None = None,
               vmem_budget: int = VMEM_BUDGET, d_v_pad: int | None = None,
               layout: str = HEAD_MAJOR, window: int | None = None) -> TilePlan:
    """``(block_q, block_k)`` of the forward, dq and dk/dv launches, from the
    shapes alone: for each launch the largest tile (by area) that divides
    both sequences, stays inside ``vmem_budget`` by :func:`launch_vmem_bytes`,
    and does not pass ``TILE_LADDER_TOP`` (scaled down for a head wider than
    ``TILE_LADDER_WIDTH``), the size past which the ladder on the chip stopped
    paying (PERF.md, PRs 28 and 39). A sequence no candidate fits gets its
    smallest candidate: there is always an answer, and it always divides.

    An explicit ``block_q`` / ``block_k`` pins that side for all three
    launches, as given (``min(block, s)``; it must divide, or ``ValueError``).
    ``n_kv_group`` is the grouped-query group: dk/dv sweeps it too, so its
    tile counts are per kv head times the group. ``d_v_pad`` is v's padded
    width where it is not q's and k's (``d_pad``): it enters the VMEM
    estimate; the ladder's top follows ``d_pad``, the width of the score
    products, where it was read. ``d_pad`` is the width of a block as the
    launches hold it: a head's own under ``IN_PLACE``, 128 for a pair of
    64-wide heads (``HEAD_PAIRS``), the padded one under ``HEAD_MAJOR``: the
    same number for the same heads whatever the ``layout``. Every layout's
    tile is fitted by ``HEAD_MAJOR``'s estimate, so the layout does not move
    a tile (the ladder's tops were read per head, and a pair's step is two
    heads' work at the same tile); ``vmem_bytes`` is the layout's own, larger
    estimate, which the launch asks for: it may pass ``vmem_budget``, a limit
    on what a tile is picked by, not on what a core has (128 MiB).

    ``window``: the launches walk a band, and the largest tile is no longer
    the best: a 2,048 square is four 512-windows wide and most of the pairs
    it multiplies no query of the band sees, while a tile far under the
    window pays a grid step and an online-softmax update for little work. Of
    the tiles that fit, each launch takes the one with the least
    ``executed_pairs + grid steps x BAND_STEP_PAIRS``: what the bodies
    multiply, and a step's fixed cost in pairs, read off the ladder on the
    chip at window 512 (PERF.md, PR 49); of equals the larger."""
    qs = _tile_sizes(s_q) if block_q is None else [min(block_q, s_q)]
    ks = _tile_sizes(s_k) if block_k is None else [min(block_k, s_k)]
    if s_q % qs[0] or s_k % ks[0]:  # only a pinned block can fail to divide
        raise ValueError(
            f"seq lengths ({s_q},{s_k}) must divide blocks ({block_q},{block_k})")
    plan = []
    for launch in TilePlan._fields:
        narrow = min(TILE_LADDER_WIDTH[launch], d_pad)  # a wider head: fewer rows
        top_q, top_k = (top * narrow // d_pad for top in TILE_LADDER_TOP[launch])
        sized = [(launch_vmem_bytes(launch, bq, bk, d_pad, itemsize, d_v_pad), bq, bk)
                 for bq in qs for bk in ks]
        # largest area first; of two equal areas the longer k block (fewer
        # steps of the forward's and dq's inner sweep)
        fits = [(bq * bk, bk, bq, need) for need, bq, bk in sized
                if need <= vmem_budget
                and (bq <= top_q or block_q is not None)
                and (bk <= top_k or block_k is not None)]
        if fits and window is not None:
            def band_cost(fit):
                _, bk, bq, _ = fit
                _, steps = live_tiles(s_q, s_k, bq, bk, offset=offset, window=window,
                                      launch=launch)
                return steps * BAND_STEP_PAIRS[launch] + executed_pairs(
                    launch, s_q, s_k, bq, bk, offset=offset, window=window)

            _, bk, bq, need = min(fits, key=lambda fit: (band_cost(fit), -fit[0], -fit[1]))
        elif fits:
            _, bk, bq, need = max(fits)
        else:
            need, bq, bk = min(sized)
        if layout != HEAD_MAJOR or window is not None:
            need = launch_vmem_bytes(launch, bq, bk, d_pad, itemsize, d_v_pad, layout, window)
        live, grid = live_tiles(s_q, s_k, bq, bk, causal=causal, offset=offset,
                                window=window, launch=launch)
        group = n_kv_group if launch == "dkv" else 1
        share = (executed_pairs(launch, s_q, s_k, bq, bk, causal=causal, offset=offset,
                                window=window)
                 / max(visible_pairs(s_q, s_k, causal=causal, offset=offset,
                                     window=window), 1))
        plan.append(LaunchTiles(bq, bk, need, live * group, grid * group, share))
    return TilePlan(*plan)


def _static_band(s_q: int, s_k: int, block_q: int, block_k: int, offset: int | None,
                 window: int) -> _Band:
    return _Band(block_q, block_k, s_k - s_q if offset is None else offset, window,
                 s_q // block_q, s_k // block_k)


def live_tiles(s_q: int, s_k: int, block_q: int, block_k: int, *,
               causal: bool = True, offset: int | None = None,
               window: int | None = None, launch: str = "fwd") -> tuple[int, int]:
    """``(live, grid)`` tiles of one (batch, head): the tiles whose body runs
    and the grid steps paid. Closed form of the kernels' ``live`` predicate.
    With a ``window`` the grid is the band's (``_Band.steps`` steps for each
    q tile, or for each k tile in ``launch`` ``dkv``)."""
    if window is not None:
        band = _static_band(s_q, s_k, block_q, block_k, offset, window)
        by = "k" if launch == "dkv" else "q"
        return (sum(1 for _ in band.tiles(by)),
                (band.n_k if by == "k" else band.n_q) * band.steps(by))
    offset = s_k - s_q if offset is None else offset
    n_q, n_k = s_q // block_q, s_k // block_k
    if not causal:
        return n_q * n_k, n_q * n_k
    live = sum(
        min(max((i * block_q + block_q - 1 + offset) // block_k + 1, 0), n_k)
        for i in range(n_q))
    return live, n_q * n_k


def visible_pairs(s_q: int, s_k: int, *, causal: bool = True,
                  offset: int | None = None, window: int | None = None) -> int:
    """(query, key) pairs of one (batch, head) that attention has to score:
    query ``r`` sees keys ``[0, r + offset]``, with a ``window`` the last
    ``window`` of them."""
    if not causal:
        return s_q * s_k
    offset = s_k - s_q if offset is None else offset
    if window is not None:
        return sum(max(min(r + offset, s_k - 1) - max(r + offset - window + 1, 0) + 1, 0)
                   for r in range(s_q))
    first = min(max(-offset, 0), s_q)  # rows before it see nothing
    full = min(max(s_k - offset - 1, first), s_q)  # rows from it on see every key
    n = full - first  # rows first .. full - 1 see first + offset + 1, ... keys
    return n * (first + offset + 1) + n * (n - 1) // 2 + (s_q - full) * s_k


def executed_pairs(launch: str, s_q: int, s_k: int, block_q: int, block_k: int, *,
                   causal: bool = True, offset: int | None = None,
                   window: int | None = None) -> int:
    """Score pairs one (batch, head) of ``launch`` multiplies at this tile.
    Closed form of ``_run_tile``: a whole tile for each live one, or, where
    the strips engage, whole tiles under the diagonal and on it each strip's
    live extent. With a ``window`` the live tiles are the band's, and the
    strips engage on a diagonal tile that the window's edge does not cut and
    on a lower-edge tile that the diagonal does not (``_edge_as_strips``)."""
    offset = s_k - s_q if offset is None else offset
    sub = strip_rows(launch, block_q, block_k, causal=causal, offset=offset, window=window)
    if window is not None:
        band = _static_band(s_q, s_k, block_q, block_k, offset, window)
        n = block_q // sub if sub else 0
        stripped = sub * sub * n * (n + 1) // 2  # a triangle of the tile, in strips
        edge = _edge_as_strips(sub, block_q, window)
        return sum(
            stripped if sub and on_c != on_w and (on_c or edge) else block_q * block_k
            for _, _, on_c, on_w in band.tiles("k" if launch == "dkv" else "q"))
    if not sub:
        live, _ = live_tiles(s_q, s_k, block_q, block_k, causal=causal, offset=offset)
        return live * block_q * block_k
    n_q, n_k, n = s_q // block_q, s_k // block_k, block_q // sub
    diagonals = [i + offset // block_q for i in range(n_q)]
    under = sum(min(max(j, 0), n_k) for j in diagonals)
    on = sum(0 <= j < n_k for j in diagonals)
    return under * block_q * block_k + on * sub * sub * n * (n + 1) // 2


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, block_q, block_k, causal, offset, use_alibi, sub, lone, pair=False, band=None):
    """``pair``: the block is two 64-wide heads side by side (``HEAD_PAIRS``).
    Each runs as a lone head does, its q with the other's lanes at zero
    (``_own_lanes``) against the whole k block, so its scores are a lone
    padded head's bit for bit; ``p @ v`` over the whole v block has its own
    output columns in its own lanes, which ``_emit`` keeps.

    ``band`` (a windowed launch): the inner axis counts the steps of this q
    tile's band, and the k tile is the band's first plus the step."""
    slopes_ref = rest[0] if use_alibi else None
    o_ref, lse_ref, *scratch = rest[1:] if use_alibi else rest
    q_blk = pl.program_id(1)
    step = k_blk = pl.program_id(2)
    n_k = pl.num_programs(2)
    window = None
    if band is not None:
        window, k_blk = band.window, band.k_span(q_blk)[0] + step
    heads = range(2 if pair else 1)

    def _attend(rows, parts, guard, carried, e):
        """``(m, l, acc)`` of head ``e``'s strip ``rows`` after all its key
        ``parts`` at once: a plain softmax over the strip's extent, merged,
        where state is ``carried``, with what earlier tiles of the row left in
        the head's scratch."""
        slope = slopes_ref[0, _stat_row(e, pair), 0] if use_alibi else None
        q = _own_lanes(q_ref[0, rows, :], e, pair)
        q0 = q_blk * block_q + offset + rows.start
        scores = [
            _scores(q, k_ref[0, ks, :], q0, k_blk * block_k + ks.start,
                    scale=scale, slope=slope, masked=masked, window=window)
            for ks, masked in parts]
        m = functools.reduce(
            jnp.maximum, [jnp.max(s, axis=-1, keepdims=True) for s in scores])  # [rows, 1]
        l = pv = None
        if carried:
            m_s, l_s, acc_s = scratch[3 * e:3 * e + 3]
            m_prev = m_s[rows, 0][:, None]
            m = jnp.maximum(m_prev, m)
            alpha = jnp.exp(m_prev - m)  # rescale of old state
            l = alpha * l_s[rows, 0][:, None]
        for s, (ks, _) in zip(scores, parts):
            p = jnp.exp(s - m)
            if guard:
                # fully-masked rows keep m == NEG_INF; exp(s - m) would be
                # exp(0)=1 there, so force p to 0 (their output and l stay 0)
                p = jnp.where(m > NEG_INF / 2, p, 0.0)
            l_part = jnp.sum(p, axis=-1, keepdims=True)
            pv_part = jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, ks, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [rows, d]
            l = l_part if l is None else l + l_part
            pv = pv_part if pv is None else pv + pv_part
        acc = acc_s[rows, :] * alpha + pv if carried else pv
        return m, l, acc

    def _emit(rows, state):
        """Writes the rows' output and log-sum-exp from each head's ``(m, l,
        acc)``: a pair's outputs side by side, its two log-sum-exps in the
        halves of the block's sublanes (``_stat_row``)."""
        safe = [jnp.where(l == 0.0, 1.0, l) for _, l, _ in state]
        o_ref[0, rows, :] = _by_head(
            [acc / l_safe for (_, _, acc), l_safe in zip(state, safe)]).astype(o_ref.dtype)
        lse_ref[0, :, rows] = _stat_block(
            [m[:, 0] + jnp.log(l_safe[:, 0]) for (m, _, _), l_safe in zip(state, safe)])

    if lone:
        # the tile is the whole sequence: nothing is carried from step to
        # step, so each strip goes straight to the outputs
        for rows, parts in _strips(block_q, sub, "q"):
            _emit(rows, [_attend(rows, parts, guard=False, carried=False, e=e)
                         for e in heads])
        return

    @pl.when(step == 0)
    def _init():
        for e in heads:
            m_s, l_s, acc_s = scratch[3 * e:3 * e + 3]
            m_s[:] = jnp.full_like(m_s, NEG_INF)
            l_s[:] = jnp.zeros_like(l_s)
            acc_s[:] = jnp.zeros_like(acc_s)

    def _compute(strips, guard):
        for rows, parts in strips:
            for e in heads:
                m_s, l_s, acc_s = scratch[3 * e:3 * e + 3]
                m, l, acc = _attend(rows, parts, guard, carried=True, e=e)
                acc_s[rows, :] = acc
                m_s[rows, :] = jnp.broadcast_to(m, (m.shape[0], LANE))
                l_s[rows, :] = jnp.broadcast_to(l, (l.shape[0], LANE))

    _run_tile(_compute, "q", q_blk, k_blk, block_q, block_k, causal=causal,
              offset=offset, sub=sub, band=band)

    @pl.when(step == n_k - 1)
    def _finalize():
        _emit(slice(0, block_q),
              [(m_s[:, 0][:, None], l_s[:, 0][:, None], acc_s[:])
               for m_s, l_s, acc_s in (scratch[3 * e:3 * e + 3] for e in heads)])


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


class _Heads(NamedTuple):
    """The launches' view of arrays read in place, ``[B, S, cols · width]``:
    the column blocks a batch row of q (o, dO, dq) and of k (v, dk, dv) has,
    a head each or, with ``pair``, two 64-wide heads each. ``None`` in its
    place: head-major ``[B·H, S, D]`` arrays, a block a whole row."""

    q_cols: int
    kv_cols: int
    pair: bool = False

    @classmethod
    def of(cls, layout: str, h_q: int, h_kv: int) -> "_Heads | None":
        """What ``layout`` makes of ``h_q`` query and ``h_kv`` key heads."""
        if layout == HEAD_MAJOR:
            return None
        return cls(h_q // 2, h_kv // 2, True) if layout == HEAD_PAIRS else cls(h_q, h_kv)


class _Launch(NamedTuple):
    """A launch's geometry, whichever arrays it reads: the grid's rows over q
    and over k / v, the heads a batch row has of each, a block's width for q
    and k and for v, the block index of ``(row, block)`` in a q-shaped and a
    k-shaped array, and whether a block is a pair of heads."""

    bh: int
    bh_k: int
    h_q: int
    h_kv: int
    d: int
    d_v: int
    at_q: object
    at_kv: object
    pair: bool
    layout: str


def _launch_of(q, k, v, h_q: int, heads: _Heads | None) -> _Launch:
    """Over head-major arrays (``heads`` is ``None``; ``h_q`` 0 → MHA: kv row
    == q row, exact head split irrelevant) or over arrays read in place."""
    if heads is None:
        bh, bh_k = q.shape[0], k.shape[0]
        h_q = h_q or 1
        return _Launch(bh, bh_k, h_q, h_q * bh_k // bh, q.shape[2], v.shape[2],
                       _block_at(0), _block_at(0), False, HEAD_MAJOR)
    b, (q_cols, kv_cols, pair) = q.shape[0], heads
    return _Launch(b * q_cols, b * kv_cols, q_cols, kv_cols, q.shape[2] // q_cols,
                   v.shape[2] // kv_cols, _block_at(q_cols), _block_at(kv_cols), pair,
                   HEAD_PAIRS if pair else IN_PLACE)


def _band_of(window, causal, block_q, block_k, offset, n_q, n_k) -> _Band | None:
    """The band a launch walks, or ``None`` for the causal square."""
    if window is None:
        return None
    if not causal or not isinstance(offset, int):
        raise ValueError("a window needs a causal launch and a static offset")
    return _Band(block_q, block_k, offset, window, n_q, n_k)


def _fwd(q, k, v, *, scale, causal, block_q, block_k, offset=None, slopes=None,
         h_q=0, interpret=False, heads: _Heads | None = None, window: int | None = None):
    """The forward launch: ``(o, lse [B·H, s_q])``. ``q``, ``k``, ``v`` (and
    ``o``) are head-major ``[B·H, S, D]``, or, with ``heads``, the
    projections' own ``[B, S, H·D]``. ``window``: the launch walks the band
    (``_Band``) under its own name."""
    s_q, s_k = q.shape[1], k.shape[1]
    # v, o and the accumulator at v's width
    bh, _, h_q, h_kv, d, d_v, at_q, at_kv, pair, layout = _launch_of(q, k, v, h_q, heads)
    n_q = pl.cdiv(s_q, block_q)
    n_k = pl.cdiv(s_k, block_k)
    kv = _kv_row(h_q, h_kv)

    # offset generalizes the causal mask to chunked/global positions:
    # visible iff q_id + offset >= k_id (ring attention passes
    # q_start - k_start; default aligns q to the end of k)
    offset = s_k - s_q if offset is None else offset
    band = _band_of(window, causal, block_q, block_k, offset, n_q, n_k)
    grid = (bh, n_q, n_k if band is None else band.steps("q"))
    kj = band.kv_block if band is not None else functools.partial(
        _kv_block, causal=causal, block_q=block_q, block_k=block_k, offset=offset, n_k=n_k)
    sub = strip_rows("fwd", block_q, block_k, causal=causal, offset=offset, window=window)
    lone = band is None and _lone_tile(sub, s_q, s_k, block_q, offset)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k, causal=causal,
        offset=offset, use_alibi=slopes is not None, sub=sub, lone=lone, pair=pair,
        **({} if band is None else {"band": band}),
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: at_q(b, i)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: at_kv(kv(b), kj(i, j))),
        pl.BlockSpec((1, block_k, d_v), lambda b, i, j: at_kv(kv(b), kj(i, j))),
    ]
    inputs = [q, k, v]
    if slopes is not None:
        in_specs.append(pl.BlockSpec((1, SUBLANE, LANE), lambda b, i, j: (b, 0, 0)))
        inputs.append(slopes)
    # lse carries SUBLANE redundant rows so its (1, 8, block_q) blocks are
    # exactly one fp32 tile; callers use row 0
    o_shape = (bh, s_q, d_v) if heads is None else (q.shape[0], s_q, h_q * d_v)
    out_shape = [
        jax.ShapeDtypeStruct(o_shape, q.dtype),
        jax.ShapeDtypeStruct((bh, SUBLANE, s_q), jnp.float32),
    ]
    launch = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, i, j: at_q(b, i)),
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j: (b, 0, i)),
        ],
        scratch_shapes=[] if lone else [
            pltpu.VMEM((block_q, LANE), jnp.float32),  # running max
            pltpu.VMEM((block_q, LANE), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d_v), jnp.float32),  # output accumulator
        ] * (2 if pair else 1),  # a pair: each head its own three
        out_shape=out_shape,
        interpret=interpret,
        **_vmem_params(launch_vmem_bytes("fwd", block_q, block_k, d, q.dtype.itemsize, d_v,
                                         layout, window)),
    )
    with _kernel_scope("flash_fwd" if band is None else FLASH_SWA_FWD_KERNEL):
        o, lse = launch(*inputs)
    return o, _stats_from_blocks(lse, pair)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest, scale, block_q, block_k, causal, offset, use_alibi, sub, pair=False, makes_delta=False, band=None):
    """``pair`` (``HEAD_PAIRS``): each head of the block with its q and dO
    strips masked to its own lanes (``_own_lanes``) against the whole k and v
    blocks; ``ds @ k`` has the head's dq in its own lanes, and the block's dq
    is the two side by side.

    ``makes_delta`` (whole-lane heads read in place): the sixth operand is
    the forward's o block, not delta, and ``delta = rowsum(dO * o)`` is a
    second RESULT, made at a q block's first step from the two blocks the
    launch holds anyway, read back by the steps of that block, and handed to
    the dk/dv launch. (Summed by XLA over a head's columns of ``[B, S, H·D]``
    it cost a relayout of the float32 product to a ``[B, S, H, D]`` view's
    layout: 7 ms a step of ``glm47flash-train``, PERF.md PR 45.)"""
    heads = range(2 if pair else 1)
    slopes_ref = None
    if use_alibi:
        slopes_ref, *rest = rest
    if makes_delta:
        o_ref = delta_ref
        dq_ref, delta_ref, dq_s = rest
    else:
        dq_ref, dq_s = rest
    q_blk = pl.program_id(1)
    step = k_blk = pl.program_id(2)
    n_k = pl.num_programs(2)
    window = None
    if band is not None:  # as the forward's
        window, k_blk = band.window, band.k_span(q_blk)[0] + step

    @pl.when(step == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)
        if makes_delta:  # never a pair
            prod = do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32)
            delta_ref[0] = _stat_block([jnp.sum(prod, axis=-1)])

    def _compute(strips, guard):
        slopes = [slopes_ref[0, _stat_row(e, pair), 0] if use_alibi else None for e in heads]
        v32 = v_ref[0].astype(jnp.float32)
        for rows, parts in strips:
            q_rows = q_ref[0, rows, :]
            do_rows = do_ref[0, rows, :].astype(jnp.float32)
            dqs = []
            for e, slope in zip(heads, slopes):
                q = _own_lanes(q_rows, e, pair)
                do = _own_lanes(do_rows, e, pair)
                lse = lse_ref[0, _stat_row(e, pair), rows][:, None]
                delta = delta_ref[0, _stat_row(e, pair), rows][:, None]
                q0 = q_blk * block_q + offset + rows.start
                dq = None
                for ks, masked in parts:
                    k = k_ref[0, ks, :]
                    s = _scores(q, k, q0, k_blk * block_k + ks.start,
                                scale=scale, slope=slope, masked=masked, window=window)
                    p = jnp.exp(s - lse)  # [rows, cols]
                    if guard:
                        # fully-masked rows (lse == NEG_INF): exp(s - lse) would be 1
                        p = jnp.where(lse > NEG_INF / 2, p, 0.0)
                    dp = jax.lax.dot_general(
                        do, v32[ks], (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    ds = p * (dp - delta) * scale
                    part = jax.lax.dot_general(
                        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    dq = part if dq is None else dq + part
                dqs.append(dq)
            dq_s[rows, :] += _by_head(dqs)

    _run_tile(_compute, "q", q_blk, k_blk, block_q, block_k, causal=causal,
              offset=offset, sub=sub, band=band)

    @pl.when(step == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_s[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest, scale, block_q, block_k, causal, offset, use_alibi, n_q, sub, pair=False, band=None):
    """Inner grid dim sweeps ``group * n_q`` steps: for grouped-query
    attention every kv row accumulates dk/dv over ALL q heads of its group
    (t // n_q picks the group member, t % n_q the q block); MHA is the
    group == 1 degenerate case.

    ``pair`` (``HEAD_PAIRS``): each head of the block with its k and v strips
    masked to its own lanes (``_own_lanes``) against the whole q and dO
    blocks; ``ds^T @ q`` and ``p^T @ dO`` have the head's dk and dv in its
    own lanes, and the block's are the two side by side.

    ``band`` (a windowed launch): ``n_q`` is the steps of one k tile's band,
    and the q tile is the band's first for this k tile plus ``t % n_q``."""
    heads = range(2 if pair else 1)
    if use_alibi:
        slopes_ref, dk_ref, dv_ref, dk_s, dv_s = rest
    else:
        slopes_ref = None
        dk_ref, dv_ref, dk_s, dv_s = rest
    k_blk = pl.program_id(1)
    t = pl.program_id(2)
    n_t = pl.num_programs(2)
    q_blk = t % n_q
    window = None
    if band is not None:
        window, q_blk = band.window, band.q_span(k_blk)[0] + q_blk

    @pl.when(t == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def _compute(strips, guard):
        slopes = [slopes_ref[0, _stat_row(e, pair), 0] if use_alibi else None for e in heads]
        # made once a tile and sliced: a key strip's queries overlap the next's
        q32 = q_ref[0].astype(jnp.float32)
        do32 = do_ref[0].astype(jnp.float32)
        stats = [(lse_ref[0, _stat_row(e, pair)][:, None],
                  delta_ref[0, _stat_row(e, pair)][:, None]) for e in heads]
        for cols, parts in strips:
            k_cols = k_ref[0, cols, :]
            v_cols = v_ref[0, cols, :].astype(jnp.float32)
            k0 = k_blk * block_k + cols.start
            dks, dvs = [], []
            for e, slope, (lse_col, delta_col) in zip(heads, slopes, stats):
                k = _own_lanes(k_cols, e, pair)
                v = _own_lanes(v_cols, e, pair)
                dk = dv = None
                for qs, masked in parts:
                    s = _scores(q_ref[0, qs, :], k, q_blk * block_q + offset + qs.start, k0,
                                scale=scale, slope=slope, masked=masked, window=window)
                    lse = lse_col[qs]
                    p = jnp.exp(s - lse)  # [rows, cols]
                    if guard:
                        # fully-masked rows (lse == NEG_INF): exp(s - lse) would be 1
                        p = jnp.where(lse > NEG_INF / 2, p, 0.0)
                    do = do32[qs]
                    # dv += p^T @ do
                    part_v = jax.lax.dot_general(
                        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                    dp = jax.lax.dot_general(
                        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                    ds = p * (dp - delta_col[qs]) * scale  # [rows, cols]
                    # dk += ds^T @ q
                    part_k = jax.lax.dot_general(
                        ds, q32[qs], (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                    dv = part_v if dv is None else dv + part_v
                    dk = part_k if dk is None else dk + part_k
                dks.append(dk)
                dvs.append(dv)
            dv_s[cols, :] += _by_head(dvs)
            dk_s[cols, :] += _by_head(dks)

    _run_tile(_compute, "k", q_blk, k_blk, block_q, block_k, causal=causal,
              offset=offset, sub=sub, band=band)

    @pl.when(t == n_t - 1)
    def _finalize():
        dk_ref[0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[:].astype(dv_ref.dtype)


def _bwd(scale, causal, dq_tile, dkv_tile, res, do, *, slopes=None, h_q=0,
         interpret=False, heads: _Heads | None = None, window: int | None = None):
    """``dq_tile`` / ``dkv_tile``: each launch's own ``(block_q, block_k)``.
    ``heads``, ``window``: as :func:`_fwd`; dO comes in and dq, dk, dv go out
    in the layout of q, k, v."""
    q, k, v, o, lse = res
    s_q, s_k = q.shape[1], k.shape[1]
    # v, do and dv at v's width
    bh, bh_k, h_q, h_kv, d, d_v, at_q, at_kv, pair, layout = _launch_of(q, k, v, h_q, heads)
    offset = s_k - s_q
    itemsize = q.dtype.itemsize
    group = h_q // h_kv
    kv = _kv_row(h_q, h_kv)

    # delta = rowsum(dO * o). Over head-major rows XLA's fused sum is cheap,
    # and over a pair's 64-wide heads too (0.5 ms a step of mpt125m-train).
    # Whole-lane heads read in place make it in the dq launch, from the o
    # block, and hand it on to dk/dv: XLA lays a [B, S, 20, 256] view out
    # sequence-minor and paid a relayout of the float32 product for the sum
    # (7 ms a step of glm47flash-train; PERF.md, PR 45)
    makes_delta = layout == IN_PLACE
    if makes_delta:
        delta = None
    elif pair:
        h = lse.shape[0] // q.shape[0]
        delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
            q.shape[0], s_q, h, -1), axis=-1)  # [B, s_q, H], then head-major like lse
        delta = jnp.transpose(delta, (0, 2, 1)).reshape(lse.shape)
    else:
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # [bh, s_q]
    # SUBLANE-replicated rows for TPU tiling (see _fwd)
    lse_b = _stats_to_blocks(lse, pair)
    delta_b = o if makes_delta else _stats_to_blocks(delta, pair)

    use_alibi = slopes is not None
    extra_inputs = [slopes] if use_alibi else []
    slope_spec = (
        [pl.BlockSpec((1, SUBLANE, LANE), lambda b, i, j: (b, 0, 0))] if use_alibi else []
    )

    block_q, block_k = dq_tile
    n_q = pl.cdiv(s_q, block_q)
    n_k = pl.cdiv(s_k, block_k)
    band = _band_of(window, causal, block_q, block_k, offset, n_q, n_k)
    banded = {} if band is None else {"band": band}
    kj = band.kv_block if band is not None else functools.partial(
        _kv_block, causal=causal, block_q=block_q, block_k=block_k, offset=offset, n_k=n_k)
    dq_specs = [pl.BlockSpec((1, block_q, d), lambda b, i, j: at_q(b, i))]
    dq_shapes = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    launch_dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
                          causal=causal, offset=offset, use_alibi=use_alibi,
                          sub=strip_rows("dq", block_q, block_k, causal=causal,
                                         offset=offset, window=window),
                          pair=pair, makes_delta=makes_delta,
                          **banded),
        grid=(bh, n_q, n_k if band is None else band.steps("q")),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: at_q(b, i)),  # q
            pl.BlockSpec((1, block_k, d), lambda b, i, j: at_kv(kv(b), kj(i, j))),  # k
            pl.BlockSpec((1, block_k, d_v), lambda b, i, j: at_kv(kv(b), kj(i, j))),  # v
            pl.BlockSpec((1, block_q, d_v), lambda b, i, j: at_q(b, i)),  # do
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j: (b, 0, i)),  # lse
            (pl.BlockSpec((1, block_q, d_v), lambda b, i, j: at_q(b, i)) if makes_delta  # o
             else pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j: (b, 0, i))),  # delta
        ] + slope_spec,
        out_specs=dq_specs + [
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, i, j: (b, 0, i))] if makes_delta
        else dq_specs[0],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=dq_shapes + [jax.ShapeDtypeStruct((bh, SUBLANE, s_q), jnp.float32)]
        if makes_delta else dq_shapes[0],
        interpret=interpret,
        **_vmem_params(launch_vmem_bytes("dq", block_q, block_k, d, itemsize, d_v, layout,
                                         window)),
    )
    with _kernel_scope("flash_dq" if band is None else FLASH_SWA_DQ_KERNEL):
        dq = launch_dq(q, k, v, do, lse_b, delta_b, *extra_inputs)
    if makes_delta:
        dq, delta_b = dq

    # dkv grid rows are the kv STORAGE rows; the inner dim sweeps the
    # group's q heads × q blocks so each kv row accumulates its whole
    # gradient in one VMEM scratch pass (GQA-native: no repeated kv, no
    # cross-row reduction)
    block_q, block_k = dkv_tile
    n_q = pl.cdiv(s_q, block_q)
    n_k = pl.cdiv(s_k, block_k)
    band = _band_of(window, causal, block_q, block_k, offset, n_q, n_k)
    banded = {} if band is None else {"band": band}
    if band is not None:
        n_q = band.steps("k")  # what one member of the group sweeps of a k tile

    def qrow(b, t):
        if group == 1:
            return b
        return (b // h_kv) * h_q + (b % h_kv) * group + t // n_q

    def qi(j, t):
        if band is not None:
            return band.q_block(j, t % n_q)
        return _q_block(t % n_q, j, causal=causal, block_q=block_q,
                        block_k=block_k, offset=offset, n_q=n_q)

    launch_dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
                          causal=causal, offset=offset, use_alibi=use_alibi, n_q=n_q,
                          sub=strip_rows("dkv", block_q, block_k, causal=causal,
                                         offset=offset, window=window),
                          pair=pair, **banded),
        grid=(bh_k, n_k, group * n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, t: at_q(qrow(b, t), qi(j, t))),  # q
            pl.BlockSpec((1, block_k, d), lambda b, j, t: at_kv(b, j)),  # k
            pl.BlockSpec((1, block_k, d_v), lambda b, j, t: at_kv(b, j)),  # v
            pl.BlockSpec((1, block_q, d_v), lambda b, j, t: at_q(qrow(b, t), qi(j, t))),  # do
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, j, t: (qrow(b, t), 0, qi(j, t))),  # lse
            pl.BlockSpec((1, SUBLANE, block_q), lambda b, j, t: (qrow(b, t), 0, qi(j, t))),  # delta
        ] + (
            [pl.BlockSpec((1, SUBLANE, LANE), lambda b, j, t: (qrow(b, t), 0, 0))]
            if use_alibi else []
        ),
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, t: at_kv(b, j)),
            pl.BlockSpec((1, block_k, d_v), lambda b, j, t: at_kv(b, j)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        **_vmem_params(launch_vmem_bytes("dkv", block_q, block_k, d, itemsize, d_v, layout,
                                         window)),
    )
    with _kernel_scope("flash_dkv" if band is None else FLASH_SWA_DKV_KERNEL):
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
# dk/dv launches: ``TilePlan.blocks``. ``heads`` (static) is ``None`` for
# head-major operands, a ``_Heads`` for operands read in place; what is saved
# for the backward is then the projections' own q, k, v and the o that
# ``out_proj`` reads, no copy of any. ``window`` (static) makes the three
# launches the banded ones.
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, slopes, scale, causal, tiles, interpret, h_q=0, heads=None, window=None):
    o, _ = _fwd(q, k, v, scale=scale, causal=causal, block_q=tiles[0][0],
                block_k=tiles[0][1], slopes=slopes, h_q=h_q, interpret=interpret,
                heads=heads, window=window)
    return o


def _flash_fwd(q, k, v, slopes, scale, causal, tiles, interpret, h_q=0, heads=None,
               window=None):
    o, lse = _fwd(q, k, v, scale=scale, causal=causal, block_q=tiles[0][0],
                  block_k=tiles[0][1], slopes=slopes, h_q=h_q, interpret=interpret,
                  heads=heads, window=window)
    return o, (q, k, v, o, lse, slopes)


def _flash_bwd(scale, causal, tiles, interpret, h_q, heads, window, res, do):
    q, k, v, o, lse, slopes = res
    dq, dk, dv = _bwd(scale, causal, tiles[1], tiles[2], (q, k, v, o, lse), do,
                      slopes=slopes, h_q=h_q, interpret=interpret, heads=heads,
                      window=window)
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
    window: int | None = None,
) -> jax.Array:
    """Flash attention over ``[batch, seq, heads, d_head]`` inputs.

    ``window`` (causal only, no ALiBi): query ``i`` sees keys ``i - window <
    j <= i``, its own among them, and the three launches walk that band and
    not the causal square. A window that holds every key of the sequence is
    the causal call itself, the same program.

    Which arrays the three launches read follows the head widths
    (:func:`flash_layout`): at whole-lane widths (``d_head % 128 == 0``, v's
    too) the inputs' own ``[batch, seq, heads * d_head]`` arrays, a head a
    column block (``in_place``); at 64 / 64 with as many k / v heads as q
    heads (an even number) the same arrays, a column block a pair of heads
    whose other half is masked where a lone head's pad would be zero
    (``head_pairs``); otherwise transposed, padded ``[batch * heads, seq,
    d_pad]`` copies, and the output transposed back (``head_major``). The
    results are the same numbers; a span attribute (``TilePlan.attrs``) says
    which layout a training step took.

    ``block_q`` / ``block_k`` ``None`` (what the models pass) derives each
    launch's tile from the shapes (:func:`pick_tiles`); an explicit value is
    used as given for all three launches and must divide the sequence.

    Grouped-query attention is native: ``k``/``v`` may carry fewer heads
    than ``q`` (``h_q % h_kv == 0``) — the kernel index-maps each q head
    onto its kv group row, so the repeated-kv tensor is never materialized
    in HBM (fwd reads and bwd dk/dv are kv-row-major).

    ``v`` may have a head width of its own (``[batch, seq, heads, d_v]``):
    the output has it too, and each width is padded to whole lanes by itself.

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
    s_k, d_v = k.shape[1], v.shape[3]
    scale = 1.0 / (d**0.5) if scale is None else float(scale)
    if window is not None:
        if window < 1 or not causal or alibi:
            raise ValueError("window needs window >= 1, causal=True and no alibi")
        if window >= s_k:  # no causal pair is further apart: the causal call
            window = None

    layout = flash_layout(h, h_kv, d, d_v)
    # a pair's block is 128 lanes wide, as the lone head's padded one is
    d_pad = lane_padded(d)
    tiles = pick_tiles(s_q, s_k, d_pad, q.dtype.itemsize, h // h_kv, causal=causal,
                       block_q=block_q, block_k=block_k, d_v_pad=lane_padded(d_v),
                       layout=layout, window=window).blocks

    def bh_slopes(pair=False):
        if not alibi:
            return None
        from photon_tpu.ops.attention import alibi_slopes as default_slopes

        h_slopes = alibi_slopes if alibi_slopes is not None else default_slopes(h)
        return _bh_slopes(h_slopes.astype(jnp.float32), b * h, pair)

    heads = _Heads.of(layout, h, h_kv)
    if heads is not None:
        # the projections' own arrays: [B, S, H, D] -> [B, S, H·D] moves nothing
        slopes = bh_slopes(heads.pair)
        o = _flash(q.reshape(b, s_q, h * d), k.reshape(b, s_k, h_kv * d),
                   v.reshape(b, s_k, h_kv * d_v), slopes, scale, causal, tiles, interpret,
                   0, heads, window)
        return o.reshape(b, s_q, h, d_v)

    def to_bh(x, s, heads):
        width = x.shape[3]
        x = jnp.transpose(x, (0, 2, 1, 3)).reshape(b * heads, s, width)
        if lane_padded(width) != width:
            x = jnp.pad(x, ((0, 0), (0, 0), (0, lane_padded(width) - width)))
        return x

    qb, kb, vb = to_bh(q, s_q, h), to_bh(k, s_k, h_kv), to_bh(v, s_k, h_kv)
    ob = _flash(qb, kb, vb, bh_slopes(), scale, causal, tiles, interpret,
                h if h_kv != h else 0, None, window)
    o = ob[..., :d_v].reshape(b, h, s_q, d_v)
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
