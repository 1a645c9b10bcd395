"""The selection's threshold, searched from VMEM.

What ``ops/dsa.select_keys`` needs of a query chunk's index scores ``[chunk,
n_keys]`` float32 is one number a query: the ``k``-th largest of its row
(``ops/dsa.kth_largest``: the largest ``v`` with ``count(x >= v) >= k``, found
from the top of the float's order-preserving bit pattern). In ``jax.numpy``
that is eight fused passes over the chunk in HBM, 15 compare-and-counts an
element each (radix 16, because a pass there costs a read of the tile from
memory). Here a block of ``rows`` queries' scores is fetched once and
searched where it lies, so a pass costs no memory traffic and the radix can
be what the vector unit likes: ``BITS`` bits a pass, ``2**BITS - 1``
compare-and-counts an element, ``32 / BITS`` passes.

One launch a query chunk, forward only (the selection takes no gradient).
The grid walks the chunk's row blocks; the next block's scores are copied in
while this one is searched. In the body:

- every float becomes the int32 whose signed order is the floats' (``-0.0``
  under ``0.0``, ``-inf`` smallest), once, into a scratch tile: an integer
  compare neither flushes subnormals nor merges the zeros, so the result is
  ``kth_largest``'s to the bit;
- a pass walks the tile's ``n_keys / 128`` column tiles adding ``key >=
  candidate`` into per-lane counts ``[rows, 128]``, reduces them across the
  lanes once, and keeps the largest candidate digit that ``k`` keys reach;
- the found key goes back to its float: ``tau [chunk, 1]``.

The answer is an exact function of the scores: any two correct searches
return the same 32 bits, and the tests hold the launch to ``kth_largest`` and
to a sort by equality.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_tpu.ops.flash_attention import (
    LANE,
    SUBLANE,
    VMEM_BUDGET,
    VMEM_SCOPED_DEFAULT,
    VMEM_SLACK,
)

#: the launch's scope inside ``dsa/select``
INDEX_SELECT_SCOPE = "index_select"
#: rows of a launch's block, at most: the per-lane counts and the candidates
#: of a block are ``2 * (2**BITS - 1) * ROW_BLOCK / 8`` vector registers, and
#: its scores lie in VMEM three times (two pipeline buffers and the keys).
#: On the chip (``scripts/index_select_ladder.py``; PERF.md section 6, PR 55)
#: the cell's searches of a step took 57.4 / 48.0 / 44.1 ms alone at 32 / 64 /
#: 128 rows: 128 spill registers and still pay a pass's end half as often,
#: for 25 MB of VMEM beside the 32 MB score tile XLA keeps there itself
ROW_BLOCK = 64
#: bits of the key decided a pass: 2 are 9 vector operations a register
#: where 1 is 3 (48.0 ms a step against 51.5 at 64 rows: the launch is bound
#: by its compares, not its loads)
BITS = 1
#: column tiles a trip of the counting loop walks, where they divide
UNROLL = 8

_SIGN = np.int32(-2 ** 31)
_MAGNITUDE = np.int32(2 ** 31 - 1)


def _vmem_bytes(rows: int, n_keys: int) -> int:
    """VMEM the launch needs: the scores' block twice (the pipeline's two
    buffers), the keys' scratch, the result's lane-padded block twice."""
    return 3 * rows * n_keys * 4 + 2 * rows * LANE * 4 + VMEM_SLACK


def row_block(chunk: int, n_keys: int) -> int:
    """The launch's row block for a chunk of ``chunk`` queries against
    ``n_keys`` keys, or 0 where the shape has none: the keys whole lanes, the
    block whole sublanes and a divisor of the chunk, its tile within
    ``flash_attention.VMEM_BUDGET``."""
    if n_keys % LANE or chunk % SUBLANE:
        return 0
    for rows in range(min(ROW_BLOCK, chunk) // SUBLANE * SUBLANE, 0, -SUBLANE):
        if chunk % rows == 0 and _vmem_bytes(rows, n_keys) <= VMEM_BUDGET:
            return rows
    return 0


def _ordered(bits: jax.Array) -> jax.Array:
    """A float's bits as the int32 whose signed order is the floats' order;
    its own inverse."""
    return bits ^ ((bits >> 31) & _MAGNITUDE)


def _kernel(x_ref, tau_ref, key_ref, *, k: int, bits: int):
    rows, n_keys = x_ref.shape
    tiles = n_keys // LANE
    step = math.gcd(tiles, UNROLL)

    def walk(body, carry):
        """``body(cols, carry)`` over the column tiles, ``step`` a trip
        (Mosaic unrolls a loop whole or not at all)."""
        def trip(i, carry):
            for u in range(step):
                carry = body(pl.ds(pl.multiple_of((i * step + u) * LANE, LANE), LANE), carry)
            return carry

        return jax.lax.fori_loop(0, tiles // step, trip, carry)

    def to_keys(cols, carry):
        key_ref[:, cols] = _ordered(jax.lax.bitcast_convert_type(x_ref[:, cols], jnp.int32))
        return carry

    walk(to_keys, None)

    # the search runs in the unsigned order with every word's top bit
    # flipped (the compares are signed): nothing found yet is the smallest
    # int32, and "set a digit" is an exclusive or, which clears that top bit
    def search(p, found):
        shift = 32 - bits * (p + 1)
        candidates = [jnp.broadcast_to(found ^ (jnp.int32(d) << shift), (rows, LANE))
                      for d in range(1, 2 ** bits)]

        def count(cols, counts):
            keys = key_ref[:, cols]
            return tuple(n + (keys >= c).astype(jnp.int32)
                         for n, c in zip(counts, candidates))

        counts = walk(count, tuple(jnp.zeros((rows, LANE), jnp.int32) for _ in candidates))
        # the counts fall with the digit: as many digits pass as the largest
        # (summed as floats, which hold a row's count exactly: one reduction
        # across the lanes where an int32 sum is two)
        digit = sum((jnp.sum(n.astype(jnp.float32), axis=-1, keepdims=True) >= k).astype(
            jnp.int32) for n in counts)
        return found ^ (digit << shift)

    # a loop, not a Python range: a step holds eight of these launches, and
    # every process lowers them before it can ask its compile cache
    found = jax.lax.fori_loop(0, 32 // bits, search, jnp.full((rows, 1), _SIGN, jnp.int32))
    tau_ref[...] = jax.lax.bitcast_convert_type(_ordered(found), jnp.float32)


def kth_largest(x: jax.Array, k: int, *, interpret: bool = False,
                rows: int | None = None, bits: int | None = None) -> jax.Array:
    """The ``k``-th largest entry of every row of ``x [C, S]`` float32 (no
    NaN; ``-inf`` entries count as smallest), to the bit what
    ``ops/dsa.kth_largest`` returns: ``[C]`` float32. ``rows``: the row
    block, :func:`row_block`'s where none is given; ``bits``: ``BITS``
    likewise (the ladder script walks both)."""
    chunk, n_keys = x.shape
    rows = rows or row_block(chunk, n_keys)
    bits = bits or BITS
    if (x.dtype != jnp.float32 or not rows or chunk % rows or rows % SUBLANE
            or n_keys % LANE or 32 % bits or not 1 <= k <= n_keys):
        raise ValueError(f"bad shapes: x {x.shape} {x.dtype}, k {k}, rows {rows}, bits {bits}")
    launch = pl.pallas_call(
        functools.partial(_kernel, k=k, bits=bits),
        grid=(chunk // rows,),
        in_specs=[pl.BlockSpec((rows, n_keys), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((chunk, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, n_keys), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(_vmem_bytes(rows, n_keys), VMEM_SCOPED_DEFAULT)),
        interpret=interpret,
        name="index_select_kth",
    )
    with jax.named_scope(INDEX_SELECT_SCOPE):
        return launch(x)[:, 0]
