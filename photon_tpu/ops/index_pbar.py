"""The heads' mean probability over the picked keys, in Pallas.

What the indexer's alignment loss aligns to (``ops/dsa.index_loss``): for the
queries ``t`` of one chunk and the keys ``s`` of its band,

    pbar[t, s] = (1 / H) * sum_h exp(q_h[t] . k_g(h)[s] * scale - lse_h[t])

on the picked keys and 0 elsewhere, ``lse`` the masked attention's own
log-sum-exp. In ``jax.numpy`` the ``H`` heads' ``[H, chunk, S]`` float32
scores cross HBM twice for a result a thirty-second their size; here every
head's score tile lives and dies in VMEM.

One launch a query chunk, forward only (``pbar`` is detached), from the
masked flash kernel's parts (``ops/masked_flash_attention.py``): a head's
tile is ``_probabilities``' product (the operands' dtype, float32
accumulation, ``* scale``) and its ``exp(s - lse)`` in float32, under
``pick_block``'s tile rule and the dense kernel's VMEM constants; a group's
heads are side by side in one block, so no row map is needed. The mask's tile is
applied ONCE, to the heads' sum, as the ``jax.numpy`` line does, and not
before each of the 32 exponentials as the attention's backward has to: an
unpicked key's term is finite or ``inf`` and never reaches the result (a
tenth of the launch's time on the chip, PERF.md section 6, PR 36). The grid
is ``(key tiles, key-value groups)``, the groups innermost: the output's
``[chunk, block_k]`` float32 block stays in VMEM while a tile's groups, and
in the body each group's heads, add into it, and is written once, divided by
the head count and zeroed where no key is picked (a query without a key
gives zeros). The mask is causal by construction, so a key tile wholly past
the chunk's last query is dead: its body is predicated off from the chunk's
index (a prefetched scalar), it names the last live tile's blocks so that
nothing is fetched for it, and its output is the zeros it was initialised
to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_tpu.ops.flash_attention import (
    SCORE_TEMPS,
    SUBLANE,
    VMEM_SCOPED_DEFAULT,
    VMEM_SLACK,
)
from photon_tpu.ops.masked_flash_attention import pick_block

#: the launch's scope inside ``dsa/index_loss``: NOT ``multihead_attention``,
#: by which the benchmark's readers find the attention's own launches
INDEX_PBAR_SCOPE = "index_pbar"
#: the widest key tile: on the chip a layer's pass took 9.36 / 9.18 / 9.34 ms
#: at 512 / 1,024 / 2,048 keys (PERF.md section 6, PR 36: fewer grid steps
#: against more dead columns in the tile the chunk's last query falls in)
BLOCK_K_CAP = 1024


def key_block(n_keys: int) -> int:
    """The launch's key tile for a band of ``n_keys`` keys."""
    return pick_block(n_keys, BLOCK_K_CAP)


def live_key_tiles(c, chunk: int, block_k: int):
    """How many key tiles of ``block_k`` hold a key that a query of chunk
    ``c`` may see: the launch computes these and skips the rest of its band."""
    return ((c + 1) * chunk - 1) // block_k + 1


def _vmem_bytes(chunk: int, per: int, block_k: int, d: int, itemsize: int) -> int:
    """VMEM the launch needs: every BlockSpec'd operand and the result twice
    (the pipeline's two buffers), and the ``[chunk, block_k]`` float32
    temporaries of one head (``flash_attention.SCORE_TEMPS`` of them, and the
    mask's upcast)."""
    tile = chunk * block_k
    piped = (per * chunk * d * itemsize + block_k * d * itemsize
             + SUBLANE * per * chunk * 4 + tile + 4 * tile)
    return 2 * piped + int((SCORE_TEMPS + 1) * 4 * tile) + VMEM_SLACK


def _kernel(c_ref, q_ref, k_ref, mask_ref, lse_ref, o_ref, *, scale, chunk, per, block_k):
    j, g, groups = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    live = j < live_key_tiles(c_ref[0], chunk, block_k)

    @pl.when(g == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _compute():
        for h in range(per):  # the group's heads, laid side by side
            rows = pl.ds(h * chunk, chunk)
            s = jax.lax.dot_general(
                q_ref[0, rows], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # [chunk, block_k]
            o_ref[...] += jnp.exp(s - lse_ref[0, 0, rows][:, None])

    @pl.when(live & (g == groups - 1))
    def _finalize():
        picked = mask_ref[0].astype(jnp.int32) != 0
        o_ref[...] = jnp.where(picked, o_ref[...] / (per * groups), 0.0)


def head_mean_probabilities(q: jax.Array, k: jax.Array, lse: jax.Array, mask: jax.Array,
                            c: jax.Array, *, scale: float, n_keys: int,
                            interpret: bool = False,
                            block_k: int | None = None) -> jax.Array:
    """``pbar [chunk, n_keys]`` float32 of one query chunk against the first
    ``n_keys`` keys of its row.

    ``q [G, H/G * chunk, D]``: a key-value group's heads side by side, head
    major; ``k [G, S, D]``; ``lse [G, H/G * chunk]`` float32 in ``q``'s row
    order; ``mask [chunk, S]`` int8 (non-zero = picked; no key past its
    query); ``c`` the chunk's index in the row, an int32 scalar. ``n_keys``
    (static) covers every key the chunk's queries may see; ``block_k``: the
    key tile, :func:`key_block`'s where none is given."""
    groups, rows, d = q.shape
    chunk, s = mask.shape
    per = rows // chunk
    block_k = block_k or key_block(n_keys)
    if n_keys % block_k or n_keys > s or rows != per * chunk:
        raise ValueError(f"bad shapes: q {q.shape}, mask {mask.shape}, n_keys {n_keys}")

    def kj(j, c_ref):  # a dead tile repeats the last live one: no copy
        return jnp.minimum(j, live_key_tiles(c_ref[0], chunk, block_k) - 1)

    launch = pl.pallas_call(
        functools.partial(_kernel, scale=scale, chunk=chunk, per=per, block_k=block_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_keys // block_k, groups),
            in_specs=[
                pl.BlockSpec((1, rows, d), lambda j, g, c: (g, 0, 0)),
                pl.BlockSpec((1, block_k, d), lambda j, g, c: (g, kj(j, c), 0)),
                pl.BlockSpec((1, chunk, block_k), lambda j, g, c: (0, 0, kj(j, c))),
                pl.BlockSpec((1, SUBLANE, rows), lambda j, g, c: (g, 0, 0)),
            ],
            out_specs=pl.BlockSpec((chunk, block_k), lambda j, g, c: (0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((chunk, n_keys), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=max(_vmem_bytes(chunk, per, block_k, d, q.dtype.itemsize),
                                 VMEM_SCOPED_DEFAULT)),
        interpret=interpret,
    )
    with jax.named_scope(INDEX_PBAR_SCOPE):
        return launch(
            jnp.reshape(c, (1,)).astype(jnp.int32), q, k, mask[None],
            jnp.broadcast_to(lse[:, None, :], (groups, SUBLANE, rows)))
