"""The headwise gate on attention's output, one pass forward and one back.

``attn_gate: headwise`` (``models/mpt.py``) multiplies every head of the
attention's output by ``sigmoid(logits)``, one gate a head and token:

    g     = sigmoid(float32(logits))                       # [B, S, H]
    gated = dtype(float32(o) * g[..., None])               # o as [B, S, H, D]

Left to autodiff, XLA moves float32 ``[S, H, D]`` arrays through HBM three
times a layer (forward, recomputed, back) with the heads crossing from lanes
to sublanes for the broadcast, and hands ``out_proj``'s ``dx`` product a
float32 cotangent (PERF.md section 6, PR 50). :func:`head_gate` is the same
arithmetic with a pull-back of its own, on the arrays as they lie in memory:
``o`` and ``gated`` are the flash kernel's ``[B, S, H·D]`` (a head a column
block of ``D`` lanes) in the compute dtype, and so are ``dy`` and ``d_o``;

    d_o      = dtype(float32(dy) * g[..., None])
    d_logits = logits.dtype(sum_D(float32(dy) * float32(o)) * (g * (1 - g)))

both from one read of ``dy`` and ``o``, the sum over ``D`` in float32. The
residuals are ``o`` and ``logits``; float32 exists in registers only. The
sigmoid and its derivative stay ``jax.numpy`` on the ``[B, S, H]`` arrays (4 MB
a layer at 16,384 x 64): every value is bit for bit the two lines' above.

Where the shape allows (``D`` whole lanes, ``S`` a multiple of
``ROW_BLOCK``) and a TPU or the interpreter is there, each pass is one Pallas
launch over blocks of ``[ROW_BLOCK, H·D]`` with the ``[ROW_BLOCK, H]`` gate
beside it; every other shape (the tiny tests, a decode step of one position)
takes the ``jax.numpy`` expression inside the same ``custom_vjp``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_tpu.ops.flash_attention import LANE, VMEM_SLACK

#: rows of a launch's block: ``[ROW_BLOCK, H·D]`` of the compute dtype is
#: contiguous in memory (4 MiB at 64 heads of 128 in bf16). On the chip
#: (``scripts/head_gate_ladder.py``; PERF.md section 6, PR 50) 64 / 128 / 256
#: rows moved a pass's bytes at 598-639 GB/s alike, 256 the fastest pull-back
#: by a hundredth; 512 wants more VMEM than the launch asks for
ROW_BLOCK = 256


def uses_kernel(impl: str, interpret: bool, seq: int, d_head: int,
                x: jax.Array | None = None) -> bool:
    """Whether rows of ``seq`` tokens with ``d_head``-wide heads take the
    Pallas launches: by the shape, and by ``ops/attention.py``'s rule for
    where a kernel can run (``pallas`` on a TPU or anywhere under
    ``interpret``)."""
    # looked up at the call: the offline compile check swaps the function
    from photon_tpu.ops import flash_attention

    return (impl == "pallas" and d_head % LANE == 0 and seq % ROW_BLOCK == 0
            and (interpret or flash_attention.pallas_supported(x)))


def _by_head(x: jax.Array, heads: int) -> jax.Array:
    return x.reshape(*x.shape[:-1], heads, x.shape[-1] // heads)


def _forward_kernel(o_ref, g_ref, out_ref, *, heads: int, d: int):
    g = g_ref[...]
    for h in range(heads):  # a head: ``d`` lanes of the block, one column of the gate
        cols = pl.ds(h * d, d)
        out_ref[:, cols] = (o_ref[:, cols].astype(jnp.float32) * g[:, h:h + 1]).astype(
            out_ref.dtype)


def _backward_kernel(dy_ref, o_ref, g_ref, do_ref, dot_ref, *, heads: int, d: int):
    g = g_ref[...]
    column = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    dots = jnp.zeros_like(g)
    for h in range(heads):
        cols = pl.ds(h * d, d)
        dy = dy_ref[:, cols].astype(jnp.float32)
        do_ref[:, cols] = (dy * g[:, h:h + 1]).astype(do_ref.dtype)
        dot = jnp.sum(dy * o_ref[:, cols].astype(jnp.float32), axis=-1, keepdims=True)
        dots = jnp.where(column == h, dot, dots)
    dot_ref[...] = dots


def _launch(name: str, kernel, wide: tuple[jax.Array, ...], g: jax.Array, n_dots: int,
            interpret: bool):
    """One launch over row blocks: ``wide`` the ``[rows, H·D]`` operands, ``g``
    the ``[rows, H]`` float32 gate; one ``[rows, H·D]`` result, then ``n_dots``
    float32 ``[rows, H]`` ones."""
    rows, width = wide[0].shape
    heads = g.shape[-1]
    wide_spec = pl.BlockSpec((ROW_BLOCK, width), lambda i: (i, 0))
    head_spec = pl.BlockSpec((ROW_BLOCK, heads), lambda i: (i, 0))
    # every block twice (the pipeline's two buffers); a ``[ROW_BLOCK, H]``
    # block is padded to whole lanes
    piped = ((len(wide) + 1) * ROW_BLOCK * width * wide[0].dtype.itemsize
             + (1 + n_dots) * ROW_BLOCK * LANE * 4)
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, d=width // heads),
        grid=(rows // ROW_BLOCK,),
        in_specs=[wide_spec] * len(wide) + [head_spec],
        out_specs=[wide_spec] + [head_spec] * n_dots,
        out_shape=[jax.ShapeDtypeStruct(wide[0].shape, wide[0].dtype)]
        + [jax.ShapeDtypeStruct(g.shape, jnp.float32)] * n_dots,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=2 * piped + 8 * VMEM_SLACK),
        interpret=interpret,
        name=name,
    )(*wide, g)


def _flat(x: jax.Array) -> jax.Array:
    return x.reshape(-1, x.shape[-1])


def _forward(o, g, kernel: bool, interpret: bool):
    if kernel:
        (out,) = _launch("head_gate_fwd", _forward_kernel, (_flat(o),), _flat(g), 0, interpret)
        return out.reshape(o.shape)
    heads = g.shape[-1]
    return (_by_head(o, heads).astype(jnp.float32) * g[..., None]).astype(o.dtype).reshape(
        o.shape)


def _backward(dy, o, g, kernel: bool, interpret: bool):
    """``d_o`` and the heads' float32 ``sum_D(dy * o)``."""
    if kernel:
        d_o, dots = _launch("head_gate_bwd", _backward_kernel, (_flat(dy), _flat(o)), _flat(g), 1,
                            interpret)
        return d_o.reshape(o.shape), dots.reshape(g.shape)
    heads = g.shape[-1]
    dy32 = _by_head(dy, heads).astype(jnp.float32)
    d_o = (dy32 * g[..., None]).astype(o.dtype).reshape(o.shape)
    return d_o, jnp.sum(dy32 * _by_head(o, heads).astype(jnp.float32), axis=-1)


def _gate(logits: jax.Array) -> jax.Array:
    return jax.nn.sigmoid(logits.astype(jnp.float32))


def _gate_slope(g: jax.Array) -> jax.Array:
    """The sigmoid's derivative from its value, grouped as autodiff's."""
    return g * (1.0 - g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _head_gate(o, logits, kernel: bool = False, interpret: bool = False):
    return _forward(o, _gate(logits), kernel, interpret)


def _head_gate_fwd(o, logits, kernel: bool, interpret: bool):
    return _head_gate(o, logits, kernel, interpret), (o, logits)


def _head_gate_bwd(kernel: bool, interpret: bool, residuals, dy):
    # (traced under the scopes of the call it pulls back: the caller's
    # ``attn/gate`` holds these operations too)
    o, logits = residuals
    g = _gate(logits)
    d_o, dots = _backward(dy, o, g, kernel, interpret)
    return d_o, (dots * _gate_slope(g)).astype(logits.dtype)


_head_gate.defvjp(_head_gate_fwd, _head_gate_bwd)


def head_gate(o: jax.Array, logits: jax.Array, *, impl: str = "xla",
              interpret: bool = False) -> jax.Array:
    """``o [B, S, H·D]`` times ``sigmoid(logits [B, S, H])`` by head, in
    ``o``'s dtype; differentiable in both."""
    heads = logits.shape[-1]
    if o.ndim != 3 or o.shape[:-1] != logits.shape[:-1] or o.shape[-1] % heads:
        raise ValueError(f"bad shapes: o {o.shape}, logits {logits.shape}")
    kernel = uses_kernel(impl, interpret, o.shape[-2], o.shape[-1] // heads, o)
    gate = functools.partial(_head_gate, kernel=kernel, interpret=interpret)
    # a Mosaic launch cannot be partitioned by GSPMD: on a mesh each shard of
    # rows (data, fsdp, expert) and of heads (tensor) runs its own, which is
    # exact for a multiply by head and token (``ops/attention.py``'s rule)
    from photon_tpu.parallel.context import current_mesh

    mesh = current_mesh()
    if kernel and mesh is not None and any(
            mesh.shape.get(a, 1) > 1 for a in ("data", "fsdp", "expert", "tensor")):
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        spec = P(("data", "fsdp", "expert"), None, "tensor")
        gate = shard_map(gate, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
                         check_vma=False)
    return gate(o, logits)
