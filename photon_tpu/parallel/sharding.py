"""GSPMD sharding rules for MPT parameters, batches and optimizer state.

The reference's parallelism plumbing — FSDP FULL_SHARD config
(``mpt-125m.yaml:85-92``), TP layer plan (``trainer_utils.py:1640-1648``) —
becomes a table of ``PartitionSpec`` rules here. XLA inserts the
all-gather/reduce-scatter collectives over ICI; nothing else to wire.

Layout logic (params carry a leading ``[n_layers]`` scan axis):
- ``wqkv``/``up_proj`` kernels  [L, D, F]: column-parallel — F on ``tensor``,
  D on ``fsdp``.
- ``out_proj``/``down_proj``    [L, F, D]: row-parallel — F on ``tensor``,
  D on ``fsdp``.
- ``wte`` [V, D]: V on ``fsdp``, D on ``tensor``. ``wpe`` [S, D]: D on fsdp.
- LayerNorm scales: replicated (tiny).
- Batches [B, S]: B over (``data``, ``fsdp``) — fsdp is data-parallel with
  sharded state, exactly ZeRO-3 — and S over ``sequence``.

Any dimension not divisible by its mesh axis is replicated instead (with the
axis silently dropped), keeping small/odd shapes valid on any mesh.
"""

from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ordered (path-regex, spec) rules; first match wins. Specs are written for
# the [L, in, out] stacked-block layout; non-block params are 1-2D. The
# leading layer axis of every in-block param is sharded over ``pipe`` —
# each pipeline stage owns its contiguous slab of layers (a no-op at
# pipe=1, the default).
_RULES: list[tuple[str, P]] = [
    (r"wte/embedding$", P("fsdp", "tensor")),
    (r"^wpe$", P(None, "fsdp")),
    # MoE (ops/moe.py): experts shard over `expert`; inner dims follow the
    # dense column/row-parallel convention
    (r"router$", P("pipe", "fsdp", None)),
    (r"router_bias$", P("pipe")),
    (r"(moe_up|moe_gate)$", P("pipe", "expert", "fsdp", "tensor")),
    (r"moe_down$", P("pipe", "expert", "tensor", "fsdp")),
    # latent attention: the down-projections' small outputs (a rank, the
    # shared rotary key) stay whole; the up-projections split their heads
    (r"(q_a_proj|kv_a_proj)/kernel$", P("pipe", "fsdp", None)),
    (r"(q_b_proj|kv_b_proj)/kernel$", P("pipe", None, "tensor")),
    (r"(q_a_norm|kv_a_norm)/scale$", P("pipe")),
    # learned sparse attention (models/mpt.py, ops/dsa.py): the indexer's
    # projections are small and their outputs (16 heads of 64, one key head,
    # a weight a head) are scored against the whole row, so they stay whole;
    # the per-head q / k norms and the indexer's key norm are replicated
    (r"idx_(q|k|w)_proj/kernel$", P("pipe", "fsdp", None)),
    (r"idx_(q|k|w)_proj/bias$", P("pipe")),
    (r"(idx_k_norm|q_norm|k_norm)/(scale|bias)$", P("pipe")),
    # a Mamba-2 mixer (models/mpt.py, ops/ssd.py): the in-projection's columns
    # are z | x B C | dt, whose boundaries no tensor split respects, so they
    # stay whole (its `out_proj` is row-parallel like attention's, below); the
    # depthwise convolution and the per-head scalars are small and replicated.
    # A gated short convolution (`conv` layers) has the same three names: its
    # in-projection's columns are B | C | u, whole for the same reason, its
    # 3-tap `conv_kernel` replicated. (An expert stack under `blocks_<i>`
    # matches the MoE rules above by its leaves' names, as under `blocks`.)
    (r"in_proj/kernel$", P("pipe", "fsdp", None)),
    (r"(conv_kernel|conv_bias|A_log|dt_bias|D)$", P("pipe")),
    (r"mamba_norm/scale$", P("pipe")),
    # hyper-connected residual streams (models/mpt.py): a sublayer's maps read
    # every stream's whole width at each token through `phi [2n + n^2, n D]`,
    # which with its bias and three scales stays whole (1.4 MB a sublayer)
    (r"hc_[12]_(phi|b|alpha)$", P("pipe")),
    # `up_proj` etc. also match the dropless layer's shared expert
    # (`shared_up_proj`, ...): the same column / row convention
    (r"(wqkv|up_proj|gate_proj|q_proj|k_proj|v_proj)/kernel$", P("pipe", "fsdp", "tensor")),
    (r"(out_proj|down_proj)/kernel$", P("pipe", "tensor", "fsdp")),
    (r"(wqkv|up_proj|gate_proj|q_proj|k_proj|v_proj)/bias$", P("pipe", "tensor")),
    (r"(out_proj|down_proj)/bias$", P("pipe", "fsdp")),
    # the headwise gate on attention's output: a column a head, split like q's
    (r"attn_gate/kernel$", P("pipe", "fsdp", "tensor")),
    (r"attn_gate/bias$", P("pipe", "tensor")),
    (r"lm_head/kernel$", P("tensor", "fsdp")),
    (r"(ln_1|ln_2)/(scale|bias)$", P("pipe")),
    (r"ln_f/(scale|bias)$", P()),
]


def _fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop axes that don't divide the dimension (or overflow rank), and
    axes the mesh doesn't have (a spec can't shard over a missing axis)."""
    out = []
    for i, dim in enumerate(shape):
        axis = spec[i] if i < len(spec) else None
        if axis is None:
            out.append(None)
            continue
        names = axis if isinstance(axis, tuple) else (axis,)
        if any(a not in mesh.shape for a in names):
            out.append(None)
            continue
        axis_size = int(np.prod([mesh.shape[a] for a in names]))
        out.append(axis if dim % axis_size == 0 else None)
    return P(*out)


def param_specs(params: Any, mesh: Mesh) -> Any:
    """Pytree of PartitionSpec matching ``params`` structure."""

    def spec_for(path, leaf) -> P:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        for pattern, spec in _RULES:
            if re.search(pattern, name):
                return _fit_spec(spec, np.shape(leaf), mesh)
        return P()  # replicate unknowns

    return jax.tree_util.tree_map_with_path(spec_for, params)


def shard_params(params: Any, mesh: Mesh) -> Any:
    """Place a host-resident param pytree onto the mesh per the rules."""
    specs = param_specs(params, mesh)
    return jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)), params, specs
    )


def batch_spec(mesh: Mesh) -> P:
    """Tokens [B, S]: batch over data+fsdp+expert, sequence over the
    sequence axis. ``expert`` joins the batch axes (the standard GShard
    layout): tokens split over expert chips too, so the MoE dispatch
    lowers to all_to_alls and the dense layers get real data parallelism
    from the expert axis instead of replicated compute. A no-op on
    expert=1 meshes."""
    del mesh
    return P(("data", "fsdp", "expert"), "sequence")


def state_shardings(state: Any, mesh: Mesh) -> Any:
    """NamedSharding pytree for a :class:`~photon_tpu.train.TrainState`.

    Params follow the rule table; optimizer moments inherit their parameter's
    spec by shape lookup (ZeRO-3 semantics — optimizer state lives with the
    weight shard, reference: FSDP FULL_SHARD sharded state dicts,
    ``photon/utils.py:279-309``); scalars/counters are replicated.

    ``state`` may hold real arrays or ``jax.ShapeDtypeStruct`` (from
    ``jax.eval_shape``), so this also produces out_shardings for jit.
    """
    pspecs = param_specs(state.params, mesh)
    shape_to_spec: dict[tuple, P] = {}
    for leaf, spec in zip(jax.tree.leaves(state.params), jax.tree.leaves(pspecs)):
        shape_to_spec.setdefault(tuple(np.shape(leaf)), spec)

    def spec_of(leaf) -> P:
        return shape_to_spec.get(tuple(np.shape(leaf)), P())

    opt_specs = jax.tree.map(spec_of, state.opt_state)
    specs = state.replace(step=P(), params=pspecs, opt_state=opt_specs)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )
