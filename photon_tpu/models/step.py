"""What a model tells the training step: the counters its blocks sow, and the
static numbers of one step.

``train/train_step.py`` reads :data:`COUNTERS` and ``train/trainer.py`` calls
:func:`step_attrs`; neither knows a model family by name. A new family's
counter is a row here and a :func:`sow` in its block.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from photon_tpu.config.schema import SLIDING_ATTENTION, ModelConfig
from photon_tpu.utils.profiling import (
    DSA_INDEX_LOSS,
    DSA_PICKED_PAIRS,
    DSA_TILES_VISITED,
    MHC_SINKHORN_GAP,
    MOE_DISPATCH_ROWS_MOVED,
    MOE_DISPATCH_ROWS_STATIC,
    MOE_MAX_EXPERT_LOAD,
    MOE_ROWS_HELD,
    TRAINER_DSA_SPAN,
)


class Counter(NamedTuple):
    """One sown key: what the step makes of its per-layer values."""

    #: the step metric it becomes (``utils/profiling.py``); ``None`` stays
    #: inside the step
    metric: str | None
    #: over the layers of all stacks: ``sum``, ``max``, or ``by_stack`` (kept
    #: ``{stack: [layers, ...]}``, under the name of the stack that sowed it)
    layers: str
    #: over the microbatches of a step: ``add``, ``max`` or ``mean``
    microbatches: str
    #: its weight in the objective: a number, the name of the ``ModelConfig``
    #: field that holds it, or ``None`` for a counter that is only read
    weight: str | float | None = None


#: the rows of every routed expert (``ops/moe.py``'s ``expert_rows``): what the
#: step's balancing rule moves each stack's selection bias by
EXPERT_ROWS = "moe_expert_rows"

#: every key a block may sow into ``intermediates``. ``moe_aux`` is the
#: capacity router's load-balance loss; the other ``moe_*`` are the names
#: ``ops/moe.dropless_moe_mlp``'s ``counters`` returns behind ``moe_``; the
#: ``dsa_*`` are the sparse-attention layers' (``MPTBlock._sparse_attention``),
#: whose index loss gives the indexer's parameters their only gradient;
#: ``mhc_sinkhorn_gap`` is a sublayer's mixing matrix's distance from doubly
#: stochastic (``_hc_read_in``)
COUNTERS: dict[str, Counter] = {
    "moe_aux": Counter(None, "sum", "add", weight="moe_aux_weight"),
    "moe_rows_held": Counter(MOE_ROWS_HELD, "sum", "add"),
    "moe_dispatch_rows_moved": Counter(MOE_DISPATCH_ROWS_MOVED, "sum", "add"),
    "moe_dispatch_rows_static": Counter(MOE_DISPATCH_ROWS_STATIC, "sum", "add"),
    "moe_max_expert_load": Counter(MOE_MAX_EXPERT_LOAD, "max", "max"),
    EXPERT_ROWS: Counter(None, "by_stack", "add"),
    "dsa_picked_pairs": Counter(DSA_PICKED_PAIRS, "sum", "add"),
    "dsa_tiles_visited": Counter(DSA_TILES_VISITED, "sum", "add"),
    "dsa_index_loss": Counter(DSA_INDEX_LOSS, "sum", "mean", weight=1.0),
    "mhc_sinkhorn_gap": Counter(MHC_SINKHORN_GAP, "max", "max"),
}


def sow(block: nn.Module, key: str, value: jax.Array) -> None:
    """``block.sow`` into ``intermediates`` of a key :data:`COUNTERS` has a row
    for; a key without one raises where the block is traced. (A no-op where
    the collection is immutable, as in every inference apply.)"""
    if key not in COUNTERS:
        raise KeyError(f"{key!r} is sown and has no row in models/step.COUNTERS")
    block.sow("intermediates", key, value)


class StepAttrs(NamedTuple):
    """The static numbers of one training step, as span attributes."""

    #: of ``trainer/steps``
    steps: dict[str, Any]
    #: of the spans inside ``trainer/fence``, by span name
    #: (``utils/profiling.FENCE_SPANS`` has their measured attrs)
    fence: dict[str, dict[str, Any]]


def _flash_attrs(cfg: ModelConfig) -> dict[str, str]:
    """The tiles the flash kernel takes at this model's shapes and how many of
    its grid steps are live. Empty unless the step holds the kernel."""
    from photon_tpu.ops.flash_attention import (
        flash_layout, lane_padded, pallas_supported, pick_tiles)

    if cfg.attn_impl != "pallas" or not (cfg.attn_interpret or pallas_supported(None)):
        return {}
    s = cfg.max_seq_len
    if cfg.sparse_attention:
        # the masked kernel's plan; which of its tiles are live is data
        # (``trainer/dsa`` has the count)
        from photon_tpu.ops.masked_flash_attention import LAUNCHES, plan_tiles

        return {"flash_tiles": " ".join(
            f"{n}={bq}x{bk}" for n, (bq, bk) in zip(LAUNCHES, plan_tiles(s, s)))}
    d_v = cfg.v_head_dim if cfg.latent_attention else cfg.d_head
    n_kv = cfg.n_kv_heads or cfg.n_heads

    def plan(n_heads: int, window: int | None) -> dict[str, str]:
        # the layout the launches read, by the model's own heads (a shard of
        # a tensor-parallel mesh applies the same rule to its local ones)
        layout = flash_layout(n_heads, n_kv, cfg.d_head, d_v)
        return pick_tiles(
            s, s, lane_padded(cfg.d_head), jnp.dtype(cfg.compute_dtype).itemsize,
            n_heads // n_kv, d_v_pad=lane_padded(d_v), layout=layout, window=window,
        ).attrs(layout, banded=window is not None)

    told = plan(cfg.n_heads, None) if cfg.full_attention_layers else {}
    if cfg.swa_layers and cfg.sliding_window < s:  # else the causal launches
        sliding = cfg.attention_kind(SLIDING_ATTENTION)
        told.update(plan(sliding.n_heads, sliding.window))
    return told


def _selection_attrs(cfg: ModelConfig, batch_rows: int) -> dict[str, Any]:
    """What a step's selection is measured against: the causal (query, key)
    pairs of every layer and row and the forward tiles of the masked kernel
    that hold one; which path makes the index loss's ``pbar`` (``ops/dsa.
    uses_kernel``: the Pallas launch or ``jax.numpy``), and the key tiles its
    launches of a step compute and skip (the skipped ones are dead by the
    causal rule; every layer and row, twice under ``remat``); which path
    searches the selection's thresholds (``ops/dsa.selects_in_vmem``: the
    launch of ``ops/index_select.py`` or ``jax.numpy``), and its launches of
    a step: one a query chunk, of every layer and row, twice under ``remat``."""
    from photon_tpu.ops import dsa
    from photon_tpu.ops.flash_attention import live_tiles
    from photon_tpu.ops.masked_flash_attention import plan_tiles

    s = cfg.max_seq_len
    rows = cfg.n_layers * batch_rows
    tiles, _ = live_tiles(s, s, *plan_tiles(s, s)[0])
    kernel = dsa.uses_kernel(cfg.attn_impl, cfg.attn_interpret)
    computed, skipped = dsa.index_loss_tiles(s, cfg.dsa_chunk)
    passes = (2 if cfg.remat else 1) * rows
    launches = passes if kernel else 0
    select = dsa.selects_in_vmem(cfg.attn_impl, cfg.attn_interpret, s, cfg.dsa_chunk,
                                 cfg.dsa_topk)
    return {"causal_pairs": float(rows * s * (s + 1) // 2),
            "tiles_causal": float(rows * tiles),
            "index_loss_kernel": kernel, "index_loss_tiles": launches * computed,
            "index_loss_tiles_skipped": launches * skipped,
            "select_kernel": select,
            "select_launches": passes * (s // min(cfg.dsa_chunk, s)) if select else 0}


def step_attrs(cfg: ModelConfig, batch_rows: int) -> StepAttrs:
    """The static numbers of a training step of ``batch_rows`` rows: told
    once, where the shapes are known, and not per launch. A kind of layer the
    model lacks adds no key."""
    steps: dict[str, Any] = _flash_attrs(cfg)
    if cfg.mamba_layers:  # the chunks each one's scan walks a row in, and the
        # layers whose scan is ``ops/ssd``'s launches at these shapes
        from photon_tpu.ops import ssd

        kernel = ssd.uses_kernel(cfg.attn_impl, cfg.attn_interpret, cfg.max_seq_len,
                                 cfg.mamba_chunk_size, cfg.mamba_n_heads, cfg.mamba_d_head,
                                 cfg.mamba_d_state, groups=cfg.mamba_n_groups)
        steps.update(mamba_layers=cfg.mamba_layers, mamba_groups=cfg.mamba_n_groups,
                     ssd_chunks=cfg.max_seq_len // cfg.mamba_chunk_size,
                     ssd_kernel_layers=cfg.mamba_layers if kernel else 0)
    if cfg.single_branch_layers:  # a layer is one branch: the other two kinds' counts
        steps.update(moe_layers=cfg.moe_layers, attention_layers=cfg.full_attention_layers)
    if cfg.conv_layers:
        steps.update(conv_layers=cfg.conv_layers)
    if cfg.swa_layers:  # beside them: the banded launches' plan (``_flash_attrs``)
        steps.update(swa_layers=cfg.swa_layers, sliding_window=cfg.sliding_window)
    if cfg.attn_gate:  # the gated layers whose multiply is ``ops/head_gate``'s launches
        from photon_tpu.ops.head_gate import uses_kernel

        kernel = uses_kernel(cfg.attn_impl, cfg.attn_interpret, cfg.max_seq_len, cfg.d_head)
        steps.update(
            head_gate_layers=cfg.full_attention_layers + cfg.swa_layers if kernel else 0)
    if cfg.hyper_connected:  # maps, read-in and write-back: two sublayers a layer
        steps.update(mhc_streams=cfg.hc_mult, mhc_sublayers=2 * cfg.n_layers)
    fence = {}
    if cfg.sparse_attention:
        steps.update(dsa_layers=cfg.n_layers, dsa_topk=cfg.dsa_topk)
        fence[TRAINER_DSA_SPAN] = _selection_attrs(cfg, batch_rows)
    return StepAttrs(steps, fence)
