"""Typed configuration schema for photon-tpu.

Mirrors the role of the reference's Hydra/pydantic schema
(``photon/conf/base_schema.py:344-392``): one fully-resolved config object is
the IPC of record — every process (server, node, executor, centralized
trainer) loads the same resolved YAML dump.

Plain dataclasses + explicit validation; YAML in/out via ``yaml.safe_load``.
"""

from __future__ import annotations

import dataclasses
import enum
import json
import math
import pathlib
import typing
import warnings
from dataclasses import dataclass, field
from typing import Any, NamedTuple

import yaml


class StrategyName(str, enum.Enum):
    """Server-side aggregation strategies (reference: ``base_schema.py:100-137``)."""

    FEDAVG = "fedavg"
    NESTEROV = "nesterov"
    FEDMOM = "fedmom"
    FEDADAM = "fedadam"
    FEDYOGI = "fedyogi"


class AttnImpl(str, enum.Enum):
    PALLAS = "pallas"  # blockwise flash attention kernel (TPU)
    XLA = "xla"  # pure-XLA reference path (reference's ``attn_impl: torch``)
    RING = "ring"  # ring/context-parallel attention over the sequence mesh axis


#: the ``layer_types`` entry of a windowed attention layer
SLIDING_ATTENTION = "sliding_attention"
#: every ``layer_types`` entry whose mixer is attention
ATTENTION_KINDS = ("attention", "full_attention", SLIDING_ATTENTION)
#: the ``layer_types`` entry of an expert layer that is the layer's one branch
MOE_LAYER = "moe"


class AttentionKind(NamedTuple):
    """One kind of attention layer (``ModelConfig.attention_kind``)."""

    n_heads: int
    window: int | None  # keys a query sees, its own among them; None = all
    rope_theta: float
    rotary_dim: int  # the head's leading dims that turn
    inv_freq: tuple[float, ...] | None  # None: plain ``rope_theta`` frequencies
    rope_factor: float  # on cos and sin


@dataclass
class ModelConfig:
    """Decoder-only MPT-style model shape (reference: ``conf/llm_config/mpt-125m.yaml:18-28``)."""

    name: str = "mpt-125m"
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    max_seq_len: int = 2048
    vocab_size: int = 50368
    expansion_ratio: int = 4
    no_bias: bool = True
    learned_pos_emb: bool = True
    # ALiBi positional attention (MPT-family option; reference llm-foundry
    # MPT exposes ``attn_config.alibi`` — the 125M recipe uses learned
    # positions, but the family supports both)
    alibi: bool = False
    tie_embeddings: bool = True
    # Per-cohort LoRA adapters (ISSUE 13, photon_tpu/adapters): rank-r A/B
    # factors on the targeted dense projections. 0 = no adapters (the
    # default graph, byte-identical to pre-adapter builds). These fields
    # are normally DERIVED from the ``photon.adapters`` block by
    # ``adapters.configure_adapter_training`` (train side) — the serving
    # engine keeps them 0 and applies adapters functionally instead
    # (base params stay adapter-free in checkpoints).
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: tuple = ()  # module names, e.g. ("wqkv", "out_proj")
    # Llama-family knobs (beyond the reference's MPT configs, which
    # llm-foundry exposes as attn_config/ffn_config variants): RoPE
    # positions, RMSNorm, SwiGLU MLP — composable rather than a separate
    # model class, so every trainer/sharding/federation path is shared.
    rope: bool = False  # rotary positions (excludes alibi/learned_pos_emb)
    rope_theta: float = 10000.0
    n_kv_heads: int = 0  # grouped-query attention; 0 -> n_heads (MHA)
    norm: str = "layernorm"  # layernorm | rmsnorm (both fp32)
    norm_eps: float = 1.0e-5  # checkpoint-interop-sensitive (rms_norm_eps)
    mlp: str = "gelu"  # gelu | swiglu | moe (expert-parallel, ops/moe.py)
    mlp_hidden_size: int = 0  # 0 -> expansion_ratio * d_model
    # MoE knobs (mlp == "moe"): GShard dense dispatch with static capacity;
    # experts shard over the `expert` mesh axis
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01  # Switch load-balance loss weight
    moe_mlp_act: str = "gelu"  # gelu | swiglu (Mixtral-style gated experts) | relu2 (dropless only)
    # The dropless expert layer (``ops/moe.dropless_moe_mlp``, training
    # only): ``moe_router: sigmoid`` scores every one of ``moe_num_experts``
    # with a sigmoid, selects ``moe_top_k`` by score + a selection bias that
    # takes no gradient, renormalises the selected scores and scales them by
    # ``moe_routed_scale``; no capacity, no dropped token, no aux loss. This
    # slice of an expert-parallel deployment HOLDS ``moe_experts_held``
    # experts (0 -> all) from ``moe_first_expert`` on: it routes over all of
    # them and computes its own experts' part of the result.
    # ``moe_shared_experts`` SwiGLU experts of the same width see every token
    # (``moe_shared_hidden_size`` > 0: one shared expert of that width).
    # ``moe_mlp_act: relu2`` makes the routed and the shared experts ungated,
    # ``W_down relu(W_up h)^2``: two matrices an expert and no ``moe_gate``.
    # After every optimizer step the selection bias moves against each
    # expert's load by ``moe_bias_update_speed`` at most
    # (``ops/moe.balanced_router_bias``); 0 holds it constant.
    # ``moe_router: softmax_topk`` is the dropless layer's second router: a
    # float32 softmax over all ``moe_num_experts`` outputs, the ``moe_top_k``
    # largest picked, their probabilities renormalised to sum to 1
    # (``norm_topk_prob``); no selection bias, no scale, no shared expert.
    moe_router: str = "softmax"  # softmax (capacity path) | sigmoid | softmax_topk (dropless)
    moe_experts_held: int = 0
    moe_first_expert: int = 0
    moe_shared_experts: int = 0
    moe_shared_hidden_size: int = 0
    moe_routed_scale: float = 1.0
    moe_bias_update_speed: float = 0.0
    # what the sigmoid router adds to the picked scores' sum before it divides
    # by it (1e-20 guards the division and changes no gate; a model that
    # publishes another, as ``+ 1e-6``, states it)
    moe_gate_eps: float = 1.0e-20
    # Leading dense blocks before the expert stack (HF ``first_k_dense_replace``):
    # the first ``first_k_dense`` layers are SwiGLU blocks of width
    # ``dense_mlp_hidden_size``, under a scan of their own (``dense_blocks``).
    first_k_dense: int = 0
    dense_mlp_hidden_size: int = 0
    # Latent attention (MLA, training form: keys and values expanded from the
    # latent; ``kv_lora_rank > 0`` turns it on). q and kv each go through a
    # low-rank pair with an RMSNorm between; a ``qk_rope_head_dim``-wide
    # rotary key is shared by all heads; a head's q/k is
    # [``qk_nope_head_dim`` | ``qk_rope_head_dim``] wide, its v ``v_head_dim``.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN's frequency scaling (HF ``rope_scaling`` with ``type: yarn``, key
    # for key; "" = plain ``rope_theta`` frequencies). The rotary dims whose
    # wavelength fits ``rope_scaling_original_max_position`` more than
    # ``rope_scaling_beta_fast`` times keep their frequency, those that fit it
    # fewer than ``rope_scaling_beta_slow`` times turn ``rope_scaling_factor``
    # times slower, a linear ramp between; the softmax scale is multiplied by
    # ``mscale(factor, mscale_all_dim)**2``, with ``mscale(f, m) = 0.1 m ln f
    # + 1`` (``ModelConfig.rope_inv_freq``, ``softmax_scale``). cos and sin
    # would be scaled by ``mscale(factor, mscale) / mscale(factor,
    # mscale_all_dim)``: validation takes the two equal, so by 1.
    rope_scaling_type: str = ""
    rope_scaling_factor: float = 1.0
    rope_scaling_original_max_position: int = 0
    rope_scaling_beta_fast: float = 32.0
    rope_scaling_beta_slow: float = 1.0
    rope_scaling_mscale: float = 1.0
    rope_scaling_mscale_all_dim: float = 0.0
    # Manifold-constrained hyper-connections (``models/mpt.py``, training path
    # only): ``hc_mult > 1`` residual streams in place of one. Around every
    # sublayer three maps, made per token from all streams at once (an RMSNorm
    # over the flattened streams with ``hc_eps``, one projection, learned
    # scales and biases): the read-in weights (a sigmoid a stream), the
    # write-back weights (twice a sigmoid) and the streams' mixing matrix,
    # ``exp`` of its logits cut to ``+-hc_res_clamp`` and then
    # ``hc_sinkhorn_iters`` rounds of column and row normalisation (each sum
    # ``+ hc_eps``), doubly stochastic to the iteration's precision.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1.0e-6
    hc_res_clamp: float = 30.0
    # A stack whose layers differ in kind (HF ``layer_types``, its list joined
    # by commas so that YAML, JSON and ``--set`` all spell it alike): one entry
    # a layer, ``mamba`` (a Mamba-2 mixer, ``ops/ssd.py``), ``conv`` (a gated
    # short convolution, below), ``attention``, or ``full_attention`` /
    # ``sliding_attention`` (attention layers of two kinds, further below);
    # every layer keeps the block's norms and residuals. "" = attention
    # everywhere, one scanned
    # stack ``blocks``; otherwise each run of layers equal in mixer AND in MLP
    # kind (the first ``first_k_dense`` dense, the others the model's
    # ``mlp``) is a scanned stack of its own, ``blocks_0``, ``blocks_1``, ...
    # (training path only). A Mamba-2 mixer has ``mamba_n_heads`` heads of
    # ``mamba_d_head`` (the mixer's inner channels, ``mamba_d_inner``), one
    # group of B and C of ``mamba_d_state``, a causal depthwise convolution
    # of ``mamba_d_conv`` taps with bias over x | B | C, and scans in chunks
    # of ``mamba_chunk_size`` positions (``max_seq_len`` a multiple of it).
    # ``mamba_n_groups`` > 1 gives B and C that many groups (head ``h`` reads
    # group ``h // (mamba_n_heads / mamba_n_groups)``; the convolution runs over
    # x | every group's B | every group's C) and norms the gated output within
    # each group's ``mamba_d_inner / mamba_n_groups`` channels.
    # ``single_branch_layers`` (HF ``nemotron_h``'s ``hybrid_override_pattern``):
    # a layer is ONE pre-norm (``ln_1``), ONE branch and one add, and its
    # ``layer_types`` entry names the branch: ``mamba``, ``attention``, or
    # ``moe`` for the model's dropless expert layer standing alone.
    layer_types: str = ""
    single_branch_layers: bool = False
    mamba_n_heads: int = 0
    mamba_n_groups: int = 1
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # A ``conv`` layer's mixer (HF ``lfm2``'s short convolution): one
    # projection to ``B | C | u``, each ``d_model`` wide; a causal depthwise
    # convolution of ``conv_kernel_size`` taps (HF ``conv_L_cache``), no bias
    # and no activation, over ``B * u``; the gate ``C *`` on its output; the
    # projection back.
    conv_kernel_size: int = 3
    # Attention layers of two kinds in one model (HF ``layer_types``'
    # ``full_attention`` / ``sliding_attention``, training path only;
    # ``attention`` stays the model-wide kind, a full layer). A sliding layer
    # sees the last ``sliding_window`` keys, its own among them (``i -
    # sliding_window < j <= i``: the flash kernel walks that band,
    # ``ops/flash_attention.py``), has ``swa_n_heads`` query heads (0 ->
    # ``n_heads``, which stays the full layers'; both kinds share
    # ``n_kv_heads`` and ``head_dim``) and turns its whole head by plain
    # ``swa_rope_theta`` frequencies (0 -> ``rope_theta``). A full layer turns
    # the first ``partial_rotary_factor`` of its head's dims (rotate-half
    # inside them; the rest pass) by ``rope_theta``'s frequencies, YaRN's
    # where ``rope_scaling_type`` says so, computed over the turned dims.
    # ``rope_scaling_attention_factor`` multiplies a full layer's cos and sin
    # (HF's ``attention_factor``: the turned part of a score carries its
    # square, the passed part 1; the softmax scale stays ``1/sqrt(d_head)``);
    # 0 keeps YaRN's ``mscale`` form above, a scale on the softmax.
    # ``attn_gate: headwise`` gates every attention layer's output before
    # ``out_proj``: ``sigmoid(h W_g)``, one gate a head and token (``attn_gate``
    # ``[d_model, heads]``).
    sliding_window: int = 0
    swa_n_heads: int = 0
    swa_rope_theta: float = 0.0
    partial_rotary_factor: float = 1.0
    rope_scaling_attention_factor: float = 0.0
    attn_gate: str = ""  # "" | headwise
    # Granite's four multipliers. At their defaults nothing is multiplied:
    # the embedding's output, each residual branch and the logits (divided by
    # ``logits_scaling``) are left as they are, and ``attention_multiplier``
    # 0 keeps the softmax scale ``1/sqrt(d_head)``.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: float = 0.0
    # Heads as wide as the projections make them (``q_proj`` to ``n_heads *
    # head_dim``, ``out_proj`` back from it); 0 -> ``d_model / n_heads``.
    head_dim: int = 0
    # A per-head RMSNorm (learned scale over the head's width, ``norm_eps``)
    # on q and on k before the rotation.
    qk_norm: bool = False
    # Learned sparse attention (``ops/dsa.py``, training path only):
    # ``dsa_topk > 0`` gives every attention layer an indexer of
    # ``dsa_index_heads`` heads of ``dsa_index_head_dim`` with one key head;
    # a query attends to the ``dsa_topk`` earlier keys its indexer scores
    # highest (all of them while there are no more), through a mask by
    # (query, key) that all heads share; the indexer learns from its own
    # alignment loss, which the step adds to the cross-entropy. The scores,
    # the selection and the loss walk the queries in chunks of ``dsa_chunk``
    # (HF ``sa_config.q_chunk_size``).
    dsa_topk: int = 0
    dsa_index_heads: int = 0
    dsa_index_head_dim: int = 0
    dsa_chunk: int = 512
    attn_impl: str = AttnImpl.PALLAS.value
    # Numerics: params kept fp32, compute in bf16 (reference: amp_bf16 + FSDP
    # PURE mixed precision, ``mpt-125m.yaml:85-92``).
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    logits_dtype: str = "float32"
    emb_init_std: float = 0.02
    resid_pdrop: float = 0.0
    remat: bool = False  # activation checkpointing (reference: fsdp_config.activation_checkpointing)
    # Nothing reads these two since PR 28: the flash kernel derives its tiles
    # from the shapes it is handed (ops/flash_attention.pick_tiles). They keep
    # their name and their 256 only because benchmark/program.py holds this
    # preset to every key of benchmark/configs/mpt-125m.json's `model` block,
    # which states both, and only a `benchmark` issue may edit that file:
    # once it drops the two keys, delete the fields (ROADMAP S3).
    flash_block_q: int = 256
    flash_block_k: int = 256
    # Run pallas kernels in the Pallas interpreter (CPU-executable). Test /
    # dryrun knob: lets the virtual-device mesh exercise the REAL sharded
    # flash program (shard_map + kernel) instead of silently falling back
    # to XLA attention off-TPU. Never set on real hardware.
    attn_interpret: bool = False

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def dropless_moe(self) -> bool:
        return self.mlp == "moe" and self.moe_router in ("sigmoid", "softmax_topk")

    @property
    def sparse_attention(self) -> bool:
        """The indexer picks each query's keys (``dsa_topk`` is set)."""
        return self.dsa_topk > 0

    @property
    def experts_held(self) -> int:
        return self.moe_experts_held or self.moe_num_experts

    @property
    def hybrid(self) -> bool:
        """The layers differ in kind (``layer_types`` is set)."""
        return bool(self.layer_types)

    @property
    def layer_kinds(self) -> tuple[str, ...]:
        return tuple(k.strip() for k in self.layer_types.split(",")) if self.hybrid else ()

    @property
    def mamba_layers(self) -> int:
        return self.layer_kinds.count("mamba")

    @property
    def conv_layers(self) -> int:
        return self.layer_kinds.count("conv")

    @property
    def moe_layers(self) -> int:
        """The layers with the model's expert MLP: ``moe`` entries where a
        layer is one branch, else every layer behind the leading dense ones."""
        if self.mlp != "moe":
            return 0
        if self.single_branch_layers:
            return self.layer_kinds.count(MOE_LAYER)
        return self.n_layers - self.first_k_dense

    @property
    def shared_expert_width(self) -> int:
        """The shared expert's hidden width (0: none)."""
        hidden = self.mlp_hidden_size or self.expansion_ratio * self.d_model
        return self.moe_shared_hidden_size or self.moe_shared_experts * hidden

    @property
    def moe_gated(self) -> bool:
        """The experts are SwiGLU (three matrices); ``relu2`` has two."""
        return self.moe_mlp_act != "relu2"

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def swa_layers(self) -> int:
        return self.layer_kinds.count(SLIDING_ATTENTION)

    @property
    def full_attention_layers(self) -> int:
        """The layers whose attention sees every earlier key."""
        if not self.hybrid:
            return self.n_layers
        return sum(k in ATTENTION_KINDS for k in self.layer_kinds) - self.swa_layers

    def attention_kind(self, kind: str = "attention") -> "AttentionKind":
        """What an attention layer of ``kind`` (a ``layer_types`` entry) reads
        where the kinds differ: its heads, window and rotation."""
        if kind == SLIDING_ATTENTION:
            return AttentionKind(self.swa_n_heads or self.n_heads, self.sliding_window,
                                 self.swa_rope_theta or self.rope_theta, self.d_head, None, 1.0)
        rotary = self.rotary_dim
        return AttentionKind(self.n_heads, None, self.rope_theta, rotary,
                             self.rope_inv_freq(rotary),
                             self.rope_scaling_attention_factor or 1.0)

    @property
    def rotary_dim(self) -> int:
        """The dims of a full layer's head that turn."""
        return int(round(self.d_head * self.partial_rotary_factor))

    @property
    def stacks(self) -> list[tuple[str, str, bool, int]]:
        """The model's scanned stacks in order: ``(name, mixer, dense MLP,
        length)``. One stack of attention blocks (``blocks``) behind the
        leading dense ones (``dense_blocks``); with ``layer_types`` every run
        of layers equal in mixer and in MLP kind, ``blocks_0``, ``blocks_1``,
        ... (a leading dense layer ends a run its mixer would go on)."""
        if not self.hybrid:
            blocks = [("blocks", "attention", False, self.n_layers - self.first_k_dense)]
            if self.first_k_dense:
                blocks.insert(0, ("dense_blocks", "attention", True, self.first_k_dense))
            return blocks
        runs: list[tuple[str, bool, int]] = []
        for i, kind in enumerate(self.layer_kinds):
            dense = i < self.first_k_dense
            if runs and runs[-1][:2] == (kind, dense):
                runs[-1] = (kind, dense, runs[-1][2] + 1)
            else:
                runs.append((kind, dense, 1))
        return [(f"blocks_{i}", *run) for i, run in enumerate(runs)]

    @property
    def scaled(self) -> bool:
        """Any of the four multipliers is off its default."""
        return (self.embedding_multiplier != 1.0 or self.residual_multiplier != 1.0
                or self.logits_scaling != 1.0 or self.attention_multiplier != 0.0)

    @property
    def hyper_connected(self) -> bool:
        """The blocks mix ``hc_mult`` residual streams (``hc_mult > 1``)."""
        return self.hc_mult > 1

    @property
    def yarn(self) -> bool:
        return self.rope_scaling_type == "yarn"

    @property
    def training_path_only(self) -> bool:
        """Latent attention, the dropless expert layer, leading dense blocks,
        layers of different kinds (a window among them), the multipliers,
        hyper-connected streams, scaled rotary frequencies, a partly turned
        head or a gated attention output: what serving, cached decode, LoRA
        and the HF maps lack."""
        return (self.latent_attention or self.dropless_moe or self.first_k_dense > 0
                or self.hybrid or self.scaled or self.sparse_attention
                or self.qk_norm or self.head_dim > 0 or self.hyper_connected
                or self.yarn or self.partial_rotary_factor != 1.0 or bool(self.attn_gate))

    def rope_inv_freq(self, dim: int) -> tuple[float, ...] | None:
        """The rotary inverse frequencies of ``dim`` rotary dims under YaRN
        (``dim / 2`` numbers, static), or ``None`` for the plain
        ``rope_theta ** (-2i / dim)`` that ``apply_rope`` makes itself."""
        if not self.yarn:
            return None
        half, theta = dim // 2, self.rope_theta

        def correction_dim(rotations: float) -> float:
            return dim * math.log(self.rope_scaling_original_max_position
                                  / (rotations * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(correction_dim(self.rope_scaling_beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.rope_scaling_beta_slow)), dim - 1)
        span = (high - low) or 0.001
        out = []
        for i in range(half):
            plain = theta ** (-2.0 * i / dim)
            keep = 1.0 - min(max((i - low) / span, 0.0), 1.0)
            out.append(plain / self.rope_scaling_factor * (1.0 - keep) + plain * keep)
        return tuple(out)

    @property
    def softmax_scale(self) -> float | None:
        """What multiplies the scores before the softmax: ``None`` for the
        dispatch's own ``1/sqrt(d_head)``, ``attention_multiplier`` where it
        is set, and under YaRN ``1/sqrt(d_head)`` times the square of
        ``mscale(factor, mscale_all_dim)`` (unless the factor is on cos and
        sin: ``rope_scaling_attention_factor``)."""
        if self.attention_multiplier:
            return self.attention_multiplier
        if self.yarn and not self.rope_scaling_attention_factor:
            m = 0.1 * self.rope_scaling_mscale_all_dim * math.log(self.rope_scaling_factor) + 1.0
            return self.d_head ** -0.5 * m * m
        return None

    @property
    def d_head(self) -> int:
        if self.latent_attention:
            # heads are as wide as the projections make them, not d_model / n_heads
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.head_dim:
            return self.head_dim
        if self.d_model % self.n_heads:
            raise ValueError(f"d_model {self.d_model} not divisible by n_heads {self.n_heads}")
        return self.d_model // self.n_heads


@dataclass
class OptimizerConfig:
    """Client-side optimizer (reference: ``mpt-125m.yaml:58-63`` uses ADOPT lr 6e-4)."""

    name: str = "adopt"  # adopt | adamw
    lr: float = 6.0e-4
    betas: tuple[float, float] = (0.9, 0.9999)
    eps: float = 1.0e-6
    weight_decay: float = 0.0
    grad_clip_norm: float = 1.0
    # param-path regexes to freeze (reference: ``freeze_blocks``,
    # ``photon/utils.py:322-387``); e.g. [r"blocks/.*ln_1"]
    freeze_patterns: list = field(default_factory=list)


@dataclass
class SchedulerConfig:
    """Cosine-with-warmup (reference: ``mpt-125m.yaml`` scheduler block)."""

    name: str = "cosine_with_warmup"
    t_warmup: int = 100  # batches
    t_max: int = 4800  # batches; total schedule horizon
    alpha_f: float = 0.1  # final LR multiplier


@dataclass
class MeshConfig:
    """Logical device mesh for one client slice.

    Axes follow the TPU-idiomatic layout: ``data`` (batch DP), ``fsdp``
    (weight sharding / ZeRO-3), ``tensor`` (TP), ``sequence`` (context
    parallel / ring attention), ``pipe`` (pipeline parallel — GPipe-style
    stage schedule, ``parallel/pipeline.py``), ``expert`` (MoE expert
    parallel, ``ops/moe.py``). The reference's DDP/FSDP/TP knobs
    (``trainer_utils.py:1640-1720``) map onto mesh axis sizes here;
    sequence, pipe, and expert have no reference analog.
    """

    data: int = 1
    fsdp: int = 1
    tensor: int = 1
    sequence: int = 1
    pipe: int = 1
    expert: int = 1
    # what make_mesh does when the device list does NOT divide evenly into
    # meshes of this size (it uses devices[:size]; a non-multiple surplus
    # usually means a mis-sized mesh silently wasting chips): "warn"
    # (default), "error", or "ignore" (the pre-ISSUE-14 silence)
    surplus_devices: str = "warn"

    @property
    def size(self) -> int:
        return (self.data * self.fsdp * self.tensor * self.sequence
                * self.pipe * self.expert)

    def axis_sizes(self) -> dict[str, int]:
        return {
            "data": self.data,
            "fsdp": self.fsdp,
            "tensor": self.tensor,
            "sequence": self.sequence,
            "pipe": self.pipe,
            "expert": self.expert,
        }


@dataclass
class TrainConfig:
    """Per-client training loop config (reference: Composer Trainer knobs)."""

    global_batch_size: int = 256
    # grad-accumulation granularity; "auto" probes descending power-of-2
    # sizes at trainer build and picks the largest that fits in HBM
    # (reference: ``device_train_microbatch_size: auto``,
    # ``photon/clients/trainer_utils.py:972-978``, ``mpt-125m.yaml:80-81``)
    device_microbatch_size: int | str = 8
    # first candidate for the "auto" probe (0 = start at the full per-device
    # batch); capping skips compiles of hopelessly large candidates
    auto_microbatch_cap: int = 0
    # tokens per chunk of the scanned cross-entropy (0 = materialize full
    # logits): bounds the fp32 logits in HBM to chunk x vocab x 4 bytes (412.6
    # MB at 2,048 x 50,368), written once a chunk and read three times
    # (``train_step._chunked_ce_sum``)
    loss_chunk_tokens: int = 2048
    seed: int = 17
    # numerics are expressed by model.param_dtype/compute_dtype (fp32 params,
    # bf16 compute = the reference's amp_bf16 + FSDP PURE); there is
    # deliberately no separate "precision" string knob duplicating them
    eval_interval: int = 0  # mid-training eval every N steps (0 = off)
    eval_batches: int = 8
    log_interval: int = 10


@dataclass
class DatasetConfig:
    """Sharded-dataset config (reference: streaming MDS, ``conf/dataset/*``)."""

    local_path: str = ""
    split_train: str = "train"
    split_eval: str = "val"
    shuffle: bool = True
    shuffle_seed: int = 17
    # stream remapping (reference: ``set_stream`` assigns ``streams[cid % n]``,
    # ``photon/clients/llm_config_functions.py:388-436``): with n_streams > 0,
    # client cid reads ``client_{cid % n_streams}/{split}`` so more clients
    # than converted streams (or deliberate stream sharing) works; 0 keeps
    # the 1:1 ``client_{cid}`` layout from the conversion pipeline
    n_streams: int = 0
    # (no num_canonical_nodes analog: the reference needs it to keep MDS data
    # order invariant to physical node count; here every client cid owns its
    # own resumable loader, so order is node-count-invariant by construction)
    synthetic: bool = False  # deterministic synthetic tokens (tests / no-data bench)


@dataclass
class CommStackConfig:
    """Bulk-tensor transport selection (reference: ``base_schema.py:11-28``).

    Exactly one of shm / objstore / collective should carry bulk tensors:
    - shm: named POSIX shared memory, single-host (reference default).
    - objstore: filesystem/S3-style object store, durable, cross-host.
    - collective: jax.distributed DCN allreduce across client slices (the
      marquee TPU-native path; no reference analog).

    The ``collective_*`` knobs shape the device-resident aggregation plane
    (``parallel/collective_agg.py``) and only apply with
    ``collective=true``:

    - ``collective_replica``: ICI width per client slice — the 2-D
      ``(clients, replica)`` hierarchical mesh; 1 = the flat degenerate
      topology (bit-compatible with the original 1-D psum).
    - ``collective_quantization``: ``off`` keeps the fp32 cross-slice
      exchange; ``q8`` ships blockwise-int8 codes + fp32 per-block scales
      over DCN (EQuARX-style, the compression/ codec's quantizer run
      on-device; ~3.94x fewer modeled DCN bytes at block 256, per-element
      error ≤ Σ_clients scale/2).
    - ``collective_q8_block``: values per fp32 absmax scale block (0 →
      the codec's DEFAULT_BLOCK of 256).
    - ``collective_device_optimizer``: run the full average →
      pseudo-gradient → server-optimizer round as ONE fused jitted SPMD
      program with optimizer state resident on device (all five
      strategies); off keeps the host-side strategy fold.
    - ``collective_zero1``: ZeRO-1 cross-replica sharding of the device
      optimizer (ISSUE 14, default on): params + optimizer moments live
      sharded ``P(replica)`` between rounds, the update runs on each
      rank's reduce-scatter shard, and ONE ICI all-gather reassembles the
      updated params after the update — per-rank server-state HBM and
      update FLOPs divide by ``collective_replica``. Bit-identical to the
      replicated plane (pinned by test); turn off to keep the PR 7
      replicated layout (no win at replica=1 or for tiny models).

    Elasticity knobs (ISSUE 8 — ``federation/collective_round.py``'s
    straggler/degradation ladder):

    - ``collective_stage_timeout_s``: absolute per-stage deadline (seconds)
      on each collective stage (context handshake/stack, exchange, update).
      0 disables deadlines (the original wedge-forever gang semantics). A
      stage that misses its deadline raises into the reconfiguration
      ladder instead of wedging the round.
    - ``collective_quorum``: minimum surviving fraction of
      ``fl.n_total_clients`` required to run the round over the collective;
      below it the round degrades directly to the host-plane
      ``aggregate_inplace`` fold over whichever deltas landed.
    - ``collective_retry_budget``: bounded reconfiguration retries per
      round after a missed stage deadline before degrading to the host
      fold.
    """

    shm: bool = True
    objstore: bool = False
    collective: bool = False
    collective_replica: int = 1
    collective_quantization: str = "off"  # off | q8
    collective_q8_block: int = 0  # 0 → compression DEFAULT_BLOCK (256)
    collective_device_optimizer: bool = False
    collective_zero1: bool = True  # ZeRO-1 shard the device optimizer state
    collective_stage_timeout_s: float = 0.0  # 0 = no stage deadlines
    collective_quorum: float = 0.5  # min surviving fraction for the collective
    collective_retry_budget: int = 1  # reconfig attempts before host fallback


@dataclass
class CompressionConfig:
    """Parameter-plane wire codec (``photon_tpu/compression``).

    Applied by :class:`ParamTransport` to the uplink (client fit results);
    broadcasts stay raw so a fresh client can always join. ``policy``
    composes the stages: round-delta encoding, top-k magnitude
    sparsification, blockwise int8 quantization — each with per-client
    error-feedback residuals when ``error_feedback`` is on.
    """

    policy: str = "off"  # off | delta | delta_q8 | delta_topk_q8
    topk_ratio: float = 0.125  # kept fraction per layer (delta_topk_q8)
    q8_block_size: int = 256  # values per fp32 absmax scale block
    error_feedback: bool = True  # per-client residual re-injection
    ef_max_clients: int = 16  # LRU cap on node-resident residual copies


@dataclass
class ChaosConfig:
    """Deterministic fault injection (``photon_tpu/chaos``).

    OFF by default, and MUST stay off in production configs — every knob
    here exists to make the failure modes the federation stack claims to
    survive mechanically reproducible in tests (``make chaos``). Disabled,
    every hook site is a None-check; no fault logic runs.
    """

    enabled: bool = False
    seed: int = 1234  # per-process stream is seeded by (seed, scope=node_id)
    # control plane: per-Envelope-frame fault probabilities (federation/tcp.py)
    tcp_drop_p: float = 0.0
    tcp_delay_p: float = 0.0
    tcp_delay_max_s: float = 0.05
    tcp_duplicate_p: float = 0.0
    tcp_corrupt_p: float = 0.0  # one-bit flip; caught by CRC32 framing
    # object store: per-write fault probabilities (checkpoint/store.py)
    store_slow_p: float = 0.0
    store_slow_max_s: float = 0.05
    store_partial_p: float = 0.0  # temp file written, never renamed into place
    store_bitflip_p: float = 0.0  # caught by checkpoint manifest checksums
    # node crash: os._exit (SIGKILL-equivalent) at a phase of fit handling
    # or — collective topology — of the aggregation round itself
    crash_phase: str = ""  # "" | pre-fit | mid-fit | pre-reply
    #                      #    | pre-exchange | mid-exchange | pre-update
    crash_round: int = 0  # only when serving this server_round (0 = any)
    crash_node_id: str = ""  # only on this node id ("" = any)
    # marker-file path making the crash one-shot across respawns: the file
    # survives the killed process; a respawned node sees it and stays up
    crash_marker: str = ""
    # cap on the CORRUPTING store faults (partial/bitflip, reads + writes)
    # this process's injector fires; 0 = unlimited. Makes "corrupt exactly
    # one object" scenarios deterministic without seed-hunting — slow
    # faults neither consume nor are blocked by the cap.
    store_fault_max: int = 0
    # numeric-poison fault (ISSUE 10): inject a NaN into a client's fit
    # delta as it is packaged, at exactly this server round (0 = off) —
    # the deterministic trigger for the health plane's NaN sentinel e2e.
    nan_delta_round: int = 0
    nan_delta_cid: int = -1  # -1 = every client serving that round
    # fleet replica-kill (ISSUE 16): SIGKILL one serving replica after the
    # router has placed exactly this many requests (0 = off) — the
    # deterministic mid-traffic death the fleet e2e asserts survivors ride
    # out with zero drops. Same no-probability-draw discipline as
    # nan_delta_round.
    replica_kill_after_requests: int = 0
    replica_kill_id: str = ""  # "" = seeded pick among the live fleet
    # deterministic per-client fit slowdown (ISSUE 18): scale a client's
    # fit duration so heterogeneous-hardware skew is reproducible in the
    # async tests. 0 = off; >= 1 = the slowdown ceiling. With
    # ``fit_delay_cid`` >= 0 exactly that client runs at the full factor
    # (the "one 4x-slow client" scenario); with -1 every client draws a
    # seeded factor in [1, factor] from its (seed, scope)-keyed stream —
    # same no-probability-draw discipline as nan_delta_round.
    fit_delay_factor: float = 0.0
    fit_delay_cid: int = -1  # -1 = seeded per-client draw
    # serve fault storm (ISSUE 19): deterministic per-token tick stall —
    # amplifies the compute-proportional cost of a serve tick so shrinking
    # the prefill chunk budget measurably protects decode cadence (TPOT)
    # on CPU test hardware. Seconds of stall per token stepped in a tick;
    # 0 = off. Same no-probability-draw discipline as fit_delay_factor.
    serve_stall_per_token_s: float = 0.0
    # deterministic HBM-pressure ramp (ISSUE 19): the n-th serve device
    # sample is inflated by ``1 + frac * n`` — strictly monotone growth
    # that latches the health plane's HBM watcher without real memory
    # pressure. 0 = off.
    serve_hbm_ramp_frac: float = 0.0


@dataclass
class AutopilotConfig:
    """SLO autopilot (ISSUE 19, ``photon_tpu/telemetry/autopilot.py``).

    A feedback controller that closes the observe→actuate loop: declared
    SLO targets are evaluated periodically against windowed reductions of
    the typed-metric hub, and breaches drive runtime-mutable knobs the
    owning subsystems registered at install time. OFF by default; the
    disabled cost is one ``None`` check per hook site. Rules whose target
    is 0 are individually off. Every actuation is reversible: after
    ``relax_after`` consecutive clean evaluations a rule probes its knob
    back toward the value the subsystem declared at registration.
    """

    enabled: bool = False
    period_s: float = 0.25  # min seconds between evaluations, per plane
    cooldown_s: float = 2.0  # per-rule min seconds between actuations
    relax_after: int = 3  # clean evaluations before a relax probe
    window_s: float = 30.0  # trailing window for metric reductions
    decisions: int = 64  # decision ring surfaced on /statusz
    # hysteresis for rules without an explicit clear bound: an evaluation
    # is clean only when observed <= clear_frac * target
    clear_frac: float = 0.8
    # serve: queue saturation -> shrink prefill_token_budget so admissions
    # drain through cheaper ticks BEFORE the 429 path fires
    queue_high_frac: float = 0.75  # breach when ewma(depth)/max_queue >= this
    queue_clear_frac: float = 0.25  # hysteresis: clean only at/below this
    prefill_budget_min: int = 16  # knob floor (declared value is the ceiling)
    prefill_shrink: float = 0.5  # multiplicative tighten step
    # serve: TPOT p50 SLO -> lower the SpecController K ceiling (0 = off)
    tpot_p50_slo_s: float = 0.0
    spec_k_min: int = 1
    # serve/collective: HBM-growth alert -> prefix-cache eviction + adapter
    # LRU shrink (the reclaim action)
    reclaim_free_blocks: int = 8  # PrefixCache.ensure_free target
    # collective: straggler-frac p90 over the window -> tighten the stage
    # timeout so stragglers are cut loose sooner (0 = off)
    straggler_p90: float = 0.0
    stage_timeout_min_s: float = 5.0
    stage_timeout_shrink: float = 0.75
    # collective: wire-bytes slope (bytes/s) -> escalate collective
    # quantization off->q8 (0 = off)
    wire_slope_bytes_per_s: float = 0.0
    # async: stale-reject rate (rejects per version advance) -> widen
    # max_staleness within [declared, max_staleness_hi] (0 = off)
    async_reject_per_version: float = 0.0
    max_staleness_hi: int = 16
    # fleet: a replica whose compile counter moved on this many consecutive
    # report polls (steady-state retraces) or whose HBM watcher latched is
    # drained and restarted through the control plane (0 = off)
    replica_compile_streak: int = 0


@dataclass
class TelemetryConfig:
    """Distributed tracing + structured telemetry plane (``photon_tpu/telemetry``).

    OFF by default; disabled cost is a single ``None`` check per hook site
    (the same discipline as ``photon.chaos``). Enabled, the server merges
    its own round-phase spans with client spans shipped back on
    ``FitRes``/``EvaluateRes`` into one Perfetto/Chrome-trace JSON under
    ``dir``, writes a structured JSONL event log (membership transitions,
    chaos injections, reconnects, corrupt frames) alongside it, and — with
    ``prom_port`` set — serves the latest-round History KPIs at
    ``http://127.0.0.1:{prom_port}/metrics`` in Prometheus text format.
    """

    enabled: bool = False
    dir: str = ""  # "" → {photon.save_path}/telemetry
    prom_port: int = 0  # 0 = no /metrics endpoint
    max_buffered_spans: int = 4096  # per-process cap; overflow drops oldest
    # run-health observatory (ISSUE 10):
    #: capture a jax.profiler trace covering the FIRST N rounds of the run
    #: (0 = off; the same controller also serves on-demand POST
    #: /debug/profile requests). Artifacts land beside trace-{run}.json.
    profile_rounds: int = 0
    #: per-instrument ring-buffer samples the typed-metric hub retains (the
    #: time-series view health watchers compute percentiles over)
    metrics_retention: int = 512
    #: SLO autopilot (ISSUE 19): the feedback controller that closes the
    #: observe→actuate loop over this plane's hub + health monitor
    autopilot: AutopilotConfig = field(default_factory=AutopilotConfig)


@dataclass
class SpeculativeConfig:
    """Self-drafted speculative decoding (ISSUE 15, ``serve/draft.py``).

    OFF by default (the serve-plane opt-in discipline). Enabled, the
    scheduler drafts up to ``k`` tokens per decoding slot per step from a
    host-side n-gram / prompt-lookup drafter over that slot's own
    prompt+generated history (zero extra weights), verifies ALL rows'
    drafts in ONE mixed-grid step (the same ``(B, Tq)`` compiled program
    shape chunked prefill already runs), and emits the longest accepted
    prefix plus one model token. Greedy output is BIT-EXACT vs the
    non-speculative engine; temperature rows use standard rejection
    sampling (distribution-preserving; seeded streams stay deterministic
    and batch-mate-independent but are NOT the non-speculative sample
    path — see docs/serving.md).

    An accept-rate EWMA auto-throttles ``k`` and falls back to plain
    decode below ``accept_floor``, so adversarial (incompressible)
    traffic never regresses; ``probe_ticks`` re-probes periodically so a
    throttled-off engine can recover when traffic turns templated again.
    """

    enabled: bool = False
    #: max draft tokens per decoding row per step (the verify grid runs
    #: at most ``k + 1`` columns; widths bucket to pow2 so the compiled
    #: shape set stays bounded)
    k: int = 4
    #: per-TICK total draft tokens across all rows, composed with
    #: ``prefill_token_budget``: a step carrying a prompt chunk of C
    #: tokens drafts at most ``min(draft_budget, prefill_token_budget - C)``
    draft_budget: int = 64
    #: n-gram match orders for the prompt-lookup drafter (longest first)
    max_ngram: int = 3
    min_ngram: int = 1
    #: accept-rate EWMA floor: below it the throttle sets K=0 (plain
    #: decode) until a periodic probe sees acceptance again
    accept_floor: float = 0.30
    #: EWMA smoothing weight for per-step accept rates
    ewma_alpha: float = 0.2
    #: while throttled off, probe with one drafted step every N ticks
    #: (0 = never probe: once off, stays off)
    probe_ticks: int = 64


@dataclass
class FleetConfig:
    """N-replica scale-out serving behind one router (ISSUE 16,
    ``serve/router.py`` + ``serve/fleet.py``).

    OFF by default (the serve-plane opt-in discipline). Enabled,
    ``python -m photon_tpu.serve --fleet`` spawns ``replicas`` engine
    daemons — each today's single-process daemon unchanged, on its own
    ephemeral port — and a router tier that places each ``/generate`` on
    state locality: the prompt's chain-hash block-prefix digest
    (``serve/prefix.py``) lands shared-system-prompt traffic where its KV
    blocks already live, cohorts pin sticky to replicas so an adapter
    pool stays hot for its tenant set, and power-of-two-choices on live
    queue depth covers everything else. The router↔replica control plane
    is the CRC-framed ``federation/tcp.py`` stack (HELLO / liveness /
    load reports / drain / rolling hot-swap); the data plane is the
    existing HTTP frontend, proxied.
    """

    enabled: bool = False
    replicas: int = 2  # engine daemons behind the router (N >= 1)
    host: str = "127.0.0.1"
    port: int = 0  # router data-plane HTTP port; 0 = bind-ephemeral
    control_port: int = 0  # router↔replica TCP control plane; 0 = ephemeral
    # chain-hash blocks of the prompt used as the prefix-affinity routing
    # key (0 = prefix affinity off). The LAST digest of the first
    # ``prefix_affinity_blocks`` full blocks identifies the whole shared
    # prefix — rendezvous-hashed over live replicas so one prefix's
    # traffic converges on one replica's cache without a routing table.
    prefix_affinity_blocks: int = 4
    # sticky cohort → replica pinning (re-pins to a survivor on death);
    # off, cohort requests fall through to prefix/p2c like any other
    cohort_affinity: bool = True
    # control-plane cadence: one poll = one load-report query per replica,
    # doubling as the liveness ping (a missed report walks the
    # LivenessTracker ladder exactly like a missed ping)
    report_poll_s: float = 0.5
    report_timeout_s: float = 2.0  # per-poll reply deadline
    # alternate replicas tried when a proxy CONNECT fails before any
    # response byte (after bytes flow the error surfaces to the client)
    route_retries: int = 2


@dataclass
class ServeConfig:
    """Continuous-batching inference plane (``photon_tpu/serve``).

    OFF by default (the same opt-in discipline as ``photon.chaos``/
    ``photon.telemetry``): the serving CLI refuses to start on a config
    with ``enabled=false`` unless the operator passes ``--enable`` — a
    resolved TRAINING config can never be pointed at the serving entry by
    accident. Enabled, ``python -m photon_tpu.serve`` loads a federated
    run's latest server round checkpoint (params only — no dead optimizer
    moments) into a paged-KV engine and serves ``/generate`` (blocking +
    chunked streaming), ``/healthz`` and ``/metrics`` over stdlib HTTP.

    Sizing: each sequence reserves ``ceil((prompt + max_new_tokens) /
    block_size)`` blocks at admission (no mid-flight preemption — see
    docs/serving.md for the math); ``n_blocks = 0`` auto-sizes the pool to
    the worst case ``n_slots * ceil(max_seq_len / block_size)``.
    """

    enabled: bool = False
    host: str = "127.0.0.1"
    port: int = 0  # HTTP port; 0 = bind-ephemeral (tests)
    n_slots: int = 4  # fixed decode batch width (continuous-batching slots)
    block_size: int = 16  # KV-cache tokens per paged block
    n_blocks: int = 0  # paged-pool size; 0 = auto (worst case, never blocks)
    max_queue: int = 64  # admission queue bound; overflow → HTTP 429
    max_new_tokens: int = 64  # per-request generation cap
    # chunked prefill (ISSUE 12): max prompt tokens prefilled per MIXED
    # step — a prompt larger than the budget is split across consecutive
    # steps while decode rows ride along every step, so one giant prompt
    # can delay a decode token by at most one budget-sized chunk (it used
    # to stall every in-flight decode for its whole prefill).
    prefill_token_budget: int = 2048
    # serving attention inner loop (ISSUE 12, ops/ragged_paged_attention):
    #   "auto"   — the ragged live-block walk: the fused Pallas kernel
    #              where Pallas runs (TPU), the bit-exact gather-reference
    #              math over the live slice elsewhere;
    #   "ragged" — the fused Pallas kernel, explicitly. Rejected at
    #              validation on a non-Pallas backend unless
    #              attention_interpret opts into the Pallas interpreter;
    #   "gather" — the PR 5 full-width dense gather (the bit-exact
    #              oracle; attention cost scales with POOL capacity —
    #              keep it for parity debugging, not for serving).
    attention_impl: str = "auto"
    # run the ragged kernel through the Pallas interpreter (CPU-testable
    # parity runs; far too slow for real serving — leave off otherwise)
    attention_interpret: bool = False
    eos_id: int = -1  # default per-request EOS (-1 = none; requests may override)
    # graceful-drain bound (SIGTERM): /healthz flips to "draining", new
    # /generate gets 503 + Retry-After, and in-flight slots get up to this
    # many seconds to finish before the scheduler hard-stops
    drain_timeout_s: float = 30.0
    # content-addressed prefix reuse (ISSUE 11, serve/prefix.py): hash full
    # prompt-prefix blocks and share their KV copy-on-write across requests
    # — prefill then runs only on each prompt's uncached suffix. OFF by
    # default (the finished-request blocks a cache pins shrink the free
    # pool until evicted under pressure); ignored for MoE models, where
    # batch-global expert capacity breaks the sharing parity argument.
    prefix_cache: bool = False
    # explicit cap on cached (hash-indexed) blocks; 0 = no cap beyond pool
    # pressure (admission evicts LRU entries whenever it needs free blocks)
    prefix_cache_blocks: int = 0
    # live checkpoint hot-swap (ISSUE 11, serve/hotswap.py): a watcher
    # thread polls the federated run's store and swaps manifest-verified
    # new rounds in at the scheduler swap point — zero dropped requests,
    # every request served end to end by exactly one round's params
    hotswap: bool = False
    hotswap_poll_s: float = 5.0  # store poll cadence (presence scan only)
    # optional federation-health gate: the TRAINING run's /statusz URL; a
    # "failing" federation plane blocks swaps (don't track a failing run).
    # Unreachable endpoints fail open — see serve/hotswap.py.
    hotswap_statusz_url: str = ""
    # self-drafted speculative decoding (ISSUE 15, serve/draft.py): every
    # decoding row may carry up to k draft tokens through the mixed grid,
    # verified in one step — greedy bit-exact, auto-throttled by accept rate
    speculative: SpeculativeConfig = field(default_factory=SpeculativeConfig)
    # N-replica scale-out behind an affinity router (ISSUE 16): each
    # replica is this daemon unchanged; the router owns placement only
    fleet: FleetConfig = field(default_factory=FleetConfig)


#: dense-projection module names LoRA can target (the per-layer matmuls
#: ``models/decode.py`` and ``models/mpt.py`` share; MoE expert weights are
#: deliberately absent — batch-global capacity routing breaks the per-slot
#: purity argument the serving gather relies on)
LORA_TARGETABLE = (
    "wqkv", "q_proj", "k_proj", "v_proj", "out_proj",
    "up_proj", "down_proj", "gate_proj",
)


@dataclass
class AdaptersConfig:
    """Federated per-cohort LoRA personalization plane (ISSUE 13,
    ``photon_tpu/adapters``).

    OFF by default (the chaos/telemetry/serve opt-in discipline). Enabled
    on a TRAINING config, ``federation/collective_round.py`` freezes the
    federated base, trains rank-``rank`` A/B adapters per client, and
    aggregates them PER COHORT — all cohorts' reductions fused into one
    jitted program on the PR 7 plane. Enabled on a SERVING config, the
    engine grows a second paged adapter pool beside the KV pool and mixed
    batches gather each slot's cohort adapter per decode step; ``cohort``
    rides ``/generate``.

    ``cohorts`` maps cohort name → list of client ids (train side; the
    serve side uses the names only). Cids must not overlap across cohorts;
    a cid in no cohort trains/serves the bare base model.
    """

    enabled: bool = False
    rank: int = 8  # LoRA rank r (> 0 when enabled)
    alpha: float = 16.0  # delta scale = alpha / rank
    # targeted dense modules (subset of LORA_TARGETABLE)
    targets: list = field(default_factory=lambda: [
        "wqkv", "q_proj", "k_proj", "v_proj", "out_proj",
    ])
    cohorts: dict = field(default_factory=dict)  # name -> [cid, ...]
    # serve-side: resident adapter pages (cohorts decodable without a host
    # reload; LRU beyond it — same refcount machinery as the KV pool)
    pool_size: int = 4


@dataclass
class MembershipConfig:
    """Elastic node membership (``federation/membership.py``).

    Server side: a ping sweep between rounds drives each node through the
    ``live → suspect → dead → readmitted`` state machine; a node that
    reappears (TCP re-HELLO, multiprocess respawn) rejoins the rotation and
    gets the current round's broadcast re-sent. Node side: the reconnect
    supervisor redials with jittered exponential backoff and re-HELLOs.

    ``enabled`` gates ONLY the between-rounds ping sweep (the proactive
    suspect/dead detection). Scheduling-level crash recovery — dead-letter
    handling, mid-round readmission with a broadcast re-send, the liveness
    KPIs — is core round-loop behavior and always on.
    """

    enabled: bool = True
    ping_interval_rounds: int = 1  # sweep every N rounds (0 = never)
    ping_timeout_s: float = 5.0
    suspect_after_misses: int = 1
    dead_after_misses: int = 2
    # node-side reconnect backoff: delay(k) = min(max, base·2^k) ± jitter
    reconnect_backoff_base_s: float = 0.5
    reconnect_backoff_max_s: float = 30.0
    reconnect_backoff_jitter: float = 0.25  # ± fraction of the raw delay
    reconnect_max_attempts: int = 60  # consecutive failed dials before giving up (0 = unlimited)


@dataclass
class FLConfig:
    """Federation hyperparameters (reference: ``base_schema.py`` fl block)."""

    n_total_clients: int = 8
    n_clients_per_round: int = 8
    n_rounds: int = 320
    local_steps: int = 128
    strategy_name: str = StrategyName.NESTEROV.value
    server_learning_rate: float = 1.0
    server_momentum: float = 0.0
    # adaptive server optimizers
    server_beta_1: float = 0.9
    server_beta_2: float = 0.99
    server_tau: float = 1.0e-9
    # lr scaling with sampled client count: none | linear | sqrt
    client_count_scaling: str = "none"
    aggregate_momenta: bool = False
    accept_failures_cnt: int = 0
    ignore_failed_rounds: bool = False
    eval_interval_rounds: int = 0
    sample_seed: int = 1234
    # sliding-window reply timeouts (seconds); previously hardcoded 3600 —
    # a wedged node stalled a round for an hour with no knob (VERDICT r3)
    fit_timeout_s: float = 3600.0
    eval_timeout_s: float = 3600.0
    # per-round client config knobs (reference FitConfig: reset_optimizer,
    # reset_dataset_state, client_checkpoints, ... — ``clients/configs.py:55-214``)
    fit_config: dict = field(default_factory=dict)
    # eval-round knobs (reference EvaluateConfig, ``clients/configs.py:289-425``)
    eval_config: dict = field(default_factory=dict)


@dataclass
class AsyncRoundsConfig:
    """Asynchronous federated rounds (ISSUE 18, ``federation/async_round.py``).

    OFF by default. Enabled, the synchronous round clock is replaced by a
    buffered version clock: clients stream deltas when *they* finish, the
    server folds each arrival into the device plane under
    staleness-discounted weights, and a new version broadcasts whenever
    ``buffer_size`` updates have landed. The elastic machinery reframes:
    deadlines become ``max_staleness`` (a staler delta is rejected with a
    fresh-version re-broadcast), quorum becomes ``min_arrivals`` (below it
    the version clock holds still — never an aborted run).

    Bit-parity pin: ``max_staleness`` irrelevant (no staleness arises),
    ``buffer_size == fl.n_total_clients`` and homogeneous client speed
    reproduce the synchronous round bit-for-bit — every sync parity oracle
    carries transitively.
    """

    enabled: bool = False
    #: K — deltas buffered before the version clock advances; 0 = the full
    #: cohort (``fl.n_total_clients``), the sync-parity configuration
    buffer_size: int = 0
    #: minimum DISTINCT clients in a full buffer before advancing (the
    #: quorum analog: a single hyperactive client cannot advance the clock
    #: alone); the clock stalls — counted + evented — until satisfied
    min_arrivals: int = 1
    #: reject deltas whose staleness (server_version − client_base_version)
    #: exceeds this; the client is re-dispatched from the fresh version
    max_staleness: int = 4
    #: staleness-discount policy: ``poly`` → w = (1 + s)^(−power)
    #: (FedAsync-style polynomial), ``const`` → w = 1 (no discount)
    staleness_policy: str = "poly"  # poly | const
    staleness_power: float = 1.0
    #: version advances to run (0 = fl.n_rounds)
    n_versions: int = 0
    #: baseline simulated seconds per client fit in the async round
    #: simulator (scaled per-client by chaos ``fit_delay_factor``); the
    #: DES clock is what time-to-target-loss is compared on
    fit_time_s: float = 1.0


@dataclass
class PhotonConfig:
    """Node/process topology (reference: ``base_schema.py`` photon block)."""

    n_nodes: int = 1
    refresh_period: int = 0  # restart executors every N rounds; 0 = never
    # host-plane round pipeline (utils/hostpool.py): worker threads shared
    # by the codec's per-layer encode/decode, the per-array aggregation
    # fold, and the one-client decode-ahead. 0 = auto (min(cpu_count−1, 8)
    # — the driving thread is itself a pipeline stage), 1 = fully serial
    # (the degenerate config — inline, zero threads).
    # Results are bit-identical across settings; only wall-clock moves.
    host_threads: int = 0
    # heterogeneity-aware layout auto-tuner (parallel/autotune.py, ISSUE
    # 14): when on, a Trainer built WITHOUT an explicit mesh derives its
    # (data, fsdp, tensor, pipe) layout from the analytic cost model over
    # its local device slice instead of the hand-set ``mesh`` block — each
    # federated client on uneven hardware gets its own best layout (AMP,
    # PAPERS.md). The chosen layout + search time land in the KPIs
    # server/layout_{search_time,est_step_s}.
    mesh_autotune: bool = False
    checkpoint: bool = True
    checkpoint_interval: int = 1
    # write round checkpoints on a background thread so round N+1's
    # broadcast/fits overlap round N's disk write (barrier at the next
    # save/resume/shutdown keeps crash-resume consistency)
    async_checkpoint: bool = True
    keep_checkpoints: int = 3
    resume_round: int | None = None  # negative = index from latest valid
    restore_run_uuid: str | None = None
    # warm-start initial global params from another run's centralized
    # checkpoint (reference: ``get_centralized_run_parameters``,
    # ``init_utils.py:43-125``)
    init_from_run: str | None = None
    comm_stack: CommStackConfig = field(default_factory=CommStackConfig)
    compression: CompressionConfig = field(default_factory=CompressionConfig)
    membership: MembershipConfig = field(default_factory=MembershipConfig)
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    async_rounds: AsyncRoundsConfig = field(default_factory=AsyncRoundsConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    adapters: AdaptersConfig = field(default_factory=AdaptersConfig)
    save_path: str = "/tmp/photon_tpu"


@dataclass
class Config:
    """Root config (reference: ``BaseConfig``, ``base_schema.py:344-392``)."""

    run_uuid: str = "dev"
    seed: int = 17
    # wandb project (None = metrics stay local; reference: wandb block in
    # BaseConfig). Per-client runs get a ``_client_{cid}`` name suffix.
    wandb_project: str | None = None
    photon: PhotonConfig = field(default_factory=PhotonConfig)
    fl: FLConfig = field(default_factory=FLConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    scheduler: SchedulerConfig = field(default_factory=SchedulerConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    dataset: DatasetConfig = field(default_factory=DatasetConfig)

    # ------------------------------------------------------------------
    # (de)serialization — the resolved config file is the IPC of record
    # (reference: ``hydra_resolver.py:15-39``).
    # ------------------------------------------------------------------

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_yaml(self, path: str | pathlib.Path) -> None:
        p = pathlib.Path(path)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(yaml.safe_dump(self.to_dict(), sort_keys=False))

    def to_json(self) -> str:
        return json.dumps(self.to_dict())

    @classmethod
    def from_dict(cls, d: dict[str, Any]) -> "Config":
        return _build_dataclass(cls, d)

    @classmethod
    def from_yaml(cls, path: str | pathlib.Path) -> "Config":
        return cls.from_dict(yaml.safe_load(pathlib.Path(path).read_text()) or {})

    @classmethod
    def from_json(cls, s: str) -> "Config":
        return cls.from_dict(json.loads(s))

    def _validate_latent_moe_family(self) -> None:
        """Latent attention, the dropless sigmoid router, the experts held
        here and the leading dense blocks (preset ``glm-4.7-flash-ep8``)."""
        m = self.model
        if m.moe_router not in ("softmax", "sigmoid", "softmax_topk"):
            raise ValueError(f"bad model.moe_router {m.moe_router!r}")
        if m.moe_router == "softmax_topk" and (
                m.moe_shared_experts or m.moe_routed_scale != 1.0
                or m.moe_bias_update_speed):
            raise ValueError(
                "moe_router='softmax_topk' has no selection bias, no scale and "
                "no shared expert: moe_bias_update_speed, moe_routed_scale and "
                "moe_shared_experts belong to moe_router='sigmoid'")
        if m.moe_router in ("sigmoid", "softmax_topk"):
            if m.mlp != "moe" or m.moe_mlp_act not in ("swiglu", "relu2"):
                raise ValueError(
                    f"moe_router={m.moe_router!r} needs mlp='moe' with "
                    "moe_mlp_act='swiglu' (or the ungated 'relu2')")
            held = m.experts_held
            if held > m.moe_num_experts:
                raise ValueError(
                    f"moe_experts_held={held} exceeds the {m.moe_num_experts} "
                    "routed experts (moe_num_experts)")
            if held < 1 or m.moe_num_experts % held:
                raise ValueError(
                    f"moe_experts_held={held} does not divide the "
                    f"{m.moe_num_experts} routed experts: an expert-parallel "
                    "deployment gives every chip the same number")
            if m.moe_first_expert % held or not (
                    0 <= m.moe_first_expert <= m.moe_num_experts - held):
                raise ValueError(
                    f"moe_first_expert={m.moe_first_expert} is not the start of "
                    f"one of the {m.moe_num_experts // held} shares of {held}")
            if self.mesh.expert > 1:
                raise ValueError(
                    f"mesh.expert > 1 with moe_router={m.moe_router!r} is not "
                    "supported yet: the dropless layer has no expert exchange "
                    "across chips (it computes the share it is told it holds)")
            if m.moe_shared_experts < 0 or m.moe_bias_update_speed < 0:
                raise ValueError(
                    "moe_shared_experts and moe_bias_update_speed must be >= 0")
            if m.moe_shared_hidden_size and (
                    m.moe_shared_hidden_size < 0 or m.moe_shared_experts != 1):
                raise ValueError(
                    "moe_shared_hidden_size is the width of ONE shared expert: "
                    "it needs moe_shared_experts=1 and must be > 0")
        elif m.moe_mlp_act == "relu2":
            raise ValueError(
                "moe_mlp_act='relu2' (ungated experts) belongs to the dropless "
                "routers ('sigmoid', 'softmax_topk'): the capacity path has gelu "
                "and swiglu experts")
        elif (m.moe_experts_held or m.moe_first_expert or m.moe_shared_experts
              or m.moe_shared_hidden_size
              or m.moe_routed_scale != 1.0 or m.moe_bias_update_speed):
            raise ValueError(
                "moe_experts_held / moe_first_expert belong to the dropless "
                "routers ('sigmoid', 'softmax_topk'); moe_shared_experts / "
                "moe_shared_hidden_size / moe_routed_scale / moe_bias_update_speed belong to "
                "moe_router='sigmoid'")
        if m.moe_gate_eps != 1.0e-20 and (m.moe_router != "sigmoid" or m.moe_gate_eps <= 0):
            raise ValueError(
                "moe_gate_eps belongs to moe_router='sigmoid' and must be > 0")
        if m.first_k_dense:
            if not 0 < m.first_k_dense < m.n_layers or m.dense_mlp_hidden_size <= 0:
                raise ValueError(
                    f"first_k_dense={m.first_k_dense} needs 0 < first_k_dense < "
                    f"n_layers={m.n_layers} and dense_mlp_hidden_size > 0")
            if self.mesh.pipe > 1:
                raise ValueError(
                    "mesh.pipe > 1 with first_k_dense > 0 is not supported: the "
                    "pipeline schedule scans one uniform stack of blocks")
        latent = (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
                  m.qk_rope_head_dim, m.v_head_dim)
        if any(latent):
            if min(latent) <= 0:
                raise ValueError(
                    "latent attention needs q_lora_rank, kv_lora_rank, "
                    "qk_nope_head_dim, qk_rope_head_dim and v_head_dim all > 0")
            if not m.rope or m.qk_rope_head_dim % 2:
                raise ValueError(
                    "latent attention needs rope=true and an even qk_rope_head_dim")
            if m.n_kv_heads:
                raise ValueError("latent attention has no grouped kv heads (n_kv_heads)")
            if m.v_head_dim != m.d_head and (
                    m.attn_impl == AttnImpl.RING.value or self.mesh.sequence > 1):
                raise ValueError(
                    f"v_head_dim={m.v_head_dim} differs from qk_nope_head_dim + "
                    f"qk_rope_head_dim={m.d_head}: ring attention (attn_impl='ring' "
                    "/ mesh.sequence > 1) takes one head width for q, k and v")
        self._validate_hybrid_family()
        self._validate_sparse_attention_family()
        self._validate_hyper_connected_family()
        self._validate_windowed_family()
        if m.training_path_only:
            if m.lora_rank or self.photon.adapters.enabled:
                raise ValueError(
                    "LoRA adapters (model.lora_rank / photon.adapters) are not "
                    "supported with latent attention, the dropless expert layer, "
                    "leading dense blocks, layer_types, the multipliers, "
                    "hc_mult > 1 or rope_scaling_type: "
                    "their projections are not adaptable modules yet")
            if self.photon.serve.prefix_cache or self.photon.serve.enabled:
                raise ValueError(
                    "photon.serve (and its prefix cache) is not supported with "
                    "latent attention, the dropless expert layer, leading "
                    "dense blocks, layer_types, the multipliers, hc_mult > 1 or "
                    "rope_scaling_type: there is no cache or decode step for "
                    "them yet")

    def _validate_hyper_connected_family(self) -> None:
        """Hyper-connected residual streams and YaRN's frequencies (preset
        ``xing4.0-29b-a4b-ep8``)."""
        m = self.model
        if m.rope_scaling_type not in ("", "yarn"):
            raise ValueError(
                f"rope_scaling_type={m.rope_scaling_type!r}: only 'yarn' (or '' "
                "for plain rope_theta frequencies) is computed here")
        if m.yarn:
            if not m.rope or m.rope_scaling_factor < 1 \
                    or m.rope_scaling_original_max_position <= 0 \
                    or not m.rope_scaling_beta_fast > m.rope_scaling_beta_slow > 0 \
                    or m.rope_scaling_mscale <= 0:
                raise ValueError(
                    "rope_scaling_type='yarn' needs rope=true, rope_scaling_factor "
                    ">= 1, rope_scaling_original_max_position > 0, "
                    "rope_scaling_beta_fast > rope_scaling_beta_slow > 0 and "
                    "rope_scaling_mscale > 0")
            if m.rope_scaling_attention_factor:
                if m.rope_scaling_mscale != 1.0 or m.rope_scaling_mscale_all_dim:
                    raise ValueError(
                        "rope_scaling_attention_factor (on cos and sin) and "
                        "rope_scaling_mscale / rope_scaling_mscale_all_dim (on "
                        "the softmax) both scale the scores: state one")
            elif m.rope_scaling_mscale != m.rope_scaling_mscale_all_dim:
                raise ValueError(
                    f"rope_scaling_mscale={m.rope_scaling_mscale} differs from "
                    f"rope_scaling_mscale_all_dim={m.rope_scaling_mscale_all_dim}: "
                    "cos and sin would be scaled by the ratio of their mscales, "
                    "which the rotation does not do yet")
            if m.attention_multiplier:
                raise ValueError(
                    "attention_multiplier and rope_scaling_mscale_all_dim both "
                    "set the softmax scale: state one")
            if m.attn_impl == AttnImpl.RING.value or self.mesh.sequence > 1:
                raise ValueError(
                    "rope_scaling_type='yarn' is not supported with ring "
                    "attention (attn_impl='ring' / mesh.sequence > 1): its merge "
                    "fixes the softmax scale at 1/sqrt(d_head)")
        elif (m.rope_scaling_factor != 1.0 or m.rope_scaling_original_max_position
              or m.rope_scaling_mscale_all_dim):
            raise ValueError(
                "rope_scaling_factor / rope_scaling_original_max_position / "
                "rope_scaling_mscale_all_dim belong to rope_scaling_type='yarn'")
        if m.hc_mult < 1:
            raise ValueError("hc_mult must be >= 1 (1 = one residual stream)")
        if not m.hyper_connected:
            return
        if m.hc_sinkhorn_iters < 1 or m.hc_eps <= 0 or m.hc_res_clamp <= 0:
            raise ValueError(
                "hc_mult > 1 needs hc_sinkhorn_iters >= 1, hc_eps > 0 and "
                "hc_res_clamp > 0")
        if m.hybrid:
            raise ValueError(
                "hc_mult > 1 does not combine with layer_types: the Mamba-2 "
                "and short-convolution mixers have no hyper-connected form here")
        if m.residual_multiplier != 1.0:
            raise ValueError(
                "hc_mult > 1 does not combine with residual_multiplier: the "
                "write-back weights scale the branch")
        if max(self.mesh.pipe, self.mesh.tensor, self.mesh.sequence, self.mesh.expert) > 1:
            raise ValueError(
                "hc_mult > 1 with mesh.pipe, mesh.tensor, mesh.sequence or "
                "mesh.expert > 1 is not supported: the pipeline schedule "
                "carries one stream, and the maps read every stream's whole "
                "width at each token")

    def _validate_windowed_family(self) -> None:
        """Sliding-window layers beside full ones, a partly turned head, the
        factor on cos and sin, and the gate on attention's output (preset
        ``laguna-xs.2-ep8``)."""
        m = self.model
        if m.attn_gate not in ("", "headwise"):
            raise ValueError(f"attn_gate={m.attn_gate!r}: only 'headwise' (or '') is computed here")
        if m.attn_gate and (m.latent_attention or m.sparse_attention):
            raise ValueError(
                "attn_gate lives in the plain attention branch: it does not "
                "combine with latent attention or dsa_topk > 0")
        if not 0.0 < m.partial_rotary_factor <= 1.0 or m.rope_scaling_attention_factor < 0:
            raise ValueError(
                "partial_rotary_factor must lie in (0, 1] and "
                "rope_scaling_attention_factor be >= 0 (0 = the mscale form)")
        if m.partial_rotary_factor != 1.0:
            if not m.rope or m.latent_attention or m.sparse_attention:
                raise ValueError(
                    "partial_rotary_factor needs rope=true and the plain attention "
                    "branch: latent attention has qk_rope_head_dim, and the "
                    "indexer turns whole heads")
            turned = m.d_head * m.partial_rotary_factor
            if turned != int(turned) or int(turned) % 2:
                raise ValueError(
                    f"partial_rotary_factor={m.partial_rotary_factor} of d_head="
                    f"{m.d_head} is {turned} dims: the turned part must be an even "
                    "whole number")
        if m.rope_scaling_attention_factor and not m.yarn:
            raise ValueError(
                "rope_scaling_attention_factor belongs to rope_scaling_type='yarn'")
        if not m.swa_layers:
            if m.sliding_window or m.swa_n_heads or m.swa_rope_theta:
                raise ValueError(
                    "sliding_window / swa_n_heads / swa_rope_theta belong to "
                    "'sliding_attention' layers in layer_types")
            return
        if m.sliding_window < 1 or m.swa_n_heads < 0 or m.swa_rope_theta < 0:
            raise ValueError(
                "a 'sliding_attention' layer needs sliding_window >= 1 (and "
                "swa_n_heads, swa_rope_theta >= 0)")
        if m.alibi or not m.rope or m.latent_attention or m.sparse_attention \
                or m.hyper_connected:
            raise ValueError(
                "'sliding_attention' layers need rope=true and do not combine "
                "with alibi, latent attention, dsa_topk > 0 or hc_mult > 1: the "
                "band is the plain causal branch's second bound")
        if m.yarn and not m.rope_scaling_attention_factor:
            raise ValueError(
                "'sliding_attention' layers beside rope_scaling_type='yarn' need "
                "rope_scaling_attention_factor: the mscale form scales every "
                "layer's softmax, the sliding layers' plain rotation too")
        if m.swa_n_heads and m.swa_n_heads != m.n_heads:
            n_kv = m.n_kv_heads or m.n_heads
            if not m.head_dim or m.swa_n_heads % n_kv:
                raise ValueError(
                    f"swa_n_heads={m.swa_n_heads} beside n_heads={m.n_heads} needs "
                    "head_dim (one head width for both kinds) and a multiple of "
                    f"the {n_kv} key-value heads")
        if m.attn_impl == AttnImpl.RING.value or max(
                self.mesh.pipe, self.mesh.tensor, self.mesh.sequence, self.mesh.expert,
                self.mesh.fsdp) > 1:
            raise ValueError(
                "'sliding_attention' layers are not supported with ring attention "
                "(attn_impl='ring') or a mesh axis above 1 other than data: ring "
                "chunks know no second bound, and the two kinds' head counts "
                "split differently")

    def _validate_sparse_attention_family(self) -> None:
        """``head_dim``, ``qk_norm`` and the indexer's sparse attention
        (preset ``keye-vl-2.0-30b-a3b-ep8``)."""
        m = self.model
        if m.head_dim < 0:
            raise ValueError("head_dim must be >= 0")
        if (m.qk_norm or m.head_dim) and (m.latent_attention or not (
                m.n_kv_heads and m.n_kv_heads != m.n_heads)):
            raise ValueError(
                "head_dim and qk_norm live in the grouped-query branch: they "
                "need 0 < n_kv_heads < n_heads and no latent attention")
        if (m.head_dim or m.qk_norm) and self.mesh.pipe > 1:
            raise ValueError(
                "mesh.pipe > 1 with head_dim or qk_norm is not supported")
        if not m.sparse_attention:
            if m.dsa_index_heads or m.dsa_index_head_dim:
                raise ValueError(
                    "dsa_index_heads / dsa_index_head_dim belong to dsa_topk > 0")
            return
        if min(m.dsa_index_heads, m.dsa_index_head_dim, m.dsa_chunk) <= 0 \
                or m.dsa_index_head_dim % 2:
            raise ValueError(
                "dsa_topk > 0 needs dsa_index_heads, dsa_chunk > 0 and an even "
                "dsa_index_head_dim > 0")
        if m.alibi or m.latent_attention or m.hybrid or not m.rope or not (
                m.n_kv_heads and m.n_kv_heads != m.n_heads):
            raise ValueError(
                "dsa_topk > 0 (the indexer's sparse attention) lives in the "
                "grouped-query branch with rotary positions: it needs rope, "
                "0 < n_kv_heads < n_heads, and no alibi, latent attention or "
                "layer_types")
        if m.attention_multiplier or m.yarn:
            raise ValueError(
                "dsa_topk > 0 fixes the softmax scale at 1/sqrt(d_head) and "
                "its indexer turns by plain rope_theta frequencies")
        if m.attn_impl == AttnImpl.RING.value or self.mesh.sequence > 1 \
                or self.mesh.tensor > 1 or self.mesh.pipe > 1:
            raise ValueError(
                "dsa_topk > 0 is not supported with ring attention "
                "(attn_impl='ring'), mesh.sequence > 1, mesh.tensor > 1 or "
                "mesh.pipe > 1: the indexer scores a query against the whole "
                "row and its mask is one for all heads")
        if m.max_seq_len % min(m.dsa_chunk, m.max_seq_len):
            raise ValueError(
                f"max_seq_len={m.max_seq_len} is not a multiple of "
                f"dsa_chunk={m.dsa_chunk}: the indexer walks whole chunks")

    def _validate_hybrid_family(self) -> None:
        """``layer_types`` with its Mamba-2 sizes, and the four multipliers
        (preset ``granite-4.0-h-micro-stage1``); ``conv`` layers, with leading
        dense layers and expert layers among them (preset
        ``lfm2-8b-a1b-ep4``)."""
        m = self.model
        if min(m.embedding_multiplier, m.residual_multiplier, m.logits_scaling) <= 0 \
                or m.attention_multiplier < 0:
            raise ValueError(
                "embedding_multiplier, residual_multiplier and logits_scaling "
                "must be > 0, attention_multiplier >= 0 (0 = 1/sqrt(d_head))")
        if m.attention_multiplier and (
                m.attn_impl == AttnImpl.RING.value or self.mesh.sequence > 1):
            raise ValueError(
                "attention_multiplier is not supported with ring attention "
                "(attn_impl='ring' / mesh.sequence > 1): its merge fixes the "
                "scale at 1/sqrt(d_head)")
        if (m.hybrid or m.scaled) and self.mesh.pipe > 1:
            raise ValueError(
                "mesh.pipe > 1 with layer_types or the multipliers is not "
                "supported: the pipeline schedule embeds the tokens itself and "
                "scans one uniform stack of blocks")
        if not m.hybrid:
            if m.single_branch_layers:
                raise ValueError(
                    "single_branch_layers needs layer_types: each entry names "
                    "its layer's one branch")
            return
        kinds = set(m.layer_kinds)
        known = ({"mamba", "attention", MOE_LAYER} if m.single_branch_layers
                 else {"mamba", "conv", *ATTENTION_KINDS})
        if len(m.layer_kinds) != m.n_layers or not kinds <= known:
            raise ValueError(
                f"layer_types needs n_layers={m.n_layers} comma-separated entries, "
                f"each 'mamba', 'conv', 'attention', 'full_attention' or "
                f"'sliding_attention' (with single_branch_layers: 'mamba', "
                f"'attention' or 'moe'); got {len(m.layer_kinds)}: {sorted(kinds)}")
        if m.latent_attention:
            raise ValueError(
                "layer_types does not combine with latent attention: its "
                "attention layers are the grouped-query branch's")
        if m.mamba_layers and not m.single_branch_layers and (
                m.first_k_dense or m.mlp == "moe"):
            raise ValueError(
                "'mamba' layers do not combine with first_k_dense or mlp='moe' "
                "unless a layer is one branch (single_branch_layers): no model "
                "with a Mamba-2 mixer AND an expert layer in one block runs here")
        if m.single_branch_layers:
            self._validate_single_branch_layers()
        if m.mlp == "moe" and not m.dropless_moe:
            raise ValueError(
                "layer_types with mlp='moe' needs a dropless router "
                "(moe_router='sigmoid' / 'softmax_topk'): the capacity path's "
                "stack is one of attention blocks")
        if m.conv_layers:
            if m.conv_kernel_size <= 0:
                raise ValueError("a 'conv' layer needs conv_kernel_size > 0")
            if self.mesh.sequence > 1 or self.mesh.tensor > 1:
                raise ValueError(
                    "mesh.sequence > 1 or mesh.tensor > 1 with 'conv' layers "
                    "is not supported: the taps reach back along the whole "
                    "row, and no tensor split respects the in-projection's "
                    "B | C | u columns")
        if m.mamba_layers:
            sizes = (m.mamba_n_heads, m.mamba_d_head, m.mamba_d_state,
                     m.mamba_d_conv, m.mamba_chunk_size)
            if min(sizes) <= 0:
                raise ValueError(
                    "a 'mamba' layer needs mamba_n_heads, mamba_d_head, "
                    "mamba_d_state, mamba_d_conv and mamba_chunk_size all > 0")
            if m.mamba_n_groups < 1 or m.mamba_n_heads % m.mamba_n_groups:
                raise ValueError(
                    f"mamba_n_groups={m.mamba_n_groups} does not divide the "
                    f"{m.mamba_n_heads} Mamba heads: a group of B and C serves "
                    "whole heads, and a share of the heads that splits a group "
                    "has no B and C of its own")
            if m.max_seq_len % m.mamba_chunk_size:
                raise ValueError(
                    f"max_seq_len={m.max_seq_len} is not a multiple of "
                    f"mamba_chunk_size={m.mamba_chunk_size}: the scan walks "
                    "whole chunks")
            if self.mesh.sequence > 1 or self.mesh.tensor > 1:
                raise ValueError(
                    "mesh.sequence > 1 or mesh.tensor > 1 with 'mamba' layers "
                    "is not supported: the scan carries its state along the "
                    "whole row, and one group's B, C and gated norm span all "
                    "heads")

    def _validate_single_branch_layers(self) -> None:
        """A layer that is one pre-norm, one branch and one add (preset
        ``nemotron-3-nano-30b-a3b-ep16``)."""
        m = self.model
        if MOE_LAYER in m.layer_kinds and not m.dropless_moe:
            raise ValueError(
                "a 'moe' layer is the dropless expert layer standing alone: it "
                "needs mlp='moe' with moe_router='sigmoid' or 'softmax_topk'")
        if m.first_k_dense or m.hyper_connected or m.sparse_attention or m.attn_gate \
                or m.alibi:
            raise ValueError(
                "single_branch_layers does not combine with first_k_dense, "
                "hc_mult > 1, dsa_topk > 0, attn_gate or alibi: a layer has no "
                "second sublayer, and its attention is the plain causal branch")
        if m.attn_impl == AttnImpl.RING.value or max(
                self.mesh.pipe, self.mesh.tensor, self.mesh.sequence, self.mesh.expert,
                self.mesh.fsdp) > 1:
            raise ValueError(
                "single_branch_layers is not supported with ring attention "
                "(attn_impl='ring') or a mesh axis above 1 other than data: the "
                "stacks are runs of one kind, each its own scan, and the Mamba "
                "heads are not split by group over mesh.tensor yet")

    def validate(self) -> "Config":
        if self.fl.n_clients_per_round > self.fl.n_total_clients:
            raise ValueError("n_clients_per_round > n_total_clients")
        micro = self.train.device_microbatch_size
        if isinstance(micro, str):
            if micro != "auto":
                raise ValueError(f"device_microbatch_size must be an int or 'auto', got {micro!r}")
        elif self.train.global_batch_size % micro:
            raise ValueError("global_batch_size must be divisible by device_microbatch_size")
        StrategyName(self.fl.strategy_name)
        AttnImpl(self.model.attn_impl)
        if self.mesh.pipe > 1:
            if self.train.device_microbatch_size == "auto":
                raise ValueError(
                    "device_microbatch_size='auto' is not supported with "
                    "mesh.pipe > 1 (the OOM probe builds the non-pipelined "
                    "step); set an explicit microbatch size"
                )
            if self.model.n_layers % self.mesh.pipe:
                raise ValueError(
                    f"n_layers={self.model.n_layers} must divide evenly into "
                    f"mesh.pipe={self.mesh.pipe} stages"
                )
            if self.mesh.sequence > 1:
                raise ValueError(
                    "mesh.pipe > 1 with mesh.sequence > 1 is not supported: "
                    "ring attention's shard_map cannot nest inside the "
                    "pipeline's manual pipe axis"
                )
            n_batch_axes = sum(
                a > 1 for a in (self.mesh.data, self.mesh.fsdp,
                                self.mesh.expert)
            )
            if n_batch_axes > 1:
                raise ValueError(
                    "mesh.pipe > 1 supports at most ONE batch-sharded axis "
                    "> 1 (data, fsdp, or expert): compound batch sharding "
                    "inside the partial-manual pipeline region hits an XLA "
                    "SPMD partitioner CHECK failure "
                    "(spmd_partitioner_util.cc group-count assertion). "
                    "Fold the batch parallelism into one axis"
                )
            # NOTE: attn_impl=pallas under pipe > 1 is NOT mutated here:
            # validation must not side-effect the config of record (a config
            # serialized after validate() has to match the operator's input).
            # The pallas→xla fallback lives in effective_model_config(),
            # applied where steps/models are actually built.
        if self.fl.client_count_scaling not in ("none", "linear", "sqrt"):
            raise ValueError(f"bad client_count_scaling {self.fl.client_count_scaling}")
        if self.model.resid_pdrop != 0.0:
            raise ValueError("resid_pdrop > 0 is not implemented yet (dropout-free pretraining)")
        if self.model.alibi and self.model.learned_pos_emb:
            raise ValueError("alibi and learned_pos_emb are mutually exclusive")
        if self.model.rope and (self.model.alibi or self.model.learned_pos_emb):
            raise ValueError("rope excludes alibi and learned_pos_emb")
        if self.model.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"bad model.norm {self.model.norm}")
        if self.model.mlp not in ("gelu", "swiglu", "moe"):
            raise ValueError(f"bad model.mlp {self.model.mlp}")
        if self.model.mlp == "moe":
            if self.model.moe_num_experts < 2:
                raise ValueError("mlp='moe' needs moe_num_experts >= 2")
            if self.model.moe_capacity_factor <= 0:
                # expert_capacity() would silently clamp every expert to
                # capacity 1 and mass-drop tokens
                raise ValueError(
                    f"moe_capacity_factor must be > 0, got "
                    f"{self.model.moe_capacity_factor}"
                )
            if self.model.moe_mlp_act not in ("gelu", "swiglu", "relu2"):
                raise ValueError(f"bad moe_mlp_act {self.model.moe_mlp_act}")
            if not 1 <= self.model.moe_top_k <= self.model.moe_num_experts:
                raise ValueError("moe_top_k must be in [1, moe_num_experts]")
            if self.mesh.expert > 1 \
                    and self.model.moe_num_experts % self.mesh.expert:
                raise ValueError(
                    f"moe_num_experts={self.model.moe_num_experts} must be "
                    f"divisible by mesh.expert={self.mesh.expert}"
                )

        elif self.mesh.expert > 1:
            raise ValueError("mesh.expert > 1 requires model.mlp='moe'")
        self._validate_latent_moe_family()
        if self.model.rope and self.model.d_head % 2:
            raise ValueError("rope needs an even d_head")
        if self.model.n_kv_heads < 0 or self.model.mlp_hidden_size < 0:
            raise ValueError("n_kv_heads and mlp_hidden_size must be >= 0")
        if self.model.n_kv_heads and self.model.n_heads % self.model.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.photon.host_threads < 0:
            raise ValueError(
                f"photon.host_threads must be >= 0 (0 = auto), got "
                f"{self.photon.host_threads}"
            )
        comp = self.photon.compression
        from photon_tpu.compression import policy_flags

        policy_flags(comp.policy)  # raises on unknown policy
        if comp.policy == "delta":
            # float64 deltas are LOSSLESS but ~2x the fp32 raw payload —
            # a correctness/debug rung, not a bytes saver
            warnings.warn(
                "compression.policy='delta' is lossless but INFLATES the "
                "wire ~2x on fp32 payloads (float64 deltas); use delta_q8 "
                "or delta_topk_q8 to actually reduce bytes",
                stacklevel=2,
            )
        if not 0.0 < comp.topk_ratio <= 1.0:
            raise ValueError(
                f"compression.topk_ratio must be in (0, 1], got {comp.topk_ratio}"
            )
        if comp.q8_block_size < 1:
            raise ValueError(
                f"compression.q8_block_size must be >= 1, got {comp.q8_block_size}"
            )
        if comp.ef_max_clients < 1:
            raise ValueError(
                f"compression.ef_max_clients must be >= 1, got {comp.ef_max_clients}"
            )
        mem = self.photon.membership
        if mem.ping_interval_rounds < 0 or mem.ping_timeout_s < 0:
            raise ValueError("membership ping knobs must be >= 0")
        if mem.suspect_after_misses < 1 or mem.dead_after_misses < mem.suspect_after_misses:
            raise ValueError(
                "membership needs 1 <= suspect_after_misses <= dead_after_misses, got "
                f"{mem.suspect_after_misses}/{mem.dead_after_misses}"
            )
        if mem.reconnect_backoff_base_s <= 0 or mem.reconnect_backoff_max_s < mem.reconnect_backoff_base_s:
            raise ValueError(
                "membership reconnect backoff needs 0 < base_s <= max_s, got "
                f"{mem.reconnect_backoff_base_s}/{mem.reconnect_backoff_max_s}"
            )
        if not 0.0 <= mem.reconnect_backoff_jitter < 1.0:
            raise ValueError(
                f"membership.reconnect_backoff_jitter must be in [0, 1), got "
                f"{mem.reconnect_backoff_jitter}"
            )
        if mem.reconnect_max_attempts < 0:
            raise ValueError("membership.reconnect_max_attempts must be >= 0 (0 = unlimited)")
        srv = self.photon.serve
        if srv.n_slots < 1 or srv.block_size < 1:
            raise ValueError(
                f"serve needs n_slots >= 1 and block_size >= 1, got "
                f"{srv.n_slots}/{srv.block_size}"
            )
        if srv.n_blocks < 0:
            raise ValueError(f"serve.n_blocks must be >= 0 (0 = auto), got {srv.n_blocks}")
        if srv.max_queue < 1 or srv.max_new_tokens < 1:
            raise ValueError(
                f"serve needs max_queue >= 1 and max_new_tokens >= 1, got "
                f"{srv.max_queue}/{srv.max_new_tokens}"
            )
        if srv.prefill_token_budget < 1:
            raise ValueError(
                f"serve.prefill_token_budget must be >= 1, got "
                f"{srv.prefill_token_budget}"
            )
        if srv.attention_impl not in ("auto", "ragged", "gather"):
            raise ValueError(
                f"serve.attention_impl must be one of auto/ragged/gather, "
                f"got {srv.attention_impl!r}"
            )
        if srv.attention_impl == "ragged" and not srv.attention_interpret:
            # fail at VALIDATION, not at the first decode step: an
            # explicitly-requested Pallas kernel needs a backend that can
            # lower it (or the interpreter opt-in for CPU parity runs)
            from photon_tpu.ops.flash_attention import pallas_supported

            if not pallas_supported(None):
                raise ValueError(
                    "serve.attention_impl='ragged' needs a Pallas-capable "
                    "backend (TPU); set serve.attention_interpret=true to "
                    "run the kernel through the interpreter, or use 'auto' "
                    "to fall back to the gather reference here"
                )
        if srv.drain_timeout_s <= 0:
            raise ValueError(
                f"serve.drain_timeout_s must be > 0, got {srv.drain_timeout_s}"
            )
        if not 0 <= srv.port <= 65535:
            raise ValueError(f"serve.port must be in [0, 65535], got {srv.port}")
        if srv.prefix_cache_blocks < 0:
            raise ValueError(
                f"serve.prefix_cache_blocks must be >= 0 (0 = no cap), got "
                f"{srv.prefix_cache_blocks}"
            )
        if srv.hotswap_poll_s <= 0:
            raise ValueError(
                f"serve.hotswap_poll_s must be > 0, got {srv.hotswap_poll_s}"
            )
        flt = srv.fleet
        if flt.replicas < 1:
            raise ValueError(
                f"serve.fleet.replicas must be >= 1, got {flt.replicas}"
            )
        for pname in ("port", "control_port"):
            pv = getattr(flt, pname)
            if not 0 <= pv <= 65535:
                raise ValueError(
                    f"serve.fleet.{pname} must be in [0, 65535], got {pv}"
                )
        if flt.prefix_affinity_blocks < 0:
            raise ValueError(
                f"serve.fleet.prefix_affinity_blocks must be >= 0 (0 = no "
                f"prefix affinity), got {flt.prefix_affinity_blocks}"
            )
        if flt.report_poll_s <= 0 or flt.report_timeout_s <= 0:
            raise ValueError(
                f"serve.fleet needs report_poll_s > 0 and report_timeout_s "
                f"> 0, got {flt.report_poll_s}/{flt.report_timeout_s}"
            )
        if flt.route_retries < 0:
            raise ValueError(
                f"serve.fleet.route_retries must be >= 0, got "
                f"{flt.route_retries}"
            )
        spec = srv.speculative
        if not 1 <= spec.k <= 32:
            raise ValueError(
                f"serve.speculative.k must be in [1, 32], got {spec.k} "
                "(the verify grid runs k+1 columns — a deeper draft than 32 "
                "is past any n-gram drafter's useful horizon)"
            )
        if spec.draft_budget < 1:
            raise ValueError(
                f"serve.speculative.draft_budget must be >= 1, got "
                f"{spec.draft_budget}"
            )
        if not 1 <= spec.min_ngram <= spec.max_ngram:
            raise ValueError(
                f"serve.speculative needs 1 <= min_ngram <= max_ngram, got "
                f"{spec.min_ngram}/{spec.max_ngram}"
            )
        if not 0.0 <= spec.accept_floor <= 1.0:
            raise ValueError(
                f"serve.speculative.accept_floor must be in [0, 1], got "
                f"{spec.accept_floor}"
            )
        if not 0.0 < spec.ewma_alpha <= 1.0:
            raise ValueError(
                f"serve.speculative.ewma_alpha must be in (0, 1], got "
                f"{spec.ewma_alpha}"
            )
        if spec.probe_ticks < 0:
            raise ValueError(
                f"serve.speculative.probe_ticks must be >= 0 (0 = never "
                f"probe), got {spec.probe_ticks}"
            )
        ad = self.photon.adapters
        if ad.enabled:
            if ad.rank < 1:
                raise ValueError(
                    f"photon.adapters.rank must be >= 1 when enabled, got "
                    f"{ad.rank} (rank 0 is no adapter at all)"
                )
            if ad.alpha <= 0:
                raise ValueError(
                    f"photon.adapters.alpha must be > 0, got {ad.alpha} "
                    "(the LoRA delta scales by alpha/rank)"
                )
            if not ad.targets:
                raise ValueError(
                    "photon.adapters.targets is empty — name at least one "
                    f"dense module to adapt (choose from {LORA_TARGETABLE})"
                )
            bad = [t for t in ad.targets if t not in LORA_TARGETABLE]
            if bad:
                raise ValueError(
                    f"photon.adapters.targets {bad} are not adaptable dense "
                    f"modules (choose from {LORA_TARGETABLE})"
                )
            if self.model.mlp == "moe":
                # same purity argument that makes MoE prefix-ineligible
                # (PR 10): expert-capacity routing is batch-global, so a
                # slot's adapted logits would depend on its batch-mates —
                # the per-cohort serving gather cannot be correct there
                raise ValueError(
                    "photon.adapters with model.mlp='moe' is not supported: "
                    "batch-global expert capacity breaks per-slot adapter "
                    "purity (the same reason MoE is prefix-cache-ineligible)"
                )
            if ad.pool_size < 1:
                raise ValueError(
                    f"photon.adapters.pool_size must be >= 1, got "
                    f"{ad.pool_size}"
                )
            if not isinstance(ad.cohorts, dict):
                raise ValueError(
                    f"photon.adapters.cohorts must map cohort name -> [cid, "
                    f"...], got {type(ad.cohorts).__name__}"
                )
            if not ad.cohorts:
                raise ValueError(
                    "photon.adapters.enabled needs a non-empty cohorts map "
                    "(cohort name -> [cid, ...]; serve-side configs may use "
                    "empty cid lists — the names select the adapter bank)"
                )
            seen_cids: dict[int, str] = {}
            for name, cids in ad.cohorts.items():
                if not isinstance(cids, (list, tuple)):
                    raise ValueError(
                        f"photon.adapters.cohorts[{name!r}] must be a list "
                        f"of client ids, got {type(cids).__name__}"
                    )
                for cid in cids:
                    if not isinstance(cid, int) or cid < 0:
                        raise ValueError(
                            f"photon.adapters.cohorts[{name!r}] has a bad "
                            f"client id {cid!r} (need ints >= 0)"
                        )
                    if cid in seen_cids:
                        raise ValueError(
                            f"client id {cid} appears in cohorts "
                            f"{seen_cids[cid]!r} AND {name!r} — cohorts must "
                            "not overlap (one adapter per client)"
                        )
                    seen_cids[cid] = name
            if self.fl.aggregate_momenta:
                raise ValueError(
                    "photon.adapters with fl.aggregate_momenta is not "
                    "supported: the adapter wire carries A/B factors only "
                    "(momenta piggybacking is a full-payload feature)"
                )
            if self.photon.comm_stack.collective_device_optimizer:
                raise ValueError(
                    "photon.adapters runs the per-cohort server optimizers "
                    "on host (adapter payloads are tiny); set "
                    "comm_stack.collective_device_optimizer=false"
                )
        if self.model.lora_rank < 0:
            raise ValueError(
                f"model.lora_rank must be >= 0, got {self.model.lora_rank}"
            )
        if self.model.lora_rank:
            if self.model.lora_alpha <= 0:
                raise ValueError(
                    f"model.lora_alpha must be > 0, got "
                    f"{self.model.lora_alpha}"
                )
            bad = [t for t in self.model.lora_targets
                   if t not in LORA_TARGETABLE]
            if bad:
                raise ValueError(
                    f"model.lora_targets {bad} are not adaptable dense "
                    f"modules (choose from {LORA_TARGETABLE})"
                )
            if self.model.mlp == "moe":
                raise ValueError("model.lora_rank with mlp='moe' is not supported")
        tel = self.photon.telemetry
        if not 0 <= tel.prom_port <= 65535:
            raise ValueError(
                f"telemetry.prom_port must be in [0, 65535] (0 = off), got "
                f"{tel.prom_port}"
            )
        if tel.max_buffered_spans < 1:
            raise ValueError(
                f"telemetry.max_buffered_spans must be >= 1, got "
                f"{tel.max_buffered_spans}"
            )
        if tel.profile_rounds < 0:
            raise ValueError(
                f"telemetry.profile_rounds must be >= 0 (0 = off), got "
                f"{tel.profile_rounds}"
            )
        if tel.metrics_retention < 1:
            raise ValueError(
                f"telemetry.metrics_retention must be >= 1, got "
                f"{tel.metrics_retention}"
            )
        if tel.profile_rounds and not tel.enabled:
            warnings.warn(
                "telemetry.profile_rounds is set but telemetry.enabled=False "
                "— no profile will be captured",
                stacklevel=2,
            )
        apc = tel.autopilot
        if apc.enabled and not tel.enabled:
            raise ValueError(
                "telemetry.autopilot.enabled needs telemetry.enabled=true: "
                "the controller reads the process-global metrics hub and "
                "health monitor"
            )
        if apc.period_s <= 0:
            raise ValueError(
                f"telemetry.autopilot.period_s must be > 0, got {apc.period_s}"
            )
        if apc.cooldown_s < 0:
            raise ValueError(
                f"telemetry.autopilot.cooldown_s must be >= 0, got "
                f"{apc.cooldown_s}"
            )
        if apc.relax_after < 1:
            raise ValueError(
                f"telemetry.autopilot.relax_after must be >= 1, got "
                f"{apc.relax_after}"
            )
        if apc.window_s <= 0:
            raise ValueError(
                f"telemetry.autopilot.window_s must be > 0, got {apc.window_s}"
            )
        if apc.decisions < 1:
            raise ValueError(
                f"telemetry.autopilot.decisions must be >= 1, got "
                f"{apc.decisions}"
            )
        if not 0.0 < apc.clear_frac <= 1.0:
            raise ValueError(
                f"telemetry.autopilot.clear_frac must be in (0, 1], got "
                f"{apc.clear_frac}"
            )
        if not 0.0 < apc.queue_high_frac <= 1.0:
            raise ValueError(
                f"telemetry.autopilot.queue_high_frac must be in (0, 1], got "
                f"{apc.queue_high_frac}"
            )
        if not 0.0 <= apc.queue_clear_frac < apc.queue_high_frac:
            raise ValueError(
                f"telemetry.autopilot.queue_clear_frac must be in "
                f"[0, queue_high_frac={apc.queue_high_frac}), got "
                f"{apc.queue_clear_frac}"
            )
        if apc.prefill_budget_min < 1:
            raise ValueError(
                f"telemetry.autopilot.prefill_budget_min must be >= 1, got "
                f"{apc.prefill_budget_min}"
            )
        if not 0.0 < apc.prefill_shrink < 1.0:
            raise ValueError(
                f"telemetry.autopilot.prefill_shrink must be in (0, 1), got "
                f"{apc.prefill_shrink}"
            )
        if apc.tpot_p50_slo_s < 0:
            raise ValueError(
                f"telemetry.autopilot.tpot_p50_slo_s must be >= 0 (0 = off), "
                f"got {apc.tpot_p50_slo_s}"
            )
        if apc.spec_k_min < 1:
            raise ValueError(
                f"telemetry.autopilot.spec_k_min must be >= 1, got "
                f"{apc.spec_k_min}"
            )
        if apc.reclaim_free_blocks < 0:
            raise ValueError(
                f"telemetry.autopilot.reclaim_free_blocks must be >= 0, got "
                f"{apc.reclaim_free_blocks}"
            )
        if not 0.0 <= apc.straggler_p90 <= 1.0:
            raise ValueError(
                f"telemetry.autopilot.straggler_p90 must be in [0, 1] "
                f"(0 = off), got {apc.straggler_p90}"
            )
        if apc.stage_timeout_min_s <= 0:
            raise ValueError(
                f"telemetry.autopilot.stage_timeout_min_s must be > 0, got "
                f"{apc.stage_timeout_min_s}"
            )
        if not 0.0 < apc.stage_timeout_shrink < 1.0:
            raise ValueError(
                f"telemetry.autopilot.stage_timeout_shrink must be in "
                f"(0, 1), got {apc.stage_timeout_shrink}"
            )
        if apc.wire_slope_bytes_per_s < 0:
            raise ValueError(
                f"telemetry.autopilot.wire_slope_bytes_per_s must be >= 0 "
                f"(0 = off), got {apc.wire_slope_bytes_per_s}"
            )
        if apc.async_reject_per_version < 0:
            raise ValueError(
                f"telemetry.autopilot.async_reject_per_version must be >= 0 "
                f"(0 = off), got {apc.async_reject_per_version}"
            )
        if apc.max_staleness_hi < 0:
            raise ValueError(
                f"telemetry.autopilot.max_staleness_hi must be >= 0, got "
                f"{apc.max_staleness_hi}"
            )
        if apc.replica_compile_streak < 0:
            raise ValueError(
                f"telemetry.autopilot.replica_compile_streak must be >= 0 "
                f"(0 = off), got {apc.replica_compile_streak}"
            )
        from photon_tpu.chaos.injector import validate_chaos_config

        validate_chaos_config(self.photon.chaos)
        if not self.photon.chaos.enabled and (
            self.photon.chaos.crash_phase
            or any(
                getattr(self.photon.chaos, p) > 0.0
                for p in (
                    "tcp_drop_p", "tcp_delay_p", "tcp_duplicate_p", "tcp_corrupt_p",
                    "store_slow_p", "store_partial_p", "store_bitflip_p",
                    "serve_stall_per_token_s", "serve_hbm_ramp_frac",
                )
            )
        ):
            warnings.warn(
                "photon.chaos knobs are set but chaos.enabled=False — no "
                "faults will be injected",
                stacklevel=2,
            )
        ar = self.photon.async_rounds
        if ar.staleness_policy not in ("poly", "const"):
            raise ValueError(
                f"async_rounds.staleness_policy must be 'poly' or 'const', "
                f"got {ar.staleness_policy!r}"
            )
        if ar.buffer_size < 0:
            raise ValueError(
                f"async_rounds.buffer_size must be >= 0 (0 = full cohort), "
                f"got {ar.buffer_size}"
            )
        if ar.max_staleness < 0:
            raise ValueError(
                f"async_rounds.max_staleness must be >= 0, got {ar.max_staleness}"
            )
        if ar.staleness_power < 0:
            raise ValueError(
                f"async_rounds.staleness_power must be >= 0, got "
                f"{ar.staleness_power}"
            )
        if ar.n_versions < 0:
            raise ValueError(
                f"async_rounds.n_versions must be >= 0 (0 = fl.n_rounds), "
                f"got {ar.n_versions}"
            )
        if ar.fit_time_s <= 0:
            raise ValueError(
                f"async_rounds.fit_time_s must be > 0, got {ar.fit_time_s}"
            )
        if ar.enabled:
            if not self.photon.comm_stack.collective:
                raise ValueError(
                    "photon.async_rounds needs comm_stack.collective=true: "
                    "the buffered server folds arrivals through the "
                    "device-resident aggregation plane"
                )
            k = ar.buffer_size or self.fl.n_total_clients
            if k > self.fl.n_total_clients:
                raise ValueError(
                    f"async_rounds.buffer_size={ar.buffer_size} exceeds "
                    f"fl.n_total_clients={self.fl.n_total_clients} — the "
                    "buffer could never fill"
                )
            if not 1 <= ar.min_arrivals <= k:
                raise ValueError(
                    f"async_rounds.min_arrivals must be in [1, K={k}], got "
                    f"{ar.min_arrivals} (above K the clock could never "
                    "advance)"
                )
        elif (
            ar.buffer_size or ar.min_arrivals != 1 or ar.max_staleness != 4
            or ar.staleness_policy != "poly" or ar.staleness_power != 1.0
            or ar.n_versions or ar.fit_time_s != 1.0
        ):
            warnings.warn(
                "photon.async_rounds knobs are set but async_rounds.enabled="
                "False — the synchronous round clock will run",
                stacklevel=2,
            )
        if comp.policy != "off" and self.photon.comm_stack.collective:
            raise ValueError(
                "compression applies to the pointer planes (shm/objstore/"
                "inline); the collective comm stack aggregates on-device and "
                "bypasses the wire codec — set compression.policy='off' "
                "(in-collective quantization is its own knob: "
                "comm_stack.collective_quantization)"
            )
        from photon_tpu.compression.quantize import COLLECTIVE_QUANTIZATIONS

        cs = self.photon.comm_stack
        if cs.collective_quantization not in COLLECTIVE_QUANTIZATIONS:
            raise ValueError(
                f"comm_stack.collective_quantization must be one of "
                f"{COLLECTIVE_QUANTIZATIONS}, got {cs.collective_quantization!r}"
            )
        if cs.collective_replica < 1:
            raise ValueError(
                f"comm_stack.collective_replica must be >= 1, got "
                f"{cs.collective_replica}"
            )
        if cs.collective_q8_block < 0:
            raise ValueError(
                f"comm_stack.collective_q8_block must be >= 0 (0 = codec "
                f"default), got {cs.collective_q8_block}"
            )
        if cs.collective_stage_timeout_s < 0:
            raise ValueError(
                f"comm_stack.collective_stage_timeout_s must be >= 0 "
                f"(0 = no deadlines), got {cs.collective_stage_timeout_s}"
            )
        if not 0.0 < cs.collective_quorum <= 1.0:
            raise ValueError(
                f"comm_stack.collective_quorum must be in (0, 1], got "
                f"{cs.collective_quorum}"
            )
        if cs.collective_retry_budget < 0:
            raise ValueError(
                f"comm_stack.collective_retry_budget must be >= 0, got "
                f"{cs.collective_retry_budget}"
            )
        if not cs.collective and (
            cs.collective_quantization != "off"
            or cs.collective_replica != 1
            or cs.collective_q8_block != 0
            or cs.collective_device_optimizer
            or not cs.collective_zero1
            or cs.collective_stage_timeout_s != 0.0
            or cs.collective_quorum != 0.5
            or cs.collective_retry_budget != 1
        ):
            raise ValueError(
                "comm_stack.collective_{quantization,replica,q8_block,"
                "device_optimizer,zero1,stage_timeout_s,quorum,retry_budget} "
                "shape the collective aggregation plane — set "
                "comm_stack.collective=true (the driver topologies "
                "would silently ignore them)"
            )
        if self.mesh.surplus_devices not in ("warn", "error", "ignore"):
            raise ValueError(
                f"mesh.surplus_devices must be one of ('warn', 'error', "
                f"'ignore'), got {self.mesh.surplus_devices!r}"
            )
        _ = self.model.d_head
        return self


def refuse_training_only_family(model: ModelConfig, what: str) -> None:
    """Raise where ``what`` (serving, cached decode, HF import / export) meets
    a model only the training path computes: latent attention has no latent
    paged cache or absorbed decode yet, the dropless expert layer no decode
    step, the leading dense blocks no place in the one-stack cache geometry
    or the import maps. Refusing beats running it wrong."""
    has = [name for name, on in (
        ("latent attention (kv_lora_rank > 0)", model.latent_attention),
        ("the dropless routers (moe_router='sigmoid' / 'softmax_topk')",
         model.dropless_moe),
        ("leading dense blocks (first_k_dense > 0)", model.first_k_dense > 0),
        ("layers of different kinds (layer_types)", model.hybrid),
        ("the embedding / residual / logits / attention multipliers", model.scaled),
        ("the indexer's sparse attention (dsa_topk > 0)", model.sparse_attention),
        ("per-head q/k norms (qk_norm)", model.qk_norm),
        ("heads of their own width (head_dim)", model.head_dim > 0),
        ("hyper-connected residual streams (hc_mult > 1)", model.hyper_connected),
        ("YaRN's rotary frequencies (rope_scaling_type)", model.yarn),
        ("sliding-window layers (layer_types' sliding_attention)", model.swa_layers > 0),
        ("a partly turned head (partial_rotary_factor)", model.partial_rotary_factor != 1.0),
        ("a gated attention output (attn_gate)", bool(model.attn_gate)),
    ) if on]
    if has:
        raise NotImplementedError(
            f"{what} does not support {', '.join(has)}: model "
            f"{model.name!r} runs on the training path only")


def effective_model_config(model: ModelConfig, mesh: MeshConfig) -> ModelConfig:
    """The model config a step builder should actually use for ``mesh``.

    Pure function of (model, mesh) — the config of record is never mutated
    (validation must stay side-effect free so a serialized config matches
    the operator's input). Fallbacks, each with a warning:

    - ``pipe > 1`` + pallas → xla: the pallas dispatch shard_maps over
      batch/head axes, which cannot nest inside the pipeline's
      partial-manual region;
    - ``sequence > 1`` + pallas → ring: a sequence-sharded mesh needs the
      context-parallel dispatch (the plain pallas call sees
      sequence-sharded operands GSPMD cannot partition — Mosaic kernels
      aren't auto-partitioned).
    """
    if mesh.pipe > 1 and model.attn_impl == AttnImpl.PALLAS.value:
        warnings.warn(
            "mesh.pipe > 1 with attn_impl=pallas: falling back to "
            "attn_impl=xla inside pipeline stages",
            stacklevel=2,
        )
        return dataclasses.replace(model, attn_impl=AttnImpl.XLA.value)
    if mesh.sequence > 1 and model.attn_impl == AttnImpl.PALLAS.value:
        warnings.warn(
            "mesh.sequence > 1 with attn_impl=pallas: upgrading to "
            "attn_impl=ring (context-parallel flash over the sequence axis)",
            stacklevel=2,
        )
        return dataclasses.replace(model, attn_impl=AttnImpl.RING.value)
    return model


def _build_dataclass(cls: type, d: dict[str, Any]) -> Any:
    """Recursively build a dataclass from a (possibly partial) dict.

    Field types are resolved with ``typing.get_type_hints`` so nested
    dataclasses work under PEP-563 string annotations without a registry.
    """
    if not dataclasses.is_dataclass(cls):
        return d
    kwargs: dict[str, Any] = {}
    hints = typing.get_type_hints(cls)
    field_names = {f.name for f in dataclasses.fields(cls)}
    for name, value in (d or {}).items():
        if name not in field_names:
            raise ValueError(f"unknown config key {cls.__name__}.{name}")
        ftype = hints.get(name)
        if ftype is not None and dataclasses.is_dataclass(ftype) and isinstance(value, dict):
            kwargs[name] = _build_dataclass(ftype, value)
        elif name in ("betas", "lora_targets") and isinstance(value, (list, tuple)):
            # tuples keep the dataclass hashable (decode_jit_pair keys the
            # shared compile cache on dataclasses.astuple(ModelConfig))
            kwargs[name] = tuple(value)
        else:
            kwargs[name] = value
    return cls(**kwargs)
