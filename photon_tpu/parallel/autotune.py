"""Heterogeneity-aware layout auto-tuner: pick (data, fsdp, tensor, pipe)
from a cost model instead of hand-set ``parallel/`` knobs (ISSUE 14b).

Federated clients run on *uneven* hardware — a 1-chip dev box, a 4-chip
v5e quarter-slice, an 8-chip host — and the right mesh layout for the same
``ModelConfig`` differs per slice. AMP (PAPERS.md) shows the shape of the
fix: enumerate the legal parallelism layouts for the client's device slice
and rank them with an *analytic* cost model (per-layer FLOPs + HBM from
the config, bandwidth terms per collective), so each client calls ONE
entry point (:func:`autotune_mesh`) instead of hand-tuning ``MeshConfig``.
The pjit/TPUv4 scaling literature grounds the cost terms; the federated
DCN term reuses the PR 7 modeled-bytes machinery
(``collective_agg.modeled_cross_slice_bytes``) so the exchange leg is
priced with exactly the model the aggregation plane's tests pin
(``tests/test_collective_agg.py``).

The model is deliberately coarse — its job is the *ranking*, not absolute
seconds. ``tests/test_autotune.py`` holds the rankings it must give (a
small model prefers data parallelism, a big one shards its state to fit)
and that the HBM estimate brackets the AOT compiler's ``memory_analysis``
on the abstract v5e topologies (``parallel/topo.py``) where libtpu is
available. Its top pick against a step measured on the chip: not measured.

Cost terms per optimizer step (see :func:`estimate_layout`):

- **compute**: ``flops_per_token × tokens / (devices × peak × mfu)``,
  inflated by the GPipe bubble ``(pipe − 1)/n_micro`` on pipelined
  layouts.
- **tensor parallel**: 4 activation all-reduces per layer (attn out +
  MLP down, fwd+bwd), ring cost ``2(t−1)/t``, over ICI.
- **data parallel**: one gradient all-reduce of the device's param shard,
  ring cost ``2(d−1)/d``, over ICI.
- **fsdp (ZeRO-3)**: params all-gather (fwd + bwd) + gradient
  reduce-scatter ≈ 3 legs of the device's gathered param bytes,
  ``(f−1)/f``, over ICI.
- **pipeline p2p**: boundary activations per microbatch, fwd+bwd.
- **federated exchange** (optional): the client's per-round DCN share
  from ``modeled_cross_slice_bytes``, amortized over ``local_steps``.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

from photon_tpu.config.schema import MeshConfig, ModelConfig
from photon_tpu.utils.profiling import (
    TPU_V5E_PEAK_FLOPS,
    model_flops_per_token,
    peak_flops_for_device_kind,
)


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Per-chip numbers the cost model prices a layout with. Defaults are
    v5e-shaped; heterogeneous clients pass their own (that's the point)."""

    peak_flops: float = TPU_V5E_PEAK_FLOPS
    hbm_bytes: float = 16.0e9
    #: achievable ICI bandwidth per chip (bytes/s, both directions summed
    #: — only the RATIO to dcn matters for the ranking)
    ici_bytes_per_s: float = 9.0e10
    #: cross-slice / data-center network bandwidth per host (bytes/s)
    dcn_bytes_per_s: float = 3.0e9
    #: fraction of peak the dense compute actually sustains (MFU); the
    #: repo's measured 125M recipe runs ~0.4 on v5e (PERF.md)
    mfu: float = 0.4
    #: fixed per-collective cost (dispatch + rendezvous), the α of the α-β
    #: model: tiny payloads are LATENCY-dominated — a layout that issues
    #: 4 all-reduces per layer (tensor parallel) pays 4L dispatches where
    #: pure data parallel pays one, regardless of bytes. Without this term
    #: the model mis-ranks small models, where bandwidth costs vanish.
    coll_latency_s: float = 1.0e-5

    @classmethod
    def for_device_kind(cls, kind: str) -> "HardwareModel":
        # a ranking aid, not a report: a kind without a published peak (the
        # CPU the tests plan on) is priced at the documented v5e planning
        # value, passed explicitly
        return cls(peak_flops=peak_flops_for_device_kind(
            kind, default=TPU_V5E_PEAK_FLOPS))


def model_param_count(cfg: ModelConfig) -> int:
    """Analytic parameter count, mirroring
    :func:`~photon_tpu.utils.profiling.model_flops_per_token`'s weight
    accounting (same MLP/GQA/MoE knob handling) so FLOPs and bytes are
    priced from one vocabulary."""
    d, L, v = cfg.d_model, cfg.n_layers, cfg.vocab_size
    hidden = cfg.mlp_hidden_size or cfg.expansion_ratio * d
    mlp_w = (3 if cfg.mlp == "swiglu" else 2) * d * hidden
    if cfg.mlp == "moe" and cfg.moe_num_experts:
        mlp_w = ((3 if cfg.moe_mlp_act == "swiglu" else 2) * d * hidden
                 * cfg.moe_num_experts + d * cfg.moe_num_experts)
    n_kv = cfg.n_kv_heads or cfg.n_heads
    attn_w = d * (cfg.n_heads + 2 * n_kv) * cfg.d_head + d * d
    n = L * (attn_w + mlp_w) + v * d
    if cfg.learned_pos_emb and not (cfg.rope or cfg.alibi):
        n += cfg.max_seq_len * d
    if not cfg.tie_embeddings:
        n += v * d
    return int(n)


@dataclasses.dataclass
class LayoutEstimate:
    """One ranked layout: the mesh plus the cost model's verdict."""

    mesh: MeshConfig
    est_step_s: float
    compute_s: float
    comm_s: float
    bubble_frac: float
    hbm_bytes_per_device: float
    fits: bool
    #: per-collective seconds (tensor/data/fsdp/pipe/federated_dcn) — the
    #: audit trail for "why did the tuner pick this"
    breakdown: dict = dataclasses.field(default_factory=dict)

    @property
    def axes(self) -> tuple[int, int, int, int]:
        m = self.mesh
        return (m.data, m.fsdp, m.tensor, m.pipe)


def _divisors(n: int) -> list[int]:
    return [k for k in range(1, n + 1) if n % k == 0]


def enumerate_layouts(
    model_cfg: ModelConfig,
    n_devices: int,
    global_batch_size: int,
    max_pipe: int | None = None,
) -> list[MeshConfig]:
    """Every LEGAL ``(data, fsdp, tensor, pipe)`` factorization of
    ``n_devices`` (sequence/expert stay 1 — context and expert parallelism
    are workload switches, not free layout choices). Legality mirrors what
    ``Config.validate`` + the sharding rules would accept usefully:

    - ``pipe`` divides ``n_layers``; a pipelined layout keeps at most ONE
      batch-sharded axis > 1 (the schema's pipeline constraint);
    - ``tensor`` divides ``d_model`` AND ``n_heads`` (and the kv heads
      when GQA narrows them) — an indivisible tensor axis would silently
      replicate (``sharding._fit_spec``), wasting the chips;
    - the global batch divides over the batch-sharded degree
      ``data × fsdp``.

    ``max_pipe`` caps the pipeline axis — callers whose step construction
    cannot pipeline (e.g. a Trainer with ``device_microbatch_size='auto'``,
    whose OOM probe builds the non-pipelined step) pass 1 so the tuner
    never hands back a layout the rest of their setup would reject.
    """
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    n_kv = model_cfg.n_kv_heads or model_cfg.n_heads
    out: list[MeshConfig] = []
    for pipe in _divisors(n_devices):
        if max_pipe is not None and pipe > max_pipe:
            continue
        if model_cfg.n_layers % pipe:
            continue
        rest = n_devices // pipe
        for tensor in _divisors(rest):
            if (model_cfg.d_model % tensor or model_cfg.n_heads % tensor
                    or n_kv % tensor):
                continue
            dp_total = rest // tensor
            for data in _divisors(dp_total):
                fsdp = dp_total // data
                if pipe > 1 and data > 1 and fsdp > 1:
                    continue  # schema: one batch-sharded axis with pipe
                if global_batch_size % (data * fsdp):
                    continue
                out.append(MeshConfig(data=data, fsdp=fsdp, tensor=tensor,
                                      pipe=pipe))
    return out


def estimate_layout(
    model_cfg: ModelConfig,
    mesh_cfg: MeshConfig,
    global_batch_size: int,
    microbatch: int = 0,
    hw: HardwareModel | None = None,
    optimizer_state_tensors: int = 2,
    n_clients: int = 0,
    local_steps: int = 1,
    quantization: str = "off",
) -> LayoutEstimate:
    """Price one layout. ``microbatch=0`` derives the per-device
    microbatch from the batch-sharded degree (no grad accumulation);
    ``n_clients > 0`` adds the federated DCN exchange amortized over
    ``local_steps`` (the PR 7 modeled-bytes machinery)."""
    hw = hw or HardwareModel()
    d, f, t, p = mesh_cfg.data, mesh_cfg.fsdp, mesh_cfg.tensor, mesh_cfg.pipe
    n_devices = d * f * t * p
    dp = d * f
    seq = model_cfg.max_seq_len
    tokens = global_batch_size * seq
    n_params = model_param_count(model_cfg)
    param_bytes = 4.0 * n_params

    per_dev_batch = max(global_batch_size // dp, 1)
    micro = min(microbatch, per_dev_batch) if microbatch else per_dev_batch
    n_micro = max(per_dev_batch // micro, 1)

    compute_s = (model_flops_per_token(model_cfg) * tokens
                 / (n_devices * hw.peak_flops * hw.mfu))
    bubble_frac = (p - 1) / n_micro if p > 1 else 0.0
    compute_s *= 1.0 + bubble_frac

    act_bytes = 2.0  # bf16 activations on the wire
    L_local = model_cfg.n_layers / p
    tok_local = tokens / dp
    alpha = hw.coll_latency_s
    comm = {
        # 4 activation all-reduces per local layer (attn out + mlp down,
        # fwd+bwd), ring 2(t-1)/t
        "tensor_s": (4.0 * L_local * (alpha + tok_local * model_cfg.d_model
                     * act_bytes * 2.0 * (t - 1) / t / hw.ici_bytes_per_s))
                    if t > 1 else 0.0,
        # one grad all-reduce of this device's param shard, ring 2(d-1)/d
        "data_s": (alpha + 2.0 * (d - 1) / d * param_bytes / (f * t * p)
                   / hw.ici_bytes_per_s) if d > 1 else 0.0,
        # ZeRO-3: params all-gather fwd+bwd + grad reduce-scatter ≈ 3 legs
        "fsdp_s": (3.0 * alpha + 3.0 * (f - 1) / f * param_bytes / (t * p)
                   / hw.ici_bytes_per_s) if f > 1 else 0.0,
        # stage-boundary activations, per microbatch, fwd+bwd
        "pipe_s": (2.0 * (p - 1) * n_micro * (alpha + micro * seq
                   * model_cfg.d_model * act_bytes / hw.ici_bytes_per_s))
                  if p > 1 else 0.0,
    }
    if n_clients > 0:
        from photon_tpu.parallel.collective_agg import modeled_cross_slice_bytes

        exchange = modeled_cross_slice_bytes(
            [n_params], n_clients, quantization=quantization,
        ) / max(n_clients, 1)  # this client's share of the exchange
        comm["federated_dcn_s"] = ((alpha + exchange / hw.dcn_bytes_per_s)
                                   / max(local_steps, 1))
    comm_s = float(sum(comm.values()))

    # per-device HBM: fp32 params + grads + optimizer moments shard over
    # (fsdp, tensor, pipe) — data parallelism replicates them — plus a
    # coarse activation term: the train step scans microbatches, so only
    # ONE microbatch's backward-pass activations live at a time (≈12 bf16
    # tensors of [micro × seq, d] per local layer — attention internals
    # and the MLP widening make 6 too optimistic against the compiler's
    # accounting; remat would shrink it further, we price the un-remat
    # worst case). ``fits`` keeps a 10% headroom: the estimate is a
    # ranking device and XLA's temps are not modeled leaf by leaf.
    state_bytes = param_bytes * (2 + optimizer_state_tensors) / (f * t * p)
    act_hbm = (12.0 * L_local * micro * seq * model_cfg.d_model
               * act_bytes / t)
    hbm = state_bytes + act_hbm
    return LayoutEstimate(
        mesh=mesh_cfg,
        est_step_s=compute_s + comm_s,
        compute_s=compute_s,
        comm_s=comm_s,
        bubble_frac=bubble_frac,
        hbm_bytes_per_device=hbm,
        fits=hbm <= 0.9 * hw.hbm_bytes,
        breakdown=comm,
    )


def rank_layouts(
    model_cfg: ModelConfig,
    n_devices: int,
    global_batch_size: int = 256,
    max_pipe: int | None = None,
    **kw,
) -> list[LayoutEstimate]:
    """All legal layouts, best first: fitting layouts before non-fitting,
    then by estimated step seconds. Raises when nothing is legal (an
    indivisible model/batch for this device count deserves a loud error,
    not a silent 1×1×1×1)."""
    layouts = enumerate_layouts(
        model_cfg, n_devices, global_batch_size, max_pipe=max_pipe
    )
    if not layouts:
        raise ValueError(
            f"no legal (data, fsdp, tensor, pipe) layout for {n_devices} "
            f"devices / batch {global_batch_size} / model {model_cfg.name!r}"
        )
    ests = [
        estimate_layout(model_cfg, m, global_batch_size, **kw)
        for m in layouts
    ]
    ests.sort(key=lambda e: (not e.fits, e.est_step_s))
    return ests


def autotune_layout(
    model_cfg: ModelConfig,
    n_devices: int | None = None,
    devices: Sequence | None = None,
    global_batch_size: int = 256,
    hw: HardwareModel | None = None,
    **kw,
) -> LayoutEstimate:
    """The per-client entry point: best layout for THIS slice. Pass either
    ``devices`` (their count and kind seed the hardware model) or an
    explicit ``n_devices``."""
    if devices is not None:
        n_devices = len(devices)
        if hw is None:
            kind = getattr(devices[0], "device_kind", "") or ""
            hw = HardwareModel.for_device_kind(kind)
    if n_devices is None:
        raise ValueError("pass devices=... or n_devices=...")
    return rank_layouts(
        model_cfg, n_devices, global_batch_size, hw=hw, **kw
    )[0]


def autotune_mesh(
    model_cfg: ModelConfig,
    n_devices: int | None = None,
    devices: Sequence | None = None,
    global_batch_size: int = 256,
    **kw,
) -> MeshConfig:
    """:func:`autotune_layout`, returning just the ``MeshConfig`` (what a
    Trainer or YAML-writing operator consumes)."""
    return autotune_layout(
        model_cfg, n_devices=n_devices, devices=devices,
        global_batch_size=global_batch_size, **kw,
    ).mesh
