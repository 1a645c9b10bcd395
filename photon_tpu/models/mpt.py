"""Decoder-only language model family, TPU-first in flax.linen.

Behavioral parity target: llm-foundry's ``mpt_causal_lm`` as configured by the
reference (``conf/llm_config/mpt-125m.yaml:18-28``): learned positional
embeddings, pre-LayerNorm blocks, fused-QKV attention, 4x GELU MLP, no biases
(MPT ``no_bias``), tied input/output embeddings, vocab 50368.

Llama-family variants compose through ``ModelConfig`` knobs rather than a
second model class (``rope``/``norm: rmsnorm``/``mlp: swiglu``/untied
embeddings — preset ``llama-1b``), the shape of llm-foundry's
attn_config/ffn_config switches; every trainer, sharding, checkpoint, and
federation path is shared because the parameter tree keeps the same names.

A latent-attention / expert family composes the same way (preset
``glm-4.7-flash-ep8``, training path only): ``kv_lora_rank > 0`` swaps the
QKV projection for MLA's two low-rank paths (``MPTBlock._latent_qkv``; scores
still go through ``multihead_attention``), ``first_k_dense`` puts leading
dense blocks under a scan of their own (``dense_blocks``) before the stack,
and ``moe_router: sigmoid`` makes the stack's MLP the dropless expert layer of
``ops/moe.py`` with a shared expert. Norms, residuals and the attention
dispatch are the one ``MPTBlock``'s.

A learned-sparse-attention / expert family composes the same way (preset
``keye-vl-2.0-30b-a3b-ep8``, training path only): in the grouped-query branch
``head_dim`` makes the heads as wide as the projections, ``qk_norm`` puts a
per-head RMSNorm on q and k before the rotation, and ``dsa_topk > 0`` gives
the block an indexer (``MPTBlock._sparse_attention`` over ``ops/dsa.py``)
whose selection the masked flash kernel takes in place of the causal rule
and whose alignment loss is sown beside the expert counters;
``moe_router: softmax_topk`` is the dropless layer's second router (a float32
softmax over all experts, the picked probabilities renormalised; no
selection bias, no scale, no shared expert).

A hybrid family composes the same way again (preset
``granite-4.0-h-micro-stage1``, training path only): ``layer_types`` gives
every layer its mixer, attention or a Mamba-2 mixer (``MPTBlock._mamba_mixer``
over ``ops/ssd.py``), and every run of equal kind is a scanned stack of its
own (``blocks_0``, ``blocks_1``, ...); the four multipliers scale the
embedding, each residual branch, the attention scores and the logits, and at
their defaults add no operation to any other model's graph.

A convolution / expert hybrid composes from all of these (preset
``lfm2-8b-a1b-ep4``, training path only): a third mixer, ``conv``
(``MPTBlock._short_conv_mixer``: a gated short convolution over
``ops/ssd.causal_conv1d``), in three layers of four, the grouped-query branch
with ``qk_norm`` in the fourth; the leading ``first_k_dense`` layers dense, the
others the sigmoid-routed dropless expert layer; every run of layers equal in
mixer and in MLP kind a scanned stack of its own (``ModelConfig.stacks``), so
the model has two expert stacks, each with its own selection bias.

Hyper-connected residual streams compose with the latent-attention / expert
family (preset ``xing4.0-29b-a4b-ep8``, training path only): ``hc_mult > 1``
makes the blocks' carry ``hc_mult`` streams ``[B, S, D]`` (a tuple: each is an
activation like any other, and no pass copies them into one array), and each
of a block's two residual adds becomes a read-in, a write-back and a mixing of
the streams by three per-token maps (``_hc_read_in`` / ``_write_back``; the
mixing matrix is Sinkhorn-projected onto the doubly stochastic ones). The
embedding enters every stream and their sum leaves for ``ln_f``. Latent
attention's v may be narrower than its q and k (``v_head_dim``), and
``rope_scaling_type: yarn`` gives the rotation YaRN's blended frequencies and
the softmax its scale. At ``hc_mult == 1`` none of it adds an operation.

Attention layers of two kinds compose with the expert family (preset
``laguna-xs.2-ep8``, training path only): ``layer_types``' ``full_attention``
and ``sliding_attention`` give a block its kind, and a kind its query heads
(``n_heads`` / ``swa_n_heads`` over the same key-value heads), its window (a
sliding layer's keys ``i - sliding_window < j <= i``: the flash kernel walks
that band), and its rotation (a full layer turns ``partial_rotary_factor`` of
its head by YaRN's frequencies with ``rope_scaling_attention_factor`` on cos
and sin, a sliding layer its whole head by plain ``swa_rope_theta``);
``attn_gate: headwise`` multiplies every head's output by a sigmoid gate of
the block's normed input before ``out_proj``. The multiply and its pull-back
are ``ops/head_gate.head_gate``, a ``custom_vjp`` that reads the flash
kernel's output and ``out_proj``'s cotangent as they lie in memory
(``[B, S, H·D]`` in the compute dtype, a head a column block) beside the
``[B, S, H]`` logits the ``attn_gate`` ``Dense`` returns, one Pallas launch a
pass where a head is whole lanes. Runs of layers equal in kind and
MLP are the scanned stacks (``ModelConfig.stacks``).

Layers of one branch compose with the Mamba-2 mixer and the expert layer
(preset ``nemotron-3-nano-30b-a3b-ep16``, training path only):
``single_branch_layers`` makes a block ONE pre-norm (``ln_1``), ONE branch and
one add, the branch by the layer's ``layer_types`` entry: a Mamba-2 mixer whose
B and C come in ``mamba_n_groups`` groups (head ``h`` reads group ``h //
(heads / groups)``) and whose gated norm works within each group's channels,
plain causal attention without positions, or the dropless expert layer alone
(``moe``), its experts ungated (``moe_mlp_act: relu2``: ``W_down relu(W_up
h)^2``, no ``moe_gate``) beside one shared expert of its own width
(``moe_shared_hidden_size``). Every run of equal kind is a scanned stack
(``ModelConfig.stacks``), so a pattern without equal neighbours is stacks of
one layer.

TPU-first design choices (not in the reference):
- Layers are stacked with ``nn.scan`` → one traced block, params carry a
  leading ``[n_layers, ...]`` axis. This keeps compile time flat in depth and
  gives FSDP a natural leading axis to shard.
- LayerNorm runs in fp32 regardless of compute dtype (the reference relies on
  Composer's amp_bf16 autocast rules for the same effect).
- Attention dispatches to the Pallas flash kernel or the XLA fallback
  (``photon_tpu/ops/attention.py``).
- ``remat=True`` wraps the block in ``jax.checkpoint`` (reference:
  ``fsdp_config.activation_checkpointing``).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from photon_tpu.config.schema import ModelConfig
from photon_tpu.models.step import sow
from photon_tpu.ops.attention import multihead_attention
from photon_tpu.ops.flash_attention import IN_PLACE, flash_layout
from photon_tpu.ops.head_gate import head_gate
from photon_tpu.utils.profiling import (
    ATTN_GATE_SCOPE,
    ATTN_PROJ_SCOPE,
    ATTN_QK_NORM_SCOPE,
    BLOCK_MLP_SCOPE,
    BLOCK_NORM_SCOPE,
    DSA_INDEX_LOSS_SCOPE,
    DSA_INDEXER_SCOPE,
    DSA_SELECT_SCOPE,
    MAMBA_CONV_SCOPE,
    MAMBA_GATE_NORM_SCOPE,
    MAMBA_PROJ_SCOPE,
    MAMBA_SCAN_SCOPE,
    MHC_MAPS_SCOPE,
    MHC_READ_IN_SCOPE,
    MHC_WRITE_BACK_SCOPE,
    SHORTCONV_MIX_SCOPE,
    SHORTCONV_PROJ_SCOPE,
)


def _dtype(name: str):
    return jnp.dtype(name)


def _constrain_activation(x: jax.Array, spec) -> jax.Array:
    """``with_sharding_constraint`` against the ambient mesh (no-op when
    tracing outside one), with indivisible axes dropped."""
    from photon_tpu.parallel.context import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return x
    from jax.sharding import NamedSharding

    from photon_tpu.parallel.sharding import _fit_spec

    fitted = _fit_spec(spec, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, fitted))


def _constrain_logits(logits: jax.Array) -> jax.Array:
    """Pin the logits layout ([B,S,V]: batch over data+fsdp+expert, seq over
    sequence, vocab over tensor) when tracing under a mesh. Without the hint
    SPMD can pick a batch-sharded logits layout and then involuntarily
    rematerialize the whole tensor to reach the loss reduction."""
    from jax.sharding import PartitionSpec as P

    return _constrain_activation(
        logits, P(("data", "fsdp", "expert"), "sequence", "tensor")
    )


class FP32LayerNorm(nn.Module):
    """LayerNorm computed in fp32, scale-only when ``no_bias``."""

    use_bias: bool = False
    eps: float = 1.0e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        orig_dtype = x.dtype
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        y = y * scale
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],), jnp.float32)
            y = y + bias
        return y.astype(orig_dtype)


class FP32RMSNorm(nn.Module):
    """RMSNorm in fp32 (llama-family norm; scale-only by construction).
    ``groups > 1`` norms within each of that many equal runs of the last axis'
    channels (the Mamba-2 gated norm's ``group_size``); the scale stays one a
    channel."""

    eps: float = 1.0e-5
    groups: int = 1

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x32 = x.astype(jnp.float32)
        if self.groups > 1:
            x32 = x32.reshape(*x.shape[:-1], self.groups, -1)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps
        )
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        return (y.reshape(x.shape) * scale).astype(x.dtype)


def _norm(cfg: ModelConfig, name: str) -> nn.Module:
    if cfg.norm == "rmsnorm":
        return FP32RMSNorm(eps=cfg.norm_eps, name=name)
    return FP32LayerNorm(use_bias=not cfg.no_bias, eps=cfg.norm_eps, name=name)


def apply_rope(q: jax.Array, k: jax.Array, theta: float,
               inv_freq: tuple[float, ...] | None = None,
               rotary_dim: int | None = None,
               factor: float = 1.0) -> tuple[jax.Array, jax.Array]:
    """Rotary positions on ``[B, S, H, D]`` q/k (llama/GPT-NeoX rotate-half
    convention, angles in fp32). Positions are LOGICAL sequence indices, so
    the rotation is correct under a GSPMD-sharded ``sequence`` mesh axis —
    ring attention receives already-rotated q/k and needs no offset.
    ``inv_freq`` (half as many static numbers as dims turn) stands in for
    ``theta``'s own frequencies: YaRN's (``ModelConfig.rope_inv_freq``).
    ``rotary_dim`` (``None``: all ``D``) turns the head's first ``rotary_dim``
    dims, dim ``i`` with ``i + rotary_dim / 2``, and passes the rest;
    ``factor`` multiplies cos and sin (HF's ``attention_factor``), so that
    the turned part of a score carries its square and the passed part 1."""
    d = q.shape[-1] if rotary_dim is None else rotary_dim
    half = d // 2
    if inv_freq is None:
        inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(q.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.cos(ang)[None, :, None, :]  # [1, S, 1, half]
    sin = jnp.sin(ang)[None, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor

    def rot(x):
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:d].astype(jnp.float32)
        parts = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
        if d != x.shape[-1]:
            parts.append(x[..., d:].astype(jnp.float32))
        return jnp.concatenate(parts, axis=-1).astype(x.dtype)

    return rot(q), rot(k)


class DenseKernel(nn.Module):
    """A bias-free ``nn.Dense``'s kernel without its product: the parameter
    ``<name>/kernel`` that ``nn.Dense(features, name=name)`` makes (the same
    shape, init and place in the tree), for a caller that multiplies by parts
    of it."""

    features: int
    param_dtype: Any
    kernel_init: Any

    @nn.compact
    def __call__(self, fan_in: int) -> jax.Array:
        return self.param("kernel", self.kernel_init, (fan_in, self.features),
                          self.param_dtype)


#: latent attention's projections (both low-rank paths, their norms, RoPE,
#: the key assembly, ``out_proj``) as a ``jax.named_scope``; the score and
#: value products between them keep ``multihead_attention``'s own names
MLA_PROJ_SCOPE = "mla/proj"


def _mamba_a_log_init(key, shape, dtype):
    """``A = -exp(A_log)`` uniform in [-16, -1], as the public Mamba-2 code."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)).astype(dtype)


def _mamba_dt_bias_init(key, shape, dtype):
    """The inverse softplus of a step log-uniform in [1e-3, 1e-1], so that a
    zero projection gives such a ``dt`` (the public Mamba-2 code's init)."""
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _conv_init(taps: int):
    """PyTorch's ``Conv1d`` default for a depthwise kernel of ``taps`` taps
    and for its bias, which the public Mamba-2 code keeps: uniform in
    ``+-1/sqrt(taps)``."""
    bound = taps ** -0.5

    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound).astype(dtype)

    return init


def _residual(cfg: ModelConfig, x: jax.Array, branch: jax.Array) -> jax.Array:
    """``x + residual_multiplier * branch``; at 1 the bare sum. (A function and
    not a method of the block: flax names a method's operations after it, and
    the other models' ``op_name``s stay as they were.)"""
    m = cfg.residual_multiplier
    return x + branch if m == 1.0 else x + branch * jnp.asarray(m, branch.dtype)


#: the mixing logits' diagonal at the start: after the projection stream j
#: keeps e^4 / (e^4 + n - 1) of itself (0.948 of four streams)
HC_RES_INIT = 4.0
#: the three learned scales (on the read-in, write-back and mixing logits'
#: data-dependent part) at the start
HC_ALPHA_INIT = 0.01


class _HyperConnection(NamedTuple):
    """What a sublayer's write-back takes from its read-in: the streams it
    read and the two maps that are not yet used, tokens on the last axis."""

    streams: tuple[jax.Array, ...]  # n x [B, S, D]
    post: jax.Array  # [n, N] float32: the branch's weight into stream j
    res: jax.Array  # [n, n, N] float32: stream i's weight into stream j, [j, i]


def _hc_bias_init(n: int):
    """``b`` of a sublayer's maps, ``[pre | post | res]``: the read-in weights
    start at ``1/n`` each (``sigmoid(-ln(n - 1))``), the write-back weights at
    1 (``2 sigmoid(0)``), the mixing logits at ``HC_RES_INIT`` on the diagonal
    and 0 off it, so that the projected matrix starts near the identity."""
    def init(key, shape, dtype):
        del key
        b = jnp.concatenate([jnp.full((n,), -jnp.log(n - 1.0)), jnp.zeros((n,)),
                             HC_RES_INIT * jnp.eye(n).reshape(-1)])
        return b.reshape(shape).astype(dtype)

    return init


def _hc_maps(cfg: ModelConfig, streams, phi, b, alpha):
    """The three maps of one sublayer from its streams (``n`` x ``[B, S, D]``),
    all float32 with the tokens on the last (lane) axis: ``pre [n, N]`` in
    (0, 1), ``post [n, N]`` in (0, 2), ``res [n, n, N]`` doubly stochastic to
    the iteration's precision, and the largest distance of one of ``res``'s
    row or column sums from 1. ``r = vec(X) / rms(vec(X))`` over all ``n D``
    values of a token (no gain: it would fold into ``phi``); ``[p | q | R] = r
    phi``; ``alpha * . + b``; a sigmoid, twice a sigmoid, and for ``R`` the
    ``exp`` of its clamped logits, then ``hc_sinkhorn_iters`` times columns
    and rows divided by their sums (``+ hc_eps``). The loops are unrolled and
    the sums over four slices are written out, so that all of it is
    elementwise on ``[.., N]`` arrays and differentiates as such."""
    n, eps = len(streams), cfg.hc_eps
    bsz, s, d = streams[0].shape
    tokens = bsz * s
    f32 = jnp.float32
    squares = sum(jnp.sum(jnp.square(x.astype(f32)), axis=-1) for x in streams)
    inv_rms = jax.lax.rsqrt(squares / (n * d) + eps).reshape(1, tokens)
    phi = phi.astype(f32).reshape(phi.shape[0], n, d)
    raw = sum(jnp.einsum("kd,bsd->kbs", phi[:, i], x.astype(f32)) for i, x in enumerate(streams))
    raw = raw.reshape(-1, tokens) * inv_rms
    alpha, b = alpha.astype(f32), b.astype(f32)[:, None]
    pre = jax.nn.sigmoid(alpha[0] * raw[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * raw[n:2 * n] + b[n:2 * n])
    logits = (alpha[2] * raw[2 * n:] + b[2 * n:]).reshape(n, n, tokens)
    m = jnp.exp(jnp.clip(logits, -cfg.hc_res_clamp, cfg.hc_res_clamp))

    def columns(m):  # [n, N]: column i's sum over the rows j
        return sum(m[j] for j in range(n))

    def rows(m):  # [n, N]: row j's sum over the columns i
        return sum(m[:, i] for i in range(n))

    for _ in range(cfg.hc_sinkhorn_iters):
        m = m / (columns(m) + eps)[None]
        m = m / (rows(m) + eps)[:, None]
    gap = jnp.maximum(jnp.max(jnp.abs(columns(m) - 1.0)), jnp.max(jnp.abs(rows(m) - 1.0)))
    return pre, post, m, gap


def _per_token(w: jax.Array, like: jax.Array) -> jax.Array:
    """A map's ``[N]`` float32 weights against ``like [B, S, D]``."""
    return w.reshape(*like.shape[:2], 1)


def _hc_read_in(block: nn.Module, x, name: str):
    """A sublayer's input and what its write-back needs. One residual stream
    (``hc_mult == 1``): ``x`` itself and ``None``, no operation. Hyper-
    connected, ``x`` is the ``n`` streams: the sublayer's maps from the
    parameters ``{name}_phi [2n + n^2, n D]``, ``{name}_b``, ``{name}_alpha``
    (float32; ``phi`` keeps the tokens' ``n D`` values on its lane axis), the
    read-in ``u = sum_i pre_i X_i`` in the streams' dtype, and the
    :class:`_HyperConnection`. The mixing matrix's distance from doubly
    stochastic is sown (``mhc_sinkhorn_gap``)."""
    cfg = block.cfg
    if not cfg.hyper_connected:
        return x, None
    n, d = cfg.hc_mult, cfg.d_model
    k = 2 * n + n * n
    phi = block.param(f"{name}_phi", nn.initializers.normal(stddev=cfg.emb_init_std),
                      (k, n * d), _dtype(cfg.param_dtype))
    b = block.param(f"{name}_b", _hc_bias_init(n), (k,), jnp.float32)
    alpha = block.param(f"{name}_alpha", nn.initializers.constant(HC_ALPHA_INIT),
                        (3,), jnp.float32)
    with jax.named_scope(MHC_MAPS_SCOPE):
        pre, post, res, gap = _hc_maps(cfg, x, phi, b, alpha)
        sow(block, "mhc_sinkhorn_gap", gap)
    with jax.named_scope(MHC_READ_IN_SCOPE):
        u = sum(_per_token(pre[i], xi) * xi.astype(jnp.float32) for i, xi in enumerate(x))
        u = u.astype(x[0].dtype)
    return u, _HyperConnection(tuple(x), post, res)


def _write_back(cfg: ModelConfig, x: jax.Array, branch: jax.Array,
                hc: _HyperConnection | None):
    """The sublayer's output into the residual path. One stream (``hc`` is
    ``None``): :func:`_residual`. Hyper-connected: the new streams ``X'_j =
    sum_i res[j, i] X_i + post_j branch``, float32 sums rounded to the
    streams' dtype, under a scope of their own (call it outside the scope of
    the branch's last projection: no operation carries two readers' scopes)."""
    if hc is None:
        return _residual(cfg, x, branch)
    with jax.named_scope(MHC_WRITE_BACK_SCOPE):
        f32 = jnp.float32
        y = branch.astype(f32)
        wide = [xi.astype(f32) for xi in hc.streams]
        return tuple(
            (sum(_per_token(hc.res[j, i], xi) * xi for i, xi in enumerate(wide))
             + _per_token(hc.post[j], y) * y).astype(hc.streams[j].dtype)
            for j in range(len(wide)))


class MPTBlock(nn.Module):
    cfg: ModelConfig
    #: a leading dense block of an expert model (``cfg.first_k_dense``): the
    #: same norms, residuals and attention, a SwiGLU of
    #: ``cfg.dense_mlp_hidden_size`` where the stack's blocks have experts
    dense_mlp: bool = False
    #: what mixes the positions (``cfg.layer_types``): ``attention`` (or
    #: ``full_attention`` / ``sliding_attention`` where a model has both kinds:
    #: ``cfg.attention_kind`` gives a kind its heads, window and rotation), or
    #: in its place ``mamba`` for a Mamba-2 mixer, ``conv`` for a gated short
    #: convolution
    mixer: str = "attention"

    def _mamba_mixer(self, h: jax.Array, dense, resid_std: float) -> jax.Array:
        """The Mamba-2 mixer on ``h [B, S, D]``: one projection to ``z | x B C
        | dt``; a causal depthwise convolution and SiLU over ``x B C``; the
        state-space scan (``ops/ssd.ssd_scan``: ``mamba_n_groups`` groups of
        ``B``, ``C``, each for its run of heads, float32 ``dt``, decays and
        state); the gate ``y * silu(z)`` before an RMSNorm within each group's
        inner channels (one group: over all of them); the projection back."""
        from photon_tpu.ops import ssd

        cfg = self.cfg
        compute = _dtype(cfg.compute_dtype)
        pd = _dtype(cfg.param_dtype)
        b, s, _ = h.shape
        inner, heads, groups = cfg.mamba_d_inner, cfg.mamba_n_heads, cfg.mamba_n_groups
        n = groups * cfg.mamba_d_state  # B's columns, and C's: group-major
        with jax.named_scope(MAMBA_PROJ_SCOPE):
            zxbcdt = dense(2 * inner + 2 * n + heads, "in_proj", cfg.emb_init_std)(h)
        z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * n], axis=-1)
        conv_init = _conv_init(cfg.mamba_d_conv)
        conv_kernel = self.param(
            "conv_kernel", conv_init, (cfg.mamba_d_conv, inner + 2 * n), pd)
        conv_bias = self.param("conv_bias", conv_init, (inner + 2 * n,), pd)
        a_log = self.param("A_log", _mamba_a_log_init, (heads,), jnp.float32)
        dt_bias = self.param("dt_bias", _mamba_dt_bias_init, (heads,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (heads,), jnp.float32)
        with jax.named_scope(MAMBA_CONV_SCOPE):
            xbc = nn.silu(ssd.causal_conv1d(xbc, conv_kernel, conv_bias)).astype(compute)
        with jax.named_scope(MAMBA_SCAN_SCOPE):
            y = ssd.ssd_scan(
                xbc[..., :inner].reshape(b, s, heads, cfg.mamba_d_head),
                nn.softplus(dt.astype(jnp.float32) + dt_bias), a_log,
                xbc[..., inner:inner + n], xbc[..., inner + n:], skip,
                # a row shorter than a chunk (``init_params``' 8 tokens) is one chunk
                chunk=min(cfg.mamba_chunk_size, s), compute_dtype=compute,
                impl=cfg.attn_impl, interpret=cfg.attn_interpret, groups=groups)
        with jax.named_scope(MAMBA_GATE_NORM_SCOPE):
            y = y.reshape(b, s, inner) * nn.silu(z.astype(jnp.float32))
            y = FP32RMSNorm(eps=cfg.norm_eps, groups=groups, name="mamba_norm")(y).astype(compute)
        with jax.named_scope(MAMBA_PROJ_SCOPE):
            return dense(cfg.d_model, "out_proj", resid_std)(y)

    def _short_conv_mixer(self, h: jax.Array, dense, resid_std: float) -> jax.Array:
        """The gated short convolution on ``h [B, S, D]`` (HF ``lfm2``): one
        projection to ``B | C | u``; a causal depthwise convolution of
        ``conv_kernel_size`` taps, without bias or activation, over ``B * u``;
        the gate ``C *`` on its output; the projection back. The taps and the
        second gate are float32, as ``causal_conv1d`` makes its sum."""
        from photon_tpu.ops import ssd

        cfg = self.cfg
        with jax.named_scope(SHORTCONV_PROJ_SCOPE):
            bcu = dense(3 * cfg.d_model, "in_proj", cfg.emb_init_std)(h)
        kernel = self.param(
            "conv_kernel", nn.initializers.normal(stddev=cfg.emb_init_std),
            (cfg.conv_kernel_size, cfg.d_model), _dtype(cfg.param_dtype))
        with jax.named_scope(SHORTCONV_MIX_SCOPE):
            b, c, u = jnp.split(bcu, 3, axis=-1)
            y = (c * ssd.causal_conv1d(b * u, kernel)).astype(_dtype(cfg.compute_dtype))
        with jax.named_scope(SHORTCONV_PROJ_SCOPE):
            return dense(cfg.d_model, "out_proj", resid_std)(y)

    def _latent_qkv(self, h: jax.Array, dense):
        """MLA in its training form: ``h [B, S, D]`` -> q, k ``[B, S, H,
        d_head]`` and v ``[B, S, H, v_head_dim]``. q through a low-rank pair
        with an RMSNorm between; one projection gives the kv latent and a rotary key that all
        heads share; the normed latent expands to per-head ``k_nope | v``.
        RoPE turns the rope part of q and the shared key; a head's key is
        ``[k_nope_h | k_rope]``."""
        cfg = self.cfg
        b, s, _ = h.shape
        nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        std = cfg.emb_init_std
        c_q = FP32RMSNorm(eps=cfg.norm_eps, name="q_a_norm")(
            dense(cfg.q_lora_rank, "q_a_proj", std)(h))
        q = dense(cfg.n_heads * (nope + rope), "q_b_proj", std)(c_q)
        q = q.reshape(b, s, cfg.n_heads, nope + rope)
        kv_a = dense(cfg.kv_lora_rank + rope, "kv_a_proj", std)(h)
        c_kv = FP32RMSNorm(eps=cfg.norm_eps, name="kv_a_norm")(
            kv_a[..., :cfg.kv_lora_rank])
        in_place = cfg.no_bias and flash_layout(
            cfg.n_heads, cfg.n_heads, nope + rope, dv) == IN_PLACE
        if not in_place:
            kv = dense(cfg.n_heads * (nope + dv), "kv_b_proj", std)(c_kv)
            kv = kv.reshape(b, s, cfg.n_heads, nope + dv)
        q_rope, k_rope = apply_rope(
            q[..., nope:], kv_a[..., None, cfg.kv_lora_rank:], cfg.rope_theta,
            cfg.rope_inv_freq(rope))
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        if in_place:
            return (q, *self._latent_kv_in_place(c_kv, k_rope[:, :, 0, :], std))
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, cfg.n_heads, rope))],
            axis=-1)
        return q, k, kv[..., nope:]

    def _latent_kv_in_place(self, c_kv: jax.Array, k_rope: jax.Array, std: float):
        """``_latent_qkv``'s k and v where the flash launches read their
        operands in place (``ops/flash_attention.flash_layout``: whole-lane
        head widths), each STRAIGHT OUT OF A PRODUCT as ``[B, S, H·D]``:
        ``v = c_kv @ W[:, h, nope:]`` and ``k = [c_kv | k_rope] @ [[W[:, h,
        :nope], 0], [0, I]]``, the shared rotary key carried into every head's
        last columns by an identity block (``rope`` more rows of a
        ``kv_lora_rank``-deep contraction, exact in any dtype). ``kv_b_proj``
        is the parameter it always was. Why: sliced and concatenated as
        ``[B, S, H, nope + dv]``, XLA lays a 20-head array out sequence-minor
        (heads would pad to whole sublane tiles) and copies k, v, dk and dv
        between that and the launches' row-major arrays, forward, under
        ``remat`` and backward; a product writes the layout its consumer
        asks for (PERF.md section 6, PR 45). q keeps its rotation on the
        ``[B, S, H, D]`` view, and its copy in and out."""
        cfg = self.cfg
        b, s, rank = c_kv.shape
        heads, nope, rope, dv = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                                 cfg.v_head_dim)
        compute = _dtype(cfg.compute_dtype)
        w = DenseKernel(heads * (nope + dv), _dtype(cfg.param_dtype),
                        nn.initializers.normal(stddev=std), name="kv_b_proj")(rank)
        w = w.astype(compute).reshape(rank, heads, nope + dv)
        eye = jnp.broadcast_to(jnp.eye(rope, dtype=compute)[:, None, :], (rope, heads, rope))
        w_k = jnp.concatenate([
            jnp.concatenate([w[..., :nope], jnp.zeros((rank, heads, rope), compute)], axis=-1),
            jnp.concatenate([jnp.zeros((rope, heads, nope), compute), eye], axis=-1),
        ], axis=0).reshape(rank + rope, heads * (nope + rope))
        c_kv = c_kv.astype(compute)
        k = jnp.concatenate([c_kv, k_rope.astype(compute)], axis=-1) @ w_k
        v = c_kv @ w[..., nope:].reshape(rank, heads * dv)
        return k.reshape(b, s, heads, nope + rope), v.reshape(b, s, heads, dv)

    def _sparse_attention(self, h: jax.Array, q: jax.Array, k: jax.Array,
                          v: jax.Array, dense) -> jax.Array:
        """Attention over the keys an indexer picks (``ops/dsa.py``): ``h [B,
        S, D]`` the block's normed input, ``q`` / ``k`` / ``v`` the rotated
        heads. The indexer reads ``h`` detached (only its alignment loss moves
        it): ``dsa_index_heads`` query heads and one key head (through a
        LayerNorm) of ``dsa_index_head_dim``, rotated like q, and a weight a
        head scaled by ``heads^-1/2 * dim^-1/2``. Its selection masks all
        heads; the kernel's log-sum-exp feeds the index loss, sown beside."""
        from photon_tpu.ops import dsa
        from photon_tpu.ops.masked_flash_attention import (
            base_tile, live_tables, masked_multihead_attention, plan_tiles, tile_counts)

        cfg = self.cfg
        b, s, _ = h.shape
        heads, dim = cfg.dsa_index_heads, cfg.dsa_index_head_dim
        with jax.named_scope(DSA_INDEXER_SCOPE):
            hd = jax.lax.stop_gradient(h)
            q_idx = dense(heads * dim, "idx_q_proj", cfg.emb_init_std)(hd)
            k_idx = FP32LayerNorm(use_bias=True, eps=cfg.norm_eps, name="idx_k_norm")(
                dense(dim, "idx_k_proj", cfg.emb_init_std)(hd))
            q_idx, k_idx = apply_rope(
                q_idx.reshape(b, s, heads, dim), k_idx[:, :, None, :], cfg.rope_theta)
            k_idx = k_idx[:, :, 0, :]
            w_idx = dense(heads, "idx_w_proj", cfg.emb_init_std)(hd).astype(
                jnp.float32) * (heads ** -0.5 * dim ** -0.5)
        with jax.named_scope(DSA_SELECT_SCOPE):
            mask = dsa.select_keys(q_idx, k_idx, w_idx, topk=cfg.dsa_topk,
                                   chunk=cfg.dsa_chunk, impl=cfg.attn_impl,
                                   interpret=cfg.attn_interpret)
            # the mask's one pass outside the kernel: the picked pairs by
            # tile give the count and all three launches' live tiles
            tiles = plan_tiles(s, s)
            counts = tile_counts(mask, *base_tile(tiles))
            live = live_tables(counts, tiles)
            sow(self, "dsa_picked_pairs", jnp.sum(counts).astype(jnp.float32))
            sow(self, "dsa_tiles_visited", jnp.sum(live[0], dtype=jnp.float32))
        out, lse = masked_multihead_attention(
            q, k, v, mask, impl=cfg.attn_impl, interpret=cfg.attn_interpret, live=live)
        with jax.named_scope(DSA_INDEX_LOSS_SCOPE):
            sow(self, "dsa_index_loss", dsa.index_loss(
                q_idx, k_idx, w_idx, q, k, lse, mask, chunk=cfg.dsa_chunk,
                impl=cfg.attn_impl, interpret=cfg.attn_interpret))
        return out

    def _dropless_moe(self, x: jax.Array, dense, hidden: int, resid_std: float,
                      norm: str = "ln_2"):
        """The dropless expert layer's residual branch (``ops/moe.py``):
        shared experts on every token plus this chip's part of the routed
        sum. The router reads the norm (``norm``: the block's second, or the
        one pre-norm of a layer that is this branch alone) in float32. Ungated
        experts (``moe_mlp_act: relu2``) have no ``moe_gate`` and no
        ``shared_gate_proj``."""
        from photon_tpu.ops import moe

        cfg = self.cfg
        compute = _dtype(cfg.compute_dtype)
        pd = _dtype(cfg.param_dtype)
        init = nn.initializers.normal(stddev=cfg.emb_init_std)
        with jax.named_scope(BLOCK_NORM_SCOPE):
            h32 = _norm(cfg, norm)(x.astype(jnp.float32))
        h = h32.astype(compute)
        held = cfg.experts_held
        router_w = self.param("router", init, (cfg.d_model, cfg.moe_num_experts), pd)
        # HF's e_score_correction_bias: selects only and takes no gradient;
        # the train step moves it by the balancing rule
        # (``moe.balanced_router_bias``, ``cfg.moe_bias_update_speed``) from
        # the rows sown below; seeded small and non-zero so that selection
        # and weights differ. The softmax top-k router has no bias: no parameter
        router_bias = None
        if cfg.moe_router == "sigmoid":
            router_bias = self.param(
                "router_bias", nn.initializers.normal(stddev=0.01),
                (cfg.moe_num_experts,), jnp.float32)
        w_gate = None
        if cfg.moe_gated:
            w_gate = self.param("moe_gate", init, (held, cfg.d_model, hidden), pd)
        w_up = self.param("moe_up", init, (held, cfg.d_model, hidden), pd)
        w_down = self.param(
            "moe_down", nn.initializers.normal(stddev=resid_std),
            (held, hidden, cfg.d_model), pd)
        out, counters = moe.dropless_moe_mlp(
            h32, router_w, router_bias, w_gate, w_up, w_down,
            top_k=cfg.moe_top_k, first_expert=cfg.moe_first_expert,
            routed_scale=cfg.moe_routed_scale, router=cfg.moe_router,
            gate_eps=cfg.moe_gate_eps,
            compute_dtype=compute, interpret=cfg.attn_interpret)
        for name, value in counters.items():
            sow(self, f"moe_{name}", value)
        if cfg.moe_shared_experts:
            with jax.named_scope(moe.SHARED_EXPERT_SCOPE):
                width = cfg.shared_expert_width
                if cfg.moe_gated:
                    gate = dense(width, "shared_gate_proj", cfg.emb_init_std)(h)
                    up = dense(width, "shared_up_proj", cfg.emb_init_std)(h)
                    act = nn.silu(gate) * up
                else:
                    act = jnp.square(nn.relu(dense(width, "shared_up_proj", cfg.emb_init_std)(h)))
                out = out + dense(cfg.d_model, "shared_down_proj", resid_std)(act)
        return out

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        compute = _dtype(cfg.compute_dtype)
        dense = lambda feats, name, init_std: nn.Dense(  # noqa: E731
            feats,
            use_bias=not cfg.no_bias,
            dtype=compute,
            param_dtype=_dtype(cfg.param_dtype),
            kernel_init=nn.initializers.normal(stddev=init_std),
            name=name,
        )

        def adapted(feats: int, name: str, init_std: float, h: jax.Array):
            """Targeted dense projection + optional LoRA delta (ISSUE 13):
            ``y + (h @ A) @ B · alpha/r`` when ``name`` is an adapted
            module. ``lora_rank == 0`` leaves the graph byte-identical to
            the pre-adapter build. A starts N(0, emb_init_std), B at zero,
            so a fresh adapter is exactly the identity; the flat param
            names (``blocks/block/{name}_lora_a``) are the wire/checkpoint
            vocabulary ``adapters/lora.py`` builds against."""
            y = dense(feats, name, init_std)(h)
            if cfg.lora_rank and name in cfg.lora_targets:
                pd = _dtype(cfg.param_dtype)
                a = self.param(
                    f"{name}_lora_a",
                    nn.initializers.normal(stddev=cfg.emb_init_std),
                    (h.shape[-1], cfg.lora_rank), pd,
                )
                bm = self.param(
                    f"{name}_lora_b", nn.initializers.zeros,
                    (cfg.lora_rank, feats), pd,
                )
                scale = cfg.lora_alpha / cfg.lora_rank
                y = y + ((h @ a.astype(h.dtype)) @ bm.astype(h.dtype)) * scale
            return y

        resid_std = cfg.emb_init_std / (2.0 * cfg.n_layers) ** 0.5
        if cfg.single_branch_layers:
            # one residual branch a layer, not two (HF ``nemotron_h``'s
            # ``rescale_prenorm_residual``: ``/ sqrt(n_layers)``)
            resid_std = cfg.emb_init_std / cfg.n_layers ** 0.5
            if self.mixer == "moe":  # the expert layer is the whole layer
                hidden = cfg.mlp_hidden_size or cfg.expansion_ratio * cfg.d_model
                return _residual(cfg, x, self._dropless_moe(x, dense, hidden, resid_std, "ln_1"))

        # --- the mixer: attention, or another kind in its place ---
        # (hyper-connected, ``x`` comes in as the streams, each sublayer reads
        # one input out of them and writes its branch back into all of them)
        x, hc = _hc_read_in(self, x, "hc_1")
        with jax.named_scope(BLOCK_NORM_SCOPE):
            h = _norm(cfg, "ln_1")(x)
        if self.mixer == "mamba":
            x = _residual(cfg, x, self._mamba_mixer(h, dense, resid_std))
        elif self.mixer == "conv":
            x = _residual(cfg, x, self._short_conv_mixer(h, dense, resid_std))
        else:
            n_kv = cfg.n_kv_heads or cfg.n_heads
            # this layer's kind: the model's one, or where ``layer_types`` has
            # full and sliding layers each kind's heads, window and rotation
            kind = cfg.attention_kind(self.mixer)
            n_heads = kind.n_heads
            b, s, _ = h.shape
            if cfg.latent_attention:
                with jax.named_scope(MLA_PROJ_SCOPE):
                    q, k, v = self._latent_qkv(h, dense)  # [B, S, H, d_head] each
            else:
                with jax.named_scope(ATTN_PROJ_SCOPE):
                    if n_kv == n_heads:
                        qkv = adapted(3 * cfg.d_model, "wqkv", cfg.emb_init_std, h)
                        q, k, v = jnp.split(qkv, 3, axis=-1)
                    else:
                        # GQA: separate projections — a fused q||k||v matrix would
                        # put shard boundaries at positions that don't align with
                        # the tensor axis and force per-layer resharding; three
                        # column-parallel matmuls stay shard-local
                        q = adapted(n_heads * cfg.d_head, "q_proj", cfg.emb_init_std, h)
                        k = adapted(n_kv * cfg.d_head, "k_proj", cfg.emb_init_std, h)
                        v = adapted(n_kv * cfg.d_head, "v_proj", cfg.emb_init_std, h)
                    q = q.reshape(b, s, n_heads, cfg.d_head)
                    k = k.reshape(b, s, n_kv, cfg.d_head)
                    v = v.reshape(b, s, n_kv, cfg.d_head)
            if cfg.qk_norm:
                with jax.named_scope(ATTN_QK_NORM_SCOPE):
                    q = FP32RMSNorm(eps=cfg.norm_eps, name="q_norm")(q)
                    k = FP32RMSNorm(eps=cfg.norm_eps, name="k_norm")(k)
            if cfg.rope and not cfg.latent_attention:
                # before the kv repeat: the rotation is per-head-identical, so
                # rotating n_kv heads then replicating equals the reverse order
                with jax.named_scope(ATTN_PROJ_SCOPE):
                    q, k = apply_rope(q, k, kind.rope_theta, kind.inv_freq,
                                      rotary_dim=kind.rotary_dim, factor=kind.rope_factor)
            # k/v go to the dispatch at their native n_kv width: the pallas
            # flash kernel consumes GQA groups directly (index-mapped kv rows,
            # no repeated tensor in HBM); the xla/ring paths replicate inside
            # ops/attention.py
            if cfg.sparse_attention:
                attn_out = self._sparse_attention(h, q, k, v, dense)
            else:
                attn_out = multihead_attention(
                    q, k, v,
                    impl=cfg.attn_impl, causal=True, alibi=cfg.alibi,
                    interpret=cfg.attn_interpret,
                    # None: the dispatch's own 1/sqrt(d_head)
                    scale=cfg.softmax_scale,
                    window=kind.window,
                )
            if cfg.attn_gate:
                # one gate a head and token, from the block's normed input
                with jax.named_scope(ATTN_GATE_SCOPE):
                    attn_out = head_gate(
                        attn_out.reshape(b, s, -1),  # as the kernel wrote it
                        dense(n_heads, "attn_gate", cfg.emb_init_std)(h),
                        impl=cfg.attn_impl, interpret=cfg.attn_interpret,
                    ).reshape(attn_out.shape)
            if cfg.latent_attention:
                with jax.named_scope(MLA_PROJ_SCOPE):
                    branch = dense(cfg.d_model, "out_proj", resid_std)(
                        attn_out.reshape(b, s, cfg.n_heads * cfg.v_head_dim))
                    if hc is None:
                        x = _residual(cfg, x, branch)
            else:
                with jax.named_scope(ATTN_PROJ_SCOPE):
                    attn_out = attn_out.reshape(b, s, n_heads * cfg.d_head)
                    branch = adapted(cfg.d_model, "out_proj", resid_std, attn_out)
                    if hc is None:
                        x = _residual(cfg, x, branch)
            if hc is not None:
                x = _write_back(cfg, x, branch, hc)

        if cfg.single_branch_layers:  # the mixer was the layer's one branch
            return x

        # --- MLP ---
        x, hc = _hc_read_in(self, x, "hc_2")
        hidden = cfg.mlp_hidden_size or cfg.expansion_ratio * cfg.d_model
        if self.dense_mlp:
            hidden = cfg.dense_mlp_hidden_size
        elif cfg.dropless_moe:
            return _write_back(cfg, x, self._dropless_moe(x, dense, hidden, resid_std), hc)
        with jax.named_scope(BLOCK_NORM_SCOPE):
            h = _norm(cfg, "ln_2")(x)
        if cfg.mlp == "moe" and not self.dense_mlp:
            # expert-parallel MLP (ops/moe.py): router + E expert FFNs,
            # GShard dense dispatch. Expert weights carry a leading [E]
            # axis sharded over the `expert` mesh axis
            # (parallel/sharding.py); the Switch aux loss is sown and
            # collected by make_loss_fn when `intermediates` is mutable
            # (inference apply() leaves it immutable -> sow is a no-op).
            from photon_tpu.ops.moe import moe_mlp

            pd = _dtype(cfg.param_dtype)
            init = nn.initializers.normal(stddev=cfg.emb_init_std)
            router_w = self.param(
                "router", init, (cfg.d_model, cfg.moe_num_experts), pd)
            w_up = self.param(
                "moe_up", init, (cfg.moe_num_experts, cfg.d_model, hidden), pd)
            w_down = self.param(
                "moe_down",
                nn.initializers.normal(stddev=resid_std),
                (cfg.moe_num_experts, hidden, cfg.d_model), pd)
            w_gate = None
            if cfg.moe_mlp_act == "swiglu":  # Mixtral-style gated experts
                w_gate = self.param(
                    "moe_gate", init,
                    (cfg.moe_num_experts, cfg.d_model, hidden), pd)
            moe_out, aux = moe_mlp(
                h.astype(compute), router_w, w_up, w_down, w_gate=w_gate,
                top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
            )
            sow(self, "moe_aux", aux)
            # pin the combine output back to the residual-stream layout
            # (batch over data+fsdp+expert; d_model REPLICATED over tensor —
            # the residual add and the next ln_1 consume the full feature
            # dim): without the hint GSPMD brings it back expert-major and
            # pays an "involuntary full rematerialization" reshard at the
            # residual add (spmd_partitioner warning on the virtual mesh)
            from jax.sharding import PartitionSpec as P

            moe_out = _constrain_activation(
                moe_out, P(("data", "fsdp", "expert"), "sequence", None)
            )
            return _write_back(cfg, x, moe_out, hc)
        # the non-expert MLP under one scope: the products, the activation
        # between them (no module's name is on it) and the residual add
        with jax.named_scope(BLOCK_MLP_SCOPE):
            if cfg.mlp == "swiglu" or self.dense_mlp:
                # separate gate/up projections (standard llama layout): each is
                # column-parallel under the same sharding rule, so silu(gate)*up
                # is shard-local — a fused gate||up matrix would put ALL of gate
                # on the first half of the tensor group and force a per-layer
                # resharding collective
                gate = adapted(hidden, "gate_proj", cfg.emb_init_std, h)
                up = adapted(hidden, "up_proj", cfg.emb_init_std, h)
                h = nn.silu(gate) * up
            else:
                h = adapted(hidden, "up_proj", cfg.emb_init_std, h)
                h = nn.gelu(h, approximate=True)
            branch = adapted(cfg.d_model, "down_proj", resid_std, h)
            if hc is None:
                return _residual(cfg, x, branch)
        return _write_back(cfg, x, branch, hc)


class _ScanBlock(nn.Module):
    """Adapter giving :class:`MPTBlock` the ``(carry, _) -> (carry, None)``
    signature ``nn.scan`` expects."""

    cfg: ModelConfig
    dense_mlp: bool = False
    mixer: str = "attention"

    @nn.compact
    def __call__(self, carry, _: None):
        return MPTBlock(self.cfg, self.dense_mlp, self.mixer, name="block")(carry), None


class MPTModel(nn.Module):
    """Decoder-only LM: tokens ``[B, S] int32`` → logits ``[B, S, vocab]``.

    ``return_hidden=True`` stops after the final LayerNorm and returns
    ``[B, S, d_model]`` hidden states instead — the training loss computes
    logits chunkwise from these (``train_step.make_loss_fn``) so the full
    fp32 ``[B, S, vocab]`` tensor is never materialized in HBM.
    """

    cfg: ModelConfig

    @nn.compact
    def __call__(self, tokens: jax.Array, return_hidden: bool = False) -> jax.Array:
        cfg = self.cfg
        compute = _dtype(cfg.compute_dtype)

        wte = nn.Embed(
            cfg.vocab_size,
            cfg.d_model,
            embedding_init=nn.initializers.normal(stddev=cfg.emb_init_std),
            param_dtype=_dtype(cfg.param_dtype),
            dtype=compute,
            name="wte",
        )
        x = wte(tokens)
        if cfg.embedding_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        # with ALiBi/RoPE the position signal lives in attention; no wpe
        if cfg.learned_pos_emb and not cfg.alibi and not cfg.rope:
            wpe = self.param(
                "wpe",
                nn.initializers.normal(stddev=cfg.emb_init_std),
                (cfg.max_seq_len, cfg.d_model),
                _dtype(cfg.param_dtype),
            )
            x = x + wpe[None, : tokens.shape[1], :].astype(compute)

        block_cls = _ScanBlock
        if cfg.remat:
            block_cls = nn.remat(
                _ScanBlock,
                policy=jax.checkpoint_policies.nothing_saveable,
                prevent_cse=False,
            )
        # stack layers: params get a leading [n_layers] axis; single trace
        def stack(length: int, name: str, dense_mlp: bool = False,
                  mixer: str = "attention"):
            return nn.scan(
                block_cls,
                # intermediates: per-layer MoE aux losses and counters stack
                # to [length] (empty when nothing is sown / the collection
                # is immutable)
                variable_axes={"params": 0, "intermediates": 0},
                split_rngs={"params": True},
                length=length,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )(cfg, dense_mlp, mixer, name=name)

        # leading dense blocks, and with ``layer_types`` every run of equal
        # mixer and MLP kind, under a scan of their own, in order
        if cfg.hyper_connected:  # the embedding enters every stream
            x = (x,) * cfg.hc_mult
        for name, mixer, dense_mlp, length in cfg.stacks:
            x, _ = stack(length, name, dense_mlp, mixer)(x, None)
        if cfg.hyper_connected:  # and their sum leaves
            with jax.named_scope(MHC_READ_IN_SCOPE):
                x = sum(xi.astype(jnp.float32) for xi in x).astype(compute)

        with jax.named_scope(BLOCK_NORM_SCOPE):
            x = _norm(cfg, "ln_f")(x)
        if return_hidden:
            return x
        if cfg.tie_embeddings:
            logits = wte.attend(x.astype(compute))
        else:
            logits = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=compute,
                param_dtype=_dtype(cfg.param_dtype),
                kernel_init=nn.initializers.normal(stddev=cfg.emb_init_std),
                name="lm_head",
            )(x)
        logits = _constrain_logits(logits)
        if cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
        return logits.astype(_dtype(cfg.logits_dtype))


def init_params(cfg: ModelConfig, seed: int = 0, batch: int = 1) -> Any:
    """Build the parameter pytree on host (reference analog:
    ``get_raw_model_parameters`` builds a CPU model to learn shapes,
    ``photon/clients/utils.py:739-868``)."""
    model = MPTModel(cfg)
    tokens = jnp.zeros((batch, min(cfg.max_seq_len, 8)), jnp.int32)
    params = model.init(jax.random.PRNGKey(seed), tokens)
    return params["params"]
