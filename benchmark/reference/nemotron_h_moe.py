"""Plain Nemotron-H with experts (``nemotron_h``; Nemotron-3-Nano-30B-A3B), one
chip's share: forward pass, loss, gradients and the recipe's optimizer step.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``. No kernel, no cache, no sort, no chunk: the state-space
recurrence walks the positions one by one, each head against its own group's
``B`` and ``C``. It imports nothing of the program: the walk over positions, the causal
convolution, the moments on the host and their ADOPT step are
``reference/granite_hybrid.py``'s, the router, the count of assignments and the
selection bias's balancing step ``reference/glm_moe_lite.py``'s, how a
balancing step rides in its ``router_bias`` leaf's place
``reference/laguna_swa_moe.py``'s, the stated-precision products and the leaf
comparison ``reference/mpt.py``'s. The layer equations (HF ``nemotron_h``;
``h = RMSNorm(x)``: float32, eps 1e-5, scale only; no bias but the
convolution's):

- Every layer is ONE branch: ``x = x + branch(RMSNorm(x))``, the branch by the
  layer's letter in ``hybrid_override_pattern`` (here its entry in
  ``layer_types``: ``mamba``, ``attention``, ``moe``). After the last layer one
  norm (``ln_f``) and an untied head over the vocabulary slice.
- ``mamba``, Mamba-2 with ``G`` = 8 groups: ``[z | xBC | dt] = h W_in``, widths
  4,096 | 4,096 + 2 x 8 x 128 | 64; ``xBC = silu(conv(xBC))``, causal,
  depthwise, 4 taps with bias; ``[x | B | C] = xBC``, ``x`` as 64 heads of 64,
  ``B`` and ``C`` as ``[S, 8, 128]``; head ``h`` reads group ``h // 8``. ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head and position ``H_t =
  exp(dt_t A) H_(t-1) + dt_t x_t B_(g,t)^T`` (64 x 128, zero before the row's
  first position), ``y_t = H_t C_(g,t) + D x_t``. The gated norm
  (``norm_before_gate`` false, group size 4,096 / 8 = 512): ``u = y *
  silu(z)``; within each group of 512 channels ``u / sqrt(mean(u^2) + eps)``;
  times a weight ``[4,096]``. Out ``u W_out``.
- ``attention``: 32 query heads over 2 key-value heads of 128, query head ``i``
  reading key-value head ``i // 16``; no rotation and no position embedding;
  causal ``softmax(q k^T / sqrt(128)) v``; then ``W_o``.
- ``moe``: scores ``sigmoid(h W_r)`` over all 128 experts; the 6 largest of
  ``scores + b`` (``b`` selects only and takes no gradient); weights the picked
  SCORES over their sum ``+ 1e-20``, times 2.5. An expert is ``W_down
  relu(W_up h)^2``, two matrices, 1,856 wide; one shared expert of the same
  form, 3,712 wide, sees every token. ``out = sum_i w_i e_i(h) + shared(h)``
  over the experts HELD here (0-7): what the 120 absent experts would add is
  left out, here as in the program.

Departures from the published modelling code, each because the program does
the same and the two must compute one function (``assumed`` in the
configuration file): ``time_step_limit`` is (0, inf), so ``dt`` is not
clamped; after every optimizer step each expert layer's ``b`` moves against
that layer's loads (``glm_moe_lite.bias_step``: the config names no rule);
every run of layers equal in kind is a ``lax.scan`` over stacked weights
(``blocks_0``, ``blocks_1``, ...: nine runs of one layer here). For memory
alone: attention runs one key-value head's group at a time in blocks of
queries; the loss makes the logits of 2,048 positions at a time; for gradients
each layer, group, block of queries, expert and block of logits is under
``jax.checkpoint`` and the walk over positions is cut into segments whose
start states are kept; the gradient and the optimizer's two moments live in
the host's memory (``granite_hybrid.HostTree``), because the comparison keeps
three sets of 667 M float32 weights on the device at once.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import glm_moe_lite as _glm
from benchmark.reference import granite_hybrid as _host
from benchmark.reference import laguna_swa_moe as _stacks
from benchmark.reference import mpt as _mpt

INIT_STD = 0.02
BIAS_STD = 0.01
#: queries scored at once, a key-value head's group (memory only)
QUERY_BLOCK = 1024
#: positions whose logits are made at once for the loss (memory only)
LOSS_BLOCK = 2048
KINDS = ("mamba", "attention", "moe")

# what every family's reference offers; ``bf16_state`` is granite's control of
# the recurrence (every product exact, the carried state rounded to bfloat16)
MATMULS = _host.MATMULS
seed_key = _mpt.seed_key
worst_leaf_gap = _mpt.worst_leaf_gap
adopt_init = _host.adopt_init
leaf_norms = _host.leaf_norms
HostTree = _host.HostTree
bias_step = _glm.bias_step
# the balancing steps ride in their ``router_bias`` leaves' places
clip_by_global_norm = _stacks.clip_by_global_norm
adopt_step = _stacks.adopt_step

_rms_norm = _glm._rms_norm
_sum_over = _stacks._sum_over


def dims_of(model: dict) -> dict:
    """The sizes this family needs, from a configuration file's ``model``."""
    if not model.get("single_branch_layers"):
        raise ValueError("this family's layers are one branch each (single_branch_layers)")
    if model.get("rope") or model.get("learned_pos_emb") or model.get("alibi"):
        raise ValueError("this family's attention has no positions")
    if model.get("moe_mlp_act") != "relu2":
        raise ValueError("this family's experts are ungated relu^2 (moe_mlp_act)")
    return {
        "d_model": int(model["d_model"]),
        "n_layers": int(model["n_layers"]),
        "layer_types": str(model["layer_types"]),
        "n_heads": int(model["n_heads"]),
        "n_kv_heads": int(model["n_kv_heads"]),
        "d_head": int(model["head_dim"]),
        "mamba_heads": int(model["mamba_n_heads"]),
        "mamba_groups": int(model["mamba_n_groups"]),
        "mamba_d_head": int(model["mamba_d_head"]),
        "mamba_d_state": int(model["mamba_d_state"]),
        "mamba_d_conv": int(model["mamba_d_conv"]),
        "norm_eps": float(model["norm_eps"]),
        "max_seq_len": int(model["max_seq_len"]),
        "vocab_size": int(model["vocab_size"]),
        "expert_hidden": int(model["mlp_hidden_size"]),
        "shared_hidden": int(model["moe_shared_hidden_size"]),
        "n_experts": int(model["moe_num_experts"]),
        "top_k": int(model["moe_top_k"]),
        "experts_held": int(model["moe_experts_held"]) or int(model["moe_num_experts"]),
        "first_expert": int(model["moe_first_expert"]),
        "routed_scale": float(model["moe_routed_scale"]),
        "bias_speed": float(model.get("moe_bias_update_speed", 0.0)),
    }


def layer_runs(dims: dict) -> list[tuple[str, str, int]]:
    """``(stack, kind, layers)`` of every run of layers equal in kind, in
    order; run ``i`` is the stack ``blocks_i``."""
    runs: list[tuple[str, int]] = []
    for kind in (k.strip() for k in dims["layer_types"].split(",")):
        if kind not in KINDS:
            raise ValueError(f"layer kind {kind!r} is none of {KINDS}")
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    if sum(n for _, n in runs) != dims["n_layers"]:
        raise ValueError("layer_types does not name n_layers layers")
    return [(f"blocks_{i}", *run) for i, run in enumerate(runs)]


def make_params(dims: dict, seed, dtype=jnp.float32) -> dict:
    """Seeded weights in the program's tree layout: normal, std 0.02; the
    residual projections (``out_proj``, ``moe_down``, ``shared_down_proj``)
    scaled by ``1/sqrt(L)``, one branch a layer (HF's
    ``rescale_prenorm_residual``); norm scales 1; the selection bias normal,
    std 0.01, so that it changes who is selected; the Mamba-2 leaves as the
    public Mamba-2 code starts them (``granite_hybrid.make_params``). ``seed``
    is a whole number or a key from :func:`seed_key`."""
    d, L, v = dims["d_model"], dims["n_layers"], dims["vocab_size"]
    h, hkv, dh = dims["n_heads"], dims["n_kv_heads"], dims["d_head"]
    mh, taps = dims["mamba_heads"], dims["mamba_d_conv"]
    inner, bc = mh * dims["mamba_d_head"], dims["mamba_groups"] * dims["mamba_d_state"]
    fe, fs, e, eh = (dims["expert_hidden"], dims["shared_hidden"], dims["n_experts"],
                     dims["experts_held"])
    resid = INIT_STD / math.sqrt(L)
    key = seed_key(seed) if isinstance(seed, (int, np.integer)) else seed
    keys = iter(jax.random.split(key, 8 * len(layer_runs(dims)) + 2))

    def normal(shape, std=INIT_STD):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def mamba(n):
        step = jnp.exp(uniform((n, mh), math.log(1e-3), math.log(1e-1)))
        bound = taps ** -0.5
        return {"in_proj": {"kernel": normal((n, d, 2 * inner + 2 * bc + mh))},
                "conv_kernel": uniform((n, taps, inner + 2 * bc), -bound, bound).astype(dtype),
                "conv_bias": uniform((n, inner + 2 * bc), -bound, bound).astype(dtype),
                "A_log": jnp.log(uniform((n, mh), 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "D": jnp.ones((n, mh), jnp.float32),
                "mamba_norm": {"scale": jnp.ones((n, inner), dtype)},
                "out_proj": {"kernel": normal((n, inner, d), resid)}}

    def attention(n):
        return {"q_proj": {"kernel": normal((n, d, h * dh))},
                "k_proj": {"kernel": normal((n, d, hkv * dh))},
                "v_proj": {"kernel": normal((n, d, hkv * dh))},
                "out_proj": {"kernel": normal((n, h * dh, d), resid)}}

    def moe(n):
        return {"router": normal((n, d, e)),
                "router_bias": normal((n, e), BIAS_STD).astype(jnp.float32),
                "moe_up": normal((n, eh, d, fe)),
                "moe_down": normal((n, eh, fe, d), resid),
                "shared_up_proj": {"kernel": normal((n, d, fs))},
                "shared_down_proj": {"kernel": normal((n, fs, d), resid)}}

    make = {"mamba": mamba, "attention": attention, "moe": moe}
    params = {"wte": {"embedding": normal((v, d))},
              "lm_head": {"kernel": normal((d, v))},
              "ln_f": {"scale": jnp.ones((d,), dtype)}}
    for name, kind, n in layer_runs(dims):
        params[name] = {"block": {"ln_1": {"scale": jnp.ones((n, d), dtype)}, **make[kind](n)}}
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def grouped_rms_norm(u, scale, groups: int, eps: float):
    """``u / sqrt(mean(u^2) + eps)`` within each of ``groups`` equal runs of
    the last axis' channels, times ``scale`` (a weight a channel)."""
    by_group = u.reshape(*u.shape[:-1], groups, -1)
    normed = by_group * jax.lax.rsqrt(
        jnp.mean(jnp.square(by_group), axis=-1, keepdims=True) + eps)
    return normed.reshape(u.shape) * scale


def grouped_recurrence(x, dt, a, b, c, d_skip, remat: bool = False, state_dtype=jnp.float32):
    """The state-space recurrence with groups: ``x [B, S, H, P]``, ``dt [B, S,
    H]``, ``a [H]`` (negative), ``b``, ``c`` ``[B, S, G, N]``, ``d_skip [H]``
    -> ``y [B, S, H, P]``. Group ``g``'s ``H / G`` heads walk the positions
    with ITS ``b`` and ``c``: ``granite_hybrid.recurrence`` a group, the
    groups side by side in one walk."""
    bsz, s, h, p = x.shape
    g = b.shape[2]
    walk = functools.partial(_host.recurrence, remat=remat, state_dtype=state_dtype)
    y = jax.vmap(walk, in_axes=(2, 2, 0, 2, 2, 0), out_axes=2)(
        x.reshape(bsz, s, g, h // g, p), dt.reshape(bsz, s, g, h // g), a.reshape(g, -1),
        b, c, d_skip.reshape(g, -1))
    return y.reshape(x.shape)


def mamba_mixer(h, p, dims, mm, remat: bool = False, state_dtype=jnp.float32):
    """The ``mamba`` branch on ``h``, already normed, with weights ``p``."""
    bsz, s, _ = h.shape
    heads, g, n = dims["mamba_heads"], dims["mamba_groups"], dims["mamba_d_state"]
    inner = heads * dims["mamba_d_head"]
    zxbcdt = mm(h, p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * n], axis=-1)
    xbc = jax.nn.silu(_host.causal_conv(xbc, p["conv_kernel"], p["conv_bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + g * n], axis=-1)
    y = grouped_recurrence(
        x.reshape(bsz, s, heads, -1), jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        b.reshape(bsz, s, g, n), c.reshape(bsz, s, g, n), p["D"], remat, state_dtype)
    u = grouped_rms_norm(y.reshape(bsz, s, inner) * jax.nn.silu(z), p["mamba_norm"]["scale"],
                         g, dims["norm_eps"])
    return mm(u, p["out_proj"]["kernel"])


def attention(h, p, dims, mm):
    """The ``attention`` branch on ``h``, already normed: no positions, one
    key-value head's group of query heads at a time, each with its own columns
    of ``W_q``, ``W_k``, ``W_v`` and its own rows of ``W_o`` (``concat(o)
    W_o`` is the sum of the groups' parts), in blocks of queries, each block
    against the keys its queries can see."""
    b, s, d = h.shape
    heads, kv, dh = dims["n_heads"], dims["n_kv_heads"], dims["d_head"]
    group = heads // kv
    block = min(QUERY_BLOCK, s)

    def one_block(qb, kb, vb, lo: int):
        """Queries ``lo ..`` of one group ``qb [group, B, n, D]`` against the
        keys up to the block's last (``kb``, ``vb [B, m, D]``)."""
        scores = mm(qb, kb[None].transpose(0, 1, 3, 2)) / math.sqrt(dh)
        i = lo + jnp.arange(qb.shape[2])[:, None]
        j = jnp.arange(kb.shape[1])[None, :]
        return mm(jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1), vb[None])

    def one_group(w):
        wq, wk, wv, wo = w  # [D, group D_h], [D, D_h], [D, D_h], [group D_h, D]
        q = mm(h, wq).reshape(b, s, group, dh).transpose(2, 0, 1, 3)
        k, v = mm(h, wk), mm(h, wv)
        out = []
        for lo in range(0, s, block):
            hi = min(lo + block, s)
            # (one block's scores at a time are kept for the gradient)
            out.append(jax.checkpoint(one_block, static_argnums=(3,))(
                q[:, :, lo:hi], k[:, :hi], v[:, :hi], lo))
        o = jnp.concatenate(out, axis=2).transpose(1, 2, 0, 3)  # [B, S, group, D_h]
        return mm(o.reshape(b, s, group * dh), wo)

    # head ``i`` is member ``i % group`` of key-value head ``i // group``
    by_group = (
        p["q_proj"]["kernel"].reshape(d, kv, group * dh).transpose(1, 0, 2),
        p["k_proj"]["kernel"].reshape(d, kv, dh).transpose(1, 0, 2),
        p["v_proj"]["kernel"].reshape(d, kv, dh).transpose(1, 0, 2),
        p["out_proj"]["kernel"].reshape(kv, group * dh, d),
    )
    return _sum_over(jax.checkpoint(one_group), by_group, h)


def relu2_expert(u, w_up, w_down, mm):
    """``W_down relu(W_up u)^2``: the ungated expert, two matrices."""
    return mm(jnp.square(jax.nn.relu(mm(u, w_up))), w_down)


def routed_experts(u, p, dims, mm, idx, gates):
    """This chip's part of the routed sum: the experts held here one after
    another, each applied to every token and weighted by its gate where it was
    picked and by zero elsewhere (the weighting inside what is recomputed for
    the gradient, so that no expert's output over every token is kept)."""
    def weighted(w):
        e, wu, wd = w
        weight = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        return weight[..., None] * relu2_expert(u, wu, wd, mm)

    held = dims["first_expert"] + jnp.arange(dims["experts_held"])
    return _sum_over(jax.checkpoint(weighted), (held, p["moe_up"], p["moe_down"]), u)


def expert_layer(u, p, dims, mm):
    """``(branch(u), rows [E])`` of a ``moe`` layer: the shared expert on
    every token plus the held experts' part of the routed sum, and the
    assignments by routed expert."""
    idx, gates = _glm.route(u, p["router"], p["router_bias"], dims, mm)
    out = routed_experts(u, p, dims, mm, idx, gates)
    out = out + relu2_expert(u, p["shared_up_proj"]["kernel"], p["shared_down_proj"]["kernel"],
                             mm)
    return out, _glm.expert_rows(idx, dims["n_experts"])


def block(x, p, kind: str, dims, mm, remat: bool = False, state_dtype=jnp.float32):
    """``(x, rows)``: one layer's output, and its assignments by routed expert
    (``None`` from a layer that routes nothing)."""
    h = _rms_norm(x, p["ln_1"]["scale"], dims["norm_eps"])
    if kind == "moe":
        out, rows = expert_layer(h, p, dims, mm)
        return x + out, rows
    if kind == "mamba":
        return x + mamba_mixer(h, p, dims, mm, remat, state_dtype), None
    return x + attention(h, p, dims, mm), None


def hidden_and_rows(params: dict, tokens: jax.Array, dims: dict,
                    matmul: str = "float32", remat: bool = False):
    """``tokens [B, S] int32`` -> ``(ln_f's output [B, S, D] float32, rows)``,
    ``rows[stack] [layers, E]`` the assignments to every routed expert by
    expert stack."""
    mm = MATMULS[matmul]
    state_dtype = jnp.bfloat16 if matmul == "bf16_state" else jnp.float32
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p32["wte"]["embedding"][tokens]
    rows = {}
    for name, kind, _ in layer_runs(dims):
        def body(x, layer, kind=kind):
            return block(x, layer, kind, dims, mm, remat, state_dtype)

        if remat:
            body = jax.checkpoint(body)
        x, by_layer = jax.lax.scan(body, x, p32[name]["block"])
        if kind == "moe":
            rows[name] = by_layer
    return _rms_norm(x, p32["ln_f"]["scale"], dims["norm_eps"]), rows


def forward_and_rows(params: dict, tokens: jax.Array, dims: dict,
                     matmul: str = "float32", remat: bool = False):
    """``tokens [B, S] int32`` -> ``(logits [B, S, vocab] float32, rows)``."""
    hidden, rows = hidden_and_rows(params, tokens, dims, matmul, remat)
    return MATMULS[matmul](hidden, params["lm_head"]["kernel"].astype(jnp.float32)), rows


def forward(params: dict, tokens: jax.Array, dims: dict,
            matmul: str = "float32", remat: bool = False) -> jax.Array:
    """``tokens [B, S] int32`` -> ``logits [B, S, vocab] float32``."""
    return forward_and_rows(params, tokens, dims, matmul, remat)[0]


def ce_sum_and_rows(params: dict, tokens: jax.Array, dims: dict,
                    matmul: str = "float32", remat: bool = False):
    """Summed next-token cross entropy over ``tokens [B, S]``, and the
    assignments by expert stack, layer and routed expert. The logits of
    ``LOSS_BLOCK`` positions at a time."""
    mm = MATMULS[matmul]
    hidden, rows = hidden_and_rows(params, tokens, dims, matmul, remat)
    head = params["lm_head"]["kernel"].astype(jnp.float32)
    b, s, d = hidden.shape
    size = LOSS_BLOCK if s % LOSS_BLOCK == 0 else s
    target = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    counted = (jnp.arange(s) < s - 1).astype(jnp.float32)  # the last position predicts nothing

    def one_block(xtc):
        x, t, c = xtc  # [B, size, D], [B, size], [size]
        logp = jax.nn.log_softmax(mm(x, head), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, t[..., None], axis=-1)[..., 0] * c)

    blocks = (hidden.reshape(b, s // size, size, d).transpose(1, 0, 2, 3),
              target.reshape(b, s // size, size).transpose(1, 0, 2),
              counted.reshape(s // size, size))
    return jnp.sum(jax.lax.map(jax.checkpoint(one_block), blocks)), rows


def ce_sum(params: dict, tokens: jax.Array, dims: dict,
           matmul: str = "float32", remat: bool = False) -> jax.Array:
    return ce_sum_and_rows(params, tokens, dims, matmul, remat)[0]


class Grad(_stacks.Grad):
    """Mean loss and its gradient over a batch as a :class:`HostTree`, each
    expert stack's balancing step where its ``b``'s zero gradient would be
    (``laguna_swa_moe.Grad``, over this family's loss)."""

    def __init__(self, dims: dict, matmul: str = "float32", rows: int = 1) -> None:
        super().__init__(dims, matmul, rows)
        self._fn = jax.jit(jax.value_and_grad(
            lambda p, t: ce_sum_and_rows(p, t, dims, matmul, remat=True), has_aux=True))
