"""Plain Laguna-XS.2 (``laguna``), one chip's share: forward pass, loss,
gradients and the recipe's optimizer step.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``. No kernel, no cache, no sort, no band: a sliding layer
is the same scores under a second mask. It imports nothing of the program:
the expert layer's router, shared expert and the selection bias's balancing
step are ``reference/glm_moe_lite.py``'s (the experts held here are a masked
loop of its own), the moments on the host and their ADOPT step
``reference/granite_hybrid.py``'s, the stated-precision products and the leaf
comparison ``reference/mpt.py``'s. The layer equations (``norm``: RMSNorm,
float32, eps 1e-6, scale only; no bias anywhere; heads of 128, 8 key-value
heads in every layer):

- Every block: ``x = x + attention(norm(x))``, ``x = x + mlp(norm(x))``
  (``ln_1``, ``ln_2``). After the last block one norm (``ln_f``) and an untied
  head over the vocabulary slice.
- Attention on ``h = norm(x)``, ``H`` = 48 query heads in a ``full_attention``
  layer and 64 in a ``sliding_attention`` one: ``q = h W_q`` as ``[S, H, 128]``,
  ``k = h W_k``, ``v = h W_v`` as ``[S, 8, 128]``; query head ``i`` reads
  key-value head ``i // (H / 8)``.
- Positions, by explicit cos / sin tables per layer kind (:func:`rope_tables`).
  A sliding layer: plain RoPE, theta 1e4, over all 128 dims (``f_i =
  theta^(-2i/128)``, ``i < 64``; dim ``i`` turns with ``i + 64``). A full
  layer: ``partial_rotary_factor`` 0.5, so dims 0-63 turn (dim ``i`` with ``i
  + 32``) and dims 64-127 pass; the 32 frequencies are YaRN's over ``dim =
  64``: ``f_i = theta^(-2i/64)`` (theta 5e5), ``g_i = f_i / 64``, ``m_i = 1 -
  clip((i - low) / (high - low), 0, 1)`` with ``low = floor(c(64))``, ``high =
  ceil(c(1))``, ``c(b) = 64 ln(4096 / (2 pi b)) / (2 ln theta)``,
  ``inv_freq_i = g_i (1 - m_i) + f_i m_i``; cos and sin are multiplied by
  ``attention_factor`` 1.4158883 (= 0.1 ln 64 + 1), so the turned half of a
  score carries its square and the passed half 1. The softmax scale is
  ``1/sqrt(128)`` in both kinds.
- Scores ``s_ij = q_i . k_j / sqrt(128)``; ``j`` is visible to ``i`` iff ``j <=
  i``, and in a sliding layer also ``j > i - 512`` (512 keys, the query's own
  among them). Softmax in float32, ``o_i = sum_j p_ij v_j``.
- The gate: ``g = sigmoid(h W_g)``, ``W_g [2,048, H]``, one gate a head and
  token; ``o_i <- g_i o_i``; then ``concat(o) W_o``.
- Layer 0's MLP: ``(silu(u W_g) * (u W_u)) W_d``, 8,192 wide.
- Every other layer's (``glm_moe_lite``'s): ``s = sigmoid(u W_r)`` over all 256
  experts; top 8 of ``s + b`` (``b`` selects only and takes no gradient); gates
  ``2.5 s_sel / (sum s_sel + 1e-20)``, on the experts' output; the shared expert
  on every token plus the held experts' part of the routed sum. No token is
  dropped. What the 224 absent experts would add is left out, here as in the
  program, and the partial result goes on.

Departures from the published description, each because the program does the
same and the two must compute one function (``assumed`` in the configuration
file): the gate is headwise (the config says ``gating: true`` and no shape;
one gate a head is what the published 33.4 B total adds up to); the router
scores with a sigmoid and renormalises the picked scores (the config gives a
routed scaling factor and no scoring function); after every optimizer step
each expert stack's ``b`` moves against that stack's loads
(``glm_moe_lite.bias_step``: the config gives neither the bias nor a rule);
RoPE pairs dim ``i`` with ``i + half`` (rotate-half) inside the turned dims,
which are the head's first; layers equal in kind and in MLP that follow each
other are a ``lax.scan`` over stacked weights (``blocks_0``, ``blocks_1``,
...). For memory alone: attention runs one key-value head's group at a time
(its own columns of the projections and rows of ``W_o``) in blocks of queries,
each block against the keys its queries can see; the loss makes the logits of
2,048 positions at a time; for gradients each layer, group, block of queries,
expert and block of logits is under ``jax.checkpoint``; the gradient and the
optimizer's two moments live in the host's memory
(``granite_hybrid.HostTree``), because the comparison keeps three sets of 692 M
float32 weights on the device at once.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import glm_moe_lite as _glm
from benchmark.reference import granite_hybrid as _host
from benchmark.reference import mpt as _mpt

INIT_STD = 0.02
BIAS_STD = 0.01
#: queries scored at once, a key-value head's group (memory only)
QUERY_BLOCK = 1024
FULL, SLIDING = "full_attention", "sliding_attention"

MATMULS = _mpt.MATMULS
seed_key = _mpt.seed_key
worst_leaf_gap = _mpt.worst_leaf_gap
adopt_init = _host.adopt_init
leaf_norms = _host.leaf_norms
HostTree = _host.HostTree
bias_step = _glm.bias_step

_rms_norm = _glm._rms_norm
_swiglu = _glm._swiglu


def dims_of(model: dict) -> dict:
    """The sizes this family needs, from a configuration file's ``model``."""
    held = int(model["moe_experts_held"]) or int(model["moe_num_experts"])
    return {
        "d_model": int(model["d_model"]),
        "n_layers": int(model["n_layers"]),
        "layer_types": str(model["layer_types"]),
        "n_dense": int(model["first_k_dense"]),
        "n_heads": int(model["n_heads"]),
        "swa_n_heads": int(model["swa_n_heads"]) or int(model["n_heads"]),
        "n_kv_heads": int(model["n_kv_heads"]),
        "d_head": int(model["head_dim"]),
        "window": int(model["sliding_window"]),
        "rope_theta": float(model["rope_theta"]),
        "swa_rope_theta": float(model["swa_rope_theta"]) or float(model["rope_theta"]),
        "rotary_share": float(model["partial_rotary_factor"]),
        "yarn": model.get("rope_scaling_type", "") == "yarn",
        "yarn_factor": float(model.get("rope_scaling_factor", 1.0)),
        "yarn_original": int(model.get("rope_scaling_original_max_position", 0)),
        "yarn_beta_fast": float(model.get("rope_scaling_beta_fast", 32.0)),
        "yarn_beta_slow": float(model.get("rope_scaling_beta_slow", 1.0)),
        "rope_factor": float(model.get("rope_scaling_attention_factor", 0.0)) or 1.0,
        "gated": model.get("attn_gate", "") == "headwise",
        "norm_eps": float(model["norm_eps"]),
        "max_seq_len": int(model["max_seq_len"]),
        "vocab_size": int(model["vocab_size"]),
        "dense_hidden": int(model["dense_mlp_hidden_size"]),
        "expert_hidden": int(model["mlp_hidden_size"]),
        "n_experts": int(model["moe_num_experts"]),
        "top_k": int(model["moe_top_k"]),
        "experts_held": held,
        "first_expert": int(model["moe_first_expert"]),
        "n_shared": int(model["moe_shared_experts"]),
        "routed_scale": float(model["moe_routed_scale"]),
        "bias_speed": float(model.get("moe_bias_update_speed", 0.0)),
    }


def stacks(dims: dict) -> list[tuple[str, str, bool, int]]:
    """``(name, kind, dense MLP, layers)`` of every run of layers equal in
    kind and in MLP, in order; run ``i`` is the stack ``blocks_i``."""
    runs: list[tuple[str, bool, int]] = []
    for i, kind in enumerate(k.strip() for k in dims["layer_types"].split(",")):
        if kind not in (FULL, SLIDING):
            raise ValueError(f"layer kind {kind!r} is neither {FULL!r} nor {SLIDING!r}")
        dense = i < dims["n_dense"]
        if runs and runs[-1][:2] == (kind, dense):
            runs[-1] = (kind, dense, runs[-1][2] + 1)
        else:
            runs.append((kind, dense, 1))
    if sum(n for *_, n in runs) != dims["n_layers"]:
        raise ValueError("layer_types does not name n_layers layers")
    return [(f"blocks_{i}", *run) for i, run in enumerate(runs)]


def heads_of(dims: dict, kind: str) -> int:
    return dims["swa_n_heads"] if kind == SLIDING else dims["n_heads"]


def make_params(dims: dict, seed, dtype=jnp.float32) -> dict:
    """Seeded weights in the program's tree layout: normal, std 0.02 (the
    gate's too); residual projections (``out_proj``, every ``down``) scaled by
    ``1/sqrt(2 L)``; norm scales 1; the selection bias normal, std 0.01, so
    that it changes who is selected. ``seed`` is a whole number or a key from
    :func:`seed_key`."""
    d, L, v = dims["d_model"], dims["n_layers"], dims["vocab_size"]
    hkv, dh = dims["n_kv_heads"], dims["d_head"]
    fd, fe = dims["dense_hidden"], dims["expert_hidden"]
    e, eh = dims["n_experts"], dims["experts_held"]
    resid = INIT_STD / math.sqrt(2.0 * L)
    key = seed_key(seed) if isinstance(seed, (int, np.integer)) else seed
    keys = iter(jax.random.split(key, 16 * len(stacks(dims)) + 2))

    def normal(shape, std=INIT_STD):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    def attention_weights(kind, n):
        h = heads_of(dims, kind)
        out = {"ln_1": {"scale": jnp.ones((n, d), dtype)},
               "ln_2": {"scale": jnp.ones((n, d), dtype)},
               "q_proj": {"kernel": normal((n, d, h * dh))},
               "k_proj": {"kernel": normal((n, d, hkv * dh))},
               "v_proj": {"kernel": normal((n, d, hkv * dh))},
               "out_proj": {"kernel": normal((n, h * dh, d), resid)}}
        if dims["gated"]:
            out["attn_gate"] = {"kernel": normal((n, d, h))}
        return out

    def mlp_weights(dense, n):
        if dense:
            return {"gate_proj": {"kernel": normal((n, d, fd))},
                    "up_proj": {"kernel": normal((n, d, fd))},
                    "down_proj": {"kernel": normal((n, fd, d), resid)}}
        out = {"router": normal((n, d, e)),
               "router_bias": normal((n, e), BIAS_STD).astype(jnp.float32),
               "moe_gate": normal((n, eh, d, fe)),
               "moe_up": normal((n, eh, d, fe)),
               "moe_down": normal((n, eh, fe, d), resid)}
        if dims["n_shared"]:
            width = dims["n_shared"] * fe
            out.update({"shared_gate_proj": {"kernel": normal((n, d, width))},
                        "shared_up_proj": {"kernel": normal((n, d, width))},
                        "shared_down_proj": {"kernel": normal((n, width, d), resid)}})
        return out

    params = {"wte": {"embedding": normal((v, d))},
              "lm_head": {"kernel": normal((d, v))},
              "ln_f": {"scale": jnp.ones((d,), dtype)}}
    for name, kind, dense, n in stacks(dims):
        params[name] = {"block": {**attention_weights(kind, n), **mlp_weights(dense, n)}}
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def yarn_inv_freq(dims: dict, dim: int) -> np.ndarray:
    """YaRN's ``dim / 2`` inverse frequencies over ``dim`` turned dims (the
    docstring's ``inv_freq_i``)."""
    theta, factor = dims["rope_theta"], dims["yarn_factor"]

    def correction(rotations: float) -> float:
        return dim * math.log(dims["yarn_original"] / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction(dims["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction(dims["yarn_beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)
    keep = 1.0 - np.clip((i - low) / ((high - low) or 0.001), 0.0, 1.0)
    return (plain / factor * (1.0 - keep) + plain * keep).astype(np.float32)


def rope_tables(dims: dict, kind: str, seq: int):
    """``(turned dims, cos [S, turned / 2], sin)`` of one kind of layer."""
    if kind == SLIDING:
        turned, factor = dims["d_head"], 1.0
        i = np.arange(turned // 2, dtype=np.float64)
        inv = (dims["swa_rope_theta"] ** (-2.0 * i / turned)).astype(np.float32)
    else:
        turned = int(round(dims["d_head"] * dims["rotary_share"]))
        factor = dims["rope_factor"]
        if dims["yarn"]:
            inv = yarn_inv_freq(dims, turned)
        else:
            i = np.arange(turned // 2, dtype=np.float64)
            inv = (dims["rope_theta"] ** (-2.0 * i / turned)).astype(np.float32)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    return turned, factor * jnp.cos(ang), factor * jnp.sin(ang)


def _rope(x, tables):
    """``x [B, S, H, D]`` with its first ``turned`` dims turned (dim ``i`` with
    ``i + turned / 2``) and the rest passed."""
    turned, cos, sin = tables
    half = turned // 2
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:turned], x[..., turned:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _sum_over(part, weights, like):
    """``sum_i part(weights[i])``, one part after another (``lax.scan`` with
    the sum as its carry: nothing of a part outlives it but its share of the
    sum, forward or backward)."""
    total, _ = jax.lax.scan(lambda acc, w: (acc + part(w), None), jnp.zeros_like(like), weights)
    return total


def attention(h, p, kind: str, dims, mm):
    """``attention(h)`` of one layer of ``kind`` with weights ``p``, ``h``
    already normed: one key-value head's group of query heads at a time, each
    with its own columns of ``W_q``, ``W_k``, ``W_v`` and ``W_g`` and its own
    rows of ``W_o`` (``concat(o) W_o`` is the sum of the groups' parts)."""
    b, s, d = h.shape
    heads, kv, dh = heads_of(dims, kind), dims["n_kv_heads"], dims["d_head"]
    group = heads // kv
    window = dims["window"] if kind == SLIDING else s
    tables = rope_tables(dims, kind, s)
    block = min(QUERY_BLOCK, s)

    def one_block(qb, kb, vb, lo: int, first: int):
        """Queries ``lo ..`` of one group ``qb [group, B, n, D]`` against keys
        ``first ..`` (``kb``, ``vb [B, m, D]``)."""
        scores = mm(qb, kb[None].transpose(0, 1, 3, 2)) / math.sqrt(dh)
        i = lo + jnp.arange(qb.shape[2])[:, None]
        j = first + jnp.arange(kb.shape[1])[None, :]
        seen = (j <= i) & (j > i - window)
        return mm(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), vb[None])

    def one_group(w):
        wq, wk, wv, wo, wg = w  # [D, group D_h], [D, D_h], [D, D_h], [group D_h, D], [D, group]
        q = _rope(mm(h, wq).reshape(b, s, group, dh), tables).transpose(2, 0, 1, 3)
        k = _rope(mm(h, wk).reshape(b, s, 1, dh), tables)[:, :, 0]
        v = mm(h, wv)
        out = []
        for lo in range(0, s, block):
            hi = min(lo + block, s)
            first = max(lo - window + 1, 0)  # the block's first query's first key
            # (one block's scores at a time are kept for the gradient)
            out.append(jax.checkpoint(one_block, static_argnums=(3, 4))(
                q[:, :, lo:hi], k[:, first:hi], v[:, first:hi], lo, first))
        o = jnp.concatenate(out, axis=2).transpose(1, 2, 0, 3)  # [B, S, group, D_h]
        if dims["gated"]:
            o = o * jax.nn.sigmoid(mm(h, wg))[..., None]
        return mm(o.reshape(b, s, group * dh), wo)

    # head ``i`` is member ``i % group`` of key-value head ``i // group``
    by_group = (
        p["q_proj"]["kernel"].reshape(d, kv, group * dh).transpose(1, 0, 2),
        p["k_proj"]["kernel"].reshape(d, kv, dh).transpose(1, 0, 2),
        p["v_proj"]["kernel"].reshape(d, kv, dh).transpose(1, 0, 2),
        p["out_proj"]["kernel"].reshape(kv, group * dh, d),
        (p["attn_gate"]["kernel"] if dims["gated"] else jnp.zeros((d, heads), h.dtype)
         ).reshape(d, kv, group).transpose(1, 0, 2),
    )
    return _sum_over(jax.checkpoint(one_group), by_group, h)


def routed_experts(u, p, dims, mm, idx, gates):
    """This chip's part of the routed sum: the experts held here one after
    another, each applied to every token and weighted by its gate where it was
    picked and by zero elsewhere (``glm_moe_lite.routed_experts``' sum; here
    the weighting is inside what is recomputed for the gradient, so that 32
    experts' outputs over 16,384 tokens are not all kept for it)."""
    def weighted(w):
        e, wg, wu, wd = w
        weight = jnp.sum(jnp.where(idx == e, gates, 0.0), axis=-1)
        return weight[..., None] * _swiglu(u, wg, wu, wd, mm)

    held = dims["first_expert"] + jnp.arange(dims["experts_held"])
    return _sum_over(jax.checkpoint(weighted),
                     (held, p["moe_gate"], p["moe_up"], p["moe_down"]), u)


def sparse_mlp(u, p, dims, mm):
    """``(mlp(u), rows [E])`` of an expert layer: the shared expert on every
    token plus the held experts' part of the routed sum, and the assignments
    by routed expert."""
    idx, gates = _glm.route(u, p["router"], p["router_bias"], dims, mm)
    out = routed_experts(u, p, dims, mm, idx, gates)
    if dims["n_shared"]:
        out = out + _glm.shared_expert(u, p, mm)
    return out, _glm.expert_rows(idx, dims["n_experts"])


def block(x, p, kind: str, dense: bool, dims, mm):
    """``(x, rows)``: one layer's output, and its assignments by routed expert
    (``None`` from a dense layer, which routes nothing)."""
    eps = dims["norm_eps"]
    x = x + attention(_rms_norm(x, p["ln_1"]["scale"], eps), p, kind, dims, mm)
    u = _rms_norm(x, p["ln_2"]["scale"], eps)
    if dense:
        return x + _swiglu(u, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                           p["down_proj"]["kernel"], mm), None
    out, rows = sparse_mlp(u, p, dims, mm)
    return x + out, rows


def hidden_and_rows(params: dict, tokens: jax.Array, dims: dict,
                    matmul: str = "float32", remat: bool = False):
    """``tokens [B, S] int32`` -> ``(ln_f's output [B, S, D] float32, rows)``,
    ``rows[stack] [layers, E]`` the assignments to every routed expert by
    expert stack."""
    mm = MATMULS[matmul]
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p32["wte"]["embedding"][tokens]
    rows = {}
    for name, kind, dense, _ in stacks(dims):
        def body(x, layer, kind=kind, dense=dense):
            return block(x, layer, kind, dense, dims, mm)

        if remat:
            body = jax.checkpoint(body)
        x, by_layer = jax.lax.scan(body, x, p32[name]["block"])
        if not dense:
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


#: positions whose logits are made at once for the loss (memory only)
LOSS_BLOCK = 2048


def ce_sum_and_rows(params: dict, tokens: jax.Array, dims: dict,
                    matmul: str = "float32", remat: bool = False):
    """Summed next-token cross entropy over ``tokens [B, S]``, and the
    assignments by expert stack, layer and routed expert. The logits of
    ``LOSS_BLOCK`` positions at a time (whole float32 logits of 16,384
    positions, their log-softmax and both gradients are 3 GB)."""
    mm = MATMULS[matmul]
    hidden, rows = hidden_and_rows(params, tokens, dims, matmul, remat)
    head = params["lm_head"]["kernel"].astype(jnp.float32)
    b, s, d = hidden.shape
    block = LOSS_BLOCK if s % LOSS_BLOCK == 0 else s
    target = jnp.concatenate([tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    counted = (jnp.arange(s) < s - 1).astype(jnp.float32)  # the last position predicts nothing

    def one_block(xtc):
        x, t, c = xtc  # [B, block, D], [B, block], [block]
        logp = jax.nn.log_softmax(mm(x, head), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, t[..., None], axis=-1)[..., 0] * c)

    blocks = (hidden.reshape(b, s // block, block, d).transpose(1, 0, 2, 3),
              target.reshape(b, s // block, block).transpose(1, 0, 2),
              counted.reshape(s // block, block))
    return jnp.sum(jax.lax.map(jax.checkpoint(one_block), blocks)), rows


def ce_sum(params: dict, tokens: jax.Array, dims: dict,
           matmul: str = "float32", remat: bool = False) -> jax.Array:
    return ce_sum_and_rows(params, tokens, dims, matmul, remat)[0]


# ---------------------------------------------------------------------------
# training: the gradient of a batch and the recipe's optimizer, the gradient
# and the two moments on the host; each expert stack's balancing step rides in
# its own ``router_bias`` leaf's place, as in ``lfm2_moe`` and ``xing_mhc_moe``
# ---------------------------------------------------------------------------


def _bias_leaves(treedef) -> dict[str, int]:
    """``{stack: where its ``block/router_bias`` lies among the tree's leaves}``."""
    paths, _ = jax.tree_util.tree_flatten_with_path(
        jax.tree.unflatten(treedef, list(range(treedef.num_leaves))))
    names = [tuple(getattr(k, "key", k) for k in path) for path, _ in paths]
    return {name[0]: i for i, name in enumerate(names) if name[1:] == ("block", "router_bias")}


def _without_bias_steps(grads: HostTree, in_place: bool = False
                        ) -> tuple[HostTree, dict[str, np.ndarray]]:
    """``(grads with zero ``router_bias`` leaves, those leaves by stack)``: a
    ``b`` has no gradient, and :class:`Grad` uses its place to hand that
    stack's balancing step to :func:`adopt_step`. ``in_place``: the result
    shares ``grads``' own list of leaves, so that the optimizer's letting go
    of each leaf once it is used frees it (a jitted step keeps the static
    ``grads`` it was called with, and with it any array its list still holds:
    2.8 GB a step here, which ended a run of two references at the host's 40
    GiB)."""
    leaves, steps = (grads.leaves if in_place else list(grads.leaves)), {}
    for stack, k in _bias_leaves(grads.treedef).items():
        steps[stack], leaves[k] = leaves[k], np.zeros_like(leaves[k])
    return HostTree(grads.treedef, leaves, grads.factor), steps


def clip_by_global_norm(grads: HostTree, max_norm: float) -> HostTree:
    """The gradient clipped; the balancing steps are no part of it."""
    return _host.clip_by_global_norm(_without_bias_steps(grads)[0], max_norm)


def adopt_step(params, state, grads: HostTree, opt: dict):
    """The host-moment ADOPT step on the gradient, then every expert stack's
    ``b`` moved by the balancing step that came in its place in the tree."""
    grads, steps = _without_bias_steps(grads, in_place=True)
    params, state = _host.adopt_step(params, state, grads, opt)
    moved = {stack: {"block": {
        **params[stack]["block"],
        "router_bias": params[stack]["block"]["router_bias"] - jnp.asarray(step, jnp.float32)}}
        for stack, step in steps.items()}
    return {**params, **moved}, state


class Grad:
    """Mean loss and its gradient over a batch, in blocks of rows whose
    gradients are summed on the device; the sum leaves it as a
    :class:`HostTree` with each expert stack's balancing step
    (``glm_moe_lite.bias_step`` of that stack's rows over the whole batch)
    where its ``b``'s zero gradient would be."""

    def __init__(self, dims: dict, matmul: str = "float32", rows: int = 1) -> None:
        self.rows = rows
        self.speed = dims["bias_speed"]
        self._fn = jax.jit(jax.value_and_grad(
            lambda p, t: ce_sum_and_rows(p, t, dims, matmul, remat=True), has_aux=True))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
        self._scale = jax.jit(lambda g, n: jax.tree.map(lambda a: a / n, g),
                              donate_argnums=(0,))

    def __call__(self, params: dict, batch: np.ndarray):
        n_rows, seq = batch.shape
        if n_rows % self.rows:
            raise ValueError(f"{n_rows} rows do not split into {self.rows}s")
        total, grads, routed = 0.0, None, None
        for lo in range(0, n_rows, self.rows):
            (loss, by_expert), g = self._fn(params, jnp.asarray(batch[lo:lo + self.rows]))
            total = total + loss
            by_expert = jax.tree.map(lambda a: np.asarray(a, np.float32), by_expert)
            routed = by_expert if routed is None else jax.tree.map(np.add, routed, by_expert)
            grads = g if grads is None else self._add(grads, g)
        n = n_rows * (seq - 1)
        host = HostTree.fetched(self._scale(grads, jnp.float32(n)))
        for stack, k in _bias_leaves(host.treedef).items():
            host.leaves[k] = np.asarray(bias_step(routed[stack], self.speed), np.float32)
        return total / n, host
