"""Plain GLM-4.7-Flash (``glm4_moe_lite``), one chip's share: forward pass,
loss, gradients and the recipe's optimizer step.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``. No kernel, no cache, no sort: the experts are a loop
over the ones held here, each applied to every token and weighted by a mask.
It imports nothing of the program; the optimizer, the stated-precision
products and the leaf comparison are ``reference/mpt.py``'s. The layer
equations (``h = RMSNorm(x)``: float32, eps 1e-5, scale only; no bias
anywhere):

- Latent attention, every layer. ``c_q = RMSNorm(h W_qa)``; ``q = c_q W_qb``
  -> heads x ``[q_nope | q_rope]``. ``[c_kv | k_r] = h W_kva``;
  ``c_kv = RMSNorm(c_kv)``; ``c_kv W_kvb`` -> heads x ``[k_nope | v]``. RoPE
  (all rotary dims) on ``q_rope`` and on ``k_r``, which every head shares.
  ``o_i = softmax([q_nope_i | q_rope_i] [k_nope_i | k_r]^T / sqrt(d_qk),
  causal) v_i``; ``x += concat(o_i) W_o``.
- The leading dense layers: ``x += (silu(h W_g) * (h W_u)) W_d``.
- The expert layers: ``s = sigmoid(h W_r)``; ``sel = top_k(s + b)`` (``b``
  selects only and takes no gradient); ``g_e = scale * s_e / (sum_{sel} s +
  1e-20)``; ``x += FFN_shared(h) + sum_{e in sel and held} g_e FFN_e(h)``.
  No token is dropped. What the absent experts would have added is left out,
  here as in the program, and the partial result goes on.
- Final RMSNorm, an untied head over the vocabulary slice.

Departures from the published modelling code, each because the program does
the same and the two must compute one function (``assumed`` in the
configuration file): RoPE pairs dimension ``i`` with ``i + half``
(rotate-half, no interleaving permutation); after every optimizer step ``b``
moves against each routed expert's load, ``b_e -= speed * clip((rows_e - mean)
/ mean, -1, 1)`` in float32 (:func:`bias_step`; ``noaux_tc``'s aux-loss-free
balancing, whose published form takes the bare sign); layers
of one kind are a ``lax.scan`` over stacked weights; attention runs one head
at a time and, for gradients, each block is under ``jax.checkpoint`` and the
batch is walked in blocks of rows whose gradients are summed on the host, so
that float32 at 4,096 tokens fits beside what the comparison keeps.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import mpt as _mpt

INIT_STD = 0.02
BIAS_STD = 0.01

# what every family's reference offers, unchanged from the dense family's
MATMULS = _mpt.MATMULS
seed_key = _mpt.seed_key
adopt_init = _mpt.adopt_init
worst_leaf_gap = _mpt.worst_leaf_gap


def dims_of(model: dict) -> dict:
    """The sizes this family needs, from a configuration file's ``model``."""
    held = int(model["moe_experts_held"]) or int(model["moe_num_experts"])
    return {
        "d_model": int(model["d_model"]),
        "n_layers": int(model["n_layers"]),
        "n_dense": int(model["first_k_dense"]),
        "n_heads": int(model["n_heads"]),
        "q_rank": int(model["q_lora_rank"]),
        "kv_rank": int(model["kv_lora_rank"]),
        "d_nope": int(model["qk_nope_head_dim"]),
        "d_rope": int(model["qk_rope_head_dim"]),
        "d_v": int(model["v_head_dim"]),
        "rope_theta": float(model["rope_theta"]),
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


def make_params(dims: dict, seed, dtype=jnp.float32) -> dict:
    """Seeded weights in the program's tree layout: normal, std 0.02;
    residual projections (``out_proj``, every ``down``) scaled by
    ``1/sqrt(2 L)``; norm scales 1; the selection bias normal, std 0.01, so
    that it changes who is selected. ``seed`` is a whole number or a key from
    :func:`seed_key`."""
    d, L, nd = dims["d_model"], dims["n_layers"], dims["n_dense"]
    h, v = dims["n_heads"], dims["vocab_size"]
    rq, rkv = dims["q_rank"], dims["kv_rank"]
    nope, rope, dv = dims["d_nope"], dims["d_rope"], dims["d_v"]
    fd, fe = dims["dense_hidden"], dims["expert_hidden"]
    e, eh, sh = dims["n_experts"], dims["experts_held"], dims["n_shared"]
    resid = INIT_STD / math.sqrt(2.0 * L)
    key = seed_key(seed) if isinstance(seed, (int, np.integer)) else seed
    keys = iter(jax.random.split(key, 40))

    def normal(shape, std=INIT_STD):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    def attention(n):
        return {
            "ln_1": {"scale": jnp.ones((n, d), dtype)},
            "q_a_proj": {"kernel": normal((n, d, rq))},
            "q_a_norm": {"scale": jnp.ones((n, rq), dtype)},
            "q_b_proj": {"kernel": normal((n, rq, h * (nope + rope)))},
            "kv_a_proj": {"kernel": normal((n, d, rkv + rope))},
            "kv_a_norm": {"scale": jnp.ones((n, rkv), dtype)},
            "kv_b_proj": {"kernel": normal((n, rkv, h * (nope + dv)))},
            "out_proj": {"kernel": normal((n, h * dv, d), resid)},
            "ln_2": {"scale": jnp.ones((n, d), dtype)},
        }

    ne = L - nd
    return {
        "wte": {"embedding": normal((v, d))},
        "dense_blocks": {"block": {
            **attention(nd),
            "gate_proj": {"kernel": normal((nd, d, fd))},
            "up_proj": {"kernel": normal((nd, d, fd))},
            "down_proj": {"kernel": normal((nd, fd, d), resid)},
        }},
        "blocks": {"block": {
            **attention(ne),
            "router": normal((ne, d, e)),
            "router_bias": normal((ne, e), BIAS_STD).astype(jnp.float32),
            "moe_gate": normal((ne, eh, d, fe)),
            "moe_up": normal((ne, eh, d, fe)),
            "moe_down": normal((ne, eh, fe, d), resid),
            "shared_gate_proj": {"kernel": normal((ne, d, sh * fe))},
            "shared_up_proj": {"kernel": normal((ne, d, sh * fe))},
            "shared_down_proj": {"kernel": normal((ne, sh * fe, d), resid)},
        }},
        "ln_f": {"scale": jnp.ones((d,), dtype)},
        "lm_head": {"kernel": normal((d, v))},
    }


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotary positions on ``x [B, S, H, R]``: dimension ``i`` turns with
    ``i + R/2`` by the angle ``position * theta**(-2i/R)``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def latent_attention(x, p, dims, mm):
    """``x + attention(RMSNorm(x))`` for one layer's weights ``p``."""
    b, s, _ = x.shape
    heads, rkv = dims["n_heads"], dims["kv_rank"]
    nope, rope, dv = dims["d_nope"], dims["d_rope"], dims["d_v"]
    eps, theta = dims["norm_eps"], dims["rope_theta"]
    h = _rms_norm(x, p["ln_1"]["scale"], eps)
    c_q = _rms_norm(mm(h, p["q_a_proj"]["kernel"]), p["q_a_norm"]["scale"], eps)
    q = mm(c_q, p["q_b_proj"]["kernel"]).reshape(b, s, heads, nope + rope)
    kv_a = mm(h, p["kv_a_proj"]["kernel"])
    c_kv = _rms_norm(kv_a[..., :rkv], p["kv_a_norm"]["scale"], eps)
    kv = mm(c_kv, p["kv_b_proj"]["kernel"]).reshape(b, s, heads, nope + dv)
    k_rope = _rope(kv_a[..., None, rkv:], theta)  # one rotary key for all heads
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rope))], axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(qkv):
        qh, kh, vh = qkv  # [B, S, width]
        scores = mm(qh, kh.transpose(0, 2, 1)) / math.sqrt(nope + rope)
        scores = jnp.where(causal, scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, axis=-1), vh)

    by_head = lambda a: a.transpose(2, 0, 1, 3)  # noqa: E731
    out = jax.lax.map(jax.checkpoint(one_head), (by_head(q), by_head(k), by_head(v)))
    out = out.transpose(1, 2, 0, 3).reshape(b, s, heads * dv)
    return x + mm(out, p["out_proj"]["kernel"])


def route(h, router, bias, dims, mm):
    """``(idx [.., k], gates [.., k])``: sigmoid scores, the top ``k`` by
    score + bias, the picked scores renormalised and scaled."""
    scores = jax.nn.sigmoid(mm(h, router))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), dims["top_k"])
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    gates = dims["routed_scale"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return idx, gates


def expert_rows(idx, n_experts: int):
    """How many assignments each of ALL the routed experts got: ``[E]``."""
    return jnp.sum(idx[..., None] == jnp.arange(n_experts), axis=tuple(range(idx.ndim)),
                   dtype=jnp.float32)


def routed_experts(h, p, dims, mm, idx_gates=None):
    """This chip's part of the routed sum: a loop over the experts held
    here, each applied to every token and weighted by its gate where it was
    selected and by zero elsewhere."""
    idx, gates = idx_gates or route(h, p["router"], p["router_bias"], dims, mm)
    # one expert's hidden activations at a time are kept for the gradient
    expert = jax.checkpoint(lambda h, wg, wu, wd: _swiglu(h, wg, wu, wd, mm))
    out = jnp.zeros_like(h)
    for e in range(dims["experts_held"]):
        weight = jnp.sum(jnp.where(idx == dims["first_expert"] + e, gates, 0.0), axis=-1)
        out = out + weight[..., None] * expert(
            h, p["moe_gate"][e], p["moe_up"][e], p["moe_down"][e])
    return out


def shared_expert(h, p, mm):
    return _swiglu(h, p["shared_gate_proj"]["kernel"], p["shared_up_proj"]["kernel"],
                   p["shared_down_proj"]["kernel"], mm)


def dense_block(x, p, dims, mm):
    """``(x, None)``: a dense layer routes nothing."""
    x = latent_attention(x, p, dims, mm)
    h = _rms_norm(x, p["ln_2"]["scale"], dims["norm_eps"])
    return x + _swiglu(h, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                       p["down_proj"]["kernel"], mm), None


def expert_block(x, p, dims, mm):
    """``(x, rows [E])``: the layer's output and its assignments by expert."""
    x = latent_attention(x, p, dims, mm)
    h = _rms_norm(x, p["ln_2"]["scale"], dims["norm_eps"])
    idx, gates = route(h, p["router"], p["router_bias"], dims, mm)
    out = routed_experts(h, p, dims, mm, (idx, gates))
    if dims["n_shared"]:
        out = out + shared_expert(h, p, mm)
    return x + out, expert_rows(idx, dims["n_experts"])


def forward_and_rows(params: dict, tokens: jax.Array, dims: dict,
                     matmul: str = "float32", remat: bool = False):
    """``tokens [B, S] int32`` -> ``(logits [B, S, vocab] float32,
    rows [expert layers, E])``, the assignments to every routed expert."""
    mm = MATMULS[matmul]
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p32["wte"]["embedding"][tokens]
    rows = None
    for block, stack in ((dense_block, "dense_blocks"), (expert_block, "blocks")):
        def body(x, layer, block=block):
            return block(x, layer, dims, mm)

        if remat:
            body = jax.checkpoint(body)
        x, rows = jax.lax.scan(body, x, p32[stack]["block"])
    x = _rms_norm(x, p32["ln_f"]["scale"], dims["norm_eps"])
    return mm(x, p32["lm_head"]["kernel"]), rows


def forward(params: dict, tokens: jax.Array, dims: dict,
            matmul: str = "float32", remat: bool = False) -> jax.Array:
    """``tokens [B, S] int32`` -> ``logits [B, S, vocab] float32``."""
    return forward_and_rows(params, tokens, dims, matmul, remat)[0]


def ce_sum_and_rows(params: dict, tokens: jax.Array, dims: dict,
                    matmul: str = "float32", remat: bool = False):
    """Summed next-token cross entropy over ``tokens [B, S]``, and the
    assignments by expert layer and routed expert."""
    logits, rows = forward_and_rows(params, tokens, dims, matmul, remat)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.sum(gold), rows


def ce_sum(params: dict, tokens: jax.Array, dims: dict,
           matmul: str = "float32", remat: bool = False) -> jax.Array:
    return ce_sum_and_rows(params, tokens, dims, matmul, remat)[0]


# ---------------------------------------------------------------------------
# the selection bias's balancing step, and the optimizer around it
# ---------------------------------------------------------------------------


def bias_step(rows, speed: float):
    """What the selection bias loses after a step that routed ``rows [.., E]``
    assignments: ``speed`` times each expert's relative excess over the mean
    load, cut to [-1, 1]; float32."""
    rows = jnp.asarray(rows, jnp.float32)
    mean = jnp.maximum(jnp.mean(rows, axis=-1, keepdims=True), 1.0)
    return jnp.float32(speed) * jnp.clip((rows - mean) / mean, -1.0, 1.0)


def _without_bias_step(grads):
    """``(grads with a zero ``router_bias`` leaf, that leaf)``. ``b`` has no
    gradient; :class:`Grad` uses its place in the tree to hand the balancing
    step to :func:`adopt_step` (the driver passes the tree from one to the
    other and nothing else)."""
    block = grads["blocks"]["block"]
    step = block["router_bias"]
    zeroed = {**grads, "blocks": {"block": {**block, "router_bias": step * 0}}}
    return zeroed, step


def clip_by_global_norm(grads, max_norm: float):
    """The gradient clipped; the balancing step is no part of it."""
    return _mpt.clip_by_global_norm(_without_bias_step(grads)[0], max_norm)


def adopt_step(params, state, grads, opt: dict):
    """The dense family's ADOPT step on the gradient, then ``b`` moved by
    the balancing step that came in its place in the tree."""
    grads, step = _without_bias_step(grads)
    params, state = _mpt.adopt_step(params, state, grads, opt)
    block = params["blocks"]["block"]
    moved = block["router_bias"] - jnp.asarray(step, jnp.float32)
    return {**params, "blocks": {"block": {**block, "router_bias": moved}}}, state


class Grad:
    """Mean loss and its gradient over a batch, in blocks of rows. Each
    block's gradient is fetched to the host and summed there (float32), so
    that the device holds one gradient beside the two sets of weights and the
    optimizer's two moments the comparison keeps: six trees of 2.4 GB and the
    float32 activations of a 4,096-token row do not fit 16 GB together. The
    gradient comes back as a tree of numpy arrays, with the selection bias's
    balancing step (:func:`bias_step`) where ``b``'s zero gradient would be."""

    def __init__(self, dims: dict, matmul: str = "float32", rows: int = 1) -> None:
        self.rows = rows
        self.speed = dims["bias_speed"]
        self._fn = jax.jit(jax.value_and_grad(
            lambda p, t: ce_sum_and_rows(p, t, dims, matmul, remat=True), has_aux=True))

    def __call__(self, params: dict, batch: np.ndarray):
        n_rows, seq = batch.shape
        if n_rows % self.rows:
            raise ValueError(f"{n_rows} rows do not split into {self.rows}s")
        total, grads, routed = 0.0, None, 0.0
        for lo in range(0, n_rows, self.rows):
            (loss, by_expert), g = self._fn(params, jnp.asarray(batch[lo:lo + self.rows]))
            total += float(loss)
            routed = routed + np.asarray(by_expert, np.float32)
            g = jax.device_get(g)
            grads = g if grads is None else jax.tree.map(np.add, grads, g)
        n = n_rows * (seq - 1)
        grads = jax.tree.map(lambda g: g / np.float32(n), grads)
        # b's gradient is exactly zero: its leaf carries the balancing step
        # of the whole batch's rows to adopt_step instead
        grads["blocks"]["block"]["router_bias"] = np.asarray(
            bias_step(routed, self.speed), np.float32)
        return total / n, grads


def leaf_norms(tree) -> dict[str, np.ndarray]:
    """L2 norm of every leaf; a leaf of either stack (weights stacked over
    layers) gives one norm per layer."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = jnp.asarray(leaf, jnp.float32)
        if name.startswith(("blocks/", "dense_blocks/")):
            norms = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
        else:
            norms = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
        out[name] = norms
    return {k: np.asarray(v, np.float64) for k, v in jax.device_get(out).items()}
