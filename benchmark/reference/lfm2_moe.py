"""Plain LFM2-8B-A1B (``lfm2_moe``), one chip's share: forward pass, loss,
gradients and the recipe's optimizer step.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``. No kernel, no cache, no sort: the taps are shifted
products, attention is whole probability rows one head at a time, the experts
are a loop over the ones held here, each applied to every token and weighted
by a mask. It imports nothing of the program; the optimizer, the
stated-precision products and the leaf comparison are ``reference/mpt.py``'s.
The layer equations (``h = RMSNorm(x)``: float32, eps 1e-5, scale only; no
bias anywhere):

- Every block: ``x = x + mixer(operator_norm(x))``, ``x = x + ffn(ffn_norm(
  x))`` (``ln_1``, ``ln_2`` here). After the last block one RMSNorm
  (``embedding_norm`` in the public code, ``ln_f`` here), then the logits on
  the tied embedding over the vocabulary slice.
- Conv mixer (``layer_types``' ``conv``): ``B | C | u = h W_in`` (three times
  ``d_model`` wide, split in that order); ``v = B * u``; per channel ``w_t =
  sum_k kernel[k] v_(t - taps + 1 + k)`` with zeros before the row's start
  (depthwise, causal, 3 taps, no bias, no activation); ``y = (C * w) W_out``.
- Attention mixer (``attention``): ``q, k, v = h W_q, h W_k, h W_v`` (32
  query / 8 key-value heads of 64); an RMSNorm over each head's 64 dimensions
  of q and of k (a learned scale each); RoPE at theta 1e6 over all 64; causal
  ``softmax(q k^T / 8) v``, key-value head ``j`` serving query heads ``4j ..
  4j+3``; then ``W_o``.
- Dense ffn (the leading layers): ``(silu(h W_g) * (h W_u)) W_d``.
- Expert ffn (every other layer): ``s = sigmoid(h W_r)`` over all 32 experts;
  ``sel = top_4(s + b)`` (``b``, the expert bias, selects only and takes no
  gradient); ``g_e = s_e / (sum_{sel} s + 1e-6)`` (``norm_topk_prob``,
  ``routed_scaling_factor`` 1); ``sum_{e in sel and held} g_e FFN_e(h)``. No
  shared expert, no token dropped. What the absent experts would have added
  is left out, here as in the program, and the partial result goes on.

Departures from the published modelling code, each because the program does
the same and the two must compute one function (``assumed`` in the
configuration file): the split order ``B | C | u`` and no activation in the
conv mixer; the per-head q / k norms before the rotation; RoPE pairs dimension
``i`` with ``i + 32`` (rotate-half); ``1e-6`` in the gate's denominator; the
head tied to the embedding; after every optimizer step each expert layer's
``b`` moves against that layer's loads, ``b_e -= speed * clip((rows_e - mean)
/ mean, -1, 1)`` in float32 (:func:`bias_step`: the config names the bias and
gives no rule); layers equal in mixer and in ffn kind that follow each other
are a ``lax.scan`` over stacked weights (``blocks_0``, ``blocks_1``, ...). For
memory alone: attention runs one head at a time and, for gradients, each
block is under ``jax.checkpoint`` and the batch is walked in blocks of rows
whose gradients are summed on the host, so that float32 at 8,192 tokens fits
beside what the comparison keeps.
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
    d, h = int(model["d_model"]), int(model["n_heads"])
    held = int(model["moe_experts_held"]) or int(model["moe_num_experts"])
    return {
        "d_model": d,
        "n_layers": int(model["n_layers"]),
        "layer_types": str(model["layer_types"]),
        "n_dense": int(model["first_k_dense"]),
        "conv_taps": int(model["conv_kernel_size"]),
        "n_heads": h,
        "n_kv_heads": int(model["n_kv_heads"]),
        "d_head": d // h,
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
        "routed_scale": float(model["moe_routed_scale"]),
        "gate_eps": float(model["moe_gate_eps"]),
        "bias_speed": float(model.get("moe_bias_update_speed", 0.0)),
    }


def stacks(dims: dict) -> list[tuple[str, str, bool, int]]:
    """``(name, mixer, dense ffn, layers)`` of every run of layers equal in
    mixer and in ffn kind, in order; run ``i`` is the stack ``blocks_i``."""
    runs: list[tuple[str, bool, int]] = []
    for i, kind in enumerate(k.strip() for k in dims["layer_types"].split(",")):
        if kind not in ("conv", "attention"):
            raise ValueError(f"layer kind {kind!r} is neither 'conv' nor 'attention'")
        dense = i < dims["n_dense"]
        if runs and runs[-1][:2] == (kind, dense):
            runs[-1] = (kind, dense, runs[-1][2] + 1)
        else:
            runs.append((kind, dense, 1))
    if sum(n for *_, n in runs) != dims["n_layers"]:
        raise ValueError("layer_types does not name n_layers layers")
    return [(f"blocks_{i}", *run) for i, run in enumerate(runs)]


def make_params(dims: dict, seed, dtype=jnp.float32) -> dict:
    """Seeded weights in the program's tree layout: normal, std 0.02 (the
    taps too); residual projections (``out_proj`` of both mixers, every
    ``down``) scaled by ``1/sqrt(2 L)``; norm scales 1; the selection bias
    normal, std 0.01, so that it changes who is selected. ``seed`` is a whole
    number or a key from :func:`seed_key`."""
    d, L, v = dims["d_model"], dims["n_layers"], dims["vocab_size"]
    h, hkv, dh = dims["n_heads"], dims["n_kv_heads"], dims["d_head"]
    fd, fe = dims["dense_hidden"], dims["expert_hidden"]
    e, eh, taps = dims["n_experts"], dims["experts_held"], dims["conv_taps"]
    resid = INIT_STD / math.sqrt(2.0 * L)
    key = seed_key(seed) if isinstance(seed, (int, np.integer)) else seed
    keys = iter(jax.random.split(key, 16 * len(stacks(dims)) + 1))

    def normal(shape, std=INIT_STD):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    def mixer(kind, n):
        norms = {"ln_1": {"scale": jnp.ones((n, d), dtype)},
                 "ln_2": {"scale": jnp.ones((n, d), dtype)}}
        if kind == "conv":
            return {**norms,
                    "in_proj": {"kernel": normal((n, d, 3 * d))},
                    "conv_kernel": normal((n, taps, d)),
                    "out_proj": {"kernel": normal((n, d, d), resid)}}
        return {**norms,
                "q_proj": {"kernel": normal((n, d, h * dh))},
                "k_proj": {"kernel": normal((n, d, hkv * dh))},
                "v_proj": {"kernel": normal((n, d, hkv * dh))},
                "q_norm": {"scale": jnp.ones((n, dh), dtype)},
                "k_norm": {"scale": jnp.ones((n, dh), dtype)},
                "out_proj": {"kernel": normal((n, h * dh, d), resid)}}

    def ffn(dense, n):
        if dense:
            return {"gate_proj": {"kernel": normal((n, d, fd))},
                    "up_proj": {"kernel": normal((n, d, fd))},
                    "down_proj": {"kernel": normal((n, fd, d), resid)}}
        return {"router": normal((n, d, e)),
                "router_bias": normal((n, e), BIAS_STD).astype(jnp.float32),
                "moe_gate": normal((n, eh, d, fe)),
                "moe_up": normal((n, eh, d, fe)),
                "moe_down": normal((n, eh, fe, d), resid)}

    params = {"wte": {"embedding": normal((v, d))},
              "ln_f": {"scale": jnp.ones((d,), dtype)}}
    for name, kind, dense, n in stacks(dims):
        params[name] = {"block": {**mixer(kind, n), **ffn(dense, n)}}
    return params


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


def short_conv(v, kernel):
    """``w_t = sum_k kernel[k] v_(t - taps + 1 + k)`` on ``v [B, S, C]``,
    zeros before the row's start: one shifted product a tap, no bias."""
    taps, s = kernel.shape[0], v.shape[1]
    out = jnp.zeros_like(v)
    for k in range(taps):
        shift = taps - 1 - k
        out = out + kernel[k] * jnp.pad(v, ((0, 0), (shift, 0), (0, 0)))[:, :s]
    return out


def conv_mixer(h, p, mm):
    """``mixer(h)`` of a conv layer with weights ``p``."""
    b, c, u = jnp.split(mm(h, p["in_proj"]["kernel"]), 3, axis=-1)
    return mm(c * short_conv(b * u, p["conv_kernel"]), p["out_proj"]["kernel"])


def attention_mixer(h, p, dims, mm):
    """``mixer(h)`` of an attention layer with weights ``p``."""
    bsz, s, _ = h.shape
    heads, kv, dh = dims["n_heads"], dims["n_kv_heads"], dims["d_head"]
    eps, theta = dims["norm_eps"], dims["rope_theta"]
    q = mm(h, p["q_proj"]["kernel"]).reshape(bsz, s, heads, dh)
    k = mm(h, p["k_proj"]["kernel"]).reshape(bsz, s, kv, dh)
    v = mm(h, p["v_proj"]["kernel"]).reshape(bsz, s, kv, dh)
    q = _rope(_rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms_norm(k, p["k_norm"]["scale"], eps), theta)
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(qkv):
        qh, kh, vh = qkv  # [B, S, d_head]
        scores = mm(qh, kh.transpose(0, 2, 1)) / math.sqrt(dh)
        scores = jnp.where(causal, scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, axis=-1), vh)

    by_head = lambda a: a.transpose(2, 0, 1, 3)  # noqa: E731
    out = jax.lax.map(jax.checkpoint(one_head), (by_head(q), by_head(k), by_head(v)))
    return mm(out.transpose(1, 2, 0, 3).reshape(bsz, s, heads * dh), p["out_proj"]["kernel"])


def route(h, router, bias, dims, mm):
    """``(idx [.., k], gates [.., k])``: sigmoid scores, the top ``k`` by
    score + bias, the picked scores over their sum + ``gate_eps``, scaled."""
    scores = jax.nn.sigmoid(mm(h, router))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), dims["top_k"])
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    gates = dims["routed_scale"] * picked / (
        jnp.sum(picked, axis=-1, keepdims=True) + dims["gate_eps"])
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


def block(x, p, kind: str, dense: bool, dims, mm):
    """``(x, rows)``: one layer's output, and its assignments by routed expert
    (``[E]``; ``None`` from a dense layer, which routes nothing)."""
    eps = dims["norm_eps"]
    h = _rms_norm(x, p["ln_1"]["scale"], eps)
    x = x + (conv_mixer(h, p, mm) if kind == "conv" else attention_mixer(h, p, dims, mm))
    h = _rms_norm(x, p["ln_2"]["scale"], eps)
    if dense:
        return x + _swiglu(h, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                           p["down_proj"]["kernel"], mm), None
    idx, gates = route(h, p["router"], p["router_bias"], dims, mm)
    return (x + routed_experts(h, p, dims, mm, (idx, gates)),
            expert_rows(idx, dims["n_experts"]))


def forward_and_rows(params: dict, tokens: jax.Array, dims: dict,
                     matmul: str = "float32", remat: bool = False):
    """``tokens [B, S] int32`` -> ``(logits [B, S, vocab] float32, rows)``,
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
    x = _rms_norm(x, p32["ln_f"]["scale"], dims["norm_eps"])
    return mm(x, p32["wte"]["embedding"].T), rows


def forward(params: dict, tokens: jax.Array, dims: dict,
            matmul: str = "float32", remat: bool = False) -> jax.Array:
    """``tokens [B, S] int32`` -> ``logits [B, S, vocab] float32``."""
    return forward_and_rows(params, tokens, dims, matmul, remat)[0]


def ce_sum_and_rows(params: dict, tokens: jax.Array, dims: dict,
                    matmul: str = "float32", remat: bool = False):
    """Summed next-token cross entropy over ``tokens [B, S]``, and the
    assignments by expert stack, layer and routed expert."""
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
    """What a selection bias loses after a step that routed ``rows [.., E]``
    assignments: ``speed`` times each expert's relative excess over the mean
    load, cut to [-1, 1]; float32."""
    rows = jnp.asarray(rows, jnp.float32)
    mean = jnp.maximum(jnp.mean(rows, axis=-1, keepdims=True), 1.0)
    return jnp.float32(speed) * jnp.clip((rows - mean) / mean, -1.0, 1.0)


def _without_bias_steps(grads):
    """``(grads with zero ``router_bias`` leaves, those leaves by stack)``. A
    ``b`` has no gradient; :class:`Grad` uses its place in the tree to hand
    that stack's balancing step to :func:`adopt_step` (the driver passes the
    tree from one to the other and nothing else)."""
    steps = {name: sub["block"]["router_bias"] for name, sub in grads.items()
             if "router_bias" in sub.get("block", {})}
    zeroed = {**grads, **{name: {"block": {**grads[name]["block"], "router_bias": step * 0}}
                          for name, step in steps.items()}}
    return zeroed, steps


def clip_by_global_norm(grads, max_norm: float):
    """The gradient clipped; the balancing steps are no part of it."""
    return _mpt.clip_by_global_norm(_without_bias_steps(grads)[0], max_norm)


def adopt_step(params, state, grads, opt: dict):
    """The dense family's ADOPT step on the gradient, then every expert
    stack's ``b`` moved by the balancing step that came in its place in the
    tree."""
    grads, steps = _without_bias_steps(grads)
    params, state = _mpt.adopt_step(params, state, grads, opt)
    moved = {name: {"block": {
        **params[name]["block"],
        "router_bias": params[name]["block"]["router_bias"] - jnp.asarray(step, jnp.float32)}}
        for name, step in steps.items()}
    return {**params, **moved}, state


class Grad:
    """Mean loss and its gradient over a batch, in blocks of rows. Each
    block's gradient is fetched to the host and summed there (float32), so
    that the device holds one gradient beside the two sets of weights and the
    optimizer's two moments the comparison keeps. The gradient comes back as a
    tree of numpy arrays, with each expert stack's balancing step
    (:func:`bias_step` of that stack's rows over the whole batch) where its
    ``b``'s zero gradient would be."""

    def __init__(self, dims: dict, matmul: str = "float32", rows: int = 1) -> None:
        self.rows = rows
        self.speed = dims["bias_speed"]
        self._fn = jax.jit(jax.value_and_grad(
            lambda p, t: ce_sum_and_rows(p, t, dims, matmul, remat=True), has_aux=True))

    def __call__(self, params: dict, batch: np.ndarray):
        n_rows, seq = batch.shape
        if n_rows % self.rows:
            raise ValueError(f"{n_rows} rows do not split into {self.rows}s")
        total, grads, routed = 0.0, None, None
        for lo in range(0, n_rows, self.rows):
            (loss, by_expert), g = self._fn(params, jnp.asarray(batch[lo:lo + self.rows]))
            total += float(loss)
            by_expert = jax.tree.map(lambda a: np.asarray(a, np.float32), by_expert)
            routed = by_expert if routed is None else jax.tree.map(np.add, routed, by_expert)
            g = jax.device_get(g)
            grads = g if grads is None else jax.tree.map(np.add, grads, g)
        n = n_rows * (seq - 1)
        grads = jax.tree.map(lambda g: g / np.float32(n), grads)
        # a b's gradient is exactly zero: its leaf carries the balancing step
        # of the whole batch's rows to adopt_step instead
        for name, rows in routed.items():
            grads[name]["block"]["router_bias"] = np.asarray(
                bias_step(rows, self.speed), np.float32)
        return total / n, grads


def leaf_norms(tree) -> dict[str, np.ndarray]:
    """L2 norm of every leaf; a leaf of a stack (``blocks_i``: weights
    stacked over the run's layers) gives one norm per layer."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = jnp.asarray(leaf, jnp.float32)
        if name.startswith("blocks_"):
            norms = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
        else:
            norms = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
        out[name] = norms
    return {k: np.asarray(v, np.float64) for k, v in jax.device_get(out).items()}
