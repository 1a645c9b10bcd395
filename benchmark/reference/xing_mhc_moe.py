"""Plain Xing4.0-29B-A4B (``xing4_0``), one chip's share: forward pass, loss,
gradients and the recipe's optimizer step.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``. No kernel, no cache, no sort, no ``remat`` in the
arithmetic. It imports nothing of the program: the expert layer (router, the
experts held here as a masked loop, the shared expert, the selection bias's
balancing step) is ``reference/glm_moe_lite.py``'s, the moments on the host
and their ADOPT step ``reference/granite_hybrid.py``'s, the stated-precision
products and the leaf comparison ``reference/mpt.py``'s. Per token, ``n`` = 4
streams ``X in R^{n x C}``, a layer is two sublayers ``F`` (attention, then the
dense SwiGLU or the expert layer; ``norm``: RMSNorm, float32, eps 1e-6, scale
only; no bias anywhere):

- Hyper-connection around every sublayer. ``r = vec(X) / sqrt(mean(vec(X)^2) +
  hc_eps)`` over all ``n C`` values, stream-major; ``[p | q | R] = r Phi^T``
  (``Phi [2n + n^2, n C]``); ``H~pre = a_0 p + b_pre``, ``H~post = a_1 q +
  b_post``, ``H~res = a_2 mat(R) + B_res`` (``mat`` row-major: ``R[j n + i]``
  is row ``j``, column ``i``); ``H_pre = sigmoid(H~pre)``, ``H_post = 2
  sigmoid(H~post)``, ``M_0 = exp(clip(H~res, -30, 30))`` and 20 times: every
  column divided by its sum ``+ hc_eps``, then every row by its sum ``+
  hc_eps``; ``H_res = M_20``. ``u = sum_i H_pre[i] X_i``; ``y = F(norm(u))``;
  ``X'_j = sum_i H_res[j, i] X_i + H_post[j] y``.
- Model: ``X_i = E[token]`` for every ``i``; after the last layer ``h = sum_i
  X_i``, the final norm, an untied head over the vocabulary slice.
- Latent attention. ``c_q = norm(h W_qa)``; ``q = c_q W_qb`` -> heads x
  ``[q_nope | q_rope]`` (128 | 64). ``[c_kv | k_r] = h W_kva`` (512 | 64);
  ``norm(c_kv) W_kvb`` -> heads x ``[k_nope | v]`` (128 | 128). The 64 rotary
  dims of ``q_rope`` and of ``k_r``, which every head shares, turn by YaRN's
  frequencies: ``f_i = theta^(-2i/64)``, ``g_i = f_i / factor``, ``m_i = 1 -
  clip((i - low) / (high - low), 0, 1)`` with ``low = floor(c(beta_fast))``,
  ``high = ceil(c(beta_slow))``, ``c(b) = 64 ln(orig / (2 pi b)) / (2 ln
  theta)``; ``inv_freq_i = g_i (1 - m_i) + f_i m_i``; cos and sin times
  ``mscale(mscale) / mscale(mscale_all_dim)``, ``mscale(m) = 0.1 m ln factor
  + 1``. Causal softmax at scale ``192^-1/2 mscale(mscale_all_dim)^2``;
  ``concat(o_i) W_o`` (4,096 -> 3,584).
- The leading dense layer: ``(silu(h W_g) * (h W_u)) W_d``.
- The expert layers (``glm_moe_lite``'s): ``s = sigmoid(h W_r)``; top 4 of ``s
  + b``; gates ``2 s_sel / (sum s_sel + 1e-20)``; the shared expert plus the
  held experts' part of the routed sum. No token is dropped.

Departures from a published modelling code, each because the program does the
same and the two must compute one function (``assumed`` in the configuration
file): RoPE pairs dimension ``i`` with ``i + 32`` (rotate-half); after every
optimizer step ``b`` moves against each routed expert's load
(``glm_moe_lite.bias_step``); the flattened norm has no gain. For memory
alone: layers of one kind are a ``lax.scan`` over stacked weights; attention
runs one head at a time in blocks of queries; for gradients each block is
under ``jax.checkpoint``; the gradient and the optimizer's two moments live in
the host's memory (``granite_hybrid.HostTree``), because the comparison keeps
three sets of 759 M float32 weights on the device at once.
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
#: the maps' parameters at the start: the three scales, and the mixing
#: logits' diagonal (the rest of ``b``: ``-ln(n - 1)`` for the read-in, 0)
ALPHA_INIT = 0.01
RES_INIT = 4.0
#: queries scored at once, a head (memory only)
QUERY_BLOCK = 1024

MATMULS = _mpt.MATMULS
seed_key = _mpt.seed_key
worst_leaf_gap = _mpt.worst_leaf_gap
adopt_init = _host.adopt_init
HostTree = _host.HostTree

_HIGHEST = jax.lax.Precision.HIGHEST
_BIAS = ("blocks", "block", "router_bias")


def dims_of(model: dict) -> dict:
    """The sizes this family needs, from a configuration file's ``model``."""
    return {
        **_glm.dims_of(model),
        "streams": int(model["hc_mult"]),
        "sinkhorn_iters": int(model["hc_sinkhorn_iters"]),
        "hc_eps": float(model["hc_eps"]),
        "res_clamp": float(model["hc_res_clamp"]),
        "yarn_factor": float(model["rope_scaling_factor"]),
        "yarn_original": int(model["rope_scaling_original_max_position"]),
        "yarn_beta_fast": float(model["rope_scaling_beta_fast"]),
        "yarn_beta_slow": float(model["rope_scaling_beta_slow"]),
        "yarn_mscale": float(model["rope_scaling_mscale"]),
        "yarn_mscale_all_dim": float(model["rope_scaling_mscale_all_dim"]),
    }


def make_params(dims: dict, seed, dtype=jnp.float32) -> dict:
    """Seeded weights in the program's tree layout: normal, std 0.02; residual
    projections (``out_proj``, every ``down``) scaled by ``1/sqrt(2 L)``; norm
    scales 1; the selection bias normal, std 0.01; a sublayer's maps: ``phi``
    normal, std 0.02, the scales 0.01, ``b`` such that at zero input the
    read-in weights are ``1/n``, the write-back weights 1 and the mixing
    logits 4 on the diagonal and 0 off it. ``seed`` is a whole number or a
    key from :func:`seed_key`."""
    d, L, nd = dims["d_model"], dims["n_layers"], dims["n_dense"]
    h, v, n = dims["n_heads"], dims["vocab_size"], dims["streams"]
    rq, rkv = dims["q_rank"], dims["kv_rank"]
    nope, rope, dv = dims["d_nope"], dims["d_rope"], dims["d_v"]
    fd, fe = dims["dense_hidden"], dims["expert_hidden"]
    e, eh, sh = dims["n_experts"], dims["experts_held"], dims["n_shared"]
    resid = INIT_STD / math.sqrt(2.0 * L)
    key = seed_key(seed) if isinstance(seed, (int, np.integer)) else seed
    keys = iter(jax.random.split(key, 48))

    def normal(shape, std=INIT_STD):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    b0 = jnp.concatenate([jnp.full((n,), -math.log(n - 1.0)), jnp.zeros((n,)),
                          RES_INIT * jnp.eye(n).reshape(-1)]).astype(jnp.float32)

    def maps(layers, site):
        return {
            f"{site}_phi": normal((layers, 2 * n + n * n, n * d)),
            f"{site}_b": jnp.tile(b0, (layers, 1)),
            f"{site}_alpha": jnp.full((layers, 3), ALPHA_INIT, jnp.float32),
        }

    def attention(layers):
        return {
            **maps(layers, "hc_1"),
            **maps(layers, "hc_2"),
            "ln_1": {"scale": jnp.ones((layers, d), dtype)},
            "q_a_proj": {"kernel": normal((layers, d, rq))},
            "q_a_norm": {"scale": jnp.ones((layers, rq), dtype)},
            "q_b_proj": {"kernel": normal((layers, rq, h * (nope + rope)))},
            "kv_a_proj": {"kernel": normal((layers, d, rkv + rope))},
            "kv_a_norm": {"scale": jnp.ones((layers, rkv), dtype)},
            "kv_b_proj": {"kernel": normal((layers, rkv, h * (nope + dv)))},
            "out_proj": {"kernel": normal((layers, h * dv, d), resid)},
            "ln_2": {"scale": jnp.ones((layers, d), dtype)},
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

_rms_norm = _glm._rms_norm


def yarn_inv_freq(dims: dict) -> np.ndarray:
    """The ``d_rope / 2`` inverse frequencies: the fast dimensions keep
    theirs, the slow ones turn ``factor`` times slower, a ramp between."""
    dim, theta = dims["d_rope"], dims["rope_theta"]

    def correction(rotations: float) -> float:
        return dim * math.log(dims["yarn_original"] / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction(dims["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction(dims["yarn_beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / dim)
    keep = 1.0 - np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return (plain / dims["yarn_factor"] * (1.0 - keep) + plain * keep).astype(np.float32)


def _mscale(dims: dict, m: float) -> float:
    return 0.1 * m * math.log(dims["yarn_factor"]) + 1.0 if dims["yarn_factor"] > 1 else 1.0


def softmax_scale(dims: dict) -> float:
    m = _mscale(dims, dims["yarn_mscale_all_dim"])
    return (dims["d_nope"] + dims["d_rope"]) ** -0.5 * m * m


def _rope(x, dims):
    """YaRN's rotation of ``x [B, S, H, R]``: dimension ``i`` turns with ``i +
    R/2`` by the angle ``position * inv_freq_i``."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * yarn_inv_freq(dims)[None, :]
    size = _mscale(dims, dims["yarn_mscale"]) / _mscale(dims, dims["yarn_mscale_all_dim"])
    cos, sin = (size * f(ang)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, p, dims, mm):
    """``attention(h)`` for one layer's weights ``p``, ``h`` already normed."""
    b, s, _ = h.shape
    heads, rkv, eps = dims["n_heads"], dims["kv_rank"], dims["norm_eps"]
    nope, rope, dv = dims["d_nope"], dims["d_rope"], dims["d_v"]
    c_q = _rms_norm(mm(h, p["q_a_proj"]["kernel"]), p["q_a_norm"]["scale"], eps)
    q = mm(c_q, p["q_b_proj"]["kernel"]).reshape(b, s, heads, nope + rope)
    kv_a = mm(h, p["kv_a_proj"]["kernel"])
    c_kv = _rms_norm(kv_a[..., :rkv], p["kv_a_norm"]["scale"], eps)
    kv = mm(c_kv, p["kv_b_proj"]["kernel"]).reshape(b, s, heads, nope + dv)
    k_rope = _rope(kv_a[..., None, rkv:], dims)  # one rotary key for all heads
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], dims)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, heads, rope))], axis=-1)
    v = kv[..., nope:]
    scale, block = softmax_scale(dims), min(QUERY_BLOCK, s)

    def one_head(qkv):
        qh, kh, vh = qkv  # [B, S, 192], [B, S, 192], [B, S, 128]
        out = []
        for lo in range(0, s, block):
            hi = min(lo + block, s)  # a block's queries see the keys up to its end
            scores = mm(qh[:, lo:hi], kh[:, :hi].transpose(0, 2, 1)) * scale
            seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
            out.append(mm(jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1),
                          vh[:, :hi]))
        return jnp.concatenate(out, axis=1)

    by_head = lambda a: a.transpose(2, 0, 1, 3)  # noqa: E731
    out = jax.lax.map(jax.checkpoint(one_head), (by_head(q), by_head(k), by_head(v)))
    return mm(out.transpose(1, 2, 0, 3).reshape(b, s, heads * dv), p["out_proj"]["kernel"])


def hyper_maps(x, p, site: str, dims, mm):
    """``(H_pre [B, S, n], H_post [B, S, n], H_res [B, S, n, n])`` of the
    sublayer ``site`` from the streams ``x [B, S, n, C]``."""
    b, s, n, c = x.shape
    eps, clamp = dims["hc_eps"], dims["res_clamp"]
    flat = x.reshape(b, s, n * c)
    r = flat * jax.lax.rsqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + eps)
    out = mm(r, p[f"{site}_phi"].T)
    a, bias = p[f"{site}_alpha"], p[f"{site}_b"]
    pre = jax.nn.sigmoid(a[0] * out[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * out[..., n:2 * n] + bias[n:2 * n])
    logits = (a[2] * out[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n)
    m = jnp.exp(jnp.clip(logits, -clamp, clamp))
    for _ in range(dims["sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)  # columns
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)  # rows
    return pre, post, m


def sublayer(x, p, site: str, branch, dims, mm):
    """``X' = H_res X + H_post branch(sum_i H_pre[i] X_i)`` on ``x [B, S, n,
    C]``; ``branch`` may return ``(y, aux)``."""
    pre, post, res = hyper_maps(x, p, site, dims, mm)
    u = jnp.einsum("bsn,bsnc->bsc", pre, x, precision=_HIGHEST)
    y, aux = branch(u)
    mixed = jnp.einsum("bsji,bsic->bsjc", res, x, precision=_HIGHEST)
    return mixed + post[..., None] * y[:, :, None, :], aux


def block(x, p, dims, mm, dense: bool):
    """One layer on the streams: ``(x, rows [E] or None)``."""
    eps = dims["norm_eps"]

    def attend(u):
        return attention(_rms_norm(u, p["ln_1"]["scale"], eps), p, dims, mm), None

    def dense_mlp(u):
        h = _rms_norm(u, p["ln_2"]["scale"], eps)
        return _glm._swiglu(h, p["gate_proj"]["kernel"], p["up_proj"]["kernel"],
                            p["down_proj"]["kernel"], mm), None

    def experts(u):
        h = _rms_norm(u, p["ln_2"]["scale"], eps)
        idx, gates = _glm.route(h, p["router"], p["router_bias"], dims, mm)
        out = _glm.routed_experts(h, p, dims, mm, (idx, gates))
        if dims["n_shared"]:
            out = out + _glm.shared_expert(h, p, mm)
        return out, _glm.expert_rows(idx, dims["n_experts"])

    x, _ = sublayer(x, p, "hc_1", attend, dims, mm)
    return sublayer(x, p, "hc_2", dense_mlp if dense else experts, dims, mm)


def forward_and_rows(params: dict, tokens: jax.Array, dims: dict,
                     matmul: str = "float32", remat: bool = False):
    """``tokens [B, S] int32`` -> ``(logits [B, S, vocab] float32,
    rows [expert layers, E])``, the assignments to every routed expert."""
    mm = MATMULS[matmul]
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    e = p32["wte"]["embedding"][tokens]
    x = jnp.broadcast_to(e[:, :, None, :], (*e.shape[:2], dims["streams"], e.shape[-1]))
    rows = None
    for dense, stack in ((True, "dense_blocks"), (False, "blocks")):
        def body(x, layer, dense=dense):
            return block(x, layer, dims, mm, dense)

        if remat:
            body = jax.checkpoint(body)
        x, rows = jax.lax.scan(body, x, p32[stack]["block"])
    h = _rms_norm(jnp.sum(x, axis=2), p32["ln_f"]["scale"], dims["norm_eps"])
    return mm(h, p32["lm_head"]["kernel"]), rows


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
# training: the gradient of a batch and the recipe's optimizer, the gradient
# and the two moments on the host; the selection bias's balancing step rides
# in its own leaf's place, as in ``glm_moe_lite``
# ---------------------------------------------------------------------------


def _bias_leaf(treedef) -> int:
    """Where ``blocks/block/router_bias`` lies among the tree's leaves."""
    paths, _ = jax.tree_util.tree_flatten_with_path(
        jax.tree.unflatten(treedef, list(range(treedef.num_leaves))))
    names = [tuple(getattr(k, "key", k) for k in path) for path, _ in paths]
    return names.index(_BIAS)


def _without_bias_step(grads: HostTree) -> tuple[HostTree, np.ndarray]:
    """``(grads with a zero ``router_bias`` leaf, that leaf)``: ``b`` has no
    gradient, and :class:`Grad` uses its place to hand the balancing step to
    :func:`adopt_step`."""
    k = _bias_leaf(grads.treedef)
    leaves = list(grads.leaves)
    step, leaves[k] = leaves[k], np.zeros_like(leaves[k])
    return HostTree(grads.treedef, leaves, grads.factor), step


def clip_by_global_norm(grads: HostTree, max_norm: float) -> HostTree:
    """The gradient clipped; the balancing step is no part of it."""
    return _host.clip_by_global_norm(_without_bias_step(grads)[0], max_norm)


def adopt_step(params, state, grads: HostTree, opt: dict):
    """The host-moment ADOPT step on the gradient, then ``b`` moved by the
    balancing step that came in its place in the tree."""
    grads, step = _without_bias_step(grads)
    params, state = _host.adopt_step(params, state, grads, opt)
    inner = params["blocks"]["block"]
    moved = inner["router_bias"] - jnp.asarray(step, jnp.float32)
    return {**params, "blocks": {"block": {**inner, "router_bias": moved}}}, state


class Grad:
    """Mean loss and its gradient over a batch, in blocks of rows whose
    gradients are summed on the device; the sum leaves it as a
    :class:`HostTree` with the selection bias's balancing step
    (``glm_moe_lite.bias_step`` of the whole batch's rows) where ``b``'s zero
    gradient would be."""

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
        total, grads, routed = 0.0, None, 0.0
        for lo in range(0, n_rows, self.rows):
            (loss, by_expert), g = self._fn(params, jnp.asarray(batch[lo:lo + self.rows]))
            total = total + loss
            routed = routed + np.asarray(by_expert, np.float32)
            grads = g if grads is None else self._add(grads, g)
        n = n_rows * (seq - 1)
        host = HostTree.fetched(self._scale(grads, jnp.float32(n)))
        host.leaves[_bias_leaf(host.treedef)] = np.asarray(
            _glm.bias_step(routed, self.speed), np.float32)
        return total / n, host


def leaf_norms(tree) -> dict[str, np.ndarray]:
    """L2 norm of every leaf; a leaf of either stack (weights stacked over
    layers) gives one norm per layer. ``tree`` is a tree of arrays or a
    :class:`HostTree`."""
    factor = 1.0
    if isinstance(tree, HostTree):
        tree, factor = tree.tree(), tree.factor
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        on_host = isinstance(leaf, np.ndarray)
        xp = np if on_host else jnp
        x = leaf if on_host else jnp.asarray(leaf, jnp.float32)
        stacked = name.startswith(("blocks/", "dense_blocks/"))
        rows = x.reshape(x.shape[0], -1) if stacked else x.reshape(1, -1)
        out[name] = xp.sqrt(xp.sum(xp.square(rows), axis=1, dtype=xp.float32)) * factor
    return {k: np.asarray(v, np.float64) for k, v in jax.device_get(out).items()}
