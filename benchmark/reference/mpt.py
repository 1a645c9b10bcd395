"""Plain MPT: forward pass, loss, gradients and the recipe's optimizer step.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``. No kernel, no cache, no batching trick; it imports
nothing of the program and is given nothing the program made. It follows
MosaicML's ``mpt_causal_lm`` as the reference recipe configures it (learned
positions, pre-LayerNorm blocks, fused QKV, 4x MLP, no biases, tied
embeddings, softmax scale ``1/sqrt(d_head)``). Departures, each because the
program under test does the same and the two must compute one function:

- GELU is the tanh approximation (llm-foundry's default is the exact erf).
- Layers are a ``lax.scan`` over weights stacked on a leading axis, so that
  compile time does not grow with depth; the body is the plain block.
- For gradients the block is wrapped in ``jax.checkpoint`` and the batch is
  walked in blocks of rows, so that float32 activations of a real batch fit.

``matmul`` picks the precision of every product: ``float32`` is the
reference; ``bfloat16`` and ``int8`` are the controls, the steps down that a
later change could be tempted to take (int8: both operands rounded to 127
levels of their largest magnitude, per tensor, product accumulated in
float32).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1.0e-5
INIT_STD = 0.02


def dims_of(model: dict) -> dict:
    """The sizes this family needs, from a configuration file's ``model``."""
    d = int(model["d_model"])
    h = int(model["n_heads"])
    return {
        "d_model": d,
        "n_layers": int(model["n_layers"]),
        "n_heads": h,
        "d_head": int(model.get("d_head", d // h)),
        "max_seq_len": int(model["max_seq_len"]),
        "vocab_size": int(model["vocab_size"]),
        "hidden": int(model["expansion_ratio"]) * d,
    }


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds pass 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def make_params(dims: dict, seed, dtype=jnp.float32) -> dict:
    """Seeded weights in the layout a checkpoint of this family has: MPT's
    init (normal, std 0.02; residual projections scaled by 1/sqrt(2L);
    LayerNorm scales 1). ``seed`` is a whole number or a key from
    :func:`seed_key`; trace it under one ``jax.jit`` with the key as the
    argument to make them on the device in one call."""
    d, L, v = dims["d_model"], dims["n_layers"], dims["vocab_size"]
    s, f = dims["max_seq_len"], dims["hidden"]
    resid = INIT_STD / math.sqrt(2.0 * L)
    key = seed_key(seed) if isinstance(seed, (int, np.integer)) else seed
    k = jax.random.split(key, 6)

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    return {
        "wte": {"embedding": normal(k[0], (v, d), INIT_STD)},
        "wpe": normal(k[1], (s, d), INIT_STD),
        "blocks": {"block": {
            "ln_1": {"scale": jnp.ones((L, d), dtype)},
            "wqkv": {"kernel": normal(k[2], (L, d, 3 * d), INIT_STD)},
            "out_proj": {"kernel": normal(k[3], (L, d, d), resid)},
            "ln_2": {"scale": jnp.ones((L, d), dtype)},
            "up_proj": {"kernel": normal(k[4], (L, d, f), INIT_STD)},
            "down_proj": {"kernel": normal(k[5], (L, f, d), resid)},
        }},
        "ln_f": {"scale": jnp.ones((d,), dtype)},
    }


# ---------------------------------------------------------------------------
# matrix products at a stated precision
# ---------------------------------------------------------------------------


def _mm_float32(a, b):
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _mm_bfloat16(a, b):
    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _fake_int8(x):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


def _mm_int8(a, b):
    # straight-through rounding, so the control also has gradients
    qa = a + jax.lax.stop_gradient(_fake_int8(a) - a)
    qb = b + jax.lax.stop_gradient(_fake_int8(b) - b)
    return jnp.matmul(qa.astype(jnp.bfloat16), qb.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


MATMULS = {"float32": _mm_float32, "bfloat16": _mm_bfloat16, "int8": _mm_int8}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _layer_norm(x, scale):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, dims, mm):
    b, s, d = x.shape
    h, dh = dims["n_heads"], dims["d_head"]
    y = _layer_norm(x, p["ln_1"]["scale"])
    qkv = mm(y, p["wqkv"]["kernel"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = mm(jax.nn.softmax(scores, axis=-1), v)
    attn = attn.transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + mm(attn, p["out_proj"]["kernel"])
    y = _layer_norm(x, p["ln_2"]["scale"])
    y = _gelu_tanh(mm(y, p["up_proj"]["kernel"]))
    return x + mm(y, p["down_proj"]["kernel"])


def forward(params: dict, tokens: jax.Array, dims: dict,
            matmul: str = "float32", remat: bool = False) -> jax.Array:
    """``tokens [B, S] int32`` -> ``logits [B, S, vocab] float32``."""
    mm = MATMULS[matmul]
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    s = tokens.shape[1]
    x = p32["wte"]["embedding"][tokens] + p32["wpe"][None, :s, :]

    def body(x, layer):
        return _block(x, layer, dims, mm), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, p32["blocks"]["block"])
    x = _layer_norm(x, p32["ln_f"]["scale"])
    return mm(x, p32["wte"]["embedding"].T)


def ce_sum(params: dict, tokens: jax.Array, dims: dict,
           matmul: str = "float32", remat: bool = False) -> jax.Array:
    """Summed next-token cross entropy over ``tokens [B, S]``."""
    logits = forward(params, tokens, dims, matmul, remat)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.sum(gold)


# ---------------------------------------------------------------------------
# training: loss and gradient of a batch, and the recipe's optimizer
# ---------------------------------------------------------------------------


class Grad:
    """Mean loss and its gradient over a batch, in blocks of rows."""

    def __init__(self, dims: dict, matmul: str = "float32",
                 rows: int = 4) -> None:
        self.rows = rows
        self._fn = jax.jit(jax.value_and_grad(
            lambda p, t: ce_sum(p, t, dims, matmul, remat=True)))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))

    def __call__(self, params: dict, batch: np.ndarray):
        n_rows, seq = batch.shape
        if n_rows % self.rows:
            raise ValueError(f"{n_rows} rows do not split into {self.rows}s")
        total, grads = 0.0, None
        for lo in range(0, n_rows, self.rows):
            loss, g = self._fn(params, jnp.asarray(batch[lo:lo + self.rows]))
            total = total + loss
            grads = g if grads is None else self._add(grads, g)
        n = n_rows * (seq - 1)
        return total / n, jax.tree.map(lambda g: g / n, grads)


def lr_at(count, opt: dict):
    """Cosine with linear warm-up, as the recipe's scheduler has it."""
    warm, t_max = max(opt["t_warmup"], 0), opt["t_max"]
    t_max = max(t_max, warm + 1)
    count = jnp.asarray(count, jnp.float32)
    frac = jnp.clip((count - warm) / (t_max - warm), 0.0, 1.0)
    cos = opt["alpha_f"] + (1 - opt["alpha_f"]) * 0.5 * (1 + jnp.cos(jnp.pi * frac))
    return opt["lr"] * jnp.where(count < warm, count / max(warm, 1), cos)


def clip_by_global_norm(grads, max_norm: float):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    factor = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree.map(lambda g: g * factor, grads)


def adopt_init(params):
    return {"count": jnp.zeros([], jnp.int32),
            "m": jax.tree.map(jnp.zeros_like, params),
            "v": jax.tree.map(jnp.zeros_like, params)}


def adopt_step(params, state, grads, opt: dict):
    """ADOPT (Taniguchi et al. 2024) after clipping by global norm: the first
    call only sets ``v = g**2``; later calls update ``m`` with the gradient
    normalised by the previous ``v`` and clipped at ``count**0.25``."""
    if opt["name"] != "adopt":
        raise ValueError(f"the plain optimizer is ADOPT, not {opt['name']!r}")
    b1, b2 = opt["betas"]
    g = clip_by_global_norm(grads, opt["grad_clip_norm"])
    count = state["count"]
    first = count == 0
    bound = jnp.maximum(count.astype(jnp.float32), 1.0) ** 0.25

    def next_m(g, m, v):
        normed = jnp.clip(g / jnp.maximum(jnp.sqrt(v), opt["eps"]), -bound, bound)
        return jnp.where(first, m, b1 * m + (1 - b1) * normed)

    m = jax.tree.map(next_m, g, state["m"], state["v"])
    v = jax.tree.map(
        lambda g, v: jnp.where(first, g * g, b2 * v + (1 - b2) * g * g),
        g, state["v"])
    scale = jnp.where(first, 0.0, lr_at(count, opt))
    params = jax.tree.map(lambda p, m: p - scale * m, params, m)
    return params, {"count": count + 1, "m": m, "v": v}


# ---------------------------------------------------------------------------
# per-leaf norms, one layer of a stacked leaf at a time
# ---------------------------------------------------------------------------


def leaf_norms(tree) -> dict[str, np.ndarray]:
    """L2 norm of every leaf; a leaf under ``blocks`` (weights stacked over
    layers) gives one norm per layer."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        x = jnp.asarray(leaf, jnp.float32)
        if name.startswith("blocks/"):
            norms = jnp.sqrt(jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
        else:
            norms = jnp.sqrt(jnp.sum(jnp.square(x)))[None]
        out[name] = norms
    return {k: np.asarray(v, np.float64) for k, v in jax.device_get(out).items()}


def worst_leaf_gap(got: dict, want: dict) -> float:
    """The widest gap between two sets of leaf norms, each measured against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    ref = np.concatenate([want[k] for k in sorted(want)])
    prog = np.concatenate([got[k] for k in sorted(want)])
    floor = float(np.median(ref))
    return float(np.max(np.abs(prog - ref) / np.maximum(ref, floor)))
