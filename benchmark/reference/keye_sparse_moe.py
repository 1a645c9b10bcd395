"""Plain Keye-VL-2.0-30B-A3B language model (``KeyeVL2``), one chip's share:
forward pass, objective, gradients and the recipe's optimizer step.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``. No kernel, no cache: the selection is a full sort of
each query's index scores, the probabilities are whole rows over all keys, the
experts are a loop over the ones held here, each applied to every token and
weighted by a mask. It imports nothing of the program; the optimizer, the
stated-precision products and the leaf comparison are ``reference/mpt.py``'s.
One layer, for a row of ``S`` positions (``x = RMSNorm(h)``, float32, eps
1e-6, scale only; no bias anywhere but the indexer's LayerNorm; ``t`` a query,
``s <= t`` a key):

1. ``q = x Wq -> [S, 32, 128]``, ``k = x Wk -> [S, 4, 128]``, ``v = x Wv``;
   ``q`` and ``k`` through an RMSNorm over the 128 (a learned scale each),
   then rotated at position ``t`` (theta 1e7, all 128 dimensions).
2. The indexer reads ``xd = stop_gradient(x)``: ``qI = xd WqI -> [S, 16, 64]``,
   ``kI = LayerNorm(xd WkI) -> [S, 64]`` (one key head), both rotated like q;
   ``w = xd Ww * 16^-1/2 * 64^-1/2 -> [S, 16]``. ``I[t, s] = sum_j w[t, j] *
   relu(qI[t, j] . kI[s])``.
3. ``tau_t`` = the 2,048th largest of ``I[t, :t+1]`` by a descending sort
   (``-inf`` while ``t < 2,048``); ``S_t = {s <= t : I[t, s] >= tau_t}``: ties
   at the threshold are ALL kept (the program follows this rule).
4. ``o[t, h] = softmax over S_t of (q[t, h] . k[s, g(h)] / sqrt(128)) . v``;
   ``h += concat(o) Wo``. ``S_t`` carries no gradient.
5. ``pbar[t, s]`` = the mean over the 32 heads of step 4's probabilities,
   detached; the layer's index loss ``L_I = (1/S) sum_t sum_{s in S_t}
   pbar[t, s] (log pbar[t, s] - log softmax over S_t of I[t, .])`` (a term
   with ``pbar = 0`` is 0). The objective is the mean cross-entropy plus the
   sum of the layers' ``L_I`` at weight 1: by the two ``stop_gradient``s the
   indexer's five leaves are moved by ``L_I`` alone and every other leaf by
   the cross-entropy alone.
6. ``r = softmax over all 128 experts of (RMSNorm(h) Wr)``, the top 8, gates
   ``r_e / (sum of the picked)``; ``h += sum over picked e held here of
   gate_e * SwiGLU_e``. No token is dropped, no auxiliary loss. What the
   absent experts would have added is left out, here as in the program.

Final RMSNorm, an untied head over the vocabulary slice.

Departures from the published modelling code, each because the program does
the same and the two must compute one function (``assumed`` in the
configuration file): the per-head q / k norms, rotate-half pairing over all
rotary dimensions with one position a token (text rows: the three
``mrope_section`` streams coincide), the indexer's scale and LayerNorm eps,
the index loss's weight; layers are a ``lax.scan`` over stacked weights;
attention, the selection and the index loss walk the queries in blocks of
``QUERY_BLOCK`` with all 32 heads' probability rows of a block whole, each
block and each layer under ``jax.checkpoint``, and the head walks the tokens
in blocks, so that float32 at 16,384 tokens fits beside what the comparison
keeps.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import mpt as _mpt

INIT_STD = 0.02
#: the token embedding's spread in the seeded state, which stands in for a
#: trained checkpoint's (the cell is a stage of continued pre-training):
#: ``torch.nn.Embedding``'s own N(0, 1), which T5's shared embedding takes
#: too (the configuration file's ``assumed.init`` has why every matrix at
#: 0.02 would not do)
EMBEDDING_STD = 1.0
#: queries whose whole probability rows are formed at once
QUERY_BLOCK = 512
#: tokens whose logits over the vocabulary slice are formed at once
HEAD_BLOCK = 2048

# what every family's reference offers, unchanged from the dense family's
MATMULS = _mpt.MATMULS
seed_key = _mpt.seed_key
adopt_init = _mpt.adopt_init
adopt_step = _mpt.adopt_step
clip_by_global_norm = _mpt.clip_by_global_norm
worst_leaf_gap = _mpt.worst_leaf_gap


def dims_of(model: dict) -> dict:
    """The sizes this family needs, from a configuration file's ``model``."""
    held = int(model["moe_experts_held"]) or int(model["moe_num_experts"])
    return {
        "d_model": int(model["d_model"]),
        "n_layers": int(model["n_layers"]),
        "n_heads": int(model["n_heads"]),
        "n_kv_heads": int(model["n_kv_heads"]),
        "d_head": int(model["head_dim"]),
        "rope_theta": float(model["rope_theta"]),
        "norm_eps": float(model["norm_eps"]),
        "max_seq_len": int(model["max_seq_len"]),
        "vocab_size": int(model["vocab_size"]),
        "topk": int(model["dsa_topk"]),
        "index_heads": int(model["dsa_index_heads"]),
        "index_dim": int(model["dsa_index_head_dim"]),
        "expert_hidden": int(model["mlp_hidden_size"]),
        "n_experts": int(model["moe_num_experts"]),
        "top_k": int(model["moe_top_k"]),
        "experts_held": held,
        "first_expert": int(model["moe_first_expert"]),
    }


def make_params(dims: dict, seed, dtype=jnp.float32) -> dict:
    """Seeded weights in the program's tree layout: normal, std 0.02 (the
    token embedding at :data:`EMBEDDING_STD`); residual projections
    (``out_proj``, ``moe_down``) scaled by ``1/sqrt(2 L)``; norm scales 1, the
    indexer's LayerNorm bias 0. ``seed`` is a whole number or a key from
    :func:`seed_key`."""
    d, L, v = dims["d_model"], dims["n_layers"], dims["vocab_size"]
    h, g, dh = dims["n_heads"], dims["n_kv_heads"], dims["d_head"]
    ih, idim = dims["index_heads"], dims["index_dim"]
    e, eh, fe = dims["n_experts"], dims["experts_held"], dims["expert_hidden"]
    resid = INIT_STD / math.sqrt(2.0 * L)
    key = seed_key(seed) if isinstance(seed, (int, np.integer)) else seed
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std=INIT_STD):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    ones = lambda *shape: jnp.ones(shape, dtype)  # noqa: E731
    return {
        "wte": {"embedding": normal((v, d), EMBEDDING_STD)},
        "blocks": {"block": {
            "ln_1": {"scale": ones(L, d)},
            "q_proj": {"kernel": normal((L, d, h * dh))},
            "k_proj": {"kernel": normal((L, d, g * dh))},
            "v_proj": {"kernel": normal((L, d, g * dh))},
            "q_norm": {"scale": ones(L, dh)},
            "k_norm": {"scale": ones(L, dh)},
            "out_proj": {"kernel": normal((L, h * dh, d), resid)},
            "idx_q_proj": {"kernel": normal((L, d, ih * idim))},
            "idx_k_proj": {"kernel": normal((L, d, idim))},
            "idx_k_norm": {"scale": ones(L, idim), "bias": jnp.zeros((L, idim), dtype)},
            "idx_w_proj": {"kernel": normal((L, d, ih))},
            "ln_2": {"scale": ones(L, d)},
            "router": normal((L, d, e)),
            "moe_gate": normal((L, eh, d, fe)),
            "moe_up": normal((L, eh, d, fe)),
            "moe_down": normal((L, eh, fe, d), resid),
        }},
        "ln_f": {"scale": ones(d)},
        "lm_head": {"kernel": normal((d, v))},
    }


# ---------------------------------------------------------------------------
# forward, one row at a time: x [S, D]
# ---------------------------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, theta):
    """Rotary positions on ``x [S, H, R]``: dimension ``i`` turns with
    ``i + R/2`` by the angle ``position * theta**(-2i/R)``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def index_scores(q_idx, k_idx, w, mm):
    """``I [Q, S]`` for the queries ``q_idx [Q, J, Di]``, ``w [Q, J]`` against
    every key ``k_idx [S, Di]``."""
    dots = mm(q_idx.transpose(1, 0, 2), k_idx.T)  # [J, Q, S]
    return jnp.sum(jax.nn.relu(dots) * w.T[:, :, None], axis=0)


def select(scores, t, topk: int):
    """``[Q, S]`` bool: the keys the queries at positions ``t [Q]`` see, from
    their index scores against all ``S`` keys."""
    causal = jnp.arange(scores.shape[1])[None, :] <= t[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    if topk >= scores.shape[1]:
        return causal
    ranked = -jnp.sort(-scores, axis=-1)  # descending, every row whole
    tau = jnp.where(t < topk, -jnp.inf, ranked[:, topk - 1])
    return causal & (scores >= tau[:, None])


def _query_block(qb, q_idx_b, w_b, t, k, v, k_idx, dims, mm):
    """One block of queries against all keys: ``(o [Q, H, dh], the block's
    sum over queries of the index loss's KL, the pairs it picked)``."""
    heads, groups, dh = dims["n_heads"], dims["n_kv_heads"], dims["d_head"]
    scores = index_scores(q_idx_b, k_idx, w_b, mm)
    picked = jax.lax.stop_gradient(select(scores, t, dims["topk"]))
    k_all = jnp.repeat(k, heads // groups, axis=1).transpose(1, 2, 0)  # [H, dh, S]
    v_all = jnp.repeat(v, heads // groups, axis=1).transpose(1, 0, 2)  # [H, S, dh]
    logits = mm(qb.transpose(1, 0, 2), k_all) / math.sqrt(dh)  # [H, Q, S]
    probs = jax.nn.softmax(jnp.where(picked[None], logits, -jnp.inf), axis=-1)
    out = mm(probs, v_all).transpose(1, 0, 2)  # [Q, H, dh]
    pbar = jax.lax.stop_gradient(jnp.mean(probs, axis=0))  # [Q, S]
    log_soft = jax.nn.log_softmax(jnp.where(picked, scores, -jnp.inf), axis=-1)
    live = picked & (pbar > 0.0)
    kl = jnp.where(live, pbar * (jnp.log(jnp.where(live, pbar, 1.0))
                                 - jnp.where(live, log_soft, 0.0)), 0.0)
    return out, jnp.sum(kl), jnp.sum(picked, dtype=jnp.float32)


def sparse_attention(x, p, dims, mm):
    """``(x + attention(RMSNorm(x)), L_I, picked pairs)`` for one layer's
    weights ``p`` and one row ``x [S, D]``."""
    s = x.shape[0]
    heads, groups, dh = dims["n_heads"], dims["n_kv_heads"], dims["d_head"]
    ih, idim = dims["index_heads"], dims["index_dim"]
    eps, theta = dims["norm_eps"], dims["rope_theta"]
    h = _rms_norm(x, p["ln_1"]["scale"], eps)
    q = mm(h, p["q_proj"]["kernel"]).reshape(s, heads, dh)
    k = mm(h, p["k_proj"]["kernel"]).reshape(s, groups, dh)
    v = mm(h, p["v_proj"]["kernel"]).reshape(s, groups, dh)
    q = _rope(_rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms_norm(k, p["k_norm"]["scale"], eps), theta)
    hd = jax.lax.stop_gradient(h)
    q_idx = _rope(mm(hd, p["idx_q_proj"]["kernel"]).reshape(s, ih, idim), theta)
    k_idx = _layer_norm(mm(hd, p["idx_k_proj"]["kernel"]), p["idx_k_norm"]["scale"],
                        p["idx_k_norm"]["bias"], eps)
    k_idx = _rope(k_idx[:, None, :], theta)[:, 0, :]
    w = mm(hd, p["idx_w_proj"]["kernel"]) * (ih ** -0.5 * idim ** -0.5)

    block = min(QUERY_BLOCK, s)
    n = s // block
    blocked = lambda a: a.reshape(n, block, *a.shape[1:])  # noqa: E731
    fn = jax.checkpoint(
        lambda a: _query_block(*a, k, v, k_idx, dims, mm))
    out, kl, picked = jax.lax.map(
        fn, (blocked(q), blocked(q_idx), blocked(w),
             jnp.arange(s, dtype=jnp.int32).reshape(n, block)))
    out = out.reshape(s, heads * dh)
    return x + mm(out, p["out_proj"]["kernel"]), jnp.sum(kl) / s, jnp.sum(picked)


def _swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def route(h, router, dims, mm):
    """``(idx [S, k], gates [S, k])``: a softmax over all the experts, the
    top ``k``, the picked probabilities renormalised to sum to 1."""
    probs = jax.nn.softmax(mm(h, router), axis=-1)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(probs), dims["top_k"])
    picked = jnp.take_along_axis(probs, idx, axis=-1)
    return idx, picked / jnp.sum(picked, axis=-1, keepdims=True)


def routed_experts(h, p, dims, mm, first_expert=None, experts=None):
    """This chip's part of the routed sum: a loop over the experts held here
    (``experts`` of them from ``first_expert`` on; by default the
    configuration's share), each applied to every token and weighted by its
    gate where it was selected and by zero elsewhere. Returns ``(out, rows
    routed to the held experts)``."""
    first = dims["first_expert"] if first_expert is None else first_expert
    held = dims["experts_held"] if experts is None else experts
    idx, gates = route(h, p["router"], dims, mm)
    expert = jax.checkpoint(lambda h, wg, wu, wd: _swiglu(h, wg, wu, wd, mm))
    out = jnp.zeros_like(h)
    for e in range(held):
        weight = jnp.sum(jnp.where(idx == first + e, gates, 0.0), axis=-1)
        out = out + weight[..., None] * expert(
            h, p["moe_gate"][e], p["moe_up"][e], p["moe_down"][e])
    rows = jnp.sum((idx >= first) & (idx < first + held), dtype=jnp.float32)
    return out, rows


def block(x, p, dims, mm):
    """``(x, (L_I, picked pairs, rows held))`` for one layer."""
    x, index_loss, picked = sparse_attention(x, p, dims, mm)
    h = _rms_norm(x, p["ln_2"]["scale"], dims["norm_eps"])
    out, rows = routed_experts(h, p, dims, mm)
    return x + out, (index_loss, picked, rows)


def hidden_and_stats(params: dict, tokens: jax.Array, dims: dict,
                     matmul: str = "float32", remat: bool = False):
    """One row ``tokens [S] int32`` -> ``(final-normed hidden [S, D], (L_I,
    picked pairs, rows held) by layer)``."""
    mm = MATMULS[matmul]
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p32["wte"]["embedding"][tokens]

    def body(x, layer):
        return block(x, layer, dims, mm)

    if remat:
        body = jax.checkpoint(body)
    x, stats = jax.lax.scan(body, x, p32["blocks"]["block"])
    return _rms_norm(x, p32["ln_f"]["scale"], dims["norm_eps"]), stats


def forward(params: dict, tokens: jax.Array, dims: dict,
            matmul: str = "float32", remat: bool = False) -> jax.Array:
    """``tokens [B, S] int32`` -> ``logits [B, S, vocab] float32``."""
    mm = MATMULS[matmul]
    head = params["lm_head"]["kernel"].astype(jnp.float32)
    return jax.lax.map(
        lambda row: mm(hidden_and_stats(params, row, dims, matmul, remat)[0], head), tokens)


def row_objective(params: dict, tokens: jax.Array, dims: dict,
                  matmul: str = "float32", remat: bool = False):
    """One row: ``(summed next-token cross-entropy, summed index losses of
    the layers, (picked pairs, rows held) summed over the layers)``."""
    mm = MATMULS[matmul]
    hidden, (index_loss, picked, rows) = hidden_and_stats(params, tokens, dims, matmul, remat)
    head = params["lm_head"]["kernel"].astype(jnp.float32)
    s = tokens.shape[0] - 1
    size = min(HEAD_BLOCK, s)
    n = -(-s // size)
    pad = n * size - s

    def piece(xtm):
        xc, tc, mc = xtm
        logp = jax.nn.log_softmax(mm(xc, head), axis=-1)
        gold = jnp.take_along_axis(logp, tc[:, None], axis=-1)[:, 0]
        return -jnp.sum(gold * mc)

    pieces = (jnp.pad(hidden[:-1], ((0, pad), (0, 0))).reshape(n, size, -1),
              jnp.pad(tokens[1:], (0, pad)).reshape(n, size),
              (jnp.arange(n * size) < s).astype(jnp.float32).reshape(n, size))
    ce = jnp.sum(jax.lax.map(jax.checkpoint(piece), pieces))
    return ce, jnp.sum(index_loss), (jnp.sum(picked), jnp.sum(rows))


def objective_sum(params: dict, tokens: jax.Array, dims: dict,
                  matmul: str = "float32", remat: bool = False):
    """Over the rows ``tokens [B, S]``: ``(sum of cross-entropy + (S - 1) *
    sum of the rows' index losses, (picked pairs, rows held))``:
    divided by ``B (S - 1)`` it is the mean cross-entropy plus the mean over
    rows of the layers' summed index losses."""
    ce, index_loss, stats = jax.lax.map(
        lambda row: row_objective(params, row, dims, matmul, remat), tokens)
    return (jnp.sum(ce) + (tokens.shape[1] - 1) * jnp.sum(index_loss),
            jax.tree.map(jnp.sum, stats))


class Grad:
    """Mean objective and its gradient over a batch, in blocks of rows. Each
    block's gradient is fetched to the host and summed there (float32), so
    that the device holds one gradient beside the two sets of weights and the
    optimizer's two moments the comparison keeps: six trees of 1.86 GB and
    the float32 activations of a 16,384-token row do not fit 16 GB together.
    The gradient comes back as a tree of numpy arrays; ``stats`` keeps the
    last call's picked pairs and rows held."""

    def __init__(self, dims: dict, matmul: str = "float32", rows: int = 1) -> None:
        self.rows = rows
        self.stats: dict[str, float] = {}
        self._fn = jax.jit(jax.value_and_grad(
            lambda p, t: objective_sum(p, t, dims, matmul, remat=True), has_aux=True))

    def __call__(self, params: dict, batch: np.ndarray):
        n_rows, seq = batch.shape
        if n_rows % self.rows:
            raise ValueError(f"{n_rows} rows do not split into {self.rows}s")
        total, grads, picked, held = 0.0, None, 0.0, 0.0
        for lo in range(0, n_rows, self.rows):
            (loss, (p, r)), g = self._fn(params, jnp.asarray(batch[lo:lo + self.rows]))
            total += float(loss)
            picked, held = picked + float(p), held + float(r)
            g = jax.device_get(g)
            grads = g if grads is None else jax.tree.map(np.add, grads, g)
        n = n_rows * (seq - 1)
        self.stats = {"picked_pairs": picked, "rows_held": held}
        return total / n, jax.tree.map(lambda g: g / np.float32(n), grads)


def leaf_norms(tree) -> dict[str, np.ndarray]:
    """L2 norm of every leaf; a leaf of the stack (weights stacked over
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
