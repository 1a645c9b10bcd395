"""Plain Granite-4.0-H (``granitemoehybrid``, dense: no experts), one pipeline
stage with a slice of the vocabulary: forward pass, loss, gradients and the
recipe's optimizer step.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``. No kernel, no cache, no chunks: the state-space
recurrence walks the positions one by one. It imports nothing of the program;
the optimizer's arithmetic, the stated-precision products and the leaf
comparison are ``reference/mpt.py``'s. The layer equations (HF
``GraniteMoeHybrid``, whose Mamba layer is Bamba's Mamba-2; ``h =
RMSNorm(x)``: float32, eps 1e-5, scale only):

- Embedding ``x0 = embedding_multiplier * E[token]`` (12). No positions
  anywhere (``position_embedding_type: nope``).
- Every layer: ``x = x + residual_multiplier * Mixer(RMSNorm(x))``, then
  ``x = x + residual_multiplier * MLP(RMSNorm(x))`` (0.22), ``MLP(h) = W_down
  (silu(W_gate h) * (W_up h))``; ``[W_gate; W_up]`` is the published
  ``input_linear`` of 2 x 8,192 rows.
- Attention mixer: ``q, k, v = W_q h, W_k h, W_v h`` (no bias, no rotation);
  causal ``softmax(attention_multiplier * q k^T) v`` (0.015625 = 1/64, not
  ``1/sqrt(64)``), key-value head ``j`` serving query heads ``4j .. 4j+3``;
  then ``W_o``.
- Mamba-2 mixer: ``[z | xBC | dt] = W_in h``; ``xBC = silu(conv(xBC))``, the
  convolution causal, depthwise, 4 taps with bias, as four shifted products:
  ``conv(u)_t = b + sum_k w_k u_(t-3+k)``; ``[x | B | C] = xBC``, ``x`` as
  heads of 64, ``B`` and ``C`` (128 wide) shared by all heads (one group);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` per head; per head and
  position ``H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T`` (64 x 128, ``H = 0``
  before each row's first position), ``y_t = H_t C_t + D x_t``;
  ``y = RMSNorm_w(y * silu(z))`` over all inner channels at once (one group,
  the gate before the norm); out ``W_out y``.
- Final RMSNorm; logits ``(x E^T) / logits_scaling`` (8), the head tied to
  the embedding, over the vocabulary slice.

Departures from the published modelling code, each because the program does
the same and the two must compute one function (``assumed`` in the
configuration file): ``time_step_limit`` is (0, inf), so ``dt`` is not
clamped; layers of one kind that follow each other are a ``lax.scan`` over
stacked weights (``blocks_0``, ``blocks_1``, ...: the runs of
``layer_types``). For memory alone: attention runs one head at a time; for
gradients each block is under ``jax.checkpoint`` and the walk over positions
is cut into segments whose start states are kept (what lies between is
walked again in the backward pass; the arithmetic is the sequential one);
and the gradient and the optimizer's two moments live in the host's memory
(:class:`HostTree`), where :func:`adopt_step` does their arithmetic in numpy
leaf by leaf, because the comparison keeps the seeded weights, the current
weights and the stepped weights on the device at once, and six float32 trees
of 772 M parameters (18.5 GB) are more than a 16 GB chip.
"""

from __future__ import annotations

import functools
import math
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import mpt as _mpt

INIT_STD = 0.02
#: positions between two kept states of the recurrence's walk (memory only)
SEGMENT = 128

# what every family's reference offers, unchanged from the dense family's;
# and one control of this family's own: every product exact, the recurrence's
# carried state rounded to bfloat16 at each position (what a scan that kept
# its state in bf16 would compute)
MATMULS = {**_mpt.MATMULS, "bf16_state": _mpt.MATMULS["float32"]}
seed_key = _mpt.seed_key
worst_leaf_gap = _mpt.worst_leaf_gap

_HIGHEST = jax.lax.Precision.HIGHEST


def dims_of(model: dict) -> dict:
    """The sizes this family needs, from a configuration file's ``model``."""
    d, h = int(model["d_model"]), int(model["n_heads"])
    return {
        "d_model": d,
        "n_layers": int(model["n_layers"]),
        "layer_types": str(model["layer_types"]),
        "n_heads": h,
        "n_kv_heads": int(model["n_kv_heads"]),
        "d_head": d // h,
        "mlp_hidden": int(model["mlp_hidden_size"]),
        "mamba_heads": int(model["mamba_n_heads"]),
        "mamba_d_head": int(model["mamba_d_head"]),
        "mamba_d_state": int(model["mamba_d_state"]),
        "mamba_d_conv": int(model["mamba_d_conv"]),
        "embedding_multiplier": float(model["embedding_multiplier"]),
        "residual_multiplier": float(model["residual_multiplier"]),
        "logits_scaling": float(model["logits_scaling"]),
        "attention_multiplier": float(model["attention_multiplier"]),
        "norm_eps": float(model["norm_eps"]),
        "max_seq_len": int(model["max_seq_len"]),
        "vocab_size": int(model["vocab_size"]),
    }


def layer_runs(dims: dict) -> list[tuple[str, int]]:
    """``layer_types`` as runs of equal kind: ``[(kind, length), ...]``; run
    ``i`` is the stack ``blocks_i``."""
    runs: list[tuple[str, int]] = []
    for kind in (k.strip() for k in dims["layer_types"].split(",")):
        if kind not in ("mamba", "attention"):
            raise ValueError(f"layer kind {kind!r} is neither 'mamba' nor 'attention'")
        if runs and runs[-1][0] == kind:
            runs[-1] = (kind, runs[-1][1] + 1)
        else:
            runs.append((kind, 1))
    if sum(n for _, n in runs) != dims["n_layers"]:
        raise ValueError("layer_types does not name n_layers layers")
    return runs


def make_params(dims: dict, seed, dtype=jnp.float32) -> dict:
    """Seeded weights in the program's tree layout. Matrices normal, std
    0.02, residual projections (``out_proj``, ``down_proj``) scaled by
    ``1/sqrt(2 L)``; norm scales 1; the Mamba-2 leaves as the public Mamba-2
    code starts them: ``A_log`` the log of a uniform in [1, 16], ``dt_bias``
    the inverse softplus of a log-uniform in [1e-3, 1e-1], ``D`` 1, the
    convolution (PyTorch's ``Conv1d`` default) uniform in ``+-1/sqrt(taps)``.
    ``seed`` is a whole number or a key from :func:`seed_key`."""
    d, L, v = dims["d_model"], dims["n_layers"], dims["vocab_size"]
    h, hkv, dh, f = dims["n_heads"], dims["n_kv_heads"], dims["d_head"], dims["mlp_hidden"]
    mh, n, taps = dims["mamba_heads"], dims["mamba_d_state"], dims["mamba_d_conv"]
    inner = mh * dims["mamba_d_head"]
    resid = INIT_STD / math.sqrt(2.0 * L)
    key = seed_key(seed) if isinstance(seed, (int, np.integer)) else seed
    keys = iter(jax.random.split(key, 64))

    def normal(shape, std=INIT_STD):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def mlp(n_):
        return {
            "ln_1": {"scale": jnp.ones((n_, d), dtype)},
            "ln_2": {"scale": jnp.ones((n_, d), dtype)},
            "gate_proj": {"kernel": normal((n_, d, f))},
            "up_proj": {"kernel": normal((n_, d, f))},
            "down_proj": {"kernel": normal((n_, f, d), resid)},
        }

    def attention(n_):
        return {
            **mlp(n_),
            "q_proj": {"kernel": normal((n_, d, h * dh))},
            "k_proj": {"kernel": normal((n_, d, hkv * dh))},
            "v_proj": {"kernel": normal((n_, d, hkv * dh))},
            "out_proj": {"kernel": normal((n_, h * dh, d), resid)},
        }

    def mamba(n_):
        step = jnp.exp(uniform((n_, mh), math.log(1e-3), math.log(1e-1)))
        bound = taps ** -0.5
        return {
            **mlp(n_),
            "in_proj": {"kernel": normal((n_, d, 2 * inner + 2 * n + mh))},
            "conv_kernel": uniform((n_, taps, inner + 2 * n), -bound, bound).astype(dtype),
            "conv_bias": uniform((n_, inner + 2 * n), -bound, bound).astype(dtype),
            "A_log": jnp.log(uniform((n_, mh), 1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "D": jnp.ones((n_, mh), jnp.float32),
            "mamba_norm": {"scale": jnp.ones((n_, inner), dtype)},
            "out_proj": {"kernel": normal((n_, inner, d), resid)},
        }

    params = {"wte": {"embedding": normal((v, d))},
              "ln_f": {"scale": jnp.ones((d,), dtype)}}
    for i, (kind, length) in enumerate(layer_runs(dims)):
        params[f"blocks_{i}"] = {"block": (mamba if kind == "mamba" else attention)(length)}
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _swiglu(h, p, mm):
    return mm(jax.nn.silu(mm(h, p["gate_proj"]["kernel"])) * mm(h, p["up_proj"]["kernel"]),
              p["down_proj"]["kernel"])


def causal_conv(u, kernel, bias):
    """``conv(u)_t = bias + sum_k kernel[k] u_(t - taps + 1 + k)`` on ``u [B,
    S, C]``, zeros before the row's start: one shifted product a tap."""
    taps, s = kernel.shape[0], u.shape[1]
    out = jnp.broadcast_to(bias, u.shape)
    for k in range(taps):
        shift = taps - 1 - k
        out = out + kernel[k] * jnp.pad(u, ((0, 0), (shift, 0), (0, 0)))[:, :s]
    return out


def recurrence(x, dt, a, b, c, d_skip, remat: bool = False, state_dtype=jnp.float32):
    """The state-space recurrence, position by position: ``x [B, S, H, P]``,
    ``dt [B, S, H]``, ``a [H]`` (negative), ``b``, ``c`` ``[B, S, N]``,
    ``d_skip [H]`` -> ``y [B, S, H, P]``. ``remat`` keeps the state every
    :data:`SEGMENT` positions for the backward pass instead of at each one;
    ``state_dtype`` other than float32 is the ``bf16_state`` control."""
    bsz, s, h, p = x.shape

    def position(state, inputs):
        x_t, dt_t, b_t, c_t = inputs  # [B, H, P], [B, H], [B, N], [B, N]
        grow = jnp.einsum("bhp,bn->bhpn", dt_t[..., None] * x_t, b_t, precision=_HIGHEST)
        state = jnp.exp(dt_t * a)[..., None, None] * state + grow
        state = state.astype(state_dtype).astype(jnp.float32)
        y_t = jnp.einsum("bhpn,bn->bhp", state, c_t, precision=_HIGHEST)
        return state, y_t + d_skip[:, None] * x_t

    by_position = [jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)]
    state0 = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    seg = math.gcd(s, SEGMENT)
    if not remat or seg == s:
        _, y = jax.lax.scan(position, state0, by_position)
        return jnp.moveaxis(y, 0, 1)

    @jax.checkpoint
    def segment(state, inputs):
        return jax.lax.scan(position, state, inputs)

    _, y = jax.lax.scan(
        segment, state0, [t.reshape(s // seg, seg, *t.shape[1:]) for t in by_position])
    return jnp.moveaxis(y.reshape(s, *y.shape[2:]), 0, 1)


def mamba_mixer(h, p, dims, mm, remat: bool = False, state_dtype=jnp.float32):
    """``Mixer(h)`` of a Mamba-2 layer with weights ``p``."""
    bsz, s, _ = h.shape
    heads, n = dims["mamba_heads"], dims["mamba_d_state"]
    inner = heads * dims["mamba_d_head"]
    zxbcdt = mm(h, p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * n], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, p["conv_kernel"], p["conv_bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + n], axis=-1)
    y = recurrence(x.reshape(bsz, s, heads, -1), jax.nn.softplus(dt + p["dt_bias"]),
                   -jnp.exp(p["A_log"]), b, c, p["D"], remat, state_dtype)
    y = _rms_norm(y.reshape(bsz, s, inner) * jax.nn.silu(z), p["mamba_norm"]["scale"],
                  dims["norm_eps"])
    return mm(y, p["out_proj"]["kernel"])


def attention_mixer(h, p, dims, mm):
    """``Mixer(h)`` of an attention layer with weights ``p``: no positions,
    the published scale, one key-value head for each group of query heads."""
    bsz, s, _ = h.shape
    heads, kv, dh = dims["n_heads"], dims["n_kv_heads"], dims["d_head"]
    q = mm(h, p["q_proj"]["kernel"]).reshape(bsz, s, heads, dh)
    k = mm(h, p["k_proj"]["kernel"]).reshape(bsz, s, kv, dh)
    v = mm(h, p["v_proj"]["kernel"]).reshape(bsz, s, kv, dh)
    k, v = (jnp.repeat(t, heads // kv, axis=2) for t in (k, v))
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(qkv):
        qh, kh, vh = qkv  # [B, S, d_head]
        scores = mm(qh, kh.transpose(0, 2, 1)) * dims["attention_multiplier"]
        scores = jnp.where(causal, scores, -jnp.inf)
        return mm(jax.nn.softmax(scores, axis=-1), vh)

    by_head = lambda a: a.transpose(2, 0, 1, 3)  # noqa: E731
    out = jax.lax.map(jax.checkpoint(one_head), (by_head(q), by_head(k), by_head(v)))
    return mm(out.transpose(1, 2, 0, 3).reshape(bsz, s, heads * dh), p["out_proj"]["kernel"])


def block(x, p, kind: str, dims, mm, remat: bool = False, state_dtype=jnp.float32):
    """One layer: the mixer's residual branch, then the MLP's."""
    r, eps = dims["residual_multiplier"], dims["norm_eps"]
    h = _rms_norm(x, p["ln_1"]["scale"], eps)
    mixed = (mamba_mixer(h, p, dims, mm, remat, state_dtype) if kind == "mamba"
             else attention_mixer(h, p, dims, mm))
    x = x + r * mixed
    return x + r * _swiglu(_rms_norm(x, p["ln_2"]["scale"], eps), p, mm)


def forward(params: dict, tokens: jax.Array, dims: dict,
            matmul: str = "float32", remat: bool = False) -> jax.Array:
    """``tokens [B, S] int32`` -> ``logits [B, S, vocab] float32``."""
    mm = MATMULS[matmul]
    state_dtype = jnp.bfloat16 if matmul == "bf16_state" else jnp.float32
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = dims["embedding_multiplier"] * p32["wte"]["embedding"][tokens]
    for i, (kind, _) in enumerate(layer_runs(dims)):
        def body(x, layer, kind=kind):
            return block(x, layer, kind, dims, mm, remat, state_dtype), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, p32[f"blocks_{i}"]["block"])
    x = _rms_norm(x, p32["ln_f"]["scale"], dims["norm_eps"])
    return mm(x, p32["wte"]["embedding"].T) / dims["logits_scaling"]


def ce_sum(params: dict, tokens: jax.Array, dims: dict,
           matmul: str = "float32", remat: bool = False) -> jax.Array:
    """Summed next-token cross entropy over ``tokens [B, S]``."""
    logits = forward(params, tokens, dims, matmul, remat)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.sum(gold)


# ---------------------------------------------------------------------------
# training: the gradient of a batch and the recipe's optimizer, with the
# gradient and the optimizer's two moments in the host's memory
# ---------------------------------------------------------------------------


@jax.tree_util.register_static
class HostTree:
    """A tree of float32 numpy arrays in the host's memory that is a NODE of
    the trees it travels in and never a leaf: handed to a jitted function it
    costs the device nothing. ``factor`` scales every array where it is read
    (a clipped gradient is its arrays and a factor, not a copy)."""

    def __init__(self, treedef, leaves: list, factor: float = 1.0) -> None:
        self.treedef, self.leaves, self.factor = treedef, leaves, factor

    @classmethod
    def zeros_like(cls, tree) -> "HostTree":
        leaves, treedef = jax.tree.flatten(tree)
        return cls(treedef, [np.zeros(a.shape, np.float32) for a in leaves])

    @classmethod
    def fetched(cls, tree) -> "HostTree":
        """``tree``'s arrays copied off the device."""
        leaves, treedef = jax.tree.flatten(tree)
        return cls(treedef, [np.asarray(a, np.float32) for a in jax.device_get(leaves)])

    def tree(self):
        return jax.tree.unflatten(self.treedef, self.leaves)


class Grad:
    """Mean loss and its gradient over a batch, in blocks of rows whose
    gradients are summed on the device; the sum leaves it as a
    :class:`HostTree`."""

    def __init__(self, dims: dict, matmul: str = "float32", rows: int = 1) -> None:
        self.rows = rows
        self._fn = jax.jit(jax.value_and_grad(
            lambda p, t: ce_sum(p, t, dims, matmul, remat=True)))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=(0,))
        self._scale = jax.jit(lambda g, n: jax.tree.map(lambda a: a / n, g),
                              donate_argnums=(0,))

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
        return total / n, HostTree.fetched(self._scale(grads, jnp.float32(n)))


def clip_by_global_norm(grads: HostTree, max_norm: float) -> HostTree:
    """The gradient clipped by its global norm: the same arrays, a factor."""
    norm = grads.factor * math.sqrt(sum(
        float(np.dot(a.ravel(), a.ravel())) for a in grads.leaves))
    factor = 1.0 if norm < max_norm else max_norm / norm
    return HostTree(grads.treedef, grads.leaves, grads.factor * factor)


@jax.tree_util.register_static
class HostMoments:
    """ADOPT's ``m`` and ``v`` (Taniguchi et al. 2024), a pair of numpy arrays
    a leaf of the weights, and the dense family's update rule on them, leaf
    by leaf, in float32. jit's caches keep every static node they have seen
    (a callback's closure, a tree's structure), so arrays are let go by hand:
    a leaf's gradient once it is used, the moments when the next state is
    made (one optimizer state lives at a time)."""

    _newest = None  # a weak reference to the instance that still has its arrays

    def __init__(self, params) -> None:
        last = HostMoments._newest and HostMoments._newest()
        if last is not None:
            last.m = last.v = None
        HostMoments._newest = weakref.ref(self)
        self.m, self.v = (HostTree.zeros_like(params).leaves for _ in range(2))

    def step(self, k: int, grads: HostTree, opt: dict, count) -> np.ndarray:
        """Leaf ``k``'s moments moved by its gradient; returns the new ``m``
        (the first call only sets ``v = g**2``)."""
        b1, b2 = opt["betas"]
        m, v = self.m[k], self.v[k]
        g = grads.leaves[k] * np.float32(grads.factor)
        grads.leaves[k] = None  # used: a step's gradient is stepped with once
        if int(count) == 0:
            np.multiply(g, g, out=v)
            return m
        bound = np.float32(max(int(count), 1)) ** np.float32(0.25)
        normed = np.sqrt(v)
        np.maximum(normed, np.float32(opt["eps"]), out=normed)
        np.divide(g, normed, out=normed)
        np.clip(normed, -bound, bound, out=normed)
        m *= b1
        normed *= 1 - b1
        m += normed
        v *= b2
        np.multiply(g, g, out=g)
        g *= 1 - b2
        v += g
        return m


def adopt_init(params):
    """ADOPT's state for ``params``: a count, and two zero trees on the host."""
    return {"count": jnp.zeros([], jnp.int32), "moments": HostMoments(params)}


def adopt_step(params, state, grads: HostTree, opt: dict):
    """ADOPT after clipping the gradient by its global norm, as the dense
    family's ``adopt_step`` has it, with the moments' arithmetic on the host:
    one leaf at a time and in order, the leaf's new ``m`` comes to the device
    and the leaf takes its step (``count``, the learning rate and the weights
    are the device's)."""
    if opt["name"] != "adopt":
        raise ValueError(f"the plain optimizer is ADOPT, not {opt['name']!r}")
    from jax.experimental import io_callback

    g = clip_by_global_norm(grads, opt["grad_clip_norm"])
    count, moments = state["count"], state["moments"]
    scale = jnp.where(count == 0, 0.0, _mpt.lr_at(count, opt))
    leaves, tree = jax.tree.flatten(params)
    stepped = []
    for k, p in enumerate(leaves):
        m = io_callback(functools.partial(moments.step, k, g, opt),
                        jax.ShapeDtypeStruct(p.shape, jnp.float32), count, ordered=True)
        stepped.append(p - scale * m)
    return jax.tree.unflatten(tree, stepped), {"count": count + 1, "moments": moments}


def leaf_norms(tree) -> dict[str, np.ndarray]:
    """L2 norm of every leaf; a leaf of a stack (``blocks_i``: weights
    stacked over the run's layers) gives one norm per layer. ``tree`` is a
    tree of arrays or a :class:`HostTree`."""
    factor = 1.0
    if isinstance(tree, HostTree):
        tree, factor = tree.tree(), tree.factor
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        on_host = isinstance(leaf, np.ndarray)
        xp = np if on_host else jnp
        x = leaf if on_host else jnp.asarray(leaf, jnp.float32)
        rows = x.reshape(x.shape[0], -1) if name.startswith("blocks_") else x.reshape(1, -1)
        out[name] = xp.sqrt(xp.sum(xp.square(rows), axis=1, dtype=xp.float32)) * factor
    return {k: np.asarray(v, np.float64) for k, v in jax.device_get(out).items()}
