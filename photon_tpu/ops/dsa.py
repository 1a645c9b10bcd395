"""Learned sparse attention: an indexer picks the keys each query attends to.

The training form of DeepSeek Sparse Attention as Keye-VL-2.0's ``sa_config``
sizes it (preset ``keye-vl-2.0-30b-a3b-ep8``). For one row of ``S`` positions,
``t`` a query and ``s <= t`` a key:

- :func:`index_scores` — ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``
  over the indexer's heads ``j``: the products in the operands' dtype with
  float32 accumulation, ``relu`` and the head-weighted sum in float32.
- :func:`select_keys` — ``tau_t`` = the ``topk``-th largest of ``I[t, :t+1]``
  (``-inf`` while ``t < topk``); the query sees ``{s <= t : I[t, s] >=
  tau_t}``, ties at the threshold all kept. Exact: the threshold comes from a
  search over the float's bits (:func:`kth_largest`, or the same search from
  VMEM: below), not from an approximate top-k. The result is an int8 mask by
  (query, key) for all heads, which ``ops/masked_flash_attention.py`` takes;
  no gradient flows through it.
- :func:`index_loss` — ``L_I = (1/S) sum_t KL(pbar[t, .] || softmax_{S_t}(I[t,
  .]))`` where ``pbar`` is the attention's own probabilities over the picked
  keys, averaged over the heads and detached. Its gradient reaches the
  indexer's three inputs only, and is formed inside the forward's chunk loop
  (``d L_I / d I = (softmax_{S_t}(I) * sum(pbar) - pbar) / S`` on the picked
  keys, pulled back through the chunk's scores): nothing of ``[S, S]`` is
  kept for the backward, which only scales.

Everything walks the queries in chunks of ``chunk`` (``sa_config``'s
``q_chunk_size``): a chunk's scores against all keys are ``[chunk, S]``
float32, the indexer heads' products ``[chunk, heads, S]``; nothing of
``[heads, S, S]`` is ever whole. The chunks go in ``_BANDS`` bands, each
against the keys up to its own last query only. The chunk loops are ``lax.scan``s
of ``jax.numpy`` but for two lines, each a launch a chunk where the attention
runs its Pallas kernel (``attn_impl: pallas``: :func:`uses_kernel`):

- the index loss takes ``pbar`` from ``ops/index_pbar.py``, which keeps
  every head's ``[chunk, block_k]`` score tile in VMEM and writes ``[chunk,
  S]`` once; the ``xla`` path forms the heads' ``[H, chunk, S]`` float32
  scores in HBM;
- the selection takes its thresholds from ``ops/index_select.py``, which
  holds a block of queries' ``[rows, n_keys]`` scores in VMEM and searches
  them there a bit a pass; the ``xla`` path is :func:`kth_largest`'s eight
  fused passes over the chunk in HBM, 15 compares an element each. The same
  32 bits either way (:func:`selects_in_vmem` says which, by the shape too).

The ``xla`` lines are what the CPU runs and what the tests hold the launches
to. The index scores are ``jax.numpy`` on both paths; PERF.md section 7 has
what forming them inside a launch would save.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from photon_tpu.ops import index_select
from photon_tpu.ops.index_pbar import head_mean_probabilities, key_block, live_key_tiles

#: bits of the threshold search decided a pass: 15 counts over the chunk in
#: one fused pass, 8 passes for a float32
_RADIX_BITS = 4
#: the chunk loops run in this many bands of queries, each against the keys
#: up to its own end only (shapes are static inside a loop): 4 bands do 10/16
#: of the square where one does all of it and the causal half is 8.5/16
_BANDS = 4


def index_scores(q_idx: jax.Array, k_idx: jax.Array, w: jax.Array) -> jax.Array:
    """``q_idx [C, J, Di]``, ``k_idx [S, Di]``, ``w [C, J]`` float32 ->
    ``I [C, S]`` float32."""
    dots = jnp.einsum("cjd,sd->cjs", q_idx, k_idx, preferred_element_type=jnp.float32)
    # an elementwise product and a sum, not a contraction: the MXU's default
    # precision would round a float32 operand to bfloat16
    return jnp.sum(jax.nn.relu(dots) * w.astype(jnp.float32)[:, :, None], axis=1)


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order."""
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def _from_ordered_bits(key: jax.Array) -> jax.Array:
    u = jnp.where(key >> 31 == 1, key & jnp.uint32(0x7FFFFFFF), ~key)
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def kth_largest(x: jax.Array, k: int) -> jax.Array:
    """The ``k``-th largest entry of every row of ``x [C, S]`` float32 (no
    NaN; ``-inf`` entries count as smallest), exactly: the largest value
    ``v`` with ``count(x >= v) >= k``, found ``_RADIX_BITS`` bits a pass from
    the top of the float's order-preserving bit pattern."""
    key = _ordered_bits(x)
    found = jnp.zeros(x.shape[:1], jnp.uint32)
    digits = jnp.arange(1, 2 ** _RADIX_BITS, dtype=jnp.uint32)
    for shift in range(32 - _RADIX_BITS, -1, -_RADIX_BITS):
        candidates = found[:, None] | (digits << shift)[None, :]  # [C, 15]
        counts = jnp.sum(key[:, None, :] >= candidates[:, :, None], axis=-1,
                         dtype=jnp.int32)
        # the counts fall with the digit: as many digits pass as the largest
        digit = jnp.sum(counts >= k, axis=-1).astype(jnp.uint32)
        found = found | (digit << shift)
    return _from_ordered_bits(found)


def _chunked(x: jax.Array, chunk: int) -> jax.Array:
    return x.reshape(x.shape[0] // chunk, chunk, *x.shape[1:])


def _bands(n_chunks: int) -> list[tuple[int, int]]:
    """``[(first chunk, end chunk), ...]``: the chunks in ``_BANDS`` equal
    runs, or in one where they do not divide."""
    n = _BANDS if n_chunks % _BANDS == 0 else 1
    return [(b * n_chunks // n, (b + 1) * n_chunks // n) for b in range(n)]


def _row_select(q_idx, k_idx, w, topk: int, chunk: int, search) -> jax.Array:
    """One row's mask ``[S, S]`` int8; ``search(scores, topk)`` is a chunk's
    thresholds (:func:`kth_largest` or its launch)."""
    s = k_idx.shape[0]
    q_chunks, w_chunks = _chunked(q_idx, chunk), _chunked(w, chunk)
    masks = []
    for lo, hi in _bands(s // chunk):
        n_keys = hi * chunk  # no query of the band sees a later key
        keys = jnp.arange(n_keys, dtype=jnp.int32)

        def one_chunk(_, qwc, keys=keys, n_keys=n_keys):
            qc, wc, c = qwc
            t = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
            causal = keys[None, :] <= t[:, None]
            # + 0.0: a negative zero becomes the positive one, as the
            # comparison below reads both
            scores = jnp.where(causal, index_scores(qc, k_idx[:n_keys], wc) + 0.0, -jnp.inf)
            tau = jnp.where(t < topk, -jnp.inf, search(scores, topk))
            return None, (causal & (scores >= tau[:, None])).astype(jnp.int8)

        _, mask = jax.lax.scan(
            one_chunk, None,
            (q_chunks[lo:hi], w_chunks[lo:hi], jnp.arange(lo, hi, dtype=jnp.int32)))
        masks.append(jnp.pad(mask, ((0, 0), (0, 0), (0, s - n_keys))))
    return jnp.concatenate(masks).reshape(s, s)


def select_keys(q_idx: jax.Array, k_idx: jax.Array, w: jax.Array, *, topk: int,
                chunk: int, impl: str = "xla", interpret: bool = False) -> jax.Array:
    """The keys every query attends to: ``q_idx [B, S, J, Di]``, ``k_idx [B,
    S, Di]``, ``w [B, S, J]`` -> ``mask [B, S, S]`` int8 (1 = picked), causal
    by construction. Takes no gradient.

    ``impl`` / ``interpret`` are the attention's own, as :func:`index_loss`
    takes them, and choose where a chunk's thresholds are searched
    (:func:`selects_in_vmem`): the mask is the same on every entry."""
    q_idx, k_idx, w = jax.lax.stop_gradient((q_idx, k_idx, w))
    s = q_idx.shape[1]
    chunk = min(chunk, s)
    search = kth_largest
    if selects_in_vmem(impl, interpret, s, chunk, topk, q_idx):
        search = functools.partial(index_select.kth_largest, interpret=bool(interpret))
    return jax.lax.map(
        lambda a: _row_select(*a, topk=topk, chunk=chunk, search=search), (q_idx, k_idx, w))


# ---------------------------------------------------------------------------
# The indexer's alignment loss, with its gradient from the forward's loop
# ---------------------------------------------------------------------------


def uses_kernel(impl: str, interpret: bool = False, x: jax.Array | None = None) -> bool:
    """Whether the chunk loops take their Pallas launches (the index loss's
    ``pbar``; the selection's thresholds where :func:`selects_in_vmem` admits
    the shape), by ``masked_multihead_attention``'s rule: ``pallas`` is the
    kernel on a TPU (or anywhere under ``interpret``) and steps down on the
    CPU backend."""
    # looked up at the call: the offline compile check swaps the function
    from photon_tpu.ops import flash_attention

    if impl not in ("pallas", "xla"):
        raise ValueError(f"the index loss has no impl {impl!r}")
    return impl == "pallas" and (interpret or flash_attention.pallas_supported(x))


def selects_in_vmem(impl: str, interpret: bool, s: int, chunk: int, topk: int,
                    x: jax.Array | None = None) -> bool:
    """Whether the thresholds of rows of ``s`` positions come from
    ``ops/index_select.py``'s launch, one a chunk: where a kernel can run
    (:func:`uses_kernel`) and every band's ``[chunk, n_keys]`` tile has a
    row block there (whole lanes of keys, whole sublanes of queries, within
    the VMEM budget) and ``topk`` keys to rank. :func:`kth_largest`'s
    ``jax.numpy`` search everywhere else."""
    chunk = min(chunk, s)
    return uses_kernel(impl, interpret, x) and all(
        topk <= hi * chunk and index_select.row_block(chunk, hi * chunk) > 0
        for _, hi in _bands(s // chunk))


def _grouped(q, k, lse, chunk: int):
    """A key-value group's heads side by side, one plain product a group:
    ``q [S, H, D]`` -> ``[chunks, G, H/G * chunk, D]``, ``lse [H, S]`` ->
    ``[chunks, G, H/G * chunk]`` in the same row order, ``k [S, G, D]`` ->
    ``[G, S, D]``."""
    s, heads, d = q.shape
    groups = k.shape[1]
    per = heads // groups
    qg = q.reshape(s // chunk, chunk, groups, per, d).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(s // chunk, groups, per * chunk, d)
    lse_g = lse.reshape(groups, per, s // chunk, chunk).transpose(2, 0, 1, 3)
    return qg, k.transpose(1, 0, 2), lse_g.reshape(s // chunk, groups, per * chunk)


def _pbar_xla(qc, kg, lc, picked, scale: float):
    """The ``xla`` path's ``pbar [chunk, n_keys]`` of one chunk: all its
    heads' scores ``[H, chunk, n_keys]`` float32 at once."""
    chunk, n_keys = picked.shape
    dots = jnp.einsum("gmd,gsd->gms", qc, kg[:, :n_keys], preferred_element_type=jnp.float32)
    probs = jnp.exp(dots * scale - lc[..., None]).reshape(-1, chunk, n_keys)
    return jnp.where(picked, jnp.sum(probs, axis=0) / probs.shape[0], 0.0)


def _row_index_loss(q_idx, k_idx, w, q, k, lse, mask, scale: float, chunk: int,
                    with_grads: bool, kernel: bool, interpret: bool):
    """One row: ``(sum over queries of the KL, gradients by q_idx, k_idx, w
    at unit cotangent or None)``. ``q [S, H, D]``, ``k [S, G, D]``, ``lse [H,
    S]``, ``mask [S, S]``."""
    s = q.shape[0]
    qg, kg, lse_g = _grouped(q, k, lse, chunk)

    def one_chunk(carry, xs, n_keys):
        total, dk_idx = carry
        qic, wc, qc, lc, mc, c = xs
        picked = mc[:, :n_keys] != 0
        # pbar: every head's probabilities over the picked keys, from the
        # attention's own log-sum-exp, averaged over the heads
        if kernel:
            pbar = head_mean_probabilities(qc, kg, lc, mc, c, scale=scale, n_keys=n_keys,
                                           interpret=interpret)
        else:
            pbar = _pbar_xla(qc, kg, lc, picked, scale)
        if with_grads:
            scores, pull = jax.vjp(index_scores, qic, k_idx[:n_keys], wc)
        else:
            scores = index_scores(qic, k_idx[:n_keys], wc)
        logits = jnp.where(picked, scores, -jnp.inf)
        top = jnp.max(logits, axis=-1, keepdims=True)
        top = jnp.where(jnp.isfinite(top), top, 0.0)
        e = jnp.where(picked, jnp.exp(logits - top), 0.0)
        z = jnp.sum(e, axis=-1, keepdims=True)
        log_soft = logits - top - jnp.log(jnp.where(z == 0.0, 1.0, z))
        live = picked & (pbar > 0.0)
        kl = jnp.where(live, pbar * (jnp.log(jnp.where(live, pbar, 1.0))
                                     - jnp.where(live, log_soft, 0.0)), 0.0)
        total = total + jnp.sum(kl)
        if not with_grads:
            return (total, dk_idx), None
        soft = e / jnp.where(z == 0.0, 1.0, z)
        d_scores = soft * jnp.sum(pbar, axis=-1, keepdims=True) - pbar
        dqic, dkc, dwc = pull(d_scores)
        return (total, dk_idx + dkc.astype(jnp.float32)), (dqic, dwc)

    xs = (_chunked(q_idx, chunk), _chunked(w, chunk), qg, lse_g, _chunked(mask, chunk),
          jnp.arange(s // chunk, dtype=jnp.int32))
    total = jnp.zeros([], jnp.float32)
    dk_idx = jnp.zeros(k_idx.shape, jnp.float32) if with_grads else None
    grads = []
    for lo, hi in _bands(s // chunk):
        n_keys = hi * chunk  # no query of the band sees a later key
        carry = (total, dk_idx[:n_keys] if with_grads else None)
        (total, dk_band), band_grads = jax.lax.scan(
            functools.partial(one_chunk, n_keys=n_keys), carry,
            jax.tree.map(lambda x: x[lo:hi], xs))
        if with_grads:
            dk_idx = dk_idx.at[:n_keys].set(dk_band)
            grads.append(band_grads)
    if not with_grads:
        return total, None
    dq_idx = jnp.concatenate([g[0] for g in grads])
    dw = jnp.concatenate([g[1] for g in grads])
    return total, (dq_idx.reshape(q_idx.shape), dk_idx.astype(k_idx.dtype),
                   dw.reshape(w.shape))


def _index_loss_loop(q_idx, k_idx, w, q, k, lse, mask, scale, chunk, kernel, interpret,
                     with_grads):
    chunk = min(chunk, q.shape[1])
    totals, grads = jax.lax.map(
        lambda a: _row_index_loss(*a, scale=scale, chunk=chunk, with_grads=with_grads,
                                  kernel=kernel, interpret=interpret),
        (q_idx, k_idx, w, q, k, lse, mask))
    n = q.shape[0] * q.shape[1]
    loss = jnp.sum(totals) / n
    if not with_grads:
        return loss, None
    return loss, jax.tree.map(lambda g: (g.astype(jnp.float32) / n).astype(g.dtype), grads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _index_loss(q_idx, k_idx, w, q, k, lse, mask, scale, chunk, kernel, interpret):
    return _index_loss_loop(q_idx, k_idx, w, q, k, lse, mask, scale, chunk, kernel,
                            interpret, False)[0]


def _index_loss_fwd(q_idx, k_idx, w, q, k, lse, mask, scale, chunk, kernel, interpret):
    return _index_loss_loop(q_idx, k_idx, w, q, k, lse, mask, scale, chunk, kernel,
                            interpret, True)


def _index_loss_bwd(scale, chunk, kernel, interpret, grads, g):
    scaled = [(g * x.astype(jnp.float32)).astype(x.dtype) for x in grads]
    # the attention's q, k and log-sum-exp and the mask are detached
    return (*scaled, None, None, None, None)


_index_loss.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(q_idx: jax.Array, k_idx: jax.Array, w: jax.Array, q: jax.Array,
               k: jax.Array, lse: jax.Array, mask: jax.Array, *, chunk: int,
               scale: float | None = None, impl: str = "xla",
               interpret: bool = False) -> jax.Array:
    """The indexer's alignment loss, mean over the batch's queries.

    ``q_idx [B, S, J, Di]``, ``k_idx [B, S, Di]``, ``w [B, S, J]`` the
    indexer's rotated queries, its one key head and its head weights (these
    take the gradient); ``q [B, S, H, D]``, ``k [B, S, G, D]`` the attention's
    own rotated queries and keys, ``lse [B, H, S]`` its log-sum-exp over the
    picked keys (``masked_flash_attention``'s second result) and ``mask [B, S,
    S]`` the selection: all four detached here.

    ``impl`` / ``interpret`` are the attention's own (``cfg.attn_impl``,
    ``cfg.attn_interpret``) and choose how a chunk's ``pbar`` is made, the
    one line the two paths do not share: ``pallas`` is one launch a chunk
    (``ops/index_pbar.py``: the heads' score tiles stay in VMEM, ``[chunk,
    S]`` float32 is written once) on a TPU or in the interpreter, and steps
    down to ``xla`` on the CPU backend; ``xla`` is an ``einsum``, an
    exponential and a sum over ``[H, chunk, S]`` float32 in HBM, the oracle
    of the kernel's tests. The index scores, the KL and the gradient are
    ``jax.numpy`` on both."""
    q, k, lse = jax.lax.stop_gradient((q, k, lse))
    scale = 1.0 / (q.shape[-1] ** 0.5) if scale is None else float(scale)
    return _index_loss(q_idx, k_idx, w, q, k, lse, mask, scale, int(chunk),
                       uses_kernel(impl, interpret, q), bool(interpret))


def index_loss_tiles(s: int, chunk: int) -> tuple[int, int]:
    """``(computed, skipped)`` key tiles of the ``pbar`` launches of one row
    of ``s`` positions, one pass: a chunk's launch walks its band's tiles and
    computes those that hold a key one of its queries may see."""
    chunk = min(chunk, s)
    computed = grid = 0
    for lo, hi in _bands(s // chunk):
        block_k = key_block(hi * chunk)
        grid += (hi - lo) * (hi * chunk // block_k)
        computed += sum(live_key_tiles(c, chunk, block_k) for c in range(lo, hi))
    return computed, grid - computed
