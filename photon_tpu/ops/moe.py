"""Mixture-of-Experts routing: GShard/Switch-style dense dispatch with a
static capacity — the TPU-native MoE formulation (einsums over one-hot
dispatch/combine tensors; every shape static, so XLA tiles the expert
matmuls onto the MXU and inserts the expert-axis all_to_alls itself).

The reference has no MoE anywhere (its models are dense MPT/llama
variants); expert parallelism is part of this framework's
beyond-the-reference scale-out surface, alongside ring attention
(sequence) and the pipeline schedule (pipe).

Design notes:
- **Dense dispatch, not gather/scatter**: token→expert routing is encoded
  as a ``[N, E, C]`` one-hot dispatch tensor and contracted with einsums.
  O(N·E·C) memory, but static shapes and pure matmuls — the standard TPU
  trade (mesh-tensorflow / GShard / Switch lineage) against the GPU-style
  dynamic gather which XLA cannot tile.
- **Static capacity**: each expert processes at most
  ``C = ceil(k·N/E · capacity_factor)`` tokens; overflow tokens fall
  through the residual connection (their combine weights are zero).
  Slot-0 (highest-gate) assignments claim capacity before slot-1, so
  top-1 routing degrades gracefully under overflow.
- **Switch aux loss** (load balance): ``E · Σ_e f_e · P_e`` where ``f_e``
  is the fraction of tokens whose top-1 choice is ``e`` and ``P_e`` the
  mean router probability — differentiable through ``P_e`` only, pushing
  probability mass toward underloaded experts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# The dropless layer's stages as ``jax.named_scope``s (they reach every
# operation's ``op_name``, forward, transpose and recomputation alike; the
# benchmark's ``moe_*`` readers sum device time by them).
ROUTER_SCOPE = "moe/router"
#: sort by expert, permute, un-permute (from the live rows: PR 43), combine
DISPATCH_SCOPE = "moe/dispatch"
#: the grouped products of the experts held here and the activation between
EXPERTS_SCOPE = "moe/experts"
SHARED_EXPERT_SCOPE = "moe/shared_expert"


def expert_capacity(n_tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Static per-expert slot count (≥1)."""
    return max(1, int(-(-top_k * n_tokens * capacity_factor // n_experts)))


def route(probs: jax.Array, top_k: int, capacity: int,
          token_mask: jax.Array | None = None):
    """Build dispatch/combine tensors from router probabilities.

    Args:
      probs: ``[N, E]`` softmax router probabilities (fp32).
      top_k: experts per token.
      capacity: static per-expert slot count.
      token_mask: optional ``[N]`` {0,1} validity mask — masked (padding)
        tokens claim NO capacity slots and are excluded from the aux-loss
        statistics (prefill over right-padded prompts would otherwise let
        padding displace real tokens from expert buffers).

    Returns:
      ``(dispatch, combine, aux)`` where ``dispatch`` is ``[N, E, C]``
      {0,1}, ``combine`` is ``[N, E, C]`` gate weights (renormalized over
      the token's kept experts), and ``aux`` is the Switch load-balance
      loss for this routing decision.
    """
    n, e = probs.shape
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # [N, k]
    # one-hot expert choice per slot: [k, N, E]
    oh = jax.nn.one_hot(jnp.swapaxes(gate_idx, 0, 1), e, dtype=probs.dtype)
    if token_mask is not None:
        oh = oh * token_mask.astype(probs.dtype)[None, :, None]
    # positions within each expert's buffer, slot-major (slot 0 first):
    # cumsum over the flattened (k·N) assignment order
    flat = oh.reshape(top_k * n, e)
    pos = (jnp.cumsum(flat, axis=0) - flat).reshape(top_k, n, e)
    pos = pos.astype(jnp.int32)  # one_hot wants integer positions
    keep = oh * (pos < capacity)
    # gates renormalized over KEPT slots only (a dropped expert's weight
    # is redistributed; fully-dropped tokens pass through the residual)
    kept_gate = gate_vals * jnp.swapaxes(keep.sum(-1), 0, 1)  # [N, k]
    denom = jnp.maximum(kept_gate.sum(-1, keepdims=True), 1e-9)
    gates = kept_gate / denom
    # dispatch[n,e,c] = Σ_k keep[k,n,e] · 1[pos[k,n,e] == c]
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=probs.dtype)  # [k,N,E,C]
    dispatch = jnp.einsum("kne,knec->nec", keep, pos_oh)
    combine = jnp.einsum("kn,kne,knec->nec",
                         jnp.swapaxes(gates, 0, 1), keep, pos_oh)
    # Switch aux loss on the top-1 choice (over VALID tokens only)
    top1 = oh[0]  # [N, E] (already zeroed for masked tokens)
    if token_mask is None:
        n_valid = jnp.asarray(n, probs.dtype)
        p_sum = jnp.sum(probs, axis=0)
    else:
        m = token_mask.astype(probs.dtype)
        n_valid = jnp.maximum(jnp.sum(m), 1.0)
        p_sum = jnp.sum(probs * m[:, None], axis=0)
    f = jnp.sum(top1, axis=0) / n_valid  # fraction routed (not differentiable)
    p = p_sum / n_valid                  # mean router prob (differentiable)
    aux = e * jnp.sum(f * p)
    return dispatch, combine, aux


def moe_mlp(x: jax.Array, router_w: jax.Array, w_up: jax.Array,
            w_down: jax.Array, *, top_k: int, capacity_factor: float,
            w_gate: jax.Array | None = None,
            token_mask: jax.Array | None = None):
    """Expert-parallel MLP over ``[B, S, D]`` activations.

    ``router_w``: ``[D, E]``; ``w_up``: ``[E, D, H]``; ``w_down``:
    ``[E, H, D]`` — shard the leading ``E`` over the ``expert`` mesh axis
    and XLA turns the dispatch/return einsums into all_to_alls over ICI.
    Returns ``(out [..., D], aux_loss scalar)``. Any number of leading
    dims (the KV-cache decode path routes single-token ``[B, D]`` steps
    through the same function).
    """
    lead, d = x.shape[:-1], x.shape[-1]
    n = int(np.prod(lead))
    e = router_w.shape[-1]
    xf = x.reshape(n, d)
    logits = jnp.asarray(xf, jnp.float32) @ jnp.asarray(router_w, jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    cap = expert_capacity(n, e, top_k, capacity_factor)
    mask_flat = None if token_mask is None else token_mask.reshape(n)
    dispatch, combine, aux = route(probs, top_k, cap, token_mask=mask_flat)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch, xf)
    up = jnp.einsum("ecd,edh->ech", expert_in, w_up.astype(x.dtype))
    if w_gate is not None:
        # SwiGLU experts (Mixtral layout: w1=gate, w3=up, w2=down)
        gate = jnp.einsum("ecd,edh->ech", expert_in, w_gate.astype(x.dtype))
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    expert_out = jnp.einsum("ech,ehd->ecd", h, w_down.astype(x.dtype))
    out = jnp.einsum("nec,ecd->nd", combine, expert_out)
    return out.reshape(*lead, d), aux


# ---------------------------------------------------------------------------
# The dropless layer: sort by expert, grouped products over the experts held
# here. No [N, E, C] tensor, no capacity, no dropped token, no aux loss.
# ---------------------------------------------------------------------------

#: caps of the grouped products' (rows, contraction, output) tile on the chip;
#: picked on a v5e over ``scripts/moe_grouped_ladder.py`` (PERF.md, PR 29)
GMM_TILING = (512, 1024, 1024)


def sigmoid_route(h32: jax.Array, router_w: jax.Array, router_bias: jax.Array,
                  top_k: int, routed_scale: float, eps: float = 1e-20):
    """``noaux_tc`` routing with one group: float32 sigmoid scores of every
    routed expert, the ``top_k`` picked by score + ``router_bias`` (which only
    selects and takes no gradient), the picked scores renormalised to sum to
    ``routed_scale`` (their sum ``+ eps`` divides: a model publishes its own).
    ``h32 [N, D]`` float32 -> ``(idx [N, k] int32, gates [N, k] float32)``."""
    scores = jax.nn.sigmoid(jnp.matmul(
        h32.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    biased = scores + jax.lax.stop_gradient(router_bias.astype(jnp.float32))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(biased), top_k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    gates = routed_scale * picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), gates


def softmax_route(h32: jax.Array, router_w: jax.Array, top_k: int):
    """Softmax top-k routing with the picked probabilities renormalised (HF
    ``norm_topk_prob``): a float32 softmax over every routed expert's logit,
    the ``top_k`` largest picked, their probabilities divided by their sum.
    No bias, no scale. ``h32 [N, D]`` float32 -> ``(idx [N, k] int32,
    gates [N, k] float32)``."""
    probs = jax.nn.softmax(jnp.matmul(
        h32.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(probs), top_k)
    picked = jnp.take_along_axis(probs, idx, axis=-1)
    return idx.astype(jnp.int32), picked / jnp.sum(picked, axis=-1, keepdims=True)


#: bytes of the expert-ordered rows' prefix the dispatch's un-permutes gather
#: from: what XLA keeps in a v5e's VMEM as a gather's operand, from where rows
#: move five times as fast as from HBM (``scripts/moe_dispatch_ladder.py``;
#: PERF.md, PR 43)
DISPATCH_CHUNK_BYTES = 80 * 2**20


def chunk_rows(src: jax.Array, chunk: int | None = None) -> int:
    """Rows of ``src [M, D]`` in that prefix (``chunk``, or ``DISPATCH_CHUNK_BYTES``' worth)."""
    rows = chunk or max(8, DISPATCH_CHUNK_BYTES // (src.shape[1] * src.dtype.itemsize) // 8 * 8)
    return min(rows, src.shape[0])


def rows_of_live_prefix(src: jax.Array, idx: jax.Array, n_live: jax.Array | None,
                        chunk: int | None = None) -> jax.Array:
    """``out[s] = src[idx[s]]``: ``src [M, D]`` whose rows past the first
    ``n_live`` are zeros (:func:`grouped_matmul`'s contract for the rows of no
    group here), ``idx [S] int32``; so zeros where ``idx[s] >= n_live``.
    Where the live rows fit the first ``chunk`` rows of ``src`` with one to
    spare (the common case: what a chip holds of an expert-parallel group is a
    fraction of the experts) that prefix is the gather's operand, small enough
    for VMEM, and every dead slot reads its last row, a zero one; where they
    do not, all ``M`` rows are the operand, as before (and as where every row
    is live by the shapes: ``n_live`` None). Which of the two runs is data
    (``n_live``), so this can be a primal or a pull-back of a ``custom_vjp``,
    not something differentiated through. A conditional and not a loop over
    chunks: a second trip costs more than the whole gather from HBM, and XLA
    does not merge a one-layer stack's recomputation with its forward across a
    loop (PERF.md, PR 43)."""
    chunk = chunk_rows(src, chunk)
    if n_live is None or chunk >= src.shape[0]:
        return src[idx]
    return jax.lax.cond(
        n_live < chunk,
        lambda: src[:chunk][jnp.where(idx < n_live, idx, chunk - 1)],
        lambda: src[idx])


def rows_moved(n_live: jax.Array | None, m: int, chunk: int) -> jax.Array:
    """Rows of its ``m``-row operand one un-permute gathers from (float32):
    :func:`rows_of_live_prefix`'s choice."""
    if n_live is None or chunk >= m:
        return jnp.asarray(m, jnp.float32)
    return jnp.where(n_live < chunk, chunk, m).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _rows_by_expert(x: jax.Array, order: jax.Array, inv: jax.Array,
                    n_live: jax.Array | None, k: int):
    """``x [N, D]`` -> ``[N k, D]``: row ``i`` is the token of the ``i``-th
    assignment in expert order (the operand is the ``N`` tokens: small enough
    for VMEM already). Its transpose is written as a gather too (a token's
    ``k`` assignments sit at ``inv[n k : n k + k]``): XLA's own transpose of a
    gather is a scatter-add, slow on a TPU. Only the first ``n_live`` rows of
    the cotangent are live (the sort puts the assignments to experts held here
    first; ``n_live`` None: every row), so the transpose gathers from that
    prefix (:func:`rows_of_live_prefix`)."""
    return x[order // k]


def _rows_by_expert_fwd(x, order, inv, n_live, k):
    return _rows_by_expert(x, order, inv, n_live, k), (inv, n_live)


def _rows_by_expert_bwd(k, res, g):
    by_token = rows_of_live_prefix(g, *res)
    dx = jnp.sum(by_token.reshape(-1, k, g.shape[-1]).astype(jnp.float32), axis=1)
    return dx.astype(g.dtype), None, None, None


_rows_by_expert.defvjp(_rows_by_expert_fwd, _rows_by_expert_bwd)


@jax.custom_vjp
def _combine(rows: jax.Array, gates: jax.Array, order: jax.Array, inv: jax.Array,
             n_live: jax.Array | None):
    """The layer's output from its expert-ordered rows: ``out[n] = sum_j
    gates[n, j] rows[inv[n k + j]]`` accumulated in float32, in ``rows``' dtype.
    ``rows [N k, D]`` (live in its first ``n_live``, zeros after; None: all
    live), ``gates [N, k]`` float32, zero where the expert is not held here.
    The rows come back in assignment order through
    :func:`rows_of_live_prefix`. The pull-back works in expert order, on the
    output's cotangent ``[N, D]`` gathered by token (an operand that fits
    VMEM): weighed by the row's gate it is the rows' cotangent, and its
    product with the row summed over ``D`` the gate's, which goes back to its
    slot as one float. So the backward pass needs no row in assignment order,
    and ``remat`` does not un-permute again."""
    per_slot = rows_of_live_prefix(rows, inv, n_live).reshape(*gates.shape, rows.shape[-1])
    out = jnp.einsum("nk,nkd->nd", gates, per_slot, preferred_element_type=jnp.float32)
    return out.astype(rows.dtype)


def _combine_fwd(rows, gates, order, inv, n_live):
    return _combine(rows, gates, order, inv, n_live), (rows, gates, order, inv)


def _combine_bwd(res, g):
    rows, gates, order, inv = res
    g32 = g[order // gates.shape[1]].astype(jnp.float32)
    # a dead row's gate is zero and the row itself is: both cotangents are
    d_rows = (gates.reshape(-1)[order][:, None] * g32).astype(rows.dtype)
    d_gates = jnp.sum(g32 * rows.astype(jnp.float32), axis=-1)[inv].reshape(gates.shape)
    return d_rows, d_gates, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _megablox():
    # the package's __init__ rebinds the name `gmm` to its own custom_vjp
    # function, so `from ...megablox import gmm` is not the module
    import importlib

    return importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")


def _ragged_tile(dim: int, cap: int) -> int:
    """The tile of a dimension no multiple of 128 divides (an expert width of
    1,856 = 14.5 x 128): the multiple of 128 under the cap that pads the
    dimension least, the largest of those (640 over 1,856: three tiles, 1,920,
    the last one masked by the kernel); the whole dimension where it is under
    128. As ONE tile 1,856 does not compile: the transposed product's
    ``[1,856, 896]`` weight tile and ``[512, 1,856]`` output take 16.6 MB of a
    16 MB scoped VMEM (PERF.md section 6, PR 52, with the ladder of the tiles
    that do: ``scripts/moe_grouped_ladder.py --ragged-tiles``)."""
    tiles = range(min(cap, dim) // 128 * 128, 0, -128)
    return min(tiles, key=lambda t: (-(-dim // t) * t, -t), default=dim)


def _tiles(caps: tuple[int, int, int], m: int, k: int, n: int) -> tuple[int, int, int]:
    """The kernel's (rows, contraction, output) tile for one product: under
    each cap, the largest multiple of 128 that divides the dimension (a tile
    that does not divide is padded and masked: 1,024 over 1,536 wastes a
    third); where none does, the whole of the rows and :func:`_ragged_tile` of
    the contraction and the output."""
    def fit(dim: int, cap: int, whole: bool = False) -> int:
        return next((t for t in range(min(cap, dim) // 128 * 128, 0, -128)
                     if dim % t == 0), None) or (dim if whole else _ragged_tile(dim, cap))

    return fit(m, caps[0], whole=True), fit(k, caps[1]), fit(n, caps[2])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(lhs, rhs, group_sizes, caps, interpret):
    """Megablox's grouped product with its transposes: ``lhs [M, K]`` rows
    sorted by group, ``rhs [G, K, N]``, ``group_sizes [G + 1]`` whose last
    entry counts the rows of no group here (the kernel's sharded-groups form:
    it visits the tiles of the first ``G`` groups only, its grid as long as
    their rows, and the rows past them come out zero)."""
    (m, k), n = lhs.shape, rhs.shape[2]
    return _megablox().gmm(lhs, rhs, group_sizes, lhs.dtype, _tiles(caps, m, k, n),
                           jnp.zeros((), jnp.int32), interpret=interpret)


def _gmm_fwd(lhs, rhs, group_sizes, caps, interpret):
    return _gmm(lhs, rhs, group_sizes, caps, interpret), (lhs, rhs, group_sizes)


def _gmm_bwd(caps, interpret, res, g):
    lhs, rhs, group_sizes = res
    (m, k), n = lhs.shape, rhs.shape[2]
    zero = jnp.zeros((), jnp.int32)
    d_lhs = _megablox().gmm(g, rhs, group_sizes, lhs.dtype, _tiles(caps, m, n, k),
                            zero, transpose_rhs=True, interpret=interpret)
    d_rhs = _megablox().tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                             _tiles(caps, m, k, n), zero, rhs.shape[0],
                             interpret=interpret)
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                   impl: str = "pallas", tiling: tuple[int, int, int] = GMM_TILING,
                   interpret: bool = False) -> jax.Array:
    """``out[i] = lhs[i] @ rhs[g(i)]`` for rows sorted by group.

    ``lhs [M, K]``, ``rhs [G, K, N]``, ``group_sizes [G + 1] int32`` summing
    to ``M``: ``G`` groups in order, then the rows of no group here, which
    give zeros. ``impl='pallas'`` is the megablox kernel, whose grid (and
    device time) follows the rows that have a group, under tiles no larger
    than ``tiling``; off the TPU it steps down to ``impl='xla'``
    (``jax.lax.ragged_dot``) like the flash kernel."""
    from photon_tpu.ops.flash_attention import pallas_supported

    if impl == "pallas" and (interpret or pallas_supported(lhs)):
        return _gmm(lhs, rhs, group_sizes, tuple(tiling), interpret)
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown grouped matmul impl {impl!r}")
    out = jax.lax.ragged_dot(lhs, rhs, group_sizes[:-1])
    grouped = jnp.arange(lhs.shape[0]) < lhs.shape[0] - group_sizes[-1]
    return jnp.where(grouped[:, None], out, 0).astype(lhs.dtype)


def dropless_moe_mlp(h32: jax.Array, router_w: jax.Array, router_bias: jax.Array | None,
                     w_gate: jax.Array | None, w_up: jax.Array, w_down: jax.Array, *,
                     top_k: int, first_expert: int, routed_scale: float = 1.0,
                     router: str = "sigmoid", gate_eps: float = 1e-20,
                     compute_dtype=jnp.bfloat16, interpret: bool = False):
    """The routed part of a dropless expert layer, for the experts held here.

    Two routers: ``router='sigmoid'`` is ``noaux_tc`` (:func:`sigmoid_route`:
    sigmoid scores, a selection bias ``router_bias [E]`` without gradient, the
    picked scores renormalised to ``routed_scale`` over their sum ``+
    gate_eps``); ``router='softmax_topk'``
    is :func:`softmax_route` (a float32 softmax over all ``E``, the picked
    probabilities renormalised to 1; no bias, no scale: ``router_bias`` is
    ``None``). Everything after the gates is one path.

    ``h32 [..., D]`` float32 normed activations; ``router_w [D, E]`` and
    ``router_bias [E]`` over ALL ``E`` routed experts; ``w_gate`` / ``w_up``
    ``[E_held, D, H]`` and ``w_down [E_held, H, D]`` the SwiGLU experts
    ``first_expert .. first_expert + E_held`` this chip holds (``w_gate``
    ``None``: ungated experts ``W_down relu(W_up h)^2``, two matrices each).
    Every token is routed over all ``E``; its assignments to experts held here
    are computed (sorted by expert, three grouped products, two where the
    experts are ungated), the others add nothing: what
    the absent experts would have given is another chip's part of the sum.

    Shapes are static for the worst case (``N k`` rows, all routed here). The
    grouped products' device time follows the rows that were, and so do the
    two un-permutes of a layer and step (expert order back to assignment
    order: forward and in the permute's pull-back; the combine's pull-back
    works in expert order, so ``remat`` does not un-permute again): they gather
    from the live rows where those fit a chunk that fits VMEM
    (:func:`rows_of_live_prefix`), and zeros stand in the slots of experts not
    held here. The permute and the combine's pull-back gather from the ``N``
    tokens, which fit VMEM as they are. Where the layer holds every routed
    expert every row is live, which the shapes say, and the un-permutes are
    whole gathers. Returns ``(out [..., D] compute_dtype, counters)`` with
    ``rows_held`` (assignments to experts held here), ``max_expert_load`` (the
    busiest held expert's rows over their mean), ``dispatch_rows_moved`` (the
    rows of their operand the two un-permutes gathered from) and
    ``dispatch_rows_static`` (2 ``N k``, what whole gathers read from), all
    float32 scalars, and ``expert_rows [E]`` float32, the
    assignments to each of ALL the routed experts (what
    :func:`balanced_router_bias` steers by).
    """
    lead, d = h32.shape[:-1], h32.shape[-1]
    n = int(np.prod(lead))
    e_held = w_up.shape[0]
    hf32 = h32.reshape(n, d)
    with jax.named_scope(ROUTER_SCOPE):
        if router == "softmax_topk":
            idx, gates = softmax_route(hf32, router_w, top_k)
        elif router == "sigmoid":
            idx, gates = sigmoid_route(hf32, router_w, router_bias, top_k, routed_scale,
                                       gate_eps)
        else:
            raise ValueError(f"the dropless layer has no router {router!r}")
        expert_rows = jnp.sum(
            idx.reshape(n * top_k, 1) == jnp.arange(router_w.shape[-1], dtype=jnp.int32),
            axis=0, dtype=jnp.float32)
    with jax.named_scope(DISPATCH_SCOPE):
        local = idx - first_expert
        held = (local >= 0) & (local < e_held)
        # assignments to absent experts sort last, under a group of their own
        key = jnp.where(held, local, e_held).reshape(n * top_k)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(e_held + 1, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        # where every routed expert is held here every row is live, which the
        # shapes say: the un-permutes stay whole gathers
        every = first_expert == 0 and e_held == router_w.shape[-1]
        n_live = None if every else jnp.sum(group_sizes[:e_held])
        rows = _rows_by_expert(hf32.astype(compute_dtype), order, inv, n_live, top_k)
    with jax.named_scope(EXPERTS_SCOPE):
        mm = functools.partial(grouped_matmul, group_sizes=group_sizes,
                               interpret=interpret)
        if w_gate is None:
            act = jnp.square(jax.nn.relu(mm(rows, w_up.astype(compute_dtype))))
        else:
            gate = mm(rows, w_gate.astype(compute_dtype))
            up = mm(rows, w_up.astype(compute_dtype))
            act = jax.nn.silu(gate) * up
        rows = mm(act, w_down.astype(compute_dtype))
    with jax.named_scope(DISPATCH_SCOPE):
        out = _combine(rows, jnp.where(held, gates, 0.0), order, inv, n_live)
        held_sizes = group_sizes[:e_held].astype(jnp.float32)
        rows_held = jnp.sum(held_sizes)
        counters = {
            # the two un-permutes of a layer and step: forward, and in the
            # permute's pull-back
            "dispatch_rows_moved": 2.0 * rows_moved(n_live, n * top_k, chunk_rows(rows)),
            "dispatch_rows_static": jnp.asarray(2.0 * n * top_k, jnp.float32),
            "rows_held": rows_held,
            "max_expert_load": jnp.max(held_sizes) / jnp.maximum(rows_held / e_held, 1.0),
            "expert_rows": expert_rows,
        }
    return out.reshape(*lead, d), counters


def balanced_router_bias(router_bias: jax.Array, expert_rows: jax.Array,
                         speed: float) -> jax.Array:
    """One step of the selection bias's balancing rule (the aux-loss-free
    rule of ``noaux_tc``: the bias of an expert over the mean load falls, of
    one under it rises; no gradient is involved). ``expert_rows [..., E]`` are
    the step's assignments to each routed expert. The step is ``speed`` times
    the load's relative error cut to [-1, 1]: an expert at twice the mean or
    more, or with no row at all, moves by ``speed`` as under the published
    sign rule; nearer the mean the step shrinks with the error instead of
    keeping its size. So the rule has a fixed point to settle on where the
    bare sign chatters by ``speed`` every step, and two precisions of one
    model, whose counts differ by a few rows, keep one bias where the sign
    would flip for an expert near the mean. All float32."""
    rows = expert_rows.astype(jnp.float32)
    mean = jnp.maximum(jnp.mean(rows, axis=-1, keepdims=True), 1.0)
    return router_bias.astype(jnp.float32) - speed * jnp.clip((rows - mean) / mean, -1.0, 1.0)
