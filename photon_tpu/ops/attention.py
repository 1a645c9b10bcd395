"""Attention dispatch: Pallas flash kernel or pure-XLA fallback.

The reference selects between CUDA flash-attention and a plain torch path via
``attn_impl: flash|torch`` (``conf/llm_config/mpt-125m.yaml:27-28``,
``README.md:96-100``). Here the same switch selects the blockwise Pallas TPU
kernel (``attn_impl=pallas``) or a pure-XLA softmax attention
(``attn_impl=xla``) that XLA fuses itself.

All shapes are ``[batch, seq, heads, d_head]`` (v, and with it the output, may
have a head width of its own: latent attention's 128 beside q / k's 192);
softmax runs in fp32 regardless of input dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def alibi_slopes(n_heads: int) -> jax.Array:
    """Standard ALiBi head slopes ``2^(-8i/H)`` for i = 1..H (MPT uses the
    power-of-two geometric schedule; non-power-of-two head counts use the
    same closed form, matching llm-foundry's ``gen_slopes``)."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start**i) for i in range(n)]

    if math.log2(n_heads).is_integer():
        slopes = pow2_slopes(n_heads)
    else:
        closest = 2 ** math.floor(math.log2(n_heads))
        slopes = pow2_slopes(closest)
        extra = pow2_slopes(2 * closest)
        slopes += extra[0::2][: n_heads - closest]
    return jnp.asarray(slopes, jnp.float32)


def xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    alibi: bool = False,
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Plain softmax attention; XLA fuses mask+softmax into the matmuls.

    Numerically the oracle for the Pallas kernel's parity tests. ``alibi``
    adds the per-head linear distance bias ``-slope_h * (q_pos - k_pos)``;
    ``scale`` multiplies the scores (``None``: ``1/sqrt(d_head)``);
    ``window`` (causal only) keeps the keys ``q_pos - window < k_pos <=
    q_pos``, the query's own among them.
    """
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    scale = 1.0 / (d**0.5) if scale is None else float(scale)
    # [b, h, s_q, s_k] in fp32 for a stable softmax
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    q_pos = jnp.arange(s_q)[:, None] + (s_k - s_q)
    k_pos = jnp.arange(s_k)[None, :]
    if alibi:
        dist = (q_pos - k_pos).astype(jnp.float32)  # >= 0 on the causal part
        scores = scores - alibi_slopes(h)[None, :, None, None] * dist[None, None]
    if causal:
        # offset supports s_q != s_k (e.g. decode); here typically equal
        mask = q_pos >= k_pos
        if window is not None:
            mask &= q_pos - k_pos < window
        scores = jnp.where(mask[None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


@functools.partial(jax.named_call, name="multihead_attention")
def multihead_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "pallas",
    causal: bool = True,
    alibi: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
    scale: float | None = None,
    window: int | None = None,
) -> jax.Array:
    """Dispatch on ``impl`` ∈ {pallas, xla, ring}. ``scale`` multiplies the
    scores before the softmax (``None``: ``1/sqrt(d_head)``; the ring merge
    knows no other). ``window`` (causal, no ALiBi, not ``ring``) keeps each
    query's last ``window`` keys: the kernel walks that band, the XLA path
    masks it. ``pallas`` runs the kernel
    on a TPU (or anywhere under ``interpret``); on the CPU backend the tests
    use — and on no other — it steps down to ``xla_attention`` without a word
    (``flash_attention.pallas_supported``), so a CPU run never shows that the
    kernel is in the program: ``chip_smoke.py`` asserts ``tpu_custom_call`` in
    the compiled train step on the chip.
    ``ring`` = context parallelism over the ambient mesh's ``sequence`` axis
    (``photon_tpu/ops/ring_attention.py``), degrading to pallas/xla when the
    axis is trivial. ALiBi runs in-kernel on the pallas path (per-head slope
    bias, ``flash_attention.py:_alibi_bias``); the ring path's pallas inner
    kernel still degrades to XLA under alibi (the lse-merge bwd oracle does
    not model the bias yet).

    Grouped-query attention: ``k``/``v`` may carry fewer heads than ``q``.
    The pallas kernel consumes them natively (index-mapped kv groups, no
    repeated-kv tensor in HBM); the xla and ring paths replicate kv up to
    the q head count here, at the dispatch, so model code never has to."""
    h_q, h_kv = q.shape[2], k.shape[2]
    if h_q % h_kv:
        raise ValueError(f"q heads ({h_q}) must be a multiple of kv heads ({h_kv})")
    if window is not None and (impl == "ring" or alibi or not causal or window < 1):
        raise NotImplementedError(
            "a window needs causal attention without ALiBi on the pallas or xla "
            "path (ring attention's chunks and the bias know no second bound)")

    def rep(x):
        return jnp.repeat(x, h_q // h_kv, axis=2) if h_kv != h_q else x

    if impl == "ring":
        from photon_tpu.ops.flash_attention import pallas_supported
        from photon_tpu.ops.ring_attention import ring_attention
        from photon_tpu.parallel.context import current_mesh

        mesh = current_mesh()
        inner = "pallas" if (pallas_supported(q) and not alibi) else "xla"
        if mesh is not None and mesh.shape.get("sequence", 1) > 1:
            if scale is not None:
                raise NotImplementedError(
                    "ring attention fixes the softmax scale at 1/sqrt(d_head)")
            # GQA kv rides the ring at native width (group× less ppermute
            # traffic); ring_attention handles the groups in its chunk
            # kernel. Exception: kv heads that don't split over the tensor
            # axis would silently drop head sharding inside ring_attention
            # (its spec falls back to replicated heads) — replicate kv up to
            # the q head count instead, like the non-ring pallas path
            if h_kv % mesh.shape.get("tensor", 1):
                k, v = rep(k), rep(v)
            return ring_attention(q, k, v, mesh, causal=causal,
                                  impl=inner, alibi=alibi)
        impl = inner
    if impl == "pallas":
        from photon_tpu.ops.flash_attention import flash_attention, pallas_supported

        if interpret or pallas_supported(q):
            # block_q / block_k None: the kernel derives its tiles from the
            # shapes it is handed (under shard_map: each shard's local ones)

            # Mosaic kernels cannot be auto-partitioned by GSPMD: on a
            # multi-device mesh the pallas call must be wrapped in
            # shard_map. Flash attention is independent per batch row and
            # per head, so mapping over the batch (data+fsdp) and head
            # (tensor) axes is exact — each shard runs the single-device
            # kernel on its slice. Under a head-sharded (tensor>1) mesh,
            # ALiBi slopes must come from the GLOBAL head index: each shard
            # slices its rows out of the full slope table (the kernel's
            # default would restart the slope sequence per shard).
            from photon_tpu.parallel.context import current_mesh

            mesh = current_mesh()
            sharded_axes = [a for a in ("data", "fsdp", "expert", "tensor")
                            if mesh is not None and mesh.shape.get(a, 1) > 1]
            if not sharded_axes:
                return flash_attention(q, k, v, causal=causal, alibi=alibi,
                                       block_q=block_q, block_k=block_k,
                                       interpret=interpret, scale=scale, window=window)
            if h_kv % mesh.shape.get("tensor", 1):
                # kv heads don't split over the tensor axis — replicate up
                # to the q head count (which always splits; param_specs
                # shards q by tensor)
                k, v = rep(k), rep(v)

            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            h_global = q.shape[2]
            global_slopes = alibi_slopes(h_global) if alibi else None

            def _local(q_s, k_s, v_s):
                sl = None
                if alibi:
                    h_loc = q_s.shape[2]
                    start = jax.lax.axis_index("tensor") * h_loc
                    sl = jax.lax.dynamic_slice(global_slopes, (start,), (h_loc,))
                return flash_attention(q_s, k_s, v_s, causal=causal,
                                       alibi=alibi, alibi_slopes=sl,
                                       block_q=block_q, block_k=block_k,
                                       interpret=interpret, scale=scale, window=window)

            spec = P(("data", "fsdp", "expert"), None, "tensor", None)
            fn = shard_map(
                _local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                # pallas_call emits un-annotated out-avals; varying-axis
                # checking can't see through it (the map is exact anyway:
                # one independent kernel instance per batch/head shard)
                check_vma=False,
            )
            return fn(q, k, v)
        impl = "xla"
    if impl != "xla":
        raise ValueError(f"unknown attention impl {impl!r}")
    return xla_attention(q, rep(k), rep(v), causal=causal, alibi=alibi, scale=scale,
                         window=window)
