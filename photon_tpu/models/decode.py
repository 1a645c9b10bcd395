"""KV-cache greedy decoding — the TPU-native inference path.

The eval harness's baseline decoder re-runs the FULL forward for every new
token (``eval/icl.py:make_generate_fn``, O(S) model passes of O(S²)
attention each). This module adds the standard cache formulation: one
``prefill`` pass over the prompt builds per-layer k/v caches, then each
``decode_step`` is a single-token pass attending into the cache — O(S)
attention per token.

TPU-first shape: parameters already carry the ``[n_layers, ...]`` scan
axis (``models/mpt.py`` stacks blocks with ``nn.scan``), so both prefill
and decode run ``lax.scan`` over that axis directly — no per-layer Python,
one trace regardless of depth. The cache stores n_kv heads (GQA's memory
saving materializes here) with grouped-einsum attention; positions, RoPE
rotations, ALiBi distances, and learned-wpe lookups are all per-row
cursors so left-aligned prompts of different lengths batch together.

Correctness is pinned by equivalence tests against the full-forward
decoder across MPT (wpe / ALiBi) and llama (RoPE / RMSNorm / SwiGLU / GQA)
configs (``tests/test_decode.py``); reference analog: the generate path
llm-foundry inherits from HF ``GenerationMixin`` (KV cache included).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp

from photon_tpu.config.schema import ModelConfig, refuse_training_only_family
from photon_tpu.ops.attention import alibi_slopes, multihead_attention


@flax.struct.dataclass
class DecodeState:
    """Per-layer post-RoPE k/v caches ``[L, B, S, H_kv, Dh]`` plus each
    row's write cursor (== its current token count)."""

    cache_k: jax.Array
    cache_v: jax.Array
    lengths: jax.Array  # [B] int32


def _norm(x: jax.Array, scale: jax.Array, bias: jax.Array | None,
          kind: str, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    else:
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale
    if bias is not None:
        y = y + bias
    return y.astype(x.dtype)


def _rope_at(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotate ``[..., H, D]`` vectors at explicit positions.

    ``x``: [B, T, H, D]; ``pos``: [B, T] absolute positions (fp32 angles,
    rotate-half convention — must match ``models.mpt.apply_rope``)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * inv  # [B, T, half]
    cos = jnp.cos(ang)[..., None, :]  # [B, T, 1, half]
    sin = jnp.sin(ang)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _lora_delta(ad: dict, h: jax.Array, scale: float) -> jax.Array:
    """Per-ROW LoRA delta ``(h @ A) @ B · scale`` for batched adapters:
    ``ad["a"]``/``ad["b"]`` carry a leading batch axis aligned with ``h``'s
    (row b of the batch uses row b's adapter — the serving pool gather and
    the contiguous mixed-cohort oracle compute the identical einsums, so
    the table indirection stays bitwise invisible exactly like the KV
    gather). ``h`` is ``[B, D]`` (decode column) or ``[B, T, D]``
    (prefill/chunk)."""
    a = ad["a"].astype(h.dtype)
    b = ad["b"].astype(h.dtype)
    if h.ndim == 2:
        t = jnp.einsum("bd,bdr->br", h, a)
        return jnp.einsum("br,bro->bo", t, b) * scale
    t = jnp.einsum("btd,bdr->btr", h, a)
    return jnp.einsum("btr,bro->bto", t, b) * scale


def _dense(lp: dict, name: str, h: jax.Array, la: dict | None = None,
           ls: float = 1.0) -> jax.Array:
    y = h @ lp[name]["kernel"].astype(h.dtype)
    if "bias" in lp[name]:
        y = y + lp[name]["bias"].astype(h.dtype)
    if la is not None and name in la:
        y = y + _lora_delta(la[name], h, ls)
    return y


def _qkv(lp: dict, h: jax.Array, cfg: ModelConfig, la: dict | None = None,
         ls: float = 1.0):
    """Project hidden → (q [..., H, Dh], k/v [..., H_kv, Dh])."""
    n_kv = cfg.n_kv_heads or cfg.n_heads
    if "wqkv" in lp:
        q, k, v = jnp.split(_dense(lp, "wqkv", h, la, ls), 3, axis=-1)
    else:
        q = _dense(lp, "q_proj", h, la, ls)
        k = _dense(lp, "k_proj", h, la, ls)
        v = _dense(lp, "v_proj", h, la, ls)
    lead = h.shape[:-1]
    return (q.reshape(*lead, cfg.n_heads, cfg.d_head),
            k.reshape(*lead, n_kv, cfg.d_head),
            v.reshape(*lead, n_kv, cfg.d_head))


def _mlp(lp: dict, x: jax.Array, cfg: ModelConfig,
         token_mask: jax.Array | None = None, la: dict | None = None,
         ls: float = 1.0) -> jax.Array:
    h = _norm(x, lp["ln_2"]["scale"], lp["ln_2"].get("bias"), cfg.norm, cfg.norm_eps)
    if cfg.mlp == "moe":
        # same routing as training (ops/moe.py); aux loss discarded.
        # token_mask (prefill): right-padding must not claim expert
        # capacity — otherwise a row's logits would depend on how much
        # padding its batch-mates carry. (Adapters never reach here:
        # config validation rejects adapters with MoE.)
        from photon_tpu.ops.moe import moe_mlp

        out, _ = moe_mlp(
            h, lp["router"], lp["moe_up"], lp["moe_down"],
            w_gate=lp.get("moe_gate"),
            top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
            token_mask=token_mask,
        )
        return x + out
    if cfg.mlp == "swiglu":
        h = (jax.nn.silu(_dense(lp, "gate_proj", h, la, ls))
             * _dense(lp, "up_proj", h, la, ls))
    else:
        h = jax.nn.gelu(_dense(lp, "up_proj", h, la, ls), approximate=True)
    return x + _dense(lp, "down_proj", h, la, ls)


def _embed(params: dict, tokens: jax.Array, pos: jax.Array,
           cfg: ModelConfig) -> jax.Array:
    compute = jnp.dtype(cfg.compute_dtype)
    # jnp.asarray first: param leaves may be host numpy arrays (npz-loaded
    # checkpoints), which reject indexing by traced token ids
    x = jnp.asarray(params["wte"]["embedding"], compute)[tokens]
    if cfg.learned_pos_emb and not cfg.alibi and not cfg.rope:
        x = x + jnp.asarray(params["wpe"], compute)[pos]
    return x


def _logits(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = _norm(x, params["ln_f"]["scale"], params["ln_f"].get("bias"),
              cfg.norm, cfg.norm_eps)
    compute = jnp.dtype(cfg.compute_dtype)
    if cfg.tie_embeddings:
        logits = x.astype(compute) @ params["wte"]["embedding"].astype(compute).T
    else:
        logits = x.astype(compute) @ params["lm_head"]["kernel"].astype(compute)
    return logits.astype(jnp.dtype(cfg.logits_dtype))


def _layer_adapters(adapters: dict | None):
    """Batched adapter tree ``{module: {"a": [B, L, ...], "b": ...}}`` →
    layer-major leaves ``[L, B, ...]`` ready to ride the layer scan's xs
    (None passes through)."""
    if adapters is None:
        return None
    return jax.tree.map(lambda x: jnp.moveaxis(jnp.asarray(x), 1, 0), adapters)


def prefill(params: dict, tokens: jax.Array, lengths: jax.Array,
            cfg: ModelConfig, adapters: dict | None = None,
            lora_scale: float = 1.0) -> tuple[jax.Array, DecodeState]:
    """Full pass over right-padded prompts ``[B, S]`` → (next-token logits
    ``[B, V]`` at each row's cursor, filled :class:`DecodeState`).

    ``adapters`` (optional, ISSUE 13): per-ROW LoRA factors
    ``{module: {"a": [B, L, d_in, r], "b": [B, L, r, d_out]}}`` — row b
    runs with row b's adapter (a mixed-cohort batch in one pass), scaled
    by ``lora_scale``. None keeps the graph byte-identical to the
    adapter-free build."""
    refuse_training_only_family(cfg, "cached decode (models/decode.py)")
    b, s = tokens.shape
    n_kv = cfg.n_kv_heads or cfg.n_heads
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    valid = (pos < lengths[:, None]).astype(jnp.float32)  # [B, S] real tokens
    x = _embed(params, tokens, pos, cfg)
    ad_l = _layer_adapters(adapters)

    def layer(x, xs):
        lp, la = xs if adapters is not None else (xs, None)
        h = _norm(x, lp["ln_1"]["scale"], lp["ln_1"].get("bias"),
                  cfg.norm, cfg.norm_eps)
        q, k, v = _qkv(lp, h, cfg, la, lora_scale)
        if cfg.rope:
            q = _rope_at(q, pos, cfg.rope_theta)
            k = _rope_at(k, pos, cfg.rope_theta)
        # dispatch on the config's impl (pallas on chip) so prefill numerics
        # match the training/logprob forward; ring is a mesh-training
        # construct — decode is single-host, so it degrades to the fallback.
        # k/v stay at n_kv width: the dispatch handles GQA natively
        attn = multihead_attention(
            q, k, v,
            impl=cfg.attn_impl if cfg.attn_impl != "ring" else "xla",
            causal=True, alibi=cfg.alibi,
        )
        x = x + _dense(lp, "out_proj", attn.reshape(b, s, cfg.d_model),
                       la, lora_scale)
        return _mlp(lp, x, cfg, token_mask=valid, la=la, ls=lora_scale), (k, v)

    xs = (params["blocks"]["block"], ad_l) if adapters is not None \
        else params["blocks"]["block"]
    x, (ck, cv) = jax.lax.scan(layer, x, xs)
    idx = jnp.clip(lengths - 1, 0, s - 1)
    last = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0]
    return _logits(params, last, cfg), DecodeState(
        cache_k=ck, cache_v=cv, lengths=lengths.astype(jnp.int32)
    )


def decode_step(params: dict, state: DecodeState, token: jax.Array,
                cfg: ModelConfig, adapters: dict | None = None,
                lora_scale: float = 1.0) -> tuple[jax.Array, DecodeState]:
    """Place ``token [B]`` at each row's cursor, attend into the caches,
    return (logits for the FOLLOWING position, advanced state).
    ``adapters``: per-row LoRA factors as in :func:`prefill`."""
    refuse_training_only_family(cfg, "cached decode (models/decode.py)")
    n_kv = cfg.n_kv_heads or cfg.n_heads
    group = cfg.n_heads // n_kv
    s = state.cache_k.shape[2]
    pos = state.lengths  # [B] — where this token lands
    x = _embed(params, token, pos, cfg)  # [B, D]
    scale = 1.0 / (cfg.d_head ** 0.5)
    k_pos = jnp.arange(s)[None, :]  # [1, S]
    valid = (k_pos <= pos[:, None])  # j <= pos, per row
    oh = jax.nn.one_hot(pos, s, dtype=state.cache_k.dtype)[:, :, None, None]
    ad_l = _layer_adapters(adapters)

    def layer(x, xs):
        if adapters is not None:
            lp, ck, cv, la = xs
        else:
            (lp, ck, cv), la = xs, None  # ck/cv: [B, S, H_kv, Dh]
        h = _norm(x, lp["ln_1"]["scale"], lp["ln_1"].get("bias"),
                  cfg.norm, cfg.norm_eps)
        q, k_new, v_new = _qkv(lp, h, cfg, la, lora_scale)  # q [B,H,Dh]
        if cfg.rope:
            q = _rope_at(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
            k_new = _rope_at(k_new[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        ck = ck * (1 - oh) + oh * k_new[:, None].astype(ck.dtype)
        cv = cv * (1 - oh) + oh * v_new[:, None].astype(cv.dtype)
        # grouped-query attention straight against the n_kv-head cache
        qg = q.reshape(q.shape[0], n_kv, group, cfg.d_head)
        scores = jnp.einsum("bkgd,bskd->bkgs", qg, ck,
                            preferred_element_type=jnp.float32) * scale
        if cfg.alibi:
            dist = (pos[:, None] - k_pos).astype(jnp.float32)  # [B, S]
            slopes = alibi_slopes(cfg.n_heads).reshape(n_kv, group)
            scores = scores - slopes[None, :, :, None] * dist[:, None, None, :]
        scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(cv.dtype), cv)
        x = x + _dense(lp, "out_proj", out.reshape(x.shape[0], cfg.d_model),
                       la, lora_scale)
        return _mlp(lp, x, cfg, la=la, ls=lora_scale), (ck, cv)

    xs = (params["blocks"]["block"], state.cache_k, state.cache_v)
    if adapters is not None:
        xs = xs + (ad_l,)
    x, (ck, cv) = jax.lax.scan(layer, x, xs)
    return _logits(params, x, cfg), DecodeState(
        cache_k=ck, cache_v=cv, lengths=state.lengths + 1
    )


# ---------------------------------------------------------------------------
# Shared compile cache (ISSUE 5 satellite): the jitted prefill/step pair is
# keyed by the MODEL CONFIG, not the decoder instance — params ride as traced
# arguments, so repeated gauntlet/eval/serving constructions with identical
# configs (and therefore identical param shapes) reuse one trace+compile
# instead of re-tracing per instance. The config key is its dataclass field
# tuple (all scalars/strings — hashable); an unhashable future field degrades
# to per-instance jits rather than failing.
# ---------------------------------------------------------------------------

_JIT_PAIR_CACHE: dict[tuple, tuple[Any, Any]] = {}
_JIT_PAIR_LOCK = threading.Lock()


def _build_jit_pair(cfg: ModelConfig) -> tuple[Any, Any]:
    prefill_jit = jax.jit(lambda p, t, l: prefill(p, t, l, cfg))
    # donate the STATE (arg 1), never the params — params are shared across
    # every request/instance using this config
    step_jit = jax.jit(
        lambda p, st, tok: decode_step(p, st, tok, cfg), donate_argnums=1
    )
    return prefill_jit, step_jit


def decode_jit_pair(cfg: ModelConfig) -> tuple[Any, Any]:
    """``(prefill_jit(params, tokens, lengths), step_jit(params, state,
    token))`` shared module-wide per config value."""
    try:
        key = dataclasses.astuple(cfg)
        hash(key)
    except TypeError:
        return _build_jit_pair(cfg)
    with _JIT_PAIR_LOCK:
        pair = _JIT_PAIR_CACHE.get(key)
        if pair is None:
            pair = _JIT_PAIR_CACHE[key] = _build_jit_pair(cfg)
    return pair


def generate(params: Any, tokens: jax.Array, lengths: jax.Array,
             cfg: ModelConfig, max_new_tokens: int, *,
             temperature: float = 0.0, top_k: int = 0,
             seed: int = 0) -> tuple[jax.Array, jax.Array]:
    """One-shot convenience over :func:`make_cached_generate_fn`:
    ``temperature == 0`` is greedy argmax (deterministic, the eval path);
    otherwise logits/temperature are sampled, optionally truncated to the
    ``top_k`` highest first (the sampling surface HF ``generate`` gives
    reference users). Compiles are shared through :func:`decode_jit_pair`,
    so repeated invocations with one config reuse the same traces."""
    fn = make_cached_generate_fn(cfg, params)
    return fn.many(tokens, lengths, max_new_tokens,
                   temperature=temperature, top_k=top_k, seed=seed)


def make_cached_generate_fn(cfg: ModelConfig, params: Any,
                            model_apply: Any = None):
    """Drop-in for ``eval/icl.py:make_generate_fn`` exposing the faster
    multi-token path: ``.many(tokens, lengths, n)`` prefills once and
    decodes ``n`` tokens through the cache. The one-step
    ``(tokens, lengths) -> (tokens, lengths)`` call signature stays
    available when a ``model_apply`` is supplied (reused, not rebuilt)."""
    from photon_tpu.eval.icl import make_generate_fn, write_at_cursor

    one_step = (
        make_generate_fn(model_apply, params) if model_apply is not None else None
    )
    # shared per-config compiles (params ride as traced args). device_put the
    # leaves once: npz-loaded numpy params would otherwise re-transfer on
    # every jitted call now that they are arguments instead of closure consts
    params = jax.tree.map(jnp.asarray, params)
    prefill_jit, step_jit = decode_jit_pair(cfg)

    def many(tokens, lengths, n: int, *, temperature: float = 0.0,
             top_k: int = 0, seed: int = 0, eos_id: int | None = None):
        """Decode up to ``n`` tokens — greedy at ``temperature == 0`` (the
        eval default), sampled otherwise. Enforces ``max(lengths) + n <= S``
        — past the buffer end the one-hot cache write would silently drop
        k/v and decode from a stale cache.

        ``eos_id`` arms per-row early exit: a row that emits ``eos_id``
        (written — the EOS itself lands in the buffer) is frozen (no further
        writes, its returned length stops growing) and the loop breaks as
        soon as EVERY row is done instead of burning all ``n`` steps. The
        all-done check is a per-step host sync, which is exactly the point:
        trading one scalar readback per token for skipped decode steps."""
        if int(jnp.max(lengths)) + n > tokens.shape[1]:
            raise ValueError(
                f"decode overflow: max length {int(jnp.max(lengths))} + "
                f"{n} new tokens > buffer {tokens.shape[1]}"
            )

        def pick(logits, key):
            if temperature <= 0.0:
                return jnp.argmax(logits, axis=-1)
            scaled = logits.astype(jnp.float32) / temperature
            if top_k:
                kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
                scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
            return jax.random.categorical(key, scaled, axis=-1)

        key = jax.random.PRNGKey(seed)
        logits, st = prefill_jit(params, tokens, lengths)
        done = None if eos_id is None else jnp.zeros(tokens.shape[0], bool)
        produced = jnp.zeros_like(lengths)
        for i in range(n):
            key, sub = jax.random.split(key)
            nxt = pick(logits, sub).astype(tokens.dtype)
            if done is None:
                tokens = write_at_cursor(tokens, st.lengths, nxt)
            else:
                # done-mask freeze: finished rows keep their buffer bytes
                # (their cache cursor still advances inside step_jit, but
                # nothing they produce is observable)
                tokens = jnp.where(done[:, None], tokens,
                                   write_at_cursor(tokens, st.lengths, nxt))
                produced = produced + jnp.where(done, 0, 1)
                done = done | (nxt == eos_id)
                if i < n - 1 and bool(jnp.all(done)):
                    break
            if i < n - 1:  # the last token's successor logits are unused
                logits, st = step_jit(params, st, nxt)
        if done is None:
            produced = jnp.full_like(lengths, n)
        return tokens, jnp.minimum(lengths + produced, tokens.shape[1])

    class _GenerateFn:
        """Callable wrapper (jitted functions reject attribute assignment)."""

        def __call__(self, tokens, lengths):
            if one_step is None:
                raise ValueError(
                    "one-step decode needs model_apply at construction; "
                    "use .many for the cached path"
                )
            return one_step(tokens, lengths)

    fn = _GenerateFn()
    fn.many = many
    return fn
