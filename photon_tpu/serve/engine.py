"""The jit'd serving engine: fixed-shape slot arrays over the paged pool.

One :class:`PagedEngine` owns the device state (paged KV pool, block
tables, per-slot cursors/temperatures/PRNG keys) and — since ISSUE 12 —
TWO compiled programs instead of the PR 5 prefill/decode pair:

- ``_install_jit``: admission bookkeeping (:func:`serve.cache.install_row`
  — point the slot's table at its reserved blocks, park the cursor at the
  prefix-hit depth). One compile, no KV movement.
- ``_mixed_jit``: the unified mixed chunked-prefill step
  (:func:`serve.cache.mixed_chunk_step` + per-slot sampling). Decode rows
  and ONE prompt chunk run in the same program; prompts prefill as a
  stream of chunks instead of one monolithic prefill, so a giant prompt
  can't monopolize a step. Attention walks the block tables at the LIVE
  width (``n_ctx`` blocks) — the ragged-paged-attention shape — so
  attention cost scales with live tokens, not pool capacity.

Shape discipline (the no-retrace contract, machine-checked by the
photon-lint sentinel tests): chunk width ``Tq`` buckets to a power-of-two
BLOCK count exactly like the old prefill (<= ``log2(max_blocks)+1``
shapes, and a chunk's width depends only on its own request + the chunk
budget — never on batch-mates); decode-only steps are ``Tq == 1``; the
live width ``n_ctx`` is a pow2 bucket of the longest ACTIVE reservation
and rises MONOTONICALLY (high-water) while any slot is live — it resets
only when the engine goes FULLY idle (see ``_ctx_width``), so a warm
engine's bucket set is a deterministic function of the traffic profile,
not of admission timing. Speculative verify widths (``n_spec``) bucket
to pow2 the same way. ``serve.attention_impl`` picks the attention
inner graph: the bit-exact gather reference or the fused Pallas ragged
kernel (``ops/ragged_paged_attention.py``).

Sampling is per request: ``temperature == 0`` rows take argmax (bit-exact
with the offline greedy path), others sample from seeded per-slot PRNG
streams (same seed → same completion, independent of batch-mates — a
slot's key advances only on steps where that slot emits, so the chunk
schedule can't perturb the stream). MoE models are the one exception to
every batch-mate-independence and parity claim here: expert-capacity
routing is batch-global (as it was in the PR 5 step), so MoE serving
stays best-effort — see the ``mixed_chunk_step`` docstring.

Params come either straight from a pytree or — the train→serve loop — via
:meth:`from_checkpoint`: ``ServerCheckpointManager.load_round_params`` (the
params-only path: no dead Adam moments), momenta split off for
momenta-aggregating runs, leaves restored onto the model template.

Thread-discipline: ONE driver thread (the scheduler loop) calls
begin/mixed_step/evict; HTTP handler threads only read the scalar stats.
The step donates the previous state, so the pool is updated in place.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from photon_tpu.config.schema import Config, ModelConfig, refuse_training_only_family
from photon_tpu.serve.cache import (
    BlockAllocator,
    PagedState,
    init_paged_state,
    install_row,
    mixed_chunk_step,
)
from photon_tpu.serve.prefix import PrefixCache, prefix_hashes


def _pow2_bucket(n: int) -> int:
    """The shape-bucketing rule, in ONE place: smallest power of two
    covering ``n`` (minimum 1). Chunk widths, the live attention width
    and the speculative verify width all bucket through this — the
    retrace-sentinel tests lean on every site agreeing."""
    return 1 << (max(1, n) - 1).bit_length()


def _sample_rows(logits: jax.Array, temps: jax.Array,
                 keys: jax.Array) -> jax.Array:
    """Per-row greedy/temperature sampling: ``temps[b] == 0`` → argmax."""
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits.astype(jnp.float32) / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)


def _verify_rows(logits: jax.Array, tokens: jax.Array, temps: jax.Array,
                 keys: jax.Array, emit_mask: jax.Array, n_valid: jax.Array,
                 n_spec: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Speculative acceptance over the verify grid (ISSUE 15): emission
    ``i`` consumes the TRUE logits at column ``i`` (``logits [B, n_spec,
    V]``); the draft it tests sits at column ``i + 1`` of ``tokens``.

    - **greedy rows** (``temps <= 0``): longest-matching-prefix — emit
      ``argmax`` at every live column and keep going while the next draft
      equals it. The emitted stream is exactly what sequential
      single-token steps would emit (per-column logits are bitwise equal
      — see ``mixed_chunk_step``), so greedy speculative output is
      BIT-EXACT vs the non-speculative engine.
    - **temperature rows**: standard rejection sampling against the
      drafter's point-mass proposal — accept draft ``d`` with probability
      ``p(d)`` (``u < p(d)``), on rejection sample from the residual
      ``p`` with ``d``'s mass removed, and stop. Distribution-preserving
      per position; the SAMPLE PATH differs from the non-speculative
      engine (pinned statistically in tests, not bitwise).

    A row with no draft at a live column (``i + 1 >= n_valid`` — the
    plain decode row, or the last column's bonus emission) emits through
    the ordinary full-sample path. Per-slot PRNG chains advance once per
    EMITTED token with EXACTLY the classic step's split discipline —
    ``s_key_m, k_{m+1} = split(k_m)``, the rejection test's extra
    uniforms derived from ``s_key_m`` and consumed only by drafted rows
    — so a seeded stream's m-th emission always draws from the same key
    regardless of how emissions grouped into steps, and a row that
    carries no draft samples BITWISE what the classic ``n_spec == 1``
    program would have sampled: batch-mates' chunk/draft schedules can
    never perturb a non-drafting row's stream.

    Returns ``(emitted tokens [B, n_spec] — zeros past each row's count,
    n_emitted [B], advanced keys)``.
    """
    B, _, V = logits.shape
    greedy_rows = temps <= 0.0
    live = emit_mask
    k = keys
    n_em = jnp.zeros(B, jnp.int32)
    outs = []
    for i in range(n_spec):
        lg = logits[:, i]
        sub = jax.vmap(jax.random.split)(k)  # [B, 2, 2] — the classic chain
        s_key, k_next = sub[:, 0], sub[:, 1]
        # the bonus emission IS the classic sampling rule — one helper,
        # so the non-drafting-row-samples-bitwise-classic invariant can't
        # drift
        bonus_tok = _sample_rows(lg, temps, s_key)
        if i + 1 < n_spec:
            draft = tokens[:, i + 1]
            has_draft = (i + 1) < n_valid  # [B]
            greedy_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            scaled = lg.astype(jnp.float32) / jnp.maximum(temps,
                                                          1e-6)[:, None]
            p = jax.nn.softmax(scaled, axis=-1)
            p_draft = jnp.take_along_axis(p, draft[:, None], axis=1)[:, 0]
            usub = jax.vmap(jax.random.split)(s_key)  # [B, 2, 2]
            u = jax.vmap(jax.random.uniform)(usub[:, 0])
            # the rejection residual: p with the draft's mass removed —
            # log(0) = -inf rows are unreachable (p(d) == 1 always accepts)
            resid = jnp.where(jnp.arange(V)[None, :] == draft[:, None], 0.0, p)
            resid_tok = jax.vmap(jax.random.categorical)(
                usub[:, 1], jnp.log(resid)
            ).astype(jnp.int32)
            accept = jnp.where(greedy_rows, draft == greedy_tok, u < p_draft)
            cont = accept & has_draft
            corr = jnp.where(greedy_rows, greedy_tok, resid_tok)
            emit_tok = jnp.where(has_draft, jnp.where(cont, draft, corr),
                                 bonus_tok)
        else:
            cont = jnp.zeros(B, bool)
            emit_tok = bonus_tok
        outs.append(jnp.where(live, emit_tok, 0))
        n_em = n_em + live.astype(jnp.int32)
        k = jnp.where(live[:, None], k_next, k)
        live = live & cont
    return jnp.stack(outs, axis=1), n_em, k


def load_serving_params(cfg: Config, mgr: Any, server_round: int) -> Any:
    """Params-only load + model-template restore for serving consumers
    (shared by :meth:`PagedEngine.from_checkpoint` and the hot-swap
    watcher, ``serve/hotswap.py``): no dead optimizer moments, aggregated
    momenta split off when the run shipped them.

    The template is built with the LoRA knobs ZEROED: server checkpoints
    store the adapter-free BASE (adapter runs save their base + separate
    ``adapter__*`` objects; ``configure_adapter_training`` mutates the
    TRAINING config's ``model.lora_*`` in place, and a serving consumer
    handed that same config — or its YAML round-trip — must not demand
    lora leaves the checkpoint never carries)."""
    import dataclasses as _dc

    from photon_tpu.codec import params_from_ndarrays
    from photon_tpu.models.mpt import init_params
    from photon_tpu.train.param_ops import has_momenta, split_momenta

    meta, arrays = mgr.load_round_params(server_round)
    if has_momenta(meta):
        meta, arrays, _, _ = split_momenta(meta, arrays)
    mc = cfg.model
    if mc.lora_rank:
        mc = _dc.replace(mc, lora_rank=0, lora_targets=())
    return params_from_ndarrays(init_params(mc, seed=0), meta, arrays)


@dataclass
class _Prefill:
    """Host-side chunk cursor for a prompt mid-prefill: positions
    ``[pos, n)`` still need to run through the chunk stream."""

    prompt: list[int] = field(default_factory=list)
    pos: int = 0  # next position to prefill (starts at the prefix-hit depth)
    n: int = 0  # full prompt length
    hashes: list[bytes] = field(default_factory=list)
    row_blocks: list[int] = field(default_factory=list)


class PagedEngine:
    def __init__(self, cfg: Config, params: Any, *,
                 loaded_round: int | None = None,
                 adapter_bank: dict | None = None) -> None:
        refuse_training_only_family(cfg.model, "the serving engine (photon_tpu/serve)")
        self.cfg = cfg
        self.mc: ModelConfig = cfg.model
        sc = cfg.photon.serve
        self.block_size = sc.block_size
        self.n_slots = sc.n_slots
        self.max_blocks = -(-self.mc.max_seq_len // self.block_size)
        self.s_cap = self.max_blocks * self.block_size
        self.n_blocks = sc.n_blocks or self.n_slots * self.max_blocks
        self.loaded_round = loaded_round
        self.params = jax.tree.map(jnp.asarray, params)
        self.allocator = BlockAllocator(self.n_blocks)
        # -- attention impl resolution (ISSUE 12; validated in schema.py) --
        # "gather": the PR 5 full-width dense gather — the bit-exact
        #   oracle whose cost scales with POOL capacity;
        # "auto": the ragged live-block walk — fused Pallas kernel on a
        #   TPU ("ragged"); on the CPU backend the tests use, and on no
        #   other, the bit-exact gather REFERENCE math over the live slice
        #   ("ragged-ref"). chip_smoke.py asserts "ragged" on the chip;
        # "ragged": the fused kernel, explicitly — schema validation
        #   already rejected it on a non-Pallas backend unless
        #   attention_interpret opted into the Pallas interpreter.
        impl = getattr(sc, "attention_impl", "auto")
        interpret = bool(getattr(sc, "attention_interpret", False))
        if impl == "gather":
            self._ctx_full, self._use_kernel = True, False
        elif impl == "ragged":
            self._ctx_full, self._use_kernel = False, True
        else:  # auto
            from photon_tpu.ops.flash_attention import pallas_supported

            self._ctx_full = False
            self._use_kernel = interpret or pallas_supported(None)
        self._interpret = interpret
        self.attn_impl = "gather" if self._ctx_full else (
            "ragged" if self._use_kernel else "ragged-ref"
        )
        # live-width high-water mark (blocks): monotone so a warm
        # engine's (Tq, n_ctx) bucket set depends only on the traffic
        # profile — never on admission timing (the retrace sentinel
        # tests lean on this determinism)
        self._ctx_hw = 1
        # content-addressed prefix reuse (ISSUE 11, serve/prefix.py): OFF
        # unless opted in, and never for MoE — expert-capacity routing is
        # batch-global, so a prefix block's KV is not a pure function of
        # its tokens there and cross-request sharing would break parity
        self.prefix_cache: PrefixCache | None = None
        if getattr(sc, "prefix_cache", False) and self.mc.mlp != "moe":
            self.prefix_cache = PrefixCache(
                self.allocator,
                max_blocks=getattr(sc, "prefix_cache_blocks", 0),
            )
        # single-slot chain-hash memo (see _chain_hashes)
        self._hash_memo: tuple[list[int], int, list[bytes]] | None = None
        # per-cohort LoRA plane (ISSUE 13, serve/adapter_pool.py): a second
        # small paged pool beside the KV pool. MoE is rejected at config
        # validation (batch-global expert capacity breaks per-slot adapter
        # purity), so no silent-ineligible branch is needed here.
        self.adapter_pool = None
        self.adapter_scale = 1.0
        ad = getattr(cfg.photon, "adapters", None)
        if ad is not None and ad.enabled:
            from photon_tpu.adapters.lora import spec_from_params
            from photon_tpu.serve.adapter_pool import AdapterPool

            spec = spec_from_params(
                self.params, ad.rank, ad.alpha, tuple(ad.targets)
            )
            self.adapter_pool = AdapterPool(spec, ad.pool_size)
            self.adapter_scale = spec.scale
            if adapter_bank:
                self.adapter_pool.install_bank(adapter_bank)
        self._adapter_spec = (
            self.adapter_pool.spec if self.adapter_pool is not None else None
        )
        #: per-slot adapter page (trash page = identity adapter); host
        #: mirror of the row ids the step gathers through
        self._adapter_rows = np.full(
            self.n_slots,
            self.adapter_pool.trash_page if self.adapter_pool else 0,
            np.int32,
        )
        self._slot_cohort: list[str | None] = [None] * self.n_slots
        self.state: PagedState = init_paged_state(
            self.mc, self.n_slots, self.n_blocks, self.block_size, self.max_blocks
        )
        self._keys = jnp.zeros((self.n_slots, 2), jnp.uint32)
        self._temps = jnp.zeros((self.n_slots,), jnp.float32)
        self._last = np.zeros(self.n_slots, np.int32)  # last emitted token
        self._lengths = np.zeros(self.n_slots, np.int32)  # host cursor mirror
        self._active = np.zeros(self.n_slots, bool)
        self._slot_blocks: list[list[int]] = [[] for _ in range(self.n_slots)]
        self._pending: dict[int, _Prefill] = {}  # slot -> chunk cursor
        mc = self.mc
        use_kernel, interp = self._use_kernel, self._interpret
        has_adapters = self.adapter_pool is not None
        a_spec, a_scale = self._adapter_spec, self.adapter_scale

        def step_fn(params, state, tokens, positions, q_valid, emit_off,
                    emit_mask, lengths_after, chunk_slot, temps, keys,
                    apool, arows, n_valid, dec_mask, *, n_ctx, has_chunk,
                    n_spec=1):
            adapters = None
            if has_adapters:
                # per-slot page gather (fixed shape: [B] rows into the
                # [P+1, ...] page stacks — cohort churn never retraces).
                # Pool leaves ride as ARGUMENTS: closure capture would
                # recompile on every page load.
                from photon_tpu.adapters.lora import adapter_tree

                adapters = adapter_tree(
                    a_spec, [leaf[arows] for leaf in apool]
                )
            logits, state = mixed_chunk_step(
                params, state, tokens, positions, q_valid, emit_off,
                lengths_after, chunk_slot, mc, n_ctx=n_ctx,
                has_chunk=has_chunk,
                impl="ragged" if use_kernel else "gather",
                interpret=interp,
                adapters=adapters, lora_scale=a_scale,
                n_spec=n_spec,
            )
            if n_spec == 1:
                sub = jax.vmap(jax.random.split)(keys)  # [B, 2, 2]
                nxt = _sample_rows(logits, temps, sub[:, 0])
                nxt = jnp.where(emit_mask, nxt, 0)
                # a slot's PRNG stream advances only when it emits: the
                # chunk schedule (how many steps a batch-mate's prefill
                # took) can never perturb another request's sampled
                # completion
                keys = jnp.where(emit_mask[:, None], sub[:, 1], keys)
                return state, nxt[:, None], emit_mask.astype(jnp.int32), keys
            # speculative grid (ISSUE 15): acceptance runs IN-GRAPH so a
            # draft burst costs one host round-trip, and decode rows'
            # lengths roll FORWARD only over accepted positions — the
            # rejected tail's KV bytes stay behind the k_pos <= position
            # mask until a later accepted write overwrites them
            out, n_em, keys = _verify_rows(
                logits, tokens, temps, keys, emit_mask, n_valid, n_spec
            )
            state = state.replace(lengths=jnp.where(
                dec_mask, positions[:, 0] + n_em, state.lengths
            ))
            return state, out, n_em, keys

        self._mixed_jit = jax.jit(
            step_fn, static_argnames=("n_ctx", "has_chunk", "n_spec"),
            donate_argnums=(1, 10),
        )
        self._install_jit = jax.jit(install_row, donate_argnums=0)

    # -- checkpoint loading ----------------------------------------------
    @classmethod
    def from_checkpoint(cls, cfg: Config, store: Any | None = None,
                        resume_round: int = -1) -> "PagedEngine":
        """Serve a federated run directly: resolve the (checksum-valid)
        round, load params ONLY, split off aggregated momenta if the run
        shipped them, restore onto the model template."""
        from photon_tpu.checkpoint import FileStore
        from photon_tpu.checkpoint.server import ServerCheckpointManager

        store = store or FileStore(cfg.photon.save_path + "/store")
        mgr = ServerCheckpointManager(store, cfg.run_uuid)
        adapters_on = (getattr(cfg.photon, "adapters", None) is not None
                       and cfg.photon.adapters.enabled)
        # adapter mode: round validity includes every cohort's adapter
        # object — a round missing one (cohort map grew since the save, or
        # a pre-adapter phase of the run) falls back to an older valid
        # round instead of crashing the daemon at the bank load
        state_keys: tuple[str, ...] = ()
        if adapters_on:
            from photon_tpu.adapters.checkpoint import adapter_key

            state_keys = tuple(
                adapter_key(c) for c in sorted(cfg.photon.adapters.cohorts)
            )
        rnd = mgr.resolve_resume_round(resume_round, state_keys)
        bank = None
        if adapters_on:
            from photon_tpu.adapters.checkpoint import load_adapter_bank

            bank = load_adapter_bank(mgr, rnd, cfg.photon.adapters.cohorts)
        return cls(cfg, load_serving_params(cfg, mgr, rnd), loaded_round=rnd,
                   adapter_bank=bank)

    def set_params(self, params: Any, loaded_round: int | None = None,
                   adapter_bank: dict | None = None) -> None:
        """The hot-swap reference assignment (ISSUE 11): install a new
        round's params. MUST be called from the scheduler driver thread at
        a swap point with zero active slots — in-flight requests always
        run end to end on one round's params. Flushes the prefix cache:
        KV computed under the old params is invalid under the new.

        ``adapter_bank`` (ISSUE 13) swaps the per-cohort adapters in the
        SAME quiesced assignment — base and adapters move atomically, and
        every resident pool page is dropped (factors trained against the
        old base are invalid under the new)."""
        if self._active.any():
            raise RuntimeError(
                f"param swap with {int(self._active.sum())} active slots — "
                "the scheduler must quiesce first"
            )
        self.params = jax.tree.map(jnp.asarray, params)
        self.loaded_round = loaded_round
        if self.adapter_pool is not None and adapter_bank is not None:
            self.adapter_pool.install_bank(adapter_bank)
        if self.prefix_cache is not None:
            self.prefix_cache.flush()

    # -- capacity ---------------------------------------------------------
    def blocks_needed(self, prompt_len: int, max_new: int) -> int:
        return -(-(prompt_len + max_new) // self.block_size)

    def fits(self, prompt_len: int, max_new: int) -> bool:
        """Static admissibility: can this request EVER run here? Bounded by
        the model's context window (``s_cap >= max_seq_len`` always, but a
        learned-wpe model has no positions past ``max_seq_len``)."""
        return (prompt_len >= 1
                and prompt_len + max_new <= min(self.s_cap, self.mc.max_seq_len)
                # ... and by the POOL size: a user-shrunk n_blocks smaller
                # than one request's reservation must reject at SUBMIT time,
                # or the request would queue behind a can_admit() that can
                # never pass and FIFO head-block the queue forever
                and self.blocks_needed(prompt_len, max_new)
                <= min(self.max_blocks, self.n_blocks))

    def has_cohort(self, cohort: str) -> bool:
        """Is ``cohort`` servable here (adapter plane on + bank entry)?"""
        return (self.adapter_pool is not None
                and self.adapter_pool.has_cohort(cohort))

    def can_admit(self, prompt_len: int, max_new: int,
                  prompt: list[int] | None = None,
                  cohort: str | None = None) -> bool:
        """With ``prompt`` given and the prefix cache on, admissibility
        accounts for cache hits (fewer fresh blocks needed) AND for
        reclaimable cache-held blocks (entries no live slot shares —
        evictable under pressure by :meth:`begin`'s ``ensure_free``).
        ``cohort`` additionally requires an acquirable adapter page
        (resident, free, or LRU-evictable)."""
        if self.free_slot() is None:
            return False
        if cohort is not None:
            if self.adapter_pool is None \
                    or not self.adapter_pool.can_acquire(cohort):
                return False
        hit, fresh_needed, _ = self._prefix_plan(
            prompt if prompt is not None else [], prompt_len, max_new,
            touch=False,
        )
        avail = self.allocator.free_blocks
        if self.prefix_cache is not None:
            avail += self.prefix_cache.reclaimable(exclude=set(hit))
        return avail >= fresh_needed

    def _prefix_plan(self, prompt: list[int], prompt_len: int, max_new: int,
                     touch: bool = True) -> tuple[list[int], int, list[bytes]]:
        """(cached-prefix physical blocks, fresh blocks still needed, the
        prompt's full-block chain hashes — ALL of them, up to
        ``prompt_len // block_size``, so admission can reuse this one
        sweep for both lookup and insert). Lookups are capped one block
        short of the prompt's end so the chunk stream always keeps at
        least the final prompt token — its forward pass produces the
        first sampled token's logits. ``touch=False`` = read-only peek
        (can_admit's per-tick retries must not reshuffle LRU order)."""
        need = self.blocks_needed(prompt_len, max_new)
        if self.prefix_cache is None or not prompt:
            return [], need, []
        hit = self.prefix_cache.lookup(
            self._chain_hashes(prompt, prompt_len)[
                : (prompt_len - 1) // self.block_size
            ],
            touch=touch,
        )
        return hit, need - len(hit), self._chain_hashes(prompt, prompt_len)

    def _chain_hashes(self, prompt: list[int], prompt_len: int) -> list[bytes]:
        """One chain-hash sweep per prompt LIST OBJECT: a single-slot memo
        keyed by identity (the memo holds the list alive, so the ``is``
        check can never alias a recycled id). Covers the can_admit→begin
        pair and a capacity-blocked queue head's per-tick retries —
        hashing is content-pure, so a stale entry is impossible."""
        memo = self._hash_memo
        if memo is not None and memo[0] is prompt and memo[1] == prompt_len:
            return memo[2]
        hashes = prefix_hashes(prompt, self.block_size,
                               limit=prompt_len // self.block_size)
        self._hash_memo = (prompt, prompt_len, hashes)
        return hashes

    def free_slot(self) -> int | None:
        idle = np.flatnonzero(~self._active)
        return int(idle[0]) if idle.size else None

    @property
    def n_active(self) -> int:
        return int(self._active.sum())

    @property
    def free_blocks(self) -> int:
        return self.allocator.free_blocks

    def pending_tokens(self, slot: int) -> int:
        """Prompt tokens still to prefill for ``slot`` (0 = decoding)."""
        p = self._pending.get(slot)
        return 0 if p is None else p.n - p.pos

    def prefix_stats(self) -> dict | None:
        """Prefix-cache counters for /healthz and the KPI tick (None when
        the cache is off)."""
        pc = self.prefix_cache
        if pc is None:
            return None
        return {
            "entries": len(pc),
            "hit_rate": round(pc.hit_rate, 4),
            "evictions": pc.evictions,
            "tokens_cached": pc.tokens_cached,
        }

    def adapter_stats(self) -> dict[str, float] | None:
        """Adapter-pool counters for /healthz and the KPI tick (None when
        the adapter plane is off)."""
        pool = self.adapter_pool
        return None if pool is None else pool.stats()

    def attn_stats(self) -> dict[str, float]:
        """Attention-plane gauges for the scheduler's KPI tick: the live
        walk width, the pool's live fraction, and whether the ragged walk
        (vs the full-width gather) is active."""
        return {
            "ctx_blocks": float(self.max_blocks if self._ctx_full
                                else self._ctx_hw),
            "live_frac": (self.n_blocks - self.allocator.free_blocks)
            / self.n_blocks,
            "ragged": 0.0 if self._ctx_full else 1.0,
        }

    # -- admission / step / eviction --------------------------------------
    def _bucket(self, n_tokens: int) -> int:
        """Chunk pad width: power-of-two BLOCK count (so the mixed step
        compiles at most log2(max_blocks)+1 distinct chunk widths), capped
        at the slot capacity. Also the pad rule that keeps the gather
        path BITWISE stable: XLA's row lowering is block-count invariant
        on the pinned shapes, single-row einsums are not."""
        need = max(1, -(-n_tokens // self.block_size))
        return min(_pow2_bucket(need), self.max_blocks) * self.block_size

    def _ctx_width(self) -> int:
        """The step's live attention width in blocks: pow2 bucket of the
        longest ACTIVE reservation, monotone high-water (never shrinks
        WHILE ANY SLOT IS LIVE) — a warm engine's compiled widths are a
        function of the traffic profile, not of which requests happened
        to overlap. The 'gather' impl pins it at full table width (the
        PR 5 cost model).

        A fully-idle engine resets the high-water (:meth:`evict` — ISSUE
        15 satellite): before the reset, one long request permanently
        inflated every later batch's attention width for the daemon's
        lifetime. The trade is a BOUNDED recompile exposure: after a
        reset, a traffic profile whose width sequence differs from the
        pre-reset warmup can reach pow2 widths that were never compiled —
        at most ``log2(max_blocks)+1`` of them, ever, because the bucket
        SET is the same pow2 family (jit caches persist across resets, so
        identical post-reset traffic replays the warm programs and the
        retrace sentinel stays green — pinned in tests)."""
        if self._ctx_full:
            return self.max_blocks
        need = max(
            (len(self._slot_blocks[s]) for s in range(self.n_slots)
             if self._active[s]),
            default=1,
        )
        w = min(_pow2_bucket(need), self.max_blocks)
        self._ctx_hw = max(self._ctx_hw, w)
        return self._ctx_hw

    def begin(self, slot: int, prompt: list[int], max_new: int,
              temperature: float = 0.0, seed: int = 0,
              cohort: str | None = None) -> None:
        """Reserve ``slot`` for a request and stage its chunk stream —
        the cheap half of admission (no model compute): reserve the worst
        case ``blocks_needed(len, max_new)`` blocks up front (an admitted
        request can never die of pool exhaustion mid-flight — the
        no-preemption design, docs/serving.md), install the block-table
        row, park the cursor at the prefix-hit depth. The prompt's
        (suffix) tokens then prefill through :meth:`mixed_step` chunks;
        the step whose chunk covers the final prompt token emits the
        request's first sampled token.

        With the prefix cache on, the longest cached full-block prefix is
        mapped copy-on-write into the slot's table (one retain per shared
        block — never written: every chunk/decode write lands strictly
        past it) and the chunk stream starts at the cached depth."""
        if self._active[slot]:
            raise RuntimeError(f"slot {slot} is occupied")
        n = len(prompt)
        if not self.fits(n, max_new):
            raise ValueError(
                f"request needs {n}+{max_new} tokens > slot capacity {self.s_cap}"
            )
        apage: int | None = None
        if cohort is not None:
            if self.adapter_pool is None:
                raise ValueError(
                    f"request names cohort {cohort!r} but this server has "
                    "no adapter plane (photon.adapters disabled)"
                )
            # pin the cohort's page FIRST (one allocator reference per
            # slot; a miss loads it — evicting the LRU unpinned resident).
            # Not a lock: a refcount checkout, released by evict() at slot
            # teardown and by the except arm below on a failed admission.
            apage = self.adapter_pool.acquire(cohort)  # photon-lint: ignore[concurrency]
        hit, fresh_needed, hashes = self._prefix_plan(prompt, n, max_new)
        k = len(hit)
        ids: list[int] | None = None
        retained = False
        try:
            if hit:
                # pin the shared blocks BEFORE any eviction can run: an
                # ensure_free dropping a hit entry now only un-indexes it
                # (our reference keeps the block — and its bytes — live)
                self.allocator.retain(hit)
                retained = True
            pc = self.prefix_cache
            if pc is not None and fresh_needed > self.allocator.free_blocks:
                pc.ensure_free(fresh_needed)
            ids = self.allocator.alloc(fresh_needed)
            if ids is None:
                raise RuntimeError(
                    "paged pool exhausted (caller must can_admit first)"
                )
            row_blocks = hit + ids
            row = np.full(self.max_blocks, self.n_blocks, np.int32)
            row[: len(row_blocks)] = row_blocks
            start = k * self.block_size
            self.state = self._install_jit(
                self.state, jnp.int32(slot), jnp.asarray(row), jnp.int32(start)
            )
        except BaseException:
            # transactional: a failed admission must not leak its blocks
            # (fresh allocations AND the references it took on shared
            # ones). A partially-written table row is harmless — the
            # mixed step trash-routes every pad/idle row's writes, and
            # re-admission overwrites the row
            if ids is not None:
                self.allocator.free(ids)
            if retained:
                self.allocator.free(hit)
            if apage is not None:
                self.adapter_pool.release(apage)
            raise
        if self.adapter_pool is not None:
            self._adapter_rows[slot] = (
                apage if apage is not None else self.adapter_pool.trash_page
            )
        self._slot_cohort[slot] = cohort
        self._keys = self._keys.at[slot].set(jax.random.PRNGKey(seed))
        self._temps = self._temps.at[slot].set(float(temperature))
        self._slot_blocks[slot] = row_blocks
        self._active[slot] = True
        self._lengths[slot] = start
        self._last[slot] = 0
        self._pending[slot] = _Prefill(
            prompt=list(prompt), pos=start, n=n, hashes=hashes,
            row_blocks=row_blocks,
        )
        if self.prefix_cache is not None:
            self.prefix_cache.tokens_seen += n
            self.prefix_cache.tokens_cached += k * self.block_size

    def _spec_bucket(self, n: int) -> int:
        """Verify-grid width: pow2 bucket of ``1 + max drafts`` so the
        speculative step compiles at most ``log2(k)+2`` distinct widths
        (the same discipline as :meth:`_bucket`'s chunk widths)."""
        return _pow2_bucket(n)

    def mixed_step(self, chunk: tuple[int, int] | None = None, *,
                   include_decode: bool = True
                   ) -> tuple[np.ndarray, np.ndarray]:
        """ONE unified serving step: every active non-prefilling slot
        decodes its last token; ``chunk = (slot, n_tokens)`` additionally
        advances that slot's prompt by up to ``n_tokens`` positions.
        Returns ``(next_token [n_slots], emitted [n_slots])`` — a decode
        row emits every step, a prefilling slot emits exactly once, on
        the step whose chunk covers its final prompt token (the request's
        FIRST sampled token). ``include_decode=False`` runs the chunk
        alone (the synchronous :meth:`admit` path — batch-mates' streams
        must not advance)."""
        out, n_em = self._grid_step(chunk, include_decode, {})
        return out[:, 0], n_em > 0

    def spec_step(self, chunk: tuple[int, int] | None = None,
                  drafts: dict[int, list[int]] | None = None, *,
                  include_decode: bool = True
                  ) -> tuple[np.ndarray, np.ndarray]:
        """The speculative generalization of :meth:`mixed_step` (ISSUE
        15): ``drafts`` maps decoding slots to proposed continuation
        tokens; EVERY drafted row verifies its whole draft in this one
        step. Returns ``(tokens [n_slots, n_spec], n_emitted [n_slots])``
        — row ``s`` emitted ``tokens[s, :n_emitted[s]]``, in order (the
        accepted draft prefix plus one model-sampled token; exactly one
        token for draft-less rows, so ``drafts={}`` degenerates to the
        classic step on the classic compiled program)."""
        return self._grid_step(chunk, include_decode, drafts or {})

    def _grid_step(self, chunk: tuple[int, int] | None,
                   include_decode: bool, drafts: dict[int, list[int]]
                   ) -> tuple[np.ndarray, np.ndarray]:
        B = self.n_slots
        decode_slots = [
            s for s in range(B)
            if include_decode and self._active[s] and s not in self._pending
        ]
        # defensive trim: a draft may never write past the slot's block
        # reservation (the scheduler already caps by remaining max_new;
        # positions len..len+K must stay inside the reserved row)
        drafts = {
            s: d[: max(0, len(self._slot_blocks[s]) * self.block_size
                       - int(self._lengths[s]) - 1)]
            for s, d in drafts.items()
            if s in decode_slots and d
        }
        drafts = {s: d for s, d in drafts.items() if d}
        n_spec = self._spec_bucket(
            1 + max((len(d) for d in drafts.values()), default=0)
        )
        seg: list[int] = []
        cs = 0
        final = False
        if chunk is not None:
            cs, want = chunk
            p = self._pending[cs]
            cn = min(want, p.n - p.pos)
            if cn < 1:
                raise RuntimeError(f"slot {cs} has no pending prompt tokens")
            seg = p.prompt[p.pos: p.pos + cn]
            final = p.pos + cn == p.n
        if not seg and not decode_slots:
            raise RuntimeError("mixed_step with no work")
        tq = max(self._bucket(len(seg)) if seg else 1, n_spec)
        tokens = np.zeros((B, tq), np.int32)
        positions = np.zeros((B, tq), np.int32)
        q_valid = np.zeros((B, tq), bool)
        emit_off = np.zeros(B, np.int32)
        emit_mask = np.zeros(B, bool)
        n_valid = np.ones(B, np.int32)
        dec_mask = np.zeros(B, bool)
        lengths_after = self._lengths.copy()
        for s in decode_slots:
            ds = drafts.get(s, [])
            nv = 1 + len(ds)
            tokens[s, 0] = self._last[s]
            if ds:
                tokens[s, 1:nv] = ds
            positions[s, :nv] = np.arange(self._lengths[s],
                                          self._lengths[s] + nv)
            q_valid[s, :nv] = True
            emit_mask[s] = True
            n_valid[s] = nv
            dec_mask[s] = True
            if n_spec == 1:
                lengths_after[s] += 1
            # n_spec > 1: the device step rolls decode rows' lengths
            # forward by the ACCEPTED count (dec_mask gates the splice) —
            # the host mirror catches up from n_emitted below
        if seg:
            p = self._pending[cs]
            cn = len(seg)
            tokens[cs, :cn] = seg
            positions[cs, :cn] = np.arange(p.pos, p.pos + cn)
            q_valid[cs, :cn] = True
            lengths_after[cs] = p.pos + cn
            if final:
                emit_off[cs] = cn - 1
                emit_mask[cs] = True
        pool = self.adapter_pool
        # n_spec rides as a kwarg ONLY when drafting widened the grid, so
        # pre-speculative _mixed_call overrides (test seams, spies) keep
        # working untouched on every classic step
        spec_kw = {} if n_spec == 1 else {"n_spec": n_spec}
        self.state, nxt, n_emitted, self._keys = self._mixed_call(
            self._ctx_width(), bool(seg), self.params, self.state,
            jnp.asarray(tokens), jnp.asarray(positions), jnp.asarray(q_valid),
            jnp.asarray(emit_off), jnp.asarray(emit_mask),
            jnp.asarray(lengths_after), jnp.int32(cs), self._temps, self._keys,
            pool.leaves() if pool is not None else (),
            jnp.asarray(self._adapter_rows),
            jnp.asarray(n_valid), jnp.asarray(dec_mask),
            **spec_kw,
        )
        out = np.asarray(nxt)  # [B, n_spec]
        n_em = np.asarray(n_emitted)  # [B]
        self._lengths = lengths_after
        for s in decode_slots:
            n = int(n_em[s])
            if n_spec > 1:
                self._lengths[s] += n
            self._last[s] = out[s, max(0, n - 1)]
        if seg:
            p = self._pending[cs]
            p.pos += len(seg)
            if final:
                self._last[cs] = out[cs, 0]
                self._finish_prefill(cs, p)
        return out, n_em

    def _mixed_call(self, n_ctx: int, has_chunk: bool, *args,
                    n_spec: int = 1):
        """The one seam between host bookkeeping and the donated device
        call (tests inject failures here: raising BEFORE the jitted call
        leaves the donated state untouched, so a failed step is
        recoverable at the scheduler layer)."""
        return self._mixed_jit(*args, n_ctx=n_ctx, has_chunk=has_chunk,
                               n_spec=n_spec)

    def _finish_prefill(self, slot: int, p: _Prefill) -> None:
        """Prompt fully prefilled: index its full blocks for the next
        request. Insertion waits until HERE — the blocks' KV exists only
        once every chunk has run, and indexing earlier could hand another
        admission unwritten bytes."""
        del self._pending[slot]
        if self.prefix_cache is not None:
            full = p.n // self.block_size
            self.prefix_cache.insert(p.hashes, p.row_blocks[:full])

    def admit(self, slot: int, prompt: list[int], max_new: int,
              temperature: float = 0.0, seed: int = 0,
              cohort: str | None = None) -> int:
        """Synchronous admission (compat shim over the chunked flow, used
        by tests and offline callers): stage the request and run its whole
        suffix as ONE chunk — no decode ride-alongs, so batch-mates'
        streams don't advance — returning the first sampled token. The
        scheduler's chunked path (:meth:`begin` + budgeted
        :meth:`mixed_step`) is the serving-loop route."""
        self.begin(slot, prompt, max_new, temperature=temperature, seed=seed,
                   cohort=cohort)
        first: int | None = None
        while self.pending_tokens(slot) > 0:
            nxt, emitted = self.mixed_step(
                (slot, self.pending_tokens(slot)), include_decode=False
            )
            if emitted[slot]:
                first = int(nxt[slot])
        assert first is not None  # the final chunk always emits
        return first

    def step(self) -> np.ndarray:
        """One decode step for every active non-prefilling slot; returns
        next token ids ``[n_slots]`` (zeros at inactive slots — callers
        mask by activity). Each active slot's previously-emitted token is
        placed at its cursor, so the returned ids are each sequence's
        NEXT token."""
        if not self._active.any():
            raise RuntimeError("no active slots")
        out, _ = self.mixed_step(None)
        return out

    def evict(self, slot: int) -> None:
        """Return ``slot``'s blocks to the free list — pure host
        bookkeeping: the mixed step trash-routes inactive slots' writes,
        so the stale table row needs no device-side reset, and recycled
        pool bytes are NOT cleared (the position mask makes stale rows
        unreadable)."""
        if not self._active[slot]:
            raise RuntimeError(f"slot {slot} is not active")
        self.allocator.free(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self._pending.pop(slot, None)
        if self._slot_cohort[slot] is not None:
            # drop this slot's pin; the page stays resident for the next
            # same-cohort admission until LRU pressure evicts it
            self.adapter_pool.release(int(self._adapter_rows[slot]))
            self._adapter_rows[slot] = self.adapter_pool.trash_page
            self._slot_cohort[slot] = None
        self._active[slot] = False
        self._last[slot] = 0
        self._lengths[slot] = 0
        if not self._active.any():
            # fully idle: drop the live-width high-water (ISSUE 15
            # satellite) so one long-dead request stops inflating every
            # later batch's attention width. Compiled widths stay cached
            # in _mixed_jit, so re-warming the same traffic profile
            # compiles nothing — see _ctx_width for the bounded-recompile
            # trade on a CHANGED profile
            self._ctx_hw = 1
