"""Paged KV cache — block-pool storage for the serving plane.

The contiguous :class:`~photon_tpu.models.decode.DecodeState` allocates
``[B, S, H_kv, Dh]`` per layer per sequence — a 12-token prompt in a
2048-token buffer pays for 2048 rows. The serving engine instead keeps ONE
fixed pool of KV blocks shared by every slot (the Ragged Paged Attention
shape, PAPERS.md arxiv 2604.15464):

- **pool**: ``cache_k/cache_v`` of shape ``[n_blocks + 1, L, block_size,
  H_kv, Dh]``. The LAST block is the trash block — never allocated, it
  absorbs the fixed-shape writes of empty slots so the jitted step needs no
  per-slot control flow.
- **block tables**: ``[n_slots, max_blocks]`` int32 mapping each slot's
  logical block ``j`` (tokens ``[j*bs, (j+1)*bs)``) to a physical pool
  block; unassigned entries point at the trash block.
- **free list**: a host-side :class:`BlockAllocator` recycles physical
  blocks between requests (allocation policy — reserve-at-admission — lives
  in the scheduler; this module only enforces no-double-alloc/free).

:func:`paged_decode_step` mirrors ``models/decode.py:decode_step`` op for
op — same RoPE/ALiBi math, same grouped-query einsums, same masking — with
the contiguous cache replaced by a block-table gather and the one-hot
cache write replaced by a scatter at ``(physical_block, offset)``. Masked
positions contribute exactly-zero probability either way, so greedy decode
through the paged pool gives the contiguous path's tokens
(``tests/test_serve.py`` pins logits AND tokens with assert_array_equal on
the CPU backend).

TPU note: the pool's layer axis sits second (``[N, L, bs, H, D]`` — block
major, so a block is one contiguous alloc unit); the step scans layers via
a ``moveaxis`` view, which XLA folds into the gather.

ISSUE 12 adds :func:`mixed_chunk_step` — ONE program that processes decode
rows and prompt chunks together (chunked prefill), attends through the
block tables at a static LIVE width ``n_ctx`` (the ragged walk: cost
scales with live tokens, not pool capacity), and dispatches the per-layer
attention between the gather reference and the fused Pallas
ragged-paged-attention kernel (``ops/ragged_paged_attention.py``,
epsilon-tier). :func:`paged_decode_step` stays as the full-width oracle
the parity harness compares against.
"""

from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp

from photon_tpu.config.schema import ModelConfig
from photon_tpu.models.decode import _dense, _embed, _logits, _mlp, _norm, _qkv, _rope_at
from photon_tpu.ops.attention import alibi_slopes


class BlockLeakError(RuntimeError):
    """Double-free / foreign-id free — a block-accounting bug, never user error."""


class BlockAllocator:
    """Host-side free list over physical block ids ``[0, n_blocks)``.

    LIFO recycling (a just-freed block is the next handed out) keeps the
    hot working set small. Guards double-free and foreign ids: the
    scheduler's no-leak invariant is only as strong as this accounting.

    Blocks are REFCOUNTED (ISSUE 11): ``alloc`` hands out ids at refcount
    1, :meth:`retain` adds a reference (the prefix cache sharing a block
    into another slot's table, or pinning it in its LRU), and :meth:`free`
    decrements — only a refcount hitting zero returns the block to the
    free list. The double-free guard survives sharing: freeing an id with
    no outstanding reference still raises :class:`BlockLeakError`.
    """

    def __init__(self, n_blocks: int) -> None:
        if n_blocks < 1:
            raise ValueError(f"need n_blocks >= 1, got {n_blocks}")
        self.n_blocks = n_blocks
        self._free: list[int] = list(range(n_blocks - 1, -1, -1))
        self._refs: dict[int, int] = {}

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def held_blocks(self) -> int:
        return len(self._refs)

    def refcount(self, block: int) -> int:
        """Outstanding references on ``block`` (0 = on the free list)."""
        return self._refs.get(block, 0)

    def alloc(self, n: int) -> list[int] | None:
        """``n`` physical ids at refcount 1, or None (and NO partial
        allocation) when the pool can't cover the request."""
        if n < 0:
            raise ValueError(f"need n >= 0, got {n}")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for b in ids:
            self._refs[b] = 1
        return ids

    def retain(self, ids: list[int]) -> None:
        """One more reference on each (already held) id — the copy-on-write
        share: a block mapped into a second slot's table, or indexed by the
        prefix cache. Retaining a free/foreign id is a BlockLeakError (it
        would resurrect a block the free list may hand out again)."""
        for b in ids:
            if b not in self._refs:
                raise BlockLeakError(f"retaining block {b} not currently held")
        for b in ids:
            self._refs[b] += 1

    def free(self, ids: list[int]) -> None:
        """Drop one reference per id; refcount-zero blocks return to the
        free list. A shared block survives until its LAST holder frees."""
        for b in ids:
            refs = self._refs.get(b, 0)
            if refs < 1:
                raise BlockLeakError(f"freeing block {b} not currently held")
            if refs == 1:
                del self._refs[b]
                self._free.append(b)
            else:
                self._refs[b] = refs - 1


@flax.struct.dataclass
class PagedState:
    """Device-side serving state — every array fixed-shape so the engine's
    step jit never retraces on admission/eviction."""

    cache_k: jax.Array  # [n_blocks + 1, L, block_size, H_kv, Dh]
    cache_v: jax.Array
    block_tables: jax.Array  # [n_slots, max_blocks] int32 physical ids
    lengths: jax.Array  # [n_slots] int32 per-slot token counts

    @property
    def block_size(self) -> int:
        return self.cache_k.shape[2]

    @property
    def trash_block(self) -> int:
        return self.cache_k.shape[0] - 1

    @property
    def n_slots(self) -> int:
        return self.block_tables.shape[0]


def init_paged_state(cfg: ModelConfig, n_slots: int, n_blocks: int,
                     block_size: int, max_blocks: int) -> PagedState:
    n_kv = cfg.n_kv_heads or cfg.n_heads
    dtype = jnp.dtype(cfg.compute_dtype)
    shape = (n_blocks + 1, cfg.n_layers, block_size, n_kv, cfg.d_head)
    return PagedState(
        cache_k=jnp.zeros(shape, dtype),
        cache_v=jnp.zeros(shape, dtype),
        block_tables=jnp.full((n_slots, max_blocks), n_blocks, jnp.int32),
        lengths=jnp.zeros((n_slots,), jnp.int32),
    )


def write_prefill_blocks(state: PagedState, slot: int, block_ids: list[int],
                         cache_k: jax.Array, cache_v: jax.Array,
                         length: int) -> PagedState:
    """Scatter a contiguous prefill cache (``[L, 1, S_pad, H_kv, Dh]`` from
    ``models/decode.py:prefill`` — so prefill numerics stay pinned by the
    existing parity tests) into ``len(block_ids)`` pool blocks and point
    ``slot``'s table at them.

    Only the blocks covering the prompt need rows here; reserved blocks
    beyond them are listed in the table but written lazily by the decode
    step — position ``p`` is always scattered before any step reads it
    (``valid`` admits ``p`` exactly at the step that writes it)."""
    bs = state.block_size
    n_pb = len(block_ids)
    need = -(-length // bs)  # ceil: blocks that actually hold prompt rows
    if need > n_pb:
        raise ValueError(f"{n_pb} blocks cannot hold a {length}-token prompt")
    if cache_k.shape[2] < need * bs:
        raise ValueError(
            f"prefill cache covers {cache_k.shape[2]} rows < {need * bs} needed"
        )
    L = cache_k.shape[0]
    ids = jnp.asarray(block_ids[:need], jnp.int32) if need else None
    if need:
        # [L, 1, S, H, D] → [L, need, bs, H, D] → block-major [need, L, bs, H, D]
        kb = cache_k[:, 0, : need * bs].reshape(L, need, bs, *cache_k.shape[3:])
        vb = cache_v[:, 0, : need * bs].reshape(L, need, bs, *cache_v.shape[3:])
        ck = state.cache_k.at[ids].set(kb.swapaxes(0, 1).astype(state.cache_k.dtype))
        cv = state.cache_v.at[ids].set(vb.swapaxes(0, 1).astype(state.cache_v.dtype))
    else:
        ck, cv = state.cache_k, state.cache_v
    row = jnp.full((state.block_tables.shape[1],), state.trash_block, jnp.int32)
    row = row.at[: n_pb].set(jnp.asarray(block_ids, jnp.int32)) if n_pb else row
    return PagedState(
        cache_k=ck,
        cache_v=cv,
        block_tables=state.block_tables.at[slot].set(row),
        lengths=state.lengths.at[slot].set(length),
    )


def install_row(state: PagedState, slot: jax.Array, row: jax.Array,
                length: jax.Array) -> PagedState:
    """Admission bookkeeping as one tiny program: point ``slot``'s table
    at its reserved (possibly prefix-shared) physical blocks and park its
    cursor at the cached-prefix depth. No KV moves — the chunk stream
    (:func:`mixed_chunk_step`) writes the suffix KV as it prefills."""
    return PagedState(
        cache_k=state.cache_k,
        cache_v=state.cache_v,
        block_tables=state.block_tables.at[slot].set(row),
        lengths=state.lengths.at[slot].set(length),
    )


def mixed_chunk_step(params: dict, state: PagedState, tokens: jax.Array,
                     positions: jax.Array, q_valid: jax.Array,
                     emit_off: jax.Array, lengths_after: jax.Array,
                     chunk_slot: jax.Array, cfg: ModelConfig, *, n_ctx: int,
                     has_chunk: bool = False, impl: str = "gather",
                     interpret: bool = False, adapters: dict | None = None,
                     lora_scale: float = 1.0,
                     n_spec: int = 1) -> tuple[jax.Array, PagedState]:
    """ONE serving program for a mixed chunked-prefill batch (ISSUE 12):
    every slot contributes a row of ``tokens [n_slots, Tq]`` — a decode
    row places its single last-emitted token in column 0 (rest padding),
    the ``chunk_slot`` row (``has_chunk``) places its next prompt chunk,
    idle slots are all padding — and attention runs through the block
    tables at the static LIVE width ``n_ctx`` blocks (the ragged walk:
    cost scales with the longest live slot, never with pool capacity).
    Returns (logits ``[n_slots, V]`` at each slot's ``emit_off`` column,
    advanced state with ``lengths_after`` installed).

    This unifies the PR 5 prefill/decode program pair. The gather path's
    two attention sub-graphs are op-for-op the two programs this step
    replaces, but XLA picks each program's summation order itself, so what
    is checked is a tolerance, not bits: every emission's logits within
    ``tests/_helpers.SERVE_LOGITS_ATOL`` of the contiguous decoder's and
    the same argmax (``tests/test_ragged_attention.py``,
    ``tests/test_serve_prefix.py``) —

    - **decode columns** (column 0 of every slot) run exactly
      :func:`paged_decode_step`'s grouped einsum
      (``bkgd,bskd->bkgs``) over the table gather; masked tail
      positions past ``n_ctx`` carry exactly-zero probability, so the
      live-width cut is bitwise-invisible;
    - **the chunk row** runs exactly :func:`suffix_prefill_admit`'s
      per-slot einsum (``qkgd,skd->qkgs``) against its own gathered
      view, and is spliced over the chunk slot's row with one dynamic
      update (``chunk_slot`` rides traced — chunk depth, slot id and
      prefix-hit depth never retrace). A prefix-cache hit just shortens
      the chunk stream: the first chunk's positions start at the cached
      depth (PR 10's suffix prefill is the single-chunk special case).

    Shared discipline (mirrors the programs it replaces): each layer
    scatters every real token's k/v at ``(table[slot, pos//bs],
    pos%bs)`` BEFORE any gather (chunk tokens attend to their own
    chunk's earlier positions); padding rows write to the trash block
    (``q_valid`` is the write mask) and read nothing (visibility is one
    comparison, ``k_pos <= position`` — causality inside a chunk, the
    live-length bound, and recycled bytes behind stale table entries
    all at once). ``Tq`` and ``n_ctx`` are pow2-bucketed by the engine;
    everything else is fixed-shape (the no-retrace discipline).

    MoE caveat (``cfg.mlp == "moe"``): expert-capacity routing is
    BATCH-GLOBAL (every row in the step competes for one capacity pool —
    true of the PR 5 step too, where even idle slots' unmasked rows
    claimed capacity), so neither the bit-parity-with-contiguous claim
    nor batch-mate independence holds there; serving MoE is best-effort,
    exactly as before. ``token_mask=q_valid`` at least keeps pad/idle
    rows from claiming capacity — strictly less cross-row interference
    than the PR 5 step, not more.

    ``impl="ragged"`` swaps both attention sub-graphs for the fused
    online-softmax Pallas kernel
    (``ops/ragged_paged_attention.py``) — the EPSILON tier
    (``interpret`` runs it through the Pallas interpreter off-TPU).

    ``adapters`` (ISSUE 13): per-SLOT LoRA factors gathered from the
    adapter pool — ``{module: {"a": [B, L, d_in, r], "b": [B, L, r,
    d_out]}}``, scaled by ``lora_scale``. Row b's projections add row b's
    delta (``models/decode._lora_delta``) — one mixed batch decodes
    requests from different cohorts, and a trash-page row (all-zero
    factors) decodes the bare base through the same graph. None keeps the
    step byte-identical to the adapter-free build.

    ``n_spec`` (ISSUE 15, speculative decoding): with ``n_spec > 1``,
    EVERY decode row may carry up to ``n_spec`` consecutive tokens
    (``[last_emitted, draft_1, .., draft_K]`` at positions ``[len, ..,
    len+K]``) and the step returns TRUE logits at every one of the first
    ``n_spec`` columns — ``[n_slots, n_spec, V]`` instead of ``[n_slots,
    V]`` — so the engine can verify all rows' drafts in one program.
    Each verified column's attention is computed op-for-op the decode
    einsum above (NOT the chunk einsum): per-position logits are then
    BITWISE what ``n_spec`` sequential single-token steps would have
    produced (projections are row-stable across the padded token width on
    this backend — the same property the PR 11/12 decode-rows-ride-chunk
    parity already leaned on — and every masked gather position
    contributes exactly-zero probability, so KV bytes scattered this step
    by later columns, or left stale by a previous step's rejected drafts,
    are bitwise invisible to earlier columns; pinned by
    ``tests/test_speculative.py``). The chunk row (``has_chunk``) still
    emits from its ``emit_off`` column, replicated across the logits
    axis. ``n_spec == 1`` keeps the graph byte-identical to the
    pre-speculative build.
    """
    from photon_tpu.models.decode import _layer_adapters
    from photon_tpu.ops.ragged_paged_attention import ragged_paged_attention

    n_kv = cfg.n_kv_heads or cfg.n_heads
    group = cfg.n_heads // n_kv
    bs = state.block_size
    n_slots, tq = tokens.shape
    s_ctx = n_ctx * bs
    scale = 1.0 / (cfg.d_head ** 0.5)
    x = _embed(params, tokens, positions, cfg)  # [B, Tq, D]
    # physical write target per token: pad rows → trash (idle slots and
    # slot padding never touch live blocks; eviction stays pure host
    # bookkeeping exactly as in paged_decode_step)
    blk = jnp.minimum(positions // bs, state.block_tables.shape[1] - 1)
    phys = jnp.take_along_axis(state.block_tables, blk, axis=1)  # [B, Tq]
    phys = jnp.where(q_valid, phys, state.trash_block)
    off = positions % bs
    rows = jax.lax.slice_in_dim(state.block_tables, 0, n_ctx, axis=1)
    k_pos = jnp.arange(s_ctx)
    # decode-column positions/masks: one per VERIFIED column (n_spec == 1
    # is the classic single-decode-column step)
    pos_cols = [positions[:, i] for i in range(n_spec)]
    valid_cols = [k_pos[None, :] <= p[:, None] for p in pos_cols]  # [B, s_ctx]
    pos0 = pos_cols[0]
    if has_chunk:
        pos_c = jax.lax.dynamic_index_in_dim(
            positions, chunk_slot, axis=0, keepdims=False
        )  # [Tq]
        row_c = jax.lax.dynamic_index_in_dim(
            rows, chunk_slot, axis=0, keepdims=False
        )  # [n_ctx]
        valid_c = k_pos[None, :] <= pos_c[:, None]  # [Tq, s_ctx]
    valid_f = q_valid.astype(jnp.float32)

    ck_l = jnp.moveaxis(state.cache_k, 1, 0)  # [L, NB, bs, H, D] view
    cv_l = jnp.moveaxis(state.cache_v, 1, 0)
    ad_l = _layer_adapters(adapters)

    def layer(x, xs):
        if adapters is not None:
            lp, ck, cv, la = xs
        else:
            (lp, ck, cv), la = xs, None  # ck/cv: [NB, bs, H_kv, Dh]
        h = _norm(x, lp["ln_1"]["scale"], lp["ln_1"].get("bias"),
                  cfg.norm, cfg.norm_eps)
        q, k_new, v_new = _qkv(lp, h, cfg, la, lora_scale)  # q [B,Tq,H,Dh]
        if cfg.rope:
            q = _rope_at(q, positions, cfg.rope_theta)
            k_new = _rope_at(k_new, positions, cfg.rope_theta)
        # scatter first (write → gather): every real token's k/v lands at
        # its (physical block, offset) before any row reads it
        ck = ck.at[phys, off].set(k_new.astype(ck.dtype))
        cv = cv.at[phys, off].set(v_new.astype(cv.dtype))
        if impl == "ragged":
            out_spec = ragged_paged_attention(
                q[:, :n_spec], ck, cv, rows, positions[:, :n_spec],
                scale=scale,
                slopes=alibi_slopes(cfg.n_heads) if cfg.alibi else None,
                interpret=interpret,
            )  # [B, n_spec, H, Dh]
        else:
            gk = ck[rows].reshape(n_slots, s_ctx, n_kv, cfg.d_head)
            gv = cv[rows].reshape(n_slots, s_ctx, n_kv, cfg.d_head)

            def dec_col(i):
                # one verified column: op-for-op paged_decode_step. The
                # shared gather is safe bitwise — columns > i's scatters
                # sit past this column's position, where the mask makes
                # their probability exactly zero
                qg = q[:, i].reshape(n_slots, n_kv, group, cfg.d_head)
                scores = jnp.einsum("bkgd,bskd->bkgs", qg, gk,
                                    preferred_element_type=jnp.float32) * scale
                if cfg.alibi:
                    dist = (pos_cols[i][:, None]
                            - k_pos[None, :]).astype(jnp.float32)
                    slopes = alibi_slopes(cfg.n_heads).reshape(n_kv, group)
                    scores = scores - slopes[None, :, :, None] * dist[:, None, None, :]
                scores = jnp.where(valid_cols[i][:, None, None, :],
                                   scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(gv.dtype), gv)
                return out.reshape(n_slots, cfg.n_heads, cfg.d_head)

            out_spec = jnp.stack([dec_col(i) for i in range(n_spec)], axis=1)
        attn = jnp.broadcast_to(
            out_spec[:, :1], (n_slots, tq, cfg.n_heads, cfg.d_head)
        )
        if n_spec > 1:
            attn = jax.lax.dynamic_update_slice_in_dim(
                attn, out_spec.astype(attn.dtype), 0, axis=1
            )
        if has_chunk:
            qc = jax.lax.dynamic_index_in_dim(
                q, chunk_slot, axis=0, keepdims=False
            )  # [Tq, H, Dh]
            if impl == "ragged":
                out_c = ragged_paged_attention(
                    qc[None], ck, cv, row_c[None], pos_c[None], scale=scale,
                    slopes=alibi_slopes(cfg.n_heads) if cfg.alibi else None,
                    interpret=interpret,
                )[0]  # [Tq, H, Dh]
            else:
                # the chunk row: op-for-op suffix_prefill_admit
                gkc = ck[row_c].reshape(s_ctx, n_kv, cfg.d_head)
                gvc = cv[row_c].reshape(s_ctx, n_kv, cfg.d_head)
                qcg = qc.reshape(tq, n_kv, group, cfg.d_head)
                sc = jnp.einsum("qkgd,skd->qkgs", qcg, gkc,
                                preferred_element_type=jnp.float32) * scale
                if cfg.alibi:
                    dist = (pos_c[:, None] - k_pos[None, :]).astype(jnp.float32)
                    slopes = alibi_slopes(cfg.n_heads).reshape(n_kv, group)
                    sc = sc - slopes[None, :, :, None] * dist[:, None, None, :]
                sc = jnp.where(valid_c[:, None, None, :], sc, -jnp.inf)
                pc = jax.nn.softmax(sc, axis=-1)
                out_c = jnp.einsum("qkgs,skd->qkgd", pc.astype(gvc.dtype), gvc)
                out_c = out_c.reshape(tq, cfg.n_heads, cfg.d_head)
            attn = jax.lax.dynamic_update_index_in_dim(
                attn, out_c.astype(attn.dtype), chunk_slot, axis=0
            )
        x = x + _dense(lp, "out_proj",
                       attn.reshape(n_slots, tq, cfg.d_model),
                       la, lora_scale)
        return _mlp(lp, x, cfg, token_mask=valid_f, la=la,
                    ls=lora_scale), (ck, cv)

    xs = (params["blocks"]["block"], ck_l, cv_l)
    if adapters is not None:
        xs = xs + (ad_l,)
    x, (ck_l, cv_l) = jax.lax.scan(layer, x, xs)
    if n_spec == 1:
        last = jnp.take_along_axis(x, emit_off[:, None, None], axis=1)[:, 0]
        lg = _logits(params, last, cfg)  # [B, V]
    else:
        # the verify grid: decode rows read columns 0..n_spec-1; the chunk
        # row reads its emit column (replicated — its later acceptance
        # loop only ever consumes emission 0)
        vcols = jnp.broadcast_to(
            jnp.arange(n_spec, dtype=jnp.int32), (n_slots, n_spec)
        )
        if has_chunk:
            off_c = jax.lax.dynamic_index_in_dim(
                emit_off, chunk_slot, keepdims=False
            )
            vcols = jax.lax.dynamic_update_index_in_dim(
                vcols, jnp.full((n_spec,), off_c, jnp.int32), chunk_slot,
                axis=0,
            )
        sel = jnp.take_along_axis(x, vcols[:, :, None], axis=1)  # [B,n_spec,D]
        lg = _logits(params, sel, cfg)  # [B, n_spec, V]
    return lg, PagedState(
        cache_k=jnp.moveaxis(ck_l, 0, 1),
        cache_v=jnp.moveaxis(cv_l, 0, 1),
        block_tables=state.block_tables,
        lengths=lengths_after,
    )


def paged_decode_step(params: dict, state: PagedState, token: jax.Array,
                      cfg: ModelConfig,
                      active: jax.Array) -> tuple[jax.Array, PagedState]:
    """One decode step over ALL slots: place ``token [n_slots]`` at each
    ACTIVE slot's cursor (inactive slots write into the trash block and
    don't advance), attend through the block tables, return (logits
    ``[n_slots, V]``, advanced state). Mirrors ``decode_step`` exactly —
    see the module docstring for the argument."""
    n_kv = cfg.n_kv_heads or cfg.n_heads
    group = cfg.n_heads // n_kv
    bs = state.block_size
    n_slots, m = state.block_tables.shape
    s = m * bs
    pos = state.lengths  # [B] — where this token lands
    x = _embed(params, token, pos, cfg)  # [B, D]
    scale = 1.0 / (cfg.d_head ** 0.5)
    k_pos = jnp.arange(s)[None, :]  # [1, S]
    valid = (k_pos <= pos[:, None])  # j <= pos, per row (garbage masked)
    # physical write target per row. INACTIVE rows route to the trash block
    # regardless of their table: eviction is then pure host bookkeeping (no
    # table reset), and a stale row left by a failed admission can never
    # write into since-recycled blocks. clip keeps an idle cursor from
    # indexing past the table.
    blk = jnp.minimum(pos // bs, m - 1)
    off = pos % bs
    phys = jnp.take_along_axis(state.block_tables, blk[:, None], axis=1)[:, 0]
    phys = jnp.where(active, phys, state.trash_block)

    ck_l = jnp.moveaxis(state.cache_k, 1, 0)  # [L, NB, bs, H, D] view
    cv_l = jnp.moveaxis(state.cache_v, 1, 0)

    def layer(x, xs):
        lp, ck, cv = xs  # ck/cv: [NB, bs, H_kv, Dh] — this layer's pool
        h = _norm(x, lp["ln_1"]["scale"], lp["ln_1"].get("bias"),
                  cfg.norm, cfg.norm_eps)
        q, k_new, v_new = _qkv(lp, h, cfg)  # q [B,H,Dh], k/v [B,Hkv,Dh]
        if cfg.rope:
            q = _rope_at(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
            k_new = _rope_at(k_new[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        ck = ck.at[phys, off].set(k_new.astype(ck.dtype))
        cv = cv.at[phys, off].set(v_new.astype(cv.dtype))
        # block-table gather → the slot's logical [S, H, D] view
        gk = ck[state.block_tables].reshape(n_slots, s, n_kv, cfg.d_head)
        gv = cv[state.block_tables].reshape(n_slots, s, n_kv, cfg.d_head)
        qg = q.reshape(q.shape[0], n_kv, group, cfg.d_head)
        scores = jnp.einsum("bkgd,bskd->bkgs", qg, gk,
                            preferred_element_type=jnp.float32) * scale
        if cfg.alibi:
            dist = (pos[:, None] - k_pos).astype(jnp.float32)  # [B, S]
            slopes = alibi_slopes(cfg.n_heads).reshape(n_kv, group)
            scores = scores - slopes[None, :, :, None] * dist[:, None, None, :]
        scores = jnp.where(valid[:, None, None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgs,bskd->bkgd", probs.astype(gv.dtype), gv)
        x = x + _dense(lp, "out_proj", out.reshape(x.shape[0], cfg.d_model))
        return _mlp(lp, x, cfg), (ck, cv)

    x, (ck_l, cv_l) = jax.lax.scan(
        layer, x, (params["blocks"]["block"], ck_l, cv_l)
    )
    return _logits(params, x, cfg), PagedState(
        cache_k=jnp.moveaxis(ck_l, 0, 1),
        cache_v=jnp.moveaxis(cv_l, 0, 1),
        block_tables=state.block_tables,
        lengths=state.lengths + active.astype(jnp.int32),
    )
