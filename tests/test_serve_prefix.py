"""Content-addressed prefix cache (ISSUE 11 tentpole a).

Contract layers:

1. refcounted :class:`BlockAllocator` — double-map / double-free /
   evict-while-pinned accounting stays exact under sharing;
2. chain hashes — a hash identifies the WHOLE prefix, not one block;
3. **parity** — a request admitted through a cached prefix produces the
   same request's cold-prefilled per-step logits to ``SERVE_LOGITS_ATOL``
   and the same tokens (``tests/_helpers.py``), including after the shared
   blocks' original owner was evicted;
4. scheduler invariants with the cache on (no leaks, LRU eviction under
   pool pressure, outputs == offline oracle), and the retrace sentinel
   stays green across warm ragged bursts with hits, misses and one live
   hot-swap.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.config.schema import Config

from tests._helpers import assert_logits_match, tiny_llama_config


def _serve_cfg(*, alibi=False, llama=False, n_slots=2, block_size=4,
               max_seq=32, max_new=8, n_blocks=0, cache_blocks=0) -> Config:
    if llama:
        cfg = tiny_llama_config(n_kv_heads=2)
    else:
        cfg = Config()
        cfg.model.d_model = 32
        cfg.model.n_layers = 2
        cfg.model.n_heads = 4
        cfg.model.vocab_size = 96
        cfg.model.attn_impl = "xla"
        cfg.model.compute_dtype = "float32"
        cfg.model.alibi = alibi
        cfg.model.learned_pos_emb = not alibi
    cfg.model.max_seq_len = max_seq
    cfg.photon.serve.n_slots = n_slots
    cfg.photon.serve.block_size = block_size
    cfg.photon.serve.max_new_tokens = max_new
    cfg.photon.serve.n_blocks = n_blocks
    cfg.photon.serve.prefix_cache = True
    cfg.photon.serve.prefix_cache_blocks = cache_blocks
    return cfg.validate()


def _offline_greedy(cfg, params, prompt, n):
    from photon_tpu.models.decode import make_cached_generate_fn

    buf = np.zeros((1, len(prompt) + n), np.int32)
    buf[0, : len(prompt)] = prompt
    fn = make_cached_generate_fn(cfg.model, params)
    t, _ = fn.many(jnp.asarray(buf), jnp.asarray([len(prompt)], np.int32), n)
    return [int(x) for x in np.asarray(t)[0, len(prompt):]]


# ---------------------------------------------------------------------------
# 1. refcounted allocator
# ---------------------------------------------------------------------------


def test_allocator_refcounts_share_and_free():
    from photon_tpu.serve.cache import BlockAllocator, BlockLeakError

    a = BlockAllocator(4)
    ids = a.alloc(2)
    assert a.free_blocks == 2 and all(a.refcount(b) == 1 for b in ids)
    a.retain(ids)  # the double-map: a second slot shares both blocks
    assert all(a.refcount(b) == 2 for b in ids)
    a.free(ids)  # first holder leaves — blocks must NOT hit the free list
    assert a.free_blocks == 2 and all(a.refcount(b) == 1 for b in ids)
    a.free(ids)  # last holder leaves
    assert a.free_blocks == 4 and all(a.refcount(b) == 0 for b in ids)
    with pytest.raises(BlockLeakError):
        a.free(ids[:1])  # double free past refcount zero still raises
    with pytest.raises(BlockLeakError):
        a.retain([ids[0]])  # retaining a FREE block would resurrect it
    with pytest.raises(BlockLeakError):
        a.retain([99])  # foreign id


def test_allocator_retain_is_atomic():
    """A retain batch containing one bad id must change nothing."""
    from photon_tpu.serve.cache import BlockAllocator, BlockLeakError

    a = BlockAllocator(4)
    ids = a.alloc(2)
    with pytest.raises(BlockLeakError):
        a.retain([ids[0], 99])
    assert a.refcount(ids[0]) == 1  # not half-applied
    a.free(ids)
    assert a.free_blocks == 4


# ---------------------------------------------------------------------------
# 2. chain hashes
# ---------------------------------------------------------------------------


def test_chain_hashes_identify_whole_prefix():
    from photon_tpu.serve.prefix import prefix_hashes

    bs = 4
    a = list(range(1, 13))  # 3 full blocks
    b = list(a)
    b[1] = 99  # differ inside block 0
    ha, hb = prefix_hashes(a, bs), prefix_hashes(b, bs)
    assert len(ha) == 3
    # blocks 1 and 2 have IDENTICAL contents across the two prompts, but
    # the chain makes every downstream hash differ — no false sharing
    assert all(x != y for x, y in zip(ha, hb))
    # same prefix → same hashes, and a partial tail block never hashes
    assert prefix_hashes(a + [5, 6], bs) == ha
    assert prefix_hashes(a, bs, limit=1) == ha[:1]


def test_prefix_cache_lru_evict_while_pinned():
    from photon_tpu.serve.cache import BlockAllocator
    from photon_tpu.serve.prefix import PrefixCache, prefix_hashes

    alloc = BlockAllocator(4)
    pc = PrefixCache(alloc)
    ids = alloc.alloc(2)
    hashes = prefix_hashes(list(range(1, 9)), 4)
    pc.insert(hashes, ids)  # cache now holds a second ref on each
    assert all(alloc.refcount(b) == 2 for b in ids)
    alloc.free(ids)  # the owning request evicts; cache keeps them alive
    assert alloc.free_blocks == 2 and len(pc) == 2
    # pin block 0 as a live request would, then demand the whole pool:
    # pool pressure evicts ONLY the unpinned entry (evicting a pinned one
    # frees nothing and would destroy a live hot prefix's index)
    alloc.retain([ids[0]])
    assert pc.ensure_free(4) is False  # pinned block yields no capacity
    assert len(pc) == 1 and pc.evictions == 1  # pinned entry stays indexed
    assert alloc.free_blocks == 3  # ids[1] came back, ids[0] stayed pinned
    # a FLUSH (hot-swap) evicts even while pinned: the entry leaves the
    # index, the pinned block (and its bytes) survives its last holder
    assert pc.flush() == 1
    assert len(pc) == 0 and pc.evictions == 2
    assert alloc.free_blocks == 3 and alloc.refcount(ids[0]) == 1
    alloc.free([ids[0]])
    assert alloc.free_blocks == 4


def test_prefix_cache_explicit_cap():
    from photon_tpu.serve.cache import BlockAllocator
    from photon_tpu.serve.prefix import PrefixCache, prefix_hashes

    alloc = BlockAllocator(8)
    pc = PrefixCache(alloc, max_blocks=2)
    ids = alloc.alloc(3)
    pc.insert(prefix_hashes(list(range(1, 13)), 4), ids)
    assert len(pc) == 2 and pc.evictions == 1  # LRU (block 0) displaced
    alloc.free(ids)
    assert alloc.free_blocks == 6  # evicted id returned, 2 cache-held


def test_prefix_cache_cap_eviction_prefers_unpinned():
    """Cap pressure with a pinned hot prefix in the LRU head position:
    the victim must be the oldest UNPINNED entry — un-indexing the pinned
    one frees nothing and tears a live chain."""
    from photon_tpu.serve.cache import BlockAllocator
    from photon_tpu.serve.prefix import PrefixCache, prefix_hashes

    alloc = BlockAllocator(8)
    pc = PrefixCache(alloc, max_blocks=2)
    hot = alloc.alloc(1)  # stays pinned: a live slot keeps mapping it
    cold = alloc.alloc(1)
    pc.insert(prefix_hashes([1, 2, 3, 4], 4), hot)
    pc.insert(prefix_hashes([9, 9, 9, 9], 4), cold)
    alloc.free(cold)  # its request finished — refcount 1, evictable
    new = alloc.alloc(1)
    pc.insert(prefix_hashes([7, 7, 7, 7], 4), new)  # cap forces one out
    assert pc.lookup(prefix_hashes([1, 2, 3, 4], 4)) == hot  # hot survived
    assert pc.lookup(prefix_hashes([9, 9, 9, 9], 4)) == []  # cold went
    assert pc.evictions == 1


# ---------------------------------------------------------------------------
# 3. parity: cached admission against cold admission, per step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["mpt-wpe", "mpt-alibi", "llama-gqa"])
def test_cached_admission_matches_cold_per_step(name):
    """The acceptance pin: admit a donor (cold), evict it, admit a second
    request re-using its cached prefix blocks; drive BOTH that engine and
    a cache-less twin step by step — every step's logits within
    ``SERVE_LOGITS_ATOL`` and the same token, starting from the first
    sampled one."""
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.cache import paged_decode_step
    from photon_tpu.serve.engine import PagedEngine

    cfg = _serve_cfg(alibi=name == "mpt-alibi", llama=name == "llama-gqa")
    cold_cfg = _serve_cfg(alibi=name == "mpt-alibi", llama=name == "llama-gqa")
    cold_cfg.photon.serve.prefix_cache = False
    mc = cfg.model
    params = init_params(mc, seed=4)
    rng = np.random.default_rng(2)
    shared = list(map(int, rng.integers(1, mc.vocab_size, 12)))  # 3 blocks
    donor = shared + list(map(int, rng.integers(1, mc.vocab_size, 3)))
    probe = shared + list(map(int, rng.integers(1, mc.vocab_size, 5)))

    warm = PagedEngine(cfg, params)
    cold = PagedEngine(cold_cfg, params)
    warm.admit(0, donor, 4)
    warm.evict(0)  # the shared blocks' original owner is GONE
    first_w = warm.admit(0, probe, 8)
    assert warm.prefix_cache.tokens_cached == 12  # the hit actually happened
    first_c = cold.admit(0, probe, 8)
    assert first_w == first_c  # first token: the two prefills' argmax
    tok = first_w
    active = jnp.asarray([True, False])
    sw, sc = warm.state, cold.state
    for step in range(6):
        t = jnp.asarray([tok, 0], jnp.int32)
        lw, sw = paged_decode_step(params, sw, t, mc, active)
        lc, sc = paged_decode_step(params, sc, t, mc, active)
        assert_logits_match(lw[0], lc[0], err_msg=f"step {step}")
        tok = int(jnp.argmax(lw[0]))


def test_nested_prefix_depths_and_block_aligned_prompt():
    """Hits at every depth: a longer prompt extends a cached shorter one,
    and a prompt that IS exactly its cached blocks (n % bs == 0) still
    keeps its last token in the suffix (the logits source)."""
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(max_seq=32)
    params = init_params(cfg.model, seed=4)
    engine = PagedEngine(cfg, params)
    batcher = ContinuousBatcher(engine, max_queue=16).start()
    rng = np.random.default_rng(5)
    base = list(map(int, rng.integers(1, cfg.model.vocab_size, 8)))
    try:
        for p in (base, base + [7, 3], base[:4], base + [7, 3, 9, 9, 1]):
            got = batcher.submit(p, 4).result(timeout=120)
            assert got == _offline_greedy(cfg, params, p, 4), p
        # block-aligned prompt: lookup must cap at (n-1)//bs so the final
        # token stays in the suffix
        got = batcher.submit(base, 4).result(timeout=120)
        assert got == _offline_greedy(cfg, params, base, 4)
        assert engine.n_active == 0
    finally:
        batcher.close()


# ---------------------------------------------------------------------------
# 4. scheduler invariants with the cache on
# ---------------------------------------------------------------------------


def test_no_leak_and_oracle_outputs_under_shared_traffic():
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(n_slots=2, max_seq=32)
    params = init_params(cfg.model, seed=4)
    engine = PagedEngine(cfg, params)
    batcher = ContinuousBatcher(engine, max_queue=32).start()
    rng = np.random.default_rng(9)
    shared = list(map(int, rng.integers(1, cfg.model.vocab_size, 8)))
    prompts = []
    for i in range(10):
        suf = list(map(int, rng.integers(1, cfg.model.vocab_size,
                                         int(rng.integers(1, 6)))))
        prompts.append((shared + suf) if i % 3 else suf)  # hits AND misses
    try:
        reqs = [batcher.submit(p, int(rng.integers(1, 6))) for p in prompts]
        outs = [r.result(timeout=180) for r in reqs]
        for p, r, out in zip(prompts, reqs, outs):
            assert out == _offline_greedy(cfg, params, p, r.max_new_tokens), p
        assert engine.n_active == 0
        assert batcher.queue_depth == 0
        # conservation: every non-free block is exactly the cache's
        held = engine.n_blocks - engine.free_blocks
        assert held == len(engine.prefix_cache), (held, len(engine.prefix_cache))
        engine.prefix_cache.flush()
        assert engine.free_blocks == engine.n_blocks  # zero leaked
    finally:
        batcher.close()


def test_lru_eviction_under_pool_pressure():
    """A pool far smaller than the traffic's total footprint: admission
    evicts cold cache entries instead of failing, everything still serves
    correctly, and the evictions counter moves."""
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(n_slots=1, max_seq=32, n_blocks=8)
    params = init_params(cfg.model, seed=4)
    engine = PagedEngine(cfg, params)
    batcher = ContinuousBatcher(engine, max_queue=32).start()
    rng = np.random.default_rng(11)
    try:
        for _ in range(6):  # distinct prompts: each fills most of the pool
            p = list(map(int, rng.integers(1, cfg.model.vocab_size, 14)))
            got = batcher.submit(p, 4).result(timeout=120)
            assert got == _offline_greedy(cfg, params, p, 4)
        assert engine.prefix_cache.evictions > 0
        assert engine.n_active == 0
        engine.prefix_cache.flush()
        assert engine.free_blocks == engine.n_blocks
    finally:
        batcher.close()


def test_prefix_kpis_recorded_and_registered():
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher
    from photon_tpu.utils.profiling import (
        SERVE_PREFIX_HIT_RATE,
        SERVE_PREFIX_SHARED_BLOCKS,
        is_registered_metric,
    )

    cfg = _serve_cfg()
    params = init_params(cfg.model, seed=4)
    engine = PagedEngine(cfg, params)
    batcher = ContinuousBatcher(engine, max_queue=8).start()
    try:
        p = list(range(1, 11))
        batcher.submit(p, 3).result(timeout=120)
        batcher.submit(p + [1, 2], 3).result(timeout=120)
        recorded = set(batcher.history.rounds)
        assert SERVE_PREFIX_HIT_RATE in recorded
        assert SERVE_PREFIX_SHARED_BLOCKS in recorded
        assert all(is_registered_metric(k) for k in recorded), recorded
        assert batcher.history.latest(SERVE_PREFIX_HIT_RATE) > 0.0
    finally:
        batcher.close()


def test_retrace_sentinel_green_with_hits_misses_and_swap():
    """The acceptance pin: with every bucket warm (cold prefill, suffix
    prefill, step), a ragged burst mixing cache hits and misses plus ONE
    live hot-swap compiles NOTHING."""
    from photon_tpu.analysis import runtime as lint_rt
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(n_slots=2, max_seq=32)
    params = init_params(cfg.model, seed=4)
    engine = PagedEngine(cfg, params)
    batcher = ContinuousBatcher(engine, max_queue=32).start()
    rng = np.random.default_rng(17)
    shared = list(map(int, rng.integers(1, cfg.model.vocab_size, 8)))

    # fixed length/budget profile so every burst exercises the SAME prefill
    # and suffix buckets (content varies → hits stay hits, misses misses)
    profile = [(1, 2), (2, 3), (3, 4), (4, 2), (5, 3), (2, 2)]

    def burst():
        reqs = []
        for i, (suf_len, max_new) in enumerate(profile):
            suf = list(map(int, rng.integers(1, cfg.model.vocab_size, suf_len)))
            reqs.append(batcher.submit(
                (shared + suf) if i % 2 else suf, max_new
            ))
        for r in reqs:
            r.result(timeout=180)

    try:
        burst()  # warm: every prefill/suffix bucket + step + swap machinery
        done = batcher.request_swap(dict(params), loaded_round=1)
        assert done.wait(60)
        burst()
        with lint_rt.retrace_guard(steady=True) as sentinel:
            burst()
            done = batcher.request_swap(dict(params), loaded_round=2)
            assert done.wait(60)
            burst()
        assert sentinel.violations == []
        assert engine.loaded_round == 2
    finally:
        batcher.close()
