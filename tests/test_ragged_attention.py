"""Ragged paged attention + mixed chunked-prefill batches (ISSUE 12).

Contract layers:

1. **kernel unit parity** — the fused Pallas kernel (interpret mode)
   vs the dense reference over the same live view, across GQA/ALiBi
   shapes, pow2 token buckets and recycled-block tables. EPSILON tier:
   the online softmax reorders the fp32 accumulation, so the bound is
   pinned, not bit-exact.
2. **mixed-step parity** — the unified chunked-prefill/decode
   program's GATHER path vs the contiguous ``models/decode.py`` oracle,
   per step, at ``SERVE_LOGITS_ATOL`` with the same argmax
   (``tests/_helpers.py``): chunked prefill across several
   chunk budgets, decode continuation, post-eviction recycled blocks,
   and prefix-cache-hit admissions, across mpt-wpe / mpt-alibi /
   llama-gqa.
3. **scheduler cadence** — a 4x-budget prompt is split across chunk
   steps and an in-flight decode emits a token on EVERY step of the
   split (the PR 5 carve-out let it stall for the whole prefill).
4. **config gating** — ``serve.attention_impl`` validation: bad values
   and ``ragged``-without-Pallas/interpret fail at validate(), not at
   the first decode step.
5. **no-retrace** — warm ragged bursts with chunked prompts, prefix
   hits and a live hot-swap compile nothing (the sentinel e2e).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.config.schema import Config

from tests._helpers import assert_logits_match, tiny_llama_config


def _serve_cfg(*, alibi=False, llama=False, n_slots=2, block_size=4,
               max_seq=32, max_new=8, budget=2048, prefix=False,
               attn="auto", interpret=False) -> Config:
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
    cfg.photon.serve.prefill_token_budget = budget
    cfg.photon.serve.prefix_cache = prefix
    cfg.photon.serve.attention_impl = attn
    cfg.photon.serve.attention_interpret = interpret
    return cfg.validate()


def _offline_greedy(cfg, params, prompt, n):
    from photon_tpu.models.decode import make_cached_generate_fn

    buf = np.zeros((1, len(prompt) + n), np.int32)
    buf[0, : len(prompt)] = prompt
    fn = make_cached_generate_fn(cfg.model, params)
    t, _ = fn.many(jnp.asarray(buf), jnp.asarray([len(prompt)], np.int32), n)
    return [int(x) for x in np.asarray(t)[0, len(prompt):]]


def _rel(a, ref):
    a = np.asarray(a, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.linalg.norm(a - ref) / (np.linalg.norm(ref) + 1e-12))


# ---------------------------------------------------------------------------
# 1. kernel unit parity (epsilon tier)
# ---------------------------------------------------------------------------

#: pinned epsilon for the fused online-softmax kernel vs the dense
#: reference, fp32 end to end (the online rescaling reorders the fp32
#: accumulation; observed ~1e-7, bound leaves one order of headroom)
RAGGED_KERNEL_EPS = 2e-6


@pytest.mark.parametrize("t", [1, 2, 4, 8])  # pow2 token buckets
@pytest.mark.parametrize("gqa,alibi", [(False, False), (False, True),
                                       (True, False)])
def test_kernel_parity_token_buckets(t, gqa, alibi):
    from photon_tpu.ops.attention import alibi_slopes
    from photon_tpu.ops.ragged_paged_attention import (
        live_view, ragged_paged_attention, ragged_reference_attention,
    )

    rng = np.random.default_rng(7)
    b, h, dh, bs, nb, n_ctx = 3, 4, 8, 4, 17, 4
    n_kv = 2 if gqa else h
    kp = jnp.asarray(rng.standard_normal((nb, bs, n_kv, dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, bs, n_kv, dh)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, nb, (b, n_ctx)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, t, h, dh)), jnp.float32)
    pos = jnp.asarray(rng.integers(0, n_ctx * bs, (b, t)), jnp.int32)
    slopes = alibi_slopes(h) if alibi else None
    kb, vb = live_view(kp, vp, rows)
    ref = ragged_reference_attention(q, kb, vb, pos, slopes=slopes)
    out = ragged_paged_attention(q, kp, vp, rows, pos, slopes=slopes,
                                 interpret=True)
    assert _rel(out, ref) < RAGGED_KERNEL_EPS, (t, gqa, alibi)


def test_kernel_parity_recycled_blocks():
    """A table whose entries point at shuffled, REUSED physical blocks
    (the post-eviction pool shape: stale bytes everywhere, shared ids
    across slots) — only positions <= each query's own position may
    contribute, and they do so identically to the dense reference."""
    from photon_tpu.ops.ragged_paged_attention import (
        live_view, ragged_paged_attention, ragged_reference_attention,
    )

    rng = np.random.default_rng(11)
    b, t, h, dh, bs, nb, n_ctx = 2, 2, 2, 8, 4, 6, 8
    kp = jnp.asarray(rng.standard_normal((nb, bs, h, dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, bs, h, dh)), jnp.float32)
    # deliberately overlapping rows (two slots sharing physical blocks —
    # the prefix-cache CoW shape) with trash-id tails
    rows = jnp.asarray([[0, 3, 3, 1, 5, 5, 5, 5],
                        [3, 0, 2, 4, 5, 5, 5, 5]], jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, t, h, dh)), jnp.float32)
    pos = jnp.asarray([[6, 13], [0, 30]], jnp.int32)
    kb, vb = live_view(kp, vp, rows)
    ref = ragged_reference_attention(q, kb, vb, pos)
    out = ragged_paged_attention(q, kp, vp, rows, pos, interpret=True)
    assert _rel(out, ref) < RAGGED_KERNEL_EPS


def test_reference_matches_full_width():
    """The live-width cut is bitwise-invisible to the reference math:
    scores past a query's position are masked to exactly-zero
    probability, so a wider walk changes nothing."""
    from photon_tpu.ops.ragged_paged_attention import (
        live_view, ragged_reference_attention,
    )

    rng = np.random.default_rng(3)
    b, t, h, dh, bs, nb = 2, 2, 2, 8, 4, 9
    kp = jnp.asarray(rng.standard_normal((nb, bs, h, dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, bs, h, dh)), jnp.float32)
    full = jnp.asarray(rng.integers(0, nb, (b, 8)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, t, h, dh)), jnp.float32)
    pos = jnp.asarray([[3, 7], [1, 6]], jnp.int32)  # all < 2 blocks
    kb2, vb2 = live_view(kp, vp, full[:, :2])
    kb8, vb8 = live_view(kp, vp, full)
    np.testing.assert_array_equal(
        np.asarray(ragged_reference_attention(q, kb2, vb2, pos)),
        np.asarray(ragged_reference_attention(q, kb8, vb8, pos)),
    )


# ---------------------------------------------------------------------------
# 2. mixed-step parity vs the contiguous decoder
# ---------------------------------------------------------------------------


def _drive_chunked(cfg, params, prompt, chunk_cap, gen, *, impl="gather",
                   n_ctx=4):
    """Chunk-prefill ``prompt`` through mixed_chunk_step on a fresh pool,
    then greedily decode ``gen`` tokens; returns (per-emission logits
    list, the paged state). Slot 1 stays idle throughout (pad rows)."""
    from photon_tpu.serve.cache import (
        BlockAllocator, init_paged_state, install_row, mixed_chunk_step,
    )

    mc = cfg.model
    bs = cfg.photon.serve.block_size
    m = -(-mc.max_seq_len // bs)
    B = 2
    alloc = BlockAllocator(B * m)
    pst = init_paged_state(mc, B, B * m, bs, m)
    need = -(-(len(prompt) + gen) // bs)
    ids = alloc.alloc(need)
    row = np.full(m, B * m, np.int32)
    row[:need] = ids
    pst = install_row(pst, jnp.int32(0), jnp.asarray(row), jnp.int32(0))
    n = len(prompt)
    lengths = np.zeros(B, np.int32)
    emissions = []

    def bucket(cn):
        blocks = -(-cn // bs)
        return min(1 << (blocks - 1).bit_length(), m) * bs

    pos0 = 0
    interpret = impl == "ragged"
    while pos0 < n:
        cn = min(chunk_cap, n - pos0)
        tq = bucket(cn)
        tk = np.zeros((B, tq), np.int32)
        ps = np.zeros((B, tq), np.int32)
        qv = np.zeros((B, tq), bool)
        eo = np.zeros(B, np.int32)
        tk[0, :cn] = prompt[pos0:pos0 + cn]
        ps[0, :cn] = np.arange(pos0, pos0 + cn)
        qv[0, :cn] = True
        la = lengths.copy()
        la[0] = pos0 + cn
        if pos0 + cn == n:
            eo[0] = cn - 1
        logits, pst = mixed_chunk_step(
            params, pst, jnp.asarray(tk), jnp.asarray(ps), jnp.asarray(qv),
            jnp.asarray(eo), jnp.asarray(la), jnp.int32(0), mc,
            n_ctx=n_ctx, has_chunk=True, impl=impl, interpret=interpret,
        )
        lengths = la
        pos0 += cn
    emissions.append(np.asarray(logits[0]))
    for _ in range(gen):
        nxt = int(np.argmax(emissions[-1]))
        tk = np.zeros((B, 1), np.int32)
        ps = np.zeros((B, 1), np.int32)
        qv = np.zeros((B, 1), bool)
        eo = np.zeros(B, np.int32)
        tk[0, 0] = nxt
        ps[0, 0] = lengths[0]
        qv[0, 0] = True
        la = lengths.copy()
        la[0] += 1
        logits, pst = mixed_chunk_step(
            params, pst, jnp.asarray(tk), jnp.asarray(ps), jnp.asarray(qv),
            jnp.asarray(eo), jnp.asarray(la), jnp.int32(0), mc,
            n_ctx=n_ctx, has_chunk=False, impl=impl, interpret=interpret,
        )
        lengths = la
        emissions.append(np.asarray(logits[0]))
    return emissions, pst


def _oracle_logits(cfg, params, prompt, gen):
    """Contiguous models/decode.py logits stream: prefill emission + every
    greedy decode step (buffer sized to never overflow the one-hot write)."""
    from photon_tpu.models.decode import decode_step, prefill

    mc = cfg.model
    n = len(prompt)
    buf = np.zeros((1, n + gen + 1), np.int32)
    buf[0, :n] = prompt
    lo, st = prefill(params, jnp.asarray(buf), jnp.asarray([n], np.int32), mc)
    out = [np.asarray(lo[0])]
    for _ in range(gen):
        nxt = int(np.argmax(out[-1]))
        lo, st = decode_step(params, st, jnp.asarray([nxt], jnp.int32), mc)
        out.append(np.asarray(lo[0]))
    return out


@pytest.mark.parametrize("name", ["mpt-wpe", "mpt-alibi", "llama-gqa"])
@pytest.mark.parametrize("chunk_cap", [4, 6, 100])
def test_mixed_step_matches_contiguous(name, chunk_cap):
    """The acceptance pin: chunked prefill (several chunk budgets,
    including the one-shot 100 case) + decode through the GATHER path
    against the contiguous oracle, every emission: logits within
    ``SERVE_LOGITS_ATOL`` and the same token."""
    from photon_tpu.models.mpt import init_params

    cfg = _serve_cfg(alibi=name == "mpt-alibi", llama=name == "llama-gqa")
    params = init_params(cfg.model, seed=4)
    rng = np.random.default_rng(1)
    prompt = list(map(int, rng.integers(1, cfg.model.vocab_size, 9)))
    got, _ = _drive_chunked(cfg, params, prompt, chunk_cap, gen=5)
    want = _oracle_logits(cfg, params, prompt, gen=5)
    for i, (a, b) in enumerate(zip(got, want)):
        assert_logits_match(a, b, err_msg=f"emission {i}")


@pytest.mark.parametrize("name", ["mpt-wpe", "mpt-alibi", "llama-gqa"])
def test_ragged_kernel_epsilon_vs_contiguous(name):
    """The fused kernel drives the same chunk/decode stream; every
    emission stays within the pinned epsilon of the contiguous oracle."""
    from photon_tpu.models.mpt import init_params

    cfg = _serve_cfg(alibi=name == "mpt-alibi", llama=name == "llama-gqa")
    params = init_params(cfg.model, seed=4)
    rng = np.random.default_rng(2)
    prompt = list(map(int, rng.integers(1, cfg.model.vocab_size, 9)))
    got, _ = _drive_chunked(cfg, params, prompt, 4, gen=4, impl="ragged")
    want = _oracle_logits(cfg, params, prompt, gen=4)
    for i, (a, b) in enumerate(zip(got, want)):
        assert _rel(a, b) < RAGGED_KERNEL_EPS, f"emission {i}"


@pytest.mark.parametrize("name", ["mpt-wpe", "llama-gqa"])
def test_engine_chunked_matches_offline_after_recycling(name):
    """Engine-level acceptance across block recycling: admissions run
    through the chunked path (budget 3 forces multi-chunk prefills on a
    LIFO-recycled pool) and every completion equals the offline oracle —
    including requests admitted into blocks a previous request just
    freed, and a prefix-cache-hit admission."""
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(llama=name == "llama-gqa", prefix=True, budget=3)
    params = init_params(cfg.model, seed=4)
    engine = PagedEngine(cfg, params)
    batcher = ContinuousBatcher(engine, max_queue=16,
                                prefill_token_budget=3).start()
    rng = np.random.default_rng(5)
    shared = list(map(int, rng.integers(1, cfg.model.vocab_size, 8)))
    try:
        for i in range(6):
            suf = list(map(int, rng.integers(1, cfg.model.vocab_size,
                                             int(rng.integers(1, 6)))))
            p = (shared + suf) if i % 2 else suf
            got = batcher.submit(p, 4).result(timeout=120)
            assert got == _offline_greedy(cfg, params, p, 4), p
        assert engine.prefix_cache.tokens_cached > 0  # hits happened
        assert batcher.chunk_split_prompts > 0  # prompts really split
        assert engine.n_active == 0
    finally:
        batcher.close()


# ---------------------------------------------------------------------------
# 3. decode cadence under a 4x-budget prompt
# ---------------------------------------------------------------------------


def test_decode_cadence_survives_giant_prompt():
    """Regression for the PR 5 carve-out: with chunked prefill, an
    in-flight decode emits a token on EVERY step of a 4x-budget prompt's
    admission — the giant prompt pays its prefill across chunks instead
    of stalling the decode for the whole thing. Driven synchronously
    (batcher not started: this test owns the driver phases)."""
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    budget = 4
    cfg = _serve_cfg(n_slots=2, max_seq=64, max_new=32, budget=budget)
    params = init_params(cfg.model, seed=4)
    engine = PagedEngine(cfg, params)
    batcher = ContinuousBatcher(engine, max_queue=4,
                                prefill_token_budget=budget)
    rng = np.random.default_rng(9)
    decode_req = batcher.submit([5, 9, 2], 24)
    giant = list(map(int, rng.integers(1, cfg.model.vocab_size, 4 * budget)))
    batcher._admit_phase()
    # get the decode request past its own (short) prefill first
    while engine.pending_tokens(0) > 0:
        batcher._step_phase()
    big_req = batcher.submit(giant, 4)
    batcher._admit_phase()
    big_slot = next(s for s, r in batcher._running.items() if r is big_req)
    assert engine.pending_tokens(big_slot) == len(giant)
    chunk_steps = 0
    while engine.pending_tokens(big_slot) > 0:
        before = len(decode_req.generated)
        batcher._step_phase()
        chunk_steps += 1
        # THE pin: the decode row advanced on this very chunk step
        assert len(decode_req.generated) == before + 1, (
            f"decode stalled during chunk step {chunk_steps}"
        )
    assert chunk_steps == 4  # 4x budget → exactly 4 chunk steps
    assert batcher.chunk_split_prompts == 1
    # drain cleanly: finish both requests, then verify against the oracle
    while not (decode_req.finished and big_req.finished):
        batcher._step_phase()
    assert decode_req.generated == _offline_greedy(cfg, params, [5, 9, 2], 24)
    assert big_req.generated == _offline_greedy(cfg, params, giant, 4)
    batcher.close()


# ---------------------------------------------------------------------------
# 4. config gating
# ---------------------------------------------------------------------------


def test_attention_impl_validation():
    cfg = _serve_cfg()
    cfg.photon.serve.attention_impl = "fused"  # unknown impl
    with pytest.raises(ValueError, match="attention_impl"):
        cfg.validate()
    # explicit ragged on this (CPU) backend without interpret: validation
    # failure, not a runtime one
    cfg.photon.serve.attention_impl = "ragged"
    cfg.photon.serve.attention_interpret = False
    with pytest.raises(ValueError, match="Pallas-capable"):
        cfg.validate()
    cfg.photon.serve.attention_interpret = True  # interpreter opt-in passes
    cfg.validate()
    cfg.photon.serve.attention_impl = "gather"
    cfg.photon.serve.attention_interpret = False
    cfg.validate()
    cfg.photon.serve.attention_impl = "auto"
    cfg.validate()


def test_engine_impl_resolution():
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine

    params_cfg = _serve_cfg(attn="gather")
    params = init_params(params_cfg.model, seed=0)
    g = PagedEngine(params_cfg, params)
    assert g.attn_impl == "gather" and g.attn_stats()["ragged"] == 0.0
    a = PagedEngine(_serve_cfg(attn="auto"), params)
    # CPU sandbox: auto = the ragged walk with the gather-reference math
    assert a.attn_impl == "ragged-ref" and a.attn_stats()["ragged"] == 1.0
    r = PagedEngine(_serve_cfg(attn="ragged", interpret=True), params)
    assert r.attn_impl == "ragged"


def test_gather_impl_serves_full_width():
    """attention_impl=gather keeps the PR 5 cost model (full-width walk)
    and still matches the offline oracle (it IS the oracle path)."""
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(attn="gather")
    params = init_params(cfg.model, seed=4)
    engine = PagedEngine(cfg, params)
    assert engine.attn_stats()["ctx_blocks"] == engine.max_blocks
    batcher = ContinuousBatcher(engine, max_queue=4).start()
    try:
        got = batcher.submit([5, 9, 2, 7], 5).result(timeout=120)
        assert got == _offline_greedy(cfg, params, [5, 9, 2, 7], 5)
    finally:
        batcher.close()


def test_ragged_kernel_engine_matches_offline():
    """The fused kernel as the ENGINE's inner loop (interpret mode):
    greedy completions equal the offline oracle — the epsilon tier is far
    inside the argmax margin on this model."""
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(attn="ragged", interpret=True)
    params = init_params(cfg.model, seed=4)
    engine = PagedEngine(cfg, params)
    batcher = ContinuousBatcher(engine, max_queue=4).start()
    try:
        for p in ([5, 9, 2, 7], [3, 3, 8, 1, 4, 4]):
            got = batcher.submit(p, 4).result(timeout=180)
            assert got == _offline_greedy(cfg, params, p, 4), p
    finally:
        batcher.close()


# ---------------------------------------------------------------------------
# 5. the retrace sentinel across chunked ragged bursts + hits + a swap
# ---------------------------------------------------------------------------


def test_retrace_sentinel_green_chunked_with_hits_and_swap():
    """The ISSUE 12 sentinel pin: with every (chunk-width, live-width)
    bucket warm, a ragged burst of SPLIT prompts (budget 4 → multi-chunk
    prefills) mixed with decode rows, prefix-cache hits AND one live
    hot-swap compiles NOTHING. Fixed length profile so every burst
    exercises the same buckets; the live width is a monotone high-water,
    so admission timing can't mint fresh shapes."""
    from photon_tpu.analysis import runtime as lint_rt
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(n_slots=2, max_seq=32, prefix=True, budget=4)
    params = init_params(cfg.model, seed=4)
    engine = PagedEngine(cfg, params)
    batcher = ContinuousBatcher(engine, max_queue=32,
                                prefill_token_budget=4).start()
    rng = np.random.default_rng(17)
    shared = list(map(int, rng.integers(1, cfg.model.vocab_size, 8)))
    profile = [(1, 2), (6, 3), (3, 4), (4, 2), (5, 3), (2, 2)]

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
        burst()  # warm 1: misses populate the cache; hws rise to final
        done = batcher.request_swap(dict(params), loaded_round=1)
        assert done.wait(60)
        burst()  # warm 2: every final-width bucket incl. hit suffixes
        with lint_rt.retrace_guard(steady=True) as sentinel:
            burst()
            done = batcher.request_swap(dict(params), loaded_round=2)
            assert done.wait(60)
            burst()
        assert sentinel.violations == []
        assert batcher.chunk_split_prompts > 0  # chunking genuinely happened
        assert engine.loaded_round == 2
    finally:
        batcher.close()
