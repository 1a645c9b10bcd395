"""Serving plane (ISSUE 5): paged cache parity, scheduler invariants, HTTP.

Three layers of contract:

1. the paged KV cache decodes BIT-EXACTLY like the contiguous
   ``models/decode.py`` path (logits compared with assert_array_equal
   across MPT/wpe, MPT/ALiBi and llama/RoPE/GQA configs);
2. the continuous batcher leaks nothing under randomized arrival/length
   streams (slots, blocks, FIFO order, queue bound);
3. the stdlib HTTP frontend streams exactly what the offline decoder
   produces for the same checkpoint.
"""

import http.client
import json

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.config.schema import Config

from tests._helpers import tiny_llama_config


def _serve_cfg(*, alibi=False, llama=False, n_slots=2, block_size=4,
               max_seq=32, max_new=8) -> Config:
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
    return cfg.validate()


def _ragged_prompts(rng, n, vocab, lo=3, hi=10):
    return [list(map(int, rng.integers(1, vocab, rng.integers(lo, hi))))
            for _ in range(n)]


def _offline_greedy(cfg, params, prompt, n):
    """Oracle: the contiguous cached decoder, one row."""
    from photon_tpu.models.decode import make_cached_generate_fn

    buf = np.zeros((1, len(prompt) + n), np.int32)
    buf[0, : len(prompt)] = prompt
    fn = make_cached_generate_fn(cfg.model, params)
    t, _ = fn.many(jnp.asarray(buf), jnp.asarray([len(prompt)], np.int32), n)
    return [int(x) for x in np.asarray(t)[0, len(prompt):]]


# ---------------------------------------------------------------------------
# 1. paged cache vs contiguous DecodeState — bit-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", ["mpt-wpe", "mpt-alibi", "llama-gqa"])
def test_paged_decode_bitexact_with_contiguous(name):
    from photon_tpu.models.decode import decode_step, prefill
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.cache import (
        BlockAllocator, init_paged_state, paged_decode_step, write_prefill_blocks,
    )

    cfg = _serve_cfg(alibi=name == "mpt-alibi", llama=name == "llama-gqa")
    mc = cfg.model
    params = init_params(mc, seed=4)
    b, s, gen, bs = 3, 16, 6, 4
    max_blocks = s // bs  # paged S_cap == contiguous S → comparable shapes
    rng = np.random.default_rng(1)
    lengths = np.asarray([4, 7, 10], np.int32)
    tokens = np.zeros((b, s), np.int32)
    for i, ln in enumerate(lengths):
        tokens[i, :ln] = rng.integers(1, mc.vocab_size, ln)

    logits_c, st = prefill(params, jnp.asarray(tokens), jnp.asarray(lengths), mc)

    alloc = BlockAllocator(b * max_blocks)
    pst = init_paged_state(mc, b, b * max_blocks, bs, max_blocks)
    for i in range(b):
        pst = write_prefill_blocks(pst, i, alloc.alloc(max_blocks),
                                   st.cache_k[:, i:i + 1], st.cache_v[:, i:i + 1],
                                   int(lengths[i]))
    active = jnp.ones(b, bool)
    logits_p = logits_c  # prefill logits ARE the contiguous ones by construction
    for _ in range(gen):
        nxt = jnp.argmax(logits_c, axis=-1).astype(jnp.int32)
        np.testing.assert_array_equal(  # every step: identical logits, bitwise
            np.asarray(logits_p), np.asarray(logits_c))
        logits_c, st = decode_step(params, st, nxt, mc)
        logits_p, pst = paged_decode_step(params, pst, nxt, mc, active)
    np.testing.assert_array_equal(np.asarray(logits_p), np.asarray(logits_c))
    np.testing.assert_array_equal(np.asarray(pst.lengths),
                                  np.asarray(st.lengths))


def test_block_allocator_guards():
    from photon_tpu.serve.cache import BlockAllocator, BlockLeakError

    a = BlockAllocator(4)
    ids = a.alloc(3)
    assert a.free_blocks == 1 and a.alloc(2) is None  # no partial allocation
    a.free(ids)
    assert a.free_blocks == 4
    with pytest.raises(BlockLeakError):
        a.free(ids[:1])  # double free
    b = a.alloc(4)
    assert a.alloc(1) is None and sorted(b) == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# 2. engine + continuous batcher
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """One tiny MPT engine + batcher shared by the behavioral tests (module
    scope: the jit compiles dominate; state fully drains between tests).

    The whole fixture lifetime runs under the photon-lint lock-order
    recorder (ISSUE 6): every lock the engine/batcher/frontend creates is
    tracked, and teardown fails on any acquisition-order cycle observed
    across ALL the behavioral tests — a potential deadlock between the
    scheduler loop, submitters, and the telemetry plane."""
    from photon_tpu.analysis import runtime as lint_rt
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    recorder = lint_rt.install_lock_order()
    try:
        cfg = _serve_cfg(n_slots=2, block_size=4, max_seq=32, max_new=8)
        params = init_params(cfg.model, seed=4)
        engine = PagedEngine(cfg, params)
        batcher = ContinuousBatcher(engine, max_queue=64).start()
        yield cfg, params, engine, batcher
        batcher.close()
        recorder.check()  # green = no lock-order inversion anywhere above
    finally:
        lint_rt.uninstall_lock_order()


def _assert_drained(engine, batcher):
    assert engine.n_active == 0, "slot leak"
    assert engine.free_blocks == engine.n_blocks, "block leak"
    assert batcher.queue_depth == 0


def test_continuous_batching_matches_offline_greedy(served):
    cfg, params, engine, batcher = served
    rng = np.random.default_rng(0)
    prompts = _ragged_prompts(rng, 5, cfg.model.vocab_size)
    reqs = [batcher.submit(p, 6) for p in prompts]
    outs = [r.result(timeout=60) for r in reqs]
    for p, got in zip(prompts, outs):
        assert got == _offline_greedy(cfg, params, p, 6), p
    _assert_drained(engine, batcher)


def test_eos_evicts_early_and_recycles(served):
    cfg, params, engine, batcher = served
    rng = np.random.default_rng(3)
    prompts = _ragged_prompts(rng, 4, cfg.model.vocab_size)
    # offline tells us each prompt's greedy stream; use its SECOND token as
    # that request's EOS: the server must stop at the FIRST occurrence of
    # that id (which may be earlier, if the stream repeats a token)
    for p in prompts:
        want = _offline_greedy(cfg, params, p, 6)
        eos = want[1]
        req = batcher.submit(p, 6, eos_id=eos)
        got = req.result(timeout=60)
        assert got == want[: want.index(eos) + 1], (got, want)
        assert len(got) < 6  # actually exited early
    _assert_drained(engine, batcher)
    assert batcher.evictions >= 4


def test_seeded_sampling_reproduces(served):
    cfg, params, engine, batcher = served
    prompt = [5, 9, 2, 7]
    a = batcher.submit(prompt, 6, temperature=1.0, seed=11).result(timeout=60)
    b = batcher.submit(prompt, 6, temperature=1.0, seed=11).result(timeout=60)
    g = batcher.submit(prompt, 6, temperature=0.0, seed=99).result(timeout=60)
    assert a == b  # same seed, same stream — independent of batch-mates
    assert g == _offline_greedy(cfg, params, prompt, 6)  # temp 0 stays greedy
    _assert_drained(engine, batcher)


def test_scheduler_invariants_random_streams(served):
    """Property test: randomized arrival/length streams; afterwards no slot
    leak, no block leak, admission strictly FIFO, queue bounded."""
    cfg, params, engine, batcher = served
    rng = np.random.default_rng(7)
    before = list(batcher.admitted_order)
    reqs = []
    for _ in range(12):
        p = _ragged_prompts(rng, 1, cfg.model.vocab_size, lo=2, hi=12)[0]
        n = int(rng.integers(1, 8))
        reqs.append(batcher.submit(p, n))
    outs = [r.result(timeout=120) for r in reqs]
    for r, out in zip(reqs, outs):
        assert 1 <= len(out) <= r.max_new_tokens
        assert out == _offline_greedy(cfg, params, r.prompt, len(out))
    admitted = list(batcher.admitted_order)[len(before):]
    assert admitted == sorted(admitted), "admission overtook FIFO order"
    _assert_drained(engine, batcher)


def test_failed_admission_is_transactional(served):
    """A chunk-step blow-up mid-prefill fails the in-flight request (the
    client gets the error, not a timeout), leaks no blocks — the failure
    is injected at the engine's device-call seam, BEFORE the donated
    state is consumed, so the engine survives — and the server keeps
    serving the queue afterwards."""
    cfg, params, engine, batcher = served
    real = engine._mixed_call
    calls = {"n": 0}

    def boom(*a, **k):
        calls["n"] += 1
        raise RuntimeError("injected prefill failure")

    engine._mixed_call = boom
    try:
        req = batcher.submit([5, 9, 2], 4)
        with pytest.raises(RuntimeError, match="injected prefill failure"):
            req.result(timeout=60)
    finally:
        engine._mixed_call = real
    assert calls["n"] >= 1
    _assert_drained(engine, batcher)
    ok = batcher.submit([5, 9, 2], 4).result(timeout=60)  # still serving
    assert ok == _offline_greedy(cfg, params, [5, 9, 2], 4)
    _assert_drained(engine, batcher)


def test_steady_state_serving_never_retraces(served):
    """ISSUE 6 e2e wiring: with the engine warm (every prefill bucket and
    the decode step already compiled by the tests above), the photon-lint
    retrace sentinel rides a fresh burst of ragged traffic — the scheduler
    loop's ``steady_point("serve/tick")`` hook bills any compile to its
    tick, and ANY compile fails. This is PR 5's "admission never retraces"
    contract, machine-checked instead of argued."""
    from photon_tpu.analysis import runtime as lint_rt

    cfg, params, engine, batcher = served
    rng = np.random.default_rng(21)
    prompts = _ragged_prompts(rng, 6, cfg.model.vocab_size, lo=2, hi=12)
    budgets = [int(rng.integers(1, 8)) for _ in prompts]
    # warmup burst: the SAME stream first runs unguarded, so this test owns
    # its compiles and stays green under -k / --lf / reordering instead of
    # leaning on earlier tests having warmed the prefill buckets
    for r in [batcher.submit(p, n) for p, n in zip(prompts, budgets)]:
        r.result(timeout=120)
    with lint_rt.retrace_guard(steady=True) as sentinel:
        reqs = [batcher.submit(p, n) for p, n in zip(prompts, budgets)]
        outs = [r.result(timeout=120) for r in reqs]
    assert sentinel.violations == []
    # ... and again with CHUNKED prefill (ISSUE 12): a small per-step
    # token budget splits every prompt into multi-chunk mixed batches.
    # The chunk widths depend only on each prompt's length and the
    # budget, so one unguarded warm pass covers every (Tq, n_ctx)
    # bucket the guarded pass can produce
    batcher.prefill_token_budget = 3
    try:
        for r in [batcher.submit(p, n) for p, n in zip(prompts, budgets)]:
            r.result(timeout=120)  # warm the chunk buckets
        with lint_rt.retrace_guard(steady=True) as sentinel:
            reqs2 = [batcher.submit(p, n) for p, n in zip(prompts, budgets)]
            for r in reqs2:
                r.result(timeout=120)
        assert sentinel.violations == []
        assert batcher.chunk_split_prompts > 0  # chunking genuinely happened
    finally:
        batcher.prefill_token_budget = 2048
    # the offline oracle runs OUTSIDE the guard: its contiguous decode
    # buffers are shaped per (prompt+n) and legitimately compile fresh
    for p, out in zip(prompts, outs):
        assert out == _offline_greedy(cfg, params, p, len(out))
    _assert_drained(engine, batcher)


def test_queue_backpressure_rejects():
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher, QueueFullError

    cfg = _serve_cfg(n_slots=1, block_size=4, max_seq=32, max_new=8)
    engine = PagedEngine(cfg, init_params(cfg.model, seed=0))
    batcher = ContinuousBatcher(engine, max_queue=2)  # NOT started: queue only fills
    try:
        batcher.submit([1, 2, 3], 4)
        batcher.submit([1, 2, 3], 4)
        with pytest.raises(QueueFullError):
            batcher.submit([1, 2, 3], 4)
        assert batcher.rejected == 1
        with pytest.raises(ValueError, match="context capacity"):
            batcher.submit(list(range(1, 40)), 8)  # can never fit → immediate 400
    finally:
        batcher.close()


def test_oversized_request_rejected_for_small_pool():
    """A request whose reservation exceeds the (user-shrunk) POOL must be
    rejected at submit — otherwise it would FIFO head-block the queue
    forever behind a can_admit() that can never pass."""
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(n_slots=1, block_size=4, max_seq=32, max_new=8)
    cfg.photon.serve.n_blocks = 2  # pool holds 8 tokens total
    engine = PagedEngine(cfg, init_params(cfg.model, seed=0))
    batcher = ContinuousBatcher(engine, max_queue=4).start()
    try:
        with pytest.raises(ValueError, match="context capacity"):
            batcher.submit([1, 2, 3, 4, 5], 8)  # needs 4 blocks > pool of 2
        ok = batcher.submit([1, 2, 3], 4).result(timeout=60)  # 2 blocks: fits
        assert len(ok) == 4
        _assert_drained(engine, batcher)
    finally:
        batcher.close()


# ---------------------------------------------------------------------------
# 3. checkpoint → engine → HTTP e2e
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def http_server(tmp_path_factory):
    """A real round checkpoint served over HTTP (module scope)."""
    from photon_tpu.checkpoint import FileStore
    from photon_tpu.checkpoint.server import ServerCheckpointManager
    from photon_tpu.codec import params_to_ndarrays
    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.frontend import ServeFrontend
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(n_slots=2, block_size=4, max_seq=32, max_new=8)
    cfg.run_uuid = "serve-e2e"
    params = init_params(cfg.model, seed=4)
    store = FileStore(tmp_path_factory.mktemp("serve-store"))
    mgr = ServerCheckpointManager(store, cfg.run_uuid)
    meta, arrays = params_to_ndarrays(params)
    mgr.save_round(3, meta, arrays, server_state={"server_round": 3})

    engine = PagedEngine.from_checkpoint(cfg, store=store, resume_round=-1)
    assert engine.loaded_round == 3
    batcher = ContinuousBatcher(engine, max_queue=8).start()
    fe = ServeFrontend(batcher, max_new_tokens_cap=8)
    port = fe.start()
    yield cfg, params, engine, batcher, port
    fe.close()
    batcher.close()


def _http(port):
    return http.client.HTTPConnection("127.0.0.1", port, timeout=60)


def test_http_blocking_matches_offline(http_server):
    cfg, params, engine, batcher, port = http_server
    prompt = [5, 9, 2, 7, 1]
    c = _http(port)
    c.request("POST", "/generate",
              json.dumps({"tokens": prompt, "max_new_tokens": 6}))
    r = c.getresponse()
    body = json.loads(r.read())
    assert r.status == 200, body
    assert body["tokens"] == _offline_greedy(cfg, params, prompt, 6)
    assert body["n_prompt"] == 5 and body["ttft_s"] >= 0.0


def test_http_streaming_matches_offline(http_server):
    cfg, params, engine, batcher, port = http_server
    prompt = [3, 3, 8, 1]
    c = _http(port)
    c.request("POST", "/generate",
              json.dumps({"tokens": prompt, "max_new_tokens": 6, "stream": True}))
    r = c.getresponse()
    assert r.status == 200
    lines = r.read().decode().strip().splitlines()
    toks = [json.loads(ln)["token"] for ln in lines[:-1]]
    final = json.loads(lines[-1])
    assert final["done"] is True and final["tokens"] == toks
    assert toks == _offline_greedy(cfg, params, prompt, 6)


def test_http_healthz_metrics_and_errors(http_server):
    cfg, params, engine, batcher, port = http_server
    c = _http(port)
    c.request("GET", "/healthz")
    h = json.loads(c.getresponse().read())
    assert h["status"] == "ok" and h["round"] == 3
    c.request("GET", "/metrics")
    m = c.getresponse().read().decode()
    assert "photon_serve_queue_depth" in m
    assert "photon_serve_slot_occupancy" in m
    def roundtrip(method, path, body=None):
        # read the body every time — HTTP/1.1 keep-alive reuse demands it
        c.request(method, path, body)
        r = c.getresponse()
        r.read()
        return r.status

    assert roundtrip("POST", "/generate", json.dumps({"max_new_tokens": 4})) == 400
    assert roundtrip("POST", "/generate", "{not json") == 400
    # un-coercible field types are a 400, not a dropped connection
    assert roundtrip("POST", "/generate",
                     json.dumps({"tokens": [1, 2], "eos_id": [5]})) == 400
    assert roundtrip("POST", "/generate",
                     json.dumps({"tokens": [1, "x"]})) == 400
    assert roundtrip("GET", "/nope") == 404


def test_request_spans_emitted(http_server):
    from photon_tpu import telemetry
    from photon_tpu.config.schema import TelemetryConfig
    from photon_tpu.utils.profiling import (
        SERVE_DECODE_SPAN, SERVE_PREFILL_SPAN, SERVE_QUEUE_SPAN, SERVE_REQUEST_SPAN,
    )

    cfg, params, engine, batcher, port = http_server
    tracer = telemetry.install(TelemetryConfig(enabled=True), scope="serve")
    try:
        batcher.submit([5, 9, 2], 3).result(timeout=60)
        spans = tracer.drain()
    finally:
        telemetry.uninstall()
    by_name = {s["name"]: s for s in spans}
    umbrella = by_name[SERVE_REQUEST_SPAN]
    for child in (SERVE_QUEUE_SPAN, SERVE_PREFILL_SPAN, SERVE_DECODE_SPAN):
        assert by_name[child]["parent_id"] == umbrella["span_id"]
        assert by_name[child]["trace_id"] == umbrella["trace_id"]


def test_graceful_drain_zero_dropped_inflight(tmp_path):
    """SIGTERM drain contract (ISSUE 8 satellite): once draining, /healthz
    reports ``draining`` and new /generate gets 503 + Retry-After, while
    everything already accepted — running slots AND queued requests — runs
    to completion within ``serve.drain_timeout_s``. Zero dropped in-flight
    requests across the drain, outputs identical to the offline oracle."""
    import threading
    import time

    from photon_tpu.models.mpt import init_params
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.frontend import ServeFrontend
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(n_slots=2, block_size=4, max_seq=32, max_new=8)
    params = init_params(cfg.model, seed=4)
    engine = PagedEngine(cfg, params)
    batcher = ContinuousBatcher(engine, max_queue=8).start()
    fe = ServeFrontend(batcher, max_new_tokens_cap=8)
    port = fe.start()
    try:
        # warm the jit caches so in-flight timing is about scheduling
        batcher.submit([5, 9, 2], 3).result(timeout=120)

        # 4 in-flight requests: 2 fill the slots, 2 wait in the queue —
        # the queued ones are "accepted" too and must NOT be dropped
        prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8], [7, 9, 3, 2]]
        results: list[tuple[int, dict]] = [None] * len(prompts)  # type: ignore[list-item]

        def _post(i: int) -> None:
            c = _http(port)
            c.request("POST", "/generate",
                      json.dumps({"tokens": prompts[i], "max_new_tokens": 8}))
            r = c.getresponse()
            results[i] = (r.status, json.loads(r.read()))

        threads = [threading.Thread(target=_post, args=(i,),
                                    name=f"drain-client-{i}", daemon=True)
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        # the drain may start only once all four are ACCEPTED: a client
        # thread that posts after the flag gets the 503 this test asserts
        # only the fifth request sees. A request leaves the queue before
        # it counts as active and leaves the slots before it counts as
        # completed, so the sum undercounts in passing and never overcounts
        base = batcher.completed
        deadline = time.monotonic() + 30.0

        def accepted() -> int:
            return (batcher.completed - base + engine.n_active
                    + batcher.queue_depth)

        while time.monotonic() < deadline and accepted() < len(prompts):
            time.sleep(0.002)
        assert accepted() == len(prompts)

        # the __main__ SIGTERM sequence: flag the edge, then drain the plane
        fe.mark_draining()
        c = _http(port)
        c.request("GET", "/healthz")
        h = c.getresponse()
        assert json.loads(h.read())["status"] == "draining"
        c.request("POST", "/generate",
                  json.dumps({"tokens": [1, 2, 3], "max_new_tokens": 2}))
        r = c.getresponse()
        refused = json.loads(r.read())
        assert r.status == 503, refused
        assert r.getheader("Retry-After") is not None

        assert batcher.drain(cfg.photon.serve.drain_timeout_s) is True
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        # zero dropped: every accepted request completed, bit-identical
        # with the offline oracle
        for p, (status, body) in zip(prompts, results):
            assert status == 200, body
            assert body["tokens"] == _offline_greedy(cfg, params, p, 8), p
        _assert_drained(engine, batcher)
        # post-drain: direct submission refuses cleanly too
        with pytest.raises(Exception):
            batcher.submit([1, 2], 2)
    finally:
        fe.close()
        batcher.close()


def test_serve_kpis_are_registered(http_server):
    """Every KPI the batcher records is a registry constant (the serving
    half of the ISSUE 4 registry contract)."""
    from photon_tpu.utils.profiling import is_registered_metric

    cfg, params, engine, batcher, port = http_server
    batcher.submit([5, 9, 2], 3).result(timeout=60)
    recorded = set(batcher.history.rounds)
    assert recorded, "batcher recorded no KPIs"
    unregistered = sorted(k for k in recorded if not is_registered_metric(k))
    assert not unregistered, unregistered


def test_load_round_params_skips_state(tmp_path):
    """The params-only load path touches ONLY the params object — a missing
    state.bin (never read) doesn't matter, and momenta stay unread."""
    from photon_tpu.checkpoint import FileStore
    from photon_tpu.checkpoint.server import PARAMS_FILE, ServerCheckpointManager
    from photon_tpu.codec import params_to_ndarrays
    from photon_tpu.models.mpt import init_params

    cfg = _serve_cfg()
    params = init_params(cfg.model, seed=1)
    store = FileStore(tmp_path)
    mgr = ServerCheckpointManager(store, "r")
    meta, arrays = params_to_ndarrays(params)
    mgr.save_round(1, meta, arrays, strategy_state={"momenta": arrays},
                   server_state={"server_round": 1})
    reads: list[str] = []
    orig_get = store.get
    store.get = lambda k: (reads.append(k), orig_get(k))[1]
    meta2, arrays2 = mgr.load_round_params(1)
    assert meta2.names == meta.names
    for a, b in zip(arrays, arrays2):
        np.testing.assert_array_equal(a, b)
    assert all(k.endswith(PARAMS_FILE) for k in reads), reads
