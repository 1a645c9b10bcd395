"""Live federated checkpoint hot-swap (ISSUE 11 tentpole b).

Contract layers:

1. ``ServerCheckpointManager.latest_complete_round`` — a presence-only
   scan (no object reads) that never reports a torn/partial round;
2. the watcher state machine — swaps to a new manifest-valid round, skips
   corrupt candidates (chaos-injected bitflip) with a warning while the
   daemon keeps serving the old params, refuses to swap during drain, and
   honors the /statusz federation-health gate;
3. swap semantics — admission pauses, in-flight requests finish their
   generations entirely on the OLD params, the swap flushes the prefix
   cache, and zero requests are dropped across a live swap (HTTP e2e).
"""

import http.client
import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from photon_tpu.checkpoint import FileStore
from photon_tpu.checkpoint.server import MANIFEST_FILE, ServerCheckpointManager
from photon_tpu.codec import params_to_ndarrays
from photon_tpu.config.schema import Config


def _serve_cfg(*, prefix_cache=False, n_slots=2, max_new=8) -> Config:
    cfg = Config()
    cfg.model.d_model = 32
    cfg.model.n_layers = 2
    cfg.model.n_heads = 4
    cfg.model.vocab_size = 96
    cfg.model.attn_impl = "xla"
    cfg.model.compute_dtype = "float32"
    cfg.model.max_seq_len = 32
    cfg.photon.serve.n_slots = n_slots
    cfg.photon.serve.block_size = 4
    cfg.photon.serve.max_new_tokens = max_new
    cfg.photon.serve.prefix_cache = prefix_cache
    return cfg.validate()


def _offline_greedy(cfg, params, prompt, n):
    from photon_tpu.models.decode import make_cached_generate_fn

    buf = np.zeros((1, len(prompt) + n), np.int32)
    buf[0, : len(prompt)] = prompt
    fn = make_cached_generate_fn(cfg.model, params)
    t, _ = fn.many(jnp.asarray(buf), jnp.asarray([len(prompt)], np.int32), n)
    return [int(x) for x in np.asarray(t)[0, len(prompt):]]


def _save_round(mgr, cfg, rnd, seed):
    from photon_tpu.models.mpt import init_params

    params = init_params(cfg.model, seed=seed)
    meta, arrays = params_to_ndarrays(params)
    mgr.save_round(rnd, meta, arrays, server_state={"server_round": rnd})
    return params


def _watcher(batcher, mgr, cfg, **kw):
    from photon_tpu.serve.hotswap import CheckpointWatcher

    return CheckpointWatcher(batcher, mgr, cfg, **kw)


# ---------------------------------------------------------------------------
# 1. latest_complete_round
# ---------------------------------------------------------------------------


def test_latest_complete_round_is_presence_only_and_skips_torn(tmp_path):
    cfg = _serve_cfg()
    store = FileStore(tmp_path)
    mgr = ServerCheckpointManager(store, "hs")
    assert mgr.latest_complete_round() is None
    _save_round(mgr, cfg, 1, seed=1)
    _save_round(mgr, cfg, 2, seed=2)
    # round 3 is TORN: params landed, manifest (written last) did not —
    # the mid-upload / crashed-writer shape the watcher must never report
    _save_round(mgr, cfg, 3, seed=3)
    store.delete(f"hs/server/3/{MANIFEST_FILE}")
    reads: list[str] = []
    orig_get = store.get
    store.get = lambda k: (reads.append(k), orig_get(k))[1]
    fresh = ServerCheckpointManager(store, "hs")
    assert fresh.latest_complete_round() == 2
    assert reads == []  # presence scan only — no object reads per poll


# ---------------------------------------------------------------------------
# 2. the watcher state machine
# ---------------------------------------------------------------------------


@pytest.fixture()
def served(tmp_path):
    """Round-1 checkpoint served by a live batcher + its manager."""
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _serve_cfg(prefix_cache=True)
    cfg.run_uuid = "hs"
    store = FileStore(tmp_path)
    mgr = ServerCheckpointManager(store, "hs")
    params1 = _save_round(mgr, cfg, 1, seed=1)
    engine = PagedEngine.from_checkpoint(cfg, store=store, resume_round=-1)
    batcher = ContinuousBatcher(engine, max_queue=16).start()
    yield cfg, store, mgr, params1, engine, batcher
    batcher.close()


def test_watcher_swaps_to_new_round(served):
    cfg, store, mgr, params1, engine, batcher = served
    w = _watcher(batcher, mgr, cfg, poll_s=0.05)
    assert w.poll_once() == "idle"
    prompt = [5, 9, 2, 7]
    assert batcher.submit(prompt, 4).result(timeout=120) \
        == _offline_greedy(cfg, params1, prompt, 4)
    params2 = _save_round(mgr, cfg, 2, seed=2)
    assert w.poll_once() == "swapped"
    assert engine.loaded_round == 2 and batcher.swaps == 1
    assert w.swaps_applied == 1
    # post-swap output comes from the NEW round's params
    assert batcher.submit(prompt, 4).result(timeout=120) \
        == _offline_greedy(cfg, params2, prompt, 4)
    assert w.poll_once() == "idle"  # no re-swap of the same round


@pytest.mark.chaos
def test_watcher_skips_corrupt_candidate_and_keeps_serving(served):
    """The chaos e2e: the candidate round's params object is bitflipped on
    write (photon.chaos store fault, scope=hotswap, capped at exactly one
    corrupting fault). The watcher must skip-and-warn, count the
    rejection, and keep serving the old round — then track a later clean
    round normally."""
    from photon_tpu import chaos
    from photon_tpu.config.schema import ChaosConfig

    cfg, store, mgr, params1, engine, batcher = served
    w = _watcher(batcher, mgr, cfg, poll_s=0.05)
    chaos.install(
        ChaosConfig(enabled=True, seed=1234, store_bitflip_p=1.0,
                    store_fault_max=1),
        scope="hotswap",
    )
    try:
        # first put under the injector = round 2's params npz → bitflipped
        _save_round(mgr, cfg, 2, seed=2)
        inj = chaos.active()
        assert inj is not None and inj.counts["store_bitflip"] == 1
    finally:
        chaos.uninstall()
    with pytest.warns(UserWarning, match="skipping candidate round 2"):
        assert w.poll_once() == "skipped-corrupt"
    assert w.rejected_corrupt == 1 and engine.loaded_round == 1
    # still serving the OLD params, bit-identically
    prompt = [3, 1, 4, 1]
    assert batcher.submit(prompt, 4).result(timeout=120) \
        == _offline_greedy(cfg, params1, prompt, 4)
    # the same corrupt candidate warns AND counts once (verify memoized,
    # warn + counter + health alert deduped per round — a stalled run must
    # not grow the rejected counter every poll forever)
    assert w.poll_once() == "skipped-corrupt"
    assert w.rejected_corrupt == 1
    # a later clean round still swaps — corruption never wedges tracking
    params3 = _save_round(mgr, cfg, 3, seed=3)
    assert w.poll_once() == "swapped" and engine.loaded_round == 3
    assert batcher.submit(prompt, 4).result(timeout=120) \
        == _offline_greedy(cfg, params3, prompt, 4)


def test_watcher_refuses_during_drain(served):
    cfg, store, mgr, params1, engine, batcher = served
    w = _watcher(batcher, mgr, cfg, poll_s=0.05)
    _save_round(mgr, cfg, 2, seed=2)
    assert batcher.drain(5.0) is True  # SIGTERM path: drains, then stops
    assert w.poll_once() == "skipped-draining"
    assert engine.loaded_round == 1 and w.swaps_applied == 0


def test_watcher_health_gate_blocks_failing_federation(served):
    """A /statusz answering `federation: failing` blocks the swap; once the
    plane recovers the same candidate swaps. Unreachable endpoints fail
    open (a dead observability server must not freeze the fleet)."""
    from http.server import BaseHTTPRequestHandler, HTTPServer

    cfg, store, mgr, params1, engine, batcher = served
    state = {"status": "failing"}

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = json.dumps({
                "status": state["status"],
                "planes": {"federation": {"status": state["status"]}},
            }).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = HTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=httpd.serve_forever,
                         name="hs-statusz", daemon=True)
    t.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}/statusz"
    try:
        w = _watcher(batcher, mgr, cfg, poll_s=0.05, statusz_url=url)
        _save_round(mgr, cfg, 2, seed=2)
        with pytest.warns(UserWarning, match="federation-failing"):
            assert w.poll_once() == "skipped-health"
        assert engine.loaded_round == 1
        state["status"] = "ok"
        assert w.poll_once() == "swapped" and engine.loaded_round == 2
    finally:
        httpd.shutdown()
        httpd.server_close()
        t.join(timeout=5)
    # unreachable endpoint: fail open
    w2 = _watcher(batcher, mgr, cfg, poll_s=0.05, statusz_url=url)
    _save_round(mgr, cfg, 3, seed=3)
    assert w2.poll_once() == "swapped" and engine.loaded_round == 3


def test_watcher_health_gate_fails_open_on_non_dict_json(served):
    """A misrouted statusz URL answering valid-but-wrong-shape JSON (a
    list) must fail OPEN, not wedge the watcher in an error loop."""
    from http.server import BaseHTTPRequestHandler, HTTPServer

    cfg, store, mgr, params1, engine, batcher = served

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = b"[1, 2, 3]\n"
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = HTTPServer(("127.0.0.1", 0), H)
    t = threading.Thread(target=httpd.serve_forever,
                         name="hs-statusz-garbage", daemon=True)
    t.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}/statusz"
    try:
        w = _watcher(batcher, mgr, cfg, poll_s=0.05, statusz_url=url)
        _save_round(mgr, cfg, 2, seed=2)
        assert w.poll_once() == "swapped" and engine.loaded_round == 2
    finally:
        httpd.shutdown()
        httpd.server_close()
        t.join(timeout=5)


def test_failed_swap_apply_releases_waiter_and_keeps_serving(served):
    """engine.set_params blowing up mid-apply must still set the staged
    swap's done event (the watcher observes the unchanged round — no
    permanent 'pending' wedge) and the batcher must keep serving on the
    old params."""
    cfg, store, mgr, params1, engine, batcher = served
    real = engine.set_params
    engine.set_params = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("injected swap failure"))
    try:
        done = batcher.request_swap(dict(params1), loaded_round=99)
        assert done.wait(30)  # released despite the failure
        assert engine.loaded_round == 1  # never applied
    finally:
        engine.set_params = real
    prompt = [4, 4, 2, 1]
    assert batcher.submit(prompt, 3).result(timeout=120) \
        == _offline_greedy(cfg, params1, prompt, 3)  # still serving


# ---------------------------------------------------------------------------
# 3. swap semantics
# ---------------------------------------------------------------------------


def test_inflight_finish_on_old_params_and_cache_flushes(served):
    """A swap requested mid-generation: the running request's FULL output
    is the old round's (bit-identical to its oracle), the swap applies
    only after it finishes, and the prefix cache is flushed."""
    cfg, store, mgr, params1, engine, batcher = served
    warm = [5, 9, 2, 7, 1, 8]
    batcher.submit(warm, 2).result(timeout=120)  # warm compiles + cache
    assert len(engine.prefix_cache) > 0
    params2 = _save_round(mgr, cfg, 2, seed=2)
    req = batcher.submit(warm + [4], 8)  # long decode: 8 steps in flight
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and engine.n_active == 0:
        time.sleep(0.002)
    assert engine.n_active > 0  # genuinely in flight before the swap stages
    done = batcher.request_swap(params2, loaded_round=2)
    out = req.result(timeout=120)
    assert out == _offline_greedy(cfg, params1, warm + [4], 8)  # OLD params
    assert done.wait(60)
    assert engine.loaded_round == 2
    assert len(engine.prefix_cache) == 0  # old-param KV flushed
    # and a fresh request decodes with the new round
    assert batcher.submit(warm, 4).result(timeout=120) \
        == _offline_greedy(cfg, params2, warm, 4)


def test_zero_dropped_requests_across_live_swap(served, tmp_path):
    """Continuous HTTP traffic across a
    watcher-driven swap — every response is a 200 whose tokens equal the
    old OR the new round's oracle (each request ran on exactly one), and
    the daemon ends on the new round."""
    from photon_tpu.serve.frontend import ServeFrontend

    cfg, store, mgr, params1, engine, batcher = served
    fe = ServeFrontend(batcher, max_new_tokens_cap=8)
    port = fe.start()
    w = _watcher(batcher, mgr, cfg, poll_s=0.02)
    prompt = [5, 9, 2, 7]
    want1 = _offline_greedy(cfg, params1, prompt, 6)
    results: list[tuple[int, dict]] = []
    lock = threading.Lock()

    def client(i):
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        for _ in range(6):
            c.request("POST", "/generate",
                      json.dumps({"tokens": prompt, "max_new_tokens": 6}))
            r = c.getresponse()
            body = json.loads(r.read())
            with lock:
                results.append((r.status, body))
        c.close()

    try:
        batcher.submit(prompt, 2).result(timeout=120)  # warm compiles
        threads = [threading.Thread(target=client, args=(i,),
                                    name=f"hs-client-{i}", daemon=True)
                   for i in range(3)]
        for t in threads:
            t.start()
        params2 = _save_round(mgr, cfg, 2, seed=2)
        w.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        w.close()
        fe.close()
    want2 = _offline_greedy(cfg, params2, prompt, 6)
    assert len(results) == 18
    dropped = [r for r in results if r[0] != 200]
    assert dropped == []  # ZERO dropped/failed across the live swap
    for status, body in results:
        assert body["tokens"] in (want1, want2), body
    assert engine.loaded_round == 2 and batcher.swaps == 1
    # the swap actually happened mid-traffic for at least one client
    assert any(body["tokens"] == want2 for _, body in results)


def test_healthz_reports_hotswap_and_prefix(served):
    from photon_tpu.serve.frontend import ServeFrontend

    cfg, store, mgr, params1, engine, batcher = served
    fe = ServeFrontend(batcher, max_new_tokens_cap=8)
    fe.watcher = _watcher(batcher, mgr, cfg, poll_s=0.05)
    port = fe.start()
    try:
        batcher.submit([5, 9, 2], 2).result(timeout=120)
        c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        c.request("GET", "/healthz")
        h = json.loads(c.getresponse().read())
        assert h["round"] == 1 and h["swaps"] == 0
        assert h["prefix_cache"]["entries"] == len(engine.prefix_cache)
        assert h["hotswap"]["last_outcome"] == "idle"
    finally:
        fe.close()


def test_hotswap_events_and_metrics(served):
    """The swap emits the registry-named event + latency histogram, the
    corrupt skip bumps the typed rejected counter, and every recorded KPI
    stays registry-known."""
    from photon_tpu import telemetry
    from photon_tpu.config.schema import TelemetryConfig
    from photon_tpu.utils.profiling import (
        EVENT_HOTSWAP_SWAPPED,
        SERVE_HOTSWAP_SWAP_LATENCY_S,
        SERVE_HOTSWAP_SWAPS_TOTAL,
        is_registered_metric,
    )

    cfg, store, mgr, params1, engine, batcher = served
    w = _watcher(batcher, mgr, cfg, poll_s=0.05)
    telemetry.install(TelemetryConfig(enabled=True), scope="serve")
    try:
        _save_round(mgr, cfg, 2, seed=2)
        assert w.poll_once() == "swapped"
        batcher.submit([5, 9, 2], 2).result(timeout=120)
        events = telemetry.drain_events()
        assert any(e["kind"] == EVENT_HOTSWAP_SWAPPED and e["attrs"]["round"] == 2
                   for e in events), events
        hub = telemetry.metrics_active()
        hist = hub.histogram(SERVE_HOTSWAP_SWAP_LATENCY_S)
        assert hist.count >= 1
    finally:
        telemetry.uninstall()
    recorded = set(batcher.history.rounds)
    assert SERVE_HOTSWAP_SWAPS_TOTAL in recorded
    unregistered = sorted(k for k in recorded if not is_registered_metric(k))
    assert not unregistered, unregistered
