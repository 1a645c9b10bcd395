"""Asynchronous federated rounds (ISSUE 18).

Contract layers:

1. **The bit-parity pin**: with homogeneous client speed and
   ``K == n_total_clients`` the async runner IS the synchronous runner —
   bit-for-bit identical parameters and optimizer state after N
   versions/rounds, for all five server optimizers, fp32 AND q8, fused
   device plane AND host path. This is the transitive oracle: every
   correctness property the sync suite proves transfers to the async
   zero-staleness corner for free.
2. staleness-discount weight math (poly/const, dtype signature switch);
3. the robustness ladder reframed on the version clock: max-staleness
   reject → fresh-version re-broadcast, min-arrivals stall (never an
   aborted run), liveness edge drops the in-flight delta, SIGKILL-mid-fit
   drops cleanly while the clock keeps advancing;
4. chaos determinism: the seeded per-client ``fit_delay_plan``;
5. the acceptance e2e: SIGKILL one client + 4x-slow another mid-stream →
   survivors advance the clock unaffected, and the PR 10 hot-swap watcher
   consumes a streamed version mid-traffic with zero dropped requests.
"""

import threading

import numpy as np
import pytest

from photon_tpu import chaos, telemetry
from photon_tpu.config.schema import Config, TelemetryConfig
from photon_tpu.federation.async_round import AsyncFedRunner
from photon_tpu.federation.collective_round import CollectiveFedRunner


@pytest.fixture(autouse=True)
def _clean_planes():
    yield
    chaos.uninstall()
    telemetry.uninstall()


def _cfg(tmp_path, strategy="fedavg", n_clients=2, quantization="off",
         device_opt=True, n_rounds=3, K=0, min_arrivals=1, max_staleness=4,
         power=1.0) -> Config:
    cfg = Config()
    cfg.model.d_model = 32
    cfg.model.n_layers = 2
    cfg.model.n_heads = 2
    cfg.model.max_seq_len = 16
    cfg.model.vocab_size = 64
    cfg.model.attn_impl = "xla"
    cfg.model.compute_dtype = "float32"
    cfg.train.global_batch_size = 4
    cfg.train.device_microbatch_size = 4
    cfg.fl.n_total_clients = n_clients
    cfg.fl.n_clients_per_round = n_clients
    cfg.fl.n_rounds = n_rounds
    cfg.fl.local_steps = 2
    cfg.fl.eval_interval_rounds = 0
    cfg.fl.strategy_name = strategy
    cfg.fl.server_learning_rate = 1.0 if strategy == "fedavg" else 0.01
    if strategy in ("fedadam", "fedyogi"):
        cfg.fl.server_tau = 1e-3
    cfg.dataset.synthetic = True
    cfg.photon.checkpoint = False
    cfg.photon.comm_stack.collective = True
    cfg.photon.comm_stack.shm = False
    cfg.photon.comm_stack.collective_replica = 2
    cfg.photon.comm_stack.collective_quantization = quantization
    cfg.photon.comm_stack.collective_q8_block = 64
    cfg.photon.comm_stack.collective_device_optimizer = device_opt
    cfg.photon.save_path = str(tmp_path / "run")
    cfg.run_uuid = "async-round"
    return cfg


def _async_cfg(tmp_path, **kw) -> Config:
    cfg = _cfg(tmp_path, **kw)
    ar = cfg.photon.async_rounds
    ar.enabled = True
    ar.buffer_size = kw.get("K", 0)
    ar.min_arrivals = kw.get("min_arrivals", 1)
    ar.max_staleness = kw.get("max_staleness", 4)
    ar.staleness_power = kw.get("power", 1.0)
    cfg.validate()
    return cfg


# ---------------------------------------------------------------------------
# 1. the bit-parity pin: zero staleness + K = cohort == the synchronous round
# ---------------------------------------------------------------------------


def _assert_bit_identical(a: AsyncFedRunner, s: CollectiveFedRunner):
    assert a.server_steps_cumulative == s.server_steps_cumulative
    for pa, ps in zip(a.strategy.current_parameters,
                      s.strategy.current_parameters):
        assert np.array_equal(pa, ps)
    sa, ss = a.state_for_checkpoint(), s.state_for_checkpoint()
    assert set(sa) == set(ss)
    for k in sa:
        for xa, xs in zip(sa[k], ss[k]):
            assert np.array_equal(xa, xs), k


@pytest.mark.parametrize(
    "strategy,quantization",
    [
        ("fedavg", "off"),
        ("fedadam", "q8"),
        pytest.param("fedavg", "q8", marks=pytest.mark.slow),
        pytest.param("nesterov", "off", marks=pytest.mark.slow),
        pytest.param("nesterov", "q8", marks=pytest.mark.slow),
        pytest.param("fedmom", "off", marks=pytest.mark.slow),
        pytest.param("fedmom", "q8", marks=pytest.mark.slow),
        pytest.param("fedadam", "off", marks=pytest.mark.slow),
        pytest.param("fedyogi", "off", marks=pytest.mark.slow),
        pytest.param("fedyogi", "q8", marks=pytest.mark.slow),
    ],
)
def test_zero_staleness_is_bitexact_sync(tmp_path, strategy, quantization):
    """K = cohort + homogeneous speed: every buffer is the full cohort at
    staleness 0, the int32 weight signature reuses the compiled sync
    program, and N async versions == N sync rounds bit-for-bit — params
    AND optimizer state, through the fused ZeRO-1 device plane."""
    sync_cfg = _cfg(tmp_path / "sync", strategy=strategy,
                    quantization=quantization)
    sync_cfg.validate()
    sync = CollectiveFedRunner(sync_cfg, [0, 1])
    for r in (1, 2, 3):
        sync.run_round(r)

    acfg = _async_cfg(tmp_path / "async", strategy=strategy,
                      quantization=quantization)
    runner = AsyncFedRunner(acfg, [0, 1])
    runner.run_versions(3, eval_every=0)

    assert runner.version == 3
    assert all(runner.aggregation_paths[v] == "async" for v in (1, 2, 3))
    _assert_bit_identical(runner, sync)
    # the parity fold rode the sync program: int32 weights, no discounts
    assert runner.history.latest("server/async_staleness_max") == 0.0
    assert runner.history.latest("server/async_discount_mean") == 1.0


def test_zero_staleness_bitexact_host_path(tmp_path):
    """Same pin with the device optimizer off: the async fold lands in
    ``_apply_average_host`` exactly like the sync host path."""
    sync_cfg = _cfg(tmp_path / "sync", device_opt=False)
    sync_cfg.validate()
    sync = CollectiveFedRunner(sync_cfg, [0, 1])
    for r in (1, 2, 3):
        sync.run_round(r)

    runner = AsyncFedRunner(_async_cfg(tmp_path / "async", device_opt=False),
                            [0, 1])
    runner.run_versions(3, eval_every=0)
    _assert_bit_identical(runner, sync)
    # N_SAMPLES stayed the sync path's integer total
    assert runner.history.latest("server/n_samples") \
        == sync.history.latest("server/n_samples")


def test_async_steady_state_is_compile_free(tmp_path):
    """Every fold zero-pads to the one full-mesh program: versions 2+ run
    the version-1 executables (PR 6 retrace discipline on the new loop)."""
    from photon_tpu.analysis.runtime import (
        install_retrace_sentinel,
        uninstall_retrace_sentinel,
    )

    cfg = _async_cfg(tmp_path, strategy="fedadam", n_rounds=4)
    sentinel = install_retrace_sentinel()
    try:
        runner = AsyncFedRunner(cfg, [0, 1])
        sentinel.mark_steady_after(1)  # version 1 = fit + fold compiles
        runner.run_versions(4, eval_every=0)
        sentinel.check("async/steady-state")
    finally:
        uninstall_retrace_sentinel()
    assert runner.version == 4


# ---------------------------------------------------------------------------
# 2. staleness-discount weight math
# ---------------------------------------------------------------------------


def test_staleness_discount_policies():
    from photon_tpu.parallel.collective_agg import staleness_discount

    np.testing.assert_allclose(
        staleness_discount([0, 1, 3], "poly", 1.0), [1.0, 0.5, 0.25]
    )
    np.testing.assert_allclose(
        staleness_discount([0, 1, 3], "poly", 2.0), [1.0, 0.25, 0.0625]
    )
    np.testing.assert_allclose(
        staleness_discount([0, 5, 9], "const"), [1.0, 1.0, 1.0]
    )
    with pytest.raises(ValueError, match="staleness"):
        staleness_discount([-1], "poly")
    with pytest.raises(ValueError):
        staleness_discount([0], "exp")


def test_discounted_fold_weights_dtype_signature():
    """All-fresh buffers come back int32 — the EXACT input signature of
    the compiled sync program (the parity mechanism); any real discount
    switches to float32 sample-weight products."""
    from photon_tpu.parallel.collective_agg import discounted_fold_weights

    w = discounted_fold_weights([10, 20], [0, 0])
    assert w.dtype == np.int32 and list(w) == [10, 20]
    w = discounted_fold_weights([10, 20], [0, 1], "poly", 1.0)
    assert w.dtype == np.float32
    np.testing.assert_allclose(w, [10.0, 10.0])
    # const policy never discounts — int32 at ANY staleness
    w = discounted_fold_weights([10, 20], [0, 7], "const")
    assert w.dtype == np.int32


# ---------------------------------------------------------------------------
# 3. the robustness ladder on the version clock
# ---------------------------------------------------------------------------


def test_max_staleness_reject_rebroadcasts_fresh_version(tmp_path):
    """K=1 + a pinned 4x-slow client: the fast client advances the clock;
    the slow delta lands 3 versions stale > max_staleness=0, is rejected
    (counted, evented) and the client re-dispatched from the CURRENT
    version — its next delta is fresh."""
    events_path = tmp_path / "events.jsonl"
    telemetry.install(TelemetryConfig(enabled=True), scope="server",
                      events_path=str(events_path))
    cfg = _async_cfg(tmp_path, K=1, max_staleness=0, n_rounds=5)
    cfg.photon.chaos.enabled = True
    cfg.photon.chaos.fit_delay_factor = 4.0
    cfg.photon.chaos.fit_delay_cid = 1
    cfg.validate()
    chaos.install(cfg.photon.chaos, scope="collective0")
    runner = AsyncFedRunner(cfg, [0, 1])
    runner.run_versions(5, eval_every=0)

    assert runner.version == 5
    assert runner.rejected_total == 1
    assert runner.history.latest("server/async_rejected_total") == 1.0
    telemetry.uninstall()
    events = telemetry.read_events_jsonl(str(events_path))
    rejects = [e for e in events if e["kind"] == "async/stale_reject"]
    assert len(rejects) == 1
    assert rejects[0]["attrs"]["cid"] == 1
    assert rejects[0]["attrs"]["staleness"] == 3
    assert any(e["kind"] == "chaos/fit_delay" for e in events)
    assert any(e["kind"] == "async/version_advance" for e in events)


def test_min_arrivals_stall_holds_clock_never_aborts(tmp_path):
    """One client SIGKILLed at its first fit leaves a single contributor:
    the buffer fills (same cid twice) but min_arrivals=2 holds the version
    clock — stall counted + evented, the run RETURNS (no exception, no
    abort) at version 0."""
    events_path = tmp_path / "events.jsonl"
    telemetry.install(TelemetryConfig(enabled=True), scope="server",
                      events_path=str(events_path))
    cfg = _async_cfg(tmp_path, K=2, min_arrivals=2, n_rounds=2)
    cfg.photon.chaos.enabled = True
    cfg.photon.chaos.crash_phase = "mid-fit"
    cfg.photon.chaos.crash_round = 1
    cfg.photon.chaos.crash_marker = str(tmp_path / "crash.marker")
    cfg.validate()

    def _client_crash(code):
        raise RuntimeError(f"simulated SIGKILL ({code})")

    chaos.install(cfg.photon.chaos, scope="collective0",
                  crash_fn=_client_crash)
    runner = AsyncFedRunner(cfg, [0, 1])
    with pytest.warns(UserWarning):
        hist = runner.run_versions(2, eval_every=0)

    assert runner.version == 0  # the clock held — never advanced undiverse
    assert runner.stalls_total >= 1
    assert runner.dropped_total == 1  # the SIGKILLed fit's delta
    assert hist is runner.history  # returned, not raised
    telemetry.uninstall()
    kinds = [e["kind"]
             for e in telemetry.read_events_jsonl(str(events_path))]
    assert "async/min_arrivals_stall" in kinds
    assert "async/delta_dropped" in kinds


def test_liveness_edge_drops_inflight_delta(tmp_path):
    """A delta in flight when its client goes dead is dropped at delivery:
    evented, counted, never buffered, client not re-dispatched."""
    cfg = _async_cfg(tmp_path, K=2)
    runner = AsyncFedRunner(cfg, [0, 1])
    assert runner._dispatch(0) and runner._dispatch(1)
    # the liveness plane marks client1 dead while its delta is in flight
    runner.liveness.observe_miss("client1")
    runner.liveness.observe_miss("client1")
    survivors = [
        cid for cid, arrays, n, base in runner._pop_burst()
        if runner._admit(cid, arrays, n, base)
    ]
    assert survivors == [0]
    assert runner.dropped_total == 1
    assert [e.cid for e in runner.buffer] == [0]


def test_sigkill_mid_fit_drops_cleanly_clock_advances(tmp_path):
    """SIGKILL (chaos mid-fit, one-shot marker) under the async loop: the
    killed client's would-be delta is dropped cleanly, survivors keep the
    version clock advancing to target, params stay finite."""
    cfg = _async_cfg(tmp_path, n_clients=3, K=2, n_rounds=4)
    cfg.photon.chaos.enabled = True
    cfg.photon.chaos.crash_phase = "mid-fit"
    cfg.photon.chaos.crash_round = 2  # first re-dispatch after version 1
    cfg.photon.chaos.crash_marker = str(tmp_path / "crash.marker")
    cfg.validate()

    def _client_crash(code):
        raise RuntimeError(f"simulated SIGKILL ({code})")

    inj = chaos.install(cfg.photon.chaos, scope="collective0",
                        crash_fn=_client_crash)
    runner = AsyncFedRunner(cfg, [0, 1, 2])
    with pytest.warns(UserWarning, match="delta is dropped"):
        runner.run_versions(4, eval_every=0)

    assert inj.counts["crash"] == 1
    assert runner.version == 4
    assert runner.dropped_total == 1
    for p in runner.strategy.current_parameters:
        assert np.all(np.isfinite(p))
    # the async clock rode into the checkpointed control state
    control = runner.control_state_for_checkpoint()
    assert control["async_version"] == 4
    assert control["async_dropped_total"] == 1


def test_grouped_burst_matches_sequential_folds(tmp_path):
    """B complete buffers landing in one burst on the host path fold
    through ONE grouped-SPMD program; the result matches B sequential
    single-buffer folds on an identically-seeded runner."""
    cfg = _async_cfg(tmp_path / "a", K=1, device_opt=False)
    ra = AsyncFedRunner(cfg, [0, 1])
    rb = AsyncFedRunner(
        _async_cfg(tmp_path / "b", K=1, device_opt=False), [0, 1]
    )
    for p, q in zip(ra.strategy.current_parameters,
                    rb.strategy.current_parameters):
        assert np.array_equal(p, q)  # same seed → same init
    assert ra._dispatch(0) and ra._dispatch(1)
    burst = ra._pop_burst()
    for cid, arrays, n, base in burst:
        assert ra._admit(cid, arrays, n, base)
    buffers = [[ra.buffer[0]], [ra.buffer[1]]]
    ra.buffer = []
    ra._fold_grouped(buffers)
    rb._fold_one([buffers[0][0]])
    rb._fold_one([buffers[1][0]])
    assert ra.version == rb.version == 2
    for p, q in zip(ra.strategy.current_parameters,
                    rb.strategy.current_parameters):
        np.testing.assert_allclose(p, q, rtol=1e-6, atol=1e-7)


def test_fold_failure_rolls_back_and_continues(tmp_path, monkeypatch):
    """A fold that raises mid-update restores the per-version snapshot:
    params/state/step-counter exactly at the pre-fold version, clock held,
    loop continues (never an aborted run)."""
    cfg = _async_cfg(tmp_path, device_opt=False)
    runner = AsyncFedRunner(cfg, [0, 1])
    assert runner._dispatch(0) and runner._dispatch(1)
    for cid, arrays, n, base in runner._pop_burst():
        runner._admit(cid, arrays, n, base)
    before = [p.copy() for p in runner.strategy.current_parameters]

    def _boom(*a, **k):
        raise RuntimeError("torn fold")

    monkeypatch.setattr(runner.strategy, "apply_average", _boom)
    entries = runner.buffer[:runner.K]
    del runner.buffer[:runner.K]
    with pytest.warns(UserWarning, match="rolled back"):
        runner._fold_one(entries)
    assert runner.version == 0
    assert runner.folds_failed_total == 1
    for p, q in zip(before, runner.strategy.current_parameters):
        assert np.array_equal(p, q)
    assert runner.history.latest("server/round_failed") == 1.0


# ---------------------------------------------------------------------------
# 4. chaos: deterministic per-client fit delay
# ---------------------------------------------------------------------------


def test_fit_delay_plan_deterministic_and_scoped(tmp_path):
    from photon_tpu.chaos.injector import FaultInjector, validate_chaos_config

    cfg = Config().photon.chaos
    cfg.enabled = True
    cfg.fit_delay_factor = 4.0
    validate_chaos_config(cfg)
    a = FaultInjector(cfg, scope="nodeA")
    # pure function of (seed, scope, cid): stable across calls + injectors
    f0, f1 = a.fit_delay_plan(0), a.fit_delay_plan(1)
    assert a.fit_delay_plan(0) == f0 and a.fit_delay_plan(1) == f1
    assert FaultInjector(cfg, scope="nodeA").fit_delay_plan(0) == f0
    assert 1.0 <= f0 < 4.0 and 1.0 <= f1 < 4.0
    assert f0 != f1  # seeded per-client draw, not one global slowdown
    assert FaultInjector(cfg, scope="nodeB").fit_delay_plan(0) != f0
    assert a.counts["fit_delay"] >= 2

    # pinned cid: exact ceiling on that client, no-op on every other
    cfg.fit_delay_cid = 1
    b = FaultInjector(cfg, scope="nodeA")
    assert b.fit_delay_plan(1) == 4.0
    assert b.fit_delay_plan(0) == 1.0

    # off (factor 0) and identity (factor 1) never fire the hook
    cfg.fit_delay_factor = 0.0
    assert FaultInjector(cfg, scope="x").fit_delay_plan(3) == 1.0
    cfg.fit_delay_factor = 0.5
    with pytest.raises(ValueError, match="fit_delay_factor"):
        validate_chaos_config(cfg)


def test_fit_delay_rides_fit_metrics(tmp_path):
    """The injector's factor lands in FitRes metrics — the wire the async
    DES clock reads its per-client duration from."""
    cfg = _async_cfg(tmp_path, K=1, n_rounds=1)
    cfg.photon.chaos.enabled = True
    cfg.photon.chaos.fit_delay_factor = 4.0
    cfg.photon.chaos.fit_delay_cid = 1
    cfg.validate()
    chaos.install(cfg.photon.chaos, scope="collective0")
    runner = AsyncFedRunner(cfg, [0, 1])
    assert runner._dispatch(0) and runner._dispatch(1)
    times = {runner._inflight[seq][0]: t for t, seq in runner._heap}
    assert times[1] == pytest.approx(4.0 * times[0])


def test_async_reaches_sync_loss_sooner_on_the_modeled_clock(tmp_path):
    """ISSUE 18's count, on the deterministic clock and never the wall
    clock: with one of four clients pinned 4x slow, the synchronous round
    pays the straggler every round (``fit_time_s x 4`` each), the buffered
    server (K=2) folds the fast clients' deltas as they land — and reaches
    the sync run's final eval loss at a strictly earlier
    ``server/async_sim_time``."""
    from photon_tpu.utils.profiling import ASYNC_SIM_TIME, EVAL_LOSS

    skew, sync_rounds = 4.0, 3

    def skewed(cfg):
        cfg.photon.chaos.enabled = True
        cfg.photon.chaos.fit_delay_factor = skew
        cfg.photon.chaos.fit_delay_cid = 3
        return cfg.validate()

    sync_cfg = skewed(_cfg(tmp_path / "sync", n_clients=4))
    chaos.install(sync_cfg.photon.chaos, scope="collective0")
    sync = CollectiveFedRunner(sync_cfg, [0, 1, 2, 3])
    for r in range(1, sync_rounds + 1):
        sync.run_round(r)
    target = float(sync.evaluate_round(sync_rounds)[EVAL_LOSS])
    sync_time = sync_rounds * sync_cfg.photon.async_rounds.fit_time_s * skew
    chaos.uninstall()

    async_cfg = skewed(_async_cfg(tmp_path / "async", n_clients=4, K=2))
    chaos.install(async_cfg.photon.chaos, scope="collective0")
    runner = AsyncFedRunner(async_cfg, [0, 1, 2, 3])
    runner.run_versions(16, eval_every=1)
    sims = dict(runner.history.series(ASYNC_SIM_TIME))
    reached = [sims[v] for v, loss in runner.history.series(EVAL_LOSS)
               if v > 0 and loss <= target and v in sims]
    assert reached, f"16 versions never reached the sync loss {target}"
    assert reached[0] < sync_time, (reached[0], sync_time)


# ---------------------------------------------------------------------------
# 5. config plumbing
# ---------------------------------------------------------------------------


def test_async_config_validation(tmp_path):
    cfg = _cfg(tmp_path)
    cfg.photon.async_rounds.enabled = True
    cfg.photon.comm_stack.collective = False
    cfg.photon.comm_stack.shm = True
    with pytest.raises(ValueError, match="collective"):
        cfg.validate()

    cfg = _cfg(tmp_path)
    cfg.photon.async_rounds.enabled = True
    cfg.photon.async_rounds.staleness_policy = "exp"
    with pytest.raises(ValueError, match="staleness_policy"):
        cfg.validate()

    cfg = _cfg(tmp_path)
    cfg.photon.async_rounds.enabled = True
    cfg.photon.async_rounds.buffer_size = 1
    cfg.photon.async_rounds.min_arrivals = 2
    with pytest.raises(ValueError, match="min_arrivals"):
        cfg.validate()

    cfg = _cfg(tmp_path)
    cfg.photon.async_rounds.buffer_size = 3  # knobs set but enabled=False
    with pytest.warns(UserWarning, match="async_rounds"):
        cfg.validate()


def test_async_runner_requires_enabled(tmp_path):
    cfg = _cfg(tmp_path)
    cfg.validate()
    with pytest.raises(ValueError, match="async_rounds.enabled"):
        AsyncFedRunner(cfg, [0, 1])


# ---------------------------------------------------------------------------
# 6. the acceptance e2e: chaos mid-stream + hot-swap mid-traffic
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_chaos_stream_hotswap_consumes_versions_mid_traffic(tmp_path):
    """SIGKILL one client (chaos mid-fit) AND 4x-slow another mid-stream:
    the version clock keeps advancing on survivors, every advance streams
    a version-tagged checkpoint, and a live serving plane (PagedEngine +
    ContinuousBatcher + CheckpointWatcher) swaps to streamed versions
    mid-traffic with ZERO dropped requests."""
    from photon_tpu.checkpoint import FileStore
    from photon_tpu.checkpoint.server import ServerCheckpointManager
    from photon_tpu.serve.engine import PagedEngine
    from photon_tpu.serve.hotswap import CheckpointWatcher
    from photon_tpu.serve.scheduler import ContinuousBatcher

    cfg = _async_cfg(tmp_path, n_clients=3, K=2, n_rounds=3)
    cfg.photon.serve.n_slots = 2
    cfg.photon.serve.block_size = 4
    cfg.photon.serve.max_new_tokens = 4
    cfg.photon.chaos.enabled = True
    cfg.photon.chaos.crash_phase = "mid-fit"
    cfg.photon.chaos.crash_round = 2
    cfg.photon.chaos.crash_marker = str(tmp_path / "crash.marker")
    cfg.photon.chaos.fit_delay_factor = 4.0
    cfg.photon.chaos.fit_delay_cid = 2
    cfg.validate()

    def _client_crash(code):
        raise RuntimeError(f"simulated SIGKILL ({code})")

    chaos.install(cfg.photon.chaos, scope="collective0",
                  crash_fn=_client_crash)
    store = FileStore(tmp_path / "store")
    mgr = ServerCheckpointManager(store, cfg.run_uuid)

    runner = AsyncFedRunner(cfg, [0, 1, 2])
    runner.save_checkpoint(mgr, 0)  # the round the engine boots from
    engine = PagedEngine.from_checkpoint(cfg, store=store, resume_round=-1)
    batcher = ContinuousBatcher(engine, max_queue=16).start()
    watcher = CheckpointWatcher(batcher, mgr, cfg, poll_s=0.01)
    assert engine.loaded_round == 0

    err: list[BaseException] = []

    def _train():
        try:
            with pytest.warns(UserWarning):
                runner.run_versions(3, ckpt_mgr=mgr, ckpt_every=1,
                                    eval_every=0)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            err.append(e)

    t = threading.Thread(target=_train)
    t.start()
    futures = []
    try:
        import time as _time

        from photon_tpu.serve.scheduler import (
            DrainingError,
            QueueFullError,
        )

        while t.is_alive():
            try:
                # drain-window/full-queue rejections are the admission
                # plane's 429/503 — an ACCEPTED request must never drop
                futures.append(batcher.submit([5, 9, 2], 4))
            except (DrainingError, QueueFullError):
                pass
            watcher.poll_once()
            _time.sleep(0.02)
        t.join()
        # drain the tail: the final streamed version must be consumable
        deadline = 100
        while engine.loaded_round < 3 and deadline:
            watcher.poll_once()
            _time.sleep(0.02)
            deadline -= 1
        futures.append(batcher.submit([5, 9, 2], 4))
        # ZERO dropped: every request admitted across the swaps completes
        assert futures
        for f in futures:
            out = f.result(timeout=120)
            assert len(out) == 4
    finally:
        batcher.close()
    assert not err, err
    assert runner.version == 3  # survivors advanced the clock to target
    assert runner.dropped_total == 1  # the SIGKILLed fit
    assert engine.loaded_round == 3 and batcher.swaps >= 1
    assert watcher.swaps_applied >= 1
    # the streamed manifests carry the async clock in server_state
    _, _, _, server_state = mgr.load_round(3)
    assert server_state["async_version"] == 3
