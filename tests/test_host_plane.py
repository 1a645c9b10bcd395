"""Host-plane round pipeline (ISSUE 2): shared pool semantics, bit-exact
fused/parallel aggregation, decode-ahead, KPI metrics, async checkpoints.

The load-bearing contract: every pipeline mode (serial, threads=1 inline,
threads=N) applies identical per-element operations in identical order, so
the aggregated fp32 result is BYTE-identical across configurations — the
``photon.host_threads`` knob moves wall-clock only, never results.
"""

import time

import numpy as np
import pytest

from photon_tpu.checkpoint import FileStore, ServerCheckpointManager
from photon_tpu.codec import ParamsMetadata
from photon_tpu.compression import Codec
from photon_tpu.strategy.aggregation import _FOLD_CHUNK, _fold_into, aggregate_inplace
from photon_tpu.utils.hostpool import HostPool, resolve_host_threads
from photon_tpu.utils.profiling import (
    AGG_DECODE_TIME,
    AGG_FOLD_TIME,
    CKPT_ASYNC_WRITE_S,
)


# ---------------------------------------------------------------------------
# HostPool
# ---------------------------------------------------------------------------


def test_hostpool_inline_degenerate():
    pool = HostPool(1)
    assert not pool.pipelined
    assert pool.submit(lambda a, b: a + b, 2, 3).result() == 5
    assert pool.map(lambda x: x * x, [1, 2, 3]) == [1, 4, 9]
    # inline futures surface exceptions at result(), like real ones
    fut = pool.submit(lambda: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        fut.result()
    pool.close()  # no executor was ever created; must be a no-op


def test_hostpool_threaded_ordered_and_reusable():
    pool = HostPool(3)
    assert pool.pipelined
    assert pool.map(lambda x: x * 2, list(range(20))) == [x * 2 for x in range(20)]
    pool.close()
    # close() is reusable: the next submit rebuilds the executor
    assert pool.submit(lambda: 7).result() == 7
    pool.close()


def test_resolve_host_threads():
    assert resolve_host_threads(4) == 4
    assert resolve_host_threads(1) == 1
    auto = resolve_host_threads(0)
    assert 1 <= auto <= 8  # bounded; leaves a core for the driving thread


# ---------------------------------------------------------------------------
# Fused fold: bit-exact + no full-payload fp64 temporary
# ---------------------------------------------------------------------------


def _payload(seed, n_layers=7):
    rng = np.random.default_rng(seed)
    shapes = [(129, 65), (513,), (33, 9, 5), (2048,), (7, 7), (1,), (300, 11)][:n_layers]
    return [rng.normal(size=s).astype(np.float32) for s in shapes]


def _stream(n_clients=6, seed=0):
    rng = np.random.default_rng(seed)
    return [(_payload(seed + i), int(n)) for i, n in enumerate(rng.integers(1, 200, n_clients))]


def test_fused_fold_matches_two_pass_bitwise():
    clients = _stream()
    acc_ref = [np.asarray(a, np.float64) for a in clients[0][0]]
    n_total = clients[0][1]
    for arrays, n_cur in clients[1:]:
        n_new = n_total + n_cur
        w_prev, w_cur = n_total / n_new, n_cur / n_new
        for i, y in enumerate(arrays):
            # the pre-PR-2 two-pass fold, full fp64 temp and all
            acc_ref[i] *= w_prev
            acc_ref[i] += np.asarray(y, np.float64) * w_cur
        n_total = n_new
    expect = [a.astype(np.float32) for a in acc_ref]

    got, n = aggregate_inplace(iter(clients))
    assert n == n_total
    for a, b in zip(expect, got):
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("threads", [1, 4])
def test_aggregate_parity_raw_threads(threads):
    clients = _stream()
    serial, n1 = aggregate_inplace(iter(clients))
    timings: dict = {}
    pooled, n2 = aggregate_inplace(iter(clients), pool=HostPool(threads), timings=timings)
    assert n1 == n2
    for a, b in zip(serial, pooled):
        assert np.array_equal(a, b), "threaded fold is not bit-exact"
    assert timings["decode_s"] >= 0.0 and timings["fold_s"] > 0.0


@pytest.mark.parametrize("threads", [1, 4])
def test_aggregate_parity_compressed_threads(threads):
    clients = _stream()
    names = [f"l{i}/w" for i in range(len(clients[0][0]))]
    meta = ParamsMetadata.from_ndarrays(names, clients[0][0])
    ref = [a + 0.01 for a in clients[0][0]]

    enc = Codec("delta_topk_q8", error_feedback=False)
    enc.set_reference(ref)
    payloads = [(enc.encode(meta, arrays), n) for arrays, n in clients]

    dec = Codec("delta_topk_q8", error_feedback=False)
    dec.set_reference(ref)
    serial, _ = aggregate_inplace(iter(payloads), decode=dec.decode)
    pool = HostPool(threads)
    pooled, _ = aggregate_inplace(
        iter(payloads), decode=lambda p: dec.decode(p, pool=pool), pool=pool
    )
    for a, b in zip(serial, pooled):
        assert np.array_equal(a, b), "pipelined compressed fold is not bit-exact"


def test_codec_pool_encode_decode_identical_bytes():
    arrays = _payload(3)
    names = [f"l{i}/w" for i in range(len(arrays))]
    meta = ParamsMetadata.from_ndarrays(names, arrays)
    ref = [a + 0.01 for a in arrays]
    pool = HostPool(4)
    for policy in ("delta_q8", "delta_topk_q8"):
        codec = Codec(policy, error_feedback=True)
        codec.set_reference(ref)
        serial_bytes = codec.encode(meta, arrays, key=1).to_bytes()
        codec2 = Codec(policy, error_feedback=True)
        codec2.set_reference(ref)
        pooled_bytes = codec2.encode(meta, arrays, key=1, pool=pool).to_bytes()
        assert serial_bytes == pooled_bytes, policy
        # decode parity, pooled vs serial
        from photon_tpu.compression import CompressedPayload

        payload = CompressedPayload.from_bytes(pooled_bytes)
        for a, b in zip(codec.decode(payload), codec.decode(payload, pool=pool)):
            assert np.array_equal(a, b)


def test_fused_fold_peak_allocation_is_chunk_not_payload():
    """The acceptance bound: no full-payload ``astype(np.float64)`` temp.

    A 16 MiB fp32 incoming array would have cost a 32 MiB fp64 temporary in
    the old two-pass fold; the fused chunked fold's transient must stay at
    chunk scale (~8 MiB)."""
    import tracemalloc

    n = 4 << 20  # 16 MiB fp32 / 32 MiB fp64
    y = np.full(n, 0.5, np.float32)
    acc = np.ones(n, np.float64)
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        _fold_into(acc, y, 0.25, 0.75)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    chunk_bytes = _FOLD_CHUNK * 8
    assert peak < 2 * chunk_bytes, (
        f"fold transient {peak / 2**20:.1f} MiB — a full fp64 payload copy "
        f"({y.size * 8 / 2**20:.0f} MiB) appears to be materialized again"
    )
    # and the math still holds
    np.testing.assert_allclose(acc, 0.25 + 0.5 * 0.75)


def test_aggregate_first_client_non_contiguous_fp64():
    """Regression (review): an already-fp64 NON-contiguous first payload
    used to flow through ``asarray`` unchanged, making ``reshape(-1)`` in
    the fold a copy — every later client's contribution silently dropped."""
    base = np.arange(16, dtype=np.float64).reshape(4, 4)
    nc = base.T
    assert not nc.flags.c_contiguous
    rest = np.full((4, 4), 2.0, np.float32)
    avg, n = aggregate_inplace(iter([([nc], 1), ([rest], 3)]))
    assert n == 4
    expect = (nc * 0.25 + rest.astype(np.float64) * 0.75).astype(np.float32)
    np.testing.assert_array_equal(avg[0], expect)
    # the fold primitive itself refuses a non-contiguous accumulator
    with pytest.raises(ValueError, match="contiguous"):
        _fold_into(base.T, rest, 0.5, 0.5)


def test_agg_decode_time_excludes_blocking_fetch():
    """Regression (review): the decode KPI must not absorb the wait for a
    client's reply — in production ``next(it)`` blocks on the driver for
    the whole client fit."""
    def slow_stream():
        yield _payload(0), 2
        time.sleep(0.25)  # "client still training"
        yield _payload(1), 3

    timings: dict = {}
    aggregate_inplace(slow_stream(), timings=timings)
    assert timings["decode_s"] < 0.2, (
        f"decode_s={timings['decode_s']:.3f}s charged the client wait"
    )


def test_aggregate_error_propagates_from_lookahead():
    def boom():
        yield _payload(0), 3
        yield _payload(1), 2
        raise RuntimeError("stream died")

    with pytest.raises(RuntimeError, match="stream died"):
        aggregate_inplace(boom(), pool=HostPool(4))
    with pytest.raises(ValueError, match="non-positive"):
        aggregate_inplace(iter([(_payload(0), 5), (_payload(1), 0)]), pool=HostPool(4))


# ---------------------------------------------------------------------------
# Async server checkpoints
# ---------------------------------------------------------------------------


class SlowStore(FileStore):
    """FileStore with a per-put delay + completion timestamps."""

    def __init__(self, root, delay=0.15):
        super().__init__(root)
        self.delay = delay
        self.completed: dict[str, float] = {}

    def put(self, key, data):
        time.sleep(self.delay)
        super().put(key, data)
        self.completed[key] = time.monotonic()


def _round_payload(seed=0):
    meta_arrays = _payload(seed, n_layers=3)
    names = [f"l{i}/w" for i in range(len(meta_arrays))]
    return ParamsMetadata.from_ndarrays(names, meta_arrays), meta_arrays


def test_async_save_then_load_barrier(tmp_path):
    """load/resume must never observe a half-landed async round."""
    store = SlowStore(tmp_path, delay=0.1)
    mgr = ServerCheckpointManager(store, "run1")
    meta, params = _round_payload()
    t0 = time.monotonic()
    enqueue_s = mgr.save_round_async(
        5, meta, params, {"momentum": params}, {"round": 5},
        cleanup_keep=(3, ("momentum",)),
    )
    assert enqueue_s < 0.05  # snapshot+enqueue is cheap; the writes are not
    assert time.monotonic() - t0 < 0.1  # did not block on the slow puts
    assert mgr.last_barrier_wait_s < 0.05  # no previous write to wait out
    # immediate read: the internal barrier waits the writer out
    m, p, st, server_state = mgr.load_round(5, ("momentum",))
    assert server_state == {"round": 5}
    np.testing.assert_array_equal(p[0], params[0])
    assert mgr.resolve_resume_round(-1, ("momentum",)) == 5
    assert mgr.last_async_write_s > 0.0


def test_async_save_write_error_surfaces_at_barrier(tmp_path):
    class BrokenStore(FileStore):
        def put(self, key, data):
            raise OSError("disk on fire")

    mgr = ServerCheckpointManager(BrokenStore(tmp_path), "run1")
    meta, params = _round_payload()
    mgr.save_round_async(1, meta, params)
    with pytest.raises(RuntimeError, match="async checkpoint write failed"):
        mgr.wait_pending()
    # the error is consumed — the manager is usable again
    mgr.wait_pending()


def test_async_snapshot_isolated_from_later_mutation(tmp_path):
    """The snapshot contract is ONE-level: list/dict containers are copied,
    slots may be rebound afterwards (that is all the strategies and the
    server do — ServerApp additionally one-level-copies ``client_states``
    at build time because IT keeps inserting into that nested dict)."""
    store = SlowStore(tmp_path, delay=0.05)
    mgr = ServerCheckpointManager(store, "run1")
    meta, params = _round_payload()
    momenta = [np.zeros_like(a) for a in params]
    server_state = {"client_states": {0: {"steps": 1}}, "round": 1}
    mgr.save_round_async(1, meta, params, {"momentum": momenta}, server_state)
    # what the round loop does next, while the writer is still asleep:
    momenta[0] = np.full_like(momenta[0], 9.0)        # slot REBIND (not in-place)
    server_state["client_states"] = {9: {"steps": 9}}  # key REBIND
    server_state["round"] = 2
    _, _, st, loaded = mgr.load_round(1, ("momentum",))
    np.testing.assert_array_equal(st["momentum"][0], np.zeros_like(params[0]))
    assert loaded == {"client_states": {0: {"steps": 1}}, "round": 1}


# ---------------------------------------------------------------------------
# Federated rounds: KPI keys, degenerate threads=1, write/round overlap
# ---------------------------------------------------------------------------


def _fed_app(tmp_path, store=None, host_threads=1, n_rounds=2, checkpoint=False,
             **fl_kw):
    from photon_tpu.federation import InProcessDriver, NodeAgent, ParamTransport, ServerApp
    from tests.test_federation import make_cfg

    cfg = make_cfg(tmp_path, n_rounds=n_rounds, **fl_kw)
    cfg.photon.host_threads = host_threads
    cfg.photon.checkpoint = checkpoint
    cfg.validate()
    transport = ParamTransport("inline")

    def make_agent(node_id):
        return NodeAgent(cfg, node_id, lambda: ParamTransport("inline"))

    driver = InProcessDriver(cfg, make_agent, n_nodes=2)
    ckpt = ServerCheckpointManager(store, cfg.run_uuid) if store is not None else None
    return ServerApp(cfg, driver, transport, ckpt_mgr=ckpt)


def test_fed_round_host_plane_kpis_and_degenerate_pool(tmp_path):
    """tier-1 coverage for ``photon.host_threads=1`` (the degenerate inline
    pool) + presence of the new host-plane KPI keys in round metrics."""
    store = FileStore(tmp_path / "ckpt")
    app = _fed_app(tmp_path, store=store, host_threads=1, checkpoint=True)
    assert not app.host_pool.pipelined
    history = app.run()
    for key in (AGG_DECODE_TIME, AGG_FOLD_TIME, "server/checkpoint_time",
                CKPT_ASYNC_WRITE_S):
        assert len(history.series(key)) == 2, key
    # the shutdown barrier landed every round on disk
    assert app.ckpt_mgr.valid_rounds(app.strategy.state_keys) != []
    app.driver.shutdown()


def test_fed_round_threaded_pool_matches_serial_params(tmp_path):
    """Same run, host_threads=1 vs 4: byte-identical final parameters (the
    whole-pipeline version of the bit-exact aggregation contract)."""
    app1 = _fed_app(tmp_path / "a", host_threads=1)
    app1.run()
    p1 = [a.copy() for a in app1.strategy.current_parameters]
    app1.driver.shutdown()

    app4 = _fed_app(tmp_path / "b", host_threads=4)
    assert app4.host_pool.pipelined
    app4.run()
    p4 = app4.strategy.current_parameters
    app4.driver.shutdown()
    for a, b in zip(p1, p4):
        assert np.array_equal(a, b), "host_threads changed the aggregation result"


def test_async_checkpoint_overlaps_next_round(tmp_path):
    """Round N+1's broadcast must fire BEFORE round N's checkpoint write
    completes (the write overlaps the next round), and the run's shutdown
    barrier still leaves every round consistent on disk."""
    store = SlowStore(tmp_path / "ckpt", delay=0.15)
    app = _fed_app(tmp_path, store=store, host_threads=1, n_rounds=2, checkpoint=True)

    bcast_at: dict[int, float] = {}
    orig = app.broadcast_parameters

    def timed_broadcast(server_round):
        bcast_at.setdefault(server_round, time.monotonic())
        return orig(server_round)

    app.broadcast_parameters = timed_broadcast
    app.run()

    done_r1 = store.completed[f"{app.cfg.run_uuid}/server/1/state.bin"]
    assert bcast_at[2] < done_r1, (
        f"round-2 broadcast at {bcast_at[2]:.3f} did not overlap the "
        f"round-1 write completing at {done_r1:.3f}"
    )
    # barrier: after run() both rounds are fully valid and resumable
    mgr = ServerCheckpointManager(store, app.cfg.run_uuid)
    assert 2 in mgr.valid_rounds(app.strategy.state_keys)
    _, p, _, server_state = mgr.load_round(2, app.strategy.state_keys)
    for a, b in zip(p, app.strategy.current_parameters):
        np.testing.assert_array_equal(a, b)
    assert server_state["server_steps_cumulative"] == app.server_steps_cumulative
    app.driver.shutdown()


def test_resume_after_async_checkpoint_matches_uninterrupted(tmp_path):
    """Crash-resume consistency: resume from the latest async-written round
    reproduces the uninterrupted run (PRNG fast-forward + params).
    ``reset_optimizer`` keeps client optimizer state round-local, as in the
    golden determinism oracle in test_federation."""
    fit_cfg = {"fit_config": {"reset_optimizer": True}}
    store = FileStore(tmp_path / "ckpt")
    full = _fed_app(tmp_path / "full", store=store, host_threads=1, n_rounds=3,
                    checkpoint=True, **fit_cfg)
    full.run()
    p_full = [a.copy() for a in full.strategy.current_parameters]
    full.driver.shutdown()

    # fresh app resuming from round 2 of the same store, same run_uuid
    resumed = _fed_app(tmp_path / "full", store=store, host_threads=1, n_rounds=3,
                       checkpoint=True, **fit_cfg)
    resumed.cfg.photon.resume_round = 2
    resumed.run()
    for a, b in zip(p_full, resumed.strategy.current_parameters):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    resumed.driver.shutdown()
