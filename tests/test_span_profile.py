"""The program's spans in a ``jax.profiler`` trace (ISSUE 27).

Every ``telemetry.span`` is a ``TraceAnnotation`` as well: with telemetry OFF
a profiler capture of a round still shows the program's phases as host
events on the device trace's clock, each on the line of the thread that ran
it, its attrs as stats. The trace is read with the benchmark's own reader
(``benchmark/trace/host_spans.py``), so these tests also hold the contract
between the span sites and the per-layer metrics that read them.
"""

import time
import timeit

import jax
import pytest

from benchmark.trace import host_spans as hs
from photon_tpu import telemetry
from photon_tpu.train.train_step import (
    FORWARD_BACKWARD_SCOPE,
    LOSS_HEAD_SCOPE,
    OPTIMIZER_SCOPE,
)
from photon_tpu.utils import profiling as P
from tests.test_federation import make_app, make_cfg


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.uninstall()
    yield
    telemetry.uninstall()


def _toy_app(tmp, enabled: bool):
    cfg = make_cfg(tmp, n_total_clients=2, n_clients_per_round=2, n_rounds=2)
    cfg.photon.checkpoint = True
    cfg.photon.host_threads = 4  # client 2's fit runs on a pool worker
    cfg.photon.telemetry.enabled = enabled
    cfg.validate()
    app = make_app(cfg, tmp, n_nodes=1, with_ckpt=True)
    app.save_checkpoint(0)
    app.run_round(1)  # compiles outside the trace
    return app


def _profiled_round(tmp, enabled: bool):
    """Round 2 of a 2-client toy run inside a profiler session: the trace's
    spans, the app's History and the tracer's buffer (None when off)."""
    app = _toy_app(tmp, enabled)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=options)
    try:
        app.run_round(2)
        app.ckpt_mgr.wait_pending()
    finally:
        jax.profiler.stop_trace()
    tracer = telemetry.active()
    buffered = tracer.snapshot() if tracer is not None else None
    app.free_transport()
    app.driver.shutdown()
    return hs.host_spans(tmp / "trace"), app.history, buffered


@pytest.fixture(scope="module")
def off(tmp_path_factory):
    telemetry.uninstall()
    return _profiled_round(tmp_path_factory.mktemp("spans_off"), enabled=False)


@pytest.fixture(scope="module")
def on(tmp_path_factory):
    telemetry.uninstall()
    try:
        return _profiled_round(tmp_path_factory.mktemp("spans_on"), enabled=True)
    finally:
        telemetry.uninstall()


# span, the spans it may sit under on its own line (None: a line of its own,
# or the top of a pool worker's line), the stats it must carry
SPAN_TABLE = [
    (P.TRANSPORT_PUT_SPAN, {P.BROADCAST_PRE_TIME, P.CLIENT_ENCODE_SPAN},
     {"mode", "nbytes", "wire_nbytes"}),
    (P.TRANSPORT_GET_SPAN, {P.NODE_SET_BROADCAST_SPAN, P.FIT_ROUND_TIME, None},
     {"mode", "copied_nbytes", "wire_nbytes"}),
    (P.TRANSPORT_FREE_SPAN, {P.BROADCAST_PRE_TIME, P.FIT_ROUND_TIME, None}, {"mode"}),
    (P.TRANSPORT_UNMAP_SPAN, {P.NODE_SET_BROADCAST_SPAN, P.CLIENT_RESOLVE_PARAMS_SPAN}, {"mode"}),
    (P.NODE_SET_BROADCAST_SPAN, {P.BROADCAST_PRE_TIME}, {"round", "node"}),
    (P.TRAINER_SET_PARAMETERS_SPAN, {P.CLIENT_FIT_SPAN}, {"nbytes"}),
    (P.TRAINER_GET_PARAMETERS_SPAN, {P.CLIENT_FIT_SPAN}, set()),
    (P.CLIENT_PSEUDO_GRAD_NORM_SPAN, {P.CLIENT_FIT_SPAN}, {"round", "cid", "threads"}),
    (P.CLIENT_TRAIN_SPAN, {P.CLIENT_FIT_SPAN}, {"round", "cid"}),
    (P.TRAINER_STEPS_SPAN, {P.CLIENT_TRAIN_SPAN}, {"steps"}),
    (P.TRAINER_NEXT_BATCH_SPAN, {P.TRAINER_STEPS_SPAN}, set()),
    (P.TRAINER_FENCE_SPAN, {P.CLIENT_TRAIN_SPAN}, set()),
    (P.CLIENT_FIT_SPAN, {P.FIT_ROUND_TIME, None}, {"round", "cid"}),
    (P.AGG_DECODE_TIME, {P.FIT_ROUND_TIME, None}, {"client_index"}),
    (P.AGG_FOLD_TIME, {P.FIT_ROUND_TIME}, set()),
    (P.SERVER_UPDATE_SPAN, {P.FIT_ROUND_TIME}, {"round", "threads"}),
    (P.FIT_ROUND_TIME, {P.ROUND_SPAN}, {"round"}),
    (P.BROADCAST_PRE_TIME, {P.ROUND_SPAN}, {"round"}),
    (P.CHECKPOINT_TIME, {P.ROUND_SPAN}, {"round"}),
    (P.CKPT_ASYNC_WRITE_S, {None}, {"round"}),
]


@pytest.mark.parametrize("name,parents,stats", SPAN_TABLE,
                         ids=[row[0] for row in SPAN_TABLE])
def test_span_is_in_the_host_plane_with_telemetry_off(off, name, parents, stats):
    spans, _, buffered = off
    assert buffered is None  # no tracer was installed
    hits = hs.named(spans, name)
    assert hits, f"{name} is not in the profiler's trace"
    for s in hits:
        assert s.parent in parents, (name, s.parent)
        assert stats <= set(s.stats), (name, s.stats)
        if "round" in stats:
            assert s.stats["round"] == 2


def test_worker_and_writer_spans_have_lines_of_their_own(off):
    spans, _, _ = off
    fits = hs.named(spans, P.CLIENT_FIT_SPAN)
    assert sorted(s.stats["cid"] for s in fits) == [0, 1]
    assert len({s.line for s in fits}) == 2, "the second fit runs on a pool worker"
    (write,) = hs.named(spans, P.CKPT_ASYNC_WRITE_S)
    assert {s.name for s in spans if s.line == write.line} == {P.CKPT_ASYNC_WRITE_S}


def test_a_round_is_covered_by_leaf_spans(off):
    spans, _, _ = off
    (rnd,) = hs.named(spans, P.ROUND_SPAN)
    rest = hs.unattributed_seconds(hs.inside(spans, rnd), rnd)
    assert 0.0 <= rest < rnd.seconds
    steps = sum(s.stats["steps"] for s in hs.named(spans, P.TRAINER_STEPS_SPAN))
    assert len(hs.named(spans, P.TRAINER_NEXT_BATCH_SPAN)) == steps == 4


def test_tracer_buffer_and_profiler_hold_the_same_names(on):
    spans, _, buffered = on
    in_trace = {s.name for s in spans}
    in_buffer = {d["name"] for d in buffered if d["attrs"].get("round", 2) == 2}
    assert in_trace == in_buffer
    # and the off and on traces are of the same program
    assert {row[0] for row in SPAN_TABLE} <= in_trace


@pytest.mark.parametrize("kpi", [P.FIT_ROUND_TIME, P.BROADCAST_PRE_TIME,
                                 P.CHECKPOINT_TIME])
def test_history_kpi_is_the_spans_own_timer(on, kpi):
    _, history, buffered = on
    (span,) = [d for d in buffered
               if d["name"] == kpi and d["attrs"].get("round") == 2]
    assert dict(history.series(kpi))[2] == span["duration_s"]


def test_fold_kpi_is_the_sum_of_its_spans(on):
    _, history, buffered = on
    folds = [d["duration_s"] for d in buffered if d["name"] == P.AGG_FOLD_TIME]
    # the buffer holds rounds 1 and 2 alike: first copy, one fold, last cast
    assert len(folds) == 6
    assert dict(history.series(P.AGG_FOLD_TIME))[2] == pytest.approx(sum(folds[3:]))


def test_run_round_is_what_the_loop_runs(tmp_path, monkeypatch):
    cfg = make_cfg(tmp_path, n_rounds=2)
    app = make_app(cfg, tmp_path)
    seen = []
    one_round = app._one_round
    monkeypatch.setattr(app, "_one_round",
                        lambda c, r: (seen.append(r), one_round(c, r))[1])
    app.run()
    app.driver.shutdown()
    assert seen == [1, 2]
    assert [r for r, _ in app.history.series(P.ROUND_TIME)] == [1, 2]


def test_lowered_train_step_carries_the_stage_scopes(tmp_path):
    from photon_tpu.train.trainer import Trainer

    text = Trainer(make_cfg(tmp_path)).lower_train_step().as_text(debug_info=True)
    for scope in (FORWARD_BACKWARD_SCOPE, LOSS_HEAD_SCOPE, OPTIMIZER_SCOPE):
        assert scope in text, scope
    # the loss head sits inside forward/backward, the optimizer beside it
    assert f"{FORWARD_BACKWARD_SCOPE}/" in text
    assert f"{FORWARD_BACKWARD_SCOPE}/{OPTIMIZER_SCOPE}" not in text
    assert "multihead_attention" in text  # what flash_attention_roofline finds


def test_flash_kernels_keep_the_instruction_name_and_gain_their_own():
    """Interpret-mode lowering of the three launches: each carries its own
    kernel name in its locations, with ``multihead_attention`` as the scope
    inside it (the TPU compiler names the instruction after the innermost
    scope: ``tests/test_tpu_compile.py`` holds that half)."""
    import jax.numpy as jnp

    from photon_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 256, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                               interpret=True).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).as_text(
        debug_info=True)
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert f"{kernel}/multihead_attention" in text, kernel


@pytest.mark.parametrize("mode", ["shm", "inline", "objstore"])
def test_transport_get_says_what_it_copied(tmp_path, mode):
    """``copied_nbytes`` is what the read materialised on the host: nothing
    where the arrays are views of the segment (shm) or the sender's own
    (inline), the payload out of the object store's npz. The caller's old
    ``copy`` flag is gone with the parameter."""
    import numpy as np

    from photon_tpu.checkpoint import FileStore
    from photon_tpu.codec import ParamsMetadata
    from photon_tpu.config.schema import TelemetryConfig
    from photon_tpu.federation import ParamTransport

    arrays = [np.ones((64, 8), np.float32), np.arange(5, dtype=np.float32)]
    meta = ParamsMetadata.from_ndarrays(["w", "b"], arrays)
    tr = ParamTransport(mode, store=FileStore(tmp_path / "store"))
    telemetry.install(TelemetryConfig(enabled=True), scope="server")
    try:
        ptr = tr.put(f"copied-{mode}-{tmp_path.name}", meta, arrays)
        _, got = tr.get(ptr)
        tr.free(ptr)
    finally:
        tr.cleanup()
    (span,) = [d for d in telemetry.active().snapshot()
               if d["name"] == P.TRANSPORT_GET_SPAN]
    assert "copy" not in span["attrs"]
    assert span["attrs"]["mode"] == mode
    assert span["attrs"]["wire_nbytes"] == meta.total_bytes
    assert span["attrs"]["copied_nbytes"] == (meta.total_bytes if mode == "objstore" else 0)
    for a, b in zip(arrays, got):
        np.testing.assert_array_equal(a, b)


def test_span_site_with_telemetry_off_stays_under_3_us():
    """Off, a span is the TraceMe's inactive path plus a timer; the ceiling
    is several times the ~1.2 us measured here, so only a real regression
    (an allocation-heavy or locking hook) trips it."""
    assert telemetry.active() is None

    def site():
        with telemetry.span(P.TRANSPORT_PUT_SPAN, push=False, mode="shm",
                            nbytes=1, wire_nbytes=1):
            pass

    n = 20_000
    best = min(timeit.repeat(site, number=n, repeat=7)) / n
    assert best < 3e-6, f"{best * 1e6:.2f} us per disabled span site"


def test_span_yields_its_seconds_on_both_paths():
    from photon_tpu.config.schema import TelemetryConfig

    with telemetry.span(P.SERVER_UPDATE_SPAN, round=1) as off_span:
        time.sleep(0.01)
    telemetry.install(TelemetryConfig(enabled=True), scope="server")
    with telemetry.span(P.SERVER_UPDATE_SPAN, round=1) as on_span:
        time.sleep(0.01)
    assert 0.01 <= off_span.seconds < 0.5
    assert 0.01 <= on_span.seconds < 0.5
    assert telemetry.active().snapshot()[-1]["duration_s"] == on_span.seconds
