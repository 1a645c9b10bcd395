"""Telemetry plane tests (ISSUE 4): tracer semantics, trace-context
propagation across process boundaries (in-process, multiprocess, TCP),
chaos interaction (dropped/duplicated envelopes must not corrupt or
double-emit spans), the Perfetto export, the JSONL event log, the
Prometheus endpoint, and the KPI-name registry.

The fast half rides tier-1 (`make telemetry-smoke` runs the whole file
including the slow cross-process e2es).
"""

import json
import pathlib
import threading
import urllib.request

import pytest

from photon_tpu import telemetry
from photon_tpu.config.schema import TelemetryConfig
from photon_tpu.telemetry.events import EventLog, read_events_jsonl
from photon_tpu.telemetry.export import (
    load_chrome_trace,
    span_index,
    write_chrome_trace,
)
from photon_tpu.telemetry.spans import Tracer
from tests.test_federation import make_cfg, make_app


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends with no process-global tracer installed
    (the same pollution-proofing discipline as chaos)."""
    telemetry.uninstall()
    yield
    telemetry.uninstall()


# ---------------------------------------------------------------------------
# Tracer unit semantics
# ---------------------------------------------------------------------------


def test_span_nesting_parents_and_trace_id():
    tr = Tracer("server")
    with tr.span("server/round_time", round=1) as outer:
        with tr.span("server/fit_round_time") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    spans = tr.drain()
    assert [s["name"] for s in spans] == [
        "server/fit_round_time", "server/round_time"
    ]  # completion order: inner closes first
    assert spans[1]["parent_id"] is None
    assert spans[0]["attrs"] == {}
    assert spans[1]["attrs"] == {"round": 1}
    assert all(s["duration_s"] >= 0 for s in spans)


def test_attach_adopts_remote_parent():
    tr = Tracer("node0")
    with tr.attach(("deadbeef", "cafe0001")):
        with tr.span("client/fit_time") as sp:
            assert sp.trace_id == "deadbeef"
            assert sp.parent_id == "cafe0001"
    # stack unwound: a fresh span starts its own trace
    with tr.span("client/fit_time") as sp2:
        assert sp2.trace_id != "deadbeef"
    assert len(tr.drain()) == 2


def test_buffer_cap_drops_oldest_and_counts():
    tr = Tracer("server", max_buffered_spans=3)
    for i in range(5):
        with tr.span(f"s{i}"):
            pass
    assert tr.dropped == 2
    assert [s["name"] for s in tr.drain()] == ["s2", "s3", "s4"]


def test_drain_ingest_roundtrip_preserves_proc():
    node = Tracer("node0", piggyback=True)
    with node.span("client/fit_time", cid=3):
        pass
    shipped = node.drain()
    assert node.drain() == []  # drained means drained
    server = Tracer("server")
    assert server.ingest(shipped) == 1
    merged = server.snapshot()
    assert merged[0]["proc"] == "node0"
    assert merged[0]["attrs"]["cid"] == 3
    # malformed shipped spans are skipped, never raise
    assert server.ingest([{"bogus": 1}, None]) == 0


def test_ingest_dedups_duplicated_shipments():
    """A chaos-duplicated reply frame ships the IDENTICAL drained list
    twice — possibly draining in a later scheduling window where mid-level
    dedup can't see it. The merge point drops the repeats for spans (by
    span_id) and events (by event id)."""
    node = Tracer("node0", piggyback=True)
    with node.span("client/fit_time", cid=1):
        pass
    shipped = node.drain()
    server = Tracer("server")
    assert server.ingest(shipped) == 1
    assert server.ingest(list(shipped)) == 0  # duplicate frame
    assert len(server.snapshot()) == 1

    nlog = EventLog("node0")
    nlog.emit("tcp/reconnect", {"reconnects": 1})
    sev = nlog.drain()
    slog = EventLog("server")
    assert slog.ingest(sev) == 1
    assert slog.ingest(list(sev)) == 0
    assert len(slog.snapshot()) == 1


def test_span_threads_have_independent_stacks():
    tr = Tracer("server")
    seen = {}

    def worker():
        # no context on this thread: new trace, no parent
        with tr.span("t2") as sp:
            seen["t2"] = (sp.trace_id, sp.parent_id)

    with tr.span("t1") as sp1:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        assert seen["t2"][0] != sp1.trace_id
        assert seen["t2"][1] is None


def test_install_disabled_is_none_and_span_is_noop():
    assert telemetry.install(TelemetryConfig(enabled=False), scope="x") is None
    assert telemetry.active() is None
    with telemetry.span("anything", round=1):  # shared null context
        assert telemetry.current_context() is None
    telemetry.emit_event("nothing")  # must not raise


# ---------------------------------------------------------------------------
# Event log + exporter
# ---------------------------------------------------------------------------


def test_event_log_write_through_and_correlation(tmp_path):
    path = tmp_path / "tel" / "events.jsonl"
    log = EventLog("server", path=str(path))
    log.emit("membership/transition", {"node": "node0", "from": "new", "to": "live"})
    log.emit("chaos/tcp_drop", {"scope": "node1"}, ctx=("abcd", "ef01"))
    log.close()
    events = read_events_jsonl(str(path))
    assert [e["kind"] for e in events] == ["membership/transition", "chaos/tcp_drop"]
    assert events[0]["proc"] == "server"
    assert events[1]["trace_id"] == "abcd" and events[1]["span_id"] == "ef01"
    assert all("ts" in e for e in events)


def test_event_log_buffered_drain_ingest():
    node = EventLog("node0")  # no path: buffer mode
    node.emit("tcp/reconnect", {"reconnects": 1})
    shipped = node.drain()
    assert node.drain() == []
    server = EventLog("server")
    assert server.ingest(shipped) == 1
    assert server.snapshot()[0]["proc"] == "node0"


def test_chrome_trace_export_structure(tmp_path):
    tr = Tracer("server")
    with tr.span("server/round_time", round=2):
        with tr.span("server/fit_round_time"):
            pass
    events = [{"ts": 123.0, "kind": "chaos/tcp_drop", "proc": "node0",
               "attrs": {}, "trace_id": "t", "span_id": "s"}]
    path = write_chrome_trace(tmp_path / "trace.json", tr.snapshot(), events)
    doc = load_chrome_trace(path)
    evs = doc["traceEvents"]
    complete = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {"server/round_time", "server/fit_round_time"}
    assert all(e["ts"] > 0 and e["dur"] >= 0 for e in complete)
    # lineage is walkable through args
    idx = span_index(doc)
    child = next(e for e in complete if e["name"] == "server/fit_round_time")
    assert idx[child["args"]["parent_id"]]["name"] == "server/round_time"
    # instant marker + process-name metadata
    assert any(e["ph"] == "i" and e["name"] == "chaos/tcp_drop" for e in evs)
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"server", "node0"} <= names


# ---------------------------------------------------------------------------
# Prometheus endpoint
# ---------------------------------------------------------------------------


def test_prom_metrics_endpoint():
    from photon_tpu.metrics.history import History
    from photon_tpu.telemetry.prom import PromServer

    h = History()
    h.record(3, {"server/round_time": 1.5, "server/n_clients": 2.0})
    srv = PromServer(h, port=0)  # ephemeral bind
    port = srv.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ).read().decode()
    finally:
        srv.close()
    assert "# TYPE photon_server_round_time gauge" in body
    assert 'photon_server_round_time 1.5' in body
    assert "photon_last_round 3" in body


# ---------------------------------------------------------------------------
# History wandb mirror (satellite): only coerced floats reach wandb
# ---------------------------------------------------------------------------


def test_history_wandb_mirrors_only_coerced_floats():
    from photon_tpu.metrics.history import History

    logged = []

    class FakeWandb:
        def log(self, d, step=None):
            logged.append((step, d))

    h = History(FakeWandb())
    h.record(1, {"server/round_time": 2.0, "server/junk": None,
                 "server/name": "not-a-float", "server/ok": "3.5"})
    assert logged == [(1, {"server/round_time": 2.0, "server/ok": 3.5})]
    assert h.latest("server/junk") is None  # local record agrees


# ---------------------------------------------------------------------------
# SpeedMonitor auto-detect (satellite)
# ---------------------------------------------------------------------------


def test_speed_monitor_auto_detects_peak_from_device_kind():
    from photon_tpu.config.schema import ModelConfig
    from photon_tpu.utils.profiling import (
        TPU_V4_PEAK_FLOPS,
        SpeedMonitor,
        peak_flops_for_device_kind,
    )

    sm = SpeedMonitor(ModelConfig(), device_kind="TPU v4", n_chips=2)
    assert sm.peak_flops_per_chip == TPU_V4_PEAK_FLOPS
    assert sm.peak == 2 * TPU_V4_PEAK_FLOPS
    # a kind the table does not hold has no peak: throughput, and no MFU
    unknown = SpeedMonitor(ModelConfig(), device_kind="cpu")
    assert unknown.peak_flops_per_chip is None
    assert "throughput/mfu" not in unknown.update(tokens=1000, seconds=0.5)
    with pytest.raises(ValueError):
        peak_flops_for_device_kind("cpu")
    assert peak_flops_for_device_kind("cpu", default=1e12) == 1e12
    # explicit peak still wins
    assert SpeedMonitor(ModelConfig(), peak_flops=1e12).peak == 1e12
    out = sm.update(tokens=1000, seconds=0.5)
    assert out["throughput/tokens_per_sec"] == 2000.0
    assert out["throughput/mfu"] > 0


# ---------------------------------------------------------------------------
# Duplicate-delivery dedup: a chaos-duplicated envelope must not double-emit
# ---------------------------------------------------------------------------


class _ScriptedConn:
    """Connection double feeding a fixed envelope sequence to NodeAgent.serve."""

    def __init__(self, envelopes):
        self._in = list(envelopes)
        self.sent = []

    def recv(self):
        if not self._in:
            raise EOFError("script exhausted")
        return self._in.pop(0)

    def send(self, obj):
        self.sent.append(obj)


def test_duplicate_envelope_single_span_emission(tmp_path):
    """The same FitIns delivered twice (chaos tcp_duplicate) runs ONE fit:
    one reply on the wire, one set of client spans piggybacked — the
    duplicate is consumed with no telemetry side effects."""
    from photon_tpu.federation import NodeAgent, ParamTransport
    from photon_tpu.federation.messages import Envelope, FitIns

    cfg = make_cfg(tmp_path, n_rounds=1)
    cfg.photon.telemetry.enabled = True
    agent = NodeAgent(cfg, "node0", lambda: ParamTransport("inline"))
    telemetry.install(cfg.photon.telemetry, scope="node0", piggyback=True)

    ptr = agent.runtime.transport.put(
        "bcast", *_tiny_params(cfg)
    )
    fit = FitIns(server_round=1, cids=[0], params=ptr, local_steps=1,
                 server_steps_cumulative=0)
    env = Envelope(fit, msg_id=7, trace=("feedc0de", "00000001"))
    conn = _ScriptedConn([env, env])  # duplicate delivery
    assert agent.serve(conn) is False  # script exhaustion = EOF
    assert len(conn.sent) == 1  # one reply despite two deliveries
    res = conn.sent[0].msg[0]
    assert res.error is None, res.error
    assert res.spans, "client spans must piggyback on the FitRes"
    fit_spans = [s for s in res.spans if s["name"] == "client/fit"]
    assert len(fit_spans) == 1  # no double emission
    assert fit_spans[0]["trace_id"] == "feedc0de"
    assert fit_spans[0]["parent_id"] == "00000001"
    span_ids = [s["span_id"] for s in res.spans]
    assert len(span_ids) == len(set(span_ids))


def _tiny_params(cfg):
    from photon_tpu.codec import params_to_ndarrays
    from photon_tpu.models.mpt import init_params

    return params_to_ndarrays(init_params(cfg.model, seed=0))


def test_dropped_envelope_then_retry_keeps_spans_clean(tmp_path):
    """A chaos-dropped FitIns manifests node-side as silence followed by a
    RETRY under a fresh msg_id (the server's timeout path). The retry must
    produce exactly one clean fit-span set — the drop corrupts nothing."""
    from photon_tpu.federation import NodeAgent, ParamTransport
    from photon_tpu.federation.messages import Envelope, FitIns

    cfg = make_cfg(tmp_path, n_rounds=1)
    cfg.photon.telemetry.enabled = True
    agent = NodeAgent(cfg, "node0", lambda: ParamTransport("inline"))
    telemetry.install(cfg.photon.telemetry, scope="node0", piggyback=True)
    ptr = agent.runtime.transport.put("bcast", *_tiny_params(cfg))
    fit = FitIns(server_round=1, cids=[0], params=ptr, local_steps=1,
                 server_steps_cumulative=0)
    # msg_id 8 = the retry; msg_id 7 (the dropped original) never arrives
    conn = _ScriptedConn([Envelope(fit, msg_id=8, trace=("feedc0de", "2"))])
    agent.serve(conn)
    res = conn.sent[0].msg[0]
    assert res.error is None, res.error
    assert len([s for s in res.spans if s["name"] == "client/fit"]) == 1
    ids = [s["span_id"] for s in res.spans]
    assert len(ids) == len(set(ids))
    assert telemetry.active().current_context() is None  # stack unwound


def test_socketconn_drop_emits_no_send_span():
    """A frame the chaos injector drops never hits the wire — and never
    emits a tcp/send span either (a phantom transport leg on the timeline
    would be corruption); the next successful send records normally."""
    import socket

    from photon_tpu import chaos as chaos_mod
    from photon_tpu.config.schema import ChaosConfig
    from photon_tpu.federation.messages import Envelope, Query
    from photon_tpu.federation.tcp import SocketConn

    telemetry.install(TelemetryConfig(enabled=True), scope="server")
    a, b = socket.socketpair()
    tx, rx = SocketConn(a), SocketConn(b)
    try:
        chaos_mod.install(
            ChaosConfig(enabled=True, seed=1234, tcp_drop_p=1.0), scope="t"
        )
        tx.send(Envelope(Query("ping"), 1))  # dropped
        assert [s["name"] for s in telemetry.active().snapshot()] == []
        chaos_mod.uninstall()
        tx.send(Envelope(Query("ping"), 2))  # delivered
        assert rx.recv().msg_id == 2
        names = [s["name"] for s in telemetry.active().snapshot()]
        assert names.count("tcp/send") == 1
        assert names.count("tcp/recv") == 1
    finally:
        chaos_mod.uninstall()
        tx.close(); rx.close()


# ---------------------------------------------------------------------------
# In-process end-to-end smoke (rides tier-1): merged trace + event log +
# KPI registry from one 1-round run
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("traced_run")
    cfg = make_cfg(tmp, n_rounds=1, eval_interval_rounds=1)
    cfg.photon.telemetry.enabled = True
    cfg.photon.checkpoint = True
    cfg.validate()
    app = make_app(cfg, tmp, with_ckpt=True)
    history = app.run()
    app.driver.shutdown()
    tdir = pathlib.Path(app.telemetry_dir)
    trace = load_chrome_trace(tdir / f"trace-{cfg.run_uuid}.json")
    events = read_events_jsonl(str(tdir / f"events-{cfg.run_uuid}.jsonl"))
    telemetry.uninstall()
    return cfg, history, trace, events


def test_traced_run_merged_timeline(traced_run):
    _, _, trace, events = traced_run
    complete = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    names = {e["name"] for e in complete}
    # server phases AND client phases in ONE file
    assert {"server/round", "server/fit_round_time",
            "server/broadcast_pre_time", "server/checkpoint_time",
            "client/fit", "client/train", "client/encode"} <= names
    # every client fit span sits under a server round span
    idx = span_index(trace)
    rounds = [e for e in complete if e["name"] == "server/round"]
    round_ids = {e["args"]["span_id"] for e in rounds}
    fits = [e for e in complete if e["name"] == "client/fit"]
    assert fits
    for f in fits:
        anc, cur = set(), f
        while cur["args"].get("parent_id") in idx:
            cur = idx[cur["args"]["parent_id"]]
            anc.add(cur["args"]["span_id"])
        assert anc & round_ids, f"fit span not parented under a round span"
    # event log carries a membership transition (new node → live)
    kinds = {e["kind"] for e in events}
    assert "membership/transition" in kinds


def test_traced_run_parses_as_perfetto_json(traced_run):
    _, _, trace, _ = traced_run
    # contract perfetto/chrome relies on: top-level traceEvents, usec ts
    assert isinstance(trace["traceEvents"], list)
    for ev in trace["traceEvents"]:
        assert "ph" in ev and "name" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    assert json.dumps(trace)  # round-trips


def test_metric_registry_covers_runtime_names(traced_run):
    """Every server/* and client/* metric name History saw at runtime is a
    declared constant in utils/profiling.py (or a declared dynamic family)
    — no more stringly-typed KPI drift (ISSUE 4 satellite)."""
    from photon_tpu.utils.profiling import is_registered_metric

    _, history, _, _ = traced_run
    runtime = [k for k in history.rounds
               if k.startswith(("server/", "client/"))]
    assert runtime, "run recorded no prefixed KPIs?"
    unregistered = sorted(k for k in runtime if not is_registered_metric(k))
    assert not unregistered, (
        f"metric names recorded at runtime but not declared in "
        f"utils/profiling.py: {unregistered}"
    )


def test_registry_constants_are_unique():
    from photon_tpu.utils import profiling

    names = [v for k, v in vars(profiling).items()
             if isinstance(v, str) and not k.startswith("_")
             and (v.startswith("server/") or v.startswith("client/")
                  or v.startswith("serve/") or v.startswith("router/"))]
    assert len(names) == len(set(names)), "duplicate KPI constants"


def _registry_constants() -> dict[str, str]:
    """``IDENT -> "plane/name"`` of every name constant in the registry."""
    import ast

    from photon_tpu.utils import profiling

    out = {}
    for node in ast.parse(pathlib.Path(profiling.__file__).read_text()).body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str) and "/" in node.value.value):
            out[node.targets[0].id] = node.value.value
    return out


def test_every_registry_name_is_used_by_the_program():
    """A name nothing produces is deleted with its hook (ISSUE 27): every
    constant is referenced somewhere in ``photon_tpu/`` beyond its own
    definition."""
    import re

    root = pathlib.Path(telemetry.__file__).resolve().parents[1]
    text = "\n".join(p.read_text() for p in root.rglob("*.py"))
    unused = [ident for ident in _registry_constants()
              if len(re.findall(rf"\b{ident}\b", text)) < 2]
    assert not unused, f"registry names nothing in the program uses: {unused}"


def test_registry_audit_table_matches_the_registry():
    """``docs/observability.md`` has one row per registry name — producer
    and reader — and no row for a name that is gone."""
    import re

    doc = pathlib.Path(telemetry.__file__).resolve().parents[2] / "docs" / "observability.md"
    audit = doc.read_text().split("## Registry audit", 1)[1].split("\n## ", 1)[0]
    rows = {}
    for line in audit.splitlines():
        m = re.match(r"\| `([^`]+)` \| ([^|]+) \| ([^|]+) \| ([^|]+) \|$", line)
        if m:
            rows[m.group(1)] = m.groups()[1:]
    names = set(_registry_constants().values())
    assert set(rows) == names, (sorted(names - set(rows)), sorted(set(rows) - names))
    for name, (kind, producer, reader) in rows.items():
        assert kind.strip() and "`" in producer and reader.strip(), name


def test_registry_covers_serve_names():
    """The serving plane's KPI vocabulary (ISSUE 5 satellite) is declared
    in the same registry as the training plane's."""
    from photon_tpu.utils.profiling import registered_metric_names

    names = registered_metric_names()
    for expect in ("serve/ttft_s", "serve/tokens_per_s", "serve/queue_depth",
                   "serve/slot_occupancy", "serve/evictions", "serve/rejected"):
        assert expect in names, expect


def test_registry_covers_fleet_router_names():
    """The fleet router's KPI vocabulary (ISSUE 16 satellite) rides the
    same registry — kpi-lint stays exit-0 for router/* emit sites."""
    from photon_tpu.utils.profiling import registered_metric_names

    names = registered_metric_names()
    for expect in ("router/requests_total", "router/reroutes_total",
                   "router/replicas_live", "serve/fleet_replicas",
                   "serve/fleet_rolling_swaps_total"):
        assert expect in names, expect


def test_telemetry_disabled_run_writes_nothing(tmp_path):
    cfg = make_cfg(tmp_path, n_rounds=1)
    app = make_app(cfg, tmp_path)
    app.run()
    app.driver.shutdown()
    assert telemetry.active() is None
    assert not pathlib.Path(app.telemetry_dir).exists()


# ---------------------------------------------------------------------------
# Cross-process propagation (slow): multiprocess + TCP round-trips
# ---------------------------------------------------------------------------


def _walk_to_round(idx, span_ev):
    cur = span_ev
    while cur["args"].get("parent_id") in idx:
        cur = idx[cur["args"]["parent_id"]]
        if cur["name"] == "server/round":
            return cur
    return None


@pytest.mark.slow
def test_multiprocess_trace_propagation_with_chaos(tmp_path):
    """The acceptance-criteria run: 2 rounds over a REAL spawned node
    process with chaos store faults on. The merged Perfetto JSON must show
    client fit spans (proc=node0) parented under the server round spans
    across the process boundary; the JSONL event log must carry a
    membership transition and an injected-fault event with trace
    correlation."""
    from photon_tpu.checkpoint import FileStore
    from photon_tpu.federation import MultiprocessDriver, ParamTransport, ServerApp

    cfg = make_cfg(tmp_path, n_rounds=2, n_total_clients=2,
                   n_clients_per_round=2, local_steps=1)
    cfg.photon.comm_stack.shm = False
    cfg.photon.comm_stack.objstore = True
    cfg.photon.telemetry.enabled = True
    cfg.photon.chaos.enabled = True
    cfg.photon.chaos.store_slow_p = 1.0
    cfg.photon.chaos.store_slow_max_s = 0.01
    driver = MultiprocessDriver(cfg, n_nodes=1, platform="cpu", n_cpu_devices=1)
    store = FileStore(cfg.photon.save_path + "/store")
    app = ServerApp(cfg, driver, ParamTransport("objstore", store=store))
    try:
        app.run()
    finally:
        driver.shutdown()

    tdir = pathlib.Path(app.telemetry_dir)
    trace = load_chrome_trace(tdir / f"trace-{cfg.run_uuid}.json")
    pid_names = {e["pid"]: e["args"]["name"]
                 for e in trace["traceEvents"] if e.get("ph") == "M"}
    complete = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    idx = span_index(trace)
    fits = [e for e in complete if e["name"] == "client/fit"]
    assert len(fits) >= 4  # 2 cids x 2 rounds
    for f in fits:
        assert pid_names[f["pid"]] == "node0"  # produced in the node process
        rnd = _walk_to_round(idx, f)
        assert rnd is not None, "fit span not under a server round span"
        assert pid_names[rnd["pid"]] == "server"
        assert f["args"]["trace_id"] == rnd["args"]["trace_id"]

    events = read_events_jsonl(str(tdir / f"events-{cfg.run_uuid}.jsonl"))
    kinds = {e["kind"] for e in events}
    assert "membership/transition" in kinds
    chaos_events = [e for e in events if e["kind"].startswith("chaos/")]
    assert chaos_events, "chaos fired but emitted no events"
    assert any(e.get("trace_id") for e in chaos_events), \
        "no chaos event carries trace correlation"


@pytest.mark.slow
def test_tcp_trace_propagation_under_duplicate_chaos(tmp_path):
    """TCP round-trip: trace context rides real socket envelopes, and with
    chaos duplicating EVERY frame (p=1.0) the node's msg_id dedup plus the
    driver's stale-mid guard keep the span stream clean — client fit spans
    carry the server round's trace_id, exactly one per fit, no duplicate
    span ids."""
    from photon_tpu import chaos as chaos_mod
    from photon_tpu.federation import ServerApp, ParamTransport
    from photon_tpu.federation.tcp import TcpServerDriver
    from tests.test_tcp_driver import _thread_node

    cfg = make_cfg(tmp_path, n_rounds=1, n_total_clients=2,
                   n_clients_per_round=2, local_steps=1, fit_timeout_s=30.0)
    cfg.photon.telemetry.enabled = True
    cfg.photon.chaos.enabled = True
    cfg.photon.chaos.tcp_duplicate_p = 1.0
    driver = TcpServerDriver("127.0.0.1", 0, expected_nodes=2)
    _threads = [_thread_node(cfg, f"node{i}", driver.port) for i in range(2)]
    driver.wait_for_nodes(timeout=30)
    app = ServerApp(cfg, driver, ParamTransport("inline"))
    try:
        history = app.run()
        assert history.latest("server/n_clients") == 2.0
    finally:
        driver.shutdown()
        chaos_mod.uninstall()

    tdir = pathlib.Path(app.telemetry_dir)
    trace = load_chrome_trace(tdir / f"trace-{cfg.run_uuid}.json")
    complete = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    idx = span_index(trace)
    rounds = [e for e in complete if e["name"] == "server/round"]
    assert len(rounds) == 1
    fits = [e for e in complete if e["name"] == "client/fit"]
    # exactly one fit span per cid: the duplicated FitIns frames were
    # deduplicated node-side, the duplicated replies server-side
    assert len(fits) == 2
    for f in fits:
        assert f["args"]["trace_id"] == rounds[0]["args"]["trace_id"]
        assert _walk_to_round(idx, f) is not None
    ids = [e["args"]["span_id"] for e in complete]
    assert len(ids) == len(set(ids)), "duplicate span ids in merged trace"
