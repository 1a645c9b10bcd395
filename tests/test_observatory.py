"""Run-health observatory tests (ISSUE 10): typed metric instruments and
their Prometheus exposition (golden-format: TYPE lines, cumulative
buckets, +Inf, exemplars), the health monitor + /statusz rollup, the
hardened PromServer, on-demand profiling, span-drop accounting, and the
chaos-injected-NaN end-to-end (alert with trace correlation, /statusz
degraded within the same round, clean runs stay ok).

All of it rides tier-1 (nothing here is slow).
"""

import http.client
import json
import math
import pathlib
import re
import threading
import time
import urllib.request

import pytest

from photon_tpu import chaos, telemetry
from photon_tpu.config.schema import TelemetryConfig
from photon_tpu.metrics.history import History
from photon_tpu.telemetry import introspect
from photon_tpu.telemetry.health import DEGRADED, FAILING, OK, PLANES, HealthMonitor
from photon_tpu.telemetry.introspect import ProfileBusyError, ProfileController
from photon_tpu.telemetry.metrics import (
    DEFAULT_BYTES_BUCKETS,
    MetricsHub,
    metric_name,
)
from photon_tpu.telemetry.prom import PromServer, render_exposition, render_history
from photon_tpu.utils.profiling import (
    AGG_DECODE_TIME,
    ALERT_DEGRADED_ROUNDS,
    ALERT_HBM_GROWTH,
    ALERT_NONFINITE,
    ALERT_QUEUE_SATURATION,
    COMPILES_TOTAL,
    HBM_BYTES_IN_USE,
    HBM_PEAK_BYTES,
    ROUND_TIME,
    SERVE_QUEUE_WAIT_S,
    SERVE_TPOT_S,
    SERVE_TTFT_S,
    SPANS_DROPPED,
    TCP_SEND_BYTES,
    registered_metric_names,
)
from tests.test_federation import make_cfg, make_app


@pytest.fixture(autouse=True)
def _clean_planes():
    telemetry.uninstall()
    chaos.uninstall()
    yield
    telemetry.uninstall()
    chaos.uninstall()


# ---------------------------------------------------------------------------
# typed instruments: golden exposition format
# ---------------------------------------------------------------------------


def test_counter_exposition_type_line_and_total_suffix():
    hub = MetricsHub()
    hub.counter(SPANS_DROPPED).inc()
    hub.counter(SPANS_DROPPED).inc(2)
    text = hub.render()
    name = metric_name(SPANS_DROPPED) + "_total"
    assert f"# TYPE {name} counter" in text
    assert f"{name} 3" in text
    with pytest.raises(ValueError):
        hub.counter(SPANS_DROPPED).inc(-1)


def test_gauge_exposition():
    hub = MetricsHub()
    hub.gauge(HBM_BYTES_IN_USE).set(123456)
    text = hub.render()
    assert f"# TYPE {metric_name(HBM_BYTES_IN_USE)} gauge" in text
    assert f"{metric_name(HBM_BYTES_IN_USE)} 123456" in text


def test_histogram_golden_format():
    """Exact exposition for a known observation set: cumulative buckets,
    the mandatory +Inf equal to _count, _sum/_count lines."""
    hub = MetricsHub()
    h = hub.histogram(ROUND_TIME, buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.7, 5.0, 100.0):
        h.observe(v)
    name = metric_name(ROUND_TIME)
    lines = [ln for ln in hub.render().splitlines() if ln]
    assert lines == [
        f"# TYPE {name} histogram",
        f'{name}_bucket{{le="0.1"}} 1',
        f'{name}_bucket{{le="1"}} 3',  # CUMULATIVE: 1 + 2
        f'{name}_bucket{{le="10"}} 4',
        f'{name}_bucket{{le="+Inf"}} 5',  # == _count
        f"{name}_sum 106.25",
        f"{name}_count 5",
    ]


def test_histogram_exemplar_carries_trace_context():
    telemetry.install(TelemetryConfig(enabled=True), scope="t")
    hub = telemetry.metrics_active()
    with telemetry.span("server/round", round=1) as sp:
        telemetry.metric_observe(SERVE_TTFT_S, 0.03)
    text = hub.render()
    # OpenMetrics exemplar on the containing bucket: trace + span ids of
    # the observing span, then value and timestamp
    m = re.search(
        r'_bucket\{le="0\.05"\} 1 # \{trace_id="([0-9a-f]{16})",'
        r'span_id="([0-9a-f]{16})"\} 0\.03 \d+\.\d+', text,
    )
    assert m, text
    assert m.group(1) == sp.trace_id


def test_exposition_content_negotiation():
    """Exemplars are OpenMetrics-only: a classic v0.0.4 scrape must get
    NO `#` annotations after values (legacy parsers fail the whole scrape
    on them); an Accept: application/openmetrics-text scrape gets the
    exemplars and the terminating # EOF."""
    from photon_tpu.telemetry.prom import negotiate_exposition

    telemetry.install(TelemetryConfig(enabled=True), scope="t")
    with telemetry.span("server/round", round=1):
        telemetry.metric_observe(SERVE_TTFT_S, 0.03)
    hub = telemetry.metrics_active()
    assert "trace_id" in hub.render(exemplars=True)
    assert "trace_id" not in hub.render(exemplars=False)
    assert negotiate_exposition(None) == (
        False, "text/plain; version=0.0.4; charset=utf-8")
    want, ctype = negotiate_exposition(
        "application/openmetrics-text;version=1.0.0,text/plain;q=0.5")
    assert want and ctype.startswith("application/openmetrics-text")
    # over HTTP: default scrape clean, OpenMetrics scrape exemplar'd
    srv = PromServer(History(), port=0, hub=hub)
    srv.start()
    try:
        url = f"http://127.0.0.1:{srv.port}/metrics"
        plain = urllib.request.urlopen(url, timeout=5)
        assert plain.headers["Content-Type"].startswith("text/plain")
        assert b"trace_id" not in plain.read()
        req = urllib.request.Request(
            url, headers={"Accept": "application/openmetrics-text"})
        om = urllib.request.urlopen(req, timeout=5)
        assert om.headers["Content-Type"].startswith(
            "application/openmetrics-text")
        body = om.read()
        assert b"trace_id" in body and body.endswith(b"# EOF\n")
    finally:
        srv.close()


def test_scrape_twice_counters_cumulative_histograms_monotone():
    """The scrape-twice pin: counters never reset between scrapes, and
    every histogram bucket is monotone non-decreasing across rounds."""
    hub = MetricsHub()
    hub.counter(COMPILES_TOTAL).inc(5)
    h = hub.histogram(ROUND_TIME, buckets=(1.0, 10.0))
    h.observe(0.5)

    def parse(text):
        counts = {}
        for ln in text.splitlines():
            m = re.match(r"(\S+?)\{le=\"([^\"]+)\"\} (\d+)", ln)
            if m:
                counts[m.group(2)] = int(m.group(3))
            m = re.match(r"(\S+_total) (\S+)", ln)
            if m:
                counts["total"] = float(m.group(2))
        return counts

    first = parse(hub.render())
    hub.counter(COMPILES_TOTAL).inc(1)
    h.observe(2.0)
    h.observe(50.0)
    second = parse(hub.render())
    assert first == {"1": 1, "10": 1, "+Inf": 1, "total": 5.0}
    assert second == {"1": 1, "10": 2, "+Inf": 3, "total": 6.0}
    for k in first:
        assert second[k] >= first[k], k


def test_instrument_kind_clash_raises():
    hub = MetricsHub()
    hub.counter(COMPILES_TOTAL)
    with pytest.raises(ValueError, match="already registered"):
        hub.histogram(COMPILES_TOTAL)


def test_bytes_named_histograms_get_bytes_buckets():
    hub = MetricsHub()
    assert hub.histogram(TCP_SEND_BYTES).buckets == DEFAULT_BYTES_BUCKETS


def test_ring_buffer_retention_and_percentile():
    hub = MetricsHub(retention=4)
    h = hub.histogram(AGG_DECODE_TIME)
    for v in range(10):
        h.observe(float(v))
    vals = h.recent_values()
    assert vals == [6.0, 7.0, 8.0, 9.0]  # bounded, oldest dropped
    assert h.percentile(1.0) == 9.0
    assert h.percentile(0.0) == 6.0
    # counters/gauges ring too
    c = hub.counter(COMPILES_TOTAL)
    for _ in range(10):
        c.inc()
    assert len(c.series()) == 4


def test_counter_inc_to_is_monotone():
    hub = MetricsHub()
    c = hub.counter(COMPILES_TOTAL)
    c.inc_to(7)
    c.inc_to(3)  # a re-installed listener must not DECREASE the series
    assert c.value == 7.0
    c.inc_to(9)
    assert c.value == 9.0


def test_render_exposition_skips_colliding_history_gauges():
    """A hub histogram and a History KPI sharing a name must not produce
    two conflicting # TYPE declarations for one family — the typed view
    wins; counters (suffixed _total) never collide."""
    hub = MetricsHub()
    hub.histogram(ROUND_TIME).observe(1.0)
    hub.counter(COMPILES_TOTAL).inc(2)   # name already *_total → collides
    hub.counter("serve/evictions").inc(3)  # _total-suffixed → no collision
    hist = History()
    hist.record(1, {ROUND_TIME: 1.0, COMPILES_TOTAL: 2.0,
                    "serve/evictions": 3.0, "server/n_clients": 4.0})
    text = render_exposition(hist, hub)
    name = metric_name(ROUND_TIME)
    assert text.count(f"# TYPE {name} ") == 1  # histogram only
    assert f"# TYPE {name} histogram" in text
    # a counter NAMED *_total owns its family outright (no doubled suffix,
    # no gauge twin); a plain counter coexists with its History gauge
    assert text.count(f"# TYPE {metric_name(COMPILES_TOTAL)} ") == 1
    assert f"# TYPE {metric_name(COMPILES_TOTAL)} counter" in text
    assert f"# TYPE {metric_name('serve/evictions')}_total counter" in text
    assert f"# TYPE {metric_name('serve/evictions')} gauge" in text
    assert f"# TYPE {metric_name('server/n_clients')} gauge" in text
    assert "photon_last_round" in text


def test_full_exposition_validates_structurally():
    """Mini promtool: every family declared exactly once, histogram
    buckets cumulative with +Inf == _count, every sample line parseable."""
    hub = MetricsHub()
    hub.counter(COMPILES_TOTAL).inc(3)
    hub.gauge(HBM_BYTES_IN_USE).set(1e9)
    for v in (0.01, 0.2, 3.0):
        hub.histogram(SERVE_TTFT_S).observe(v)
    text = hub.render()
    types: dict[str, str] = {}
    buckets: dict[str, list] = {}
    samples: dict[str, float] = {}
    for ln in text.splitlines():
        if not ln:
            continue
        if ln.startswith("# TYPE "):
            _, _, fam, kind = ln.split(" ")
            assert fam not in types, f"duplicate family {fam}"
            types[fam] = kind
            continue
        m = re.match(r'^([a-zA-Z0-9_]+)(\{le="([^"]+)"\})? ([0-9.e+-]+|\d+)( # .*)?$', ln)
        assert m, f"unparseable exposition line: {ln!r}"
        if m.group(3):
            buckets.setdefault(m.group(1), []).append((m.group(3), float(m.group(4))))
        else:
            samples[m.group(1)] = float(m.group(4))
    assert types[metric_name(COMPILES_TOTAL)] == "counter"
    assert types[metric_name(HBM_BYTES_IN_USE)] == "gauge"
    hname = metric_name(SERVE_TTFT_S)
    assert types[hname] == "histogram"
    series = buckets[hname + "_bucket"]
    counts = [c for _, c in series]
    assert counts == sorted(counts), "buckets must be cumulative"
    assert series[-1][0] == "+Inf"
    assert series[-1][1] == samples[hname + "_count"] == 3


def test_new_kpi_names_are_registered():
    names = registered_metric_names()
    for expect in (SERVE_TPOT_S, SERVE_QUEUE_WAIT_S, HBM_BYTES_IN_USE,
                   HBM_PEAK_BYTES, COMPILES_TOTAL,
                   "serve/hbm_bytes_in_use", "serve/backend_compiles_total"):
        assert expect in names, expect


# ---------------------------------------------------------------------------
# health monitor + watchers
# ---------------------------------------------------------------------------


def test_nonfinite_sentinel_latches_federation_failing():
    h = HealthMonitor()
    alerts = h.check_round_metrics(3, {"server/round_time": 1.0})
    assert alerts == [] and h.overall() == OK
    alerts = h.check_round_metrics(
        4, {"server/pseudo_grad_norm": float("nan"),
            "server/eval_loss": float("inf"), "server/round_time": 1.0},
    )
    assert len(alerts) == 1
    assert alerts[0].kind == ALERT_NONFINITE
    assert alerts[0].attrs["keys"] == ["server/eval_loss", "server/pseudo_grad_norm"]
    assert h.plane_status("federation") == FAILING
    h.resolve("federation")  # failing LATCHES: quiet rounds don't heal NaN
    assert h.plane_status("federation") == FAILING
    z = h.statusz()
    assert z["status"] == FAILING
    assert set(z["planes"]) == set(PLANES)


def test_collective_degraded_and_budget_watchers():
    h = HealthMonitor()
    h.degraded_budget_min_rounds = 4
    # one degraded round → degraded, clean rounds clear it
    h.check_collective_round(1, stragglers=1, n_total=4, degraded=True)
    assert h.plane_status("collective") == DEGRADED
    h.check_collective_round(2, stragglers=0, n_total=4, degraded=False)
    h.check_collective_round(3, stragglers=0, n_total=4, degraded=False)
    assert h.plane_status("collective") == OK
    # budget breach (2 degraded of 5 > 25%) → failing, latched
    h.check_collective_round(4, stragglers=2, n_total=4, degraded=True)
    assert h.plane_status("collective") == FAILING
    kinds = [a.kind for a in h.alerts]
    assert ALERT_DEGRADED_ROUNDS in kinds


def test_collective_failed_round_is_failing():
    h = HealthMonitor()
    h.check_collective_round(1, stragglers=4, n_total=4, degraded=False, failed=True)
    assert h.plane_status("collective") == FAILING


def test_straggler_percentile_watcher_needs_full_window():
    h = HealthMonitor()
    h.straggler_window = 4
    h._straggler_fracs = type(h._straggler_fracs)(maxlen=4)
    for r in range(3):
        h.check_collective_round(r, stragglers=2, n_total=4, degraded=False)
    assert all(a.kind != "alert/stragglers" for a in h.alerts)
    h.check_collective_round(3, stragglers=2, n_total=4, degraded=False)
    assert any(a.kind == "alert/stragglers" for a in h.alerts)
    assert h.plane_status("collective") == DEGRADED


def test_queue_saturation_hysteresis():
    h = HealthMonitor()
    h.queue_saturation_ticks = 4
    for _ in range(3):
        assert h.check_serve_tick(queue_depth=60, max_queue=64) is None
    a = h.check_serve_tick(queue_depth=60, max_queue=64)  # 4th tick fires
    assert a is not None and a.kind == ALERT_QUEUE_SATURATION
    assert h.plane_status("serve") == DEGRADED
    # stays degraded at the bound, exactly one alert
    assert h.check_serve_tick(queue_depth=64, max_queue=64) is None
    # drains below the clear fraction → resolves
    h.check_serve_tick(queue_depth=10, max_queue=64)
    assert h.plane_status("serve") == OK
    assert sum(a.kind == ALERT_QUEUE_SATURATION for a in h.alerts) == 1


def test_hbm_growth_watcher_monotone_window_only():
    h = HealthMonitor()
    h.hbm_window = 4
    h._hbm = type(h._hbm)(maxlen=4)
    base = 1_000_000.0
    # sawtooth never fires
    for v in (base, base * 1.2, base, base * 1.2, base):
        assert h.note_hbm_sample(v) is None
    # strictly-monotone growth > 20% across the window fires once
    h._hbm.clear()
    out = [h.note_hbm_sample(base * f) for f in (1.0, 1.1, 1.2, 1.35)]
    assert out[-1] is not None and out[-1].kind == ALERT_HBM_GROWTH


def test_alert_event_has_trace_correlation():
    telemetry.install(TelemetryConfig(enabled=True), scope="server")
    h = telemetry.health_active()
    with telemetry.span("server/round", round=7):
        h.alert(ALERT_NONFINITE, plane="federation", severity=FAILING, round=7)
    evs = telemetry.events_active().snapshot()
    ev = next(e for e in evs if e["kind"] == ALERT_NONFINITE)
    assert ev["trace_id"] and ev["span_id"]
    assert ev["attrs"]["plane"] == "federation"


# ---------------------------------------------------------------------------
# PromServer: exposition + statusz + debug/profile + handler hardening
# ---------------------------------------------------------------------------


class FakeProfiler:
    def __init__(self):
        self.calls = []

    def start_trace(self, out):
        self.calls.append(("start", out))

    def stop_trace(self):
        self.calls.append(("stop",))


def _prom(tmp_path, with_profiler=True):
    hub = MetricsHub()
    hub.histogram(SERVE_TTFT_S).observe(0.02)
    health = HealthMonitor()
    prof = ProfileController(str(tmp_path), profiler=FakeProfiler()) \
        if with_profiler else None
    hist = History()
    hist.record(1, {"server/round_time": 0.5})
    srv = PromServer(hist, port=0, hub=hub, health=health, profiler=prof)
    srv.start()
    return srv


def test_prom_serves_typed_exposition_and_statusz(tmp_path):
    srv = _prom(tmp_path)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=5
        ).read().decode()
        assert f"# TYPE {metric_name(SERVE_TTFT_S)} histogram" in body
        assert 'le="+Inf"' in body
        assert "photon_server_round_time 0.5" in body
        z = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/statusz", timeout=5
        ).read())
        assert z["status"] == "ok"
        assert set(z["planes"]) == set(PLANES)
        srv.health.alert(ALERT_NONFINITE, plane="federation", severity=FAILING)
        z = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/statusz", timeout=5
        ).read())
        assert z["status"] == "failing"
        assert z["planes"]["federation"]["status"] == "failing"
        assert z["alerts"][-1]["kind"] == ALERT_NONFINITE
    finally:
        srv.close()


def test_prom_debug_profile_endpoint(tmp_path):
    srv = _prom(tmp_path)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=5)
        payload = json.dumps({"units": 2, "tag": "t"}).encode()
        conn.request("POST", "/debug/profile", body=payload,
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        assert r.status == 202
        assert json.loads(r.read())["armed"] == {"armed_units": 2, "tag": "t"}
        # second request while armed → 409
        conn.request("POST", "/debug/profile", body=payload,
                     headers={"Content-Type": "application/json"})
        r = conn.getresponse()
        assert r.status == 409
        conn.close()
    finally:
        srv.close()


def test_prom_profile_503_when_no_profiler(tmp_path):
    srv = _prom(tmp_path, with_profiler=False)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=5)
        conn.request("POST", "/debug/profile", body=b"{}")
        assert conn.getresponse().status == 503
        conn.close()
    finally:
        srv.close()


def test_prom_keepalive_404_with_body_does_not_desync(tmp_path):
    """The hardening regression (mirrors the PR 8 frontend fix): a 404'd
    request WITH a body on a keep-alive connection must consume that body,
    or the next request on the same socket parses garbage."""
    srv = _prom(tmp_path)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=5)
        body = b"x" * 4096
        for _ in range(2):  # twice: the desync would poison the SECOND
            conn.request("POST", "/no/such/route", body=body)
            r = conn.getresponse()
            assert r.status == 404
            r.read()
        # same socket must still parse a clean scrape
        conn.request("GET", "/metrics")
        r = conn.getresponse()
        assert r.status == 200
        assert b"# TYPE" in r.read()
        conn.close()
    finally:
        srv.close()


def test_prom_handler_has_socket_timeout_and_close_is_bounded(tmp_path):
    """A byte-dripping scraper can't pin close(): the handler socket times
    out, and close() joins handler threads bounded."""
    srv = _prom(tmp_path)
    srv.handler_timeout_s  # the knob exists
    import socket as socket_mod

    s = socket_mod.create_connection(("127.0.0.1", srv.port), timeout=5)
    s.sendall(b"GET /metr")  # partial request line, then stall
    t0 = time.monotonic()
    srv.close(handler_join_s=2.0)
    assert time.monotonic() - t0 < 8.0, "close() pinned by a stalled handler"
    s.close()


# ---------------------------------------------------------------------------
# on-demand profiling controller
# ---------------------------------------------------------------------------


def test_profile_controller_lifecycle(tmp_path):
    fake = FakeProfiler()
    pc = ProfileController(str(tmp_path), profiler=fake)
    pc.tick("x")  # idle ticks are free
    assert fake.calls == []
    pc.request(2, tag="bench")
    with pytest.raises(ProfileBusyError):
        pc.request(1)
    pc.tick("server/round")  # starts
    assert fake.calls[0][0] == "start"
    assert "profile-bench-1" in fake.calls[0][1]
    pc.tick("server/round")  # 1/2
    assert len(fake.calls) == 1
    pc.tick("server/round")  # 2/2 → stops
    assert fake.calls[-1] == ("stop",)
    st = pc.status()
    assert st["armed_units"] == 0 and st["active_units_left"] == 0
    assert len(st["completed"]) == 1
    assert pathlib.Path(st["completed"][0]["dir"]).is_dir()
    # re-armable after completion
    pc.request(1)


def test_profile_controller_close_flushes_active(tmp_path):
    fake = FakeProfiler()
    pc = ProfileController(str(tmp_path), profiler=fake)
    pc.request(10)
    pc.tick("r")
    pc.close()  # run ended before 10 units elapsed
    assert fake.calls[-1] == ("stop",)
    with pytest.raises(ValueError):
        pc.request(0)


def test_prom_profile_rejects_non_object_json_body(tmp_path):
    srv = _prom(tmp_path)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=5)
        for bad in (b"null", b"[1,2]", b'"units"'):
            conn.request("POST", "/debug/profile", body=bad)
            r = conn.getresponse()
            assert r.status == 400, bad
            r.read()
        conn.close()
    finally:
        srv.close()


def test_over_armed_startup_profile_flushes_at_run_end(tmp_path):
    """profile_rounds greater than the run length: export_telemetry must
    still stop_trace so the capture artifact flushes."""
    cfg = make_cfg(tmp_path, n_rounds=2, n_clients_per_round=2)
    cfg.photon.telemetry.enabled = True
    cfg.photon.telemetry.profile_rounds = 10
    cfg.validate()
    app = make_app(cfg, tmp_path)
    fake = FakeProfiler()
    telemetry.profiler_active()._profiler = fake  # no real jax.profiler cost
    app.run()
    app.driver.shutdown()
    assert fake.calls[0][0] == "start"
    assert fake.calls[-1] == ("stop",)


def test_hbm_growth_alert_carries_callers_plane():
    h = HealthMonitor()
    h.hbm_window = 3
    h._hbm = type(h._hbm)(maxlen=3)
    out = [h.note_hbm_sample(v, plane="serve")
           for v in (1e6, 1.2e6, 1.5e6)]
    assert out[-1] is not None and out[-1].plane == "serve"
    assert h.plane_status("serve") == DEGRADED
    assert h.plane_status("federation") == OK


def test_profile_rounds_config_validation(tmp_path):
    cfg = make_cfg(tmp_path)
    cfg.photon.telemetry.profile_rounds = -1
    with pytest.raises(ValueError, match="profile_rounds"):
        cfg.validate()
    cfg.photon.telemetry.profile_rounds = 2
    with pytest.warns(UserWarning, match="profile_rounds"):
        cfg.validate()  # set without telemetry.enabled warns
    cfg.photon.telemetry.enabled = True
    cfg.photon.telemetry.metrics_retention = 0
    with pytest.raises(ValueError, match="metrics_retention"):
        cfg.validate()


# ---------------------------------------------------------------------------
# span-drop accounting (observability of the observability)
# ---------------------------------------------------------------------------


def test_span_buffer_drops_are_counted_and_warned_once():
    telemetry.install(
        TelemetryConfig(enabled=True, max_buffered_spans=4), scope="t"
    )
    for i in range(10):
        with telemetry.span("server/round", i=i):
            pass
    hub = telemetry.metrics_active()
    c = hub.get(SPANS_DROPPED)
    assert c is not None and c.value == 6.0
    warnings_ = [e for e in telemetry.events_active().snapshot()
                 if e["kind"] == SPANS_DROPPED]
    assert len(warnings_) == 1, "exactly ONE warning event per run"
    assert warnings_[0]["attrs"]["dropped_total"] == 1


def test_disabled_hooks_are_none_checks():
    assert telemetry.metrics_active() is None
    assert telemetry.health_active() is None
    assert telemetry.profiler_active() is None
    # and the hook helpers no-op without error
    telemetry.metric_inc(SPANS_DROPPED)
    telemetry.metric_set(HBM_BYTES_IN_USE, 1.0)
    telemetry.metric_observe(SERVE_TTFT_S, 0.1)
    telemetry.profile_tick("server/round")


# ---------------------------------------------------------------------------
# device-plane sampling
# ---------------------------------------------------------------------------


def test_sample_device_plane_feeds_metrics_and_hub(monkeypatch):
    monkeypatch.setattr(
        introspect, "device_memory",
        lambda device=None: {"bytes_in_use": 1000, "peak_bytes_in_use": 2000},
    )
    monkeypatch.setattr(introspect, "compile_count", lambda: 7)
    hub = MetricsHub()
    metrics: dict = {}
    introspect.sample_device_plane(
        metrics, hub, hbm_key=HBM_BYTES_IN_USE, peak_key=HBM_PEAK_BYTES,
        compiles_key=COMPILES_TOTAL,
    )
    assert metrics == {HBM_BYTES_IN_USE: 1000.0, HBM_PEAK_BYTES: 2000.0,
                       COMPILES_TOTAL: 7.0}
    assert hub.get(HBM_BYTES_IN_USE).value == 1000.0
    assert hub.get(COMPILES_TOTAL).value == 7.0


def test_compile_counter_counts_real_jax_compiles():
    """The monitoring listener sees an actual backend compile (the same
    event the PR 6 retrace sentinel counts)."""
    c = introspect.install_compile_counter()
    try:
        assert c is not None
        import jax
        import jax.numpy as jnp

        before = c.count
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
        assert c.count > before
        assert introspect.compile_count() == c.count
    finally:
        introspect.uninstall_compile_counter()
    assert introspect.compile_count() is None


# ---------------------------------------------------------------------------
# serve-plane request histograms (fake engine: no jax in the loop)
# ---------------------------------------------------------------------------


class FakeEngine:
    """Chunked-flow engine stub (ISSUE 12 interface: begin + mixed_step):
    every prompt 'prefills' in one fake chunk whose emission is token 1,
    decode rows emit token 2."""

    n_slots = 2

    def __init__(self):
        self._active = {}
        self._pending = {}

    @property
    def n_active(self):
        return len(self._active)

    def fits(self, n_prompt, max_new):
        return True

    def can_admit(self, n_prompt, max_new, prompt=None):
        return True

    def free_slot(self):
        return next((s for s in range(self.n_slots) if s not in self._active), None)

    def begin(self, slot, prompt, max_new, temperature=0.0, seed=0):
        self._active[slot] = True
        self._pending[slot] = len(prompt)

    def pending_tokens(self, slot):
        return self._pending.get(slot, 0)

    def mixed_step(self, chunk=None, include_decode=True):
        import numpy as np

        nxt = np.zeros(self.n_slots, np.int32)
        emitted = np.zeros(self.n_slots, bool)
        chunk_slot = None
        if chunk is not None:
            chunk_slot = chunk[0]
            self._pending.pop(chunk_slot, None)
            nxt[chunk_slot], emitted[chunk_slot] = 1, True
        if include_decode:
            for s in list(self._active):
                if s != chunk_slot and s not in self._pending:
                    nxt[s], emitted[s] = 2, True
        return nxt, emitted

    def evict(self, slot):
        self._active.pop(slot, None)
        self._pending.pop(slot, None)


def test_scheduler_observes_request_histograms():
    from photon_tpu.serve.scheduler import ContinuousBatcher

    telemetry.install(TelemetryConfig(enabled=True), scope="serve")
    batcher = ContinuousBatcher(FakeEngine(), max_queue=8).start()
    try:
        reqs = [batcher.submit([1, 2, 3], 3) for _ in range(4)]
        for r in reqs:
            r.result(timeout=30)
    finally:
        batcher.close()
    hub = telemetry.metrics_active()
    assert hub.get(SERVE_TTFT_S).count == 4
    assert hub.get(SERVE_QUEUE_WAIT_S).count == 4
    assert hub.get(SERVE_TPOT_S).count == 4  # 3 tokens → TPOT defined
    # TTFT exemplars link to the request umbrella spans
    assert any(ex.trace_id for ex in hub.get(SERVE_TTFT_S)._exemplars.values())
    # tick gauges/counters landed too
    assert hub.get("serve/queue_depth") is not None
    assert hub.get("serve/evictions").value == 4.0


# ---------------------------------------------------------------------------
# end-to-end: chaos-injected NaN delta → alert + /statusz degraded
# ---------------------------------------------------------------------------


def _observatory_cfg(tmp_path, nan_round=0):
    cfg = make_cfg(tmp_path, n_rounds=2, n_clients_per_round=2)
    cfg.photon.telemetry.enabled = True
    if nan_round:
        cfg.photon.chaos.enabled = True
        cfg.photon.chaos.nan_delta_round = nan_round
    return cfg.validate()


def test_clean_run_stays_ok_end_to_end(tmp_path):
    cfg = _observatory_cfg(tmp_path)
    app = make_app(cfg, tmp_path)
    app.run()
    app.driver.shutdown()
    health = telemetry.health_active()
    z = health.statusz()
    assert z["status"] == OK, z
    assert z["alerts"] == []
    # device-plane KPI sampling ran at round boundaries (compile counter
    # is available even on CPU; HBM only where the backend reports)
    assert len(app.history.series(COMPILES_TOTAL)) == 2
    hub = telemetry.metrics_active()
    assert hub.get(ROUND_TIME).count == 2  # stage-timing histogram


def test_nan_delta_round_fires_alert_and_degrades_statusz(tmp_path):
    cfg = _observatory_cfg(tmp_path, nan_round=2)
    app = make_app(cfg, tmp_path)
    history = app.run()
    app.driver.shutdown()
    # the injector fired exactly at round 2
    assert chaos.active().counts["nan_delta"] >= 1
    # the aggregate this round IS poisoned (the sentinel watched reality)
    r2 = dict(history.series("server/pseudo_grad_norm"))
    assert math.isnan(r2[2]) and not math.isnan(r2[1])
    health = telemetry.health_active()
    z = health.statusz()
    assert z["planes"]["federation"]["status"] == FAILING
    # alert carries the SAME round it fired in — "within the same round"
    alert = next(a for a in health.alerts if a.kind == ALERT_NONFINITE)
    assert alert.attrs["round"] == 2
    # ... and trace correlation: the event log's copy links to round 2's
    # server/round span in the merged trace
    tdir = pathlib.Path(app.telemetry_dir)
    events = [json.loads(ln) for ln in
              (tdir / f"events-{cfg.run_uuid}.jsonl").read_text().splitlines()]
    ev = next(e for e in events if e["kind"] == ALERT_NONFINITE)
    assert ev["trace_id"]
    trace_path = app.export_telemetry()
    trace = json.loads(pathlib.Path(trace_path).read_text())
    round_spans = [e for e in trace["traceEvents"]
                   if e.get("name") == "server/round"
                   and e.get("args", {}).get("round") == 2]
    assert any(e["args"]["trace_id"] == ev["trace_id"] for e in round_spans)
