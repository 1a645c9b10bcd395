"""The trace reducer against a small recorded trace with known answers."""

from __future__ import annotations

import pathlib

import pytest
from jax.profiler import ProfileData

from benchmark.tests import toy  # noqa: F401  (puts the repo on sys.path)
from benchmark.trace.reduce import op_seconds, reduce_trace, self_times, union

FIXTURE = pathlib.Path(__file__).with_name("small_trace.xplane.txt")
US = 1e-6


@pytest.fixture()
def trace_dir(tmp_path):
    text = "\n".join(ln for ln in FIXTURE.read_text().splitlines()
                     if not ln.startswith("#"))
    out = tmp_path / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return tmp_path


def test_busy_window_and_self_times(trace_dir):
    r = reduce_trace(trace_dir, device_ids=[0])
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(12 * US)
    assert r["busy_s"] == pytest.approx(8 * US)  # the Steps line is not added
    ops = {e["name"]: e for e in r["ops"]}
    assert ops["while.1"]["seconds"] == pytest.approx(3 * US)  # 5 less its child
    assert ops["fusion.2"]["seconds"] == pytest.approx(2 * US)
    assert ops["fusion.3"]["seconds"] == pytest.approx(2 * US)
    assert sum(e["seconds"] for e in r["ops"]) == pytest.approx(r["busy_s"])
    assert r["device_ops"][0] == ["while.1", pytest.approx(3 * US)]


def test_idle_gaps_are_named_by_the_host_span_over_them(trace_dir):
    r = reduce_trace(trace_dir)
    gaps = dict(map(tuple, r["idle_gaps"]))
    assert gaps["np.asarray"] == pytest.approx(3 * US)  # [9,12]: covers 2 of 3
    assert gaps["trainer/fit"] == pytest.approx(1 * US)  # [5,6]
    assert r["longest_gap_s"] == pytest.approx(3 * US)


def test_a_kernel_is_found_by_its_event_text(trace_dir):
    r = reduce_trace(trace_dir)
    seconds, launches = op_seconds(r, r'custom_call_target="tpu_custom_call"')
    assert (seconds, launches) == (pytest.approx(1 * US), 1)
    assert op_seconds(r, "no such kernel") == (0, 0)


def test_given_window_and_missing_device(trace_dir):
    assert reduce_trace(trace_dir, window_s=1.0)["window_s"] == 1.0
    with pytest.raises(RuntimeError, match="no device plane"):
        reduce_trace(trace_dir, device_ids=[3])


def test_union_and_nesting_helpers():
    assert union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    out = self_times([(0, 10, "a", ""), (1, 4, "b", ""), (2, 3, "c", ""), (5, 6, "d", "")])
    assert sorted(out) == [("a", "", 6), ("b", "", 2), ("c", "", 1), ("d", "", 1)]
