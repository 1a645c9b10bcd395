"""The host-plane reader and the readers of the program's spans, against the
spans a chip run recorded (``fedround_host_spans.xplane.txt``: the answers
are that run's own result line) and against a toy of known nesting."""

from __future__ import annotations

import pathlib
import types

import pytest
from jax.profiler import ProfileData

from benchmark.spec import Spec
from benchmark.tests import toy
from benchmark.trace import host_spans as hs

HERE = pathlib.Path(__file__).parent
MS = 1e-3


def write_trace(tmp_path: pathlib.Path, fixture: str) -> pathlib.Path:
    text = "\n".join(ln for ln in (HERE / fixture).read_text().splitlines()
                     if not ln.startswith("#"))
    out = tmp_path / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    return tmp_path


@pytest.fixture()
def fedround(tmp_path):
    return write_trace(tmp_path, "fedround_host_spans.xplane.txt")


def reader(name: str):
    return Spec(toy.ROOT).layer_metric(name)


def run_of(trace_dir):
    return types.SimpleNamespace(trace_dir=trace_dir)


# the chip run's own result line (the fixture's header)
RECORDED = {
    "round_transport_s": 7.319219520500006,
    "round_handoff_s": 6.025779992,
    "round_server_update_s": 7.991366593000005,
    "round_unattributed_s": 0.250364266499993,
}


@pytest.mark.parametrize("metric", sorted(RECORDED))
def test_round_reader_gives_what_the_chip_run_read(fedround, metric):
    assert reader(metric).read(run_of(fedround), None) == pytest.approx(
        RECORDED[metric], rel=1e-9)


@pytest.mark.parametrize("metric", [*sorted(RECORDED), "loader_wait_ms_train"])
def test_reader_finds_nothing_without_a_trace_or_without_spans(tmp_path, metric):
    """An untraced run, and a parent commit whose program writes no span:
    the metric is left out and nothing raises."""
    assert reader(metric).read(run_of(None), None) is None
    bare = write_trace(tmp_path, "small_trace.xplane.txt")  # trainer/fit alone
    assert reader(metric).read(run_of(bare), None) is None


def test_the_round_adds_up(fedround):
    """The four round metrics and the clients' train loops make the round:
    nothing is counted twice and nothing is left out."""
    spans = hs.host_spans(fedround)
    rounds = hs.named(spans, "server/round")
    assert [round(r.seconds, 3) for r in rounds] == [26.566, 25.907]
    for rnd in rounds:
        members = hs.inside(spans, rnd)
        named = sum(hs.named_self_seconds(*names)(members, rnd) for names in (
            ("transport/put", "transport/get", "transport/free"),
            ("trainer/set_parameters", "trainer/get_parameters",
             "client/pseudo_grad_norm_time"),
            ("server/agg_decode_time", "server/agg_fold_time", "server/update")))
        loops = hs.named_self_seconds("trainer/fence", "trainer/next_batch")(members, rnd)
        rest = hs.unattributed_seconds(members, rnd)
        assert named + loops + rest == pytest.approx(rnd.seconds, rel=2e-3)


def test_lines_stats_parents_and_what_is_not_a_span(fedround):
    spans = hs.host_spans(fedround)
    names = {s.name for s in spans}
    assert "PjitFunction(train_step)" not in names  # the runtime's, not a span
    assert "np.asarray(jax.Array)" not in names
    fits = hs.named(spans, "client/fit")
    assert sorted((s.stats["round"], s.stats["cid"]) for s in fits) == [
        (2, 0), (2, 1), (3, 0), (3, 1)]
    # the second client of a round is fitted on a pool worker's line
    assert len({s.line for s in fits if s.stats["round"] == 2}) == 2
    put = hs.named(spans, "transport/put")[0]
    assert (put.parent, put.stats["mode"], put.stats["nbytes"]) == (
        "server/broadcast_pre_time", "shm", 500837376)
    assert put.leaf and put.self_s == put.seconds
    fit_round = hs.named(spans, "server/fit_round_time")[0]
    assert fit_round.parent == "server/round" and not fit_round.leaf
    assert fit_round.self_s < fit_round.seconds
    # the checkpoint writer's span lies on a line of its own, and covers
    # nothing of the round it overlaps
    writes = hs.named(spans, *hs.BACKGROUND)
    assert writes and all(w.parent is None for w in writes)


def test_nesting_self_time_and_union_over_lines():
    line0 = [(0.0, 10.0, "a/outer", {}), (1.0, 4.0, "a/child", {}),
             (2.0, 3.0, "a/leaf", {}), (6.0, 7.0, "a/leaf", {})]
    line1 = [(3.5, 6.5, "b/worker", {})]
    spans = hs._nest(line0, 0) + hs._nest(line1, 1)
    by = {(s.name, s.start_s): s for s in spans}
    outer = by[("a/outer", 0.0)]
    assert (outer.self_s, outer.leaf, outer.parent) == (6.0, False, None)
    child = by[("a/child", 1.0)]
    assert (child.self_s, child.leaf, child.parent) == (2.0, False, "a/outer")
    assert by[("a/leaf", 2.0)].parent == "a/child"
    assert by[("a/leaf", 6.0)].parent == "a/outer"
    # leaves: [2,3], [6,7] and the other line's [3.5,6.5] -> [2,3] + [3.5,7]
    assert hs.unattributed_seconds(hs.inside(spans, outer), outer) == pytest.approx(5.5)
    rows = {r["span"]: r for r in hs.table(spans)}
    assert rows["a/leaf"]["count"] == 2 and rows["b/worker"]["lines"] == [1]
