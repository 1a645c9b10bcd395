"""Operation scopes from a trace's event metadata, and the readers of the
train step's scopes and of the flash kernels' own names, against a
hand-written trace with known answers (``train_scopes.xplane.txt``)."""

from __future__ import annotations

import types

import pytest

from benchmark.tests.test_host_spans import reader, write_trace
from benchmark.trace import op_scopes
from benchmark.trace.reduce import op_seconds, reduce_trace

US = 1e-6
OLD_FLASH = r'^%?multihead_attention[.\d]* = .*custom_call_target="tpu_custom_call"'


@pytest.fixture()
def train(tmp_path):
    trace_dir = write_trace(tmp_path, "train_scopes.xplane.txt")
    return types.SimpleNamespace(trace_dir=trace_dir), reduce_trace(trace_dir, [0])


def test_op_names_come_from_the_event_metadata(train):
    run, reduction = train
    names = op_scopes.op_names(run.trace_dir)
    assert any("flash_fwd/multihead_attention/pallas_call" in n
               for n in names["multihead_attention.22"])
    assert "copy-done.8" not in names  # an operation without an op_name
    # what ProfileData shows of the same event holds no scope at all
    assert not op_seconds(reduction, "flash_fwd")[1]
    assert op_scopes.scope_seconds(reduction, names, "no such scope") == (0, 0)


@pytest.mark.parametrize("metric,ms_per_step", [
    ("flash_fwd_ms_train", 0.026),
    ("flash_bwd_ms_train", 0.036),  # dk/dv 22 us + dq 14 us
    ("loss_head_ms_train", 0.010),
    ("optimizer_ms_train", 0.002),
    ("loader_wait_ms_train", 0.0015),  # 2 us and 1 us
])
def test_train_reader_against_known_answers(train, metric, ms_per_step):
    run, reduction = train
    assert reader(metric).read(run, reduction) == pytest.approx(ms_per_step)


def test_own_names_find_what_the_enclosing_name_finds(train):
    """``flash_fwd_ms_train + flash_bwd_ms_train`` is the flash time per
    step that ``flash_attention_roofline``'s pattern finds."""
    run, reduction = train
    seconds, launches = op_seconds(reduction, OLD_FLASH)
    assert launches == 6
    split = sum(reader(m).read(run, reduction)
                for m in ("flash_fwd_ms_train", "flash_bwd_ms_train"))
    assert split == pytest.approx(1000.0 * seconds / 2)


@pytest.mark.parametrize("metric", ["flash_fwd_ms_train", "flash_bwd_ms_train",
                                    "loss_head_ms_train", "optimizer_ms_train"])
def test_scope_reader_finds_nothing_in_a_program_without_scopes(tmp_path, metric):
    """The parent commit: no op_name carries a scope and no span counts the
    steps; the metric is left out and nothing raises."""
    trace_dir = write_trace(tmp_path, "small_trace.xplane.txt")
    run = types.SimpleNamespace(trace_dir=trace_dir)
    assert reader(metric).read(run, reduce_trace(trace_dir, [0])) is None


def test_raw_fields_decoder():
    # field 1 varint 300, field 2 bytes b"ab", field 3 fixed32
    buf = bytes([0x08, 0xAC, 0x02, 0x12, 0x02]) + b"ab" + bytes([0x1D, 1, 0, 0, 0])
    assert list(op_scopes._fields(buf)) == [(1, 300), (2, b"ab"), (3, b"\x01\x00\x00\x00")]
    with pytest.raises(ValueError, match="wire type"):
        list(op_scopes._fields(bytes([0x0B])))  # a group: not in an xplane file
