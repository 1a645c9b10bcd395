"""A later PR adds a cell, a configuration, a traffic mix and a per-layer
metric as new files and new entries, and edits no file that is there; names
and units outside the allowed characters are refused."""

from __future__ import annotations

import json
import re

import pytest

from benchmark.tests import toy
from benchmark.spec import Spec, SpecError, check_name, check_unit

NEW_METRIC = '''"""Layer: a throw-away layer. Counts the spans a run recorded."""


def read(run, reduction):
    return float(len(run.spans))
'''


def test_a_new_cell_needs_only_new_files_and_entries(tmp_path):
    root = toy.copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}
    toy.add_files(root, {
        "benchmark/configs/throwaway.json": toy.toy_config("throwaway"),
        "benchmark/traffic/throwaway-mix.json": {
            "kind": "train_steps", "why": "throw-away", "overrides": {}},
        "benchmark/layer_metrics/span_count.py": NEW_METRIC,
    })
    toy.add_entries(
        root,
        configs=[toy.config_entry("throwaway")],
        workloads=[{"name": "throwaway-cell", "config": "throwaway",
                    "traffic": "throwaway-mix", "chips": 1, "why": "throw-away"}],
        end_to_end=[{"name": "throwaway_rate", "unit": "rows/s", "better": "higher",
                     "bound": 0.01, "source": "host_clock",
                     "workloads": ["throwaway-cell"]}],
        per_layer=[{"name": "span_count", "unit": "spans", "better": "lower",
                    "source": "program_counter", "layer": "a throw-away layer",
                    "moves": "throwaway_rate", "workloads": ["throwaway-cell"]}])
    parts = Spec(root).resolve("throwaway-cell")
    assert parts["config"]["name"] == "throwaway"
    assert parts["traffic"]["why"] == "throw-away"
    assert parts["driver"].__name__.endswith("train_steps")
    assert {m.name for m in parts["end_to_end"]} == {"throwaway_rate", "setup_s"}
    # compile_s lists no workloads: every cell that reports setup_s reports it
    assert set(parts["per_layer"]) == {"compile_s", "span_count"}

    class Run:
        spans = [1, 2, 3]

    assert parts["per_layer"]["span_count"].read(Run(), None) == 3.0
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"


def test_every_committed_cell_resolves():
    spec = Spec(toy.ROOT)
    for name in spec.cells:
        parts = spec.resolve(name)
        assert parts["per_layer"], name
        assert {"setup_s"} < {m.name for m in parts["end_to_end"]}, name
        assert parts["config"]["model"]["d_model"] > 0


COMMITTED = Spec(toy.ROOT)
STRETCH_KEYS = {"train_steps": "loss_fall_fits", "fed_rounds": "loss_fall_rounds"}


@pytest.mark.parametrize("cell", sorted(COMMITTED.cells))
def test_a_committed_cell_that_trains_names_its_stretch_for_loss_fell(cell):
    """``[a, b]`` with three fits (rounds) or more for the median, a limit
    beside it, and the readings that both were set from."""
    traffic = COMMITTED.traffic_file(COMMITTED.cell(cell))
    key = STRETCH_KEYS[traffic["kind"]]
    a, b = traffic[key]
    assert isinstance(a, int) and isinstance(b, int) and 0 <= a <= b - 3
    assert isinstance(traffic["limits"]["loss_fall_min"], float)
    assert key in traffic["limits_from"]


def test_run_py_names_no_cell_configuration_kind_or_metric():
    spec = Spec(toy.ROOT)
    words = (set(spec.cells) | set(spec.configs)
             | {m.name for m in spec.end_to_end + spec.per_layer}
             | {spec.traffic_file(c)["kind"] for c in spec.cells.values()}
             | {c.traffic for c in spec.cells.values()}) - {"setup_s"}
    for rel in ("benchmark/run.py", "benchmark/spec.py", "benchmark/harness.py"):
        text = (toy.ROOT / rel).read_text()
        named = [w for w in words
                 if re.search(rf"(?<![\w.\-]){re.escape(w)}(?![\w.\-])", text)]
        assert not named, rel


@pytest.mark.parametrize("name", ["tokens per second", "a,b", "a/b", "-lead", ".x",
                                  "", "x" * 65, "µs", 7])
def test_names_outside_the_allowed_characters_are_refused(name):
    with pytest.raises(SpecError):
        check_name(name, "metric")


@pytest.mark.parametrize("unit", ["tokens per second", "", "x" * 17, "µs", "a,b"])
def test_units_outside_the_allowed_characters_are_refused(unit):
    with pytest.raises(SpecError):
        check_unit(unit, "m")


@pytest.mark.parametrize("value", ["tokens/s", "%", "ms", "GB/s", "us"])
def test_units_inside_the_allowed_characters_pass(value):
    assert check_unit(value, "m") == value


def test_a_bad_entry_is_refused_before_any_run(tmp_path):
    root = toy.copy_benchmark(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"][0]["unit"] = "seconds of compile"
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(SpecError, match="unit"):
        Spec(root)


def test_an_unknown_chip_is_an_error():
    from benchmark.harness import NoAcceleratorError, load_peaks

    assert load_peaks("TPU v5 lite")["flops_per_s_bf16"] == 197.0e12
    with pytest.raises(NoAcceleratorError, match="peaks.json"):
        load_peaks("TPU v9 imaginary")
