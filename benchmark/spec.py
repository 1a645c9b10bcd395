"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix, one kind of
traffic or one per-layer metric is a file of its own, found from the name in
``BENCHMARK.json``. Nothing here (or in ``run.py``) lists cells,
configurations, kinds or metrics: a later PR adds files and entries and
edits no file that is there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks the contract."""


def check_name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise SpecError(
            f"{what} {value!r}: a name is 1-64 letters, digits, '_', '.', '-' "
            "and does not start with '.' or '-'")
    return value


def check_unit(value, what: str) -> str:
    if not isinstance(value, str) or not UNIT_RE.match(value):
        raise SpecError(
            f"{what} unit {value!r}: 1-16 letters, digits, '_', '/', '%', '.', "
            "'-' and no space ('tokens/s', never 'tokens per second')")
    return value


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    workloads: tuple[str, ...] | None  # None: every cell
    bound: float | None = None  # end-to-end only
    layer: str | None = None  # per-layer only
    moves: str | None = None  # per-layer only

    def in_cell(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    why: str


class Spec:
    """A checkout's ``BENCHMARK.json``, checked, with its files resolved."""

    def __init__(self, root: pathlib.Path | str) -> None:
        self.root = pathlib.Path(root).resolve()
        path = self.root / "BENCHMARK.json"
        try:
            self.raw = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError) as e:
            raise SpecError(f"cannot read {path}: {e}") from e
        self.paths = [self.root / p for p in self.raw["paths"]]
        self.run_seconds = int(self.raw["run_seconds"])
        self.configs = {}
        for c in self.raw["configs"]:
            check_name(c["name"], "configuration")
            self.configs[c["name"]] = c
        self.cells: dict[str, Cell] = {}
        for w in self.raw["workloads"]:
            check_name(w["name"], "workload")
            check_name(w["traffic"], "traffic")
            if w["config"] not in self.configs:
                raise SpecError(f"workload {w['name']!r} names configuration "
                                f"{w['config']!r}, which has no entry")
            if w["chips"] not in (1, 4):
                raise SpecError(f"workload {w['name']!r}: chips is 1 or 4")
            self.cells[w["name"]] = Cell(w["name"], w["config"], w["traffic"],
                                         int(w["chips"]), w["why"])
        self.end_to_end = [self._metric(m, end_to_end=True)
                           for m in self.raw["end_to_end"]]
        self.per_layer = [self._metric(m, end_to_end=False)
                          for m in self.raw["per_layer"]]
        names = [m.name for m in self.end_to_end + self.per_layer]
        if len(set(names)) != len(names):
            raise SpecError("two metrics share a name")
        e2e = {m.name for m in self.end_to_end}
        if "setup_s" not in e2e:
            raise SpecError("end_to_end lacks setup_s")
        for m in self.per_layer:
            if m.moves not in e2e:
                raise SpecError(f"{m.name} moves {m.moves!r}, which is not an "
                                "end-to-end metric")

    def _metric(self, m: dict, end_to_end: bool) -> Metric:
        check_name(m["name"], "metric")
        check_unit(m["unit"], m["name"])
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"{m['name']}: better is 'lower' or 'higher'")
        if m["source"] not in SOURCES:
            raise SpecError(f"{m['name']}: source is one of {SOURCES}")
        if end_to_end and m["source"] not in ("host_clock", "device_trace"):
            raise SpecError(f"{m['name']}: an end-to-end metric is taken by "
                            "the benchmark itself (host_clock or device_trace)")
        cells = m.get("workloads")
        for w in cells or ():
            if w not in self.cells:
                raise SpecError(f"{m['name']} lists workload {w!r}, which has "
                                "no entry")
        return Metric(m["name"], m["unit"], m["better"], m["source"],
                      None if cells is None else tuple(cells),
                      bound=m.get("bound"), layer=m.get("layer"),
                      moves=m.get("moves"))

    # -- one cell ---------------------------------------------------------
    def cell(self, name: str) -> Cell:
        try:
            return self.cells[name]
        except KeyError:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                            f"(it has {sorted(self.cells)})") from None

    def cell_end_to_end(self, cell: Cell) -> list[Metric]:
        return [m for m in self.end_to_end if m.in_cell(cell.name)]

    def cell_per_layer(self, cell: Cell) -> list[Metric]:
        e2e = {m.name for m in self.cell_end_to_end(cell)}
        return [m for m in self.per_layer
                if m.in_cell(cell.name) and m.moves in e2e]

    # -- files, by name ---------------------------------------------------
    def _under_paths(self, rel: str) -> pathlib.Path:
        for base in self.paths:
            p = base / rel
            if p.is_file():
                return p
        raise SpecError(f"no file {rel!r} under {[str(p) for p in self.paths]}")

    def config_file(self, cell: Cell) -> dict:
        entry = self.configs[cell.config]
        path = self.root / entry["file"]
        if not any(base in path.resolve().parents for base in self.paths):
            raise SpecError(f"{entry['file']} is not under paths")
        data = json.loads(path.read_text())
        if sorted(data.get("reduced", [])) != sorted(entry["reduced"]):
            raise SpecError(f"{entry['file']}: 'reduced' differs from "
                            "BENCHMARK.json's")
        return data

    def traffic_file(self, cell: Cell) -> dict:
        return json.loads(self._under_paths(f"traffic/{cell.traffic}.json").read_text())

    def _module(self, rel: str, name: str):
        path = self._under_paths(rel)
        spec = importlib.util.spec_from_file_location(name, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def driver(self, kind: str):
        check_name(kind, "traffic kind")
        return self._module(f"drivers/{kind}.py", f"benchmark_driver_{kind}")

    def layer_metric(self, name: str):
        check_name(name, "metric")
        return self._module(f"layer_metrics/{name}.py",
                            "benchmark_metric_" + re.sub(r"\W", "_", name))

    def resolve(self, workload: str) -> dict:
        """Everything one cell names, each found by its name: the test of
        'a later PR edits no existing file'."""
        cell = self.cell(workload)
        traffic = self.traffic_file(cell)
        return {
            "cell": cell,
            "config": self.config_file(cell),
            "traffic": traffic,
            "driver": self.driver(traffic["kind"]),
            "end_to_end": self.cell_end_to_end(cell),
            "per_layer": {m.name: self.layer_metric(m.name)
                          for m in self.cell_per_layer(cell)},
        }
