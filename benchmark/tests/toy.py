"""A throw-away copy of the benchmark with toy cells added as NEW files and
NEW entries only, the way a later PR adds a cell. The tests drive the harness
in that copy on the CPU, at sizes a test run can hold."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TOY_MODEL = {"d_model": 32, "n_layers": 2, "n_heads": 2, "d_head": 16,
             "max_seq_len": 32, "vocab_size": 128, "expansion_ratio": 4,
             "param_dtype": "float32", "compute_dtype": "float32",
             "attn_impl": "xla"}
TOY_PEAKS = {"name": "test peaks, not a chip", "flops_per_s_bf16": 1.0e12,
             "hbm_bytes_per_s": 1.0e11, "hbm_bytes": 1.0e9}


def copy_benchmark(tmp: pathlib.Path) -> pathlib.Path:
    root = tmp / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    return root


def add_files(root: pathlib.Path, files: dict[str, object]) -> None:
    """New files only: adding a cell may not touch a file that is there."""
    for rel, content in files.items():
        path = root / rel
        assert not path.exists(), f"{rel} exists: a later PR may not edit it"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(content if isinstance(content, str)
                        else json.dumps(content, indent=1))


def add_entries(root: pathlib.Path, **lists) -> None:
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for key, entries in lists.items():
        bench[key].extend(entries)
    path.write_text(json.dumps(bench, indent=1))


def toy_config(name: str = "toy-mpt", **model) -> dict:
    m = dict(TOY_MODEL, **model)
    return {
        "name": name, "source": "benchmark/tests/toy.py (a test, not a model)",
        "preset": "mpt-125m", "reference": "mpt", "model": m,
        "overrides": {f"model.{k}": v for k, v in m.items() if k != "d_head"},
        "reduced": [], "assumed": {}, "deployment": "a test",
    }


def config_entry(name: str = "toy-mpt") -> dict:
    return {"name": name, "source": "benchmark/tests/toy.py",
            "file": f"benchmark/configs/{name}.json", "reduced": [],
            "why": "a toy for the tests"}
