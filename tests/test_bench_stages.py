"""The post-parity bench evidence stages (CONVERGENCE_TPU.json /
PERF_1B_MEASURED.json writers) run end to end with tiny monkeypatched
configs on CPU — the on-chip run only changes the dims and the platform
stamp, so the artifact plumbing (incremental atomic writes, deadline
skips, loss curves, predicted-vs-measured fields) is what these cover."""

import json

import numpy as np
import pytest


@pytest.fixture
def bench(monkeypatch, tmp_path):
    import bench as bench_mod

    # keep artifacts out of the repo root during tests
    monkeypatch.setattr(bench_mod, "HERE", tmp_path)
    return bench_mod


class _FakeDev:
    """Stats grow per call so the probe's pre/post live-bytes delta is
    non-trivial: first call (pre-probe) 123 MiB, second (post-step) 444."""

    platform = "cpu"
    device_kind = "cpu"

    def __init__(self):
        self._calls = 0

    def memory_stats(self):
        self._calls += 1
        live = (123 if self._calls == 1 else 444) * 2**20
        return {"bytes_in_use": live, "peak_bytes_in_use": 456 * 2**20}


from photon_tpu.config.schema import Config as _RealConfig


def _tiny_cfg():
    cfg = _RealConfig()
    cfg.model.d_model = 32
    cfg.model.n_layers = 2
    cfg.model.n_heads = 2
    cfg.model.max_seq_len = 16
    cfg.model.vocab_size = 64
    cfg.model.attn_impl = "xla"
    cfg.model.compute_dtype = "float32"
    cfg.model.remat = False
    return cfg


def test_convergence_slice_writes_curves(bench, monkeypatch, tmp_path):
    import photon_tpu.config.schema as schema

    monkeypatch.setattr(schema, "Config", _tiny_cfg)
    # 2-row batches over a synthetic byte stream; 4 steps -> one eval point
    monkeypatch.setattr(
        bench, "_corpus_tokens",
        lambda: np.random.default_rng(0).integers(0, 64, 3000).astype(np.uint8),
    )
    monkeypatch.setenv("PHOTON_BENCH_CONV_GBS", "2")
    monkeypatch.setenv("PHOTON_BENCH_CONV_STEPS", "4")
    monkeypatch.setenv("PHOTON_BENCH_MICROBATCH", "2")
    monkeypatch.delenv("PHOTON_BENCH_CHILD_DEADLINE", raising=False)
    monkeypatch.delenv("PHOTON_BENCH_FLASH_BLOCK", raising=False)

    bench.tpu_convergence_slice(_FakeDev())

    out = json.loads((tmp_path / "CONVERGENCE_TPU.json").read_text())
    assert out["complete"], out.get("error")
    assert out["steps"] == 4 and out["global_batch"] == 2
    assert len(out["train_loss"]) == 1 and len(out["val_loss"]) == 1
    assert np.isfinite(out["val_loss"][0][1])
    assert out["tokens_per_sec"] > 0
    assert "val_loss_drop" in out


def test_convergence_slice_deadline_skip(bench, monkeypatch, tmp_path):
    import time

    monkeypatch.setenv("PHOTON_BENCH_CHILD_DEADLINE", str(time.time() + 10))
    bench.tpu_convergence_slice(_FakeDev())
    assert not (tmp_path / "CONVERGENCE_TPU.json").exists()


def _tiny_byte_cfg():
    """Byte-tokenizer-compatible tiny model (vocab must cover ids < 256)."""
    cfg = _tiny_cfg()
    cfg.model.vocab_size = 320
    cfg.model.max_seq_len = 64
    return cfg


def test_convergence_slice_returns_params_and_gauntlet_scores(
    bench, monkeypatch, tmp_path
):
    """The conv slice hands its trained params to the gauntlet stage, which
    writes GAUNTLET_TPU.json with per-task scores through the real scorers
    (byte tokenizer, cached decoder for the generation task)."""
    import photon_tpu.config.schema as schema

    monkeypatch.setattr(schema, "Config", _tiny_byte_cfg)
    monkeypatch.setattr(
        bench, "_corpus_tokens",
        lambda: np.random.default_rng(0).integers(0, 250, 4000).astype(np.uint8),
    )
    monkeypatch.setattr(bench, "_GAUNTLET_SLICE_TASKS", [
        "symbolic_problem_solving/svamp.jsonl",
        "commonsense_reasoning/copa_demo.jsonl",
    ])
    # the stage resolves task files relative to HERE, which the fixture
    # moved to tmp_path — point it back at the repo's local_data
    import pathlib

    (tmp_path / "photon_tpu" / "eval").mkdir(parents=True)
    (tmp_path / "photon_tpu" / "eval" / "local_data").symlink_to(
        pathlib.Path(__file__).parent.parent / "photon_tpu" / "eval" / "local_data"
    )
    monkeypatch.setenv("PHOTON_BENCH_CONV_GBS", "2")
    monkeypatch.setenv("PHOTON_BENCH_CONV_STEPS", "2")
    monkeypatch.setenv("PHOTON_BENCH_MICROBATCH", "2")
    monkeypatch.delenv("PHOTON_BENCH_CHILD_DEADLINE", raising=False)
    monkeypatch.delenv("PHOTON_BENCH_FLASH_BLOCK", raising=False)

    params = bench.tpu_convergence_slice(_FakeDev())
    assert params is not None and "wte" in params

    bench.gauntlet_on_slice(params, _FakeDev())
    out = json.loads((tmp_path / "GAUNTLET_TPU.json").read_text())
    assert out["complete"], out.get("error")
    assert set(out["tasks"]) == {"svamp", "copa_demo"}
    assert "icl/average" in out["scores"]


def test_conv_slice_persists_params_for_cross_process_gauntlet(
    bench, monkeypatch, tmp_path
):
    """In stage-orchestration mode (--stage conv) the trained params are
    serialized atomically for the gauntlet stage's separate process, and
    _load_slice_params round-trips them; without the env flag (a direct
    caller, in-memory handoff) nothing is written."""
    import photon_tpu.config.schema as schema

    monkeypatch.setattr(schema, "Config", _tiny_byte_cfg)
    monkeypatch.setattr(
        bench, "_corpus_tokens",
        lambda: np.random.default_rng(0).integers(0, 250, 4000).astype(np.uint8),
    )
    params_path = tmp_path / ".conv_slice_params.msgpack"
    monkeypatch.setattr(bench, "SLICE_PARAMS_PATH", params_path)
    monkeypatch.setenv("PHOTON_BENCH_CONV_GBS", "2")
    monkeypatch.setenv("PHOTON_BENCH_CONV_STEPS", "2")
    monkeypatch.setenv("PHOTON_BENCH_MICROBATCH", "2")
    monkeypatch.delenv("PHOTON_BENCH_CHILD_DEADLINE", raising=False)
    monkeypatch.delenv("PHOTON_BENCH_FLASH_BLOCK", raising=False)
    monkeypatch.delenv("PHOTON_BENCH_SAVE_SLICE_PARAMS", raising=False)

    params = bench.tpu_convergence_slice(_FakeDev())
    assert params is not None
    assert not params_path.exists()  # inline mode: in-memory handoff only

    monkeypatch.setenv("PHOTON_BENCH_SAVE_SLICE_PARAMS", "1")
    bench.tpu_convergence_slice(_FakeDev())
    assert params_path.exists()
    restored = bench._load_slice_params()
    np.testing.assert_array_equal(
        np.asarray(restored["wte"]["embedding"]),
        np.asarray(params["wte"]["embedding"]),
    )


def test_one_b_probe_predicted_vs_measured(bench, monkeypatch, tmp_path):
    import photon_tpu.config as config_mod

    monkeypatch.setattr(config_mod, "load_preset", lambda name: _tiny_cfg())
    monkeypatch.setenv("PHOTON_BENCH_1B_LAYERS", "2")
    monkeypatch.delenv("PHOTON_BENCH_CHILD_DEADLINE", raising=False)

    bench.one_b_memory_probe(_FakeDev())

    out = json.loads((tmp_path / "PERF_1B_MEASURED.json").read_text())
    assert out["complete"], out.get("error")
    assert out["n_params"] > 0
    assert np.isfinite(out["final_loss"])
    # the fake dev reports stats, so the measured fields must be present:
    # live = post-step minus pre-probe (444 - 123 MiB), peak = lifetime
    assert out["pre_probe_live_gib"] == pytest.approx(round(123 / 1024, 2))
    assert out["measured_live_gib"] == pytest.approx(round((444 - 123) / 1024, 2))
    assert out["process_lifetime_peak_gib"] == pytest.approx(round(456 / 1024, 2))
    # predicted may be None-gated on backends without memory_analysis, but
    # CPU provides it — require the args-vs-live ratio when both sides exist
    if "predicted_args_gib" in out:
        assert "predicted_over_measured" in out
