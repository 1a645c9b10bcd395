"""Every driver end to end at a toy size on the CPU, through the harness's
``execute`` (which skips only the look for a chip), in a throw-away copy to
which the toy cells were added as new files. Also: the timed path broken
underneath must come out as not correct."""

from __future__ import annotations

import json
import time

import jax
import pytest

from benchmark.tests import toy

TRAIN_TRAFFIC = {
    "kind": "train_steps", "why": "toy",
    "overrides": {"train.global_batch_size": 4, "train.device_microbatch_size": 2,
                  "dataset.synthetic": True},
    "rows": 64, "zipf_a": 1.3, "steps_per_fit": 2, "warm_fits": 1,
    "trace_seconds": 1, "reference_rows": 2, "control_matmul": "bfloat16",
    # from readings at this size over a few seeds: the float32 program stays
    # under 1e-6 / 2e-7 / 2e-5, the bfloat16 control reads 4e-4 and 6e-4 or more
    # on the two norms
    "limits": {"loss_fall_min": -1.0, "loss_gap_step1": 1e-5, "loss_gap_step2": 1e-5,
               "loss_gap_step3": 1e-5, "first_grad_norm_gap": 1e-5,
               "param_change_norm_gap": 1e-4},
}

FED_TRAFFIC = {
    "kind": "fed_rounds", "why": "toy",
    "overrides": {"fl.n_total_clients": 2, "fl.n_clients_per_round": 2,
                  "fl.local_steps": 2, "fl.eval_interval_rounds": 0,
                  "train.global_batch_size": 4, "train.device_microbatch_size": 2,
                  "photon.checkpoint": True, "dataset.synthetic": True,
                  "dataset.shuffle": False},
    "rows": 64, "zipf_a": 1.3, "warm_rounds": 1, "trace_seconds": 1,
    "reference_rows": 2, "control_matmul": "bfloat16",
    # program 5e-7 / 5e-7 / 1.2e-6; the bfloat16 control 3e-4 or more on the
    # change of the global weights
    "limits": {"loss_fall_min": -1.0, "round_change_norm_gap": 1e-5, "round_loss_gap": 1e-5,
               "pseudo_grad_norm_gap": 1e-4, "pseudo_grad_norm_min": 1e-9},
}
SERVE_TRAFFIC = {
    "kind": "serve_open_loop", "why": "toy", "overrides": {},
    "rate_per_s": 20.0, "knee_per_s": None,
    "prompt_tokens": {"median": 10, "sigma": 0.5, "min": 4, "max": 24},
    "answer_tokens": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
    "lead_s": 1.0, "drain_s": 120.0, "stats_every_s": 0.02, "trace_seconds": 1,
    "check_requests": 3, "control_matmul": "bfloat16",
    "limits": {"served_logit_gap": 1e-3},
}
CELL_METRICS = {
    "toy-serve": ("ttft_p95_ms", "itl_p95_ms", "queue_p95_ms", "slot_occupancy",
                  "ragged_paged_attention_roofline"),
    "toy-train": ("train_tokens_per_s", "mfu_train", "step_ms_train"),
    "toy-fed": ("round_s", "round_overhead_s", "fit_tokens_per_s"),
}


@pytest.fixture()
def checkout(tmp_path):
    root = toy.copy_benchmark(tmp_path)
    toy.add_files(root, {
        "benchmark/configs/toy-mpt.json": toy.toy_config(),
        "benchmark/traffic/toy-train.json": TRAIN_TRAFFIC,
        "benchmark/traffic/toy-fed.json": FED_TRAFFIC,
        "benchmark/configs/toy-mpt-serve.json": dict(
            toy.toy_config("toy-mpt-serve", max_seq_len=64),
            expect_attention_impl="ragged-ref",
            overrides={**toy.toy_config("x", max_seq_len=64)["overrides"],
                       "photon.serve.n_slots": 2, "photon.serve.block_size": 4,
                       "photon.serve.max_new_tokens": 8,
                       "photon.serve.prefill_token_budget": 32}),
        "benchmark/traffic/toy-serve.json": SERVE_TRAFFIC,
    })
    toy.add_entries(
        root, configs=[toy.config_entry(), toy.config_entry("toy-mpt-serve")],
        workloads=[{"name": name, "traffic": name, "chips": 1, "why": "toy",
                    "config": "toy-mpt-serve" if name == "toy-serve" else "toy-mpt"}
                   for name in CELL_METRICS],
        # no serving cell is committed yet: its metrics come as new entries,
        # the way the PR that adds the cell will bring them
        end_to_end=[{"name": n, "unit": "ms", "better": "lower", "bound": 0.05,
                     "source": "host_clock", "workloads": []}
                    for n in ("ttft_p95_ms", "itl_p95_ms")],
        per_layer=[{"name": n, "unit": u, "better": "lower", "source": src,
                    "layer": layer, "moves": moves, "workloads": []}
                   for n, u, src, layer, moves in (
                       ("queue_p95_ms", "ms", "program_span", "serve scheduler", "ttft_p95_ms"),
                       ("slot_occupancy", "%", "program_counter", "serve engine", "itl_p95_ms"),
                       ("ragged_paged_attention_roofline", "%", "device_trace", "kernels",
                        "itl_p95_ms"))])
    # a later PR lists its cell on the metrics it reports; the toy cells are
    # added to the entries that are there
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        for cell, names in CELL_METRICS.items():
            if m["name"] in names:
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def execute(root, workload, *, trace=False, seed=3, seconds=0.5):
    from benchmark.harness import execute
    from benchmark.spec import Spec

    lines = []
    result = execute(Spec(root), workload, seed, seconds, trace,
                     t_process=time.monotonic(),
                     devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS),
                     log=lines.append)
    return result, [json.loads(ln) for ln in lines]


def test_train_steps_toy_run_is_correct(checkout):
    result, checks = execute(checkout, "toy-train", seed=2**31 + 11)
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] >= 2
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert {c["check"] for c in checks} >= {
        "loss_gap_step1", "first_grad_norm_gap", "param_change_norm_gap",
        "compiles_in_window"}


def test_fed_rounds_toy_run_is_correct(checkout):
    result, checks = execute(checkout, "toy-fed", seed=7, seconds=0.3)
    assert result["correct"], checks
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"round_s", "setup_s"}


def test_fed_rounds_server_that_keeps_its_weights_is_not_correct(checkout, monkeypatch):
    """The timed path broken underneath: a server update that returns the
    global weights unchanged."""
    from photon_tpu.strategy.optimizers import FedNesterov

    monkeypatch.setattr(FedNesterov, "server_update",
                        lambda self, pseudo_grad, lr: self.current_parameters)
    result, checks = execute(checkout, "toy-fed", seed=7, seconds=0.3)
    assert not result["correct"]
    assert not {c["check"]: c["ok"] for c in checks}["round_change_norm_gap"]


def test_train_steps_step_that_keeps_its_state_is_not_correct(checkout, monkeypatch):
    """The timed path broken underneath: a train step that returns its state
    as it got it (the loss is still computed)."""
    import photon_tpu.train.trainer as trainer_mod

    real = trainer_mod.make_train_step

    def frozen(*args, **kw):
        step = real(*args, **kw)

        def keep_state(state, tokens):
            _, metrics = step(state, tokens)
            return state, metrics
        return keep_state

    monkeypatch.setattr(trainer_mod, "make_train_step", frozen)
    result, checks = execute(checkout, "toy-train", seed=5)
    assert not result["correct"]
    ok = {c["check"]: c["ok"] for c in checks}
    assert not ok["param_change_norm_gap"]


def test_serve_open_loop_toy_run_is_correct(checkout):
    result, checks = execute(checkout, "toy-serve", seed=9, seconds=1.0)
    assert result["correct"], checks
    assert result["attempted"] == 20 and result["failed"] == 0
    assert set(result["metrics"]) == {"ttft_p95_ms", "itl_p95_ms", "setup_s"}


def test_serve_open_loop_altered_token_is_not_correct(checkout, monkeypatch):
    """The timed path broken underneath: a token altered where it is
    produced (the scheduler pushes token + 1)."""
    from photon_tpu.serve.scheduler import ContinuousBatcher

    real = ContinuousBatcher._push_token
    monkeypatch.setattr(
        ContinuousBatcher, "_push_token",
        lambda self, slot, req, tok: real(self, slot, req, (tok + 1) % 128))
    result, checks = execute(checkout, "toy-serve", seed=9, seconds=1.0)
    assert not result["correct"]
    assert not {c["check"]: c["ok"] for c in checks}["served_logit_gap"]


@pytest.mark.parametrize("cell,seconds", [("toy-train", 0.0), ("toy-fed", 0.0)])
def test_the_control_one_precision_down_is_not_correct(checkout, cell, seconds):
    """The reference put in the program's place and computed in the nearest
    precision below the (float32) toy configuration's, bfloat16, breaks a
    limit that the program keeps. The chip readings at the cells' own sizes
    are in PERF.md. (No serving cell is committed yet; at a toy vocabulary a
    lower precision all but never changes which token comes first, so its
    control has to be read at a real size when that cell is added.)"""
    from benchmark.harness import prepare
    from benchmark.spec import Spec

    parts, run = prepare(Spec(checkout), cell, 13, seconds, False,
                         t_process=time.monotonic(),
                         devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS))
    try:
        out = parts["driver"].readings(run)
    finally:
        run.clock.close()
    limits = run.traffic["limits"]
    numbers = [k for k in limits if k in out["program"]]
    assert numbers and all(out["program"][k] <= limits[k] for k in numbers), out
    assert any(out["control"][k] > limits[k] for k in numbers), out
