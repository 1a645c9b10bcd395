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
    "rows": 64, "zipf_a": 1.3, "steps_per_fit": 2, "loss_fall_fits": [0, 3],
    "warm_fits": 1, "trace_seconds": 1, "reference_rows": 2, "control_matmul": "bfloat16",
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
    "rows": 64, "zipf_a": 1.3, "warm_rounds": 1, "loss_fall_rounds": [0, 3],
    "trace_seconds": 1, "reference_rows": 2, "control_matmul": "bfloat16",
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


# the call that is one unit of a kind's window, and how many set-up makes
UNITS = {"train_steps": ("fit_chunk", lambda t: t["warm_fits"]),
         "fed_rounds": ("one_round", lambda t: 1 + t["warm_rounds"])}


def hold_units(run, driver, n, raised=()):
    """Make the window hold exactly ``n`` fits (rounds) on a machine of any
    speed: it is over when the driver has made that many past set-up's own.
    A fit whose place in the window is in ``raised`` returns its loss raised
    by 10."""
    name, in_setup = UNITS[run.traffic["kind"]]
    real, in_setup, done = getattr(driver, name), in_setup(run.traffic), [0]

    def counted(*args):
        out = real(*args)
        done[0] += 1
        if done[0] - 1 - in_setup in raised:
            out = dict(out, loss=out["loss"] + 10.0)
        return out

    # the module was loaded for this run alone: nothing to put back
    setattr(driver, name, counted)
    run.window_over = lambda t0: done[0] - in_setup >= n


def execute(root, workload, *, trace=False, seed=3, seconds=0.5, units=None,
            raised=()):
    """One run through the harness; with ``units`` its window holds exactly
    that many fits (rounds)."""
    from benchmark.harness import finish, prepare
    from benchmark.spec import Spec

    spec, lines = Spec(root), []
    parts, run = prepare(spec, workload, seed, seconds, trace,
                         t_process=time.monotonic(),
                         devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS))
    if units is not None:
        hold_units(run, parts["driver"], units, raised)
    try:
        parts["driver"].run(run)
    finally:
        run.clock.close()
    result = finish(spec, parts, run, log=lines.append)
    return result, [json.loads(ln) for ln in lines]


def edit_traffic(root, name, stretch, loss_fall_min):
    """The toy's own file in the throw-away copy, with another stretch."""
    path = root / "benchmark" / "traffic" / f"{name}.json"
    traffic = json.loads(path.read_text())
    key = next(k for k in traffic if k.startswith("loss_fall_"))
    traffic[key] = stretch
    traffic["limits"]["loss_fall_min"] = loss_fall_min
    path.write_text(json.dumps(traffic))


def values(checks):
    return {c["check"]: c["value"] for c in checks}


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


def freeze_the_step(monkeypatch):
    """A train step that returns its state as it got it (the loss is still
    computed)."""
    import photon_tpu.train.trainer as trainer_mod

    real = trainer_mod.make_train_step

    def frozen(*args, **kw):
        step = real(*args, **kw)

        def keep_state(state, tokens):
            _, metrics = step(state, tokens)
            return state, metrics
        return keep_state

    monkeypatch.setattr(trainer_mod, "make_train_step", frozen)


def test_train_steps_step_that_keeps_its_state_is_not_correct(checkout, monkeypatch):
    """The timed path broken underneath."""
    freeze_the_step(monkeypatch)
    result, checks = execute(checkout, "toy-train", seed=5)
    assert not result["correct"]
    ok = {c["check"]: c["ok"] for c in checks}
    assert not ok["param_change_norm_gap"]


def test_train_steps_loss_fell_tells_a_step_that_learns_nothing(checkout, monkeypatch):
    """Against a limit set between the toy's fall and none, as a cell's is:
    over fits 10-15 toy-train reads 0.084-0.136 (seeds 3, 5, 11, 2**31 + 11)
    and a step that learns nothing -0.012 to 0.031; 0.05 is their geometric
    middle."""
    edit_traffic(checkout, "toy-train", [10, 15], 0.05)
    result, checks = execute(checkout, "toy-train", seed=5, units=16)
    assert result["correct"] and values(checks)["loss_fell"] > 0.05, checks
    freeze_the_step(monkeypatch)
    result, checks = execute(checkout, "toy-train", seed=5, units=16)
    ok = {c["check"]: c["ok"] for c in checks}
    assert not result["correct"] and not ok["loss_fell"], checks
    assert abs(values(checks)["loss_fell"]) < 0.05


@pytest.mark.parametrize("cell,key", [("toy-train", "window_fits"),
                                      ("toy-fed", "window_rounds")])
def test_loss_fell_is_one_number_whatever_the_window_holds(checkout, cell, key):
    """The stretch is the traffic file's (3 units here), so a window of 4
    and one of 6 read the same ``loss_fell`` on one seed."""
    (short, short_checks), (long, long_checks) = (
        execute(checkout, cell, seed=11, units=n) for n in (4, 6))
    assert short["correct"] and long["correct"], (short_checks, long_checks)
    assert (short["attempted"], long["attempted"]) in {(4, 6), (8, 12)}  # rounds; steps, two a fit
    assert (values(short_checks)[key], values(long_checks)[key]) == (4, 6)
    assert values(short_checks)["loss_fell"] == values(long_checks)["loss_fell"]


@pytest.mark.parametrize("cell,key", [("toy-train", "window_fits"),
                                      ("toy-fed", "window_rounds")])
def test_a_window_shorter_than_the_stretch_is_not_correct(checkout, cell, key):
    """It fails a check of its own by name and reads no ``loss_fell``: what
    it has instead would be the accident of its length."""
    result, checks = execute(checkout, cell, seed=11, units=2)
    assert not result["correct"]
    failed = [c["check"] for c in checks if not c["ok"]]
    assert failed == [key] and values(checks)[key] == 2
    assert "loss_fell" not in values(checks)
    assert result["checks"][key] == {"value": 2.0, "limit": 3, "ok": False}


@pytest.mark.parametrize("raised,correct", [
    ((), True), ((3,), True), ((1,), True), ((0, 2), False), ((0, 1, 2), False)])
def test_one_fit_whose_loss_leaps_does_not_decide_loss_fell(checkout, raised, correct):
    """A loss raised by 10 past the stretch changes nothing, one inside it
    moves the median to a neighbour, a majority of the stretch fails."""
    result, checks = execute(checkout, "toy-train", seed=11, units=4, raised=raised)
    fell = values(checks)["loss_fell"]
    assert result["correct"] == correct, checks
    assert (abs(fell) < 0.2) if correct else (fell < -9.0)
    if raised == (3,):
        _, plain_checks = execute(checkout, "toy-train", seed=11, units=4)
        assert fell == values(plain_checks)["loss_fell"]


def test_the_profilers_stop_is_not_the_windows_time(checkout):
    """A traced window holds the fits a timed one holds: the seconds the
    profiler takes to stop (13 s after a 24 s trace on the chip) are not
    counted against ``--seconds``."""
    from benchmark.harness import prepare
    from benchmark.spec import Spec

    _, run = prepare(Spec(checkout), "toy-train", 3, 10.0, True,
                     t_process=time.monotonic(),
                     devices_and_peaks=(jax.devices()[:1], toy.TOY_PEAKS))
    run.clock.close()
    t0 = time.monotonic() - 12.0
    assert run.window_over(t0)
    run._trace_stop_s = 5.0
    assert not run.window_over(t0)
    run._trace_stop_s = 1.5
    assert run.window_over(t0)


def test_a_stretch_too_short_for_a_median_is_refused(checkout):
    edit_traffic(checkout, "toy-train", [2, 4], -1.0)
    with pytest.raises(ValueError, match="a median needs three"):
        execute(checkout, "toy-train", seed=11, units=4)


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
