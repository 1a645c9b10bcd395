"""Traffic kind ``fed_rounds``: whole federated rounds on one chip.

``photon_tpu.federated.build_app(cfg, n_nodes=1)``: the in-process driver,
one node whose persistent trainer fits each sampled client in turn, the
parameter transport, the strategy's aggregation and server update, and the
round checkpoint. The benchmark makes the initial global weights and every
client's rows from the seed. Set-up runs round 1 (which compiles, and which
the plain reference then follows in full) and one more warm round; the
window runs whole rounds until ``--seconds`` have passed.

``round_s`` is the window over the whole rounds completed in it;
``loss_fell`` reads the rounds of it that ``loss_fall_rounds`` names.
"""

from __future__ import annotations

import gc
import json

import numpy as np

from benchmark.drivers.train_steps import (
    make_rows,
    make_weights,
    reference_family,
    write_rows,
)
from benchmark.harness import median
from benchmark.program import build_config, optimizer_settings

# what the program's History says of a round, kept for the per-layer readers
# and printed (medians over the window's rounds) on a line before the result
HISTORY_KEYS = (
    "client/fit_time", "client/tokens_per_sec", "client/fit_init_time",
    "client/fit_set_parameters_time", "node_training_time_s",
    "server/round_time", "server/fit_round_time", "server/checkpoint_time",
    "server/broadcast_pre_time", "server/ckpt_async_write_s",
    "server/ckpt_barrier_wait_s", "server/agg_decode_time", "server/agg_fold_time")


def build_app(run, cfg, ref, dims):
    """The app as ``python -m photon_tpu.federated`` builds it, on the
    benchmark's weights and rows."""
    from photon_tpu import federated
    from photon_tpu.codec import params_to_ndarrays

    with run.span("setup/rows"):
        for cid in range(cfg.fl.n_total_clients):
            # where ClientRuntime looks before it would write rows of its own
            write_rows(run.work_dir / "save" / "synthetic" / f"client_{cid}"
                       / cfg.dataset.split_train,
                       client_rows(run, cfg, cid), cfg.model.vocab_size)
    with run.span("setup/weights"):
        metadata, arrays = params_to_ndarrays(make_weights(ref, dims, run.seed))
    with run.span("setup/app"):
        app = federated.build_app(cfg, n_nodes=1)
        app.metadata = metadata
        app.strategy.initialize(arrays)
        if app.ckpt_mgr is not None:
            app.save_checkpoint(0)  # as ServerApp.run does before round 1
    return app


def one_round(run, app, rnd: int) -> None:
    # ServerApp.run() is one-shot (it frees the transport when it returns),
    # so the rounds are driven one at a time through the loop's own body
    with run.span("server/round"):
        app._one_round(app.cfg, rnd)


def close_app(app) -> None:
    try:
        if app.ckpt_mgr is not None:
            app.ckpt_mgr.wait_pending()
    finally:
        app.free_transport()
        app.driver.shutdown()


def client_rows(run, cfg, cid: int) -> np.ndarray:
    return make_rows(run.traffic["rows"], cfg.model.max_seq_len,
                     cfg.model.vocab_size, run.traffic["zipf_a"], run.seed, salt=cid)


def client_batches(run, cfg, cid: int, n: int) -> list[np.ndarray]:
    """The first ``n`` batches of client ``cid``: its rows in order (the
    cell turns shuffling off, so the feed is known without asking the
    program)."""
    rows, b = client_rows(run, cfg, cid), cfg.train.global_batch_size
    return [rows[i * b:(i + 1) * b] for i in range(n)]


def reference_round(run, ref, dims, cfg, opt: dict, matmul: str) -> dict:
    """Round 1 by the plain reference: each client in turn from the global
    weights, on one node whose optimizer state carries over from client to
    client with its step count set back to the server's (as the program's
    persistent trainer does), then the sample-weighted average through the
    server rule (Nesterov, momentum 0, rate 1: the average itself)."""
    import jax
    import jax.numpy as jnp

    if (cfg.fl.strategy_name, cfg.fl.server_momentum, cfg.fl.server_learning_rate) \
            != ("nesterov", 0.0, 1.0):
        raise ValueError("the plain server rule is Nesterov with momentum 0, rate 1")
    global0 = make_weights(ref, dims, run.seed)
    grad = ref.Grad(dims, matmul, rows=run.traffic["reference_rows"])
    step = jax.jit(lambda p, s, g: ref.adopt_step(p, s, g, opt))
    state = ref.adopt_init(global0)
    total, losses = None, []
    n_clients = cfg.fl.n_clients_per_round
    for cid in range(n_clients):
        params = global0
        state = dict(state, count=jnp.zeros([], jnp.int32))
        for batch in client_batches(run, cfg, cid, cfg.fl.local_steps):
            loss, g = grad(params, batch)
            params, state = step(params, state, g)
        losses.append(float(loss))
        total = params if total is None else jax.tree.map(jnp.add, total, params)
    change = jax.tree.map(lambda t, g0: t / n_clients - g0, total, global0)
    norms = ref.leaf_norms(change)
    return {"change_norms": norms, "loss": float(np.mean(losses)),
            "pseudo_grad_norm": float(np.sqrt(sum(
                float(np.sum(v ** 2)) for v in norms.values())))}


def program_round(run, app, ref, dims) -> dict:
    """Round 1 by the program: the change of the global weights, the
    clients' mean last loss and the server's pseudo-gradient norm."""
    import jax.numpy as jnp

    from photon_tpu.codec import unflatten_params

    before = [a.copy() for a in app.strategy.current_parameters]
    one_round(run, app, 1)
    template = ref.make_params(  # only its structure is used
        dict(dims, d_model=1, hidden=1, vocab_size=1, max_seq_len=1, n_layers=1), 0)
    change = unflatten_params(template, [
        jnp.asarray(a) - jnp.asarray(b)
        for a, b in zip(app.strategy.current_parameters, before)])
    return {"change_norms": ref.leaf_norms(change),
            "loss": app.history.latest("loss"),
            "pseudo_grad_norm": app.history.latest("server/pseudo_grad_norm")}


def gaps(ref, got: dict, want: dict) -> dict[str, float]:
    return {
        "round_change_norm_gap": ref.worst_leaf_gap(got["change_norms"],
                                                    want["change_norms"]),
        "round_loss_gap": abs(got["loss"] - want["loss"]),
        "pseudo_grad_norm_gap": abs(got["pseudo_grad_norm"] - want["pseudo_grad_norm"])
        / want["pseudo_grad_norm"],
    }


def start(run):
    ref = reference_family(run.config)
    dims = ref.dims_of(run.config["model"])
    cfg = build_config(run.config, run.traffic, run.work_dir / "save", run.seed)
    if cfg.dataset.shuffle or cfg.fl.n_clients_per_round != cfg.fl.n_total_clients:
        raise ValueError("fed_rounds needs every client in every round, rows in order")
    return ref, dims, cfg, optimizer_settings(cfg), build_app(run, cfg, ref, dims)


def run(run) -> None:
    ref, dims, cfg, opt, app = start(run)
    try:
        got = program_round(run, app, ref, dims)
        rnd = 1
        for _ in range(run.traffic["warm_rounds"]):
            rnd += 1
            one_round(run, app, rnd)
        first_window_round = rnd + 1
        with run.timed_window() as t0:
            while True:
                rnd += 1
                one_round(run, app, rnd)
                run.stop_trace_if_due()
                if run.window_over(t0):
                    break
        rounds = rnd - first_window_round + 1
        run.attempted = rounds
        run.failed = sum(1 for r, v in app.history.series("server/round_failed")
                         if r >= first_window_round and v)
        run.end_to_end["round_s"] = (run.window[1] - run.window[0]) / rounds
        for key in HISTORY_KEYS:
            run.samples[key] = [v for r, v in app.history.series(key)
                                if r >= first_window_round]
        print(json.dumps({"round_medians_s": {
            k: median(v) for k, v in run.samples.items() if v}}), flush=True)
        run.counters.update(rounds=rounds, clients_per_round=cfg.fl.n_clients_per_round)
        round_losses = [v for r, v in app.history.series("loss")
                        if r >= first_window_round]
    finally:
        close_app(app)
    del app
    gc.collect()

    want = reference_round(run, ref, dims, cfg, opt, "float32")
    limits = run.traffic["limits"]
    for name, value in gaps(ref, got, want).items():
        run.check(name, value, limits[name])
    run.check("pseudo_grad_norm", got["pseudo_grad_norm"],
              limits["pseudo_grad_norm_min"], at_least=True)
    run.check_loss_fell(got["loss"], round_losses, "rounds")
    run.check("failed_rounds", run.failed, 0)


def readings(run) -> dict:
    """For setting limits: round 1 by the program, by the reference and by
    the control, at the cell's own size, with no measured window."""
    ref, dims, cfg, opt, app = start(run)
    try:
        got = program_round(run, app, ref, dims)
    finally:
        close_app(app)
    del app
    gc.collect()
    want = reference_round(run, ref, dims, cfg, opt, "float32")
    control = reference_round(run, ref, dims, cfg, opt, run.traffic["control_matmul"])
    return {"program": gaps(ref, got, want), "control": gaps(ref, control, want)}
