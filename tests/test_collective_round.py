"""Collective federated rounds match the driver topology exactly.

The marquee-path integration test (SURVEY §7 stage 6): two
``jax.distributed`` processes (2 clients each) run TWO full federated
rounds entirely over XLA collectives (``CollectiveFedRunner``: local
ClientRuntime fits → client-axis psum average → replica strategy update),
and the resulting global parameters must match an ``InProcessDriver``
ServerApp run of the same config to float tolerance — proving the DCN
plane is a drop-in replacement for the pointer plane, not a lookalike.
"""

import json
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from photon_tpu.config.schema import Config

CHILD = r"""
import json, sys
import jax

pid = int(sys.argv[1]); port = sys.argv[2]; cfg_path = sys.argv[3]; out_path = sys.argv[4]
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)
jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=2, process_id=pid)

import numpy as np
from photon_tpu.config.schema import Config
from photon_tpu.federation.collective_round import CollectiveFedRunner, partition_cids

cfg = Config.from_yaml(cfg_path)
cfg.photon.save_path = cfg.photon.save_path + f"/proc{pid}"
cfg.validate()
cids = partition_cids(cfg.fl.n_total_clients, 2, pid)
runner = CollectiveFedRunner(cfg, cids)
history = runner.run()
np.savez(out_path, *runner.strategy.current_parameters)
with open(out_path + ".metrics.json", "w") as f:
    json.dump({
        "steps": runner.server_steps_cumulative,
        "eval_loss": history.latest("server/eval_loss"),
        "pseudo_grad_norm": history.latest("server/pseudo_grad_norm"),
    }, f)
print(json.dumps({"pid": pid, "cids": cids}), flush=True)
"""


def _cfg(tmp_path, strategy="fedavg", momenta=False) -> Config:
    cfg = Config()
    cfg.model.d_model = 32
    cfg.model.n_layers = 2
    cfg.model.n_heads = 2
    cfg.model.max_seq_len = 16
    cfg.model.vocab_size = 64
    cfg.model.attn_impl = "xla"
    cfg.model.compute_dtype = "float32"
    cfg.train.global_batch_size = 4
    cfg.train.device_microbatch_size = 4
    cfg.fl.n_total_clients = 4
    cfg.fl.n_clients_per_round = 4  # collective mode = full participation
    cfg.fl.n_rounds = 2
    cfg.fl.local_steps = 2
    cfg.fl.eval_interval_rounds = 2
    cfg.fl.strategy_name = strategy
    cfg.fl.server_learning_rate = 1.0 if strategy == "fedavg" else 0.01
    cfg.fl.aggregate_momenta = momenta
    if strategy == "fedadam":
        # adaptive updates divide by sqrt(v)+tau: with tau ~ 0 and v ~ 0 in
        # early rounds, fp32 reduction-order noise between the psum and the
        # host streaming average flips near-zero momenta signs and the
        # topologies legitimately diverge elementwise. A non-degenerate tau
        # keeps the comparison about the momenta PLUMBING, which is what
        # this test asserts.
        cfg.fl.server_tau = 1e-3
    cfg.dataset.synthetic = True
    cfg.photon.checkpoint = False
    cfg.photon.comm_stack.collective = True
    cfg.photon.comm_stack.shm = False
    cfg.run_uuid = "collective-round"
    return cfg


@pytest.mark.slow
@pytest.mark.parametrize(
    "strategy,momenta",
    [("fedavg", False), ("fedadam", True)],
    ids=["fedavg", "fedadam-momenta"],
)
def test_collective_rounds_match_driver_topology(tmp_path, strategy, momenta):
    from tests._helpers import free_port, subprocess_env

    # ---- oracle: the same config through the InProcessDriver ServerApp ----
    from photon_tpu.federated import build_app

    oracle_cfg = _cfg(tmp_path, strategy, momenta)
    oracle_cfg.photon.comm_stack.collective = False
    oracle_cfg.photon.comm_stack.shm = True
    oracle_cfg.photon.save_path = str(tmp_path / "oracle")
    oracle_cfg.validate()
    app = build_app(oracle_cfg, n_nodes=1)
    oracle_hist = app.run()
    oracle_params = app.strategy.current_parameters
    oracle_eval = oracle_hist.latest("server/eval_loss")
    app.driver.shutdown()

    # ---- collective: two real processes, two clients each ----------------
    cfg = _cfg(tmp_path, strategy, momenta)
    cfg.photon.save_path = str(tmp_path / "collective")
    cfg.validate()
    cfg_path = str(tmp_path / "collective.yaml")
    cfg.to_yaml(cfg_path)

    port = free_port()
    script = tmp_path / "child.py"
    script.write_text(CHILD)
    outs = [tmp_path / f"params_{pid}.npz" for pid in range(2)]
    logs = [tmp_path / f"child_{pid}.log" for pid in range(2)]
    procs = []
    for pid in range(2):
        with logs[pid].open("w") as logf:
            procs.append(
                subprocess.Popen(
                    [sys.executable, str(script), str(pid), str(port),
                     cfg_path, str(outs[pid])],
                    env=subprocess_env(), stdout=logf, stderr=subprocess.STDOUT,
                    text=True,
                )
            )
    for pid, p in enumerate(procs):
        try:
            p.wait(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("collective round processes timed out")
        assert p.returncode == 0, logs[pid].read_text()[-3000:]

    # every controller must hold params equal to the oracle's up to fp32
    # reduction-order noise (psum tree-reduce vs the host streaming rescale
    # compound through the rounds: observed max |Δ| ≈ 1e-5 after 2 rounds)
    for out in outs:
        with np.load(out) as z:
            got = [z[k] for k in z.files]
        assert len(got) == len(oracle_params)
        for g, o in zip(got, oracle_params):
            np.testing.assert_allclose(g, o, rtol=1e-3, atol=5e-5)
    # ...and bitwise-identical to EACH OTHER (same psum on every controller)
    with np.load(outs[0]) as z0, np.load(outs[1]) as z1:
        for k in z0.files:
            np.testing.assert_array_equal(z0[k], z1[k])
    # fed eval over the collective matches the driver topology's eval
    for out in outs:
        m = json.loads(pathlib.Path(str(out) + ".metrics.json").read_text())
        assert m["eval_loss"] is not None and oracle_eval is not None
        np.testing.assert_allclose(m["eval_loss"], oracle_eval, rtol=1e-3)


# ---------------------------------------------------------------------------
# ISSUE 7: device-resident aggregation plane e2e (single-controller,
# in-process — the multi-process parity e2e above stays the slow oracle)
# ---------------------------------------------------------------------------


def _plane_cfg(tmp_path, quantization, n_rounds=3):
    cfg = _cfg(tmp_path, strategy="fedadam", momenta=False)
    cfg.fl.n_total_clients = 2
    cfg.fl.n_clients_per_round = 2
    cfg.fl.n_rounds = n_rounds
    cfg.fl.eval_interval_rounds = 0  # retrace discipline is about run_round
    cfg.photon.comm_stack.collective_replica = 2
    cfg.photon.comm_stack.collective_quantization = quantization
    cfg.photon.comm_stack.collective_q8_block = 64
    cfg.photon.comm_stack.collective_device_optimizer = True
    cfg.photon.save_path = str(tmp_path / f"plane-{quantization}")
    cfg.validate()
    return cfg


@pytest.mark.parametrize("quantization", ["off", "q8"])
def test_collective_round_e2e_retrace_free_from_round_2(tmp_path, quantization):
    """Acceptance: the full collective-round e2e (real ClientRuntime fits →
    hierarchical exchange → fused device FedAdam) is compile-free from
    round 2 under the PR 6 RetraceSentinel for both quantization policies.
    Also pins the new per-round KPIs and the device-path param flow."""
    from photon_tpu.analysis.runtime import (
        install_retrace_sentinel,
        uninstall_retrace_sentinel,
    )
    from photon_tpu.federation.collective_round import CollectiveFedRunner
    from photon_tpu.parallel.collective_agg import modeled_cross_slice_bytes

    cfg = _plane_cfg(tmp_path, quantization)
    sentinel = install_retrace_sentinel()
    try:
        runner = CollectiveFedRunner(cfg, [0, 1])
        assert runner.device_plane is not None
        sentinel.mark_steady_after(1)  # round 1 = warmup (fit + program compiles)
        for rnd in range(1, cfg.fl.n_rounds + 1):
            metrics = runner.run_round(rnd)
        sentinel.check("collective/e2e")
    finally:
        uninstall_retrace_sentinel()

    # KPI surface: hierarchy stage timings + modeled DCN bytes every round
    hist = runner.history
    for name in (
        "server/collective_agg_time",
        "server/collective_stack_time",
        "server/collective_exchange_time",
        "server/collective_update_time",
        "server/collective_wire_bytes",
    ):
        assert len(hist.series(name)) == cfg.fl.n_rounds, name
    sizes = [int(np.prod(p.shape)) for p in runner.strategy.current_parameters]
    expect = modeled_cross_slice_bytes(
        sizes, 2, replica=2, quantization=quantization, block=64
    )
    assert metrics["server/collective_wire_bytes"] == float(expect)
    # the device plane's params ARE the strategy's params (broadcast mirror)
    for a, b in zip(runner.strategy.current_parameters,
                    runner.device_plane.params_host()):
        np.testing.assert_array_equal(a, b)
    # adaptive bias-correction counter advanced once per round and is
    # checkpointable through the existing host path
    assert runner.device_plane.t == cfg.fl.n_rounds
    assert "_t" in runner.state_for_checkpoint()


def test_collective_round_device_path_matches_host_path(tmp_path):
    """The fused device-optimizer path and the host-strategy path must
    produce the same parameters for the same config (fp32 tolerance —
    psum average is identical, only the update arithmetic moves)."""
    from photon_tpu.federation.collective_round import CollectiveFedRunner

    cfg_dev = _plane_cfg(tmp_path / "dev", "off", n_rounds=2)
    runner_dev = CollectiveFedRunner(cfg_dev, [0, 1])
    runner_dev.run(2)

    cfg_host = _plane_cfg(tmp_path / "host", "off", n_rounds=2)
    cfg_host.photon.comm_stack.collective_device_optimizer = False
    cfg_host.validate()
    runner_host = CollectiveFedRunner(cfg_host, [0, 1])
    runner_host.run(2)

    assert runner_dev.device_plane is not None
    assert runner_host.device_plane is None
    for a, b in zip(runner_dev.strategy.current_parameters,
                    runner_host.strategy.current_parameters):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("device_opt", [True, False], ids=["device-opt", "host-opt"])
def test_collective_round_q8_momenta_stays_finite(tmp_path, device_opt):
    """Regression: q8 + aggregate_momenta went NaN at round 3 — quantization
    noise turns the exactly-zero pseudo-gradient of idle second-moment
    elements tiny-nonzero, the sign-like adaptive server step then kicks
    them negative, and the next fit sqrt()s them. Both optimizer paths now
    clamp the m2 rows >= 0 on the q8 policy (collective_round._nonneg_rows)."""
    from photon_tpu.federation.collective_round import CollectiveFedRunner
    from photon_tpu.train.param_ops import M2_PREFIX

    cfg = _cfg(tmp_path, strategy="fedadam", momenta=True)
    cfg.fl.n_rounds = 3  # the unclamped run NaNs exactly here
    cfg.fl.eval_interval_rounds = 0
    cfg.photon.comm_stack.collective_replica = 2
    cfg.photon.comm_stack.collective_quantization = "q8"
    cfg.photon.comm_stack.collective_device_optimizer = device_opt
    cfg.photon.save_path = str(tmp_path / "q8-momenta")
    cfg.validate()

    runner = CollectiveFedRunner(cfg, list(range(4)))
    assert runner._nonneg_rows  # momenta payload → m2 rows identified
    for rnd in range(1, cfg.fl.n_rounds + 1):
        metrics = runner.run_round(rnd)
        assert np.isfinite(metrics["server/pseudo_grad_norm"]), rnd
    for name, p in zip(runner.meta.names, runner.strategy.current_parameters):
        assert np.all(np.isfinite(p)), name
        if name.startswith(M2_PREFIX):
            assert float(p.min()) >= 0.0, name


def test_collective_runner_resume_via_load_server_state(tmp_path):
    """Runner-level resume: state_for_checkpoint + control_state_for_checkpoint
    → load_server_state keeps the fused FedAdam run bit-identical with the
    uninterrupted run. As in the driver topology's golden resume test,
    ``reset_optimizer`` keeps client optimizer state round-local; loader
    positions resume via the checkpointed client-state sample counters."""
    from photon_tpu.federation.collective_round import CollectiveFedRunner

    def resume_cfg(name):
        cfg = _plane_cfg(tmp_path / name, "off", n_rounds=3)
        cfg.fl.fit_config = {"reset_optimizer": True}
        return cfg

    cont = CollectiveFedRunner(resume_cfg("cont"), [0, 1])
    for rnd in range(1, 4):
        cont.run_round(rnd)

    part = CollectiveFedRunner(resume_cfg("parta"), [0, 1])
    for rnd in range(1, 3):
        part.run_round(rnd)
    params = [p.copy() for p in part.strategy.current_parameters]
    state = {k: [a.copy() for a in v] for k, v in part.state_for_checkpoint().items()}
    control = part.control_state_for_checkpoint()

    resumed = CollectiveFedRunner(resume_cfg("partb"), [0, 1])
    resumed.load_server_state(params, state, control)
    assert resumed.device_plane.t == 2
    assert resumed.server_steps_cumulative == part.server_steps_cumulative
    resumed.run_round(3)

    for a, b in zip(cont.strategy.current_parameters,
                    resumed.strategy.current_parameters):
        np.testing.assert_array_equal(a, b)


def test_collective_round_reads_fetched_views_after_the_free(tmp_path):
    """``run_round`` gets each client's upload, frees it at once and folds
    later. On the shm plane the arrays are then read-only views of a segment
    whose name is gone: they must still hold the client's values, so the
    round comes out as on the inline plane, bit for bit."""
    from photon_tpu.federation.collective_round import CollectiveFedRunner
    from photon_tpu.federation.transport import ParamTransport
    from tests._helpers import is_readonly_view, shm_mappings

    cfg = _plane_cfg(tmp_path / "inline", "off", n_rounds=2)
    inline = CollectiveFedRunner(cfg, [0, 1])
    inline.run(2)

    cfg = _plane_cfg(tmp_path / "shm", "off", n_rounds=2)
    runner = CollectiveFedRunner(cfg, [0, 1])
    runner.transport = runner.runtime.transport = ParamTransport("shm")
    aggregate, folded = runner._aggregate_elastic, []

    def spy(server_round, landed):
        folded.extend(a for arrays, _ in landed.values() for a in arrays)
        return aggregate(server_round, landed)

    runner._aggregate_elastic = spy
    try:
        runner.run(2)
    finally:
        runner.transport.cleanup()
    assert folded and all(map(is_readonly_view, folded))
    # only what the test itself still holds: the uploads, unlinked long ago
    held = shm_mappings()
    assert held and all(line.endswith("(deleted)") for line in held)
    for a, b in zip(inline.strategy.current_parameters,
                    runner.strategy.current_parameters):
        assert a.tobytes() == b.tobytes()
