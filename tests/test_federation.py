"""Federation-layer tests: end-to-end fed rounds (in-process driver),
sampling determinism, failure budget, checkpoint/resume determinism,
broadcast semantics. The multiprocess driver gets its own slower test.

Reference oracles (SURVEY.md §4): norm telemetry presence, deterministic
client sampling incl. resume fast-forward, TooManyFailuresError budget.
"""

import gc

import numpy as np
import pytest

from photon_tpu.checkpoint import FileStore, ServerCheckpointManager
from photon_tpu.config.schema import (
    Config,
    FLConfig,
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    PhotonConfig,
    SchedulerConfig,
    TrainConfig,
)
from photon_tpu.federation import (
    InProcessDriver,
    NodeAgent,
    ParamTransport,
    ServerApp,
    TooManyFailuresError,
)
from tests._helpers import is_readonly_view, shm_mappings


def make_cfg(tmp_path, **fl_kw) -> Config:
    fl = dict(
        n_total_clients=4, n_clients_per_round=2, n_rounds=3, local_steps=2,
        strategy_name="nesterov", server_learning_rate=1.0, server_momentum=0.0,
        eval_interval_rounds=0, sample_seed=99,
    )
    fl.update(fl_kw)
    cfg = Config(
        run_uuid="testrun",
        model=ModelConfig(
            d_model=32, n_layers=2, n_heads=2, max_seq_len=16, vocab_size=64,
            attn_impl="xla", compute_dtype="float32",
        ),
        mesh=MeshConfig(),
        optimizer=OptimizerConfig(name="adopt", lr=1e-3),
        scheduler=SchedulerConfig(t_warmup=2, t_max=1000),
        train=TrainConfig(global_batch_size=4, device_microbatch_size=4, eval_batches=2),
        fl=FLConfig(**fl),
        photon=PhotonConfig(save_path=str(tmp_path / "save"), checkpoint=False),
    )
    cfg.dataset.synthetic = True
    return cfg.validate()


def make_app(cfg, tmp_path, n_nodes=2, with_ckpt=False, mode="inline"):
    transport = ParamTransport(mode)

    def make_agent(node_id):
        return NodeAgent(cfg, node_id, lambda: ParamTransport(mode))

    driver = InProcessDriver(cfg, make_agent, n_nodes=n_nodes)
    ckpt = None
    if with_ckpt:
        ckpt = ServerCheckpointManager(FileStore(tmp_path / "ckpt"), cfg.run_uuid)
    return ServerApp(cfg, driver, transport, ckpt_mgr=ckpt)


def test_fed_rounds_end_to_end(tmp_path):
    cfg = make_cfg(tmp_path)
    app = make_app(cfg, tmp_path)
    history = app.run()
    # three rounds recorded with the reference KPI names — server-side AND
    # the client-side timing decomposition (BASELINE.md instrumentation row:
    # ``llm_client_functions.py:161-209``, ``node_manager_app.py:463-468``)
    for key in ("server/round_time", "server/fit_round_time", "server/broadcast_pre_time",
                "server/n_clients", "server/pseudo_grad_norm",
                "node_training_time_s", "client/fit_time", "client/fit_init_time",
                "client/fit_set_parameters_time"):
        assert len(history.series(key)) == 3, key
    assert app.server_steps_cumulative == 3 * cfg.fl.local_steps
    # client states merged for trained cids
    assert all(st["steps_cumulative"] > 0 for st in app.client_states.values())
    app.driver.shutdown()


@pytest.mark.slow
def test_training_actually_changes_params(tmp_path):
    cfg = make_cfg(tmp_path, n_rounds=2)
    app = make_app(cfg, tmp_path)
    before = [a.copy() for a in app.strategy.current_parameters]
    app.run(n_rounds=2)
    after = app.strategy.current_parameters
    assert any(not np.allclose(b, a) for b, a in zip(before, after))
    app.driver.shutdown()


@pytest.mark.slow
def test_sampling_deterministic(tmp_path):
    cfg = make_cfg(tmp_path)
    a = make_app(cfg, tmp_path)
    b = make_app(cfg, tmp_path)
    sa = [a._sample_clients() for _ in range(5)]
    sb = [b._sample_clients() for _ in range(5)]
    assert sa == sb
    assert len(set(map(tuple, sa))) > 1  # actually varies round to round
    a.driver.shutdown(); b.driver.shutdown()


@pytest.mark.slow
def test_failure_budget(tmp_path):
    cfg = make_cfg(tmp_path, accept_failures_cnt=0)
    app = make_app(cfg, tmp_path)

    # sabotage: all agents raise for cid 0 via a broken runtime fit
    for agent in app.driver._agents.values():
        orig_fit = agent.runtime.fit

        def fit(ins, cid, _orig=orig_fit):
            if cid == app._doomed:
                from photon_tpu.federation.messages import FitRes
                return FitRes(ins.server_round, cid, None, error="boom")
            return _orig(ins, cid)

        agent.runtime.fit = fit

    app._doomed = -1  # nobody fails
    app.broadcast_parameters(1)
    app.fit_round(1)

    # choose a cid guaranteed to be sampled next round: replay the PRNG
    import random as _r
    rng = _r.Random(cfg.fl.sample_seed)
    for _ in range(app._rounds_sampled + 1):
        next_cids = sorted(rng.sample(range(cfg.fl.n_total_clients), cfg.fl.n_clients_per_round))
    app._doomed = next_cids[0]
    app.broadcast_parameters(2)
    with pytest.raises(TooManyFailuresError):
        app.fit_round(2)
    app.driver.shutdown()


@pytest.mark.slow
def test_failed_cid_retries_once_then_counts(tmp_path):
    """A cid that fails once but succeeds on retry must not raise."""
    cfg = make_cfg(tmp_path, accept_failures_cnt=0, n_clients_per_round=2)
    app = make_app(cfg, tmp_path)
    calls = {"n": 0}
    agents = list(app.driver._agents.values())
    for agent in agents:
        orig_fit = agent.runtime.fit

        def fit(ins, cid, _orig=orig_fit):
            if calls["n"] == 0:
                calls["n"] += 1
                from photon_tpu.federation.messages import FitRes
                return FitRes(ins.server_round, cid, None, error="flaky")
            return _orig(ins, cid)

        agent.runtime.fit = fit
    app.broadcast_parameters(1)
    metrics = app.fit_round(1)
    assert metrics["server/n_clients"] == 2  # both cids aggregated despite one flake
    app.driver.shutdown()


@pytest.mark.slow
def test_eval_round(tmp_path):
    cfg = make_cfg(tmp_path, eval_interval_rounds=1, n_rounds=1)
    app = make_app(cfg, tmp_path)
    history = app.run()
    assert history.latest("server/eval_loss") is not None
    assert history.latest("server/eval_loss") > 0
    app.driver.shutdown()


@pytest.mark.slow
def test_checkpoint_resume_matches_uninterrupted(tmp_path):
    """Golden determinism oracle: run 4 rounds straight vs 2 + resume + 2.
    Parameters and the sampled-client sequence must match exactly.

    ``reset_optimizer`` keeps client optimizer state round-local (the
    non-reset path needs client checkpoints to survive a node restart);
    loader positions resume via the client-state sample counters."""
    cfg_a = make_cfg(tmp_path / "a", n_rounds=4, fit_config={"reset_optimizer": True})
    cfg_a.photon.checkpoint = True
    app_a = make_app(cfg_a, tmp_path / "a", with_ckpt=True)
    app_a.run()
    final_a = [a.copy() for a in app_a.strategy.current_parameters]
    app_a.driver.shutdown()

    cfg_b = make_cfg(tmp_path / "b", n_rounds=2, fit_config={"reset_optimizer": True})
    cfg_b.photon.checkpoint = True
    app_b = make_app(cfg_b, tmp_path / "b", with_ckpt=True)
    app_b.run()
    app_b.driver.shutdown()

    cfg_c = make_cfg(tmp_path / "b", n_rounds=4, fit_config={"reset_optimizer": True})
    cfg_c.photon.checkpoint = True
    cfg_c.photon.resume_round = -1
    app_c = make_app(cfg_c, tmp_path / "b", with_ckpt=True)
    assert app_c.try_resume() == 2
    assert app_c.start_round == 3
    app_c.cfg.photon.resume_round = None  # already resumed
    for rnd in range(3, 5):
        app_c.broadcast_parameters(rnd)
        m = app_c.fit_round(rnd)
        app_c.save_checkpoint(rnd)
        app_c.history.record(rnd, m)
    final_c = app_c.strategy.current_parameters
    app_c.driver.shutdown()

    for x, y in zip(final_a, final_c):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-7)


@pytest.mark.slow
def test_refresh_period_broadcast(tmp_path):
    cfg = make_cfg(tmp_path, n_rounds=3)
    cfg.photon.refresh_period = 2
    app = make_app(cfg, tmp_path)
    history = app.run()
    assert len(history.series("server/round_time")) == 3
    app.driver.shutdown()


# ---------------------------------------------------------------------------
# the shm plane hands its readers views of the segment (ISSUE 32)
# ---------------------------------------------------------------------------


def _photon_mappings() -> int:
    return len(shm_mappings())


def _shm_run(tmp_path, strategy: str, n_rounds: int) -> list[np.ndarray]:
    cfg = make_cfg(tmp_path, n_total_clients=2, n_clients_per_round=2,
                   n_rounds=n_rounds, strategy_name=strategy,
                   server_learning_rate=1.0 if strategy == "fedavg" else 0.01)
    app = make_app(cfg, tmp_path, n_nodes=1, mode="shm")
    try:
        app.run()
        return [np.array(a) for a in app.strategy.current_parameters]
    finally:
        app.free_transport()
        app.driver.shutdown()


@pytest.mark.parametrize("strategy", ["fedavg", "fedadam"])
def test_shm_views_give_the_bytes_deep_copies_gave(tmp_path, monkeypatch, strategy):
    """Two rounds with every reader on views of the mapped segments against
    the same run with ``get`` deep-copying every array, as it did before:
    the same float32 values reach the device, the fold and the norms in the
    same order, so the global parameters are the same bytes."""
    viewed = _shm_run(tmp_path / "views", strategy, n_rounds=2)

    plain_get = ParamTransport.get
    seen = []

    def copying_get(self, ptr, **kw):
        meta, arrays = plain_get(self, ptr, **kw)
        seen.append(all(map(is_readonly_view, arrays)))
        return meta, [a.copy() for a in arrays]

    monkeypatch.setattr(ParamTransport, "get", copying_get)
    copied = _shm_run(tmp_path / "copies", strategy, n_rounds=2)
    # a round: the node's read of the broadcast, the server's of each upload
    assert len(seen) == 2 * 3 and all(seen), "every get was a read-only view"
    assert len(viewed) == len(copied)
    for a, b in zip(viewed, copied):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_ten_shm_rounds_leave_no_mapping_behind(tmp_path):
    """The leak guard: a view holds its segment's mapping, so views that
    pile up are memory that piles up. Inside the run at most the live
    broadcast stays mapped between rounds (on the CPU backend ``device_put``
    may alias an aligned view, so this also trains on, and donates, buffers
    whose segment was freed); after it, nothing."""
    gc.collect()
    before = _photon_mappings()  # what earlier tests of this process still hold
    cfg = make_cfg(tmp_path, n_total_clients=2, n_clients_per_round=2, n_rounds=10)
    app = make_app(cfg, tmp_path, n_nodes=1, mode="shm")
    try:
        for rnd in range(1, 11):
            app.run_round(rnd)
            gc.collect()
            # the node's hold on this round's broadcast, and nothing older
            assert 1 <= _photon_mappings() - before <= 2, f"round {rnd}"
    finally:
        app.free_transport()
        app.driver.shutdown()
    assert app.server_steps_cumulative == 10 * cfg.fl.local_steps
    del app
    gc.collect()
    assert _photon_mappings() <= before
