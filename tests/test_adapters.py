"""Per-cohort LoRA personalization — train plane (ISSUE 13).

Contracts pinned here:

1. config validation of the ``photon.adapters`` block (clear errors for
   bad rank/alpha/targets, overlapping cohorts, MoE, momenta);
2. the LoRA payload algebra (split/merge roundtrips in canonical codec
   order; a fresh adapter is exactly the identity);
3. the FUSED multi-cohort reduction matches a per-cohort host
   ``aggregate_inplace`` oracle at quantization off (fp32 reduction-order
   tolerance — the same pin as the PR 7 plane) and stays within the
   documented per-element blockwise bound at q8;
4. federated adapter rounds: base frozen bit-exact, per-cohort updates
   match the host oracle, steady-state rounds compile-free;
5. the chaos e2e: one cohort's clients all dying degrades THAT cohort
   only — adapter frozen, ``adapter/cohort_degraded`` + ``alert/*``
   events emitted, every other cohort updates;
6. checkpoint → resume → (test_adapter_serve.py picks up hot-swap).
"""

import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402

from photon_tpu import telemetry  # noqa: E402
from photon_tpu.config.schema import Config, TelemetryConfig  # noqa: E402

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_planes():
    yield
    telemetry.uninstall()


def _adapter_cfg(tmp_path, strategy="fedavg", n_clients=4,
                 cohorts=None, quantization="off", local_steps=2) -> Config:
    cfg = Config()
    cfg.model.d_model = 32
    cfg.model.n_layers = 2
    cfg.model.n_heads = 2
    cfg.model.max_seq_len = 16
    cfg.model.vocab_size = 64
    cfg.model.attn_impl = "xla"
    cfg.model.compute_dtype = "float32"
    cfg.train.global_batch_size = 2
    cfg.train.device_microbatch_size = 2
    cfg.fl.n_total_clients = n_clients
    cfg.fl.n_clients_per_round = n_clients
    cfg.fl.n_rounds = 2
    cfg.fl.local_steps = local_steps
    cfg.fl.strategy_name = strategy
    cfg.fl.server_learning_rate = 1.0 if strategy == "fedavg" else 0.01
    if strategy == "fedadam":
        cfg.fl.server_tau = 1e-3
    cfg.dataset.synthetic = True
    cfg.photon.checkpoint = False
    cfg.photon.comm_stack.collective = True
    cfg.photon.comm_stack.shm = False
    cfg.photon.comm_stack.collective_quantization = quantization
    cfg.photon.comm_stack.collective_q8_block = 64
    cfg.photon.adapters.enabled = True
    cfg.photon.adapters.rank = 4
    cfg.photon.adapters.cohorts = cohorts if cohorts is not None else {
        "alpha": [0, 1], "beta": [2, 3],
    }
    cfg.photon.save_path = str(tmp_path / "run")
    cfg.run_uuid = "adapters-e2e"
    cfg.validate()
    return cfg


# ---------------------------------------------------------------------------
# 1. config validation (ISSUE 13 satellite)
# ---------------------------------------------------------------------------


def _base_cfg() -> Config:
    cfg = Config()
    cfg.photon.adapters.enabled = True
    cfg.photon.adapters.cohorts = {"a": [0]}
    return cfg


def test_adapters_config_rejects_bad_rank_alpha_targets():
    cfg = _base_cfg()
    cfg.photon.adapters.rank = 0
    with pytest.raises(ValueError, match="rank must be >= 1"):
        cfg.validate()
    cfg = _base_cfg()
    cfg.photon.adapters.alpha = 0.0
    with pytest.raises(ValueError, match="alpha must be > 0"):
        cfg.validate()
    cfg = _base_cfg()
    cfg.photon.adapters.targets = []
    with pytest.raises(ValueError, match="targets is empty"):
        cfg.validate()
    cfg = _base_cfg()
    cfg.photon.adapters.targets = ["wqkv", "router"]
    with pytest.raises(ValueError, match=r"\['router'\] are not adaptable"):
        cfg.validate()
    cfg = _base_cfg()
    cfg.photon.adapters.pool_size = 0
    with pytest.raises(ValueError, match="pool_size must be >= 1"):
        cfg.validate()


def test_adapters_config_rejects_overlapping_cohorts_and_bad_cids():
    cfg = _base_cfg()
    cfg.photon.adapters.cohorts = {"a": [0, 1], "b": [1]}
    with pytest.raises(ValueError, match="appears in cohorts 'a' AND 'b'"):
        cfg.validate()
    cfg = _base_cfg()
    cfg.photon.adapters.cohorts = {"a": [0, -1]}
    with pytest.raises(ValueError, match="bad client id -1"):
        cfg.validate()
    cfg = _base_cfg()
    cfg.photon.adapters.cohorts = {"a": 3}
    with pytest.raises(ValueError, match="must be a list"):
        cfg.validate()
    cfg = _base_cfg()
    cfg.photon.adapters.cohorts = {}
    with pytest.raises(ValueError, match="non-empty cohorts map"):
        cfg.validate()


def test_adapters_config_rejects_moe_momenta_device_optimizer():
    # MoE: batch-global expert capacity breaks per-slot adapter purity —
    # the same argument PR 10 used for prefix-cache ineligibility
    cfg = _base_cfg()
    cfg.model.mlp = "moe"
    cfg.model.moe_num_experts = 2
    with pytest.raises(ValueError, match="moe"):
        cfg.validate()
    cfg = _base_cfg()
    cfg.fl.aggregate_momenta = True
    with pytest.raises(ValueError, match="aggregate_momenta"):
        cfg.validate()
    cfg = _base_cfg()
    cfg.photon.comm_stack.collective = True
    cfg.photon.comm_stack.collective_device_optimizer = True
    with pytest.raises(ValueError, match="device_optimizer"):
        cfg.validate()


# ---------------------------------------------------------------------------
# 2. LoRA payload algebra
# ---------------------------------------------------------------------------


def _tiny_model_payload(llama=False):
    from photon_tpu.codec import params_to_ndarrays
    from photon_tpu.models.mpt import init_params

    cfg = Config()
    cfg.model.d_model = 32
    cfg.model.n_layers = 2
    cfg.model.n_heads = 4
    cfg.model.vocab_size = 64
    cfg.model.attn_impl = "xla"
    cfg.model.compute_dtype = "float32"
    if llama:
        cfg.model.rope = True
        cfg.model.learned_pos_emb = False
        cfg.model.n_kv_heads = 2
        cfg.model.norm = "rmsnorm"
        cfg.model.mlp = "swiglu"
    cfg.validate()
    return cfg, params_to_ndarrays(init_params(cfg.model, seed=0))


@pytest.mark.parametrize("llama", [False, True])
def test_spec_resolves_model_family_and_roundtrips(llama):
    from photon_tpu.adapters.lora import (
        adapter_metadata, init_adapter_arrays, merge_payload, spec_from_base,
        split_adapter,
    )

    cfg, (meta, arrays) = _tiny_model_payload(llama)
    spec = spec_from_base(
        meta, 4, 16.0, ("wqkv", "q_proj", "k_proj", "v_proj", "out_proj")
    )
    modules = set(spec.modules())
    # MHA resolves the fused wqkv; GQA the split projections — from the
    # actual payload, not the target list
    if llama:
        assert {"q_proj", "k_proj", "v_proj", "out_proj"} <= modules
        assert "wqkv" not in modules
    else:
        assert "wqkv" in modules and "q_proj" not in modules
    am, aa = init_adapter_arrays(spec, seed=3)
    assert am.names == adapter_metadata(spec).names
    mm, ma = merge_payload(meta, arrays, am, aa)
    bm, ba, am2, aa2 = split_adapter(mm, ma)
    assert bm.names == meta.names and am2.names == am.names
    for x, y in zip(ba, arrays):
        np.testing.assert_array_equal(x, y)
    # the merged order IS the lora-enabled model's canonical order
    from photon_tpu.models.mpt import init_params as ip

    cfg.model.lora_rank = 4
    cfg.model.lora_targets = ("wqkv", "q_proj", "k_proj", "v_proj", "out_proj")
    from photon_tpu.codec import params_to_ndarrays

    full_meta, _ = params_to_ndarrays(ip(cfg.model, seed=0))
    assert mm.names == full_meta.names
    assert mm.shapes == full_meta.shapes


def test_adapter_exchange_modeled_bytes_50x_below_full_model():
    """ISSUE 13's count: on the 125M recipe at the default targets and
    rank 8, one adapter exchange is priced at least 50x below one
    full-model exchange for the same clients, by the model the collective
    plane reports as ``server/adapter_wire_bytes``."""
    from photon_tpu.adapters.lora import adapter_metadata, spec_from_base
    from photon_tpu.codec import ParamsMetadata, flatten_params
    from photon_tpu.config.schema import AdaptersConfig, ModelConfig
    from photon_tpu.models.mpt import init_params
    from photon_tpu.parallel.collective_agg import modeled_cross_slice_bytes

    names, leaves = flatten_params(
        jax.eval_shape(lambda: init_params(ModelConfig(), seed=0)))
    base = ParamsMetadata(
        names=tuple(names),
        shapes=tuple(tuple(int(d) for d in leaf.shape) for leaf in leaves),
        dtypes=("float32",) * len(names),
    )
    acfg = AdaptersConfig()
    spec = spec_from_base(base, 8, acfg.alpha, tuple(acfg.targets))

    def sizes(meta):
        return [int(np.prod(s, dtype=np.int64)) for s in meta.shapes]

    full = modeled_cross_slice_bytes(sizes(base), 8)
    adapter = modeled_cross_slice_bytes(sizes(adapter_metadata(spec)), 8)
    assert sum(sizes(adapter_metadata(spec))) == spec.n_params
    assert full / adapter >= 50.0, full / adapter


def test_fresh_adapter_is_identity_and_merge_math():
    from photon_tpu.adapters.lora import (
        init_adapter_arrays, merge_adapter_into_base, spec_from_base,
    )

    _, (meta, arrays) = _tiny_model_payload()
    spec = spec_from_base(meta, 4, 8.0, ("wqkv",))
    am, aa = init_adapter_arrays(spec, seed=1)
    merged = merge_adapter_into_base(meta, arrays, spec, aa)
    for x, y in zip(merged, arrays):  # B = 0 → delta exactly zero
        np.testing.assert_array_equal(x, y)
    # nonzero B: merged kernel = W + (alpha/r)·A@B, others untouched
    rng = np.random.default_rng(2)
    aa = [a if n.endswith("_lora_a")
          else rng.normal(0, 0.1, a.shape).astype(np.float32)
          for n, a in zip(am.names, aa)]
    merged = merge_adapter_into_base(meta, arrays, spec, aa)
    ki = meta.names.index("blocks/block/wqkv/kernel")
    a_i = am.names.index("blocks/block/wqkv_lora_a")
    b_i = am.names.index("blocks/block/wqkv_lora_b")
    want = arrays[ki] + spec.scale * np.einsum("lir,lro->lio", aa[a_i], aa[b_i])
    np.testing.assert_allclose(merged[ki], want, rtol=1e-6)
    for i, (x, y) in enumerate(zip(merged, arrays)):
        if i != ki:
            np.testing.assert_array_equal(x, y)


def test_spec_rejects_rankless_and_unmatched():
    from photon_tpu.adapters.lora import spec_from_base

    _, (meta, _) = _tiny_model_payload()
    with pytest.raises(ValueError, match="rank"):
        spec_from_base(meta, 0, 16.0, ("wqkv",))
    with pytest.raises(ValueError, match="no base parameter matches"):
        spec_from_base(meta, 4, 16.0, ("q_proj",))  # MHA has no q_proj


# ---------------------------------------------------------------------------
# 3. fused multi-cohort reduction vs the per-cohort host oracle
# ---------------------------------------------------------------------------


def _grouped_fixture(n_clients=4, seed=0, shapes=((6, 4), (9,))):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from photon_tpu.parallel.collective_agg import (
        CLIENT_AXIS, make_hierarchical_mesh,
    )

    rng = np.random.default_rng(seed)
    mesh = make_hierarchical_mesh(n_clients, 1)
    clients = [[rng.normal(size=s).astype(np.float32) for s in shapes]
               for _ in range(n_clients)]
    ns = (rng.integers(1, 30, n_clients)).astype(np.int32)
    sharding = NamedSharding(mesh, P(CLIENT_AXIS))
    stacked = [jax.device_put(np.stack([c[i] for c in clients]), sharding)
               for i in range(len(shapes))]
    return mesh, clients, ns, sharding, stacked


def test_grouped_fused_matches_per_cohort_aggregate_inplace_off():
    """The satellite pin: ONE fused program == K sequential host folds,
    cohort by cohort, at fp32 reduction-order tolerance (the PR 7
    discipline); Σn per cohort exact; a cohort-less client contributes
    nowhere; an empty cohort totals zero."""
    from photon_tpu.parallel.collective_agg import grouped_weighted_average
    from photon_tpu.strategy.aggregation import aggregate_inplace

    mesh, clients, ns, sharding, stacked = _grouped_fixture()
    # cohorts: a = {0, 1}, b = {3}; client 2 in NO cohort; c EMPTY
    onehot = np.zeros((4, 3), np.float32)
    onehot[0, 0] = onehot[1, 0] = 1.0
    onehot[3, 1] = 1.0
    avgs, totals = grouped_weighted_average(
        stacked, jax.device_put(jnp.asarray(ns), sharding),
        jax.device_put(jnp.asarray(onehot), sharding), mesh,
    )
    totals = np.asarray(totals)
    assert totals[0] == ns[0] + ns[1] and totals[1] == ns[3]
    assert totals[2] == 0.0  # the empty cohort
    for k, members in ((0, [0, 1]), (1, [3])):
        host, n_host = aggregate_inplace(
            (clients[m], int(ns[m])) for m in members
        )
        assert n_host == int(totals[k])
        for li in range(2):
            np.testing.assert_allclose(
                np.asarray(avgs[li])[k], host[li], rtol=1e-5, atol=1e-6
            )
    # the empty cohort's slot is exact zeros (callers must skip it)
    for li in range(2):
        np.testing.assert_array_equal(
            np.asarray(avgs[li])[2], np.zeros_like(np.asarray(avgs[li])[2])
        )


def test_grouped_q8_error_within_documented_blockwise_bound():
    """Pinned epsilon at q8: per element, the fused grouped average errs
    from the ``off`` average by at most Σ_clients scale_c/2, with scales
    reconstructed by the byte-parity-pinned HOST quantizer over the SAME
    per-client contribution vectors the collective quantizes (each
    client's flattened ``[K, ...]`` cohort-weighted stack)."""
    from photon_tpu.compression.quantize import quantize_q8
    from photon_tpu.parallel.collective_agg import grouped_weighted_average

    block = 16
    mesh, clients, ns, sharding, stacked = _grouped_fixture(seed=3)
    onehot = np.zeros((4, 2), np.float32)
    onehot[0, 0] = onehot[1, 0] = 1.0
    onehot[2, 1] = onehot[3, 1] = 1.0
    ns_dev = jax.device_put(jnp.asarray(ns), sharding)
    oh_dev = jax.device_put(jnp.asarray(onehot), sharding)
    off, _ = grouped_weighted_average(stacked, ns_dev, oh_dev, mesh)
    q8, _ = grouped_weighted_average(
        stacked, ns_dev, oh_dev, mesh, quantization="q8", block=block
    )
    totals = onehot.T @ ns.astype(np.float64)  # [K]
    for li, shape in enumerate(((6, 4), (9,))):
        n = int(np.prod((2,) + shape))  # the [K, ...] contrib element count
        chunk = -(-n // block) * block
        bound = np.zeros(chunk, np.float64)
        for c in range(4):
            w = onehot[c] * (ns[c] / np.maximum(totals, 1.0))  # [K]
            contrib = (w.reshape((2,) + (1,) * len(shape)).astype(np.float32)
                       * clients[c][li][None].astype(np.float32))
            flat = np.zeros(chunk, np.float32)
            flat[:n] = contrib.reshape(-1)
            _, scales = quantize_q8(flat, block=block)
            bound += np.repeat(scales.astype(np.float64), block) / 2.0
        err = np.abs(np.asarray(q8[li]) - np.asarray(off[li])).reshape(-1)
        assert (err <= bound[:n] + 1e-6).all(), (
            f"leaf {li}: max err {err.max()} exceeds bound"
        )
        assert err.max() > 0  # q8 genuinely differs — the bound does work


def test_grouped_program_cached_no_steady_state_recompile():
    from photon_tpu.analysis import runtime as lint_rt
    from photon_tpu.parallel.collective_agg import grouped_weighted_average

    mesh, clients, ns, sharding, stacked = _grouped_fixture(seed=5)
    onehot = np.zeros((4, 2), np.float32)
    onehot[:2, 0] = 1.0
    onehot[2:, 1] = 1.0
    ns_dev = jax.device_put(jnp.asarray(ns), sharding)
    oh_dev = jax.device_put(jnp.asarray(onehot), sharding)
    grouped_weighted_average(stacked, ns_dev, oh_dev, mesh)  # warm
    sentinel = lint_rt.install_retrace_sentinel()
    try:
        sentinel.mark_steady()
        for _ in range(3):
            avgs, totals = grouped_weighted_average(
                stacked, ns_dev, oh_dev, mesh
            )
            jax.block_until_ready(totals)
        sentinel.check("adapters/grouped-steady")
    finally:
        lint_rt.uninstall_retrace_sentinel()


# ---------------------------------------------------------------------------
# 4. federated adapter rounds (single controller, 8 emulated CPU devices)
# ---------------------------------------------------------------------------


def test_adapter_rounds_base_frozen_cohorts_diverge_and_steady(tmp_path):
    """Two personalization rounds: the federated base never moves (bit
    exact), each cohort's adapter moves and the cohorts diverge from each
    other, wire metrics model the ADAPTER payload (not the model), and
    round 2 runs compile-free under the retrace sentinel."""
    from photon_tpu.analysis import runtime as lint_rt
    from photon_tpu.federation.collective_round import CollectiveFedRunner
    from photon_tpu.parallel.collective_agg import modeled_cross_slice_bytes

    cfg = _adapter_cfg(tmp_path)
    sentinel = lint_rt.install_retrace_sentinel()
    try:
        runner = CollectiveFedRunner(cfg, [0, 1, 2, 3])
        plane = runner.adapter_plane
        assert plane is not None and runner.device_plane is None
        base0 = [a.copy() for a in plane.base_arrays]
        a0 = [a.copy() for a in plane.strategies.params("alpha")]
        sentinel.mark_steady_after(1)
        m1 = runner.run_round(1)
        m2 = runner.run_round(2)
        sentinel.check("adapters/rounds")
    finally:
        lint_rt.uninstall_retrace_sentinel()
    for before, after in zip(base0, plane.base_arrays):
        np.testing.assert_array_equal(before, after)  # frozen base
    a2 = plane.strategies.params("alpha")
    b2 = plane.strategies.params("beta")
    assert any(not np.array_equal(x, y) for x, y in zip(a0, a2))
    assert any(not np.array_equal(x, y) for x, y in zip(a2, b2))
    for m in (m1, m2):
        assert m["server/adapter_cohorts"] == 2.0
        assert m["server/adapter_cohorts_degraded"] == 0.0
        assert m["server/collective_stragglers"] == 0.0
        want = float(modeled_cross_slice_bytes(plane.adapter_sizes(), 4))
        assert m["server/adapter_wire_bytes"] == want
        assert m["server/collective_wire_bytes"] == want
    assert runner.aggregation_paths == {1: "collective", 2: "collective"}


def test_adapter_round_fused_matches_host_oracle(tmp_path):
    """Numeric pin at the ROUND level: a clean fused round's per-cohort
    results equal the host oracle (per-cohort ``aggregate_inplace`` over
    the landed adapter deltas + the same FedAvg server step) to fp32
    reduction-order tolerance."""
    from photon_tpu.federation.collective_round import CollectiveFedRunner
    from photon_tpu.strategy.grouped import grouped_host_fold
    from photon_tpu.strategy.optimizers import FedAvgEff

    cfg = _adapter_cfg(tmp_path)
    runner = CollectiveFedRunner(cfg, [0, 1, 2, 3])
    plane = runner.adapter_plane
    before = {n: [a.copy() for a in plane.strategies.params(n)]
              for n in plane.cohort_names}
    landed_spy = {}
    real = CollectiveFedRunner._aggregate_elastic_adapters

    def spy(self, server_round, landed):
        landed_spy.update({
            cid: ([a.copy() for a in arrs], n)
            for cid, (arrs, n) in landed.items()
        })
        return real(self, server_round, landed)

    import photon_tpu.federation.collective_round as cr

    orig = cr.CollectiveFedRunner._aggregate_elastic_adapters
    cr.CollectiveFedRunner._aggregate_elastic_adapters = spy
    try:
        runner.run_round(1)
    finally:
        cr.CollectiveFedRunner._aggregate_elastic_adapters = orig
    folds = grouped_host_fold(landed_spy, plane.cohort_of)
    for name in plane.cohort_names:
        avg, n_total, k = folds[name]
        oracle = FedAvgEff(server_learning_rate=1.0)
        oracle.initialize([a.copy() for a in before[name]])
        oracle.apply_average(1, avg, n_total, k)
        for got, want in zip(plane.strategies.params(name),
                             oracle.current_parameters):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# 5. chaos: one cohort's clients all die → that cohort only degrades
# ---------------------------------------------------------------------------


def test_cohort_death_degrades_that_cohort_only(tmp_path, monkeypatch):
    """The ISSUE 13 chaos e2e: cohort beta's clients (2, 3) both fail
    their round-2 fits. Cohort alpha's adapter updates normally; beta's is
    bit-frozen; ``adapter/cohort_degraded`` and the ``alert/*`` twin are
    emitted (PR 9 plane); the round itself completes (reconfigured, never
    aborted); round 3 readmits beta at full strength."""
    events_path = tmp_path / "events.jsonl"
    telemetry.install(TelemetryConfig(enabled=True), scope="server",
                      events_path=str(events_path))
    from photon_tpu.federation.collective_round import CollectiveFedRunner

    cfg = _adapter_cfg(tmp_path)
    runner = CollectiveFedRunner(cfg, [0, 1, 2, 3])
    plane = runner.adapter_plane
    runner.run_round(1)

    real_fit = runner.runtime.fit

    def failing_fit(ins, cid):
        if ins.server_round == 2 and cid in (2, 3):
            from photon_tpu.federation.messages import FitRes

            return FitRes(server_round=ins.server_round, cid=cid,
                          params=None, error="simulated cohort loss")
        return real_fit(ins, cid)

    monkeypatch.setattr(runner.runtime, "fit", failing_fit)
    alpha1 = [a.copy() for a in plane.strategies.params("alpha")]
    beta1 = [a.copy() for a in plane.strategies.params("beta")]
    with pytest.warns(UserWarning, match="no surviving members"):
        m2 = runner.run_round(2)
    # beta frozen BIT-EXACT; alpha moved
    for x, y in zip(beta1, plane.strategies.params("beta")):
        np.testing.assert_array_equal(x, y)
    assert any(not np.array_equal(x, y)
               for x, y in zip(alpha1, plane.strategies.params("alpha")))
    assert m2["server/adapter_cohorts"] == 1.0
    assert m2["server/adapter_cohorts_degraded"] == 1.0
    assert m2["server/collective_stragglers"] == 2.0
    assert runner.aggregation_paths[2] == "collective_reconfigured"
    # health plane: federation degraded (scoped alert), not failing
    health = telemetry.health_active()
    assert health is not None
    assert health.plane_status("federation") == "degraded"
    # round 3: beta's clients answer again — full strength
    m3 = runner.run_round(3)
    assert m3["server/adapter_cohorts"] == 2.0
    assert m3["server/adapter_cohorts_degraded"] == 0.0
    assert any(not np.array_equal(x, y)
               for x, y in zip(beta1, plane.strategies.params("beta")))
    telemetry.uninstall()  # flush the event log
    kinds = [e["kind"] for e in telemetry.read_events_jsonl(str(events_path))]
    assert "adapter/cohort_degraded" in kinds
    assert "alert/adapter_cohort" in kinds
    assert "collective/straggler" in kinds


def test_all_cohorts_dead_records_failed_round(tmp_path, monkeypatch):
    from photon_tpu.federation.collective_round import CollectiveFedRunner

    cfg = _adapter_cfg(tmp_path)
    runner = CollectiveFedRunner(cfg, [0, 1, 2, 3])
    plane = runner.adapter_plane
    runner.run_round(1)
    state1 = {n: [a.copy() for a in plane.strategies.params(n)]
              for n in plane.cohort_names}
    steps1 = runner.server_steps_cumulative

    from photon_tpu.federation.messages import FitRes

    monkeypatch.setattr(
        runner.runtime, "fit",
        lambda ins, cid: FitRes(server_round=ins.server_round, cid=cid,
                                params=None, error="total loss"),
    )
    with pytest.warns(UserWarning, match="no client deltas landed"):
        m2 = runner.run_round(2)
    assert m2["server/round_failed"] == 1.0
    assert runner.server_steps_cumulative == steps1
    for name in plane.cohort_names:
        for x, y in zip(state1[name], plane.strategies.params(name)):
            np.testing.assert_array_equal(x, y)
    assert runner.aggregation_paths[2] == "failed"


def test_below_quorum_degrades_to_per_cohort_host_fold(tmp_path, monkeypatch):
    """Quorum 0.75 with half the fleet dead → straight to the grouped
    host fold, which is bit-exact with ``aggregate_inplace`` per cohort
    on the survivors."""
    from photon_tpu.federation.collective_round import CollectiveFedRunner
    from photon_tpu.strategy.grouped import grouped_host_fold
    from photon_tpu.strategy.optimizers import FedAvgEff

    cfg = _adapter_cfg(tmp_path)
    cfg.photon.comm_stack.collective_quorum = 0.75
    cfg.validate()
    runner = CollectiveFedRunner(cfg, [0, 1, 2, 3])
    plane = runner.adapter_plane
    runner.run_round(1)
    real_fit = runner.runtime.fit

    def failing_fit(ins, cid):
        if ins.server_round == 2 and cid in (1, 2):
            from photon_tpu.federation.messages import FitRes

            return FitRes(server_round=ins.server_round, cid=cid,
                          params=None, error="simulated node loss")
        return real_fit(ins, cid)

    monkeypatch.setattr(runner.runtime, "fit", failing_fit)
    before = {n: [a.copy() for a in plane.strategies.params(n)]
              for n in plane.cohort_names}
    landed_spy = {}
    import photon_tpu.federation.collective_round as cr

    real_fb = cr.CollectiveFedRunner._grouped_host_fallback

    def spy_fb(self, server_round, cohort, landed):
        landed_spy["cohort"] = cohort
        landed_spy["landed"] = {
            cid: ([a.copy() for a in arrs], n)
            for cid, (arrs, n) in landed.items()
        }
        return real_fb(self, server_round, cohort, landed)

    monkeypatch.setattr(
        cr.CollectiveFedRunner, "_grouped_host_fallback", spy_fb
    )
    with pytest.warns(UserWarning, match="below quorum"):
        m2 = runner.run_round(2)
    assert m2["server/collective_degraded_rounds"] == 1.0
    assert runner.aggregation_paths[2] == "host_fallback"
    assert landed_spy["cohort"] == (0, 3)
    folds = grouped_host_fold(
        {cid: landed_spy["landed"][cid] for cid in (0, 3)}, plane.cohort_of
    )
    # each surviving member updates its cohort bit-exactly vs the oracle
    for name, (avg, n_total, k) in folds.items():
        oracle = FedAvgEff(server_learning_rate=1.0)
        oracle.initialize([a.copy() for a in before[name]])
        oracle.apply_average(2, avg, n_total, k)
        for got, want in zip(plane.strategies.params(name),
                             oracle.current_parameters):
            np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# 6. checkpoint → resume
# ---------------------------------------------------------------------------


def test_adapter_checkpoint_resume_continuity(tmp_path):
    """Round → save (manifest machinery) → fresh runner → resume: base,
    per-cohort adapters and optimizer state (incl. the adaptive ``_t``)
    all bit-equal, and the resumed runner trains on."""
    from photon_tpu.checkpoint import FileStore
    from photon_tpu.checkpoint.server import ServerCheckpointManager
    from photon_tpu.federation.collective_round import CollectiveFedRunner

    cfg = _adapter_cfg(tmp_path, strategy="fedadam")
    store = FileStore(str(tmp_path / "store"))
    mgr = ServerCheckpointManager(store, cfg.run_uuid)
    runner = CollectiveFedRunner(cfg, [0, 1, 2, 3])
    runner.run_round(1)
    runner.save_checkpoint(mgr, 1)
    assert mgr.latest_complete_round() == 1  # manifest written last
    assert mgr.verify_round(1)

    cfg2 = _adapter_cfg(tmp_path, strategy="fedadam")
    runner2 = CollectiveFedRunner(cfg2, [0, 1, 2, 3])
    rnd = runner2.resume_from(mgr, -1)
    assert rnd == 1
    p1, p2 = runner.adapter_plane, runner2.adapter_plane
    for x, y in zip(p1.base_arrays, p2.base_arrays):
        np.testing.assert_array_equal(x, y)
    for name in p1.cohort_names:
        for x, y in zip(p1.strategies.params(name),
                        p2.strategies.params(name)):
            np.testing.assert_array_equal(x, y)
        s1, s2 = p1.strategies[name], p2.strategies[name]
        assert getattr(s1, "_t", 0) == getattr(s2, "_t", 0) == 1
        for key in s1.state_keys:
            for x, y in zip(s1.state[key], s2.state[key]):
                np.testing.assert_array_equal(x, y)
    assert runner2.server_steps_cumulative == runner.server_steps_cumulative
    m2 = runner2.run_round(2)  # resumes training without error
    assert m2["server/adapter_cohorts"] == 2.0
