"""Device-resident aggregation plane (ISSUE 7): hierarchical ICI/DCN
collectives vs the host streaming-average + server-optimizer oracle.

Pinned contracts:
- ``off`` on the degenerate ``(clients, 1)`` hierarchical mesh is BIT-EXACT
  against the original flat 1-D psum;
- ``off`` with a real replica axis matches ``aggregate_inplace`` to fp32
  tolerance;
- ``q8`` stays within the documented per-element blockwise bound
  ``Σ_clients scale/2`` (scales reconstructed with the host codec — valid
  because numpy↔jnp parity is byte-exact, ``test_compression.py``);
- the fused device server optimizers match ``strategy/optimizers.py``
  bit-exactly given the same average, and the full fused round matches the
  host ``aggregate_inplace`` + ``server_update`` oracle to fp32 tolerance
  for ALL five strategies;
- FedAdam resumes through ``Strategy.state_for_checkpoint`` with ``_t``
  continuity;
- programs are cached — steady-state rounds never recompile.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_tpu.compression.quantize import quantize_q8
from photon_tpu.parallel.collective_agg import (
    CLIENT_AXIS,
    DeviceAggregationPlane,
    collective_fedavg_round,
    collective_weighted_average,
    device_server_update,
    hierarchical_weighted_average,
    make_client_mesh,
    make_hierarchical_mesh,
    mesh_replica,
    modeled_cross_slice_bytes,
    stack_for_clients,
)
from photon_tpu.strategy.aggregation import aggregate_inplace
from photon_tpu.strategy.optimizers import (
    FedAdam,
    FedAvgEff,
    FedMom,
    FedNesterov,
    FedYogi,
)

N_CLIENTS = 4

STRATEGIES = {
    "fedavg": FedAvgEff,
    "nesterov": FedNesterov,
    "fedmom": FedMom,
    "fedadam": FedAdam,
    "fedyogi": FedYogi,
}


def _client_params(seed):
    rng = np.random.default_rng(seed)
    return {
        "w": rng.normal(size=(6, 4)).astype(np.float32),
        "b": rng.normal(size=(4,)).astype(np.float32),
    }


def _strategy(name, **kw):
    kw.setdefault("server_learning_rate", 0.5)
    kw.setdefault("server_momentum", 0.9)
    kw.setdefault("server_tau", 1e-3)
    return STRATEGIES[name](**kw)


# ---------------------------------------------------------------------------
# flat path (the original contract, unchanged)
# ---------------------------------------------------------------------------


def test_collective_average_matches_streaming_host_average():
    mesh = make_client_mesh(N_CLIENTS)
    clients = [_client_params(i) for i in range(N_CLIENTS)]
    n = np.asarray([10, 20, 5, 65], np.int32)

    stacked = stack_for_clients(clients, mesh)
    avg = collective_weighted_average(stacked, jnp.asarray(n), mesh)

    host_avg, total = aggregate_inplace(
        ([c["w"], c["b"]], int(ni)) for c, ni in zip(clients, n)
    )
    assert total == 100
    np.testing.assert_allclose(np.asarray(avg["w"]), host_avg[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(avg["b"]), host_avg[1], rtol=1e-5, atol=1e-6)


def test_collective_fedavg_round_lr1_returns_average():
    mesh = make_client_mesh(N_CLIENTS)
    clients = [_client_params(10 + i) for i in range(N_CLIENTS)]
    n = jnp.asarray([1, 1, 1, 1], jnp.int32)
    globals_ = _client_params(99)
    stacked = stack_for_clients(clients, mesh)
    new = collective_fedavg_round(stacked, globals_, n, mesh, server_lr=1.0)
    uniform = collective_weighted_average(stacked, n, mesh)
    np.testing.assert_allclose(np.asarray(new["w"]), np.asarray(uniform["w"]), rtol=1e-6)


def test_collective_fedavg_round_lr_scales_step():
    mesh = make_client_mesh(2)
    clients = [{"w": np.zeros((2, 2), np.float32)}, {"w": np.full((2, 2), 2.0, np.float32)}]
    globals_ = {"w": np.full((2, 2), 4.0, np.float32)}
    n = jnp.asarray([1, 1], jnp.int32)
    stacked = stack_for_clients(clients, mesh)
    # avg = 1.0; pseudo-grad = 4 - 1 = 3; lr 0.5 → new = 4 - 1.5 = 2.5
    new = collective_fedavg_round(stacked, globals_, n, mesh, server_lr=0.5)
    np.testing.assert_allclose(np.asarray(new["w"]), np.full((2, 2), 2.5), rtol=1e-6)


# ---------------------------------------------------------------------------
# hierarchical mesh + two-stage reduce
# ---------------------------------------------------------------------------


def test_hierarchical_mesh_shape_and_degenerate_replica():
    mesh = make_hierarchical_mesh(2, 2)
    assert mesh.axis_names == (CLIENT_AXIS, "replica")
    assert mesh.shape[CLIENT_AXIS] == 2 and mesh_replica(mesh) == 2
    assert mesh_replica(make_client_mesh(2)) == 1
    with pytest.raises(ValueError, match="replica must be >= 1"):
        make_hierarchical_mesh(2, 0)
    with pytest.raises(ValueError, match="need 16 devices"):
        make_hierarchical_mesh(8, 2, devices=jax.devices())


def test_hierarchical_off_replica1_bit_exact_vs_flat_psum():
    """The (clients, 1) hierarchical topology IS the flat psum — pinned
    bitwise so enabling the new mesh cannot perturb existing runs."""
    clients = [_client_params(40 + i) for i in range(N_CLIENTS)]
    n = jnp.asarray([3, 9, 27, 81], jnp.int32)

    flat_mesh = make_client_mesh(N_CLIENTS)
    flat_avg = collective_weighted_average(
        stack_for_clients(clients, flat_mesh), n, flat_mesh
    )
    h_mesh = make_hierarchical_mesh(N_CLIENTS, 1)
    h_avg = hierarchical_weighted_average(
        stack_for_clients(clients, h_mesh), n, h_mesh
    )
    for k in ("w", "b"):
        np.testing.assert_array_equal(np.asarray(flat_avg[k]), np.asarray(h_avg[k]))


@pytest.mark.parametrize("replica", [2, 4])
def test_hierarchical_off_matches_host_oracle(replica):
    mesh = make_hierarchical_mesh(N_CLIENTS // (replica // 2), replica)
    n_clients = int(mesh.shape[CLIENT_AXIS])
    clients = [_client_params(60 + i) for i in range(n_clients)]
    counts = np.arange(1, n_clients + 1, dtype=np.int32) * 7

    avg, total = hierarchical_weighted_average(
        stack_for_clients(clients, mesh), jnp.asarray(counts), mesh,
        return_total=True,
    )
    host_avg, host_total = aggregate_inplace(
        ([c["w"], c["b"]], int(ni)) for c, ni in zip(clients, counts)
    )
    assert int(np.asarray(total)) == host_total
    np.testing.assert_allclose(np.asarray(avg["w"]), host_avg[0], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(avg["b"]), host_avg[1], rtol=1e-5, atol=1e-6)


def _expected_q8_bound(clients, counts, shape_key, mesh, block):
    """Exact per-element error bound Σ_c scale_c/2, reconstructed with the
    HOST quantizer over the same chunk/block layout the collective uses."""
    replica = mesh_replica(mesh)
    total = float(sum(counts))
    n = clients[0][shape_key].size
    chunk = -(-n // (replica * block)) * block
    padded_len = replica * chunk
    bound = np.zeros(padded_len, np.float64)
    for c, cnt in zip(clients, counts):
        contrib = np.zeros(padded_len, np.float32)
        contrib[:n] = (c[shape_key].astype(np.float32) * np.float32(cnt / total)).reshape(-1)
        # scales come from the byte-parity-pinned host codec
        _, scales = quantize_q8(contrib, block=block)
        bound += np.repeat(scales.astype(np.float64), block) / 2.0
    return bound[:n].reshape(clients[0][shape_key].shape)


@pytest.mark.parametrize("replica", [1, 2])
def test_q8_error_within_documented_blockwise_bound(replica):
    block = 16  # small block → many blocks per chunk, ragged tail exercised
    mesh = make_hierarchical_mesh(N_CLIENTS, replica)
    clients = [_client_params(80 + i) for i in range(N_CLIENTS)]
    counts = np.asarray([5, 11, 2, 31], np.int32)

    stacked = stack_for_clients(clients, mesh)
    off = hierarchical_weighted_average(stacked, jnp.asarray(counts), mesh)
    q8 = hierarchical_weighted_average(
        stacked, jnp.asarray(counts), mesh, quantization="q8", block=block
    )
    for k in ("w", "b"):
        err = np.abs(np.asarray(q8[k]) - np.asarray(off[k]))
        bound = _expected_q8_bound(clients, counts, k, mesh, block)
        assert (err <= bound + 1e-6).all(), (
            f"{k}: max err {err.max()} exceeds bound {bound.max()}"
        )
        # and the bound is doing real work: q8 differs from fp32 somewhere
        assert err.max() > 0


def test_q8_all_zero_blocks_exact():
    mesh = make_hierarchical_mesh(2, 2)
    zero = {"w": np.zeros((8, 8), np.float32)}
    stacked = stack_for_clients([zero, zero], mesh)
    q8 = hierarchical_weighted_average(
        stacked, jnp.asarray([1, 1], jnp.int32), mesh, quantization="q8", block=16
    )
    np.testing.assert_array_equal(np.asarray(q8["w"]), zero["w"])


def test_bad_quantization_rejected():
    mesh = make_client_mesh(2)
    stacked = stack_for_clients([_client_params(0), _client_params(1)], mesh)
    with pytest.raises(ValueError, match="quantization"):
        hierarchical_weighted_average(
            stacked, jnp.asarray([1, 1], jnp.int32), mesh, quantization="int4"
        )
    # the config's 0-means-default sentinel must be resolved by callers, not
    # forwarded (it would die as a bare ZeroDivisionError in the chunk math)
    with pytest.raises(ValueError, match="block"):
        hierarchical_weighted_average(
            stacked, jnp.asarray([1, 1], jnp.int32), mesh,
            quantization="q8", block=0,
        )
    strat = _strategy("fedavg")
    strat.initialize([np.zeros(4, np.float32)])
    with pytest.raises(ValueError, match="block"):
        DeviceAggregationPlane(mesh, strat, quantization="q8", block=0)


# ---------------------------------------------------------------------------
# modeled DCN bytes
# ---------------------------------------------------------------------------


def _mpt125m_leaf_sizes():
    """Every leaf of the 125M recipe (big matrices AND the ragged
    layernorm / bias leaves whose block padding q8 pays for), by shape
    alone."""
    from photon_tpu.config.schema import ModelConfig
    from photon_tpu.models.mpt import init_params

    abstract = jax.eval_shape(lambda: init_params(ModelConfig(), seed=0))
    return [int(np.prod(leaf.shape, dtype=np.int64))
            for leaf in jax.tree_util.tree_leaves(abstract)]


@pytest.mark.parametrize("sizes", [
    # one 1M-element leaf at block 256: 4 bytes/val → 1 + 4/256 bytes/val
    pytest.param(lambda: [1 << 20], id="one-1M-leaf"),
    # the whole recipe: the ratio survives the ragged leaves' padding
    pytest.param(_mpt125m_leaf_sizes, id="mpt-125m-leaves"),
])
def test_modeled_cross_slice_bytes_q8_ratio(sizes):
    sizes = sizes()
    fp32 = modeled_cross_slice_bytes(sizes, 4, quantization="off")
    q8 = modeled_cross_slice_bytes(sizes, 4, quantization="q8", block=256)
    assert fp32 == 4 * sum(sizes) * 4
    ratio = fp32 / q8
    assert 3.5 <= ratio <= 4.0, ratio
    # hierarchy splits the modeled total and adds only the block padding
    # of each rank's chunk (nothing where the blocks divide evenly)
    hier = modeled_cross_slice_bytes(sizes, 4, replica=4, quantization="q8",
                                     block=256)
    assert q8 <= hier <= q8 * 1.001 and fp32 / hier >= 3.5
    if len(sizes) == 1:
        assert hier == q8


def test_modeled_cross_slice_bytes_padding_accounted():
    # 5 elements in a 256-block: q8 "compression" must model the padding
    # cost honestly (worse than fp32 for tiny leaves)
    assert modeled_cross_slice_bytes([5], 1, quantization="q8", block=256) == 256 + 4
    assert modeled_cross_slice_bytes([5], 1, quantization="off") == 20


# ---------------------------------------------------------------------------
# device-resident server optimizers (fused with the average)
# ---------------------------------------------------------------------------


def _stacked_flat(clients, mesh):
    stacked = stack_for_clients(
        [{f"x{i}": a for i, a in enumerate(c)} for c in clients], mesh
    )
    return [stacked[f"x{i}"] for i in range(len(clients[0]))]


def _ns_global(counts, mesh):
    return jax.device_put(
        np.asarray(counts, np.int32), NamedSharding(mesh, P(CLIENT_AXIS))
    )


SHAPES = [(6, 20), (5,), (3, 3, 3)]


def _rounds_parity(name, quantization, replica, n_rounds=3, seed=5):
    """Run n_rounds through host oracle + device plane side by side;
    return max |param delta| across all rounds."""
    rng = np.random.default_rng(seed)
    init = [rng.normal(size=s).astype(np.float32) for s in SHAPES]
    host = _strategy(name)
    host.initialize([p.copy() for p in init])
    dev_strat = _strategy(name)
    dev_strat.initialize([p.copy() for p in init])
    mesh = make_hierarchical_mesh(N_CLIENTS, replica)
    plane = DeviceAggregationPlane(mesh, dev_strat, quantization=quantization)

    max_d = 0.0
    for rnd in range(1, n_rounds + 1):
        clients = [
            [rng.normal(size=s).astype(np.float32) for s in SHAPES]
            for _ in range(N_CLIENTS)
        ]
        counts = rng.integers(1, 50, N_CLIENTS).astype(np.int32)
        avg, total = aggregate_inplace(
            (c, int(k)) for c, k in zip(clients, counts)
        )
        host_metrics = host.apply_average(rnd, avg, int(total), N_CLIENTS)
        metrics = plane.run_round(
            _stacked_flat(clients, mesh), _ns_global(counts, mesh),
            lr=host.effective_lr(N_CLIENTS),
        )
        assert metrics["server/n_samples"] == float(total)
        if quantization == "off":
            # KPI vocabulary parity: same key, same meaning on both
            # optimizer paths (param norm is PRE-update on the host — the
            # device program mirrors that)
            for key in ("server/param_norm", "server/pseudo_grad_norm"):
                np.testing.assert_allclose(
                    metrics[key], host_metrics[key], rtol=1e-4, err_msg=key
                )
        for a, b in zip(host.current_parameters, plane.params_host()):
            max_d = max(max_d, float(np.abs(a - b).max()))
    return max_d, host, plane


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_device_plane_matches_host_oracle_all_strategies(name):
    """Acceptance: the `off` hierarchical fused round matches the host
    ``aggregate_inplace`` + ``server_update`` oracle to fp32 tolerance for
    ALL five strategies (3 rounds, stateful rules accumulate)."""
    max_d, host, plane = _rounds_parity(name, "off", replica=2)
    assert max_d < 1e-5, f"{name}: device path diverged by {max_d}"
    # state mirrors too (momenta parity keeps checkpoints interchangeable)
    for key in host.state_keys:
        for a, b in zip(host.state[key], plane.state_host()[key]):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def test_device_update_rule_bit_exact_given_same_average(name):
    """Feed the device rule the SAME pseudo-gradients the host rule sees:
    parameters must match bit-for-bit across 3 stateful steps (the jnp port
    is op-for-op, not just close)."""
    rng = np.random.default_rng(11)
    init = [rng.normal(size=s).astype(np.float32) for s in SHAPES]
    host = _strategy(name)
    host.initialize([p.copy() for p in init])
    params = [jnp.asarray(p) for p in init]
    state = {k: [jnp.zeros_like(p) for p in params] for k in host.state_keys}
    for t in range(1, 4):
        grads = [rng.normal(size=s).astype(np.float32) for s in SHAPES]
        host_params = host.server_update([g.copy() for g in grads], 0.5)
        host.current_parameters = host_params
        b1t = 1.0 - host.beta_1 ** t if hasattr(host, "beta_1") else 1.0
        b2t = 1.0 - host.beta_2 ** t if hasattr(host, "beta_2") else 1.0
        params, state = device_server_update(
            name, params, [jnp.asarray(g) for g in grads], state,
            jnp.float32(0.5), jnp.float32(b1t), jnp.float32(b2t),
            momentum=0.9, beta_1=0.9, beta_2=0.99, tau=1e-3,
        )
        for a, b in zip(host_params, params):
            np.testing.assert_array_equal(a, np.asarray(b), err_msg=f"{name} t={t}")


def test_device_plane_q8_stays_near_off():
    """q8 fused rounds track the off fused rounds within the quantization
    budget (pseudo-gradients see the bounded average error through an
    lr-scaled linear rule)."""
    _, _, plane_off = _rounds_parity("fedavg", "off", replica=2, seed=9)
    _, _, plane_q8 = _rounds_parity("fedavg", "q8", replica=2, seed=9)
    for a, b in zip(plane_off.params_host(), plane_q8.params_host()):
        assert float(np.abs(a - b).max()) < 5e-2


def test_device_plane_rejects_unknown_strategy_and_bad_payload():
    from photon_tpu.strategy.base import Strategy

    mesh = make_hierarchical_mesh(2, 1)
    base = Strategy()
    base.initialize([np.zeros(4, np.float32)])
    with pytest.raises(ValueError, match="no device update rule"):
        DeviceAggregationPlane(mesh, base)

    strat = _strategy("fedavg")
    strat.initialize([np.zeros(4, np.float32)])
    plane = DeviceAggregationPlane(mesh, strat)
    with pytest.raises(ValueError, match="momenta mismatch"):
        plane.run_round(
            [jnp.zeros((2, 4)), jnp.zeros((2, 4))],
            _ns_global([1, 1], mesh), lr=1.0,
        )


def test_device_plane_nonneg_rows_clamped_on_q8_only():
    """Regression (q8 + aggregate_momenta NaN): when every client reports an
    exactly-zero second-moment element while the server's copy is small-
    positive, the adaptive step is ~lr-sized regardless of the gradient's
    magnitude and drives the element negative — clients then sqrt it. The
    plane clamps rows named in ``nonneg_rows`` on the q8 policy; `off` stays
    untouched (bit-exact vs the host oracle, which does not clamp)."""
    mesh = make_hierarchical_mesh(2, 1)
    rng = np.random.default_rng(33)
    w = rng.normal(size=(6, 20)).astype(np.float32)
    m2 = np.full((5,), 1e-4, np.float32)  # idle second moments, barely > 0
    clients = [[rng.normal(size=w.shape).astype(np.float32), np.zeros_like(m2)]
               for _ in range(2)]

    def one_round(quantization, nonneg_rows):
        strat = _strategy("fedadam")
        strat.initialize([w.copy(), m2.copy()])
        plane = DeviceAggregationPlane(
            mesh, strat, quantization=quantization, nonneg_rows=nonneg_rows
        )
        plane.run_round(_stacked_flat(clients, mesh), _ns_global([1, 1], mesh), lr=0.5)
        return plane.params_host()[1]

    # the mechanism: unprotected q8 round turns the m2 row negative
    assert float(one_round("q8", ()).min()) < 0.0
    # the fix: the clamp restores the invariant on the q8 policy
    assert float(one_round("q8", (1,)).min()) >= 0.0
    # `off` is out of the clamp's scope even with the mask set
    assert float(one_round("off", (1,)).min()) < 0.0

    strat = _strategy("fedadam")
    strat.initialize([w.copy(), m2.copy()])
    with pytest.raises(ValueError, match="nonneg_rows out of range"):
        DeviceAggregationPlane(mesh, strat, nonneg_rows=(2,))


def test_fedadam_checkpoint_resume_bias_correction_continuity():
    """Acceptance: a multi-round fused FedAdam run checkpointed through the
    EXISTING host ``Strategy.state_for_checkpoint`` and resumed into a
    fresh plane continues bit-identically — ``_t`` (bias correction) rides
    the state blob, so round 3-after-resume equals round 3-continuous."""
    rng = np.random.default_rng(21)
    init = [rng.normal(size=s).astype(np.float32) for s in SHAPES]
    mesh = make_hierarchical_mesh(N_CLIENTS, 2)

    def make_plane(params, state=None):
        strat = _strategy("fedadam")
        strat.initialize(params, state)
        return strat, DeviceAggregationPlane(mesh, strat, quantization="off")

    def round_data(rnd):
        r = np.random.default_rng(100 + rnd)
        clients = [
            [r.normal(size=s).astype(np.float32) for s in SHAPES]
            for _ in range(N_CLIENTS)
        ]
        return clients, r.integers(1, 30, N_CLIENTS).astype(np.int32)

    # continuous: 3 rounds on one plane
    strat_c, plane_c = make_plane([p.copy() for p in init])
    for rnd in range(1, 4):
        clients, counts = round_data(rnd)
        plane_c.run_round(_stacked_flat(clients, mesh), _ns_global(counts, mesh), lr=0.5)

    # interrupted: 2 rounds → checkpoint via the host strategy → resume
    strat_a, plane_a = make_plane([p.copy() for p in init])
    for rnd in range(1, 3):
        clients, counts = round_data(rnd)
        plane_a.run_round(_stacked_flat(clients, mesh), _ns_global(counts, mesh), lr=0.5)
    plane_a.sync_strategy(strat_a)
    assert strat_a._t == 2
    ckpt_state = strat_a.state_for_checkpoint()
    assert "_t" in ckpt_state  # the counter rides the existing state blob
    ckpt_params = [p.copy() for p in strat_a.current_parameters]

    strat_b, plane_b = make_plane(ckpt_params, ckpt_state)
    assert plane_b.t == 2  # bias correction continues, not restarts
    clients, counts = round_data(3)
    plane_b.run_round(_stacked_flat(clients, mesh), _ns_global(counts, mesh), lr=0.5)

    for a, b in zip(plane_c.params_host(), plane_b.params_host()):
        np.testing.assert_array_equal(a, b)
    for key in ("momentum_1", "momentum_2"):
        for a, b in zip(plane_c.state_host()[key], plane_b.state_host()[key]):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# ZeRO-1 sharded plane (ISSUE 14)
# ---------------------------------------------------------------------------


def _round_clients(rnd, n_clients=N_CLIENTS, shapes=SHAPES):
    r = np.random.default_rng(1000 + rnd)
    clients = [
        [r.normal(size=s).astype(np.float32) for s in shapes]
        for _ in range(n_clients)
    ]
    return clients, r.integers(1, 30, n_clients).astype(np.int32)


@pytest.mark.parametrize("name", sorted(STRATEGIES))
@pytest.mark.parametrize("quantization", ["off", "q8"])
def test_sharded_plane_bit_exact_vs_replicated(name, quantization):
    """Acceptance (ISSUE 14): the ZeRO-1 sharded round — update on each
    rank's reduce-scatter chunk, all-gather only after the update — is
    BIT-IDENTICAL to the replicated PR 7 plane for all five strategies at
    off AND q8 (the update is elementwise, the padded-flat layout is
    value-preserving, and the q8 block boundaries stay globally aligned).
    Since the replicated plane is pinned against the host
    ``aggregate_inplace`` + ``apply_average`` oracle, the sharded plane
    inherits that oracle parity transitively."""
    rng = np.random.default_rng(7)
    init = [rng.normal(size=s).astype(np.float32) for s in SHAPES]
    mesh = make_hierarchical_mesh(N_CLIENTS, 2)

    def make_plane(sharded):
        strat = _strategy(name)
        strat.initialize([p.copy() for p in init])
        return DeviceAggregationPlane(
            mesh, strat, quantization=quantization, block=16, sharded=sharded
        )

    plane_s, plane_r = make_plane(True), make_plane(False)
    assert plane_s.sharded and not plane_r.sharded
    assert plane_s.shard_fraction() < 1.0 <= plane_r.shard_fraction()
    assert (plane_s.server_state_bytes_per_rank()
            < plane_r.server_state_bytes_per_rank())
    for rnd in range(1, 4):
        clients, counts = _round_clients(rnd)
        ms = plane_s.run_round(
            _stacked_flat(clients, mesh), _ns_global(counts, mesh), lr=0.5
        )
        mr = plane_r.run_round(
            _stacked_flat(clients, mesh), _ns_global(counts, mesh), lr=0.5
        )
        assert ms["server/n_samples"] == mr["server/n_samples"]
        # norm KPIs agree to fp32 (sharded sums partial-then-psum)
        np.testing.assert_allclose(
            ms["server/pseudo_grad_norm"], mr["server/pseudo_grad_norm"],
            rtol=1e-4,
        )
    for a, b in zip(plane_s.params_host(), plane_r.params_host()):
        np.testing.assert_array_equal(a, b)
    for key in plane_s.state_keys:
        for a, b in zip(plane_s.state_host()[key], plane_r.state_host()[key]):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("quantization", ["off", "q8"])
@pytest.mark.parametrize("save_replica,resume_replica", [(4, 1), (1, 4)])
def test_sharded_checkpoint_bit_exact_across_resharding(
    quantization, save_replica, resume_replica
):
    """Acceptance (ISSUE 14): save at replica=4, resume at replica=1 (and
    vice versa) continues BIT-identically — including FedAdam ``_t``
    continuity — because ``state_for_checkpoint`` stores full unpadded
    leaves and re-seeding re-slices them; at q8 the block boundaries stay
    aligned to the global padded vector for every replica, so even the
    quantized average is replica-invariant."""
    n_clients = 2
    rng = np.random.default_rng(3)
    init = [rng.normal(size=s).astype(np.float32) for s in SHAPES]

    def make_plane(replica, params, state=None):
        strat = _strategy("fedadam")
        strat.initialize(params, state)
        mesh = make_hierarchical_mesh(n_clients, replica)
        return strat, DeviceAggregationPlane(
            mesh, strat, quantization=quantization, block=16, sharded=True
        ), mesh

    def run(plane, mesh, rnd):
        clients, counts = _round_clients(rnd, n_clients)
        plane.run_round(
            _stacked_flat(clients, mesh), _ns_global(counts, mesh), lr=0.5
        )

    # continuous: 3 rounds at the SAVE replica count
    strat_c, plane_c, mesh_c = make_plane(
        save_replica, [p.copy() for p in init]
    )
    for rnd in range(1, 4):
        run(plane_c, mesh_c, rnd)

    # interrupted: 2 rounds → checkpoint → resume at the OTHER replica
    strat_a, plane_a, mesh_a = make_plane(
        save_replica, [p.copy() for p in init]
    )
    for rnd in range(1, 3):
        run(plane_a, mesh_a, rnd)
    plane_a.sync_strategy(strat_a)
    assert strat_a._t == 2
    ckpt_state = strat_a.state_for_checkpoint()
    ckpt_params = [p.copy() for p in strat_a.current_parameters]

    strat_b, plane_b, mesh_b = make_plane(
        resume_replica, ckpt_params, ckpt_state
    )
    assert plane_b.t == 2  # bias correction continues across the reshard
    run(plane_b, mesh_b, 3)

    # round 3 after a resharded resume == round 3 continuous, bitwise.
    # NOTE this also pins the round itself replica-invariant (the psum
    # order and q8 block alignment arguments) — strictly stronger than
    # the save/load identity alone
    for a, b in zip(plane_c.params_host(), plane_b.params_host()):
        np.testing.assert_array_equal(a, b)
    for key in ("momentum_1", "momentum_2"):
        for a, b in zip(plane_c.state_host()[key], plane_b.state_host()[key]):
            np.testing.assert_array_equal(a, b)


def test_sharded_state_bytes_per_rank_divide_by_replica():
    """ISSUE 14's count: under FedAdam ([params|m1|m2]) at R=4 a rank of
    the sharded plane holds <= (1/R + 0.05) of what a rank of the
    replicated plane holds, and never less than 1/R — the 0.05 is the
    chunk padding of leaves that R does not divide."""
    replica = 4
    rng = np.random.default_rng(3)
    init = [rng.normal(size=s).astype(np.float32)
            for s in [(512, 768), (768,), (256, 3), (5,)]]
    mesh = make_hierarchical_mesh(2, replica)

    def state_bytes(sharded):
        strat = _strategy("fedadam")
        strat.initialize([p.copy() for p in init])
        plane = DeviceAggregationPlane(mesh, strat, sharded=sharded)
        assert set(plane.state_keys) == {"momentum_1", "momentum_2"}
        return plane.server_state_bytes_per_rank(), plane.shard_fraction()

    full, frac_r = state_bytes(False)
    shard, frac_s = state_bytes(True)
    assert full == 4 * sum(p.size for p in init) * 3 and frac_r == 1.0
    assert 1 / replica <= shard / full <= 1 / replica + 0.05
    assert frac_s == pytest.approx(shard / full)


def test_sharded_seeding_peak_host_rss_bounded():
    """ISSUE 14 satellite: ``_seed_from_host`` seeds every leaf DIRECTLY
    into its sharded layout — no full fp32 host copy per leaf, and missing
    state keys zero-fill chunk-by-chunk instead of materializing whole
    zero arrays. Peak traced host allocation during construction must stay
    near ONE chunk (payload/replica), far below the payload itself; the
    old path held full zero copies of every missing state tensor at once
    (2 × payload for FedAdam)."""
    import tracemalloc

    replica = 4
    leaf = np.zeros((512, 2048), np.float32)  # 4 MiB
    payload_bytes = leaf.nbytes
    chunk_bytes = payload_bytes // replica

    def construction_peak(sharded):
        strat = _strategy("fedadam")
        strat.initialize([leaf.copy()])  # m1/m2 zero-filled by the plane
        strat.state.clear()  # initialize() pre-fills; force the plane path
        mesh = make_hierarchical_mesh(2, replica)
        tracemalloc.start()
        plane = DeviceAggregationPlane(mesh, strat, sharded=sharded)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        # the zero-filled state actually landed either way
        assert float(np.abs(plane.state_host()["momentum_1"][0]).max()) == 0.0
        return peak

    sharded_peak = construction_peak(True)
    replicated_peak = construction_peak(False)
    # replicated seeding materializes full zero tensors per missing state
    # key (2 × 4 MiB here); sharded seeding allocates ~one chunk (params
    # seed as views, zero shards alias one shared chunk buffer)
    assert sharded_peak < replicated_peak, (
        f"sharded seeding peaked at {sharded_peak / 2**20:.1f} MiB, "
        f"replicated at {replicated_peak / 2**20:.1f} MiB"
    )
    assert sharded_peak < 2 * chunk_bytes, (
        f"sharded seeding allocated {sharded_peak / 2**20:.1f} MiB on host "
        f"for a {payload_bytes / 2**20:.1f} MiB payload at replica={replica} "
        f"(expected ~one {chunk_bytes / 2**20:.1f} MiB chunk)"
    )


def test_sharded_update_leg_no_steady_state_compiles():
    """The sharded round's FULL update leg (fused program + post-update
    params all-gather + state mirror gather) reuses cached programs from
    round 2 on — the PR 6 retrace sentinel discipline extends to the new
    gather programs."""
    from photon_tpu.analysis.runtime import retrace_guard

    rng = np.random.default_rng(41)
    mesh = make_hierarchical_mesh(N_CLIENTS, 2)
    strat = _strategy("fedadam")
    strat.initialize([rng.normal(size=s).astype(np.float32) for s in SHAPES])
    plane = DeviceAggregationPlane(mesh, strat, sharded=True)

    def one_round(rnd):
        clients, counts = _round_clients(rnd)
        plane.run_round(
            _stacked_flat(clients, mesh), _ns_global(counts, mesh), lr=0.5
        )
        plane.params_host()
        plane.state_host()

    one_round(1)  # warmup: fused program + gather programs compile once
    with retrace_guard(steady=True):
        one_round(2)
        one_round(3)
    assert plane.last_allgather_s > 0.0


def test_sharded_snapshot_restore_and_abandon_epoch():
    """PR 8 elastic semantics hold shard-aware: snapshot → run → restore
    rolls the sharded plane back bit-exactly, and an abandoned epoch's
    late commit is skipped (the commit path never mixes layouts)."""
    rng = np.random.default_rng(55)
    mesh = make_hierarchical_mesh(N_CLIENTS, 2)
    strat = _strategy("fedadam")
    strat.initialize([rng.normal(size=s).astype(np.float32) for s in SHAPES])
    plane = DeviceAggregationPlane(mesh, strat, sharded=True)
    clients, counts = _round_clients(1)
    plane.run_round(_stacked_flat(clients, mesh), _ns_global(counts, mesh), lr=0.5)
    before = plane.params_host()
    snap = plane.snapshot()

    clients2, counts2 = _round_clients(2)
    plane.run_round(_stacked_flat(clients2, mesh), _ns_global(counts2, mesh), lr=0.5)
    assert plane.t == 2
    plane.abandon()
    plane.restore(snap)
    assert plane.t == 1
    for a, b in zip(before, plane.params_host()):
        np.testing.assert_array_equal(a, b)

    # a run dispatched under the pre-abandon epoch must not commit
    stale_epoch = 0  # current_epoch() was 0 before abandon bumped it
    plane.run_round(
        _stacked_flat(clients2, mesh), _ns_global(counts2, mesh), lr=0.5,
        epoch=stale_epoch,
    )
    assert plane.t == 1  # skipped: the round completed another way
    for a, b in zip(before, plane.params_host()):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# steady-state compile discipline (programs cached, not rebuilt per round)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("quantization", ["off", "q8"])
def test_average_program_cached_no_steady_state_compiles(quantization):
    from photon_tpu.analysis.runtime import retrace_guard

    mesh = make_hierarchical_mesh(N_CLIENTS, 2)
    clients = [_client_params(70 + i) for i in range(N_CLIENTS)]
    stacked = stack_for_clients(clients, mesh)
    n = jnp.asarray([1, 2, 3, 4], jnp.int32)
    kw = dict(quantization=quantization, block=16)
    # warmup builds + compiles the program once
    hierarchical_weighted_average(stacked, n, mesh, **kw)
    with retrace_guard(steady=True):
        for _ in range(3):
            hierarchical_weighted_average(stacked, n, mesh, **kw)


@pytest.mark.parametrize("quantization", ["off", "q8"])
def test_device_plane_round_no_steady_state_compiles(quantization):
    from photon_tpu.analysis.runtime import retrace_guard

    rng = np.random.default_rng(31)
    mesh = make_hierarchical_mesh(N_CLIENTS, 2)
    strat = _strategy("fedadam")
    strat.initialize([rng.normal(size=s).astype(np.float32) for s in SHAPES])
    plane = DeviceAggregationPlane(mesh, strat, quantization=quantization, block=16)

    def one_round(rnd):
        r = np.random.default_rng(rnd)
        clients = [
            [r.normal(size=s).astype(np.float32) for s in SHAPES]
            for _ in range(N_CLIENTS)
        ]
        counts = r.integers(1, 20, N_CLIENTS).astype(np.int32)
        plane.run_round(_stacked_flat(clients, mesh), _ns_global(counts, mesh), lr=0.5)

    one_round(1)  # warmup: the only allowed compile
    with retrace_guard(steady=True):
        one_round(2)
        one_round(3)
