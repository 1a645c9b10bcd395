"""Golden-value tests for aggregation + server optimizers (SURVEY.md §7.5:
"golden-value unit tests against hand-computed rounds")."""

import math
import sys
import tracemalloc

import numpy as np
import pytest

from photon_tpu.config.schema import FLConfig
from photon_tpu.strategy import (
    ClientResult,
    FedAdam,
    FedAvgEff,
    FedMom,
    FedNesterov,
    FedYogi,
    aggregate_inplace,
    dispatch_strategy,
    weighted_loss_avg,
)
from photon_tpu.strategy import aggregation
from photon_tpu.strategy.metrics import GradientNoiseScale
from photon_tpu.utils.hostpool import HostPool


def arrs(*vals):
    return [np.full((2, 2), v, np.float32) for v in vals]


def test_aggregate_inplace_weighted_mean():
    results = [(arrs(1.0), 1), (arrs(4.0), 3)]
    avg, n = aggregate_inplace(iter(results))
    assert n == 4
    np.testing.assert_allclose(avg[0], np.full((2, 2), (1 * 1 + 4 * 3) / 4), rtol=1e-6)


def test_aggregate_inplace_matches_direct_mean_many():
    rng = np.random.default_rng(0)
    payloads = [([rng.normal(size=(3, 5)).astype(np.float32)], int(n)) for n in rng.integers(1, 100, 12)]
    avg, n_tot = aggregate_inplace(iter(payloads))
    direct = sum(a[0].astype(np.float64) * n for a, n in payloads) / sum(n for _, n in payloads)
    np.testing.assert_allclose(avg[0], direct, rtol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("threads", [1, 4])
def test_aggregate_inplace_never_writes_a_payload(dtype, threads):
    """The payloads are the sender's (the inline plane) or read-only views
    of its segment (shm): the accumulator is a copy of the first even when
    that is already float64 and would pass through ``asarray`` as itself."""
    payloads = [([np.full((3, 5), v, dtype)], n) for v, n in ((1.0, 1), (4.0, 3), (2.0, 4))]
    for arrays, _ in payloads:
        arrays[0].setflags(write=False)
    pool = HostPool(threads)
    try:
        avg, n = aggregate_inplace(iter(payloads), pool=pool)
    finally:
        pool.close()
    assert n == 8
    np.testing.assert_allclose(avg[0], np.full((3, 5), (1 + 12 + 8) / 8), rtol=1e-6)
    assert [float(a[0][0, 0]) for a, _ in payloads] == [1.0, 4.0, 2.0]


def test_aggregate_rejects_empty_and_bad_counts():
    with pytest.raises(ValueError):
        aggregate_inplace(iter([]))
    with pytest.raises(ValueError):
        aggregate_inplace(iter([(arrs(1.0), 0)]))


def _round(strategy, client_vals, server_val=1.0, n_samples=None, rnd=1):
    strategy.initialize(arrs(server_val)) if strategy.current_parameters is None else None
    n_samples = n_samples or [1] * len(client_vals)
    results = (
        ClientResult(cid=i, arrays=arrs(v), n_samples=n)
        for i, (v, n) in enumerate(zip(client_vals, n_samples))
    )
    params, metrics = strategy.aggregate_fit(rnd, results)
    return params[0][0, 0], metrics


def test_fedavg_lr1_is_plain_average():
    s = FedAvgEff(server_learning_rate=1.0)
    val, _ = _round(s, [0.0, 2.0])  # avg=1.0, g = 1-1 = 0 → x=1... use server 4
    s2 = FedAvgEff(server_learning_rate=1.0)
    s2.initialize(arrs(4.0))
    val, _ = _round(s2, [0.0, 2.0])
    # g = 4 - 1 = 3; x = 4 - 3 = 1 = the average
    np.testing.assert_allclose(val, 1.0, rtol=1e-6)


def test_fedavg_halved_lr():
    s = FedAvgEff(server_learning_rate=0.5)
    s.initialize(arrs(4.0))
    val, _ = _round(s, [0.0, 2.0])
    np.testing.assert_allclose(val, 4.0 - 0.5 * 3.0, rtol=1e-6)  # 2.5


def test_client_count_scaling():
    s = FedAvgEff(server_learning_rate=0.1, client_count_scaling="linear")
    assert s.effective_lr(4) == pytest.approx(0.4)
    s2 = FedAvgEff(server_learning_rate=0.1, client_count_scaling="sqrt")
    assert s2.effective_lr(4) == pytest.approx(0.2)


def test_nesterov_two_rounds_golden():
    # μ=0.5, η=1. Round1: avg=0 from x=1 → g=1; m=0.5*0+1=1; step=g+μm=1.5; x=-0.5
    # Round2: clients at -0.5 → g = x - avg = 0 → m=0.5; step=0+0.25... compute:
    s = FedNesterov(server_learning_rate=1.0, server_momentum=0.5)
    s.initialize(arrs(1.0))
    v1, _ = _round(s, [0.0, 0.0], rnd=1)
    np.testing.assert_allclose(v1, -0.5, rtol=1e-6)
    # round 2: clients return -1.5 (avg), g = -0.5 - (-1.5) = 1.0
    v2, _ = _round(s, [-1.5, -1.5], rnd=2)
    # m = 0.5*1 + 1 = 1.5; step = 1 + 0.5*1.5 = 1.75; x = -0.5 - 1.75 = -2.25
    np.testing.assert_allclose(v2, -2.25, rtol=1e-6)


def test_fedmom_golden():
    s = FedMom(server_learning_rate=1.0, server_momentum=0.9)
    s.initialize(arrs(1.0))
    v1, _ = _round(s, [0.0], rnd=1)  # g=1, m=1, x = 0
    np.testing.assert_allclose(v1, 0.0, atol=1e-7)
    v2, _ = _round(s, [-1.0], rnd=2)  # g = 0-(-1)=1; m=0.9+1=1.9; x=0-1.9
    np.testing.assert_allclose(v2, -1.9, rtol=1e-6)


def test_fedadam_first_step_golden():
    # t=1: m=(1-b1)g /(1-b1) = g; v=(1-b2)g²/(1-b2)=g²; x -= lr·g/(|g|+tau) = sign
    s = FedAdam(server_learning_rate=0.1, server_beta_1=0.9, server_beta_2=0.99, server_tau=0.0)
    s.initialize(arrs(1.0))
    v1, _ = _round(s, [0.5], rnd=1)  # g=0.5 → step = 0.1 * 0.5/0.5 = 0.1
    np.testing.assert_allclose(v1, 0.9, rtol=1e-6)


def test_fedyogi_second_moment_sign():
    s = FedYogi(server_learning_rate=0.1, server_beta_1=0.0, server_beta_2=0.99, server_tau=0.0)
    s.initialize(arrs(1.0))
    # v starts 0; g²>0 ⇒ sign(0-g²)=-1 ⇒ v = (1-b2)·g², same as adam's first step
    v1, _ = _round(s, [0.5], rnd=1)
    np.testing.assert_allclose(v1, 0.9, rtol=1e-6)


def test_adaptive_state_checkpoint_roundtrip():
    s = FedAdam(server_learning_rate=0.1)
    s.initialize(arrs(1.0))
    _round(s, [0.5], rnd=1)
    ckpt_state = s.state_for_checkpoint()
    ckpt_params = [a.copy() for a in s.current_parameters]

    s2 = FedAdam(server_learning_rate=0.1)
    s2.initialize(ckpt_params, ckpt_state)
    assert s2._t == 1
    v_a, _ = _round(s, [0.2], rnd=2)
    v_b, _ = _round(s2, [0.2], rnd=2)
    np.testing.assert_allclose(v_a, v_b, rtol=1e-6)


def test_dispatcher_covers_all():
    for name in ("fedavg", "nesterov", "fedmom", "fedadam", "fedyogi"):
        s = dispatch_strategy(FLConfig(strategy_name=name))
        assert s.name == name


def test_weighted_loss_avg():
    assert weighted_loss_avg([(1, 2.0), (3, 4.0)]) == pytest.approx((2 + 12) / 4)


def test_weighted_average_metrics_ragged_exact():
    """Single-pass rewrite (ISSUE 2 satellite): exact values pinned on a
    ragged metrics dict — every key normalizes by the samples of the
    clients that REPORTED it, not the round total."""
    from photon_tpu.strategy import weighted_average_metrics

    results = [
        (2, {"loss": 4.0, "acc": 0.5}),
        (6, {"loss": 2.0}),                 # no "acc"
        (4, {"acc": 1.0, "extra": 7.0}),    # no "loss"
        (0, {"ghost": 3.0}),                # zero-weight: must not divide by 0
    ]
    out = weighted_average_metrics(results)
    assert out == {
        "loss": pytest.approx((2 * 4.0 + 6 * 2.0) / 8),   # 2.5 over 8 samples
        "acc": pytest.approx((2 * 0.5 + 4 * 1.0) / 6),    # 5/6 over 6 samples
        "extra": pytest.approx(7.0),
    }
    assert "ghost" not in out
    assert weighted_average_metrics([]) == {}


def test_metrics_weighted_and_telemetry():
    s = FedAvgEff(server_learning_rate=1.0)
    s.initialize(arrs(1.0))
    results = (
        ClientResult(cid=i, arrays=arrs(v), n_samples=n, metrics={"loss": loss})
        for i, (v, n, loss) in enumerate([(0.0, 1, 2.0), (2.0, 3, 4.0)])
    )
    _, metrics = s.aggregate_fit(1, results)
    assert metrics["loss"] == pytest.approx(3.5)
    assert metrics["server/n_clients"] == 2
    assert "server/pseudo_grad_norm" in metrics


def test_gradient_noise_scale_uniform_grads():
    """Identical client grads ⇒ zero noise ⇒ S≈0."""
    gns = GradientNoiseScale(ema_alpha=0.0)
    out = gns.update([4.0, 4.0], [10, 10], aggregate_sq_norm=4.0, total_samples=20)
    assert out["server/gns_trace_est"] == pytest.approx(0.0, abs=1e-9)
    assert out["server/gradient_noise_scale"] == pytest.approx(0.0, abs=1e-9)


def test_gradient_noise_scale_positive():
    gns = GradientNoiseScale(ema_alpha=0.0)
    # small-batch norms larger than big-batch ⇒ positive noise scale
    out = gns.update([5.0, 5.0], [10, 10], aggregate_sq_norm=3.0, total_samples=20)
    assert out["server/gradient_noise_scale"] > 0


# ---------------------------------------------------------------------------
# round-3 golden additions: weighted, two distinct layers, all five
# strategies against fully hand-computed values
# ---------------------------------------------------------------------------


def _two_layer_round(strategy, server=(4.0, -2.0)):
    """One round, 2 clients with unequal weights, 2 distinct layers.

    clients: c0 = (1, -1) with n=1;  c1 = (5, 3) with n=3
    weighted avg = (1*1+5*3)/4 , (-1*1+3*3)/4 = (4.0, 2.0)
    pseudo-grad g = x - avg = (0.0, -4.0)
    """
    strategy.initialize([np.full((2,), v, np.float32) for v in server])
    results = (
        ClientResult(
            cid=i,
            arrays=[np.full((2,), a, np.float32), np.full((2,), b, np.float32)],
            n_samples=n,
        )
        for i, (a, b, n) in enumerate([(1.0, -1.0, 1), (5.0, 3.0, 3)])
    )
    params, _ = strategy.aggregate_fit(1, results)
    return params[0][0], params[1][0]


def test_golden_weighted_fedavg_two_layers():
    s = FedAvgEff(server_learning_rate=0.5)
    l0, l1 = _two_layer_round(s)
    # x - 0.5*g: 4 - 0 = 4 ; -2 - 0.5*(-4) = 0
    np.testing.assert_allclose((l0, l1), (4.0, 0.0), rtol=1e-6)


def test_golden_weighted_nesterov_two_layers():
    s = FedNesterov(server_learning_rate=1.0, server_momentum=0.5)
    l0, l1 = _two_layer_round(s)
    # m = 0.5*0 + g = g; step = g + 0.5*g = 1.5g: (0, -6); x - step = (4, 4)
    np.testing.assert_allclose((l0, l1), (4.0, 4.0), rtol=1e-6)


def test_golden_weighted_fedmom_two_layers():
    s = FedMom(server_learning_rate=1.0, server_momentum=0.9)
    l0, l1 = _two_layer_round(s)
    # m = g; x - m = (4-0, -2-(-4)) = (4, 2)
    np.testing.assert_allclose((l0, l1), (4.0, 2.0), rtol=1e-6)


def test_golden_weighted_fedadam_two_layers():
    # t=1 bias correction cancels: m̂=g, v̂=g²; step = 0.1·g/(|g|+τ) ≈ 0.1·sign(g)
    # (τ>0 keeps the g=0 layer at exactly 0/τ = 0)
    s = FedAdam(server_learning_rate=0.1, server_beta_1=0.9, server_beta_2=0.99, server_tau=1e-9)
    l0, l1 = _two_layer_round(s)
    np.testing.assert_allclose(l0, 4.0, atol=1e-6)        # g=0: no movement
    np.testing.assert_allclose(l1, -2.0 + 0.1, rtol=1e-5)  # DESCENT: -η·sign(g)= +0.1
    # the sign decision (divergence note in strategy/optimizers.py): the step
    # moves TOWARD the client average (avg=2 > x=-2), unlike the reference's +g


def test_golden_weighted_fedyogi_two_layers():
    s = FedYogi(server_learning_rate=0.1, server_beta_1=0.9, server_beta_2=0.99, server_tau=1e-9)
    l0, l1 = _two_layer_round(s)
    # first step: v=(1-b2)g²·sign(g²-0)=(1-b2)g² == adam's first step
    np.testing.assert_allclose(l0, 4.0, atol=1e-6)
    np.testing.assert_allclose(l1, -2.0 + 0.1, rtol=1e-5)


def test_adaptive_descends_toward_client_average():
    """The sign decision, behaviorally: repeated rounds with clients pinned at
    avg=2 must move the server params toward 2, not away (the reference's
    ``x + η·…`` on ``g = x − avg`` walks away; see strategy/optimizers.py)."""
    for cls in (FedAdam, FedYogi):
        s = cls(server_learning_rate=0.5, server_tau=1e-9)
        s.initialize(arrs(-2.0))
        dist0 = abs(-2.0 - 2.0)
        v = -2.0
        for rnd in range(1, 6):
            v, _ = _round(s, [2.0, 2.0], rnd=rnd)
        assert abs(v - 2.0) < dist0, f"{cls.__name__} moved away from the client average"


# ---------------------------------------------------------------------------
# the chunked, pooled server update against the plain whole-array rules
# ---------------------------------------------------------------------------

CHUNK = 256
HYPER = dict(server_learning_rate=0.7, server_momentum=0.9, server_beta_1=0.9,
             server_beta_2=0.99, server_tau=1e-3)
RULES = {"fedavg": FedAvgEff, "nesterov": FedNesterov, "fedmom": FedMom,
         "fedadam": FedAdam, "fedyogi": FedYogi}


def _traced_peak(fn):
    """``fn()`` and the bytes tracemalloc saw allocated at its peak, numpy's
    buffers included."""
    tracemalloc.start()
    try:
        before, _ = tracemalloc.get_traced_memory()
        tracemalloc.reset_peak()
        out = fn()
        return out, tracemalloc.get_traced_memory()[1] - before
    finally:
        tracemalloc.stop()


def _plain_l2(arrays):
    return math.sqrt(sum(float(np.sum(np.square(a, dtype=np.float64))) for a in arrays))


class PlainRules:
    """The five rules as whole-array numpy expressions, one temporary an
    operation, as ``strategy/optimizers.py`` had them before the chunked
    pass: the oracle that pass is held to, bit for bit."""

    def __init__(self, name, params):
        self.name, self.x, self.t = name, [p.copy() for p in params], 0
        keys = {"fedavg": (), "nesterov": ("momentum",), "fedmom": ("momentum",)}.get(
            name, ("momentum_1", "momentum_2"))
        self.state = {k: [np.zeros_like(p) for p in params] for k in keys}

    def apply_average(self, avg, lr):
        mu, b1, b2, tau = (HYPER[k] for k in ("server_momentum", "server_beta_1",
                                              "server_beta_2", "server_tau"))
        g = [x - a for x, a in zip(self.x, avg)]
        out = []
        if self.name == "fedavg":
            out = [x - lr * gi for x, gi in zip(self.x, g)]
        elif self.name in ("nesterov", "fedmom"):
            m = self.state["momentum"]
            for i, (x, gi) in enumerate(zip(self.x, g)):
                m[i] = mu * m[i] + gi
                out.append(x - lr * (gi + mu * m[i] if self.name == "nesterov" else m[i]))
        else:
            self.t += 1
            m1, m2 = self.state["momentum_1"], self.state["momentum_2"]
            for i, (x, gi) in enumerate(zip(self.x, g)):
                m1[i] = b1 * m1[i] + (1.0 - b1) * gi
                g2 = np.square(gi)
                if self.name == "fedadam":
                    m2[i] = b2 * m2[i] + (1.0 - b2) * g2
                else:
                    m2[i] = m2[i] - (1.0 - b2) * g2 * np.sign(m2[i] - g2)
                m_hat = m1[i] / (1.0 - b1 ** self.t)
                v_hat = m2[i] / (1.0 - b2 ** self.t)
                out.append(x - lr * m_hat / (np.sqrt(v_hat) + tau))
        norms = {"server/pseudo_grad_norm": _plain_l2(g), "server/param_norm": _plain_l2(self.x)}
        norms.update({f"server/{k}_norm": _plain_l2(v) for k, v in self.state.items()})
        self.x = out
        return norms


def _model(rng, first_shape):
    # the array under test, then a matrix of 1.5 chunks and a vector
    return [rng.standard_normal(s).astype(np.float32) * np.float32(0.1)
            for s in (first_shape, (24, 16), (7,))]


@pytest.mark.parametrize("shape", [(1,), (CHUNK - 1,), (CHUNK,), (CHUNK + 1,),
                                   (5, CHUNK // 2), ()],
                         ids=["1", "chunk-1", "chunk", "chunk+1", "2.5chunks", "0-d"])
@pytest.mark.parametrize("threads", [1, 4])
@pytest.mark.parametrize("name", sorted(RULES))
def test_chunked_update_matches_plain_rules(monkeypatch, name, threads, shape):
    monkeypatch.setattr(aggregation, "_FOLD_CHUNK", CHUNK)
    rng = np.random.default_rng(3)
    init = _model(rng, shape)
    s = RULES[name](**HYPER)
    s.initialize([p.copy() for p in init])
    s.host_pool = HostPool(threads)
    plain = PlainRules(name, init)
    try:
        for rnd in (1, 2):
            avg = [x - g for x, g in zip(plain.x, _model(rng, shape))]
            got = s.apply_average(rnd, [a.copy() for a in avg], 10, 2)
            want = plain.apply_average(avg, s.effective_lr(2))
            for a, b in zip(s.current_parameters, plain.x):
                assert a.shape == b.shape and a.dtype == b.dtype
                np.testing.assert_array_equal(a, b)
            assert set(s.state) == set(plain.state)
            for key in plain.state:
                for a, b in zip(s.state[key], plain.state[key]):
                    np.testing.assert_array_equal(a, b)
            for key, value in want.items():
                assert got[key] == pytest.approx(value, rel=1e-12), key
    finally:
        s.host_pool.close()


def _reachable(s, avg):
    """Every array a caller could hold before ``apply_average``."""
    return (list(s.current_parameters) + list(avg)
            + [a for v in s.state.values() for a in v]
            + [a for v in s.state_for_checkpoint().values() for a in v])


@pytest.mark.parametrize("name", sorted(RULES))
def test_apply_average_rebinds_and_mutates_nothing(name):
    """The ``save_round_async`` contract: what a checkpoint writer or a
    pinned broadcast was handed before the call reads the same after it."""
    rng = np.random.default_rng(5)
    s = RULES[name](**HYPER)
    s.initialize(_model(rng, (40,)))
    s.host_pool = HostPool(4)
    s.apply_average(1, _model(rng, (40,)), 10, 2)  # state is no longer zeros
    avg = _model(rng, (40,))
    for a in avg:  # as np.asarray of a device array is
        a.setflags(write=False)
    params_list, state_lists = s.current_parameters, dict(s.state)
    ckpt = s.state_for_checkpoint()
    ckpt_members = {k: list(v) for k, v in ckpt.items()}
    held = _reachable(s, avg)
    copies = [a.copy() for a in held]
    s.apply_average(2, avg, 10, 2)
    s.host_pool.close()
    for a, c in zip(held, copies):
        np.testing.assert_array_equal(a, c)
    assert all(ckpt[k][i] is a for k, v in ckpt_members.items() for i, a in enumerate(v))
    assert s.current_parameters is not params_list
    for key in s.state_keys:
        assert s.state[key] is not state_lists[key]
    fresh = list(s.current_parameters) + [a for k in s.state_keys for a in s.state[k]]
    assert not any(np.shares_memory(n, o) for n in fresh for o in held)
    assert all(n.flags.writeable for n in fresh)  # the q8 clamp writes them


@pytest.mark.parametrize("name", sorted(RULES))
def test_apply_average_peak_memory_is_its_outputs(monkeypatch, name):
    """tracemalloc sees numpy's buffers: inside ``apply_average`` only the
    new parameters and the new state tensors are model-sized (the
    whole-array rules peaked at several models: a float32 pseudo-gradient,
    one temporary an operation, a float64 copy a norm)."""
    chunk = 1 << 16
    monkeypatch.setattr(aggregation, "_FOLD_CHUNK", chunk)
    rng = np.random.default_rng(7)
    shapes = [(2048, 1024), (1024, 1024), (1 << 20,), (1024,)]  # 4.2 M elements
    s = RULES[name](**HYPER)
    s.initialize([rng.standard_normal(sh, dtype=np.float32) for sh in shapes])
    avg = [rng.standard_normal(sh, dtype=np.float32) for sh in shapes]
    model_bytes = sum(a.nbytes for a in avg)
    s.host_pool = HostPool(4)
    _, peak = _traced_peak(lambda: s.apply_average(1, avg, 10, 2))
    s.host_pool.close()
    assert peak <= (1 + len(s.state_keys)) * model_bytes + 16 * chunk * 8


def test_diff_sumsq_reads_only_and_holds_no_array(monkeypatch):
    chunk = 1 << 12
    monkeypatch.setattr(aggregation, "_FOLD_CHUNK", chunk)
    rng = np.random.default_rng(9)
    xs = [rng.standard_normal(sh, dtype=np.float32) for sh in ((300, 1000), (chunk,), ())]
    ys = [rng.standard_normal(x.shape, dtype=np.float32) for x in xs]
    for a in xs + ys:
        a.setflags(write=False)
    want = (sum(float(np.sum(np.square(x - y, dtype=np.float64))) for x, y in zip(xs, ys)),
            sum(float(np.sum(np.square(x, dtype=np.float64))) for x in xs))
    for pool in (None, HostPool(1), HostPool(4)):
        got, peak = _traced_peak(lambda: aggregation.diff_sumsq(xs, ys, pool))
        assert got == pytest.approx(want, rel=1e-12)
        assert peak <= 16 * chunk * 8 < xs[0].nbytes
        assert aggregation.sumsq(xs, pool) == pytest.approx(want[1], rel=1e-12)
    with pytest.raises(ValueError):
        aggregation.diff_sumsq(xs, ys[:-1])


def test_server_update_takes_plain_arrays_and_is_the_seam():
    """``server_update(pseudo_grad, lr)`` on plain arrays (the device plane's
    oracle calls it so) equals the pass ``apply_average`` makes, and a
    strategy whose ``server_update`` is replaced uses the replacement."""
    rng = np.random.default_rng(11)
    init, avg = _model(rng, (40,)), _model(rng, (40,))
    a, b = (FedNesterov(**HYPER) for _ in range(2))
    a.initialize([p.copy() for p in init])
    b.initialize([p.copy() for p in init])
    a.apply_average(1, avg, 10, 2)
    b.current_parameters = b.server_update([x - y for x, y in zip(init, avg)], a.effective_lr(2))
    for x, y in zip(a.current_parameters + a.state["momentum"],
                    b.current_parameters + b.state["momentum"]):
        np.testing.assert_array_equal(x, y)

    class Kept(FedNesterov):
        def server_update(self, pseudo_grad, lr):
            assert len(pseudo_grad) == len(init)
            np.testing.assert_array_equal(pseudo_grad[0], init[0] - avg[0])
            return self.current_parameters

    k = Kept(**HYPER)
    k.initialize([p.copy() for p in init])
    metrics = k.apply_average(1, avg, 10, 2)
    np.testing.assert_array_equal(k.current_parameters[0], init[0])
    assert metrics["server/pseudo_grad_norm"] == pytest.approx(
        _plain_l2([x - y for x, y in zip(init, avg)]), rel=1e-12)


def test_client_norm_pass_reads_only_and_holds_no_array(tmp_path, monkeypatch):
    """The client's half of the same pass, inside a real ``fit``: both norms
    equal the whole-array expressions', what the client was sent
    (``initial``) and what it trained (``out_arrays``) are left as they were,
    the pass goes over the transport's pool, and it holds no array."""
    from photon_tpu.codec import params_to_ndarrays
    from photon_tpu.federation import ParamTransport, client_runtime
    from photon_tpu.federation.messages import FitIns
    from tests.test_federation import make_cfg

    chunk = 1 << 12
    monkeypatch.setattr(aggregation, "_FOLD_CHUNK", chunk)
    cfg = make_cfg(tmp_path)
    cfg.model.vocab_size = 8192  # an embedding of 64 chunks
    seen = {}

    def spy(xs, ys, pool):
        copies = [a.copy() for a in list(xs) + list(ys)]
        got, seen["peak"] = _traced_peak(lambda: aggregation.diff_sumsq(xs, ys, pool))
        for a, c in zip(list(xs) + list(ys), copies):
            np.testing.assert_array_equal(a, c)
        seen.update(xs=copies[:len(xs)], ys=copies[len(xs):], pool=pool)
        return got

    monkeypatch.setattr(client_runtime, "diff_sumsq", spy)
    rt = client_runtime.ClientRuntime(cfg.validate(), ParamTransport("inline", host_threads=4))
    meta, arrays = params_to_ndarrays(rt.trainer.state.params)
    sent = [a.copy() for a in arrays]
    rt.set_broadcast_params(rt.transport.put("init", meta, arrays))
    res = rt.fit(FitIns(server_round=1, cids=[0], params=None, local_steps=2,
                        server_steps_cumulative=0, config={}), cid=0)
    rt.close()
    assert res.error is None, res.error
    assert seen["pool"] is rt.transport.host_pool and seen["pool"].threads == 4
    for a, b in zip(seen["ys"], sent):  # the difference is against what was sent
        np.testing.assert_array_equal(a, b)
    assert seen["peak"] <= 16 * chunk * 8 < max(a.nbytes for a in sent)
    assert res.metrics["client/pseudo_grad_norm"] == pytest.approx(
        _plain_l2([o - i for o, i in zip(seen["xs"], seen["ys"])]), rel=1e-12)
    assert res.metrics["client/pseudo_grad_norm"] > 0
    assert res.metrics["client/param_norm"] == pytest.approx(_plain_l2(seen["xs"]), rel=1e-12)


def test_chunked_update_under_thread_pressure(monkeypatch):
    """More workers than cores and a short switch interval: chunks are
    disjoint and scratch is per thread, so twenty rounds on sixteen threads
    leave the bits an inline strategy leaves."""
    monkeypatch.setattr(aggregation, "_FOLD_CHUNK", 64)
    rng = np.random.default_rng(13)
    init = _model(rng, (40, 50))
    inline, pooled = (FedYogi(**HYPER) for _ in range(2))
    for s in (inline, pooled):
        s.initialize([p.copy() for p in init])
    pooled.host_pool = HostPool(16)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for rnd in range(1, 21):
            avg = _model(rng, (40, 50))
            want = inline.apply_average(rnd, avg, 10, 2)
            assert pooled.apply_average(rnd, avg, 10, 2) == want
    finally:
        sys.setswitchinterval(interval)
        pooled.host_pool.close()
    for a, b in zip(pooled.current_parameters + pooled.state["momentum_2"],
                    inline.current_parameters + inline.state["momentum_2"]):
        np.testing.assert_array_equal(a, b)
