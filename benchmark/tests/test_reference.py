"""``reference/mpt.py`` against the program's ``models/mpt.py`` at a tiny
size on the CPU, and its optimizer against the program's."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.tests import toy
from benchmark.reference import mpt as ref


@pytest.fixture(scope="module")
def tiny():
    from photon_tpu.config.schema import ModelConfig

    model = dict(toy.TOY_MODEL)
    cfg = ModelConfig(**{k: v for k, v in model.items() if k != "d_head"})
    dims = ref.dims_of(model)
    params = ref.make_params(dims, seed=2**31 + 5)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 32), 0, 128)
    return cfg, dims, params, tokens


def test_forward_matches_the_programs_model(tiny):
    from photon_tpu.models import MPTModel

    cfg, dims, params, tokens = tiny
    want = MPTModel(cfg).apply({"params": params}, tokens)
    got = ref.forward(params, tokens, dims)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_loss_matches_the_programs_loss(tiny):
    from photon_tpu.models import MPTModel
    from photon_tpu.train.train_step import make_loss_fn

    cfg, dims, params, tokens = tiny
    want = make_loss_fn(MPTModel(cfg))(params, tokens)
    got = ref.ce_sum(params, tokens, dims) / (tokens.shape[0] * (tokens.shape[1] - 1))
    assert float(got) == pytest.approx(float(want), rel=1e-5)


def test_lower_precision_moves_the_logits(tiny):
    _, dims, params, tokens = tiny
    exact = ref.forward(params, tokens, dims)
    gaps = {mm: float(jnp.max(jnp.abs(ref.forward(params, tokens, dims, mm) - exact)))
            for mm in ("bfloat16", "int8")}
    assert 0 < gaps["bfloat16"] < gaps["int8"]


def test_adopt_matches_the_programs_optimizer(tiny):
    from photon_tpu.config.schema import OptimizerConfig, SchedulerConfig
    from photon_tpu.optim import build_optimizer

    _, dims, params, _ = tiny
    opt = {"name": "adopt", "lr": 6e-4, "betas": (0.9, 0.9999), "eps": 1e-6,
           "grad_clip_norm": 1.0, "t_warmup": 2, "t_max": 10, "alpha_f": 0.1}
    tx, _ = build_optimizer(
        OptimizerConfig(name="adopt", lr=6e-4, grad_clip_norm=1.0),
        SchedulerConfig(t_warmup=2, t_max=10, alpha_f=0.1))
    their_state, ours, our_state = tx.init(params), params, ref.adopt_init(params)
    theirs = params
    for i in range(4):
        grads = jax.tree.map(
            lambda p: jax.random.normal(jax.random.PRNGKey(i), p.shape) * 3.0, params)
        updates, their_state = tx.update(grads, their_state, theirs)
        theirs = jax.tree.map(jnp.add, theirs, updates)
        ours, our_state = ref.adopt_step(ours, our_state, grads, opt)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-8)


def test_worst_leaf_gap_uses_the_median_leaf_as_floor():
    want = {"a": np.array([1.0]), "blocks/b": np.array([2.0, 1e-9]), "c": np.array([4.0])}
    got = {"a": np.array([1.1]), "blocks/b": np.array([2.0, 2e-9]), "c": np.array([4.0])}
    # the median leaf norm is 1.5: the all-but-zero leaf (gap 1e-9) and leaf
    # "a" (gap 0.1, own norm 1.0) are both measured against it
    assert ref.worst_leaf_gap(got, want) == pytest.approx(0.1 / 1.5, rel=1e-6)
