"""LFM2 (``lfm2_moe``) on the training path, at a tiny size with the published
structure: a gated short convolution in three layers of four, grouped attention
with per-head q / k norms in the fourth, a leading dense layer, and the
sigmoid-routed dropless expert layer behind a selection bias in every other
one, so that the model has TWO expert stacks (``blocks_1``: attention,
``blocks_2``: conv), each with a bias of its own.

The plain reference is ``benchmark/reference/lfm2_moe.py`` (float32,
``Precision.HIGHEST``, the experts as a masked loop); on the CPU the program
runs ``attn_impl: xla`` in float32, so the two differ by the order of
summation alone and every tolerance below is a float32 one.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # `benchmark` is a sibling of `tests`, not installed
    sys.path.insert(0, str(ROOT))

from benchmark.reference import lfm2_moe as ref  # noqa: E402
from photon_tpu.config import load_preset  # noqa: E402
from photon_tpu.config.schema import Config  # noqa: E402
from photon_tpu.models import MPTModel, init_params  # noqa: E402
from photon_tpu.ops import moe, ssd  # noqa: E402
from photon_tpu.train.train_step import make_loss_fn  # noqa: E402
from photon_tpu.utils.profiling import (  # noqa: E402
    MAMBA_CONV_SCOPE,
    SHORTCONV_MIX_SCOPE,
    SHORTCONV_PROJ_SCOPE,
)

PRESET = "lfm2-8b-a1b-ep4"
TINY = dict(
    d_model=32, n_heads=4, n_kv_heads=2, max_seq_len=32, vocab_size=96,
    dense_mlp_hidden_size=48, mlp_hidden_size=24, moe_num_experts=8, moe_top_k=2,
    moe_experts_held=4, attn_impl="xla", compute_dtype="float32",
)
STACKS = [("blocks_0", "conv", True, 1), ("blocks_1", "attention", False, 1),
          ("blocks_2", "conv", False, 3)]


def tiny_cfg(**model):
    """The preset with every size shrunk and nothing of its structure changed:
    ``c a c c c`` with layer 0 dense, two key-value heads for four query heads,
    four of eight experts held."""
    cfg = load_preset(PRESET)
    for key, value in {**TINY, **model}.items():
        setattr(cfg.model, key, value)
    cfg.train.global_batch_size = 2
    cfg.train.device_microbatch_size = 2
    return cfg.validate()


def dims_of(cfg) -> dict:
    return ref.dims_of(dataclasses.asdict(cfg.model))


def leaf_names(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


TOKENS = np.random.default_rng(3).integers(0, 96, size=(2, 32)).astype(np.int32)

# ---------------------------------------------------------------------------
# the conv mixer alone
# ---------------------------------------------------------------------------


def test_taps_without_a_bias_are_the_taps_with_a_zero_bias():
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=(2, 9, 6)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(3, 6)), jnp.float32)
    got = ssd.causal_conv1d(u, kernel)
    np.testing.assert_array_equal(got, ssd.causal_conv1d(u, kernel, jnp.zeros(6)))
    np.testing.assert_allclose(got, ref.short_conv(u, kernel), atol=1e-6)
    # the Mamba-2 caller's sum still starts from its bias: `bias + tap_0 + ...`
    text = str(jax.make_jaxpr(ssd.causal_conv1d)(u, kernel, jnp.ones(6)))
    assert text.count(" add ") == 3 and str(
        jax.make_jaxpr(ssd.causal_conv1d)(u, kernel)).count(" add ") == 2


def _mixer_by_position(h, w_in, kernel, w_out):
    """``B | C | u = h W_in``; ``v = B * u``; ``w_t = sum_k kernel[k] v_(t - 2 +
    k)``; ``y = (C * w) W_out``: one row and position at a time, float64."""
    rows, seq, d = h.shape
    out = np.zeros((rows, seq, w_out.shape[1]))
    for r in range(rows):
        bcu = h[r] @ w_in
        b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
        v = b * u
        for t in range(seq):
            w = np.zeros(d)
            for k in range(kernel.shape[0]):
                if t - (kernel.shape[0] - 1) + k >= 0:
                    w += kernel[k] * v[t - (kernel.shape[0] - 1) + k]
            out[r, t] = (c[t] * w) @ w_out
    return out


@pytest.mark.parametrize("taps", [3, 1, 4])
def test_conv_mixer_matches_a_per_position_loop(taps):
    """The program's mixer (the block's own method, through ``apply``) and the
    reference's against the issue's equations walked position by position."""
    rng = np.random.default_rng(taps)
    d = 32
    h = rng.normal(size=(2, 11, d))
    w_in, w_out = rng.normal(size=(d, 3 * d)) * 0.3, rng.normal(size=(d, d)) * 0.3
    kernel = rng.normal(size=(taps, d))
    want = _mixer_by_position(h, w_in, kernel, w_out)
    assert float(np.max(np.abs(want))) > 1.0
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    p = {"in_proj": {"kernel": f32(w_in)}, "conv_kernel": f32(kernel),
         "out_proj": {"kernel": f32(w_out)}}
    np.testing.assert_allclose(ref.conv_mixer(f32(h), p, ref.MATMULS["float32"]), want,
                               atol=2e-4, rtol=2e-5)

    import flax.linen as nn

    from photon_tpu.models.mpt import MPTBlock

    class Mixer(MPTBlock):  # the block's own method, without the block around it
        @nn.compact
        def __call__(self, x):
            dense = lambda feats, name, std: nn.Dense(  # noqa: E731
                feats, use_bias=False, dtype=jnp.float32, name=name)
            return self._short_conv_mixer(x, dense, 0.02)

    got = Mixer(tiny_cfg(conv_kernel_size=taps).model, mixer="conv").apply(
        {"params": p}, f32(h))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-5)


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights in the program's layout, and loss + gradients of one
    batch from the program (float32 compute) and from the reference."""
    cfg = tiny_cfg()
    dims = dims_of(cfg)
    params = ref.make_params(dims, 7)
    n = TOKENS.shape[0] * (TOKENS.shape[1] - 1)
    got = jax.value_and_grad(make_loss_fn(MPTModel(cfg.model), 16))(params, TOKENS)
    want = jax.value_and_grad(lambda p: ref.ce_sum(p, TOKENS, dims) / n)(params)
    return cfg, dims, params, got, want


def test_the_stacks_are_runs_of_equal_mixer_and_mlp_kind():
    assert tiny_cfg().model.stacks == STACKS == ref.stacks(dims_of(tiny_cfg()))
    assert load_preset(PRESET).model.stacks == STACKS
    # the other families' stacks keep their names
    assert Config().model.stacks == [("blocks", "attention", False, 12)]
    assert load_preset("glm-4.7-flash-ep8").model.stacks == [
        ("dense_blocks", "attention", True, 1), ("blocks", "attention", False, 4)]
    assert load_preset("granite-4.0-h-micro-stage1").model.stacks == [
        ("blocks_0", "mamba", False, 5), ("blocks_1", "attention", False, 1),
        ("blocks_2", "mamba", False, 4)]
    # two leading dense conv layers are one stack; a dense attention layer
    # after them is another
    two = tiny_cfg(first_k_dense=2, layer_types="conv,conv,attention,conv,conv").model
    assert two.stacks == [("blocks_0", "conv", True, 2), ("blocks_1", "attention", False, 1),
                          ("blocks_2", "conv", False, 2)]
    three = tiny_cfg(first_k_dense=3, layer_types="conv,conv,attention,conv,conv").model
    assert [s[1:] for s in three.stacks] == [
        ("conv", True, 2), ("attention", True, 1), ("conv", False, 2)]


def test_init_gives_the_reference_tree():
    cfg = tiny_cfg()
    mine = init_params(cfg.model, seed=0)
    theirs = ref.make_params(dims_of(cfg), 0)
    assert leaf_names(mine) == leaf_names(theirs)
    assert jax.tree.map(jnp.shape, mine) == jax.tree.map(jnp.shape, theirs)
    assert sorted(mine) == ["blocks_0", "blocks_1", "blocks_2", "ln_f", "wte"]
    assert [mine[f"blocks_{i}"]["block"]["ln_1"]["scale"].shape[0] for i in range(3)] == [1, 1, 3]
    # each expert stack has a selection bias of its own, a row a layer
    assert mine["blocks_1"]["block"]["router_bias"].shape == (1, 8)
    assert mine["blocks_2"]["block"]["router_bias"].shape == (3, 8)
    assert "router" not in mine["blocks_0"]["block"]  # the leading layer is dense
    assert mine["blocks_2"]["block"]["conv_kernel"].shape == (3, 3, 32)


def test_forward_logits_match_reference(seeded):
    cfg, dims, params, _, _ = seeded
    logits = MPTModel(cfg.model).apply({"params": params}, TOKENS)
    want = ref.forward(params, TOKENS, dims)
    assert float(jnp.max(jnp.abs(want))) > 0.05
    # float32 on both sides: only the order of summation differs
    np.testing.assert_allclose(logits, want, atol=2e-5)


def test_loss_matches_reference(seeded):
    *_, (loss, _), (want, _) = seeded
    # float32 on both sides, chunked against whole log-softmax
    assert abs(float(loss) - float(want)) < 1e-5


LEAVES = leaf_names(ref.make_params(ref.dims_of({
    **dataclasses.asdict(load_preset(PRESET).model), **TINY}), 0))


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(seeded, leaf):
    *_, (_, got), (_, want) = seeded
    got = dict(zip(leaf_names(got), jax.tree.leaves(got)))[leaf]
    want = dict(zip(leaf_names(want), jax.tree.leaves(want)))[leaf]
    scale = float(jnp.max(jnp.abs(want)))
    if leaf.endswith("router_bias"):  # selects only: no gradient at all
        assert scale == 0 and not np.any(got)
        return
    # float32 on both sides; a leaf's largest entry runs from 1e-6 (a conv
    # layer's norm) to 1e-1 (the embedding), so the tolerance is relative to it
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=1e-4 * scale, rtol=1e-3)


def test_bfloat16_compute_stays_near_the_reference(seeded):
    cfg, dims, params, _, (want, _) = seeded
    low = tiny_cfg(compute_dtype="bfloat16")
    loss = make_loss_fn(MPTModel(low.model), 16)(params, TOKENS)
    assert abs(float(loss) - float(want)) < 2e-2


@pytest.mark.parametrize("t", [1, 2, 3, 17, 31])
def test_changing_a_token_leaves_every_earlier_output_bit_equal(seeded, t):
    """Through the taps (which reach two positions back) and attention: the
    logits before position ``t`` do not see token ``t``."""
    cfg, _, params, _, _ = seeded
    model = MPTModel(cfg.model)
    changed = TOKENS.copy()
    changed[:, t] = (changed[:, t] + 1) % 96
    a = np.asarray(model.apply({"params": params}, TOKENS))
    b = np.asarray(model.apply({"params": params}, changed))
    assert np.array_equal(a[:, :t], b[:, :t])
    assert not np.array_equal(a[:, t], b[:, t])


# ---------------------------------------------------------------------------
# two expert stacks: the bias moves per stack
# ---------------------------------------------------------------------------


def test_the_balancing_rows_reach_each_stacks_own_bias():
    """``_balance_router_bias`` by path: a tree with ``[1, E]`` and ``[3, E]``
    biases, each moved by the rows under its own stack's name and by no
    other's."""
    from photon_tpu.train.train_step import _balance_router_bias

    rows = {"blocks_1": jnp.asarray([[0.0, 20.0, 10.0, 10.0]]),
            "blocks_2": jnp.asarray([[10.0, 10.0, 20.0, 0.0], [10.0] * 4, [40.0, 0.0, 0.0, 0.0]])}
    params = {"wte": {"embedding": jnp.ones((4, 2))},
              "blocks_0": {"block": {"ln_1": {"scale": jnp.ones((1, 2))}}},
              "blocks_1": {"block": {"router_bias": jnp.zeros((1, 4)), "router": jnp.ones((1, 2, 4))}},
              "blocks_2": {"block": {"router_bias": jnp.zeros((3, 4)), "router": jnp.ones((3, 2, 4))}}}
    moved = _balance_router_bias(params, rows, 0.1)
    np.testing.assert_allclose(moved["blocks_1"]["block"]["router_bias"],
                               [[0.1, -0.1, 0.0, 0.0]], atol=1e-7)
    np.testing.assert_allclose(moved["blocks_2"]["block"]["router_bias"],
                               [[0.0, 0.0, -0.1, 0.1], [0.0] * 4, [-0.1, 0.1, 0.1, 0.1]], atol=1e-7)
    for stack in ("blocks_1", "blocks_2"):  # nothing else of the tree moves
        np.testing.assert_array_equal(moved[stack]["block"]["router"], params[stack]["block"]["router"])
    np.testing.assert_array_equal(moved["wte"]["embedding"], params["wte"]["embedding"])


def test_a_step_moves_each_router_bias_by_its_own_stacks_rows(seeded):
    """One optimizer step of the tiny model: the attention stack and the conv
    stack route differently, and each ``router_bias`` ends where the
    reference's rows OF THAT STACK put it."""
    from photon_tpu.optim import build_optimizer
    from photon_tpu.train import init_train_state
    from photon_tpu.train.train_step import make_train_step

    cfg, dims, params, _, _ = seeded
    model = MPTModel(cfg.model)
    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    state, _ = jax.jit(make_train_step(model, tx, loss_chunk_tokens=16))(
        init_train_state(model, tx, params), TOKENS)
    _, rows = ref.forward_and_rows(params, TOKENS, dims)
    assert rows["blocks_1"].shape == (1, 8) and rows["blocks_2"].shape == (3, 8)
    assert float(jnp.sum(rows["blocks_1"])) == 2 * 32 * 2  # every assignment, once
    assert not np.array_equal(rows["blocks_1"][0], rows["blocks_2"][0])
    speed = cfg.model.moe_bias_update_speed
    for stack in ("blocks_1", "blocks_2"):
        before = params[stack]["block"]["router_bias"]
        after = state.params[stack]["block"]["router_bias"]
        np.testing.assert_allclose(after, before - ref.bias_step(rows[stack], speed), atol=1e-7)
        assert float(jnp.max(jnp.abs(after - before))) > 0.01
    # the other stack's first row would have moved this one elsewhere
    wrong = params["blocks_1"]["block"]["router_bias"] - ref.bias_step(rows["blocks_2"][:1], speed)
    assert float(jnp.max(jnp.abs(
        wrong - state.params["blocks_1"]["block"]["router_bias"]))) > 0.01


@pytest.mark.parametrize("microbatches", [1, 2])
def test_three_adopt_steps_follow_the_reference(microbatches):
    """Three optimizer steps through ``Trainer`` and through the reference's
    ``Grad`` + ``adopt_step``: every leaf, both selection biases among them
    (the rows summed over microbatches, stack by stack). The speed is large
    here so that a bias changes who is chosen within three steps."""
    from benchmark.program import optimizer_settings
    from photon_tpu.train.trainer import Trainer

    cfg = tiny_cfg(moe_bias_update_speed=0.2)
    cfg.scheduler.t_warmup = 1  # a learning rate from the second step on
    cfg.train.global_batch_size = 4
    cfg.train.device_microbatch_size = 4 // microbatches
    dims = dims_of(cfg)
    params0 = ref.make_params(dims, 11)
    rows = np.concatenate([TOKENS, np.roll(TOKENS, 5, axis=1)])
    batches = [np.roll(rows, i, axis=1) for i in range(3)]
    trainer = Trainer(cfg, params=jax.tree.map(jnp.array, params0))
    losses = [trainer.fit([b], duration_steps=1)["loss"] for b in batches]
    got = trainer.state.params

    opt = optimizer_settings(cfg)
    grad = ref.Grad(dims, rows=2)
    want, state = params0, ref.adopt_init(params0)
    for batch, loss in zip(batches, losses):
        ref_loss, g = grad(want, batch)
        assert abs(float(loss) - ref_loss) < 1e-5
        # the balancing steps ride the gradient tree and are no part of the gradient
        clipped = ref.clip_by_global_norm(g, 1.0)
        assert not any(np.any(clipped[s]["block"]["router_bias"]) for s in ("blocks_1", "blocks_2"))
        want, state = ref.adopt_step(want, state, g, opt)

    for stack in ("blocks_1", "blocks_2"):
        bias0 = np.asarray(params0[stack]["block"]["router_bias"])
        bias = np.asarray(got[stack]["block"]["router_bias"])
        assert np.max(np.abs(bias - bias0)) > 0.05  # it moved, by up to 3 x 0.2
        np.testing.assert_allclose(bias, want[stack]["block"]["router_bias"], atol=1e-6)
    for name, a, b in zip(leaf_names(got), jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    change = lambda p: ref.leaf_norms(jax.tree.map(jnp.subtract, p, params0))  # noqa: E731
    assert ref.worst_leaf_gap(change(got), change(want)) < 1e-3


def test_fit_returns_the_routing_counters_of_both_stacks():
    from photon_tpu.train.trainer import Trainer
    from photon_tpu.utils.profiling import (
        MOE_DISPATCH_ROWS_MOVED, MOE_DISPATCH_ROWS_STATIC, MOE_MAX_EXPERT_LOAD, MOE_ROWS_HELD)

    cfg = tiny_cfg()
    cfg.train.global_batch_size, cfg.train.device_microbatch_size = 4, 2  # two microbatches
    trainer = Trainer(cfg, init_seed=0)
    out = trainer.fit([np.concatenate([TOKENS, TOKENS])] * 2, duration_steps=2)
    # 4 rows x 32 tokens x top-2 x 4 expert layers = 1,024 assignments, about
    # half of them to the 4 of 8 experts held here
    assert 256 <= out[MOE_ROWS_HELD] <= 768
    assert 1.0 <= out[MOE_MAX_EXPERT_LOAD] <= 4.0
    # two un-permutes a layer over both stacks' layers; a tiny layer is one chunk
    assert out[MOE_DISPATCH_ROWS_MOVED] == out[MOE_DISPATCH_ROWS_STATIC] == 2 * 1024


# ---------------------------------------------------------------------------
# the share: what expert parallelism asks of the layer
# ---------------------------------------------------------------------------


def _layer_weights(seed: int, n_experts: int = 32, d: int = 32, hidden: int = 24):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape, scale=0.2: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * scale, jnp.float32)
    return {"router": f32(d, n_experts, scale=0.5), "router_bias": f32(n_experts, scale=0.05),
            "moe_gate": f32(n_experts, d, hidden), "moe_up": f32(n_experts, d, hidden),
            "moe_down": f32(n_experts, hidden, d)}


@pytest.mark.parametrize("held", [32, 8])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """Sigmoid top-4 of 32 experts, the published counts: the routed parts of
    the four shares of 8 (nothing is computed alike on every chip but the
    router: no shared expert) add up to the layer with every expert held,
    which the reference computes as a masked loop."""
    p = _layer_weights(1)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(2, 24, 32)), jnp.float32)
    uncut = dict(top_k=4, routed_scale=1.0, gate_eps=1e-6, experts_held=32, first_expert=0)
    want = ref.routed_experts(h, p, uncut, ref.MATMULS["float32"])
    parts, rows, by_expert = [], 0.0, 0.0
    for first in range(0, 32, held):
        sl = slice(first, first + held)
        out, counters = moe.dropless_moe_mlp(
            h, p["router"], p["router_bias"], p["moe_gate"][sl], p["moe_up"][sl],
            p["moe_down"][sl], top_k=4, first_expert=first, routed_scale=1.0,
            gate_eps=1e-6, compute_dtype=jnp.float32)
        parts.append(out)
        rows += float(counters["rows_held"])
        by_expert = counters["expert_rows"]  # every share routes over all 32
    assert rows == 2 * 24 * 4  # every assignment is some share's, once
    assert float(jnp.sum(by_expert)) == 2 * 24 * 4
    np.testing.assert_allclose(sum(parts), want, atol=1e-5)
    if held < 32:  # and one share alone is not the layer
        assert float(jnp.max(jnp.abs(parts[0] - want))) > 1e-3


def test_the_gates_denominator_takes_the_published_epsilon():
    """``g = s / (sum of the picked s + eps)``: 1e-6 here, glm's 1e-20 by
    default (its gates are unchanged: the parameter's default is the constant
    that was there)."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)) * 0.3, jnp.float32)
    scores = np.asarray(jax.nn.sigmoid(h @ w))
    idx, gates = moe.sigmoid_route(h, w, jnp.zeros(8), 2, 1.0, eps=0.5)
    picked = np.take_along_axis(scores, np.asarray(idx), axis=-1)
    np.testing.assert_allclose(gates, picked / (picked.sum(-1, keepdims=True) + 0.5), rtol=1e-6)
    default = moe.sigmoid_route(h, w, jnp.zeros(8), 2, 1.0)[1]
    np.testing.assert_array_equal(default, moe.sigmoid_route(h, w, jnp.zeros(8), 2, 1.0, 1e-20)[1])
    assert "e-21:f32[]" in str(jax.make_jaxpr(  # float32's 1e-20 prints as 9.99...e-21
        lambda h: moe.sigmoid_route(h, w, jnp.zeros(8), 2, 1.8)[1])(h))
    assert Config().model.moe_gate_eps == 1e-20 == load_preset(
        "glm-4.7-flash-ep8").model.moe_gate_eps
    assert load_preset(PRESET).model.moe_gate_eps == 1e-6


@pytest.mark.parametrize("dim,tile", [(1792, 896), (2048, 1024), (1536, 768), (65536, 1024)])
def test_grouped_tiles_divide_the_expert_width(dim, tile):
    """1,792 = 7 x 256 has no divisor at the 1,024 cap but 896: the tile
    divides the dimension, so nothing is padded and masked."""
    assert moe._tiles(moe.GMM_TILING, 65536, dim, dim)[1:] == (tile, tile)
    assert dim % tile == 0 and tile % 128 == 0


# ---------------------------------------------------------------------------
# the two scopes, as the trace's readers find them
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def step_op_names():
    """The ``op_name``s of the tiny model's whole compiled train step."""
    from photon_tpu.optim import build_optimizer
    from photon_tpu.train import init_train_state
    from photon_tpu.train.train_step import make_train_step

    cfg = tiny_cfg()
    model = MPTModel(cfg.model)
    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    state = init_train_state(model, tx, init_params(cfg.model, seed=0))
    compiled = jax.jit(make_train_step(model, tx, loss_chunk_tokens=16)).lower(
        state, jnp.asarray(TOKENS)).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', compiled)


def test_every_operation_of_the_conv_mixer_is_under_one_of_its_two_scopes(step_op_names):
    mixer = [n for n in step_op_names if "_short_conv_mixer" in n]
    assert len(mixer) > 30
    proj = re.compile(rf"\b{SHORTCONV_PROJ_SCOPE}\b")
    mix = re.compile(rf"\b{SHORTCONV_MIX_SCOPE}\b")
    neither = [n for n in mixer if not proj.search(n) and not mix.search(n)]
    both = [n for n in mixer if proj.search(n) and mix.search(n)]
    assert not neither and not both, (neither, both)
    # the projections by their modules' names, the taps and gates by none
    for name in ("in_proj", "out_proj"):
        mine = [n for n in mixer if f"/{name}/" in n]
        assert mine and all(proj.search(n) for n in mine), name
    assert any(re.search(rf"{SHORTCONV_MIX_SCOPE}/(mul|pad|split|slice|add)", n) for n in mixer)
    # nothing outside the mixer carries either
    stray = [n for n in step_op_names if "_short_conv_mixer" not in n
             and (proj.search(n) or mix.search(n))]
    assert not stray, stray


@pytest.mark.parametrize("scope", [SHORTCONV_PROJ_SCOPE, SHORTCONV_MIX_SCOPE])
def test_new_scope_is_on_forward_backward_and_recomputation(step_op_names, scope):
    hits = [n for n in step_op_names if re.search(rf"\b{scope}\b", n)]
    assert any("transpose(" not in n and "rematted" not in n for n in hits), scope
    assert any("transpose(jvp(" in n for n in hits), scope
    assert any("rematted_computation" in n for n in hits), scope
    # in both stacks that hold conv layers
    for stack in ("blocks_0", "blocks_2"):
        assert any(f"/{stack}/" in n for n in hits), (scope, stack)
    assert not any("/blocks_1/" in n for n in hits)  # the attention layer has none


def test_the_two_families_patterns_never_match_each_others_operations(step_op_names):
    """``mamba_conv_ms_train``'s pattern finds nothing in this step, and the
    new readers' patterns nothing in a Mamba-2 step's names."""
    mamba = re.compile(rf"\b{MAMBA_CONV_SCOPE}\b")
    assert not [n for n in step_op_names if mamba.search(n)]
    new = re.compile(r"\bshortconv/(proj|mix)\b")
    theirs = ("jit(train_step)/train_step/forward_backward/jvp(MPTModel)/blocks_0/while/body/"
              "closed_call/block/block._mamba_mixer/mamba/conv/mul:",
              "jit(train_step)/.../block._mamba_mixer/mamba/proj/in_proj/dot_general:")
    assert not [n for n in theirs if new.search(n)]
    assert new.search("jit(train_step)/.../block._short_conv_mixer/shortconv/mix/mul:")


def test_every_operation_of_the_step_carries_a_stage_and_the_experts_their_scopes(
        step_op_names):
    own = [n for n in step_op_names if n.startswith("jit(train_step)/")]
    assert len(own) > 300
    hoisted = [n for n in own if re.match(r"jit\(train_step\)/blocks_\d/block/", n)]
    assert not sorted({n for n in own if "train_step/" not in n} - set(hoisted))
    # both expert stacks' operations are under the dropless layer's scopes
    for stack in ("blocks_1", "blocks_2"):
        for scope in ("moe/router", "moe/dispatch", "moe/experts"):
            assert any(f"/{stack}/" in n and scope in n for n in own), (stack, scope)
    assert any("/blocks_0/" in n and "block/mlp" in n for n in own)  # the dense layer


# ---------------------------------------------------------------------------
# the published cut, its rules, and who refuses the family
# ---------------------------------------------------------------------------


def test_the_published_width_cut_counts_its_parameters():
    """``jax.eval_shape`` of the preset's own tree: ISSUE 41's table, to the
    parameter."""
    model = load_preset(PRESET).model
    shapes = jax.eval_shape(lambda: init_params(model, seed=0))
    count = lambda t: sum(math.prod(a.shape) for a in jax.tree.leaves(t))  # noqa: E731
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    experts = 2048 * 32 + 32 + 8 * 3 * 2048 * 1792
    assert (conv, attention, experts) == (16_783_360, 10_485_888, 88_145_952)
    assert count(shapes["blocks_0"]) == conv + 4096 + 3 * 2048 * 7168 == 60_827_648
    assert count(shapes["blocks_1"]) == attention + 4096 + experts == 98_635_936
    assert count(shapes["blocks_2"]) == 3 * (conv + 4096 + experts) == 3 * 104_933_408
    assert count(shapes["wte"]) + count(shapes["ln_f"]) == 33_556_480
    assert count(shapes) == 507_820_288
    block = shapes["blocks_2"]["block"]
    assert block["in_proj"]["kernel"].shape == (3, 2048, 6144)
    assert block["conv_kernel"].shape == (3, 3, 2048)
    assert block["moe_gate"].shape == (3, 8, 2048, 1792)
    assert block["router"].shape == (3, 2048, 32) and block["router_bias"].shape == (3, 32)
    assert shapes["blocks_1"]["block"]["k_proj"]["kernel"].shape == (1, 2048, 512)
    assert shapes["blocks_1"]["block"]["q_norm"]["scale"].shape == (1, 64)
    # the reference's tree is the same one
    theirs = jax.eval_shape(lambda: ref.make_params(ref.dims_of(dataclasses.asdict(model)), 0))
    assert jax.tree.map(lambda a: a.shape, theirs) == jax.tree.map(lambda a: a.shape, shapes)


def test_the_preset_is_what_the_benchmark_configuration_states():
    from benchmark.program import build_config

    config = json.loads((ROOT / "benchmark/configs" / f"{PRESET}.json").read_text())
    traffic = json.loads((ROOT / "benchmark/traffic/ep4-share-2x8192.json").read_text())
    cfg = build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=2**31 + 5)
    assert (cfg.train.global_batch_size, cfg.train.device_microbatch_size) == (2, 2)
    assert cfg.model.stacks == STACKS and cfg.model.conv_layers == 4
    assert cfg.model.d_head == 64 and cfg.model.training_path_only
    assert config["parameters"] == 507_820_288
    # a preset edited under the benchmark is refused
    config["model"]["conv_kernel_size"] = 4
    with pytest.raises(ValueError, match="conv_kernel_size"):
        build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=1)


def test_model_flops_per_token_counts_the_family():
    """The program's own estimate against the benchmark's cost file at the
    expected rows: they differ by the attention layer's other half square
    (the program counts the full one) and by nothing else."""
    from benchmark.costs import lfm2_moe_train as cost
    from photon_tpu.utils.profiling import model_flops_per_token

    model = load_preset(PRESET).model
    m = json.loads((ROOT / "benchmark/configs" / f"{PRESET}.json").read_text())["model"]
    parts = cost.parts_per_token(m, cost.expected_routed_rows_per_token(m))
    assert model_flops_per_token(model) == pytest.approx(
        sum(parts.values()) + parts["flash_core"], rel=1e-3)


def test_every_parameter_has_a_sharding_rule():
    """No leaf of the family falls through to the replicate-unknowns default,
    under any of its three stacks."""
    from jax.sharding import PartitionSpec as P

    from photon_tpu.config.schema import MeshConfig
    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.parallel.sharding import _RULES, param_specs

    params = init_params(tiny_cfg().model, seed=0)
    names = leaf_names(params)
    assert not [n for n in names if not any(re.search(p, n) for p, _ in _RULES)]
    mesh = make_mesh(MeshConfig(fsdp=2, expert=2), devices=jax.devices()[:4])
    specs = param_specs(params, mesh)
    conv = specs["blocks_2"]["block"]
    assert conv["in_proj"]["kernel"] == P("pipe", "fsdp", None)  # B | C | u stay whole
    assert conv["out_proj"]["kernel"] == P("pipe", "tensor", "fsdp")
    assert conv["conv_kernel"] == P("pipe", None, None)
    for stack in ("blocks_1", "blocks_2"):  # the expert names under either stack
        assert specs[stack]["block"]["moe_up"] == P("pipe", "expert", "fsdp", "tensor")
        assert specs[stack]["block"]["router_bias"] == P("pipe", None)


def test_trainer_tells_the_conv_layers_on_its_span():
    from photon_tpu.models.step import step_attrs

    # (no kernel in a step on the CPU backend: the flash plan adds no key)
    told = lambda model: step_attrs(model, batch_rows=2).steps  # noqa: E731
    assert told(load_preset(PRESET).model) == {"conv_layers": 4}
    assert told(tiny_cfg().model) == {"conv_layers": 4}
    assert told(load_preset("mpt-125m").model) == {}
    assert "conv_layers" not in told(load_preset("granite-4.0-h-micro-stage1").model)


def test_a_federated_client_fit_trains_the_family(tmp_path):
    """Through ``Trainer`` and ``StreamingLoader`` like every other model: the
    loss falls over a few steps on repeated rows."""
    from photon_tpu.data import ShardedDataset, StreamingLoader
    from photon_tpu.data.shard_format import ShardWriter
    from photon_tpu.parallel.mesh import single_device_mesh
    from photon_tpu.train.trainer import Trainer

    cfg = tiny_cfg()
    cfg.scheduler.t_warmup = 1
    cfg.photon.save_path = str(tmp_path / "save")
    with ShardWriter(tmp_path / "rows", 32, 96, samples_per_shard=8) as w:
        w.write(np.tile(TOKENS, (4, 1)))
    trainer = Trainer(cfg, mesh=single_device_mesh(jax.devices()[0]))
    loader = StreamingLoader(ShardedDataset(tmp_path / "rows"), batch_size=2, seed=1,
                             shuffle=False)
    first = trainer.fit(loader, 1)["loss"]
    last = trainer.fit(loader, 6)["loss"]
    assert last < first


def _refuse_serving():
    from photon_tpu.serve.engine import PagedEngine

    PagedEngine(tiny_cfg(), params={})


def _refuse_decode():
    from photon_tpu.models.decode import prefill

    prefill({}, jnp.zeros((1, 4), jnp.int32), jnp.array([4]), tiny_cfg().model)


def _refuse_hf_export():
    from photon_tpu.checkpoint.hf_export import llama_state_dict

    llama_state_dict({}, tiny_cfg().model)


def _refuse_hf_import():
    from photon_tpu.checkpoint.hf_import import llama_params_from_hf

    llama_params_from_hf({}, tiny_cfg().model)


@pytest.mark.parametrize("call", [_refuse_serving, _refuse_decode,
                                  _refuse_hf_export, _refuse_hf_import],
                         ids=lambda f: f.__name__.removeprefix("_refuse_"))
def test_serving_decode_and_hf_interop_refuse_the_family(call):
    with pytest.raises(NotImplementedError, match="training path only"):
        call()


def test_hf_import_refuses_the_model_type():
    from photon_tpu.checkpoint.hf_import import model_config_from_hf

    with pytest.raises(ValueError, match="lfm2_moe"):
        model_config_from_hf({"model_type": "lfm2_moe"})


def _with(cfg, **paths):
    for dotted, value in paths.items():
        obj = cfg
        *parents, leaf = dotted.split("__")
        for name in parents:
            obj = getattr(obj, name)
        setattr(obj, leaf, value)
    return cfg


@pytest.mark.parametrize("change, message", [
    (dict(model__layer_types="conv,attention,conv"), "needs n_layers=5"),
    (dict(model__layer_types="conv,linear_attention,conv,conv,conv"),
     "'mamba', 'conv', 'attention', 'full_attention' or"),
    (dict(model__conv_kernel_size=0), "conv_kernel_size > 0"),
    (dict(model__moe_router="softmax", model__moe_experts_held=0, model__moe_gate_eps=1e-20,
          model__moe_bias_update_speed=0.0), "needs a dropless router"),
    (dict(model__moe_gate_eps=0.0), "moe_gate_eps"),
    (dict(model__moe_router="softmax_topk", model__moe_bias_update_speed=0.0,
          model__moe_gate_eps=1e-6), "moe_gate_eps belongs to moe_router='sigmoid'"),
    (dict(model__first_k_dense=5), "0 < first_k_dense < n_layers"),
    (dict(model__kv_lora_rank=8, model__q_lora_rank=8, model__qk_nope_head_dim=4,
          model__qk_rope_head_dim=4, model__v_head_dim=8, model__n_kv_heads=0,
          model__qk_norm=False), "does not combine with latent attention"),
    (dict(mesh__pipe=5), "mesh.pipe > 1"),
    (dict(mesh__tensor=2), "mesh.tensor > 1 with 'conv' layers"),
    (dict(mesh__sequence=2), "mesh.sequence > 1 or mesh.tensor > 1 with 'conv' layers"),
    (dict(mesh__expert=2), "mesh.expert > 1 with moe_router='sigmoid'"),
    (dict(model__lora_rank=4), "LoRA adapters"),
    (dict(photon__adapters__enabled=True), "LoRA adapters"),
    (dict(photon__serve__enabled=True), "photon.serve"),
    (dict(photon__serve__prefix_cache=True), "photon.serve"),
])
def test_schema_refuses_what_the_family_cannot_do_yet(change, message):
    cfg = tiny_cfg()
    with pytest.raises(ValueError, match=message):
        _with(cfg, **change).validate()


def test_layer_types_and_the_new_fields_survive_yaml_and_json(tmp_path):
    cfg = tiny_cfg()
    cfg.to_yaml(tmp_path / "resolved.yaml")
    back = Config.from_yaml(tmp_path / "resolved.yaml").validate()
    assert back.model.layer_kinds == ("conv", "attention", "conv", "conv", "conv")
    assert (back.model.conv_kernel_size, back.model.moe_gate_eps) == (3, 1e-6)
    assert Config.from_json(cfg.to_json()).model.stacks == STACKS
    assert Config().model.conv_layers == 0 and not Config().model.training_path_only
