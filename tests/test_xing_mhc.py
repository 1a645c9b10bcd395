"""Xing4.0-29B-A4B (``xing4_0``) on the training path, at a tiny size with the
published structure: four hyper-connected residual streams mixed by
Sinkhorn-projected maps around every sublayer, latent attention whose v is
narrower than its q and k under YaRN's frequencies, a leading dense layer and
the sigmoid-routed dropless expert layer with a shared expert.

The plain reference is ``benchmark/reference/xing_mhc_moe.py`` (float32,
``Precision.HIGHEST``, the streams one ``[B, S, n, C]`` array, the Sinkhorn
sums ``jnp.sum``, attention per head in query blocks); on the CPU the program
runs ``attn_impl: xla`` in float32, so the two differ by the order of
summation alone and every tolerance below is a float32 one: 1e-5 on a loss
or a logit (values of order 1, a few hundred float32 additions apart), 2e-5 on
a stepped weight, 1e-3 on a leaf norm's relative gap. Maps rounded to bfloat16
(eight bits of ``H_res``) move the logits by over 1e-4 and a map's gradient by
over a thousandth, and fail both (``test_bfloat16_maps_fail_the_tolerances``).
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

from benchmark.reference import xing_mhc_moe as ref  # noqa: E402
from photon_tpu.config import load_preset  # noqa: E402
from photon_tpu.config.schema import Config  # noqa: E402
from photon_tpu.models import MPTModel, init_params  # noqa: E402
from photon_tpu.models import mpt  # noqa: E402
from photon_tpu.ops import moe  # noqa: E402
from photon_tpu.ops.attention import xla_attention  # noqa: E402
from photon_tpu.ops.flash_attention import (  # noqa: E402
    flash_attention, lane_padded, launch_vmem_bytes, pick_tiles)
from photon_tpu.train.train_step import make_loss_fn  # noqa: E402
from photon_tpu.utils.profiling import (  # noqa: E402
    MHC_MAPS_SCOPE, MHC_READ_IN_SCOPE, MHC_SINKHORN_GAP, MHC_WRITE_BACK_SCOPE)
from tests._helpers import TINY_PRESETS, tiny_preset  # noqa: E402

PRESET = "xing4.0-29b-a4b-ep8"
TINY = {**TINY_PRESETS[PRESET], "attn_impl": "xla", "compute_dtype": "float32"}


def tiny_cfg(**model):
    """The preset with every size shrunk and nothing of its structure changed:
    four streams, 20 Sinkhorn rounds, v narrower than q and k, YaRN over a
    quarter of the row, one dense and two expert layers, four of eight held."""
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
# the maps alone
# ---------------------------------------------------------------------------


def _streams_and_maps(seed: int = 0, scale: float = 1.0):
    cfg = tiny_cfg().model
    rng = np.random.default_rng(seed)
    streams = tuple(jnp.asarray(rng.normal(size=(2, 5, 32)), jnp.float32) for _ in range(4))
    phi = jnp.asarray(rng.normal(size=(24, 128)) * 0.3 * scale, jnp.float32)
    b = jnp.asarray(rng.normal(size=(24,)) * scale, jnp.float32)
    alpha = jnp.asarray([0.5, -0.7, 0.2], jnp.float32)
    return cfg, streams, phi, b, alpha


def test_the_mixing_matrix_is_doubly_stochastic_and_the_weights_stay_in_their_ranges():
    """Rows sum to 1 within ``hc_eps`` (they were divided last: ``1 - eps /
    sum``, and a row's sum is of order 1), columns within 1e-3 after 20 rounds
    from logits of order 1; ``H_pre`` in (0, 1), ``H_post`` in (0, 2); the sown
    gap is the larger of the two distances."""
    cfg, streams, phi, b, alpha = _streams_and_maps()
    pre, post, res, gap = mpt._hc_maps(cfg, streams, phi, b, alpha)
    assert pre.shape == post.shape == (4, 10) and res.shape == (4, 4, 10)
    assert pre.dtype == res.dtype == jnp.float32
    rows, cols = jnp.sum(res, axis=1), jnp.sum(res, axis=0)
    np.testing.assert_allclose(rows, 1.0, atol=5e-6)
    np.testing.assert_allclose(cols, 1.0, atol=1e-3)
    assert float(gap) == pytest.approx(
        max(float(jnp.max(jnp.abs(rows - 1))), float(jnp.max(jnp.abs(cols - 1)))))
    assert 0 < float(jnp.min(pre)) and float(jnp.max(pre)) < 1
    assert 0 < float(jnp.min(post)) and float(jnp.max(post)) < 2
    assert float(jnp.min(res)) > 0
    # the reference's maps from the same numbers, tokens first
    x = jnp.stack(streams, axis=2)
    p = {"hc_1_phi": phi, "hc_1_b": b, "hc_1_alpha": alpha}
    want = ref.hyper_maps(x, p, "hc_1", dims_of(tiny_cfg()), ref.MATMULS["float32"])
    np.testing.assert_allclose(pre.reshape(4, 2, 5).transpose(1, 2, 0), want[0], atol=1e-6)
    np.testing.assert_allclose(post.reshape(4, 2, 5).transpose(1, 2, 0), want[1], atol=2e-6)
    np.testing.assert_allclose(res.reshape(4, 4, 2, 5).transpose(2, 3, 0, 1), want[2], atol=2e-6)


def test_the_clamp_cuts_the_logits_before_the_exponential():
    """Logits of +-200 would overflow float32's ``exp``; cut to +-30 every
    entry stays finite and the projection still ends on row sums of 1."""
    cfg, streams, phi, b, alpha = _streams_and_maps(scale=200.0)
    _, _, res, _ = mpt._hc_maps(cfg, streams, phi, b, alpha)
    assert bool(jnp.all(jnp.isfinite(res)))
    np.testing.assert_allclose(jnp.sum(res, axis=1), 1.0, atol=1e-4)


def test_a_fresh_model_starts_as_the_one_stream_model():
    """``b`` at init: read-in weights 1/4, write-back weights 1, the mixing
    matrix 0.948 on the diagonal and 0.0174 off it (``e^4 / (e^4 + 3)``), up to
    the 0.01-scaled data-dependent part; the flax initializers give the
    reference's numbers."""
    cfg = tiny_cfg().model
    params = init_params(cfg, seed=0)
    want = ref.make_params(dims_of(tiny_cfg()), 0)
    for stack in ("dense_blocks", "blocks"):
        for site in ("hc_1", "hc_2"):
            np.testing.assert_allclose(params[stack]["block"][f"{site}_b"],
                                       want[stack]["block"][f"{site}_b"], atol=1e-7)
            np.testing.assert_array_equal(params[stack]["block"][f"{site}_alpha"],
                                          want[stack]["block"][f"{site}_alpha"])
    block = jax.tree.map(lambda a: a[0], params["blocks"]["block"])
    zeros = tuple(jnp.zeros((1, 3, 32)) for _ in range(4))
    pre, post, res, gap = mpt._hc_maps(
        cfg, zeros, block["hc_1_phi"], block["hc_1_b"], block["hc_1_alpha"])
    np.testing.assert_allclose(pre, 0.25, atol=1e-6)
    np.testing.assert_allclose(post, 1.0, atol=1e-6)
    keep = math.exp(4) / (math.exp(4) + 3)
    np.testing.assert_allclose(res[:, :, 0], np.full((4, 4), (1 - keep) / 3)
                               + np.eye(4) * (keep - (1 - keep) / 3), atol=1e-5)
    assert float(gap) < 1e-5


# ---------------------------------------------------------------------------
# the whole model against the plain reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def seeded():
    cfg = tiny_cfg()
    dims = dims_of(cfg)
    params = ref.make_params(dims, 7)
    model = MPTModel(cfg.model)
    n = TOKENS.shape[0] * (TOKENS.shape[1] - 1)
    loss, grads = jax.value_and_grad(make_loss_fn(model, 16))(params, jnp.asarray(TOKENS))
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.ce_sum(p, jnp.asarray(TOKENS), dims) / n)(params)
    return dict(cfg=cfg, dims=dims, params=params, model=model, loss=loss, grads=grads,
                want_loss=want_loss, want_grads=want_grads)


LEAVES = leaf_names(jax.eval_shape(lambda: ref.make_params(dims_of(tiny_cfg()), 0)))


def test_init_gives_the_reference_tree():
    cfg = tiny_cfg()
    ours = jax.eval_shape(lambda: init_params(cfg.model, seed=0))
    theirs = jax.eval_shape(lambda: ref.make_params(dims_of(cfg), 0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), ours) == jax.tree.map(
        lambda a: (a.shape, a.dtype), theirs)
    assert [n for n in LEAVES if "hc_" in n] == [
        f"{stack}/block/hc_{site}_{leaf}" for stack in ("blocks", "dense_blocks")
        for site in (1, 2) for leaf in ("alpha", "b", "phi")]


def test_forward_logits_match_reference(seeded):
    got = seeded["model"].apply({"params": seeded["params"]}, jnp.asarray(TOKENS))
    want = ref.forward(seeded["params"], jnp.asarray(TOKENS), seeded["dims"])
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_loss_matches_reference(seeded):
    assert abs(float(seeded["loss"]) - float(seeded["want_loss"])) < 1e-5


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(seeded, leaf):
    """Every leaf's first gradient, the maps' among them (their gradients are
    of order 1e-4 and smaller: the tolerance is relative to the leaf's
    largest entry, with an absolute floor at float32's rounding of the
    sums)."""
    got = dict(zip(LEAVES, jax.tree.leaves(seeded["grads"])))[leaf]
    want = dict(zip(LEAVES, jax.tree.leaves(seeded["want_grads"])))[leaf]
    if leaf.endswith("router_bias"):  # selects only: no gradient
        assert not np.any(got) and not np.any(want)
        return
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0, leaf
    np.testing.assert_allclose(got, want, atol=1e-4 * scale + 1e-9, err_msg=leaf)


def test_bfloat16_maps_fail_the_tolerances(seeded, monkeypatch):
    """The control of the tolerances above: the same program with its three
    maps rounded to bfloat16 is over 1e-4 off on the logits and over a
    thousandth on a map's gradient, and fails both."""
    exact = mpt._hc_maps

    def rounded(*args):
        pre, post, res, gap = exact(*args)
        return tuple(a.astype(jnp.bfloat16).astype(jnp.float32) for a in (pre, post, res)) + (gap,)

    monkeypatch.setattr(mpt, "_hc_maps", rounded)
    got = seeded["model"].apply({"params": seeded["params"]}, jnp.asarray(TOKENS))
    want = ref.forward(seeded["params"], jnp.asarray(TOKENS), seeded["dims"])
    assert float(jnp.max(jnp.abs(got - want))) > 1e-4  # ten times the logits' tolerance
    grads = jax.grad(make_loss_fn(seeded["model"], 16))(seeded["params"], jnp.asarray(TOKENS))
    got, want = (g["blocks"]["block"]["hc_2_b"] for g in (grads, seeded["want_grads"]))
    assert float(jnp.max(jnp.abs(got - want))) > 1e-3 * float(jnp.max(jnp.abs(want)))


def test_bfloat16_compute_stays_near_the_reference(seeded):
    cfg = tiny_cfg(compute_dtype="bfloat16")
    got = MPTModel(cfg.model).apply({"params": seeded["params"]}, jnp.asarray(TOKENS))
    want = ref.forward(seeded["params"], jnp.asarray(TOKENS), seeded["dims"])
    assert float(jnp.max(jnp.abs(got - want))) < 0.05  # logits of order 1 at eight bits


@pytest.mark.parametrize("t", [1, 17, 31])
def test_changing_a_token_leaves_every_earlier_output_bit_equal(seeded, t):
    """The maps are per token and attention is causal: nothing flows back."""
    tokens = np.array(TOKENS)
    tokens[:, t] = (tokens[:, t] + 1) % 96
    apply = lambda tk: seeded["model"].apply(  # noqa: E731
        {"params": seeded["params"]}, jnp.asarray(tk))
    a, b = apply(TOKENS), apply(tokens)
    np.testing.assert_array_equal(a[:, :t], b[:, :t])
    assert float(jnp.max(jnp.abs(a[:, t] - b[:, t]))) > 0


@pytest.mark.parametrize("microbatches", [1, 2])
def test_three_adopt_steps_follow_the_reference(microbatches):
    """Three optimizer steps through ``Trainer`` and through the reference's
    ``Grad`` + ``adopt_step`` (its moments on the host): every leaf, the maps'
    and the selection bias among them."""
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
    fits = [trainer.fit([b], duration_steps=1) for b in batches]
    got = trainer.state.params
    assert all(0 <= f[MHC_SINKHORN_GAP] < 1e-3 for f in fits)

    opt = optimizer_settings(cfg)
    grad = ref.Grad(dims, rows=2)
    want, state = params0, ref.adopt_init(params0)
    for batch, fit in zip(batches, fits):
        ref_loss, g = grad(want, batch)
        assert abs(float(fit["loss"]) - float(ref_loss)) < 1e-5
        want, state = jax.jit(lambda p, s, g=g: ref.adopt_step(p, s, g, opt))(want, state)

    bias0 = np.asarray(params0["blocks"]["block"]["router_bias"])
    bias = np.asarray(got["blocks"]["block"]["router_bias"])
    assert np.max(np.abs(bias - bias0)) > 0.05  # it moved, by up to 3 x 0.2
    for name, a, b in zip(leaf_names(got), jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    moved = np.asarray(got["blocks"]["block"]["hc_1_phi"]) - np.asarray(
        params0["blocks"]["block"]["hc_1_phi"])
    assert np.max(np.abs(moved)) > 1e-4  # the maps train
    change = lambda p: ref.leaf_norms(jax.tree.map(jnp.subtract, p, params0))  # noqa: E731
    assert ref.worst_leaf_gap(change(got), change(want)) < 1e-3


def test_fit_returns_the_mixing_gap_as_the_worst_of_its_microbatches():
    from photon_tpu.train.trainer import Trainer
    from photon_tpu.utils.profiling import MOE_ROWS_HELD

    cfg = tiny_cfg()
    cfg.train.global_batch_size, cfg.train.device_microbatch_size = 4, 2  # two microbatches
    trainer = Trainer(cfg, init_seed=0)
    out = trainer.fit([np.concatenate([TOKENS, TOKENS])] * 2, duration_steps=2)
    assert 0 <= out[MHC_SINKHORN_GAP] < 1e-3
    # 4 rows x 32 tokens x top-2 x 2 expert layers = 512 assignments, about half held
    assert 128 <= out[MOE_ROWS_HELD] <= 384


# ---------------------------------------------------------------------------
# the flash kernel with a v width of its own, and YaRN
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def qkv():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    shape = lambda d: (1, 512, 2, d)  # noqa: E731
    return (jax.random.normal(keys[0], shape(192)), jax.random.normal(keys[1], shape(192)),
            jax.random.normal(keys[2], shape(128)), jax.random.normal(keys[3], shape(128)))


@pytest.mark.parametrize("block", [256, 512], ids=["strips_over_two_tiles", "lone_tile"])
def test_flash_kernel_at_qk_192_v_128_matches_xla_attention(qkv, block):
    """Under ``interpret``, float32, the published widths and YaRN's scale:
    the output and all three gradients against ``xla_attention``; 2e-5 is
    float32 over 512 keys (values and gradients of order 1 to 5)."""
    q, k, v, w = qkv
    scale = 0.14468
    kernel = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, interpret=True, scale=scale, block_q=block, block_k=block)
    out = kernel(q, k, v)
    assert out.shape == (1, 512, 2, 128)
    np.testing.assert_allclose(out, xla_attention(q, k, v, scale=scale), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(xla_attention(*a, scale=scale) * w), (0, 1, 2))(q, k, v)
    assert [g.shape[-1] for g in got] == [192, 192, 128]
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=f"d{name}")


def test_a_narrower_v_is_not_padded_to_the_score_width():
    """v, o, dO, dv and the accumulator at 128 lanes beside q / k's 256: each
    launch's VMEM estimate falls, the tiles stay the ladder's for q / k's
    width; with one width the estimate and the tiles are unchanged (the
    numbers of ``mpt-125m``'s and ``glm-4.7-flash-ep8``'s launches before this
    v width existed)."""
    assert (lane_padded(192), lane_padded(128)) == (256, 128)
    both = pick_tiles(4096, 4096, 256, 2, d_v_pad=128)
    one = pick_tiles(4096, 4096, 256, 2)
    assert both.blocks == one.blocks == ((2048, 2048), (1024, 1024), (1024, 1024))
    assert [t.vmem_bytes for t in both] == [37_888_000, 13_770_752, 15_867_904]
    assert [t.vmem_bytes for t in one] == [43_130_880, 15_867_904, 19_013_632]
    mpt125m = pick_tiles(2048, 2048, 128, 2)
    assert mpt125m.blocks == ((2048, 2048),) * 3
    assert [t.vmem_bytes for t in mpt125m] == [35_790_848, 34_873_344, 38_019_072]
    for launch in ("fwd", "dq", "dkv"):
        assert launch_vmem_bytes(launch, 1024, 1024, 256, 2, 256) == launch_vmem_bytes(
            launch, 1024, 1024, 256, 2)
    # what the trainer tells on its span is the same plan
    from photon_tpu.models.step import step_attrs

    model = load_preset(PRESET).model
    model.attn_interpret = True  # the plan is told where the kernel is in the step
    told = step_attrs(model, batch_rows=1).steps
    assert {k: v for k, v in told.items() if k.startswith("flash_")} == both.attrs()


def test_yarn_frequencies_and_scale_from_the_published_numbers():
    """theta 10,000, 64 rotary dims, factor 64 over 4,096, beta 32 / 1: the
    correction range is floor(10.47) = 10 to ceil(22.51) = 23; below it the
    plain frequency, above it a 64th, the ramp's thirteenths between; scale
    ``192^-1/2 (0.1 ln 64 + 1)^2 = 0.14468``; cos and sin unscaled."""
    model = load_preset(PRESET).model
    c = lambda b: 64 * math.log(4096 / (b * 2 * math.pi)) / (2 * math.log(10000))  # noqa: E731
    assert (c(32), c(1)) == (pytest.approx(10.47, abs=0.01), pytest.approx(22.51, abs=0.01))
    inv = np.array(model.rope_inv_freq(64))
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], plain[23:] / 64, rtol=1e-12)
    for i in range(11, 23):
        keep = 1 - (i - 10) / 13
        assert inv[i] == pytest.approx(plain[i] * (keep + (1 - keep) / 64), rel=1e-12)
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(ref.dims_of(dataclasses.asdict(model))),
                               rtol=1e-6)
    assert model.softmax_scale == pytest.approx(192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    assert model.softmax_scale == pytest.approx(0.14468, rel=1e-4)
    # without scaling: the dispatch's own scale, apply_rope's own frequencies
    plain_model = load_preset("glm-4.7-flash-ep8").model
    assert plain_model.softmax_scale is None and plain_model.rope_inv_freq(64) is None
    assert load_preset("granite-4.0-h-micro-stage1").model.softmax_scale == 0.015625


def test_apply_rope_turns_by_the_frequencies_it_is_given():
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 4))
    inv = (0.5, 0.01)
    got, _ = mpt.apply_rope(q, q, 10000.0, inv)
    ang = np.arange(8)[:, None] * np.array(inv)[None, :]
    cos, sin = np.cos(ang)[None, :, None, :], np.sin(ang)[None, :, None, :]
    x1, x2 = np.asarray(q[..., :2]), np.asarray(q[..., 2:])
    np.testing.assert_allclose(got, np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1),
                               atol=1e-6)
    plain, _ = mpt.apply_rope(q, q, 10000.0)
    np.testing.assert_allclose(
        plain, mpt.apply_rope(q, q, 10000.0, (1.0, 0.01))[0], atol=1e-6)


# ---------------------------------------------------------------------------
# the share: what expert parallelism asks of the layer
# ---------------------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Sigmoid top-4 of 64 experts and one shared expert, the published
    counts, in one hyper-connected expert layer: over the 8 shares of 8
    experts the branch outputs, with the shared expert counted once, add up to
    the uncut reference's, and so do the new streams ``X'``, with what every
    chip computes alike (``H_res X + H_post shared``) counted once."""
    cfg = tiny_cfg(moe_num_experts=64, moe_top_k=4, moe_experts_held=8, mlp_hidden_size=8)
    full = {**dims_of(cfg), "experts_held": 64, "first_expert": 0}
    layer = jax.tree.map(lambda a: a[0], ref.make_params(full, 5)["blocks"]["block"])
    # larger expert weights, so that one share's part is not lost in the tolerance
    layer = {**layer, **{k: layer[k] * 10 for k in ("moe_gate", "moe_up", "moe_down")}}
    rng = np.random.default_rng(2)
    streams = tuple(jnp.asarray(rng.normal(size=(2, 32, 32)), jnp.float32) for _ in range(4))
    mm = ref.MATMULS["float32"]
    x = jnp.stack(streams, axis=2)
    uncut, rows = ref.block(x, layer, full, mm, dense=False)
    alike, _ = ref.block(x, layer, {**full, "experts_held": 0}, mm, dense=False)
    assert float(jnp.sum(rows)) == 2 * 32 * 4

    # the branch: the program's dropless layer on the reference's normed input
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32))
    shared = ref._glm.shared_expert(h, layer, mm)
    branch_uncut = ref._glm.routed_experts(h, layer, full, mm) + shared
    parts, held_rows = [], 0.0
    new_streams = []
    for first in range(0, 64, 8):
        sl = slice(first, first + 8)
        out, counters = moe.dropless_moe_mlp(
            h, layer["router"], layer["router_bias"], layer["moe_gate"][sl],
            layer["moe_up"][sl], layer["moe_down"][sl], top_k=4, first_expert=first,
            routed_scale=2.0, compute_dtype=jnp.float32)
        parts.append(out + shared)
        held_rows += float(counters["rows_held"])
        share = dataclasses.replace(cfg.model, moe_first_expert=first)
        weights = {**layer, **{k: layer[k][sl] for k in ("moe_gate", "moe_up", "moe_down")}}
        new_streams.append(jnp.stack(
            mpt.MPTBlock(share).apply({"params": weights}, streams), axis=2))
    assert held_rows == 2 * 32 * 4  # every assignment is some share's, once
    np.testing.assert_allclose(sum(parts) - 7 * shared, branch_uncut, atol=1e-5)
    np.testing.assert_allclose(sum(new_streams) - 7 * alike, uncut, atol=2e-5)
    # and one share alone is not the layer
    assert float(jnp.max(jnp.abs(new_streams[0] - uncut))) > 1e-3


# ---------------------------------------------------------------------------
# the scopes, as the trace's readers find them
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def step_op_names():
    """The ``op_name``s of the tiny model's whole compiled train step."""
    from photon_tpu.optim import build_optimizer
    from photon_tpu.train import init_train_state
    from photon_tpu.train.train_step import make_train_step

    cfg = tiny_cfg(remat=True)
    model = MPTModel(cfg.model)
    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    state = init_train_state(model, tx, init_params(cfg.model, seed=0))
    compiled = jax.jit(make_train_step(model, tx, loss_chunk_tokens=16)).lower(
        state, jnp.asarray(TOKENS)).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', compiled)


@pytest.mark.parametrize("scope", [MHC_MAPS_SCOPE, MHC_READ_IN_SCOPE, MHC_WRITE_BACK_SCOPE])
def test_new_scope_is_on_forward_backward_and_recomputation(step_op_names, scope):
    hits = [n for n in step_op_names if re.search(rf"\b{scope}\b", n)]
    assert any("transpose(" not in n and "rematted" not in n for n in hits), scope
    assert any("transpose(jvp(" in n for n in hits), scope
    assert any("rematted_computation" in n for n in hits), scope
    for stack in ("dense_blocks", "blocks"):
        assert any(f"/{stack}/" in n for n in hits), (scope, stack)


def test_no_operation_is_under_two_of_the_readers_scopes(step_op_names):
    """The write-back is outside the scope of the branch's last projection
    (``mla/proj``, ``block/mlp``), so that ``mla_proj_ms_train`` / ``mlp_ms_train``
    and the two new readers never count one operation twice; the three new
    scopes exclude each other; every operation of the step keeps a stage."""
    new = [re.compile(rf"\b{s}\b") for s in (MHC_MAPS_SCOPE, MHC_READ_IN_SCOPE,
                                              MHC_WRITE_BACK_SCOPE)]
    old = re.compile(r"\b(mla/proj|block/mlp|block/norm|moe/(router|dispatch|experts|shared_expert))\b")
    for n in step_op_names:
        mine = sum(bool(rx.search(n)) for rx in new)
        assert mine <= 1, n
        assert not (mine and old.search(n)), n
    own = [n for n in step_op_names if n.startswith("jit(train_step)/")]
    assert len(own) > 300
    hoisted = [n for n in own if re.match(r"jit\(train_step\)/(dense_)?blocks/block/", n)]
    assert not sorted({n for n in own if "train_step/" not in n} - set(hoisted))
    assert any(re.search(rf"{MHC_MAPS_SCOPE}/.*dot_general", n) for n in own)
    assert any(re.search(rf"{MHC_WRITE_BACK_SCOPE}/(mul|add)", n) for n in own)


# ---------------------------------------------------------------------------
# one residual stream and one head width: every other preset as it was
# ---------------------------------------------------------------------------

#: each benchmark preset at its tiny size (``tests/_helpers.TINY_PRESETS``): the
#: leaves of its parameter tree and its loss on ``TOKENS`` with seed-0 weights,
#: read on the commit before hyper-connections and the v width existed; the
#: lowered train steps were equal text for text there too (PERF.md, PR 44)
UNCHANGED = {
    "mpt-125m": (9, 4.5944647789001465),
    "glm-4.7-flash-ep8": (32, 4.5490946769714355),
    "granite-4.0-h-micro-stage1": (37, 4.566521644592285),
    "keye-vl-2.0-30b-a3b-ep8": (20, 4.790014743804932),
    "lfm2-8b-a1b-ep4": (33, 4.588274002075195),
}


@pytest.mark.parametrize("preset", list(UNCHANGED))
def test_every_other_preset_keeps_its_tree_its_loss_and_its_step(preset):
    """``hc_mult == 1`` and ``v_head_dim == d_head`` (or no latent attention):
    no new leaf, the loss of the commit before, and a train step that lowers
    to the same text whether its residual adds go through the new helper pair
    or straight to ``_residual`` (under a projection's scope the add stays
    where it was: the blocks call ``_residual`` there themselves). In a child
    interpreter: the driver's run of PR 48's tree lost the worker that was
    running the ``lfm2-8b-a1b-ep4`` case (``/root/TESTS_LAST_RUN.json``; the
    case passes alone and with its file under ``-n 6``: ROADMAP D1's native
    crash of a long-lived worker, not this tree's numbers)."""
    from tests._helpers import run_in_fresh_process

    run_in_fresh_process("tests.test_xing_mhc", "_keeps_its_tree_its_loss_and_its_step", preset)


def _keeps_its_tree_its_loss_and_its_step(preset: str) -> None:
    from unittest import mock

    from photon_tpu.optim import build_optimizer
    from photon_tpu.train import init_train_state
    from photon_tpu.train.train_step import make_train_step

    cfg = tiny_preset(preset)
    assert not cfg.model.hyper_connected and not cfg.model.yarn
    params = init_params(cfg.model, seed=0)
    names = leaf_names(params)
    assert len(names) == UNCHANGED[preset][0] and not [n for n in names if "hc_" in n]
    tokens = np.random.default_rng(3).integers(
        0, 96, size=(2, cfg.model.max_seq_len)).astype(np.int32)
    model = MPTModel(cfg.model)
    loss = float(make_loss_fn(model, 16)(params, jnp.asarray(tokens)))
    assert loss == pytest.approx(UNCHANGED[preset][1], abs=1e-6)

    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    state = init_train_state(model, tx, params)

    def lowered() -> str:
        return jax.jit(make_train_step(MPTModel(cfg.model), tx, loss_chunk_tokens=16)).lower(
            state, jnp.asarray(tokens)).as_text()

    with_helpers = lowered()

    def straight(cfg_, x, branch, hc):
        assert hc is None
        return mpt._residual(cfg_, x, branch)

    with mock.patch.object(mpt, "_hc_read_in", lambda block, x, name: (x, None)), \
            mock.patch.object(mpt, "_write_back", straight):
        assert lowered() == with_helpers
    assert "mhc" not in with_helpers


# ---------------------------------------------------------------------------
# the published cut, its rules, and who refuses the family
# ---------------------------------------------------------------------------


def test_the_published_width_cut_counts_its_parameters():
    """``jax.eval_shape`` of the preset's own tree: ISSUE 44's table, to the
    parameter."""
    model = load_preset(PRESET).model
    shapes = jax.eval_shape(lambda: init_params(model, seed=0))
    count = lambda t: sum(math.prod(a.shape) for a in jax.tree.leaves(t))  # noqa: E731
    attention = (3584 * 768 + 768 + 768 * 6144 + 3584 * 576 + 512 + 512 * 8192
                 + 4096 * 3584)
    maps = 14336 * 24 + 24 + 3
    assert (attention, maps) == (28_411_136, 344_091)
    assert count(shapes["dense_blocks"]) == attention + 7168 + 2 * maps + 3 * 3584 * 9216 \
        == 128_196_918
    expert = (attention + 7168 + 2 * maps + 3584 * 64 + 64 + 3 * 3584 * 1024
              + 8 * 3 * 3584 * 1024)
    assert count(shapes["blocks"]) == 4 * expert == 4 * 128_426_358
    assert count(shapes["wte"]) + count(shapes["lm_head"]) + count(shapes["ln_f"]) \
        == 117_444_096
    assert count(shapes) == 759_346_446
    block = shapes["blocks"]["block"]
    assert block["hc_1_phi"].shape == block["hc_2_phi"].shape == (4, 24, 14336)
    assert block["hc_1_b"].shape == (4, 24) and block["hc_2_alpha"].shape == (4, 3)
    assert block["kv_b_proj"]["kernel"].shape == (4, 512, 32 * (128 + 128))
    assert block["out_proj"]["kernel"].shape == (4, 32 * 128, 3584)
    assert block["moe_gate"].shape == (4, 8, 3584, 1024)
    assert block["router"].shape == (4, 3584, 64)
    theirs = jax.eval_shape(lambda: ref.make_params(ref.dims_of(dataclasses.asdict(model)), 0))
    assert jax.tree.map(lambda a: a.shape, theirs) == jax.tree.map(lambda a: a.shape, shapes)


def test_the_preset_is_what_the_benchmark_configuration_states():
    from benchmark.program import build_config

    config = json.loads((ROOT / "benchmark/configs" / f"{PRESET}.json").read_text())
    traffic = json.loads((ROOT / "benchmark/traffic/ep8-share-4096.json").read_text())
    cfg = build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=2**31 + 5)
    assert (cfg.train.global_batch_size, cfg.train.device_microbatch_size) == (1, 1)
    assert cfg.model.stacks == [("dense_blocks", "attention", True, 1),
                                ("blocks", "attention", False, 4)]
    assert (cfg.model.d_head, cfg.model.v_head_dim, cfg.model.hc_mult) == (192, 128, 4)
    assert cfg.model.training_path_only and cfg.model.remat
    config["model"]["hc_sinkhorn_iters"] = 10
    with pytest.raises(ValueError, match="hc_sinkhorn_iters"):
        build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=1)


def test_model_flops_per_token_counts_the_family():
    """The program's own estimate against the benchmark's cost file at the
    expected rows: the same terms but the elementwise mixing, which the
    program leaves out (0.2 %)."""
    from benchmark.costs import xing_mhc_moe_train as cost
    from photon_tpu.utils.profiling import model_flops_per_token

    model = load_preset(PRESET).model
    m = json.loads((ROOT / "benchmark/configs" / f"{PRESET}.json").read_text())["model"]
    parts = cost.parts_per_token(m, cost.expected_routed_rows_per_token(m))
    assert model_flops_per_token(model) == pytest.approx(
        sum(parts.values()) - parts["hyper_connection_mix"], rel=1e-3)


def test_every_parameter_has_a_sharding_rule():
    from jax.sharding import PartitionSpec as P

    from photon_tpu.config.schema import MeshConfig
    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.parallel.sharding import _RULES, param_specs

    params = init_params(tiny_cfg().model, seed=0)
    names = leaf_names(params)
    assert not [n for n in names if not any(re.search(p, n) for p, _ in _RULES)]
    mesh = make_mesh(MeshConfig(fsdp=2), devices=jax.devices()[:2])
    specs = param_specs(params, mesh)
    for stack in ("dense_blocks", "blocks"):  # the maps stay whole
        assert specs[stack]["block"]["hc_1_phi"] == P("pipe", None, None)
        assert specs[stack]["block"]["hc_2_b"] == P("pipe", None)
        assert specs[stack]["block"]["hc_2_alpha"] == P("pipe", None)
    assert specs["blocks"]["block"]["kv_b_proj"]["kernel"] == P("pipe", None, "tensor")


def test_trainer_tells_the_streams_and_sublayers_on_its_span():
    from photon_tpu.models.step import step_attrs

    # (no kernel in a step on the CPU backend: the flash plan adds no key)
    told = lambda model: step_attrs(model, batch_rows=2).steps  # noqa: E731
    assert told(load_preset(PRESET).model) == {"mhc_streams": 4, "mhc_sublayers": 10}
    assert told(tiny_cfg().model) == {"mhc_streams": 4, "mhc_sublayers": 6}
    assert told(load_preset("glm-4.7-flash-ep8").model) == {}


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


def test_a_dense_model_takes_the_streams_too():
    """``hc_mult`` composes with the plain blocks (no latent attention, no
    experts): the streams go around a fused-QKV attention and a GELU MLP."""
    cfg = load_preset("mpt-125m")
    for key, value in dict(d_model=32, n_layers=2, n_heads=2, max_seq_len=32, vocab_size=96,
                           attn_impl="xla", compute_dtype="float32", hc_mult=2).items():
        setattr(cfg.model, key, value)
    cfg.train.global_batch_size = cfg.train.device_microbatch_size = 2
    cfg.validate()
    params = init_params(cfg.model, seed=0)
    assert params["blocks"]["block"]["hc_1_phi"].shape == (2, 8, 64)
    loss, grads = jax.value_and_grad(make_loss_fn(MPTModel(cfg.model), 16))(
        params, jnp.asarray(TOKENS))
    assert np.isfinite(float(loss))
    assert float(jnp.max(jnp.abs(grads["blocks"]["block"]["hc_2_phi"]))) > 0


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


def test_the_refusal_names_the_streams_and_the_frequencies():
    from photon_tpu.config.schema import refuse_training_only_family

    cfg = load_preset("mpt-125m")
    cfg.model.hc_mult = 4
    with pytest.raises(NotImplementedError, match=r"hyper-connected residual streams"):
        refuse_training_only_family(cfg.model, "serving")
    cfg = load_preset("mpt-125m")
    cfg.model.rope, cfg.model.rope_scaling_type = True, "yarn"
    with pytest.raises(NotImplementedError, match="YaRN"):
        refuse_training_only_family(cfg.model, "the HF export")


def test_hf_import_refuses_the_model_type_and_the_scaling():
    from photon_tpu.checkpoint.hf_import import model_config_from_hf

    with pytest.raises(ValueError, match="xing4_0"):
        model_config_from_hf({"model_type": "xing4_0"})


def _with(cfg, **paths):
    for dotted, value in paths.items():
        obj = cfg
        *parents, leaf = dotted.split("__")
        for name in parents:
            obj = getattr(obj, name)
        setattr(obj, leaf, value)
    return cfg


@pytest.mark.parametrize("change, message", [
    (dict(model__hc_mult=0), "hc_mult must be >= 1"),
    (dict(model__hc_sinkhorn_iters=0), "hc_sinkhorn_iters >= 1"),
    (dict(model__hc_eps=0.0), "hc_eps > 0"),
    (dict(model__hc_res_clamp=-30.0), "hc_res_clamp > 0"),
    (dict(model__residual_multiplier=0.5), "does not combine with residual_multiplier"),
    (dict(mesh__pipe=3), "mesh.pipe > 1"),
    (dict(mesh__tensor=2), "hc_mult > 1 with mesh.pipe, mesh.tensor"),
    (dict(mesh__sequence=2), "mesh.sequence > 1"),
    (dict(mesh__expert=2), "mesh.expert > 1"),
    (dict(model__rope_scaling_type="linear"), "only 'yarn'"),
    (dict(model__rope_scaling_factor=0.5), "rope_scaling_factor >= 1"),
    (dict(model__rope_scaling_original_max_position=0), "rope_scaling_original_max_position > 0"),
    (dict(model__rope_scaling_beta_fast=1.0), "rope_scaling_beta_fast > rope_scaling_beta_slow"),
    (dict(model__rope_scaling_type="", model__rope_scaling_mscale_all_dim=0.0,
          model__rope_scaling_original_max_position=0),
     "belong to rope_scaling_type='yarn'"),
    (dict(model__rope_scaling_mscale=0.707), "differs from rope_scaling_mscale_all_dim"),
    (dict(model__attention_multiplier=0.1), "both set the softmax scale"),
    (dict(model__attn_impl="ring"), "ring"),
    (dict(model__lora_rank=4), "LoRA adapters"),
    (dict(photon__adapters__enabled=True), "LoRA adapters"),
    (dict(photon__serve__enabled=True), "photon.serve"),
    (dict(photon__serve__prefix_cache=True), "photon.serve"),
])
def test_schema_refuses_what_the_family_cannot_do_yet(change, message):
    cfg = tiny_cfg()
    with pytest.raises(ValueError, match=message):
        _with(cfg, **change).validate()


def test_streams_do_not_combine_with_layer_types():
    cfg = load_preset("lfm2-8b-a1b-ep4")
    cfg.model.hc_mult = 4
    with pytest.raises(ValueError, match="does not combine with layer_types"):
        cfg.validate()


def test_a_v_width_of_its_own_is_accepted_and_ring_attention_still_refuses_it():
    cfg = load_preset("glm-4.7-flash-ep8")
    cfg.model.v_head_dim = 128
    cfg.validate()
    assert (cfg.model.d_head, cfg.model.v_head_dim) == (256, 128)
    cfg.model.attn_impl = "ring"
    with pytest.raises(ValueError, match="takes one head width"):
        cfg.validate()


def test_the_new_fields_survive_yaml_and_json(tmp_path):
    cfg = tiny_cfg()
    cfg.to_yaml(tmp_path / "resolved.yaml")
    back = Config.from_yaml(tmp_path / "resolved.yaml").validate()
    assert (back.model.hc_mult, back.model.hc_sinkhorn_iters, back.model.hc_eps,
            back.model.hc_res_clamp) == (4, 20, 1e-6, 30.0)
    assert (back.model.rope_scaling_type, back.model.rope_scaling_factor,
            back.model.rope_scaling_original_max_position) == ("yarn", 64.0, 16)
    assert Config.from_json(cfg.to_json()).model.softmax_scale == cfg.model.softmax_scale
    plain = Config().model
    assert plain.hc_mult == 1 and not plain.hyper_connected and not plain.yarn
    assert not plain.training_path_only
