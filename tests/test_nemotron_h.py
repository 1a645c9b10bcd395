"""Nemotron-H with experts (``nemotron_h``; Nemotron-3-Nano-30B-A3B) on the
training path, at a tiny size with the published structure: layers of ONE
branch (``MEMEM*EME``), Mamba-2 mixers whose B and C come in groups and whose
gated norm works within a group, attention without positions, ungated relu^2
experts beside a shared expert of its own width.

The plain reference is ``benchmark/reference/nemotron_h_moe.py`` (float32,
``Precision.HIGHEST``, the recurrence walked position by position); on the CPU
the program runs ``attn_impl: xla`` in float32, so the two differ by the order
of summation alone and every tolerance below is a float32 one.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # `benchmark` is a sibling of `tests`, not installed
    sys.path.insert(0, str(ROOT))

from benchmark.reference import granite_hybrid as one_group_ref  # noqa: E402
from benchmark.reference import nemotron_h_moe as ref  # noqa: E402
from photon_tpu.config import load_preset  # noqa: E402
from photon_tpu.models import MPTModel, init_params  # noqa: E402
from photon_tpu.models import mpt  # noqa: E402
from photon_tpu.ops import moe, ssd  # noqa: E402
from photon_tpu.train.train_step import make_loss_fn  # noqa: E402
from tests._helpers import TINY_PRESETS, leaf_names, tiny_preset  # noqa: E402

PRESET = "nemotron-3-nano-30b-a3b-ep16"
PATTERN = "mamba,moe,mamba,moe,mamba,attention,moe,mamba,moe"
TOKENS = np.random.default_rng(3).integers(0, 96, size=(2, 32)).astype(np.int32)
EXPERT_STACKS = ("blocks_1", "blocks_3", "blocks_6", "blocks_8")


def tiny_cfg(**model):
    return tiny_preset(PRESET, **model)


def dims_of(cfg) -> dict:
    return ref.dims_of(dataclasses.asdict(cfg.model))


# ---------------------------------------------------------------------------
# the grouped scan against the position-by-position recurrence
# ---------------------------------------------------------------------------

SCAN_ARGS = ("x", "dt", "a_log", "b", "c", "d")
GROUPS = (1, 2, 8)
#: the Pallas launches under the interpreter: two chunks of 128, 16 heads of 64
#: (a block of 16, of 8 and of 2 heads at 1, 2 and 8 groups), state 128
LAUNCH_SHAPE = dict(seq=256, chunk=128, b=1, h=16, p=64, n=128)


def _scan_inputs(groups: int, seq: int, b: int = 2, h: int = 8, p: int = 4, n: int = 5, **_):
    rng = np.random.default_rng(seq + groups)
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    return dict(
        x=f32(rng.normal(size=(b, seq, h, p))),
        dt=f32(rng.uniform(0.01, 0.6, size=(b, seq, h))),
        a_log=f32(np.log(rng.uniform(1.0, 16.0, size=h))),
        b=f32(rng.normal(size=(b, seq, groups * n))), c=f32(rng.normal(size=(b, seq, groups * n))),
        d=f32(rng.normal(size=h)))


def _chunked(args: dict, groups: int, chunk: int, **how):
    return ssd.ssd_scan(*(args[k] for k in SCAN_ARGS), chunk=chunk, groups=groups,
                        **{"compute_dtype": jnp.float32, **how})


def _sequential(args: dict, groups: int):
    """The reference's walk over positions, each head against its group's
    ``b`` and ``c`` (one group: ``granite_hybrid``'s own)."""
    grouped = lambda t: t.reshape(*t.shape[:2], groups, -1)  # noqa: E731
    return ref.grouped_recurrence(args["x"], args["dt"], -jnp.exp(args["a_log"]),
                                  grouped(args["b"]), grouped(args["c"]), args["d"])


@functools.lru_cache(maxsize=None)
def _readings(groups: int, launches: bool, fault: bool = False) -> dict:
    """``y`` and the six gradients of ``sum(weights * y)`` from the chunked
    scan (the walk over 5 chunks of 8, or the launches under the interpreter
    at ``LAUNCH_SHAPE``) and from the sequential recurrence, float32."""
    args = _scan_inputs(groups, **(LAUNCH_SHAPE if launches else dict(seq=40)))
    weights = jnp.asarray(np.random.default_rng(1).normal(size=args["x"].shape), jnp.float32)

    def readings(fn):
        y, pull = jax.vjp(lambda *a: fn(dict(zip(SCAN_ARGS, a))), *(args[k] for k in SCAN_ARGS))
        return dict(zip(("y", *SCAN_ARGS), (y, *pull(weights))))

    if fault:  # group 0's B and C for every head
        first = lambda t: jnp.tile(t[..., :t.shape[-1] // groups], groups)  # noqa: E731
        scan = lambda a: _chunked({**a, "b": first(a["b"]), "c": first(a["c"])}, groups, 8)  # noqa: E731
    elif launches:
        scan = lambda a: _chunked(a, groups, LAUNCH_SHAPE["chunk"], impl="pallas",  # noqa: E731
                                  interpret=True)
        assert str(jax.make_jaxpr(scan)(args)).count("pallas_call") == 1
    else:
        scan = lambda a: _chunked(a, groups, 8)  # noqa: E731
    return {"chunked": jax.jit(readings, static_argnums=0)(scan),
            "sequential": readings(lambda a: _sequential(a, groups))}


def _check_scan(groups: int, launches: bool, name: str, fault: bool = False):
    found = _readings(groups, launches, fault)
    got, want = found["chunked"][name], found["sequential"][name]
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 1e-2
    # (``a_log``: one number a head, summed over every position and channel)
    slack = 8 if name == "a_log" else 1
    np.testing.assert_allclose(got, want, atol=2e-5 * scale * slack, rtol=2e-4 * slack,
                               err_msg=f"{name} against the recurrence")


@pytest.mark.parametrize("name", ("y", *SCAN_ARGS))
@pytest.mark.parametrize("launches", [False, True], ids=["walk", "launches"])
@pytest.mark.parametrize("groups", GROUPS)
def test_grouped_scan_matches_the_position_by_position_recurrence(groups, launches, name):
    _check_scan(groups, launches, name)


def test_one_group_of_the_grouped_recurrence_is_the_one_group_recurrence():
    args = _scan_inputs(1, 24)
    np.testing.assert_array_equal(
        _sequential(args, 1),
        one_group_ref.recurrence(args["x"], args["dt"], -jnp.exp(args["a_log"]), args["b"],
                                 args["c"], args["d"]))


@pytest.mark.parametrize("name", ["y", "x", "b"])
def test_group_zeros_b_and_c_for_every_head_fails_the_comparison(name):
    """The planted fault of ISSUE 52: every head reading group 0."""
    with pytest.raises(AssertionError, match=f"{name} against the recurrence"):
        _check_scan(2, False, name, fault=True)


@pytest.mark.parametrize("heads,groups,block", [(64, 1, 16), (64, 8, 8), (32, 1, 16), (16, 2, 8),
                                                (16, 8, 2), (24, 2, 12), (12, 1, 12)])
def test_a_launch_blocks_heads_are_of_one_group(heads, groups, block):
    assert ssd._head_block(heads, groups) == block
    assert (heads // groups) % block == 0


@pytest.mark.parametrize("heads,groups,takes", [
    (64, 8, True),  # the cell's shapes: pairs of heads inside a group of 8
    (64, 1, True), (64, 32, True),
    (64, 64, False),  # a group of one head has no pair
    (24, 8, False),  # a group of three heads
])
def test_the_launches_are_taken_where_a_groups_heads_pair(heads, groups, takes):
    assert ssd.uses_kernel("pallas", True, 8192, 128, heads, 64, 128, groups=groups) is takes


def test_scan_refuses_groups_that_do_not_divide():
    args = _scan_inputs(2, 16)
    with pytest.raises(ValueError, match="3 groups do not divide"):
        _chunked(args, 3, 8)


# ---------------------------------------------------------------------------
# the grouped gated norm, the ungated experts
# ---------------------------------------------------------------------------


def test_the_grouped_norm_is_a_norm_of_each_groups_channels():
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.normal(size=(2, 5, 24)) * np.repeat([1.0, 10.0, 0.1], 8), jnp.float32)
    norm = mpt.FP32RMSNorm(eps=1e-5, groups=3)
    scale = jnp.asarray(rng.normal(size=(24,)), jnp.float32)
    got = np.asarray(norm.apply({"params": {"scale": scale}}, u))
    np.testing.assert_allclose(got, ref.grouped_rms_norm(u, scale, 3, 1e-5), rtol=2e-6)
    # one group, the block's own RMSNorm, lets the loud group drown the quiet ones
    # (the planted fault of ISSUE 52: the norm over all channels)
    whole = np.asarray(mpt.FP32RMSNorm(eps=1e-5).apply({"params": {"scale": scale}}, u))
    assert np.max(np.abs(whole - got)) > 0.5


#: an expert width no multiple of 128 divides (1,856 at the tiny end)
WIDTH = 200


def _expert_operands(width: int = WIDTH, d: int = 128, experts: int = 16, held: int = 4):
    rng = np.random.default_rng(width)
    f32 = lambda *shape, std=1.0: jnp.asarray(rng.normal(size=shape) * std, jnp.float32)  # noqa: E731
    return dict(h=f32(2, 32, d), router=f32(d, experts, std=0.3),
                bias=f32(experts, std=0.01), up=f32(held, d, width, std=0.1),
                down=f32(held, width, d, std=0.1))


def _routed(o: dict, first: int = 0, interpret: bool = False, compute=jnp.float32):
    out, counters = moe.dropless_moe_mlp(
        o["h"], o["router"], o["bias"], None, o["up"], o["down"], top_k=3, first_expert=first,
        routed_scale=2.5, compute_dtype=compute, interpret=interpret)
    return out, counters


def _plain_routed(o: dict, first: int = 0):
    """The reference's masked sum over the experts held from ``first`` on."""
    held = o["up"].shape[0]
    dims = {"top_k": 3, "routed_scale": 2.5, "first_expert": first, "experts_held": held,
            "n_experts": o["router"].shape[1]}
    mm = ref.MATMULS["float32"]
    idx, gates = ref._glm.route(o["h"], o["router"], o["bias"], dims, mm)
    return ref.routed_experts(o["h"], {"moe_up": o["up"], "moe_down": o["down"]}, dims, mm,
                              idx, gates)


@pytest.mark.parametrize("interpret", [False, True], ids=["ragged_dot", "megablox"])
def test_ungated_grouped_products_at_a_width_no_multiple_of_128_divides(interpret):
    """Two grouped products and ``relu(.)^2`` between, values and every
    gradient, against the reference's masked sum; under the interpreter the
    megablox kernel walks the 200-wide dimension as two tiles of 128, the
    second masked (``ops/moe._ragged_tile``), forward and in both transposes."""
    o = _expert_operands()
    assert moe._tiles(moe.GMM_TILING, 192, 128, WIDTH) == (192, 128, 128)
    assert moe._tiles(moe.GMM_TILING, 49152, 2688, 1856) == (512, 896, 640)
    assert moe._tiles(moe.GMM_TILING, 49152, 1856, 2688) == (512, 640, 896)
    weights = jnp.asarray(np.random.default_rng(2).normal(size=o["h"].shape), jnp.float32)

    def through(fn):
        loss = lambda h, up, down: jnp.sum(weights * fn({**o, "h": h, "up": up, "down": down}))  # noqa: E731
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(o["h"], o["up"], o["down"])

    with jax.default_matmul_precision("highest"):
        got = through(lambda a: _routed(a, interpret=interpret)[0])
        want = through(_plain_routed)
    for name, g, w in zip(("out", "h", "up", "down"), jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, atol=3e-5 * scale, rtol=2e-4, err_msg=name)


def test_relu_in_place_of_its_square_fails_the_comparison(monkeypatch):
    """The planted fault of ISSUE 52: ``relu`` for ``relu^2``."""
    o = _expert_operands()
    class NoSquare:  # ``jax.numpy`` as ``ops/moe.py`` alone sees it
        def __getattr__(self, name):
            return (lambda t: t) if name == "square" else getattr(jnp, name)

    monkeypatch.setattr(moe, "jnp", NoSquare())
    got, want = _routed(o)[0], _plain_routed(o)
    assert float(jnp.max(jnp.abs(got - want))) > 0.1 * float(jnp.max(jnp.abs(want)))


def test_the_shares_add_up():
    """16 shares of one expert each, every one routing over all 16 and
    computing its own expert's part, plus the shared expert counted ONCE, are
    the uncut reference's expert layer; and each share reports the same rows
    of all routed experts."""
    o = _expert_operands(width=24, d=32, held=16)
    rng = np.random.default_rng(9)
    shared = {"shared_up_proj": {"kernel": jnp.asarray(rng.normal(size=(32, 40)) * 0.1, jnp.float32)},
              "shared_down_proj": {"kernel": jnp.asarray(rng.normal(size=(40, 32)) * 0.1, jnp.float32)}}
    mm = ref.MATMULS["float32"]
    dims = {"top_k": 3, "routed_scale": 2.5, "first_expert": 0, "experts_held": 16, "n_experts": 16}
    uncut, rows = ref.expert_layer(o["h"], {
        "router": o["router"], "router_bias": o["bias"], "moe_up": o["up"], "moe_down": o["down"],
        **shared}, dims, mm)
    total = ref.relu2_expert(o["h"], shared["shared_up_proj"]["kernel"],
                             shared["shared_down_proj"]["kernel"], mm)
    held_rows = 0.0
    with jax.default_matmul_precision("highest"):
        for share in range(16):
            part, counters = _routed({**o, "up": o["up"][share:share + 1],
                                      "down": o["down"][share:share + 1]}, first=share)
            total = total + part
            held_rows += float(counters["rows_held"])
            np.testing.assert_array_equal(counters["expert_rows"], rows)
    assert held_rows == 2 * 32 * 3  # every assignment is some share's
    np.testing.assert_allclose(total, uncut, atol=2e-5 * float(jnp.max(jnp.abs(uncut))))


# ---------------------------------------------------------------------------
# the whole model against the plain reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def seeded():
    cfg = tiny_cfg()
    dims = dims_of(cfg)
    params = ref.make_params(dims, seed=5)
    logits = ref.forward(params, TOKENS, dims)
    loss, grads = jax.value_and_grad(
        lambda p: ref.ce_sum(p, TOKENS, dims) / (TOKENS.shape[0] * (TOKENS.shape[1] - 1)))(params)
    return cfg, dims, params, logits, (loss, grads)


def test_init_gives_the_reference_tree():
    cfg = tiny_cfg()
    ours = init_params(cfg.model, seed=0)
    theirs = ref.make_params(dims_of(cfg), seed=0)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), ours) == jax.tree.map(
        lambda a: (a.shape, a.dtype), theirs)
    assert cfg.model.stacks == [(f"blocks_{i}", kind, False, 1)
                                for i, kind in enumerate(PATTERN.split(","))]
    block = ours["blocks_1"]["block"]
    assert "moe_gate" not in block and "shared_gate_proj" not in block and "ln_2" not in block
    assert block["shared_up_proj"]["kernel"].shape == (1, 32, 40)
    assert ours["blocks_0"]["block"]["in_proj"]["kernel"].shape == (1, 32, 2 * 64 + 2 * 16 + 8)
    # one branch a layer: residual projections start at std / sqrt(L), not / sqrt(2 L)
    for tree in (ours, theirs):
        out = np.asarray(tree["blocks_1"]["block"]["moe_down"])
        assert np.std(out) == pytest.approx(0.02 / 3.0, rel=0.08)


def test_forward_logits_match_reference(seeded):
    cfg, _, params, logits, _ = seeded
    with jax.default_matmul_precision("highest"):
        got = MPTModel(cfg.model).apply({"params": params}, jnp.asarray(TOKENS))
    np.testing.assert_allclose(got, logits, atol=2e-5, rtol=2e-5)


def test_loss_matches_reference(seeded):
    cfg, _, params, _, (loss, _) = seeded
    with jax.default_matmul_precision("highest"):
        got = make_loss_fn(MPTModel(cfg.model), 16)(params, jnp.asarray(TOKENS))
    assert abs(float(got) - float(loss)) < 2e-6


LEAVES = leaf_names(ref.make_params(ref.dims_of(dataclasses.asdict(
    tiny_preset(PRESET).model)), seed=0))


@pytest.fixture(scope="module")
def our_grads(seeded):
    cfg, _, params, _, _ = seeded
    with jax.default_matmul_precision("highest"):
        return jax.grad(make_loss_fn(MPTModel(cfg.model), 16))(params, jnp.asarray(TOKENS))


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(seeded, our_grads, leaf):
    _, _, _, _, (_, grads) = seeded
    got, want = our_grads, grads
    for key in leaf.split("/"):
        got, want = got[key], want[key]
    if leaf.endswith("router_bias"):  # selects only: no gradient on either side
        assert not np.any(got) and not np.any(want)
        return
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 1e-7, leaf
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=1e-3)


def test_three_adopt_steps_match_reference_and_every_expert_layers_bias_moves(seeded):
    """Three optimizer steps through ``make_train_step`` and through the
    reference's ``Grad`` + ``adopt_step``: the losses, every leaf's norm of
    the weights' change, and each of the four expert layers' selection bias
    moved by the balancing rule from its own rows."""
    from photon_tpu.optim import build_optimizer
    from photon_tpu.train import init_train_state
    from photon_tpu.train.train_step import make_train_step

    cfg, dims, params, _, _ = seeded
    cfg.scheduler.t_warmup = 1  # a learning rate from the second step on
    model = MPTModel(cfg.model)
    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    step = jax.jit(make_train_step(model, tx, loss_chunk_tokens=16))
    state = init_train_state(model, tx, params)
    o, s = cfg.optimizer, cfg.scheduler
    opt = {"name": o.name, "lr": o.lr, "betas": tuple(o.betas), "eps": o.eps,
           "grad_clip_norm": o.grad_clip_norm, "schedule": s.name, "t_warmup": s.t_warmup,
           "t_max": s.t_max, "alpha_f": s.alpha_f}
    grad = ref.Grad(dims, rows=1)
    theirs, moments = params, ref.adopt_init(params)
    with jax.default_matmul_precision("highest"):
        for _ in range(3):
            state, metrics = step(state, TOKENS)
            loss, g = grad(theirs, TOKENS)
            theirs, moments = ref.adopt_step(theirs, moments, g, opt)
            assert abs(float(metrics["loss"]) - float(loss)) < 1e-5
    change = lambda p: ref.leaf_norms(jax.tree.map(jnp.subtract, p, params))  # noqa: E731
    assert ref.worst_leaf_gap(change(state.params), change(theirs)) < 1e-3
    for stack in EXPERT_STACKS:
        ours, plain, seed = (t[stack]["block"]["router_bias"] for t in (
            state.params, theirs, params))
        moved = np.abs(np.asarray(ours) - np.asarray(seed))
        assert 0 < moved.max() <= 3 * 0.1 + 1e-6 and np.count_nonzero(moved) > 8, stack
        np.testing.assert_allclose(ours, plain, atol=1e-6)
    biases = [np.asarray(state.params[s]["block"]["router_bias"]) for s in EXPERT_STACKS]
    assert not any(np.array_equal(biases[0], b) for b in biases[1:])  # each by its own rows


@pytest.mark.parametrize("t", [1, 8, 20, 31])
def test_changing_a_token_leaves_every_earlier_output_bit_equal(seeded, t):
    cfg, _, params, _, _ = seeded
    model = MPTModel(cfg.model)
    base = model.apply({"params": params}, jnp.asarray(TOKENS))
    changed = TOKENS.copy()
    changed[:, t] = (changed[:, t] + 1) % 96
    out = model.apply({"params": params}, jnp.asarray(changed))
    np.testing.assert_array_equal(out[:, :t], base[:, :t])
    assert np.any(np.asarray(out[:, t:]) != np.asarray(base[:, t:]))


# ---------------------------------------------------------------------------
# the preset, what the step is told, sharding, refusals
# ---------------------------------------------------------------------------


def test_the_published_width_cut_counts_its_parameters():
    m = load_preset(PRESET).model
    shapes = jax.eval_shape(lambda: init_params(m, seed=0))
    by_stack = {name: sum(math.prod(a.shape) for a in jax.tree.leaves(tree))
                for name, tree in shapes.items()}
    assert by_stack["blocks_0"] == by_stack["blocks_7"] == 38_744_896
    assert by_stack["blocks_5"] == 23_399_040
    assert by_stack["blocks_1"] == by_stack["blocks_8"] == 100_125_440
    assert sum(by_stack.values()) == 666_963_456
    assert (m.mamba_d_inner, m.shared_expert_width, m.moe_layers, m.mamba_layers,
            m.full_attention_layers, m.d_head) == (4096, 3712, 4, 4, 1, 128)


def test_the_preset_is_what_the_benchmark_configuration_states(tmp_path):
    from benchmark.program import build_config

    config = json.loads((ROOT / "benchmark/configs" / f"{PRESET}.json").read_text())
    traffic = json.loads((ROOT / "benchmark/traffic/ep16-share-1x8192.json").read_text())
    cfg = build_config(config, traffic, tmp_path, seed=2**31 + 5)
    assert (cfg.train.global_batch_size, cfg.train.device_microbatch_size) == (1, 1)
    assert cfg.model.layer_types == PATTERN and cfg.model.remat


def test_the_steps_count_is_the_benchmarks_at_the_expected_rows():
    from benchmark.costs import nemotron_h_moe_train as cost
    from photon_tpu.utils.profiling import model_flops_per_token

    config = json.loads((ROOT / "benchmark/configs" / f"{PRESET}.json").read_text())["model"]
    want = cost.flops_per_token(config, cost.expected_routed_rows_per_token(config))
    assert model_flops_per_token(load_preset(PRESET).model) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(2.1456e9, rel=1e-4)


def test_trainer_tells_the_layers_by_kind_and_the_groups_on_its_span():
    from photon_tpu.models.step import step_attrs

    told = lambda model: step_attrs(model, batch_rows=1).steps  # noqa: E731
    preset = load_preset(PRESET).model
    assert told(preset) == {"mamba_layers": 4, "mamba_groups": 8, "ssd_chunks": 64,
                            "ssd_kernel_layers": 0, "moe_layers": 4, "attention_layers": 1}
    # where a kernel can run (on the chip; here under the interpreter) the
    # preset's shapes take the launches, the tiny configuration's never do
    on_kernels = told(dataclasses.replace(preset, attn_interpret=True))
    assert on_kernels["ssd_kernel_layers"] == 4
    assert told(tiny_cfg().model) == {"mamba_layers": 4, "mamba_groups": 2, "ssd_chunks": 4,
                                      "ssd_kernel_layers": 0, "moe_layers": 4,
                                      "attention_layers": 1}


def test_every_parameter_has_a_sharding_rule():
    import re

    from photon_tpu.parallel.sharding import _RULES

    names = leaf_names(init_params(tiny_cfg().model, seed=0))
    assert not [n for n in names if not any(re.search(p, n) for p, _ in _RULES)]


def test_a_federated_client_fit_trains_the_family():
    from photon_tpu.train.trainer import Trainer

    cfg = tiny_cfg()
    trainer = Trainer(cfg, init_seed=0)
    rows = np.random.default_rng(0).integers(0, 96, size=(2, 32)).astype(np.int32)
    out = trainer.fit([rows] * 12, duration_steps=12)
    assert np.isfinite(out["loss"]) and out["loss"] < 4.6
    assert out["moe/rows_held"] > 0 and out["moe/max_expert_load"] >= 1.0


def _refuse_serving():
    from photon_tpu.serve.engine import PagedEngine

    PagedEngine(tiny_cfg(), params={})


def _refuse_decode():
    from photon_tpu.models.decode import prefill

    prefill({}, jnp.zeros((1, 4), jnp.int32), jnp.array([4]), tiny_cfg().model)


def _refuse_hf_export():
    from photon_tpu.checkpoint.hf_export import llama_state_dict

    llama_state_dict({}, tiny_cfg().model)


@pytest.mark.parametrize("call", [_refuse_serving, _refuse_decode, _refuse_hf_export],
                         ids=lambda f: f.__name__.removeprefix("_refuse_"))
def test_serving_decode_and_hf_export_refuse_the_family(call):
    with pytest.raises(NotImplementedError, match="training path only"):
        call()


def _with(cfg, **paths):
    for dotted, value in paths.items():
        obj = cfg
        *parents, leaf = dotted.split("__")
        for name in parents:
            obj = getattr(obj, name)
        setattr(obj, leaf, value)
    return cfg


@pytest.mark.parametrize("change, message", [
    (dict(model__layer_types="mamba,moe"), "needs n_layers=9"),
    (dict(model__layer_types=PATTERN.replace("attention", "sliding_attention")),
     "with single_branch_layers: 'mamba', 'attention' or 'moe'"),
    (dict(model__layer_types=PATTERN.replace("attention", "conv")), "with single_branch_layers"),
    (dict(model__layer_types=""), "single_branch_layers needs layer_types"),
    (dict(model__single_branch_layers=False), "'mamba', 'conv', 'attention'"),
    (dict(model__mamba_n_groups=3), "does not divide the 8 Mamba heads"),
    (dict(model__mamba_n_groups=0), "does not divide"),
    (dict(model__moe_router="softmax", model__moe_experts_held=0, model__moe_shared_experts=0,
          model__moe_shared_hidden_size=0, model__moe_routed_scale=1.0,
          model__moe_bias_update_speed=0.0), "moe_mlp_act='relu2'"),
    (dict(model__moe_shared_experts=2), "ONE shared expert"),
    (dict(model__moe_mlp_act="gelu"), "needs mlp='moe' with"),
    (dict(model__alibi=True), "single_branch_layers does not combine"),
    (dict(model__first_k_dense=1, model__dense_mlp_hidden_size=8),
     "single_branch_layers does not combine"),
    (dict(model__attn_impl="ring"), "not supported with ring attention"),
    (dict(mesh__tensor=2), "a mesh axis above 1 other than data"),
    (dict(mesh__fsdp=2), "a mesh axis above 1 other than data"),
    (dict(mesh__expert=2), "mesh.expert > 1"),
    (dict(mesh__pipe=3), "mesh.pipe > 1 with layer_types"),
    (dict(model__lora_rank=4), "LoRA adapters"),
    (dict(photon__serve__enabled=True), "photon.serve"),
])
def test_schema_refuses_what_the_family_cannot_do_yet(change, message):
    cfg = tiny_cfg()
    with pytest.raises(ValueError, match=message):
        _with(cfg, **change).validate()


def test_a_data_parallel_mesh_is_what_the_family_runs_on():
    cfg = tiny_cfg()
    cfg.mesh.data = 2
    cfg.validate()


# ---------------------------------------------------------------------------
# the new fields at their defaults: every other preset as it was
# ---------------------------------------------------------------------------

#: each other benchmark preset at its tiny size (``tests/_helpers.TINY_PRESETS``):
#: the leaves of its parameter tree and its loss on ``TOKENS``-like rows with
#: seed-0 weights, read on the commit before groups, ungated experts and layers
#: of one branch existed (PR 51's tree; six of them are ``tests/test_laguna_swa
#: .py``'s numbers of the commit before PR 49); the lowered train steps of the
#: seven were equal text for text on parent and change there too, and the real
#: ``granite-4.0-h-micro-stage1`` step lowered for a described v5e (PERF.md
#: section 6, PR 52)
UNCHANGED = {
    "mpt-125m": (9, 4.5944647789001465),
    "glm-4.7-flash-ep8": (32, 4.5490946769714355),
    "granite-4.0-h-micro-stage1": (37, 4.566521644592285),
    "keye-vl-2.0-30b-a3b-ep8": (20, 4.790014743804932),
    "lfm2-8b-a1b-ep4": (33, 4.588274002075195),
    "xing4.0-29b-a4b-ep8": (44, 4.586148738861084),
    "laguna-xs.2-ep8": (43, 4.587403297424316),
}
LOSS_ATOL = 5e-6


def _old_walk(x, dt, a_log, b, c, d, chunk, compute_dtype):
    """``ops/ssd._walk`` as it was before B and C could come in groups."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    a_neg = -jnp.exp(a_log.astype(jnp.float32))

    def by_chunk(t):
        return jnp.moveaxis(t.reshape(bsz, s // chunk, chunk, *t.shape[2:]), 1, 0)

    step = jax.checkpoint(functools.partial(ssd._chunk, a_neg, compute_dtype))
    _, y = jax.lax.scan(
        step, jnp.zeros((bsz, h, p, n), jnp.float32),
        (by_chunk(x.astype(compute_dtype)), by_chunk(dt),
         by_chunk(b.astype(compute_dtype)), by_chunk(c.astype(compute_dtype))))
    y = jnp.transpose(y, (1, 0, 3, 2, 4)).reshape(bsz, s, h, p)
    return y + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)


def _keeps_its_tree_its_loss_and_its_step(preset: str) -> None:
    """No group, gated experts, two branches a layer: no new leaf, the loss of
    the commit before, and a train step that lowers to the same text whether
    its scan and its norms read the new fields or are the old ones (the scan's
    walk and the RMSNorm as they were)."""
    from photon_tpu.optim import build_optimizer
    from photon_tpu.train import init_train_state
    from photon_tpu.train.train_step import make_train_step

    cfg = tiny_preset(preset)
    m = cfg.model
    assert not m.single_branch_layers and m.mamba_n_groups == 1 and m.moe_gated
    assert m.moe_shared_hidden_size == 0
    params = init_params(m, seed=0)
    names = leaf_names(params)
    assert len(names) == UNCHANGED[preset][0]
    tokens = np.random.default_rng(3).integers(0, 96, size=(2, m.max_seq_len)).astype(np.int32)
    model = MPTModel(m)
    loss = float(make_loss_fn(model, 16)(params, jnp.asarray(tokens)))
    assert abs(loss - UNCHANGED[preset][1]) <= LOSS_ATOL, loss

    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    state = init_train_state(model, tx, params)

    def lowered() -> str:
        return jax.jit(make_train_step(MPTModel(cfg.model), tx, loss_chunk_tokens=16)).lower(
            state, jnp.asarray(tokens)).as_text()

    with_fields = lowered()

    def old_scan(x, dt, a_log, b, c, d, *, chunk, compute_dtype, impl, interpret, groups):
        assert groups == 1 and impl == "xla"
        return _old_walk(x, dt.astype(jnp.float32), a_log, b, c, d, chunk, compute_dtype)

    class OldRMSNorm(mpt.nn.Module):  # ``FP32RMSNorm`` as it was before groups
        eps: float = 1.0e-5
        groups: int = 1

        @mpt.nn.compact
        def __call__(self, x):
            assert self.groups == 1
            x32 = x.astype(jnp.float32)
            y = x32 * jax.lax.rsqrt(
                jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
            scale = self.param("scale", mpt.nn.initializers.ones, (x.shape[-1],), jnp.float32)
            return (y * scale).astype(x.dtype)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ssd, "ssd_scan", old_scan)
        patch.setattr(mpt, "FP32RMSNorm", OldRMSNorm)
        assert lowered() == with_fields


@pytest.mark.parametrize("preset", list(UNCHANGED))
def test_every_other_preset_keeps_its_tree_its_loss_and_its_step(preset):
    from tests._helpers import run_in_fresh_process

    assert set(UNCHANGED) == set(TINY_PRESETS) - {PRESET}
    run_in_fresh_process("tests.test_nemotron_h", "_keeps_its_tree_its_loss_and_its_step", preset)
