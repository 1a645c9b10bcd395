"""Granite-4.0-H (``granitemoehybrid``, dense) on the training path, at a tiny
size with the published structure: Mamba-2 mixers (causal convolution, chunked
state-space scan, gated norm) with an attention layer among them, grouped
key-value heads, no positions, the four multipliers and the softmax scale.

The plain reference is ``benchmark/reference/granite_hybrid.py`` (float32,
``Precision.HIGHEST``, the recurrence walked position by position); on the
CPU the program runs ``attn_impl: xla`` in float32, so the two differ by the
order of summation alone and every tolerance below is a float32 one.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
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

from benchmark.reference import granite_hybrid as ref  # noqa: E402
from photon_tpu.config import load_preset  # noqa: E402
from photon_tpu.config.schema import Config  # noqa: E402
from photon_tpu.models import MPTModel, init_params  # noqa: E402
from photon_tpu.ops import ssd  # noqa: E402
from photon_tpu.train.train_step import make_loss_fn  # noqa: E402

PRESET = "granite-4.0-h-micro-stage1"
TINY = dict(
    d_model=32, n_layers=4, layer_types="mamba,mamba,attention,mamba", n_heads=4,
    n_kv_heads=2, max_seq_len=32, vocab_size=96, mamba_n_heads=4, mamba_d_head=16,
    mamba_d_state=8, mamba_chunk_size=8, mlp_hidden_size=48, attention_multiplier=0.125,
    attn_impl="xla", compute_dtype="float32",
)


def tiny_cfg(**model):
    """The preset with every size shrunk and nothing of its structure changed:
    ``m m a m``, two key-value heads for four query heads, every multiplier."""
    cfg = load_preset(PRESET)
    for key, value in {**TINY, **model}.items():
        setattr(cfg.model, key, value)
    cfg.train.global_batch_size = 2
    cfg.train.device_microbatch_size = 2
    return cfg.validate()


def dims_of(cfg) -> dict:
    return ref.dims_of({**dataclasses.asdict(cfg.model), "d_head": cfg.model.d_head})


def leaf_names(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


TOKENS = np.random.default_rng(3).integers(0, 96, size=(2, 32)).astype(np.int32)

# ---------------------------------------------------------------------------
# the chunked scan against the sequential recurrence
# ---------------------------------------------------------------------------

SCAN_ARGS = ("x", "dt", "a_log", "b", "c", "d")
#: (sequence, chunk): 1, 2 and 5 chunks of 8, and a chunk that is not 8
SCAN_SHAPES = [(8, 8), (16, 8), (40, 8), (24, 4)]
#: the Pallas launches under the interpreter, by compute dtype: three chunks of
#: 128 and two blocks of two heads of 64 (``HEAD_BLOCK`` held to 2), state 128
LAUNCH_SHAPE = dict(seq=384, chunk=128, b=1, h=4, p=64, n=128)
SCAN_CASES = [(seq, chunk, None) for seq, chunk in SCAN_SHAPES] + [
    (LAUNCH_SHAPE["seq"], LAUNCH_SHAPE["chunk"], dtype) for dtype in ("float32", "bfloat16")]
#: bf16 operands under float32 sums, against float32: of the largest entry
BF16_TOLERANCE = 0.05


def _scan_inputs(seq: int, b: int = 2, h: int = 3, p: int = 4, n: int = 5, **_):
    rng = np.random.default_rng(seq)
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    return dict(
        x=f32(rng.normal(size=(b, seq, h, p))),
        dt=f32(rng.uniform(0.01, 0.6, size=(b, seq, h))),
        a_log=f32(np.log(rng.uniform(1.0, 16.0, size=h))),
        b=f32(rng.normal(size=(b, seq, n))), c=f32(rng.normal(size=(b, seq, n))),
        d=f32(rng.normal(size=h)))


def _chunked(args: dict, chunk: int, **how):
    return ssd.ssd_scan(*(args[k] for k in SCAN_ARGS), chunk=chunk,
                        **{"compute_dtype": jnp.float32, **how})


def _sequential(args: dict):
    return ref.recurrence(args["x"], args["dt"], -jnp.exp(args["a_log"]), args["b"],
                          args["c"], args["d"])


@functools.lru_cache(maxsize=None)
def _launch_readings(dtype: str, fault: str | None = None) -> dict:
    """``y`` and the six gradients of ``sum(weights * y)`` at ``LAUNCH_SHAPE``
    from the launches (under the interpreter), the walk (both with ``dtype``
    operands) and the sequential recurrence (float32)."""
    args = _scan_inputs(**LAUNCH_SHAPE)
    shape = args["x"].shape
    weights = jnp.asarray(np.random.default_rng(1).normal(size=shape), jnp.float32)

    def readings(fn):
        y, pull = jax.vjp(lambda *a: fn(dict(zip(SCAN_ARGS, a))), *(args[k] for k in SCAN_ARGS))
        return dict(zip(("y", *SCAN_ARGS), (y, *pull(weights))))

    how = dict(chunk=LAUNCH_SHAPE["chunk"], compute_dtype=jnp.dtype(dtype))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ssd, "HEAD_BLOCK", 2)
        if fault:
            FAULTS[fault](patch)
        launches = lambda a: _chunked(a, **how, impl="pallas", interpret=True)  # noqa: E731
        assert str(jax.make_jaxpr(launches)(args)).count("pallas_call") == 1
        got = jax.jit(readings, static_argnums=0)(launches)
    return {"launches": got, "walk": readings(lambda a: _chunked(a, **how)),
            "sequential": readings(_sequential)}


def _plant_bf16_state(patch):
    """The carried state rounded to bf16 between chunks."""
    real = ssd._forward_kernel

    def kernel(*refs, **static):
        real(*refs, **static)
        state = refs[-5]  # the first of the launch's five scratch buffers
        state[...] = state[...].astype(jnp.bfloat16).astype(jnp.float32)

    patch.setattr(ssd, "_forward_kernel", kernel)


def _plant_dropped_dh(patch):
    """``dH`` dropped across every chunk boundary."""
    real = ssd._backward_kernel

    def kernel(*refs, **static):
        d_state = refs[list(inspect.signature(real).parameters).index("d_state")]
        d_state[...] = jnp.zeros_like(d_state)
        real(*refs, **static)

    patch.setattr(ssd, "_backward_kernel", kernel)


FAULTS = {"bf16_state": _plant_bf16_state, "dropped_dh": _plant_dropped_dh}


def _check_launches(dtype: str, name: str, fault: str | None = None):
    """One reading of the launches against both references: float32 at the
    tests' float32 tolerance, bf16 operands at ``BF16_TOLERANCE``."""
    found = _launch_readings(dtype, fault)
    for other in ("sequential", "walk"):
        got, want = found["launches"][name], found[other][name]
        scale = float(jnp.max(jnp.abs(want)))
        assert scale > 1e-2
        if dtype == "float32":
            # (``a_log``: one number a head, summed over every position and
            # channel: at these widths the walk itself stands 0.5-1.6 of the
            # tolerance from the recurrence, the launches 0.3-3.4)
            slack = 8 if name == "a_log" else 1
            np.testing.assert_allclose(got, want, atol=2e-5 * scale * slack, rtol=2e-4 * slack,
                                       err_msg=f"{name} against the {other}")
        else:
            assert float(jnp.max(jnp.abs(got - want))) < BF16_TOLERANCE * scale, (name, other)


@pytest.mark.parametrize("seq,chunk,launches", SCAN_CASES)
def test_chunked_scan_values_match_the_sequential_recurrence(seq, chunk, launches):
    if launches:
        return _check_launches(launches, "y")
    args = _scan_inputs(seq)
    want = _sequential(args)
    assert float(jnp.max(jnp.abs(want))) > 1.0
    # float32 on both sides; outputs are of order 1-10, the chunked form sums
    # a chunk's terms in another order
    np.testing.assert_allclose(_chunked(args, chunk), want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("wrt", SCAN_ARGS)
@pytest.mark.parametrize("seq,chunk,launches", SCAN_CASES)
def test_chunked_scan_gradient_matches_the_sequential_recurrence(seq, chunk, launches, wrt):
    if launches:
        return _check_launches(launches, wrt)
    args = _scan_inputs(seq)
    weights = jnp.asarray(np.random.default_rng(1).normal(size=(2, seq, 3, 4)), jnp.float32)

    def through(fn):
        return jax.grad(lambda t: jnp.sum(weights * fn({**args, wrt: t})))(args[wrt])

    got, want = through(lambda a: _chunked(a, chunk)), through(_sequential)
    # float32 on both sides; gradients reach 1e2, so the tolerance is relative
    # to the leaf's largest entry
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 1e-2
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=2e-4)


@pytest.mark.parametrize("fault,wrt", [("bf16_state", "y"), ("dropped_dh", "dt")])
def test_a_planted_fault_fails_the_launches_comparison(fault, wrt):
    with pytest.raises(AssertionError, match=f"{wrt} against the"):
        _check_launches("float32", wrt, fault)


def test_on_a_mesh_every_shard_of_rows_runs_its_own_launches(monkeypatch):
    """Rows over ``fsdp``: the launches under ``shard_map`` (a Mosaic call is
    not GSPMD's to partition), the per-head gradients summed over the shards,
    against the same launches on one device."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from photon_tpu.config.schema import MeshConfig
    from photon_tpu.parallel.context import use_mesh
    from photon_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(ssd, "HEAD_BLOCK", 2)
    shape = dict(LAUNCH_SHAPE, b=2, seq=256)
    args = _scan_inputs(**shape)
    operands = tuple(args[k] for k in SCAN_ARGS)
    weights = jnp.asarray(np.random.default_rng(1).normal(size=args["x"].shape), jnp.float32)

    def readings(*a):
        y, pull = jax.vjp(lambda *a: _chunked(dict(zip(SCAN_ARGS, a)), 128, impl="pallas",
                                              interpret=True), *a)
        return (y, *pull(weights))

    want = jax.jit(readings)(*operands)
    mesh = make_mesh(MeshConfig(fsdp=2), devices=jax.devices()[:2])
    with use_mesh(mesh):
        rows, whole = NamedSharding(mesh, P("fsdp")), NamedSharding(mesh, P())
        placed = tuple(jax.device_put(a, whole if a.ndim == 1 else rows) for a in operands)
        sharded = jax.jit(readings)
        text = sharded.lower(*placed).as_text()
        assert "shard_map" in text or "manual" in text
        got = sharded(*placed)
    assert got[0].sharding.spec == P("fsdp")
    for name, a, b in zip(("y", *SCAN_ARGS), got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=1e-5 * scale, rtol=1e-5, err_msg=name)


def test_scan_refuses_a_sequence_that_is_not_whole_chunks():
    with pytest.raises(ValueError, match="multiple of the scan's chunk"):
        _chunked(_scan_inputs(12), 8)


def test_scan_keeps_state_and_decays_in_float32_under_bfloat16_compute():
    """bf16 operands, float32 accumulation: the result stays within bf16's
    rounding of the float32 one, and the scan's carry is float32, in the walk
    and in the launches (whose chunks' start states are what a chunk hands
    the next)."""
    args = _scan_inputs(40)
    exact = _chunked(args, 8)
    low = _chunked(args, 8, compute_dtype=jnp.bfloat16)
    assert low.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(low - exact))) < BF16_TOLERANCE * float(jnp.max(jnp.abs(exact)))
    bf16 = lambda k: args[k][:, :8].astype(jnp.bfloat16)  # noqa: E731
    state, y = jax.eval_shape(
        lambda: ssd._chunk(-jnp.exp(args["a_log"]), jnp.bfloat16,
                           jnp.zeros((2, 3, 4, 5), jnp.float32),
                           (bf16("x"), args["dt"][:, :8], bf16("b"), bf16("c"))))
    assert state.dtype == y.dtype == jnp.float32  # what a chunk hands the next
    found = _launch_readings("bfloat16")["launches"]
    assert {v.dtype for v in found.values()} == {jnp.dtype("float32")}
    wide = _scan_inputs(**LAUNCH_SHAPE)
    seq, h, p = (LAUNCH_SHAPE[k] for k in ("seq", "h", "p"))
    y, states = jax.eval_shape(
        lambda: ssd._forward(wide["x"].astype(jnp.bfloat16).reshape(1, seq, h * p), wide["dt"],
                             -jnp.exp(wide["a_log"]), wide["b"].astype(jnp.bfloat16),
                             wide["c"].astype(jnp.bfloat16), wide["d"], 128, True, True))
    assert y.dtype == states.dtype == jnp.float32
    assert states.shape == (1, 3, LAUNCH_SHAPE["n"], h * p)  # a start state a chunk


@pytest.mark.parametrize("impl,interpret,seq,chunk,heads,d_head,d_state,takes", [
    ("pallas", True, 8192, 256, 64, 64, 128, True),  # the cell's shapes
    ("pallas", False, 8192, 256, 64, 64, 128, False),  # no TPU here and no interpreter
    ("xla", True, 8192, 256, 64, 64, 128, False),
    ("pallas", True, 8, 8, 64, 64, 128, False),  # ``init_params``' row of 8 tokens
    ("pallas", True, 32, 8, 4, 16, 8, False),  # the tiny preset
    ("pallas", True, 8192, 256, 64, 128, 128, False),  # a head that is a whole lane block
    ("pallas", True, 8192, 256, 63, 64, 128, False),  # heads that do not pair
    ("pallas", True, 8192, 192, 64, 64, 128, False),  # a chunk that is not whole strips
    ("pallas", True, 8192, 256, 64, 64, 64, False),  # a state under a lane block
])
def test_the_launches_are_taken_by_shape_and_where_a_kernel_can_run(
        impl, interpret, seq, chunk, heads, d_head, d_state, takes):
    assert ssd.uses_kernel(impl, interpret, seq, chunk, heads, d_head, d_state) is takes


def test_convolution_matches_the_reference():
    rng = np.random.default_rng(2)
    u = jnp.asarray(rng.normal(size=(2, 9, 6)), jnp.float32)
    kernel = jnp.asarray(rng.normal(size=(4, 6)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=6), jnp.float32)
    np.testing.assert_allclose(ssd.causal_conv1d(u, kernel, bias),
                               ref.causal_conv(u, kernel, bias), atol=1e-6)


# ---------------------------------------------------------------------------
# the model against the reference: a Mamba block alone, and `m m a m`
# ---------------------------------------------------------------------------


def _seeded(cfg):
    dims = dims_of(cfg)
    params = ref.make_params(dims, 7)
    n = TOKENS.shape[0] * (TOKENS.shape[1] - 1)
    got = jax.value_and_grad(make_loss_fn(MPTModel(cfg.model), 16))(params, TOKENS)
    want = jax.value_and_grad(lambda p: ref.ce_sum(p, TOKENS, dims) / n)(params)
    return cfg, dims, params, got, want


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights in the program's layout, and loss + gradients of one
    batch from the program (float32 compute) and from the reference."""
    return _seeded(tiny_cfg())


@pytest.fixture(scope="module")
def seeded_block():
    """The same for one Mamba-2 layer alone."""
    return _seeded(tiny_cfg(n_layers=1, layer_types="mamba"))


def test_init_gives_the_reference_tree():
    cfg = tiny_cfg()
    mine = init_params(cfg.model, seed=0)
    theirs = ref.make_params(dims_of(cfg), 0)
    assert leaf_names(mine) == leaf_names(theirs)
    assert jax.tree.map(jnp.shape, mine) == jax.tree.map(jnp.shape, theirs)
    assert sorted(mine) == ["blocks_0", "blocks_1", "blocks_2", "ln_f", "wte"]
    # two Mamba layers, the attention layer, one Mamba layer
    assert [mine[f"blocks_{i}"]["block"]["ln_1"]["scale"].shape[0] for i in range(3)] == [2, 1, 1]


def test_init_follows_the_public_mamba2_code():
    block = init_params(tiny_cfg().model, seed=1)["blocks_0"]["block"]
    a = np.exp(np.asarray(block["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(block["dt_bias"])))  # softplus
    assert dt.min() >= 1e-3 * 0.999 and dt.max() <= 1e-1 * 1.001
    assert np.all(np.asarray(block["D"]) == 1.0)
    assert np.abs(np.asarray(block["conv_kernel"])).max() <= 0.5


@pytest.mark.parametrize("which", ["seeded", "seeded_block"])
def test_forward_logits_match_reference(which, request):
    cfg, dims, params, _, _ = request.getfixturevalue(which)
    logits = MPTModel(cfg.model).apply({"params": params}, TOKENS)
    want = ref.forward(params, TOKENS, dims)
    assert float(jnp.max(jnp.abs(want))) > 0.05
    # float32 on both sides: only the order of summation differs
    np.testing.assert_allclose(logits, want, atol=2e-5)


@pytest.mark.parametrize("which", ["seeded", "seeded_block"])
def test_loss_matches_reference(which, request):
    *_, (loss, _), (want, _) = request.getfixturevalue(which)
    # float32 on both sides, chunked against whole log-softmax
    assert abs(float(loss) - float(want)) < 1e-5


LEAVES = leaf_names(ref.make_params(ref.dims_of({
    **dataclasses.asdict(load_preset(PRESET).model), **TINY, "d_head": 8}), 0))
BLOCK_LEAVES = [n for n in LEAVES if n.startswith("blocks_0/")]


def _check_leaf(seeded, leaf):
    *_, (_, got), (_, want) = seeded
    got = dict(zip(leaf_names(got), jax.tree.leaves(got)))[leaf]
    want = dict(zip(leaf_names(want), jax.tree.leaves(want)))[leaf]
    # float32 on both sides; a leaf's largest entry runs from 1e-7 (A_log,
    # dt_bias) to 3e-2 (the embedding), so the tolerance is relative to it
    scale = float(jnp.max(jnp.abs(want)))
    assert scale > 0
    np.testing.assert_allclose(got, want, atol=1e-4 * scale, rtol=1e-3)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(seeded, leaf):
    _check_leaf(seeded, leaf)


@pytest.mark.parametrize("leaf", BLOCK_LEAVES)
def test_mamba_block_gradient_leaf_matches_reference(seeded_block, leaf):
    _check_leaf(seeded_block, leaf)


CELL_TOKENS = np.random.default_rng(3).integers(0, 96, size=(2, 384)).astype(np.int32)


def _cell_block_cfg(**model):
    """One Mamba-2 layer at widths the launches take: three chunks of 128, two
    blocks of two heads of 64 (``HEAD_BLOCK`` held to 2), state 128."""
    return tiny_cfg(n_layers=1, layer_types="mamba", max_seq_len=384, mamba_n_heads=4,
                    mamba_d_head=64, mamba_d_state=128, mamba_chunk_size=128, **model)


@functools.lru_cache(maxsize=None)
def _cell_block_readings(compute: str, fault: str | None = None):
    """Loss and gradient of one Mamba-2 layer as ``granite4hmicro-train``
    configures the step (``remat``, the kernels' path, here under the
    interpreter) at widths the launches take (three chunks of 128, two blocks
    of two heads of 64, state 128), and the reference's."""
    cfg = _cell_block_cfg(remat=True, attn_impl="pallas", attn_interpret=True,
                          compute_dtype=compute)
    params = ref.make_params(dims_of(cfg), 7)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ssd, "HEAD_BLOCK", 2)
        if fault:
            FAULTS[fault](patch)
        loss_fn = make_loss_fn(MPTModel(cfg.model), 16)
        launches = re.findall(r"name=(ssd_scan_\w+)",
                              str(jax.make_jaxpr(jax.grad(loss_fn))(params, CELL_TOKENS)))
        # forward, forward again under ``remat`` (keeping the start states), backward
        assert sorted(launches) == ["ssd_scan_bwd", "ssd_scan_fwd", "ssd_scan_fwd"]
        got = jax.value_and_grad(loss_fn)(params, CELL_TOKENS)
    return got, _cell_block_reference(), [
        n for n in LEAVES if not re.match("blocks_[12]/", n)]


@functools.lru_cache(maxsize=None)
def _cell_block_reference():
    dims = dims_of(_cell_block_cfg())
    n = CELL_TOKENS.shape[0] * (CELL_TOKENS.shape[1] - 1)
    return jax.value_and_grad(lambda p: ref.ce_sum(p, CELL_TOKENS, dims) / n)(
        ref.make_params(dims, 7))


def _check_cell_block(compute: str, fault: str | None = None):
    (loss, got), (want_loss, want), names = _cell_block_readings(compute, fault)
    assert names == leaf_names(got) == leaf_names(want)
    # float32: the order of summation alone, ``_check_leaf``'s tolerance;
    # bf16 operands: the benchmark's limit on a loss, and of a leaf's largest
    # entry (the sound program reads 0.3-1.1 % here)
    loss_gap, atol, rtol = (1e-5, 1e-4, 1e-3) if compute == "float32" else (2e-3, 0.03, 0.0)
    assert abs(float(loss) - float(want_loss)) < loss_gap
    for name, g, w in zip(names, jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0
        np.testing.assert_allclose(g, w, atol=atol * scale, rtol=rtol, err_msg=name)


@pytest.mark.parametrize("compute", ["bfloat16", "float32"])
def test_mamba_block_gradient_as_the_cell_configures_the_step_matches_reference(compute):
    _check_cell_block(compute)


@pytest.mark.parametrize("compute,fault", [
    ("bfloat16", "dropped_dh"), ("float32", "dropped_dh"), ("float32", "bf16_state")])
def test_a_planted_fault_fails_the_cell_blocks_comparison(compute, fault):
    """(A state rounded to bf16 between chunks cannot fail the bf16 case: the
    products round the state they read to bf16 there, as the configuration
    states, and at the seeded decays a chunk's end state is ``exp(-30)`` of
    its start's; under float32 operands it moves ``dt_bias`` by 0.8 %.)"""
    with pytest.raises(AssertionError, match="A_log|dt_bias"):
        _check_cell_block(compute, fault)


def test_three_adopt_steps_match_reference(seeded):
    """Three optimizer steps through ``make_train_step`` and through the
    reference's ``Grad`` + ``adopt_step``: the losses, and every leaf's norm
    of the weights' change."""
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
    for _ in range(3):
        state, metrics = step(state, TOKENS)
        loss, g = grad(theirs, TOKENS)
        theirs, moments = ref.adopt_step(theirs, moments, g, opt)
        assert abs(float(metrics["loss"]) - float(loss)) < 1e-5
    change = lambda p: ref.leaf_norms(jax.tree.map(jnp.subtract, p, params))  # noqa: E731
    # float32 both; ADOPT divides by sqrt(v), which magnifies rounding where
    # a gradient entry is all but zero: 1e-3 of the median leaf's change
    assert ref.worst_leaf_gap(change(state.params), change(theirs)) < 1e-3


# ---------------------------------------------------------------------------
# causality, and nothing silently dropped
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t", [1, 7, 8, 20, 31])
def test_changing_a_token_leaves_every_earlier_output_bit_equal(seeded, t):
    """Through the convolution, the scan (across its chunk of 8) and
    attention: the logits before position ``t`` do not see token ``t``."""
    cfg, _, params, _, _ = seeded
    model = MPTModel(cfg.model)
    changed = TOKENS.copy()
    changed[:, t] = (changed[:, t] + 1) % 96
    a = np.asarray(model.apply({"params": params}, TOKENS))
    b = np.asarray(model.apply({"params": params}, changed))
    assert np.array_equal(a[:, :t], b[:, :t])
    assert not np.array_equal(a[:, t], b[:, t])


@pytest.mark.parametrize("field,value", [
    ("embedding_multiplier", 6.0), ("residual_multiplier", 0.5), ("logits_scaling", 4.0),
    ("attention_multiplier", 0.25), ("attention_multiplier", 0.0)])
def test_every_multiplier_changes_the_output(seeded, field, value):
    """None of the four multipliers nor the softmax scale is silently dropped:
    changing one moves the logits and the training loss, in the program as in
    the reference."""
    cfg, dims, params, _, _ = seeded
    # queries and keys large enough that the softmax is far from uniform
    # (seeded at std 0.02 its scores are ~1e-3 and no scale would show)
    attn = params["blocks_1"]["block"]
    params = {**params, "blocks_1": {"block": {
        **attn, **{k: {"kernel": attn[k]["kernel"] * 60.0} for k in ("q_proj", "k_proj")}}}}
    other = tiny_cfg(**{field: value})
    logits = MPTModel(other.model).apply({"params": params}, TOKENS)
    base = MPTModel(cfg.model).apply({"params": params}, TOKENS)
    # logits are ~2e-2 and float32 noise between two runs of one model ~1e-8;
    # the attention branch (one layer, x 0.22, a down-scaled out_proj) moves
    # them by ~5e-5, every other multiplier by far more
    assert float(jnp.max(jnp.abs(logits - base))) > 1e-6
    changed = dict(dims, **{field: value or 1 / math.sqrt(dims["d_head"])})
    np.testing.assert_allclose(logits, ref.forward(params, TOKENS, changed), atol=2e-5)


def _jaxpr_of_default_block() -> str:
    cfg = Config()
    m = cfg.model
    m.d_model, m.n_layers, m.n_heads, m.max_seq_len, m.vocab_size = 32, 2, 2, 16, 64
    m.attn_impl, m.compute_dtype = "xla", "float32"
    model = MPTModel(m)
    params = init_params(m)
    return str(jax.make_jaxpr(lambda p: model.apply({"params": p}, TOKENS[:, :16] % 64))(params))


def test_default_fields_add_nothing_to_another_models_graph(monkeypatch):
    """With every new field at its default the model's jaxpr holds no multiply
    by a multiplier and no division of the logits, and the attention call gets
    the scale it got before (``None``: the dispatch's own ``1/sqrt(d_head)``)."""
    import photon_tpu.models.mpt as mpt_mod

    seen = []
    real = mpt_mod.multihead_attention

    def spy(*args, **kwargs):
        seen.append(kwargs.get("scale"))
        return real(*args, **kwargs)

    monkeypatch.setattr(mpt_mod, "multihead_attention", spy)
    jaxpr = _jaxpr_of_default_block()
    assert seen and set(seen) == {None}
    # the only literal scalings of a default block: the softmax scale 1/sqrt(16)
    # and gelu's constants; 12, 0.22 and 8 appear nowhere
    literals = ("12.0:f32[]", "0.2199", "8.0:f32[]")  # 0.22 prints as 0.21999999880...
    assert not [lit for lit in literals if lit in jaxpr]
    scaled = tiny_cfg()
    text = str(jax.make_jaxpr(lambda p: MPTModel(scaled.model).apply(
        {"params": p}, TOKENS))(init_params(scaled.model)))
    assert all(lit in text for lit in literals)


@pytest.mark.parametrize("scale", [None, 0.015625])
def test_attention_scale_on_the_pallas_path_matches_xla(scale):
    """``multihead_attention(scale=)`` through the flash kernel in the Pallas
    interpreter against the XLA path, grouped 32 / 8 heads of 64 as the
    published model has them, forward and gradient."""
    from photon_tpu.ops.attention import multihead_attention

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 128, 32, 64)) * 2, jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 128, 8, 64)) * 2, jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 128, 8, 64)), jnp.float32)

    def run(impl, **kw):
        fn = lambda q, k, v: multihead_attention(  # noqa: E731
            q, k, v, impl=impl, causal=True, scale=scale, **kw)
        out, grads = jax.value_and_grad(lambda *a: jnp.sum(fn(*a) ** 2), argnums=(0, 1, 2))(
            q, k, v)
        return fn(q, k, v), grads

    (got, got_g), (want, want_g) = run("pallas", interpret=True), run("xla")
    # float32 in the interpreter and in XLA: blockwise against whole softmax
    np.testing.assert_allclose(got, want, atol=2e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)
    if scale is not None:
        # and the scale is not the default one
        assert float(jnp.max(jnp.abs(want - run("xla")[0]))) == 0.0
        plain = multihead_attention(q, k, v, impl="xla", causal=True)
        assert float(jnp.max(jnp.abs(want - plain))) > 1e-3


# ---------------------------------------------------------------------------
# the published cut, its rules, and who refuses the family
# ---------------------------------------------------------------------------


def test_the_published_width_cut_counts_its_parameters():
    """``jax.eval_shape`` of the preset's own tree: ISSUE 33's table."""
    model = load_preset(PRESET).model
    shapes = jax.eval_shape(lambda: init_params(model, seed=0))
    count = lambda t: sum(math.prod(a.shape) for a in jax.tree.leaves(t))  # noqa: E731
    assert count(shapes["blocks_0"]) == 5 * 76_182_976
    assert count(shapes["blocks_1"]) == 60_821_504
    assert count(shapes["blocks_2"]) == 4 * 76_182_976
    assert count(shapes["wte"]) + count(shapes["ln_f"]) == 25_692_160
    assert count(shapes) == 772_160_448
    block = shapes["blocks_0"]["block"]
    assert block["in_proj"]["kernel"].shape == (5, 2048, 8512)
    assert block["conv_kernel"].shape == (5, 4, 4352)
    assert block["out_proj"]["kernel"].shape == (5, 4096, 2048)
    assert shapes["blocks_1"]["block"]["k_proj"]["kernel"].shape == (1, 2048, 512)


def test_the_preset_is_what_the_benchmark_configuration_states():
    from benchmark.program import build_config

    config = json.loads((ROOT / "benchmark/configs" / f"{PRESET}.json").read_text())
    traffic = json.loads((ROOT / "benchmark/traffic/stage1-1x8192.json").read_text())
    cfg = build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=2**31 + 5)
    assert (cfg.train.global_batch_size, cfg.train.device_microbatch_size) == (1, 1)
    assert [s[1:] for s in cfg.model.stacks] == [
        ("mamba", False, 5), ("attention", False, 1), ("mamba", False, 4)]
    assert cfg.model.mamba_layers == 9 and cfg.model.mamba_d_inner == 4096
    assert cfg.model.d_head == 64 and cfg.model.training_path_only
    # a preset edited under the benchmark is refused
    config["model"]["mamba_d_state"] = 64
    with pytest.raises(ValueError, match="mamba_d_state"):
        build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=1)


def test_every_new_parameter_has_a_sharding_rule():
    """No leaf of the family falls through to the replicate-unknowns default:
    each matches a rule of ``parallel/sharding.py``."""
    import re

    from photon_tpu.parallel.sharding import _RULES

    names = leaf_names(init_params(tiny_cfg().model, seed=0))
    new = [n for n in names if re.search(
        r"(in_proj|conv_kernel|conv_bias|A_log|dt_bias|/D$|mamba_norm|blocks_\d/)", n)]
    assert len(new) == len(names) - 2  # all but wte and ln_f
    unmatched = [n for n in names if not any(re.search(p, n) for p, _ in _RULES)]
    assert not unmatched


def test_sharded_specs_keep_the_in_projection_whole():
    from jax.sharding import PartitionSpec as P

    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.config.schema import MeshConfig
    from photon_tpu.parallel.sharding import param_specs

    mesh = make_mesh(MeshConfig(fsdp=2, tensor=2), devices=jax.devices()[:4])
    specs = param_specs(init_params(tiny_cfg().model, seed=0), mesh)
    block = specs["blocks_0"]["block"]
    assert block["in_proj"]["kernel"] == P("pipe", "fsdp", None)
    assert block["out_proj"]["kernel"] == P("pipe", "tensor", "fsdp")
    assert block["A_log"] == P("pipe", None)
    assert block["conv_kernel"] == P("pipe", None, None)


def test_trainer_tells_the_mamba_layers_and_chunks_on_its_span():
    from photon_tpu.models.step import step_attrs

    # (no kernel in a step on the CPU backend: the flash plan adds no key, and
    # no scan takes ``ops/ssd``'s launches)
    told = lambda model: step_attrs(model, batch_rows=2).steps  # noqa: E731
    preset = load_preset(PRESET).model
    assert told(preset) == {"mamba_layers": 9, "mamba_groups": 1, "ssd_chunks": 32,
                            "ssd_kernel_layers": 0}
    # where a kernel can run (on the chip; here under the interpreter) the
    # preset's shapes take the launches, the tiny configuration's never do
    on_the_kernels_path = told(dataclasses.replace(preset, attn_interpret=True))
    assert on_the_kernels_path["ssd_kernel_layers"] == on_the_kernels_path["mamba_layers"] == 9
    tiny = tiny_cfg(attn_impl="pallas", attn_interpret=True).model
    assert {k: v for k, v in told(tiny).items() if "flash" not in k} == {
        "mamba_layers": 3, "mamba_groups": 1, "ssd_chunks": 4, "ssd_kernel_layers": 0}
    assert told(tiny_cfg().model) == {"mamba_layers": 3, "mamba_groups": 1, "ssd_chunks": 4,
                                      "ssd_kernel_layers": 0}
    assert told(load_preset("mpt-125m").model) == {}


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
    rows = np.tile(TOKENS, (4, 1))
    with ShardWriter(tmp_path / "rows", 32, 96, samples_per_shard=8) as w:
        w.write(rows)
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


def test_the_multipliers_alone_are_refused_by_serving():
    """A model with attention everywhere but a scaled embedding would be
    served wrong, not refused, if only ``layer_types`` were checked."""
    from photon_tpu.config.schema import refuse_training_only_family

    model = Config().model
    refuse_training_only_family(model, "serving")  # the default model passes
    model.embedding_multiplier = 12.0
    with pytest.raises(NotImplementedError, match="multipliers"):
        refuse_training_only_family(model, "serving")


def test_hf_import_refuses_the_model_type():
    from photon_tpu.checkpoint.hf_import import model_config_from_hf

    with pytest.raises(ValueError, match="granitemoehybrid"):
        model_config_from_hf({"model_type": "granitemoehybrid"})


def _with(cfg, **paths):
    for dotted, value in paths.items():
        obj = cfg
        *parents, leaf = dotted.split("__")
        for name in parents:
            obj = getattr(obj, name)
        setattr(obj, leaf, value)
    return cfg


@pytest.mark.parametrize("change, message", [
    (dict(model__layer_types="mamba,attention"), "needs n_layers=4"),
    (dict(model__layer_types="mamba,mamba,linear_attention,mamba"),
     "each 'mamba', 'conv', 'attention', 'full_attention' or"),
    (dict(model__max_seq_len=36), "not a multiple of mamba_chunk_size"),
    (dict(model__mamba_d_state=0), "all > 0"),
    (dict(model__first_k_dense=1, model__dense_mlp_hidden_size=8),
     "'mamba' layers do not combine with first_k_dense or mlp='moe'"),
    (dict(model__mlp="moe", model__moe_router="sigmoid", model__moe_mlp_act="swiglu",
          model__moe_num_experts=4), "'mamba' layers do not combine"),
    (dict(model__kv_lora_rank=8, model__q_lora_rank=8, model__qk_nope_head_dim=4,
          model__qk_rope_head_dim=4, model__v_head_dim=8, model__n_kv_heads=0, model__rope=True),
     "layer_types does not combine with latent attention"),
    (dict(model__residual_multiplier=0.0), "must be > 0"),
    (dict(model__attention_multiplier=-1.0), "attention_multiplier >= 0"),
    (dict(model__attn_impl="ring"), "not supported with ring attention"),
    (dict(mesh__pipe=2), "mesh.pipe > 1 with layer_types or the multipliers"),
    (dict(mesh__tensor=2), "mesh.tensor > 1 with 'mamba' layers"),
    (dict(mesh__sequence=2, model__attention_multiplier=0.0), "mesh.sequence > 1"),
    (dict(model__lora_rank=4), "LoRA adapters"),
    (dict(photon__serve__enabled=True), "photon.serve"),
])
def test_schema_refuses_what_the_family_cannot_do_yet(change, message):
    cfg = tiny_cfg()
    with pytest.raises(ValueError, match=message):
        _with(cfg, **change).validate()


def test_layer_types_survives_yaml_and_json_as_written(tmp_path):
    """One spelling everywhere: the preset's YAML, a resolved config on disk,
    the benchmark's JSON and ``--set`` all hold the comma-joined string."""
    cfg = tiny_cfg()
    cfg.to_yaml(tmp_path / "resolved.yaml")
    back = Config.from_yaml(tmp_path / "resolved.yaml").validate()
    assert back.model.layer_types == TINY["layer_types"]
    assert back.model.layer_kinds == ("mamba", "mamba", "attention", "mamba")
    assert Config.from_json(cfg.to_json()).model.stacks == [
        ("blocks_0", "mamba", False, 2), ("blocks_1", "attention", False, 1),
        ("blocks_2", "mamba", False, 1)]
    assert Config().model.layer_kinds == () and not Config().model.training_path_only
