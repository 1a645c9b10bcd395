"""GLM-4.7-Flash (``glm4_moe_lite``) on the training path, at a tiny size with
the published structure: latent attention (both ranks, split head widths), a
dense block before the expert stack, sigmoid top-k routing with a selection
bias, a shared expert, and a chip that holds 4 of 8 routed experts.

The plain reference is ``benchmark/reference/glm_moe_lite.py`` (float32,
``Precision.HIGHEST``, experts as a masked loop); on the CPU the program runs
``attn_impl: xla`` and the grouped products through ``jax.lax.ragged_dot``,
and the megablox kernel in the Pallas interpreter where a test says so.
"""

from __future__ import annotations

import dataclasses
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # `benchmark` is a sibling of `tests`, not installed
    sys.path.insert(0, str(ROOT))

from benchmark.reference import glm_moe_lite as ref  # noqa: E402
from photon_tpu.config import load_preset  # noqa: E402
from photon_tpu.models import MPTModel  # noqa: E402
from photon_tpu.ops import moe  # noqa: E402
from photon_tpu.train.train_step import make_loss_fn  # noqa: E402
from tests._helpers import recorded_spans  # noqa: E402

TINY = dict(
    d_model=64, n_layers=3, n_heads=4, max_seq_len=32, vocab_size=96,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
    v_head_dim=16, dense_mlp_hidden_size=160, mlp_hidden_size=48,
    moe_num_experts=8, moe_top_k=2, moe_experts_held=4,
    attn_impl="xla", compute_dtype="float32",
)


def tiny_cfg(**model):
    """The preset with every size shrunk and nothing of its structure changed:
    1 dense + 2 expert layers, 8 experts top-2 of which 4 are held."""
    cfg = load_preset("glm-4.7-flash-ep8")
    for key, value in {**TINY, **model}.items():
        setattr(cfg.model, key, value)
    cfg.train.global_batch_size = 4
    cfg.train.device_microbatch_size = 4
    return cfg.validate()


def dims_of(cfg) -> dict:
    return ref.dims_of(dataclasses.asdict(cfg.model))


def leaf_names(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


TOKENS = np.random.default_rng(3).integers(0, 96, size=(4, 32)).astype(np.int32)


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


def test_init_gives_the_reference_tree():
    """``init_params`` and the reference's ``make_params`` build one tree:
    the same names and shapes, so seeded reference weights load as they are."""
    from photon_tpu.models import init_params

    cfg = tiny_cfg()
    mine = init_params(cfg.model, seed=0)
    theirs = ref.make_params(dims_of(cfg), 0)
    assert leaf_names(mine) == leaf_names(theirs)
    assert jax.tree.map(jnp.shape, mine) == jax.tree.map(jnp.shape, theirs)


def test_forward_logits_match_reference(seeded):
    cfg, dims, params, _, _ = seeded
    logits = MPTModel(cfg.model).apply({"params": params}, TOKENS)
    want = ref.forward(params, TOKENS, dims)
    # float32 on both sides: only the order of summation differs (logits ~0.6)
    np.testing.assert_allclose(logits, want, atol=2e-5)


def test_loss_matches_reference(seeded):
    *_, (loss, _), (want, _) = seeded
    # float32 on both sides, chunked against whole log-softmax
    assert abs(float(loss) - float(want)) < 1e-5


LEAVES = leaf_names(ref.make_params(ref.dims_of({
    **dataclasses.asdict(load_preset("glm-4.7-flash-ep8").model), **TINY}), 0))


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(seeded, leaf):
    *_, (_, got), (_, want) = seeded
    got = dict(zip(leaf_names(got), jax.tree.leaves(got)))[leaf]
    want = dict(zip(leaf_names(want), jax.tree.leaves(want)))[leaf]
    if leaf.endswith("router_bias"):
        # selects only: no gradient on either side
        assert not np.any(got) and not np.any(want)
        return
    # float32 on both sides; the largest entries of a leaf are 1e-4 .. 6e-2,
    # so 1e-6 absolute is two or more digits below every leaf's scale
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-4)


def test_bfloat16_compute_stays_near_the_reference(seeded):
    cfg, dims, params, _, (want, _) = seeded
    loss = make_loss_fn(MPTModel(tiny_cfg(compute_dtype="bfloat16").model), 16)(
        params, TOKENS)
    # bf16 products (8 bits of mantissa) over 3 layers on a loss of ~4.6
    assert abs(float(loss) - float(want)) < 2e-2


def test_the_megablox_kernel_in_the_interpreter_gives_the_same_gradients(seeded):
    """The grouped products the chip runs (the kernel, its transposed form
    and the weight-gradient kernel), here in the Pallas interpreter."""
    cfg, dims, params, _, (want_loss, want) = seeded
    model = MPTModel(tiny_cfg(attn_interpret=True).model)
    loss, got = jax.value_and_grad(make_loss_fn(model, 16))(params, TOKENS)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-4)


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------


def test_the_bias_changes_who_is_chosen_and_not_the_weights():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 8)) * 0.3, jnp.float32)
    scores = jax.nn.sigmoid(h @ w)
    plain_idx, plain_gates = moe.sigmoid_route(h, w, jnp.zeros(8), 2, 1.8)
    bias = jnp.zeros(8).at[5].set(10.0)  # expert 5 always selected
    idx, gates = moe.sigmoid_route(h, w, bias, 2, 1.8)
    assert np.all(np.any(np.asarray(idx) == 5, axis=-1))
    assert np.any(np.asarray(idx) != np.asarray(plain_idx))
    # the weights are the raw scores of whoever was chosen, renormalised to
    # the scale: the bias is nowhere in them
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), axis=-1)
    np.testing.assert_allclose(
        gates, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.8, rtol=1e-5)
    # ... and it takes no gradient
    grad = jax.grad(lambda b: jnp.sum(moe.sigmoid_route(h, w, b, 2, 1.8)[1] ** 2))(bias)
    assert not np.any(grad)


def test_the_balancing_rule_moves_the_bias_against_the_load():
    """An expert over the mean load loses bias, one under it gains, by the
    relative error times the speed and by the speed at most; an expert at the
    mean stays. The reference's ``bias_step`` is the same rule."""
    rows = jnp.asarray([[0.0, 50.0, 100.0, 150.0, 400.0, 100.0, 0.0, 0.0]])  # mean 100
    bias = jnp.full((1, 8), 0.25, jnp.float32)
    moved = moe.balanced_router_bias(bias, rows, 0.02)
    np.testing.assert_allclose(
        moved - bias, [[0.02, 0.01, 0.0, -0.01, -0.02, 0.0, 0.02, 0.02]], atol=1e-7)
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(bias - ref.bias_step(rows, 0.02)))
    # a few rows' difference in the count (two precisions of one model) is a
    # few thousandths of the speed, where a sign rule would differ by twice it
    nudged = moe.balanced_router_bias(bias, rows.at[0, 2].add(1.0).at[0, 3].add(-1.0), 0.02)
    assert float(jnp.max(jnp.abs(nudged - moved))) < 0.02 * 0.011


@pytest.mark.parametrize("microbatches", [1, 2])
def test_three_steps_with_the_balancing_rule_follow_the_reference(microbatches):
    """The train step moves ``router_bias`` after the optimizer by the rows
    the step routed (summed over microbatches); the reference's ``Grad`` and
    ``adopt_step`` do the same, and the weights end within float32 of each
    other. The speed is large here so that the bias changes who is chosen
    within three steps."""
    from benchmark.program import optimizer_settings
    from photon_tpu.train.trainer import Trainer

    cfg = tiny_cfg(moe_bias_update_speed=0.2)
    cfg.train.device_microbatch_size = 4 // microbatches
    dims = dims_of(cfg)
    params0 = ref.make_params(dims, 11)
    batches = [np.roll(TOKENS, i, axis=1) for i in range(3)]
    trainer = Trainer(cfg, params=jax.tree.map(jnp.array, params0))
    trainer.fit(list(batches), duration_steps=3)
    got = trainer.state.params

    opt = optimizer_settings(cfg)
    grad = ref.Grad(dims, rows=2)
    want, state = params0, ref.adopt_init(params0)
    for batch in batches:
        _, g = grad(want, batch)
        # the balancing step rides the gradient tree and is no part of the gradient
        clipped = ref.clip_by_global_norm(g, 1.0)
        assert not np.any(clipped["blocks"]["block"]["router_bias"])
        want, state = ref.adopt_step(want, state, g, opt)

    bias0 = np.asarray(params0["blocks"]["block"]["router_bias"])
    bias = np.asarray(got["blocks"]["block"]["router_bias"])
    assert np.max(np.abs(bias - bias0)) > 0.05  # it moved, by up to 3 x 0.2
    np.testing.assert_allclose(bias, want["blocks"]["block"]["router_bias"], atol=1e-6)
    for name, a, b in zip(leaf_names(got), jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    # held constant at speed 0
    still = Trainer(tiny_cfg(moe_bias_update_speed=0.0),
                    params=jax.tree.map(jnp.array, params0))
    still.fit(list(batches), duration_steps=2)
    np.testing.assert_array_equal(
        np.asarray(still.state.params["blocks"]["block"]["router_bias"]), bias0)


# ---------------------------------------------------------------------------
# the share: what expert parallelism asks of the layer
# ---------------------------------------------------------------------------


def _layer_weights(seed: int, n_experts: int = 8, d: int = 32, hidden: int = 48):
    rng = np.random.default_rng(seed)
    f32 = lambda *shape, scale=0.2: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * scale, jnp.float32)
    return {
        "router": f32(d, n_experts, scale=0.5), "router_bias": f32(n_experts, scale=0.05),
        "moe_gate": f32(n_experts, d, hidden), "moe_up": f32(n_experts, d, hidden),
        "moe_down": f32(n_experts, hidden, d),
        "shared_gate_proj": {"kernel": f32(d, hidden)},
        "shared_up_proj": {"kernel": f32(d, hidden)},
        "shared_down_proj": {"kernel": f32(hidden, d)},
    }


def _program_share(h, p, first: int, held: int, top_k: int = 2, **kw):
    """The program's routed part for the experts ``first .. first + held``."""
    sl = slice(first, first + held)
    return moe.dropless_moe_mlp(
        h, p["router"], p["router_bias"], p["moe_gate"][sl], p["moe_up"][sl],
        p["moe_down"][sl], top_k=top_k, first_expert=first, routed_scale=1.8,
        compute_dtype=jnp.float32, **kw)


@pytest.mark.parametrize("held", [8, 4, 2])
def test_the_shares_add_up_to_the_uncut_layer(held):
    """The routed parts of all the shares, with the shared expert counted
    once, are the uncut layer's output: the reference with every expert held."""
    p = _layer_weights(1)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(2, 24, 32)), jnp.float32)
    uncut = dict(top_k=2, routed_scale=1.8, experts_held=8, first_expert=0)
    mm = ref.MATMULS["float32"]
    want = ref.routed_experts(h, p, uncut, mm) + ref.shared_expert(h, p, mm)
    parts, rows = [], 0.0
    for first in range(0, 8, held):
        out, counters = _program_share(h, p, first, held)
        parts.append(out)
        rows += float(counters["rows_held"])
    assert rows == 2 * 24 * 2  # every assignment is some share's, once
    got = sum(parts) + ref.shared_expert(h, p, mm)
    np.testing.assert_allclose(got, want, atol=1e-5)
    if held < 8:  # and one share alone is not the layer
        assert float(jnp.max(jnp.abs(parts[0] + ref.shared_expert(h, p, mm) - want))) > 1e-3


IMBALANCE = {
    # bias so large that it alone selects: every token to experts 0 and 1,
    # so experts 2 and 3 (held) see no token
    "all_to_two_held": [50.0, 40.0, 0, 0, 0, 0, 0, 0],
    # every token to one held expert and one absent one
    "one_held_one_absent": [0, 0, 50.0, 0, 0, 0, 40.0, 0],
    # every token to absent experts: nothing is routed here at all
    "none_held": [0, 0, 0, 0, 50.0, 40.0, 0, 0],
}


@pytest.mark.parametrize("impl", ["xla", "pallas-interpret"])
@pytest.mark.parametrize("case", sorted(IMBALANCE))
def test_sorted_dispatch_matches_the_loop_under_imbalance(case, impl):
    p = dict(_layer_weights(4), router_bias=jnp.asarray(IMBALANCE[case], jnp.float32))
    h = jnp.asarray(np.random.default_rng(5).normal(size=(64, 32)), jnp.float32)
    dims = dict(top_k=2, routed_scale=1.8, experts_held=4, first_expert=0)
    # off the TPU the grouped products step down to ``jax.lax.ragged_dot``
    kw = dict(interpret=True) if impl == "pallas-interpret" else {}

    def program(h, p):
        return _program_share(h, p, 0, 4, **kw)[0]

    def looped(h, p):
        return ref.routed_experts(h, {**p, **{k: p[k][:4] for k in (
            "moe_gate", "moe_up", "moe_down")}}, dims, ref.MATMULS["float32"])

    np.testing.assert_allclose(program(h, p), looped(h, p), atol=1e-5)
    _, counters = _program_share(h, p, 0, 4, **kw)
    expect = {"all_to_two_held": (128.0, 2.0), "one_held_one_absent": (64.0, 4.0),
              "none_held": (0.0, 0.0)}[case]
    assert (float(counters["rows_held"]), float(counters["max_expert_load"])) == expect
    # gradients through the permutation, the grouped products and the gates
    weigh = jnp.asarray(np.random.default_rng(6).normal(size=(64, 32)), jnp.float32)
    got = jax.grad(lambda h, p: jnp.sum(program(h, p) * weigh), argnums=(0, 1))(h, p)
    want = jax.grad(lambda h, p: jnp.sum(looped(h, p) * weigh), argnums=(0, 1))(h, p)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_no_tokens_by_experts_by_capacity_tensor_in_the_dropless_path():
    """The capacity path builds ``[k, N, E, C]`` one-hots; the dropless
    layer's largest intermediate is the permuted rows, ``N k`` by the wider
    of the model and the expert (the model is no narrower than the router's 64
    outputs, as published, so the rows counted by expert are smaller too)."""
    n, d, hidden, e, k = 512, 64, 48, 64, 4
    p = _layer_weights(0, n_experts=e, d=d, hidden=hidden)
    h = jnp.zeros((n, d), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda h: _program_share(h, p, 0, 8, top_k=k)[0])(h)

    def sizes(jaxpr):
        for eqn in jaxpr.eqns:
            for v in eqn.outvars:
                yield int(np.prod(v.aval.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    largest = max(sizes(jaxpr.jaxpr))
    assert largest <= n * k * max(d, hidden)
    capacity = moe.expert_capacity(n, e, k, 1.0)
    assert n * e * capacity >= 8 * largest  # what one [N, E, C] tensor would have been


# ---------------------------------------------------------------------------
# the dispatch's un-permutes gather from the live prefix (PR 43)
# ---------------------------------------------------------------------------

CHUNK = 512  # rows a trip reads in these tests (the chip's: DISPATCH_CHUNK_BYTES' worth)
LIVE = {"none": 0, "one": 1, "chunk-1": CHUNK - 1, "chunk": CHUNK, "chunk+1": CHUNK + 1,
        "all": None}


@pytest.fixture
def small_chunks(monkeypatch):
    """``CHUNK`` rows a trip for bfloat16 rows of width 32."""
    monkeypatch.setattr(moe, "DISPATCH_CHUNK_BYTES", CHUNK * 32 * 2)


def _routed_order(router: str, k: int, n: int, d: int = 16, experts: int = 16):
    """``n`` tokens routed by ``router`` to ``k`` of ``experts``, sorted as the
    layer sorts them with the first quarter of the experts held."""
    rng = np.random.default_rng(k)
    h = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(d, experts)), jnp.float32)
    if router == "sigmoid":
        idx, _ = moe.sigmoid_route(h, w, jnp.zeros(experts), k, 1.0)
    else:
        idx, _ = moe.softmax_route(h, w, k)
    held = experts // 4
    key = jnp.where(idx < held, idx, held).reshape(n * k)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    return order, jnp.argsort(order).astype(jnp.int32)


# the parent's formulation (before PR 43): every movement a whole gather from
# all ``N k`` rows, unmasked; the combine an einsum over the un-permuted rows


@jax.custom_vjp
def _whole_rows_by_token(rows, order, inv):
    return rows[inv]


_whole_rows_by_token.defvjp(lambda rows, order, inv: (rows[inv], order),
                            lambda order, g: (g[order], None, None))


def _whole_combine(rows, gates, order, inv, n_live):
    per_slot = _whole_rows_by_token(rows, order, inv).reshape(*gates.shape, rows.shape[-1])
    return jnp.einsum("nk,nkd->nd", gates, per_slot,
                      preferred_element_type=jnp.float32).astype(rows.dtype)


@pytest.mark.parametrize("router", ["sigmoid", "softmax_topk"])
@pytest.mark.parametrize("k", [4, 8])
@pytest.mark.parametrize("live", sorted(LIVE))
def test_the_unpermutes_are_the_masked_gathers_exactly(live, k, router, small_chunks):
    """The un-permute and the two functions it sits in, forward and through
    ``jax.vjp``, against ``where(live, rows[inv], 0)`` and the parent's whole
    gathers, to the bit, with the live rows ending before, at and after a
    chunk's end: nothing is rounded differently, dead slots are zeros."""
    n, d = CHUNK, 32  # N k = 4 or 8 chunks
    m = n * k
    order, inv = _routed_order(router, k, n)
    n_live = jnp.asarray(m if LIVE[live] is None else LIVE[live], jnp.int32)
    rng = np.random.default_rng(11)
    bf16 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)  # noqa: E731
    x, g = bf16(n, d), bf16(n, d)
    # expert-ordered rows are zero past the live ones (megablox's own zeros)
    prefix = (jnp.arange(m) < n_live)[:, None]
    rows, g_rows = jnp.where(prefix, bf16(m, d), 0), jnp.where(prefix, bf16(m, d), 0)
    slots = (inv < n_live)[:, None]
    gates = jnp.where(slots.reshape(n, k), jnp.asarray(rng.uniform(size=(n, k)), jnp.float32), 0)

    np.testing.assert_array_equal(
        moe.rows_of_live_prefix(rows, inv, n_live), jnp.where(slots, rows[inv], 0))

    by_expert, pull = jax.vjp(lambda x: moe._rows_by_expert(x, order, inv, n_live, k), x)
    np.testing.assert_array_equal(by_expert, x[order // k])
    want = jnp.sum(g_rows[inv].reshape(n, k, d).astype(jnp.float32), axis=1)
    np.testing.assert_array_equal(pull(g_rows)[0], want.astype(jnp.bfloat16))

    got, pull = jax.vjp(lambda r, w: moe._combine(r, w, order, inv, n_live), rows, gates)
    want, pull_whole = jax.vjp(lambda r, w: _whole_combine(r, w, order, inv, n_live), rows, gates)
    np.testing.assert_array_equal(got, want)
    (d_rows, d_gates), (want_rows, want_gates) = pull(g), pull_whole(g)
    np.testing.assert_array_equal(d_rows, want_rows)
    # the gates' cotangent sums the same products over D in expert order: on
    # the CPU the einsum's transpose is a matrix product with an order of its own
    np.testing.assert_allclose(d_gates, want_gates, rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("router, k, experts, held, interpret", [
    ("sigmoid", 4, 8, 3, False), ("sigmoid", 8, 16, 5, False),
    ("softmax_topk", 4, 8, 3, False), ("softmax_topk", 8, 16, 5, False),
    ("sigmoid", 4, 16, 1, False), ("sigmoid", 2, 8, 3, True)])
def test_two_rematted_layers_in_a_scan_give_the_whole_gathers_results_exactly(
        router, k, experts, held, interpret, monkeypatch, small_chunks):
    """``dropless_moe_mlp`` under ``jax.checkpoint`` inside a ``lax.scan`` of
    two layers (the model's own nesting), bfloat16 compute: the output and the
    experts' gradients are the parent formulation's to the bit, the router's
    and the input's to the order of a sum over ``D``, at sizes where the
    live rows pass the first chunk, where they fit it (1 of 16 held), and,
    with megablox in the interpreter, where the chunk is all the rows (the
    kernel reads no dead row and zeroes what it does not write)."""
    n, d, hidden = (256, 128, 128) if interpret else (2 * CHUNK, 32, 48)
    rng = np.random.default_rng(k)
    f32 = lambda *shape, scale=0.2: jnp.asarray(  # noqa: E731
        rng.normal(size=shape) * scale, jnp.float32)
    h0 = f32(n, d, scale=1.0)
    layers = {"router": f32(2, d, experts, scale=0.5), "gate": f32(2, held, d, hidden),
              "up": f32(2, held, d, hidden), "down": f32(2, held, hidden, d)}
    bias = None if router == "softmax_topk" else jnp.zeros(experts)

    def layer(h, p):
        out, counters = moe.dropless_moe_mlp(
            h, p["router"], bias, p["gate"], p["up"], p["down"], top_k=k, first_expert=1,
            router=router, interpret=interpret)
        return h + out.astype(jnp.float32), counters

    def loss(h, layers):
        h, counters = jax.lax.scan(jax.checkpoint(layer), h, layers)
        return jnp.sum(h ** 2), (h, counters)

    run = lambda: jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(h0, layers)  # noqa: E731
    (_, (got, counters)), got_grads = run()
    with monkeypatch.context() as patch:
        patch.setattr(moe, "rows_of_live_prefix", lambda src, idx, n_live: src[idx])
        patch.setattr(moe, "_combine", _whole_combine)
        (_, (want, _)), want_grads = run()
    np.testing.assert_array_equal(got, want)
    (d_h, d_layers), (want_h, want_layers) = got_grads, want_grads
    for name in ("gate", "up", "down"):
        np.testing.assert_array_equal(d_layers[name], want_layers[name])
    assert float(jnp.max(jnp.abs(d_layers["down"]))) > 0
    # what passes through the gates' cotangent (see the test above)
    scale = float(jnp.max(jnp.abs(want_h)))
    np.testing.assert_allclose(d_h, want_h, rtol=1e-4, atol=1e-4 * scale)
    np.testing.assert_allclose(d_layers["router"], want_layers["router"], rtol=1e-4,
                               atol=1e-4 * float(jnp.max(jnp.abs(want_layers["router"]))))
    # and the counter says from how many of the rows the un-permutes gathered:
    # the first chunk where the live rows fit it, else all of them
    m = n * k
    chunk = min(m, CHUNK * 32 // d)
    held_rows = np.asarray(counters["rows_held"])
    np.testing.assert_array_equal(
        counters["dispatch_rows_moved"], 2 * np.where(held_rows <= chunk, chunk, m))
    np.testing.assert_array_equal(counters["dispatch_rows_static"], [2.0 * m] * 2)
    if not interpret:
        fits = held == 1
        assert np.all((held_rows <= chunk) == fits) and 0 < held_rows.min() < 0.8 * m


def test_a_layer_that_holds_every_expert_keeps_its_whole_gathers(monkeypatch):
    """``e_held == E``: every row is live, which the shapes say, so the layer
    has no conditional in its program, forward or backward (a share of the
    experts has one an un-permute), and counts every row."""
    monkeypatch.setattr(moe, "DISPATCH_CHUNK_BYTES", 16 * 32 * 4)  # 16 of the 48 float32 rows
    p = _layer_weights(1)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(24, 32)), jnp.float32)
    program = lambda held: str(jax.make_jaxpr(jax.grad(  # noqa: E731
        lambda h: jnp.sum(_program_share(h, p, 0, held)[0] ** 2)))(h))
    assert program(4).count(" cond[") == 2 and " cond[" not in program(8)
    _, counters = _program_share(h, p, 0, 8)
    assert float(counters["dispatch_rows_moved"]) == float(
        counters["dispatch_rows_static"]) == 2 * 24 * 2


# ---------------------------------------------------------------------------
# the step, the trainer, a federated round
# ---------------------------------------------------------------------------


def test_fit_returns_the_routing_counters_and_no_aux_loss(monkeypatch):
    from photon_tpu.train.trainer import Trainer
    from photon_tpu.utils.profiling import (
        MOE_DISPATCH_ROWS_MOVED, MOE_DISPATCH_ROWS_STATIC, MOE_MAX_EXPERT_LOAD,
        MOE_ROWS_HELD, TRAINER_MOE_LOAD_SPAN)

    cfg = tiny_cfg()
    cfg.train.global_batch_size, cfg.train.device_microbatch_size = 4, 2  # two microbatches
    trainer = Trainer(cfg, init_seed=0)
    spans = recorded_spans(monkeypatch)
    out = trainer.fit([TOKENS] * 3, duration_steps=3)
    # 4 rows x 32 tokens x top-2 x 2 expert layers = 512 assignments, about
    # half of them to the 4 of 8 experts held here
    assert 128 <= out[MOE_ROWS_HELD] <= 384
    assert 1.0 <= out[MOE_MAX_EXPERT_LOAD] <= 4.0
    # a tiny layer's 128 static rows are one chunk: two un-permutes a layer
    # gather from it, in both layers and both microbatches
    assert out[MOE_DISPATCH_ROWS_MOVED] == out[MOE_DISPATCH_ROWS_STATIC] == 2 * 512
    (attrs,) = [a for name, a in spans if name == TRAINER_MOE_LOAD_SPAN]
    assert attrs == {"rows_held": out[MOE_ROWS_HELD],
                     "max_expert_load": out[MOE_MAX_EXPERT_LOAD],
                     "dispatch_rows_moved": 2 * 512, "dispatch_rows_static": 2 * 512}
    # the sigmoid router has no aux loss: the step's loss is the cross entropy
    model = MPTModel(cfg.model)
    ce = make_loss_fn(model, 16)(trainer.state.params, TOKENS)
    dims = dims_of(cfg)
    want = ref.ce_sum(trainer.state.params, TOKENS, dims) / (4 * 31)
    assert abs(float(ce) - float(want)) < 1e-5


def test_every_new_parameter_has_a_sharding_rule():
    import re

    from photon_tpu.parallel.sharding import _RULES

    names = LEAVES
    unruled = [n for n in names if not any(re.search(rx, n) for rx, _ in _RULES)]
    assert not unruled
    # and on a mesh the rules split what can be split: heads over `tensor`
    from jax.sharding import PartitionSpec as P

    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.config.schema import MeshConfig
    from photon_tpu.parallel.sharding import param_specs

    mesh = make_mesh(MeshConfig(fsdp=2, tensor=2), devices=jax.devices()[:4])
    specs = param_specs(ref.make_params(dims_of(tiny_cfg()), 0), mesh)
    block = specs["blocks"]["block"]
    assert block["q_b_proj"]["kernel"] == P("pipe", None, "tensor")
    assert block["kv_a_proj"]["kernel"] == P("pipe", "fsdp", None)
    assert block["shared_down_proj"]["kernel"] == P("pipe", "tensor", "fsdp")
    assert specs["dense_blocks"]["block"]["gate_proj"]["kernel"] == P("pipe", "fsdp", "tensor")


def test_one_federated_round_of_the_tiny_preset(tmp_path):
    """The heterogeneous tree (two stacks, expert weights with two leading
    axes, a float32 bias beside them) through transport, aggregation and the
    server checkpoint of ``photon_tpu.federated``."""
    from photon_tpu.checkpoint import FileStore, ServerCheckpointManager
    from photon_tpu.codec import params_to_ndarrays
    from photon_tpu.federated import build_app
    from photon_tpu.models import init_params

    cfg = tiny_cfg()
    cfg.run_uuid = "glmtiny"
    cfg.dataset.synthetic = True
    cfg.train.eval_batches = 2
    cfg.fl.n_total_clients = cfg.fl.n_clients_per_round = 2
    cfg.fl.n_rounds, cfg.fl.local_steps, cfg.fl.eval_interval_rounds = 1, 2, 1
    cfg.scheduler.t_warmup = 1  # so that two local steps move the weights
    cfg.photon.save_path = str(tmp_path / "save")
    cfg.photon.checkpoint = True
    cfg.validate()
    app = build_app(cfg, n_nodes=1)
    try:
        before = [a.copy() for a in app.strategy.current_parameters]
        history = app.run()
    finally:
        app.driver.shutdown()
    assert len(history.series("server/round_time")) == 1
    assert history.series("server/pseudo_grad_norm")[-1][1] > 0
    after = app.strategy.current_parameters
    meta, flat = params_to_ndarrays(init_params(cfg.model, seed=0))
    assert [a.shape for a in after] == [a.shape for a in flat]
    assert any(not np.allclose(a, b) for a, b in zip(after, before))
    # the round's checkpoint holds the whole tree
    mgr = ServerCheckpointManager(FileStore(tmp_path / "save" / "store"), cfg.run_uuid)
    assert mgr.list_rounds() == [0, 1]  # the initial weights, then the round


# ---------------------------------------------------------------------------
# what refuses the family, and what the schema refuses
# ---------------------------------------------------------------------------


def _refuse_serving():
    from photon_tpu.serve.engine import PagedEngine

    PagedEngine(tiny_cfg(), params={})


def _refuse_decode():
    from photon_tpu.models.decode import prefill

    prefill({}, jnp.zeros((1, 4), jnp.int32), jnp.array([4]), tiny_cfg().model)


def _refuse_hf_export():
    from photon_tpu.checkpoint.hf_export import mixtral_state_dict

    mixtral_state_dict({}, tiny_cfg().model)  # swiglu experts: the exporter it would reach


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

    with pytest.raises(ValueError, match="glm4_moe_lite"):
        model_config_from_hf({"model_type": "glm4_moe_lite"})


def _with(cfg, **paths):
    for dotted, value in paths.items():
        obj = cfg
        *parents, leaf = dotted.split("__")
        for name in parents:
            obj = getattr(obj, name)
        setattr(obj, leaf, value)
    return cfg


@pytest.mark.parametrize("change, message", [
    (dict(model__moe_experts_held=3), "does not divide"),
    (dict(model__moe_experts_held=16), "exceeds"),
    (dict(model__moe_first_expert=2), "not the start"),
    (dict(mesh__expert=2, mesh__surplus_devices="ignore"), "no expert exchange"),
    (dict(model__lora_rank=4, model__lora_targets=("out_proj",)), "LoRA"),
    (dict(photon__adapters__enabled=True), "LoRA|adapters"),
    (dict(photon__serve__prefix_cache=True), "prefix cache"),
    # since PR 44 the kernels take a v width of its own; ring attention does not
    (dict(model__v_head_dim=32, model__attn_impl="ring"), "one head width"),
    (dict(model__rope=False, model__learned_pos_emb=True), "rope=true"),
    (dict(model__moe_mlp_act="gelu"), "swiglu"),
    (dict(model__moe_bias_update_speed=-0.1), "moe_bias_update_speed"),
    (dict(model__moe_router="softmax", model__moe_experts_held=0,
          model__moe_shared_experts=0, model__moe_routed_scale=1.0,
          model__first_k_dense=0, model__moe_bias_update_speed=0.1),
     "belong to moe_router='sigmoid'"),
    (dict(mesh__pipe=3), "first_k_dense|pipe"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_schema_refuses_what_the_family_cannot_do_yet(change, message):
    cfg = tiny_cfg()
    with pytest.raises(ValueError, match=message):
        _with(cfg, **change).validate()


def test_d_head_is_the_heads_own_width_not_d_model_over_heads():
    cfg = load_preset("glm-4.7-flash-ep8")
    assert cfg.model.d_model % cfg.model.n_heads  # 2,048 over 20 heads
    assert cfg.model.d_head == 256 == cfg.model.v_head_dim
    assert cfg.model.experts_held == 8 and cfg.model.dropless_moe


def test_the_preset_is_what_the_benchmark_configuration_states():
    """``benchmark/program.build_config`` holds the preset to every size of
    ``benchmark/configs/glm-4.7-flash-ep8.json``; the cut's arithmetic
    (PERF.md section 4) is the tree's own count."""
    import json

    from benchmark.program import build_config
    from photon_tpu.models import init_params

    config = json.loads((ROOT / "benchmark/configs/glm-4.7-flash-ep8.json").read_text())
    traffic = json.loads((ROOT / "benchmark/traffic/ep8-share-4x4096.json").read_text())
    cfg = build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=2 ** 31 + 5)
    assert cfg.train.global_batch_size == cfg.train.device_microbatch_size == 4
    shapes = jax.eval_shape(lambda: init_params(cfg.model, seed=0))
    count = sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes))
    assert 590e6 < count < 592e6  # 591 M parameters, 9.46 GB at 16 bytes each
    # a preset edited under the benchmark is refused
    config["model"]["moe_top_k"] = 6
    with pytest.raises(ValueError, match="moe_top_k"):
        build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=1)


# ---------------------------------------------------------------------------
# whole-lane head widths: k and v straight out of their products (PR 45)
# ---------------------------------------------------------------------------

WHOLE_LANES = dict(qk_nope_head_dim=96, qk_rope_head_dim=32, v_head_dim=128)


@pytest.mark.parametrize("widths,in_place", [({}, False), (WHOLE_LANES, True)],
                         ids=["tiny-widths", "whole-lanes"])
def test_latent_kv_in_place_is_the_reference(widths, in_place, monkeypatch):
    """Where the flash launches read their operands in place (head widths of
    whole lanes, as the preset's 256 / 256), ``_latent_qkv`` makes k and v by
    products with parts of ``kv_b_proj`` and an identity block for the shared
    rotary key. The tree, the seeded init, the logits and every gradient are
    what the sliced and concatenated form gives, and the plain reference's."""
    from photon_tpu.models import init_params, mpt

    cfg = tiny_cfg(**widths)
    took = []
    real = mpt.MPTBlock._latent_kv_in_place
    monkeypatch.setattr(mpt.MPTBlock, "_latent_kv_in_place",
                        lambda self, *a: (took.append(1), real(self, *a))[1])
    dims = dims_of(cfg)
    params = ref.make_params(dims, 11)
    model = MPTModel(cfg.model)
    loss = make_loss_fn(model, 16)
    got = jax.value_and_grad(loss)(params, TOKENS)
    assert bool(took) == in_place
    n = TOKENS.shape[0] * (TOKENS.shape[1] - 1)
    want = jax.value_and_grad(lambda p: ref.ce_sum(p, TOKENS, dims) / n)(params)
    assert abs(float(got[0]) - float(want[0])) < 1e-5
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got[1]),
                            jax.tree.leaves(want[1])):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4, err_msg=str(path))
    # the same tree and the same seeded init as the path that calls nn.Dense
    mine = init_params(cfg.model, seed=0)
    monkeypatch.setattr(mpt, "flash_layout", lambda *a: "head_major")
    theirs = init_params(cfg.model, seed=0)
    assert leaf_names(mine) == leaf_names(theirs)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(a, b)
