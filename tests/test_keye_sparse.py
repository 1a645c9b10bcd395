"""Keye-VL-2.0-30B-A3B's language model on the training path, at a tiny size
with the published structure: grouped-query heads wider than ``d_model /
n_heads`` with per-head q / k norms, an indexer whose selection the attention
takes in place of the causal rule, the indexer's own alignment loss in the
objective, softmax top-k routing over experts of which a share is held.

The plain reference is ``benchmark/reference/keye_sparse_moe.py`` (float32,
``Precision.HIGHEST``, the selection by a full sort, whole probability rows);
on the CPU the program runs ``attn_impl: xla`` and the grouped products
through ``jax.lax.ragged_dot``, and the masked flash kernel in the Pallas
interpreter where a test says so.
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

from benchmark.reference import keye_sparse_moe as ref  # noqa: E402
from photon_tpu.config import load_preset  # noqa: E402
from photon_tpu.models import MPTModel  # noqa: E402
from photon_tpu.ops import dsa, index_pbar, index_select, moe  # noqa: E402
from photon_tpu.ops import masked_flash_attention as mfa  # noqa: E402
from photon_tpu.train.train_step import _make_loss_and_counters_fn, make_loss_fn  # noqa: E402
from photon_tpu.utils.profiling import (  # noqa: E402
    DSA_INDEX_LOSS,
    DSA_INDEX_LOSS_SCOPE,
    DSA_PICKED_PAIRS,
    DSA_TILES_VISITED,
)

PRESET = "keye-vl-2.0-30b-a3b-ep8"
TINY = dict(
    d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=32, max_seq_len=64,
    vocab_size=96, dsa_topk=16, dsa_index_heads=4, dsa_index_head_dim=16, dsa_chunk=16,
    mlp_hidden_size=32, moe_num_experts=8, moe_top_k=2, moe_experts_held=2,
    attn_impl="xla", compute_dtype="float32",
)
INDEXER = ("idx_q_proj", "idx_k_proj", "idx_k_norm", "idx_w_proj")


def tiny_cfg(**model):
    """The preset with every size shrunk and nothing of its structure changed:
    heads of 32 where ``d_model / n_heads`` is 16, 16 keys a query of 64, 8
    experts top-2 of which 2 are held."""
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


def by_name(tree) -> dict:
    return dict(zip(leaf_names(tree), jax.tree.leaves(tree)))


TOKENS = np.random.default_rng(3).integers(0, 96, size=(2, 64)).astype(np.int32)


def reference_objective(params, dims, tokens=TOKENS):
    total, _ = ref.objective_sum(params, jnp.asarray(tokens), dims)
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights in the program's layout, and objective + gradients of
    one batch from the program (float32 compute) and from the reference."""
    cfg = tiny_cfg()
    dims = dims_of(cfg)
    params = ref.make_params(dims, 7)
    got = jax.value_and_grad(make_loss_fn(MPTModel(cfg.model), 16))(params, TOKENS)
    want = jax.value_and_grad(lambda p: reference_objective(p, dims))(params)
    return cfg, dims, params, got, want


def test_init_gives_the_reference_tree():
    from photon_tpu.models import init_params

    cfg = tiny_cfg()
    mine = init_params(cfg.model, seed=0)
    theirs = ref.make_params(dims_of(cfg), 0)
    assert leaf_names(mine) == leaf_names(theirs)
    assert jax.tree.map(jnp.shape, mine) == jax.tree.map(jnp.shape, theirs)


def test_forward_logits_match_reference(seeded):
    cfg, dims, params, _, _ = seeded
    logits = MPTModel(cfg.model).apply({"params": params}, TOKENS)
    np.testing.assert_allclose(logits, ref.forward(params, TOKENS, dims), atol=2e-5)


def test_objective_matches_reference_and_holds_the_index_losses(seeded):
    cfg, dims, params, (loss, _), (want, _) = seeded
    assert abs(float(loss) - float(want)) < 1e-5
    # cross-entropy plus the layers' index losses, which are not nothing
    total, counters = _make_loss_and_counters_fn(MPTModel(cfg.model), 16)(params, TOKENS)
    index = float(counters["dsa_index_loss"])  # by sown key: models/step.COUNTERS
    assert 0.01 < index < 2.0
    _, (picked, _) = ref.objective_sum(params, jnp.asarray(TOKENS), dims)
    assert float(counters["dsa_picked_pairs"]) == float(picked)
    # and the cross-entropy it is added to is the reference's
    want_ce = sum(float(ref.row_objective(params, jnp.asarray(row), dims)[0])
                  for row in TOKENS) / (2 * 63)
    assert abs(float(total) - index - want_ce) < 1e-5


LEAVES = leaf_names(ref.make_params(ref.dims_of({
    **dataclasses.asdict(load_preset(PRESET).model), **TINY}), 0))


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(seeded, leaf):
    *_, (_, got), (_, want) = seeded
    got, want = by_name(got)[leaf], by_name(want)[leaf]
    assert np.any(want), "a leaf without a gradient tests nothing"
    # float32 on both sides; the largest entries of a leaf are 1e-4 .. 6e-2
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-4)


@pytest.fixture(scope="module")
def gradients_by_loss(seeded):
    """Every leaf's gradient from the cross-entropy alone and from the
    layers' index losses alone."""
    cfg, _, params, _, _ = seeded
    model = MPTModel(cfg.model)
    objective = _make_loss_and_counters_fn(model, 16)

    def ce_only(p):
        total, counters = objective(p, TOKENS)
        return total - counters["dsa_index_loss"]

    index_only = lambda p: objective(p, TOKENS)[1]["dsa_index_loss"]  # noqa: E731
    return by_name(jax.grad(ce_only)(params)), by_name(jax.grad(index_only)(params))


@pytest.mark.parametrize("leaf", LEAVES)
def test_the_two_losses_have_disjoint_gradients(gradients_by_loss, leaf):
    """The indexer's leaves are moved by the index loss alone, every other
    leaf by the cross-entropy alone."""
    from_ce, from_index = (g[leaf] for g in gradients_by_loss)
    if any(f"/{name}/" in leaf for name in INDEXER):
        assert not np.any(from_ce) and np.any(from_index)
    else:
        assert np.any(from_ce) and not np.any(from_index)


def test_bfloat16_compute_stays_near_the_reference(seeded):
    _, dims, params, _, (want, _) = seeded
    cfg = tiny_cfg(compute_dtype="bfloat16")
    loss = make_loss_fn(MPTModel(cfg.model), 16)(params, TOKENS)
    assert abs(float(loss) - float(want)) < 3e-2


@pytest.mark.parametrize("microbatches", [1, 2])
def test_three_steps_follow_the_reference(microbatches):
    """The train step (objective, clip, ADOPT) against the reference's steps
    on the same rows: losses, and the weights after three steps."""
    from photon_tpu.optim import build_optimizer
    from photon_tpu.train import init_train_state, make_train_step
    from benchmark.program import optimizer_settings

    cfg = tiny_cfg()
    cfg.scheduler.t_warmup = 0
    dims = dims_of(cfg)
    params = ref.make_params(dims, 11)
    model = MPTModel(cfg.model)
    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    state = init_train_state(model, tx, params)
    step = jax.jit(make_train_step(model, tx, n_microbatches=microbatches,
                                   loss_chunk_tokens=16))
    opt = optimizer_settings(cfg)
    grad = ref.Grad(dims, rows=1)
    p, s = params, ref.adopt_init(params)
    rng = np.random.default_rng(5)
    for _ in range(3):
        batch = rng.integers(0, 96, size=(2, 64)).astype(np.int32)
        state, metrics = step(state, batch)
        loss, g = grad(p, batch)
        p, s = ref.adopt_step(p, s, g, opt)
        assert abs(float(metrics["loss"]) - loss) < 2e-5
    moved = ref.leaf_norms(jax.tree.map(jnp.subtract, state.params, params))
    want = ref.leaf_norms(jax.tree.map(jnp.subtract, p, params))
    assert ref.worst_leaf_gap(moved, want) < 2e-3


# ---------------------------------------------------------------------------
# the selection
# ---------------------------------------------------------------------------


def _indexer_inputs(seed: int, b=2, s=64, heads=4, dim=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, s, heads, dim)),
            jax.random.normal(ks[1], (b, s, dim)),
            jax.random.normal(ks[2], (b, s, heads)))


def _reference_mask(q_idx, k_idx, w, topk):
    mm = ref.MATMULS["float32"]
    t = jnp.arange(q_idx.shape[1], dtype=jnp.int32)
    return jnp.stack([ref.select(ref.index_scores(q, k, ww, mm), t, topk)
                      for q, k, ww in zip(q_idx, k_idx, w)])


def _selected(q_idx, k_idx, w, topk, chunk, launch):
    """``select_keys``' mask; with ``launch`` through ``ops/index_select.py``
    under the interpreter, which has to leave the ``jax.numpy`` path's mask
    bit for bit."""
    s = q_idx.shape[1]
    assert dsa.selects_in_vmem("pallas", True, s, chunk, topk) is launch
    with jax.default_matmul_precision("highest"):
        mask = dsa.select_keys(q_idx, k_idx, w, topk=topk, chunk=chunk)
        if launch:
            select = lambda *a: dsa.select_keys(  # noqa: E731
                *a, topk=topk, chunk=chunk, impl="pallas", interpret=True)
            # one launch a band's chunk loop, and more than one row block in it
            assert str(jax.make_jaxpr(select)(q_idx, k_idx, w)).count("pallas_call") == 4
            assert index_select.row_block(min(chunk, s), s) < min(chunk, s)
            np.testing.assert_array_equal(select(q_idx, k_idx, w), mask)
    return mask


# rows of 64 are under a lane's width and stay ``jax.numpy``; 512 in chunks
# of 128 are four bands of whole column tiles, two row blocks a launch
SELECT_SHAPES = [pytest.param(64, 16, False, id="s64-c16"), pytest.param(64, 64, False, id="s64-c64"),
                 pytest.param(512, 128, True, id="s512-c128-launch")]


@pytest.mark.parametrize("s, chunk, launch", SELECT_SHAPES)
@pytest.mark.parametrize("topk", [1, 16, 33])
def test_the_selection_is_the_references_set_on_every_row(topk, s, chunk, launch):
    """Exactly: rows with fewer earlier keys than ``topk`` keep them all,
    every later row keeps its ``topk`` highest (more only where scores tie
    at the threshold: a query whose every product is cut by the ``relu``
    scores exactly 0 against several keys)."""
    q_idx, k_idx, w = _indexer_inputs(0, s=s)
    mask = _selected(q_idx, k_idx, w, topk, chunk, launch)
    want = _reference_mask(q_idx, k_idx, w, topk)
    assert mask.dtype == jnp.int8 and bool(jnp.all((mask != 0) == want))
    per_row = np.asarray(jnp.sum(mask, axis=-1))
    least = np.minimum(np.arange(s) + 1, topk)[None, :]
    assert (per_row >= least).all()
    if topk > 1:  # the 16th place is rarely an exact zero; the first often is
        assert (per_row == least).mean() > 0.95


@pytest.mark.parametrize("s, chunk, launch", [SELECT_SHAPES[0], SELECT_SHAPES[2]])
def test_ties_at_the_threshold_are_all_kept(s, chunk, launch):
    """Keys with one and the same score straddle the threshold: the rule
    keeps every one of them, in the program as in the reference."""
    q_idx, k_idx, w = _indexer_inputs(1, s=s)
    # row 0: all keys alike, so every score of a query ties; row 1: keys
    # 8..39 alike, a tie that the 16th place falls into for most queries
    k_idx = k_idx.at[0].set(k_idx[0, 0]).at[1, 8:40].set(k_idx[1, 8])
    mask = _selected(q_idx, k_idx, w, 16, chunk, launch)
    want = _reference_mask(q_idx, k_idx, w, 16)
    assert bool(jnp.all((mask != 0) == want))
    assert bool(jnp.all((mask[0] != 0) == jnp.tril(jnp.ones((s, s), bool))))
    assert int(jnp.max(jnp.sum(mask[1], axis=-1))) > 16


def _planted_rows(k: int, n: int = 64) -> np.ndarray:
    rng = np.random.default_rng(k)
    x = rng.normal(size=(16, n)).astype(np.float32)
    x[0, :10] = -np.inf  # masked entries count as smallest
    x[1] = np.round(x[1])  # many ties
    x[2, 3] = 0.0
    x[2, 4] = -0.0
    x[3] *= 1e-30  # tiny magnitudes, both signs
    x[4] = 1.5  # one value, repeated
    x[5, 3:] = -np.inf  # fewer finite entries than most ``k``
    x[6] *= 1e-42  # subnormals of both signs: an integer compare does not flush them
    x[7, ::2] = np.float32(1e-45) * rng.integers(-3, 4, size=n // 2)
    return x


@pytest.mark.parametrize("k", [1, 5, 64])
def test_kth_largest_is_exact(k):
    x = _planted_rows(k)
    got = dsa.kth_largest(jnp.asarray(x), k)
    np.testing.assert_array_equal(np.asarray(got), -np.sort(-x, axis=-1)[:, k - 1])


@pytest.mark.parametrize("bits", [1, 2])
@pytest.mark.parametrize("k", [1, 5, 384])
def test_the_select_launch_is_kth_largest_to_the_bit(k, bits):
    """``ops/index_select.py`` under the interpreter, three column tiles and
    two row blocks, against the ``jax.numpy`` search (the same 32 bits: the
    zero's sign too) and against a sort."""
    x = _planted_rows(k, n=384)
    got = np.asarray(index_select.kth_largest(jnp.asarray(x), k, interpret=True, rows=8,
                                              bits=bits))
    want = np.asarray(dsa.kth_largest(jnp.asarray(x), k))
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))
    np.testing.assert_array_equal(got, -np.sort(-x, axis=-1)[:, k - 1])


def test_the_select_launch_takes_the_shapes_it_can_hold():
    """Whole lanes of keys, whole sublanes of queries, a tile within the
    VMEM budget; a chunk loop with a band outside them stays ``jax.numpy``."""
    assert index_select.row_block(512, 16384) == index_select.ROW_BLOCK
    assert index_select.row_block(512, 4096) == index_select.ROW_BLOCK
    assert index_select.row_block(24, 256) == 24 and index_select.row_block(40, 128) == 40
    assert index_select.row_block(16, 64) == 0 and index_select.row_block(12, 128) == 0
    rows = index_select.row_block(512, 2 ** 18)  # a narrower block where the keys are many
    assert 0 < rows < index_select.ROW_BLOCK and 512 % rows == 0
    assert index_select.row_block(512, 2 ** 22) == 0
    assert dsa.selects_in_vmem("pallas", True, 16384, 512, 2048)
    assert not dsa.selects_in_vmem("pallas", False, 16384, 512, 2048)  # the CPU backend
    assert not dsa.selects_in_vmem("xla", True, 16384, 512, 2048)
    assert not dsa.selects_in_vmem("pallas", True, 64, 16, 16)  # the tiny presets' rows
    assert not dsa.selects_in_vmem("pallas", True, 512, 128, 129)  # a band under ``topk`` keys
    with pytest.raises(ValueError, match="bad shapes"):
        index_select.kth_largest(jnp.zeros((16, 64)), 1, interpret=True)


def test_with_every_key_picked_the_sparse_branch_is_the_dense_one(seeded):
    """``dsa_topk >= S``: the mask is the causal rule, and the block's output
    is the dense grouped-query branch's on the same weights."""
    _, _, params, _, _ = seeded
    sparse = tiny_cfg(dsa_topk=64)
    logits = MPTModel(sparse.model).apply({"params": params}, TOKENS)
    dense = tiny_cfg()
    dense.model.dsa_topk = dense.model.dsa_index_heads = dense.model.dsa_index_head_dim = 0
    dense.validate()
    block = {k: v for k, v in params["blocks"]["block"].items() if not k.startswith("idx_")}
    want = MPTModel(dense.model).apply(
        {"params": {**params, "blocks": {"block": block}}}, TOKENS)
    np.testing.assert_allclose(logits, want, atol=2e-5)


# ---------------------------------------------------------------------------
# the masked kernel
# ---------------------------------------------------------------------------


def _attention_inputs(seed: int, s=256, h=4, g=2, d=32, b=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, g, d))
    v = jax.random.normal(ks[2], (b, s, g, d))
    picked = jax.random.uniform(ks[3], (b, s, s)) < 0.2
    mask = (picked | jnp.eye(s, dtype=bool)) & jnp.tril(jnp.ones((s, s), bool))
    return q, k, v, mask.astype(jnp.int8)


TILES = ((128, 128),) * 3


def test_the_masked_kernel_matches_the_masked_xla_path():
    """Forward, log-sum-exp and both backward launches, in the interpreter,
    with several tiles a launch and grouped heads."""
    q, k, v, mask = _attention_inputs(0)
    want, want_lse = mfa.masked_xla_attention(q, k, v, mask)
    got, lse = mfa.masked_flash_attention(q, k, v, mask, interpret=True, tiles=TILES)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5)
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)[0]))  # noqa: E731
    g_want = jax.grad(loss(lambda q, k, v: mfa.masked_xla_attention(q, k, v, mask)),
                      (0, 1, 2))(q, k, v)
    g_got = jax.grad(loss(lambda q, k, v: mfa.masked_flash_attention(
        q, k, v, mask, interpret=True, tiles=TILES)), (0, 1, 2))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_tiles_without_a_picked_pair_are_not_fetched():
    """A mask that empties whole tiles: the tables name a live block for
    every dead step (a repeated index: no copy), the counts say how many
    tiles were visited, and the kernel's results do not change."""
    q, k, v, mask = _attention_inputs(1)
    # queries 128.. see nothing of keys 0..127 (a dead tile under the
    # diagonal), and key block 1 is seen by its own queries alone
    mask = mask.at[:, 128:, :128].set(0)
    counts = mfa.tile_counts(mask, 128, 128)
    assert int(jnp.sum(counts)) == int(jnp.sum(mask))
    live = counts > 0
    assert live.shape == (2, 2, 2) and int(jnp.sum(live)) == 4  # of 6 causal
    # launches of different tiles take their tables from one pass over the
    # mask at the tiles' common divisor
    mixed = ((256, 128), (128, 256), (128, 128))
    assert mfa.base_tile(mixed) == (128, 128)
    wide, tall, same = mfa.live_tables(counts, mixed)
    assert wide.tolist() == [[[True, True]]] * 2 and tall.tolist() == [[[True], [True]]] * 2
    assert jnp.array_equal(same, live)
    flat_live, fetch = mfa.tile_tables(live)
    assert flat_live.tolist() == [1, 0, 0, 1] * 2
    # row 0: its dead step repeats block 0; row 1: its dead first step names
    # the live block that follows
    assert fetch.tolist() == [0, 0, 1, 1] * 2
    # the dk/dv launch sweeps the queries of a key block
    assert mfa.tile_tables(live.swapaxes(1, 2))[1].tolist() == [0, 0, 1, 1] * 2
    want, _ = mfa.masked_xla_attention(q, k, v, mask)
    got, _ = mfa.masked_flash_attention(q, k, v, mask, interpret=True, tiles=TILES)
    np.testing.assert_allclose(got, want, atol=2e-5)
    g_want = jax.grad(lambda k: jnp.sum(mfa.masked_xla_attention(q, k, v, mask)[0] ** 2))(k)
    # the caller's tables, as the block hands them over, and mixed tiles
    for tiles in (TILES, mixed):
        tables = mfa.live_tables(counts, tiles)
        g_got = jax.grad(lambda k: jnp.sum(mfa.masked_flash_attention(
            q, k, v, mask, interpret=True, tiles=tiles, live=tables)[0] ** 2))(k)
        np.testing.assert_allclose(g_got, g_want, atol=5e-5)


def test_a_query_without_a_key_gives_zeros_and_no_nan():
    q, k, v, mask = _attention_inputs(2, s=128)
    mask = mask.at[:, 5].set(0)
    got, lse = mfa.masked_flash_attention(q, k, v, mask, interpret=True)
    assert not np.any(np.asarray(got[:, 5])) and np.all(np.isfinite(np.asarray(got)))
    grads = jax.grad(lambda q, k, v: jnp.sum(mfa.masked_flash_attention(
        q, k, v, mask, interpret=True)[0]), (0, 1, 2))(q, k, v)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in grads)


def test_the_model_through_the_kernel_in_the_interpreter(seeded, monkeypatch):
    """The whole objective with the masked kernel and the index loss's
    ``pbar`` launch where the CPU run has the XLA paths: the same loss and the
    same gradients."""
    _, _, params, (want, g_want), _ = seeded
    launches = []

    def counted(*args, **kwargs):
        launches.append(kwargs["n_keys"])
        return index_pbar.head_mean_probabilities(*args, **kwargs)

    monkeypatch.setattr(dsa, "head_mean_probabilities", counted)
    cfg = tiny_cfg(attn_impl="pallas", attn_interpret=True)
    loss, grads = jax.value_and_grad(make_loss_fn(MPTModel(cfg.model), 16))(params, TOKENS)
    # every trace of the block walks the four bands' chunk loops
    assert launches and sorted(set(launches)) == [16, 32, 48, 64]
    assert abs(float(loss) - float(want)) < 1e-5
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-4)


# ---------------------------------------------------------------------------
# the step as the cell configures it (ROADMAP S12, step 0)
# ---------------------------------------------------------------------------

#: ``keyevl2-train-16k``'s step at a tiny size: ``remat`` on, the kernels'
#: path (under the interpreter), bfloat16 compute; rows of 512 in chunks of
#: 128 are four bands, and with every launch's tile cut to 128 a chunk has
#: more than one tile of each: the masked kernel 4 x 4, ``pbar`` 1 to 4 key
#: tiles, the selection's search 1 to 4 column tiles in two row blocks
AS_THE_CELL = dict(max_seq_len=512, dsa_chunk=128, dsa_topk=32, attn_impl="pallas",
                   attn_interpret=True, remat=True, compute_dtype="bfloat16")
#: the cell's own limit on the first gradient's worst leaf norm
#: (``benchmark/traffic/ep8-share-1x16384.json``); this step reads 0.0114
CELL_GRAD_NORM_GAP = 0.03


class _Leaky:
    """``real`` with some attributes replaced (a module seen through a
    planted fault)."""

    def __init__(self, real, **planted):
        self._real, self._planted = real, planted

    def __getattr__(self, name):
        return self._planted[name] if name in self._planted else getattr(self._real, name)


def _assert_the_cells_step_is_the_references(monkeypatch):
    """The objective's gradient and every mask the step makes (the forward's
    and the one ``remat`` makes again, of each layer) against the plain
    reference: the gradient's leaves by the cell's own measure, the masks on
    every entry, from the indexer's own bfloat16 inputs."""
    monkeypatch.setattr(mfa, "TILE_CAPS", dict.fromkeys(mfa.TILE_CAPS, (128, 128)))
    monkeypatch.setattr(index_pbar, "BLOCK_K_CAP", 128)
    cfg = tiny_cfg(**AS_THE_CELL)
    model, dims = cfg.model, dims_of(cfg)
    assert dsa.selects_in_vmem(model.attn_impl, model.attn_interpret, 512, 128, 32)
    params = ref.make_params(dims, 7)
    tokens = np.random.default_rng(3).integers(0, 96, size=(1, 512)).astype(np.int32)
    made, select_keys = [], dsa.select_keys

    def recorded(q_idx, k_idx, w, **kwargs):
        mask = select_keys(q_idx, k_idx, w, **kwargs)
        jax.debug.callback(lambda *a: made.append([np.asarray(x) for x in a]),
                           q_idx, k_idx, w, mask)
        return mask

    monkeypatch.setattr(dsa, "select_keys", recorded)
    loss, grads = jax.jit(jax.value_and_grad(make_loss_fn(MPTModel(model), 16)))(params, tokens)
    want_loss, want = jax.value_and_grad(
        lambda p: reference_objective(p, dims, tokens))(params)
    assert abs(float(loss) - float(want_loss)) < 3e-3
    assert len(made) == 2 * model.n_layers  # a layer's forward, and again under ``remat``
    for q_idx, k_idx, w, mask in made:
        assert q_idx.dtype == jnp.bfloat16
        picked = _reference_mask(*(jnp.asarray(a, jnp.float32) for a in (q_idx, k_idx, w)), 32)
        assert np.array_equal(mask != 0, np.asarray(picked)), "a mask is not the reference's"
    got, want = by_name(grads), by_name(want)
    for name in INDEXER[:2] + INDEXER[3:]:  # moved by the index loss alone
        assert np.any(np.asarray(got[f"blocks/block/{name}/kernel"]))
    gap = ref.worst_leaf_gap(ref.leaf_norms(grads), ref.leaf_norms(want))
    assert gap < CELL_GRAD_NORM_GAP, f"the gradient's worst leaf norm is off by {gap:.4f}"
    for name in want:  # entry by entry: bfloat16, and a selection from bfloat16 scores
        a, b = np.asarray(got[name], np.float64), np.asarray(want[name], np.float64)
        assert np.linalg.norm(a - b) < 0.2 * np.linalg.norm(b), name


def test_the_step_as_the_cell_configures_it_is_the_references(monkeypatch):
    _assert_the_cells_step_is_the_references(monkeypatch)


@pytest.mark.parametrize("fault", ["a_mask_from_rounded_scores", "a_gradient_past_its_stop"])
def test_a_fault_a_remat_change_can_make_fails_the_comparison(monkeypatch, fault):
    """The two planted faults: the masks made from index scores rounded
    another way than the reference's rule reads them (here through bfloat16:
    the gradient's norms still pass), and the indexer's gradient on the wrong
    side of its ``stop_gradient`` (the index loss moves the block's input)."""
    from photon_tpu.models import mpt

    if fault == "a_mask_from_rounded_scores":
        index_scores = dsa.index_scores
        monkeypatch.setattr(dsa, "index_scores", lambda *a: index_scores(*a).astype(
            jnp.bfloat16).astype(jnp.float32))
        match = "a mask is not the reference's"
    else:
        monkeypatch.setattr(mpt, "jax", _Leaky(jax, lax=_Leaky(
            jax.lax, stop_gradient=lambda x: x)))
        match = "the gradient's worst leaf norm"
    with pytest.raises(AssertionError, match=match):
        _assert_the_cells_step_is_the_references(monkeypatch)


# ---------------------------------------------------------------------------
# the index loss, and the launch that makes its pbar
# ---------------------------------------------------------------------------


def _pbar_case(case: str, h: int, g: int):
    """One chunk's operands as ``dsa._row_index_loss`` lays them out, from a
    row's attention inputs and the masked attention's own log-sum-exp:
    ``(q, k, lse, mask of the chunk, chunk index, n_keys, block_k)``."""
    s, chunk, d = 256, 32, 128
    q, k, _, mask = _attention_inputs(5, s=s, h=h, g=g, d=d, b=1)
    c, n_keys, block_k = 7, s, 128  # the row's last chunk: two live tiles
    if case == "one_tile":
        n_keys, block_k = 128, None
        c = 3
    elif case == "dead_tiles":
        # keys 128.. are past chunk 3's last query, and none of its queries
        # picked a key of 0..63 either
        c = 3
        mask = mask.at[:, c * chunk:(c + 1) * chunk, :64].set(0)
        block_k = 64
    elif case == "query_without_a_key":
        mask = mask.at[:, c * chunk + 5].set(0)
    elif case == "first_band":  # t < topk: every causal key is picked
        c, n_keys = 1, 128
        mask = jnp.tril(jnp.ones((s, s), jnp.int8))[None]
    else:
        assert case == "several_tiles"
    _, lse = mfa.masked_xla_attention(q, k, k, mask)
    qg, kg, lse_g = dsa._grouped(q[0], k[0], lse[0], chunk)
    return qg[c], kg, lse_g[c], mask[0, c * chunk:(c + 1) * chunk], c, n_keys, block_k


@pytest.mark.parametrize("h, g", [(4, 2), (8, 1)], ids=["h4g2", "h8g1"])
@pytest.mark.parametrize("case", ["one_tile", "several_tiles", "dead_tiles",
                                  "query_without_a_key", "first_band"])
def test_the_pbar_launch_is_the_jax_numpy_line(case, h, g):
    """``ops/index_pbar`` in the interpreter against ``dsa._pbar_xla``, the
    line it replaces, and against the heads' softmax over the picked keys."""
    with jax.default_matmul_precision("highest"):
        qc, kg, lc, mc, c, n_keys, block_k = _pbar_case(case, h, g)
        scale = qc.shape[-1] ** -0.5
        got = index_pbar.head_mean_probabilities(
            qc, kg, lc, mc, jnp.int32(c), scale=scale, n_keys=n_keys, interpret=True,
            block_k=block_k)
        picked = mc[:, :n_keys] != 0
        want = dsa._pbar_xla(qc, kg, lc, picked, scale)
        scores = jnp.einsum("gmd,gsd->gms", qc, kg) * scale
        soft = jax.nn.softmax(jnp.where(jnp.tile(mc != 0, (h // g, 1))[None], scores, -1e30), -1)
    assert got.shape == (32, n_keys) and got.dtype == jnp.float32
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(got, want, atol=1e-6)
    rows = np.asarray(jnp.any(mc != 0, axis=-1))
    plain = jnp.mean(soft.reshape(h, 32, -1), axis=0)[:, :n_keys]
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(plain)[rows], atol=1e-6)
    assert not np.any(np.asarray(got)[~np.asarray(picked)])
    if case == "query_without_a_key":
        assert not rows[5] and not np.any(np.asarray(got[5]))
    elif case == "first_band":
        assert bool(jnp.all(picked == (jnp.arange(n_keys)[None] <= c * 32 + jnp.arange(32)[:, None])))
        np.testing.assert_allclose(jnp.sum(got, axis=-1), 1.0, atol=1e-5)
    elif case == "dead_tiles":
        assert not np.any(np.asarray(got[:, :64])) and np.any(np.asarray(got[:, 64:128]))


def test_the_pbar_launch_counts_its_tiles():
    """The tiles a row's launches compute and skip, as the ``trainer/dsa``
    span tells them: at the cell's shapes 1,024-key tiles, of which a chunk
    computes those up to its own last query."""
    assert index_pbar.key_block(16384) == index_pbar.key_block(12288) == 1024
    computed, skipped = dsa.index_loss_tiles(16384, 512)
    assert computed == 2 * sum(range(1, 17)) == 272
    assert computed + skipped == 8 * (4 + 8 + 12 + 16)
    assert dsa.index_loss_tiles(64, 16) == (4, 0)  # a tiny row: one tile a chunk
    assert [int(index_pbar.live_key_tiles(c, 512, 1024)) for c in (0, 1, 2, 31)] == [1, 1, 2, 16]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_index_loss_takes_its_gradient_in_the_forward_loop(impl):
    """``ops/dsa.index_loss`` against plain autodiff of the same formula, on
    the ``jax.numpy`` path and through the launch in the interpreter."""
    b, s, h, g, d = 2, 64, 4, 2, 16
    with jax.default_matmul_precision("highest"):
        q_idx, k_idx, w = _indexer_inputs(4)
        q, k, _, _ = _attention_inputs(4, s=s, h=h, g=g, d=d)
        mask = dsa.select_keys(q_idx, k_idx, w, topk=16, chunk=16)
        _, lse = mfa.masked_xla_attention(q, k, k, mask)

        def plain(q_idx, k_idx, w):
            mm = ref.MATMULS["float32"]
            picked = mask != 0
            scores = jnp.stack([ref.index_scores(a, c, e, mm)
                                for a, c, e in zip(q_idx, k_idx, w)])
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, h // g, axis=2)) / d ** 0.5
            pbar = jnp.mean(jax.nn.softmax(
                jnp.where(picked[:, None], logits, -jnp.inf), axis=-1), axis=1)
            log_soft = jax.nn.log_softmax(jnp.where(picked, scores, -jnp.inf), axis=-1)
            kl = jnp.where(picked, pbar * (jnp.log(jnp.where(picked, pbar, 1.0))
                                           - jnp.where(picked, log_soft, 0.0)), 0.0)
            return jnp.sum(kl) / (b * s)

        mine = lambda *a: dsa.index_loss(  # noqa: E731
            *a, q, k, lse, mask, chunk=16, impl=impl, interpret=impl == "pallas")
        want, g_want = jax.value_and_grad(plain, (0, 1, 2))(q_idx, k_idx, w)
        got, g_got = jax.value_and_grad(mine, (0, 1, 2))(q_idx, k_idx, w)
    assert abs(float(got) - float(want)) < 1e-5
    for a, c in zip(g_got, g_want):
        np.testing.assert_allclose(a, c, atol=1e-6)


def test_the_index_loss_has_no_impl_but_the_attentions():
    q_idx, k_idx, w = _indexer_inputs(4)
    q, k, _, mask = _attention_inputs(4, s=64, d=16)
    with pytest.raises(ValueError, match="no impl"):
        dsa.index_loss(q_idx, k_idx, w, q, k, jnp.zeros((2, 4, 64)), mask, chunk=16,
                       impl="ring")


def _equations(jaxpr, inside_launch=False):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold, each
    with whether it is inside a ``pallas_call``."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_launch
        inner = inside_launch or eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, inner)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_the_heads_scores_never_leave_the_launch(impl):
    """What the launch is for: in the traced program of the kernel path (the
    loss and its gradient, every chunk's step) no float32 value holds a
    chunk's scores for all heads outside the ``pallas_call``; the ``xla``
    path makes two of them (the product, the exponential) a band and pass."""
    s, h, g, d, chunk = 320, 8, 2, 32, 80  # no two of the sizes below alike
    q_idx, k_idx, w = _indexer_inputs(6, b=1, s=s)
    q, k, _, mask = _attention_inputs(6, s=s, h=h, g=g, d=d, b=1)
    lse = jnp.zeros((1, h, s), jnp.float32)
    loss = lambda *a: dsa.index_loss(  # noqa: E731
        *a, q, k, lse, mask, chunk=chunk, impl=impl, interpret=impl == "pallas")
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2)))(q_idx, k_idx, w).jaxpr
    # [H, chunk, n_keys], or the groups' [G, H/G * chunk, n_keys], for a band's n_keys
    scores = {(a, b, n) for a, b in ((h, chunk), (g, h // g * chunk))
              for n in range(chunk, s + 1, chunk)}
    whole, launches = [], 0
    for eqn, inside in _equations(jaxpr):
        launches += eqn.primitive.name == "pallas_call"
        whole += [(eqn.primitive.name, v.aval.shape) for v in eqn.outvars
                  if not inside and tuple(v.aval.shape[-3:]) in scores]
    if impl == "pallas":
        assert launches == 4 and not whole, whole  # a launch a band
    else:
        assert launches == 0 and len(whole) >= 8, whole


def test_the_pbar_launch_is_counted_under_the_index_loss_alone():
    """Every operation of the launch (here the interpreter's, on the chip one
    custom call: ``tests/test_tpu_compile.py``) carries ``dsa/index_loss`` and
    its own scope, and none the names by which ``flash_fwd_ms_train``,
    ``flash_bwd_ms_train`` and ``sparse_attention_roofline`` find the
    attention's launches."""
    import re

    from benchmark.layer_metrics.sparse_attention_roofline import KERNELS

    cfg = tiny_cfg(attn_impl="pallas", attn_interpret=True, n_layers=1)
    params = ref.make_params(dims_of(cfg), 7)
    compiled = jax.jit(jax.grad(make_loss_fn(MPTModel(cfg.model), 16))).lower(
        params, TOKENS).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', compiled))
    launch = {n for n in names if index_pbar.INDEX_PBAR_SCOPE in n}
    assert len(launch) > 10
    # ``dsa_index_loss_ms_train``'s pattern
    assert all(re.search(rf"\b{DSA_INDEX_LOSS_SCOPE}\b", n) for n in launch)
    flash = re.compile(KERNELS.replace(".*pallas_call", ""))
    assert not any(flash.search(n) for n in launch)
    assert any(flash.search(n) for n in names)  # the attention's own are there


# ---------------------------------------------------------------------------
# the softmax top-k router and the share
# ---------------------------------------------------------------------------


def _layer_weights(seed: int, e=16, d=32, f=24):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n = lambda key, shape: jax.random.normal(key, shape, jnp.float32) * 0.2  # noqa: E731
    return {"router": n(ks[0], (d, e)), "moe_gate": n(ks[1], (e, d, f)),
            "moe_up": n(ks[2], (e, d, f)), "moe_down": n(ks[3], (e, f, d))}


@pytest.mark.parametrize("held", [16, 2])
def test_the_shares_routed_parts_sum_to_the_uncut_layer(held):
    """Softmax top-8 of 16 experts: the eight shares of two experts each (or
    the one share of all) add up to the layer with every expert held, which
    the reference computes as a masked loop."""
    p = _layer_weights(1)
    h = jnp.asarray(np.random.default_rng(2).normal(size=(48, 32)), jnp.float32)
    dims = dict(top_k=8, first_expert=0, experts_held=16)
    mm = ref.MATMULS["float32"]
    want, rows = ref.routed_experts(h, p, dims, mm)
    assert float(rows) == 48 * 8
    parts, seen = [], 0.0
    for first in range(0, 16, held):
        share = slice(first, first + held)
        out, counters = moe.dropless_moe_mlp(
            h, p["router"], None, p["moe_gate"][share], p["moe_up"][share],
            p["moe_down"][share], top_k=8, first_expert=first, router="softmax_topk",
            compute_dtype=jnp.float32)
        parts.append(out)
        seen += float(counters["rows_held"])
        held_here = {**p, **{n: p[n][share] for n in ("moe_gate", "moe_up", "moe_down")}}
        one, _ = ref.routed_experts(h, held_here, dims, mm, first_expert=first, experts=held)
        np.testing.assert_allclose(out, one, atol=1e-5)
    assert seen == 48 * 8  # every assignment is some share's, once
    np.testing.assert_allclose(sum(parts), want, atol=1e-5)
    if held < 16:  # and one share alone is not the layer
        assert float(jnp.max(jnp.abs(parts[0] - want))) > 1e-3


def test_the_softmax_router_renormalises_the_picked_probabilities():
    p = _layer_weights(3)
    h = jnp.asarray(np.random.default_rng(4).normal(size=(10, 32)), jnp.float32)
    idx, gates = moe.softmax_route(h, p["router"], 8)
    probs = jax.nn.softmax(h @ p["router"], axis=-1)
    np.testing.assert_allclose(jnp.sum(gates, axis=-1), 1.0, atol=1e-6)
    want_idx = jnp.argsort(-probs, axis=-1)[:, :8]
    assert (np.sort(np.asarray(idx)) == np.sort(np.asarray(want_idx))).all()
    picked = jnp.take_along_axis(probs, idx, axis=-1)
    np.testing.assert_allclose(gates, picked / jnp.sum(picked, -1, keepdims=True), atol=1e-6)


def test_the_block_has_no_selection_bias():
    assert not any("router_bias" in name for name in LEAVES)


# ---------------------------------------------------------------------------
# the trainer, the sharding rules, the refusals, the preset
# ---------------------------------------------------------------------------


def test_fit_returns_the_selection_counters_on_their_span():
    from photon_tpu.train.trainer import Trainer
    from photon_tpu.utils.profiling import (
        DSA_CAUSAL_PAIRS, DSA_TILES_CAUSAL, MOE_DISPATCH_ROWS_MOVED,
        MOE_DISPATCH_ROWS_STATIC, MOE_ROWS_HELD)

    cfg = tiny_cfg()
    trainer = Trainer(cfg, init_seed=0)
    assert trainer._step_attrs.steps["dsa_layers"] == 2
    assert trainer._step_attrs.steps["dsa_topk"] == 16
    out = trainer.fit([TOKENS] * 2, duration_steps=2)
    rows = 2 * 2  # layers x batch rows
    assert out[DSA_CAUSAL_PAIRS] == rows * 64 * 65 // 2
    # 16 full early rows then 16 a query, a few ties allowed for
    least = rows * (16 * 17 // 2 + 48 * 16)
    assert least <= out[DSA_PICKED_PAIRS] <= least + 200
    assert out[DSA_TILES_VISITED] == out[DSA_TILES_CAUSAL] == rows  # one tile a row
    assert 0.0 < out[DSA_INDEX_LOSS] < 2.0
    assert 64 <= out[MOE_ROWS_HELD] <= 256  # ~ 2 x 64 x 2 x 2 x 2/8
    # two un-permutes a layer, a tiny layer's rows one chunk: 2 x 64 x 2 x 2
    assert out[MOE_DISPATCH_ROWS_MOVED] == out[MOE_DISPATCH_ROWS_STATIC] == 2 * 512


@pytest.mark.parametrize("impl, interpret, remat, seq", [
    ("xla", False, False, 64), ("pallas", False, False, 64), ("pallas", True, False, 64),
    ("pallas", True, True, 64), ("pallas", True, True, 512), ("pallas", False, True, 512)])
def test_the_dsa_span_says_which_path_made_pbar(impl, interpret, remat, seq):
    """``trainer/dsa`` carries the path the index loss took and the key tiles
    its launches computed and skipped a step, and the path that searched the
    selection's thresholds with its launches a step (one a chunk: rows of 64
    have no shape for it); on the CPU backend ``pallas`` without the
    interpreter steps down, as the attention does."""
    from photon_tpu.models.step import step_attrs
    from photon_tpu.utils.profiling import TRAINER_DSA_SPAN

    model = tiny_cfg(attn_impl=impl, attn_interpret=interpret, remat=remat, max_seq_len=seq,
                     dsa_chunk=seq // 4).model
    attrs = step_attrs(model, batch_rows=2).fence[TRAINER_DSA_SPAN]
    assert attrs["index_loss_kernel"] is (impl == "pallas" and interpret)
    launches = 2 * 2 * (2 if remat else 1) if interpret else 0  # layers x rows x passes
    assert attrs["index_loss_tiles"] == launches * 4
    assert attrs["index_loss_tiles_skipped"] == 0
    assert attrs["select_kernel"] is (interpret and seq == 512)
    assert attrs["select_launches"] == (launches * 4 if seq == 512 else 0)  # x chunks


def test_every_new_parameter_has_a_sharding_rule():
    import re

    from jax.sharding import PartitionSpec as P

    from photon_tpu.config.schema import MeshConfig
    from photon_tpu.parallel.mesh import make_mesh
    from photon_tpu.parallel.sharding import _RULES, param_specs

    unruled = [n for n in LEAVES if not any(re.search(rx, n) for rx, _ in _RULES)]
    assert not unruled
    mesh = make_mesh(MeshConfig(fsdp=2, tensor=2), devices=jax.devices()[:4])
    block = param_specs(ref.make_params(dims_of(tiny_cfg()), 0), mesh)["blocks"]["block"]
    # the indexer's small outputs stay whole, the attention's heads split
    assert block["idx_q_proj"]["kernel"] == P("pipe", "fsdp", None)
    assert block["idx_w_proj"]["kernel"] == P("pipe", "fsdp", None)
    assert block["q_proj"]["kernel"] == P("pipe", "fsdp", "tensor")
    assert block["q_norm"]["scale"] == block["idx_k_norm"]["bias"] == P("pipe", None)


def _refuse_serving():
    from photon_tpu.serve.engine import PagedEngine

    PagedEngine(tiny_cfg(), params={})


def _refuse_decode():
    from photon_tpu.models.decode import prefill

    prefill({}, jnp.zeros((1, 4), jnp.int32), jnp.array([4]), tiny_cfg().model)


def _refuse_hf_export():
    from photon_tpu.checkpoint.hf_export import mixtral_state_dict

    mixtral_state_dict({}, tiny_cfg().model)


def _refuse_hf_import():
    from photon_tpu.checkpoint.hf_import import llama_params_from_hf

    llama_params_from_hf({}, tiny_cfg().model)


@pytest.mark.parametrize("call", [_refuse_serving, _refuse_decode,
                                  _refuse_hf_export, _refuse_hf_import],
                         ids=lambda f: f.__name__.removeprefix("_refuse_"))
def test_serving_decode_and_hf_interop_refuse_the_family(call):
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
    (dict(model__moe_bias_update_speed=0.1), "no selection bias"),
    (dict(model__moe_shared_experts=1), "no shared expert"),
    (dict(model__moe_routed_scale=1.8), "no scale"),
    (dict(model__moe_experts_held=3), "does not divide"),
    (dict(mesh__expert=2, mesh__surplus_devices="ignore"), "no expert exchange"),
    (dict(model__alibi=True), "alibi"),
    (dict(model__kv_lora_rank=16, model__q_lora_rank=24, model__qk_nope_head_dim=12,
          model__qk_rope_head_dim=4, model__v_head_dim=16, model__n_kv_heads=0),
     "grouped-query branch"),
    (dict(model__attn_impl="ring"), "ring attention"),
    (dict(mesh__sequence=2, mesh__surplus_devices="ignore"), "mesh.sequence"),
    (dict(model__n_kv_heads=4), "grouped-query branch"),
    (dict(model__dsa_index_head_dim=15), "even"),
    (dict(model__dsa_chunk=48), "whole chunks"),
    (dict(model__dsa_topk=0), "belong to dsa_topk"),
    (dict(model__attention_multiplier=0.5), "softmax scale"),
    (dict(model__lora_rank=4, model__lora_targets=("out_proj",)), "LoRA"),
    (dict(photon__serve__prefix_cache=True), "prefix cache"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_schema_refuses_what_the_family_cannot_do_yet(change, message):
    cfg = tiny_cfg()
    with pytest.raises(ValueError, match=message):
        _with(cfg, **change).validate()


def test_the_preset_is_what_the_benchmark_configuration_states():
    """``benchmark/program.build_config`` holds the preset to every size of
    the configuration file; the cut's arithmetic (ISSUE 35, PERF.md section
    4) is the tree's own count."""
    import json

    from benchmark.program import build_config
    from photon_tpu.models import init_params

    config = json.loads((ROOT / f"benchmark/configs/{PRESET}.json").read_text())
    traffic = json.loads((ROOT / "benchmark/traffic/ep8-share-1x16384.json").read_text())
    cfg = build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=2 ** 31 + 5)
    assert cfg.train.global_batch_size == cfg.train.device_microbatch_size == 1
    assert cfg.optimizer.lr == 7.3e-6 and cfg.scheduler.t_warmup == 0
    assert cfg.model.d_head == 128 and cfg.model.experts_held == 16
    shapes = jax.eval_shape(lambda: init_params(cfg.model, seed=0))
    by_leaf = {n: int(np.prod(leaf.shape)) for n, leaf in by_name(shapes).items()}
    assert sum(by_leaf.values()) == 465_391_104  # x 16 bytes = 7.45 GB
    layer = sum(v for n, v in by_leaf.items() if n.startswith("blocks/")) // 4
    assert layer == 96_899_456
    assert sum(v for n, v in by_leaf.items() if "/idx_" in n) // 4 == 2_261_120
    config["model"]["dsa_topk"] = 1024
    with pytest.raises(ValueError, match="dsa_topk"):
        build_config(config, traffic, ROOT / ".bench_work" / "unused", seed=1)


def test_model_flops_per_token_are_the_benchmarks_cost_module():
    """``utils/profiling.model_flops_per_token`` answers for the family with
    the terms of ``benchmark/costs/keye_sparse_moe_train.py`` at the expected
    share of rows and the pairs the selection picks without ties."""
    import json

    from benchmark.costs import keye_sparse_moe_train as cost
    from photon_tpu.utils.profiling import model_flops_per_token

    config = json.loads((ROOT / f"benchmark/configs/{PRESET}.json").read_text())
    cfg = load_preset(PRESET)
    model = config["model"]
    want = cost.flops_per_token(
        model, cost.expected_routed_rows_per_token(model),
        cost.expected_picked_pairs_per_token(model))
    assert model_flops_per_token(cfg.model) == pytest.approx(want, rel=1e-9)
    tiny = tiny_cfg()
    model = dataclasses.asdict(tiny.model)
    assert model_flops_per_token(tiny.model) == pytest.approx(cost.flops_per_token(
        model, cost.expected_routed_rows_per_token(model),
        cost.expected_picked_pairs_per_token(model)), rel=1e-9)
