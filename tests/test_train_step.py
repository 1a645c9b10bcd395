import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from photon_tpu.config.schema import ModelConfig, OptimizerConfig, SchedulerConfig
from photon_tpu.models.mpt import MLA_PROJ_SCOPE, MPTModel, init_params
from photon_tpu.optim import build_optimizer, build_schedule
from photon_tpu.train import init_train_state, make_eval_step, make_train_step
from photon_tpu.train.train_step import (
    LOSS_HEAD_SCOPE,
    _chunked_ce_sum,
    _output_embedding,
)
from photon_tpu.utils.profiling import (
    ATTN_PROJ_SCOPE,
    BLOCK_MLP_SCOPE,
    BLOCK_NORM_SCOPE,
    GRAD_NORM_SCOPE,
)

TINY = ModelConfig(
    d_model=64, n_layers=2, n_heads=4, max_seq_len=32, vocab_size=64,
    attn_impl="xla", compute_dtype="float32",
)


def _setup(opt_name="adamw", n_micro=1):
    ocfg = OptimizerConfig(name=opt_name, lr=1e-3)
    scfg = SchedulerConfig(t_warmup=2, t_max=50)
    tx, sched = build_optimizer(ocfg, scfg)
    model = MPTModel(TINY)
    params = init_params(TINY, seed=0)
    state = init_train_state(model, tx, params)
    step = jax.jit(make_train_step(model, tx, n_microbatches=n_micro))
    return model, state, step, sched


def _batch(key, b=4, s=32):
    return jax.random.randint(key, (b, s), 0, TINY.vocab_size)


def test_loss_decreases_adamw():
    _, state, step, _ = _setup("adamw")
    tokens = _batch(jax.random.PRNGKey(0))
    losses = []
    for _ in range(20):
        state, m = step(state, tokens)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses


def test_loss_decreases_adopt():
    _, state, step, _ = _setup("adopt")
    tokens = _batch(jax.random.PRNGKey(0))
    losses = []
    for _ in range(20):
        state, m = step(state, tokens)
        losses.append(float(m["loss"]))
    # ADOPT step 0 only initializes v; still must learn overall
    assert losses[-1] < losses[0] - 0.5, losses


def test_microbatching_matches_full_batch():
    """Grad accumulation must be numerically equivalent to the full batch."""
    _, state, step_full, _ = _setup("adamw", n_micro=1)
    _, state2, step_micro, _ = _setup("adamw", n_micro=4)
    tokens = _batch(jax.random.PRNGKey(1), b=8)
    s1, m1 = step_full(state, tokens)
    s2, m2 = step_micro(state2, tokens)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6)


def test_schedule_shape():
    sched = build_schedule(SchedulerConfig(t_warmup=10, t_max=100, alpha_f=0.1), base_lr=1.0)
    assert float(sched(0)) == 0.0
    np.testing.assert_allclose(float(sched(10)), 1.0, rtol=1e-6)
    np.testing.assert_allclose(float(sched(100)), 0.1, rtol=1e-6)
    assert float(sched(55)) > float(sched(90))


def test_eval_step():
    model, state, step, _ = _setup()
    eval_step = jax.jit(make_eval_step(model))
    tokens = _batch(jax.random.PRNGKey(2))
    ce_sum, n = eval_step(state.params, tokens)
    assert n == tokens.shape[0] * (tokens.shape[1] - 1)
    assert np.isfinite(float(ce_sum))


def test_determinism():
    _, state, step, _ = _setup()
    tokens = _batch(jax.random.PRNGKey(3))
    s1, m1 = step(state, tokens)
    s2, m2 = step(state, tokens)
    assert float(m1["loss"]) == float(m2["loss"])
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- the chunked cross-entropy head (train_step._chunked_ce_sum) -------------

HEAD_VOCAB = 96  # no other dimension of the head's toy shapes is 96


def _head_model(tied: bool, logits_scaling: float, compute: str) -> MPTModel:
    return MPTModel(ModelConfig(
        d_model=32, n_layers=1, n_heads=2, max_seq_len=16, vocab_size=HEAD_VOCAB,
        attn_impl="xla", compute_dtype=compute, tie_embeddings=tied,
        logits_scaling=logits_scaling))


def _head_inputs(model: MPTModel, rows: int, seq: int):
    cfg = model.cfg
    k_e, k_h, k_t = jax.random.split(jax.random.PRNGKey(7), 3)
    emb = 0.5 * jax.random.normal(k_e, (cfg.vocab_size, cfg.d_model), jnp.float32)
    params = ({"wte": {"embedding": emb}} if cfg.tie_embeddings
              else {"lm_head": {"kernel": emb.T}})
    hidden = jax.random.normal(k_h, (rows, seq, cfg.d_model), jnp.float32)
    targets = jax.random.randint(k_t, (rows, seq), 0, cfg.vocab_size)
    return params, hidden.astype(cfg.compute_dtype), targets


def _plain_ce_sum(model, params, hidden, targets):
    """Full ``[N, vocab]`` float32 logits, no chunks: what the head must equal."""
    emb = _output_embedding(model, params).astype(jnp.float32)
    logits = hidden.astype(jnp.float32) @ emb.T / model.cfg.logits_scaling
    return jnp.sum(optax.softmax_cross_entropy_with_integer_labels(logits, targets))


def _parent_chunked_ce_sum(model, params, hidden, targets, chunk):
    """The head as it was before its gradient moved into the forward loop (a
    checkpointed scan that XLA differentiates): the yardstick for how far
    bf16 compute may sit from float32."""
    b, s, d = hidden.shape
    n = b * s
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    xf = jnp.pad(hidden.reshape(n, d), ((0, pad), (0, 0)))
    tf = jnp.pad(targets.reshape(n), (0, pad))
    mask = (jnp.arange(n_chunks * chunk) < n).astype(jnp.float32)
    emb_t = _output_embedding(model, params).astype(hidden.dtype).T

    def piece(carry, xtm):
        xc, tc, mc = xtm
        logits = jnp.dot(xc, emb_t, preferred_element_type=jnp.float32)
        logits = logits / model.cfg.logits_scaling
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return carry + jnp.sum((lse - gold) * mc), None

    total, _ = jax.lax.scan(
        jax.checkpoint(piece), jnp.zeros([], jnp.float32),
        (xf.reshape(n_chunks, chunk, d), tf.reshape(n_chunks, chunk),
         mask.reshape(n_chunks, chunk)))
    return total


def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _value_and_grads(fn, params, hidden, cotangent):
    """``(value, d params leaf, d hidden)`` of ``cotangent * fn``."""
    value, (g_params, g_hidden) = jax.value_and_grad(
        lambda p, h: cotangent * fn(p, h), argnums=(0, 1))(params, hidden)
    (g_emb,) = jax.tree.leaves(g_params)
    return value, g_emb, g_hidden


HEAD_CASES = {
    "tied": dict(tied=True, logits_scaling=1.0, rows=2, seq=16, chunk=8, cotangent=1.0),
    "untied_logits_scaling_8": dict(tied=False, logits_scaling=8.0, rows=2, seq=16,
                                    chunk=8, cotangent=1.0),
    "padded_last_chunk": dict(tied=True, logits_scaling=1.0, rows=3, seq=15, chunk=8,
                              cotangent=1.0),
    "cotangent_not_1": dict(tied=True, logits_scaling=1.0, rows=2, seq=16, chunk=8,
                            cotangent=-0.37),
}


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(HEAD_CASES))
def test_chunked_head_matches_plain_cross_entropy(case, compute):
    """Loss, d hidden and d embedding of the chunked head against the plain
    full-logits float32 cross-entropy: to 1e-5 at float32 compute; at bf16
    compute no further from it than the parent's head."""
    c = HEAD_CASES[case]
    model = _head_model(c["tied"], c["logits_scaling"], compute)
    params, hidden, targets = _head_inputs(model, c["rows"], c["seq"])
    want = _value_and_grads(lambda p, h: _plain_ce_sum(model, p, h, targets),
                            params, hidden, c["cotangent"])
    got = _value_and_grads(lambda p, h: _chunked_ce_sum(model, p, h, targets, c["chunk"]),
                           params, hidden, c["cotangent"])
    assert got[1].dtype == jnp.float32 and got[2].dtype == hidden.dtype
    gaps = [_rel(g, w) for g, w in zip(got, want)]
    if compute == "float32":
        assert max(gaps) < 1e-5, gaps
        return
    parent = _value_and_grads(
        lambda p, h: _parent_chunked_ce_sum(model, p, h, targets, c["chunk"]),
        params, hidden, c["cotangent"])
    parent_gaps = [_rel(g, w) for g, w in zip(parent, want)]
    # the loss is the parent's bit for bit; d embedding is nearer (summed over
    # chunks in float32, not bf16); d hidden has one rounding more HERE: the
    # CPU's products take the parent's float32 ``d`` whole, where the MXU's
    # default precision rounds it to bf16 as the new head's cast does
    assert gaps[0] == parent_gaps[0]
    assert gaps[1] <= parent_gaps[1]
    assert gaps[2] <= 1.25 * parent_gaps[2]


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_chunked_head_inside_the_microbatch_scan(compute):
    """The whole step with two accumulated microbatches, each a padded pair
    of chunks: loss and every leaf of the gradient (plain SGD at rate 1, so
    the weights' change is the gradient) against the full-logits head."""
    cfg = ModelConfig(d_model=32, n_layers=1, n_heads=2, max_seq_len=12, vocab_size=HEAD_VOCAB,
                      attn_impl="xla", compute_dtype=compute)
    model = MPTModel(cfg)
    tx = optax.sgd(1.0)
    params = init_params(cfg, seed=3)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (4, 12), 0, HEAD_VOCAB)

    def stepped(loss_chunk_tokens):
        step = jax.jit(make_train_step(model, tx, n_microbatches=2,
                                       loss_chunk_tokens=loss_chunk_tokens))
        state, metrics = step(init_train_state(model, tx, params), tokens)
        return metrics["loss"], jax.tree.map(lambda a, b: a - b, params, state.params)

    loss, grads = stepped(16)  # 22 targets a microbatch: a whole chunk and 6 of the next
    want_loss, want_grads = stepped(0)
    tol = 1e-5 if compute == "float32" else 2e-2
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=tol)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert _rel(got, want) < tol


def test_eval_step_sum_is_the_plain_sum_and_the_parents():
    """The eval step (no gradient taken: the loop without its gradient half)
    sums what the full-logits path sums, and what the parent's head summed."""
    model, state, _, _ = _setup()
    tokens = _batch(jax.random.PRNGKey(2), b=3, s=27)  # 78 targets: 4 chunks of 24, padded
    ce_sum, n = jax.jit(make_eval_step(model, loss_chunk_tokens=24))(state.params, tokens)
    plain_sum, plain_n = jax.jit(make_eval_step(model, loss_chunk_tokens=0))(state.params, tokens)
    assert int(n) == int(plain_n) == 78
    np.testing.assert_allclose(float(ce_sum), float(plain_sum), rtol=1e-6)
    hidden = model.apply({"params": state.params}, tokens, return_hidden=True)
    parent_sum = _parent_chunked_ce_sum(model, state.params, hidden[:, :-1], tokens[:, 1:], 24)
    np.testing.assert_allclose(float(ce_sum), float(parent_sum), rtol=1e-6)


def _lowered_head_gradient(chunk=8):
    """The head's gradient alone, under its scope, over three chunks:
    ``(its jaxpr as text, the lowering)``."""
    model = _head_model(tied=True, logits_scaling=1.0, compute="bfloat16")
    params, hidden, targets = _head_inputs(model, rows=2, seq=12)

    def head(p, h):
        with jax.named_scope(LOSS_HEAD_SCOPE):
            return _chunked_ce_sum(model, p, h, targets, chunk) / targets.size

    grad = jax.grad(head, argnums=(0, 1))
    return str(jax.make_jaxpr(grad)(params, hidden)), jax.jit(grad).lower(params, hidden)


def test_head_gradient_takes_three_vocabulary_products_a_chunk_and_recomputes_nothing():
    jaxpr, lowered = _lowered_head_gradient()
    text = lowered.as_text()
    # the chunk loop's body is in the program once, however many chunks run
    products = [line for line in text.splitlines()
                if "stablehlo.dot_general" in line and f"x{HEAD_VOCAB}x" in line.replace("<", "x")]
    assert len(products) == 3, products  # logits, d hidden, d embedding (the parent: 4)
    for word in ("checkpoint", "remat"):
        assert word not in jaxpr and word not in text
    assert text.count("stablehlo.while") == 1  # one loop: forward and gradient together


def test_head_operations_all_carry_the_scope():
    """Forward loop and backward scaling alike: every operation's ``op_name``
    holds ``train_step/loss_head``, so ``loss_head_ms_train`` counts the whole
    head. (Names without a ``/`` are the arguments' and the reducers' own.)"""
    compiled = _lowered_head_gradient()[1].compile().as_text()
    names = [n for n in re.findall(r'op_name="([^"]*)"', compiled) if "/" in n]
    assert len(names) > 40
    outside = sorted({n for n in names if LOSS_HEAD_SCOPE not in n})
    assert not outside, outside
    assert any(f"transpose(jvp({LOSS_HEAD_SCOPE}))" in n for n in names)  # the backward's


# -- every operation of a step under a name (ISSUE 37) ----------------------
# The four scopes of ``models/mpt.py`` and ``train_step.py`` beside the ones
# that were there, read as the trace's readers read them: from the compiled
# step's ``op_name``s.
_GQA = dict(d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, max_seq_len=32,
            vocab_size=64, attn_impl="xla", compute_dtype="float32", rope=True,
            learned_pos_emb=False, norm="rmsnorm", mlp="swiglu", remat=True)
#: what the parent's ``init_params`` gave each toy: a scope renames no module
_BLOCK_PATHS = {
    "dense": {"blocks": ("down_proj/kernel", "ln_1/scale", "ln_2/scale",
                         "out_proj/kernel", "up_proj/kernel", "wqkv/kernel"),
              "": ("ln_f/scale", "wpe", "wte/embedding")},
    "gqa_swiglu": {"blocks": ("down_proj/kernel", "gate_proj/kernel", "k_proj/kernel",
                              "ln_1/scale", "ln_2/scale", "out_proj/kernel",
                              "q_proj/kernel", "up_proj/kernel", "v_proj/kernel"),
                   "": ("ln_f/scale", "wte/embedding")},
    "dense_then_experts": {
        "blocks": ("kv_a_norm/scale", "kv_a_proj/kernel", "kv_b_proj/kernel",
                   "ln_1/scale", "ln_2/scale", "moe_down", "moe_gate", "moe_up",
                   "out_proj/kernel", "q_a_norm/scale", "q_a_proj/kernel",
                   "q_b_proj/kernel", "router", "router_bias",
                   "shared_down_proj/kernel", "shared_gate_proj/kernel",
                   "shared_up_proj/kernel"),
        "dense_blocks": ("down_proj/kernel", "gate_proj/kernel", "kv_a_norm/scale",
                         "kv_a_proj/kernel", "kv_b_proj/kernel", "ln_1/scale",
                         "ln_2/scale", "out_proj/kernel", "q_a_norm/scale",
                         "q_a_proj/kernel", "q_b_proj/kernel", "up_proj/kernel"),
        "": ("lm_head/kernel", "ln_f/scale", "wte/embedding")},
}
#: the new scopes a toy's step must hold (a latent model has no ``attn/proj``)
_SCOPES_OF = {
    "dense": (BLOCK_MLP_SCOPE, ATTN_PROJ_SCOPE, BLOCK_NORM_SCOPE, GRAD_NORM_SCOPE),
    "gqa_swiglu": (BLOCK_MLP_SCOPE, ATTN_PROJ_SCOPE, BLOCK_NORM_SCOPE, GRAD_NORM_SCOPE),
    "dense_then_experts": (BLOCK_MLP_SCOPE, BLOCK_NORM_SCOPE, GRAD_NORM_SCOPE),
}


def _scoped_model_cfg(kind: str) -> ModelConfig:
    if kind == "dense":
        return TINY
    if kind == "gqa_swiglu":
        return ModelConfig(**_GQA)
    from tests.test_glm_moe_lite import tiny_cfg  # 1 dense + 2 expert layers, latent attention

    return tiny_cfg().model


@pytest.fixture(scope="module", params=sorted(_SCOPES_OF))
def scoped_step(request):
    """``(kind, the parameter paths, the op_names of the compiled step)`` of a
    toy model's whole train step."""
    cfg = _scoped_model_cfg(request.param)
    tx, _ = build_optimizer(OptimizerConfig(name="adopt", lr=1e-3),
                            SchedulerConfig(t_warmup=2, t_max=50))
    model = MPTModel(cfg)
    params = init_params(cfg, seed=0)
    state = init_train_state(model, tx, params)
    tokens = jnp.zeros((4, cfg.max_seq_len), jnp.int32)
    compiled = jax.jit(make_train_step(model, tx, loss_chunk_tokens=16)).lower(
        state, tokens).compile().as_text()
    paths = sorted("/".join(str(getattr(k, "key", k)) for k in path)
                   for path, _ in jax.tree_util.tree_leaves_with_path(params))
    return request.param, paths, re.findall(r'op_name="([^"]*)"', compiled)


def test_every_operation_of_the_step_carries_a_train_step_scope(scoped_step):
    """Nothing of the step is outside its stages, so ``step_unscoped_ms_train``
    reads what a later change drops there. Two kinds of name are not the
    step's own and are left out: a reducer's body (no ``jit(`` in front, no
    ``/`` or the bare tail of its caller's name), and a loop-invariant table
    jax hoists out of the layer scan (the rotation's angles, the router's
    ``iota``s), which keeps only the block's own names: those must sit under a
    scope the trace's partition (``benchmark/trace/step_parts.py``) has a part
    for. The XLA attention's causal mask is such a table too; the kernel the
    chip runs has none."""
    _, _, names = scoped_step
    own = [n for n in names if n.startswith("jit(train_step)/")]
    assert len(own) > 200
    hoisted = [n for n in own if re.match(r"jit\(train_step\)/(dense_)?blocks/block/", n)]
    outside = sorted({n for n in own if "train_step/" not in n} - set(hoisted))
    assert not outside, outside
    from benchmark.trace import step_parts

    lost = sorted({n for n in hoisted if "multihead_attention" not in n
                   and step_parts.part_of([n]) in step_parts.REMAINDERS})
    assert not lost, lost


@pytest.mark.parametrize("scope", [BLOCK_MLP_SCOPE, ATTN_PROJ_SCOPE, BLOCK_NORM_SCOPE,
                                   GRAD_NORM_SCOPE])
def test_new_scope_is_on_the_forward_and_on_the_backward(scoped_step, scope):
    """Forward, pull-back and recomputation alike carry a block's scope; the
    norms of ``train_step/grad_norm`` are outside the differentiated function
    and have no pull-back."""
    kind, _, names = scoped_step
    hits = [n for n in names if re.search(rf"\b{scope}\b", n)]
    if scope not in _SCOPES_OF[kind]:
        assert not hits  # the latent branch keeps ``mla/proj``
        return
    assert any("transpose(" not in n for n in hits), scope
    if scope != GRAD_NORM_SCOPE:
        assert any("transpose(jvp(" in n for n in hits), scope
    if scope == BLOCK_NORM_SCOPE:  # the blocks' two and the model's last
        for norm in ("ln_1", "ln_2", "ln_f"):
            assert any(f"{scope}/{norm}/" in n for n in hits), norm
    if scope == BLOCK_MLP_SCOPE:  # the activation has no module's name, only the scope's
        assert any(re.search(rf"{scope}/(jit\(silu\)|tanh|mul)", n) for n in hits)


def test_scopes_of_one_partition_never_share_an_operation(scoped_step):
    _, _, names = scoped_step
    both = [n for n in names
            if (ATTN_PROJ_SCOPE in n and MLA_PROJ_SCOPE in n)
            or (BLOCK_MLP_SCOPE in n and "moe/" in n)
            or sum(s in n for s in (BLOCK_MLP_SCOPE, ATTN_PROJ_SCOPE, BLOCK_NORM_SCOPE)) > 1]
    assert not both, both


def test_a_scope_renames_no_parameter(scoped_step):
    """Checkpoints and the HF maps find every leaf where the parent put it."""
    kind, paths, _ = scoped_step
    want = sorted(f"{stack}/block/{leaf}" if stack else leaf
                  for stack, leaves in _BLOCK_PATHS[kind].items() for leaf in leaves)
    assert paths == want


# ---------------------------------------------------------------------------
# the seam between the model and the step: ``models/step.COUNTERS``, the one
# walk and the one merge here, ``models/step.step_attrs`` and
# ``utils/profiling.FENCE_SPANS`` in ``Trainer.fit``
# ---------------------------------------------------------------------------

from photon_tpu.models import mpt  # noqa: E402
from photon_tpu.models.step import COUNTERS, Counter, sow  # noqa: E402
from photon_tpu.train.train_step import _make_loss_and_counters_fn  # noqa: E402
from photon_tpu.utils import profiling as names  # noqa: E402
from tests._helpers import TINY_PRESETS, recorded_spans, tiny_preset  # noqa: E402


def _tiny_tokens(cfg, rows: int = 4) -> np.ndarray:
    return np.random.default_rng(3).integers(
        0, 96, size=(rows, cfg.model.max_seq_len)).astype(np.int32)


def _sowing_blocks(monkeypatch, key: str, value) -> None:
    """Every block sows ``value(x)`` under ``key`` before it runs."""
    run = mpt.MPTBlock.__call__

    def call(self, x):
        sow(self, key, value(x))
        return run(self, x)

    monkeypatch.setattr(mpt.MPTBlock, "__call__", call)


#: the keys each preset's blocks sow
SOWN = {
    "mpt-125m": set(),
    "granite-4.0-h-micro-stage1": set(),
    "glm-4.7-flash-ep8": {k for k in COUNTERS if k.startswith("moe_")} - {"moe_aux"},
    "lfm2-8b-a1b-ep4": {k for k in COUNTERS if k.startswith("moe_")} - {"moe_aux"},
    "keye-vl-2.0-30b-a3b-ep8":
        {k for k in COUNTERS if k.startswith(("moe_", "dsa_"))} - {"moe_aux"},
    "xing4.0-29b-a4b-ep8":
        {k for k in COUNTERS if k.startswith(("moe_", "mhc_"))} - {"moe_aux"},
    "laguna-xs.2-ep8": {k for k in COUNTERS if k.startswith("moe_")} - {"moe_aux"},
    "nemotron-3-nano-30b-a3b-ep16": {k for k in COUNTERS if k.startswith("moe_")} - {"moe_aux"},
}


@pytest.mark.parametrize("preset", list(TINY_PRESETS))
def test_every_sown_key_has_a_row(preset, monkeypatch):
    """What a preset's blocks sow is in the table, key for key, and a block
    that sows a key without a row raises where it is traced."""
    cfg = tiny_preset(preset, batch=4, microbatch=4)
    model = MPTModel(cfg.model)
    params = jax.eval_shape(lambda: init_params(cfg.model, seed=0))
    tokens = jax.ShapeDtypeStruct((4, cfg.model.max_seq_len), jnp.int32)

    def sown():  # (a new function a call: a trace is cached by its function)
        return jax.eval_shape(
            lambda p, t: model.apply({"params": p}, t, mutable=["intermediates"])[1],
            params, tokens)

    tree = sown().get("intermediates", {})
    found = {k.key for path, _ in jax.tree_util.tree_leaves_with_path(tree)
             for k in path if getattr(k, "key", None) in COUNTERS}
    assert found == SOWN[preset]
    assert len(jax.tree.leaves(tree)) == sum(
        1 for path, _ in jax.tree_util.tree_leaves_with_path(tree)
        if any(getattr(k, "key", None) in COUNTERS for k in path))
    _sowing_blocks(monkeypatch, "rows_nobody_lists", lambda x: jnp.zeros([]))
    with pytest.raises(KeyError, match="rows_nobody_lists"):
        sown()


def test_the_walk_refuses_a_key_without_a_row():
    """A value that reached ``intermediates`` around ``models/step.sow`` is
    not dropped in silence either."""
    from photon_tpu.train.train_step import collect_counters

    with pytest.raises(KeyError, match="stray"):
        collect_counters({"blocks": {"block": {"stray": (jnp.zeros([2]),)}}})
    assert collect_counters({}) == {} and collect_counters(None) == {}


#: what every ``Trainer.fit`` returns
FIT_KEYS = {
    "loss", "grad_norm", "param_norm", names.CLIENT_FINAL_LOSS,
    names.CLIENT_FIT_SET_PARAMETERS_TIME, names.CLIENT_FIT_TIME, names.CLIENT_LR,
    names.CLIENT_STEPS, names.CLIENT_TOKENS_PER_SEC, "throughput/tokens_per_sec",
    "throughput/tokens_per_sec_ema"}
_MOE = {"rows_held": names.MOE_ROWS_HELD, "max_expert_load": names.MOE_MAX_EXPERT_LOAD,
        "dispatch_rows_moved": names.MOE_DISPATCH_ROWS_MOVED,
        "dispatch_rows_static": names.MOE_DISPATCH_ROWS_STATIC}
#: the parent of the seam at these sizes (4 rows in two microbatches): the
#: static attrs of ``trainer/steps``, and the spans inside ``trainer/fence``
#: in order, each attr with the step metric ``fit`` returns under it or with
#: its static value
PARENTS = {
    "mpt-125m": ({}, []),
    "granite-4.0-h-micro-stage1": (
        {"mamba_layers": 3, "mamba_groups": 1, "ssd_chunks": 4, "ssd_kernel_layers": 0}, []),
    "glm-4.7-flash-ep8": ({}, [(names.TRAINER_MOE_LOAD_SPAN, _MOE)]),
    "lfm2-8b-a1b-ep4": ({"conv_layers": 4}, [(names.TRAINER_MOE_LOAD_SPAN, _MOE)]),
    "xing4.0-29b-a4b-ep8": (
        {"mhc_streams": 4, "mhc_sublayers": 6},
        [(names.TRAINER_MOE_LOAD_SPAN, _MOE),
         (names.TRAINER_MHC_SPAN, {"sinkhorn_gap": names.MHC_SINKHORN_GAP})]),
    "laguna-xs.2-ep8": (
        {"swa_layers": 3, "sliding_window": 8, "head_gate_layers": 0},
        [(names.TRAINER_MOE_LOAD_SPAN, _MOE)]),
    "nemotron-3-nano-30b-a3b-ep16": (
        {"mamba_layers": 4, "mamba_groups": 2, "ssd_chunks": 4, "ssd_kernel_layers": 0,
         "moe_layers": 4, "attention_layers": 1},
        [(names.TRAINER_MOE_LOAD_SPAN, _MOE)]),
    "keye-vl-2.0-30b-a3b-ep8": (
        {"dsa_layers": 2, "dsa_topk": 16},
        [(names.TRAINER_MOE_LOAD_SPAN, _MOE),
         (names.TRAINER_DSA_SPAN, {
             "picked_pairs": names.DSA_PICKED_PAIRS, "causal_pairs": names.DSA_CAUSAL_PAIRS,
             "tiles_visited": names.DSA_TILES_VISITED, "tiles_causal": names.DSA_TILES_CAUSAL,
             "index_loss": names.DSA_INDEX_LOSS, "index_loss_kernel": False,
             "index_loss_tiles": 0, "index_loss_tiles_skipped": 0,
             "select_kernel": False, "select_launches": 0})]),
}


@pytest.mark.parametrize("preset", list(TINY_PRESETS))
def test_step_metrics_and_span_attrs_are_the_parents(preset, monkeypatch):
    """The keys ``Trainer.fit`` returns, the attrs of ``trainer/steps`` and
    the spans inside ``trainer/fence`` with theirs, name for name and in the
    order the parent of the seam opened them."""
    from photon_tpu.train.trainer import Trainer

    steps, fence = PARENTS[preset]
    cfg = tiny_preset(preset, batch=4, microbatch=2)
    trainer = Trainer(cfg, init_seed=0)
    spans = recorded_spans(monkeypatch)
    out = trainer.fit([_tiny_tokens(cfg)] * 2, duration_steps=2)
    metrics = {m for _, attrs in fence for m in attrs.values() if isinstance(m, str)}
    assert set(out) == FIT_KEYS | metrics
    (told,) = [attrs for name, attrs in spans if name == names.TRAINER_STEPS_SPAN]
    assert told == {"steps": 2, **steps}
    after = [name for name, _ in spans]
    after = after[after.index(names.TRAINER_FENCE_SPAN) + 1:]
    assert after == [name for name, _ in fence]
    for name, attrs in fence:
        (got,) = [a for n, a in spans if n == name]
        assert list(got) == list(attrs)
        assert got == {a: out[m] if isinstance(m, str) else m for a, m in attrs.items()}
    if preset.startswith("keye"):  # its static counts: 2 layers x 4 rows of 64 tokens
        assert out[names.DSA_CAUSAL_PAIRS] == 8 * 64 * 65 // 2
        assert out[names.DSA_TILES_CAUSAL] == 8


def test_microbatches_merge_by_each_rows_rule():
    """Two microbatches' counters as one step's: a row that adds is the sum
    of the two, one that takes the worst their maximum, one that is a mean
    their mean; the scan starts from zeros of the loss function's own
    counters' shapes (``jax.eval_shape``), the rows by expert stack included."""
    cfg = tiny_preset("keye-vl-2.0-30b-a3b-ep8", batch=4, microbatch=2)
    model = MPTModel(cfg.model)
    params = init_params(cfg.model, seed=0)
    tx, _ = build_optimizer(cfg.optimizer, cfg.scheduler)
    tokens = jnp.asarray(_tiny_tokens(cfg))
    counters = jax.jit(lambda p, t: _make_loss_and_counters_fn(model, 16)(p, t)[1])
    first, second = counters(params, tokens[:2]), counters(params, tokens[2:])
    assert first["moe_expert_rows"]["blocks"].shape == (2, 8)
    step = jax.jit(make_train_step(model, tx, n_microbatches=2, loss_chunk_tokens=16))
    _, metrics = step(init_train_state(model, tx, params), tokens)
    rules = set()
    for key, row in COUNTERS.items():
        if key not in first or row.metric is None:
            continue
        a, b, got = float(first[key]), float(second[key]), float(metrics[row.metric])
        want = {"add": a + b, "max": max(a, b), "mean": (a + b) / 2}[row.microbatches]
        assert got == pytest.approx(want, rel=1e-6), key
        assert a != b or row.microbatches == "add", key  # the rules tell apart
        rules.add(row.microbatches)
    assert rules == {"add", "max", "mean"}
    assert "moe_expert_rows" not in metrics and "moe/expert_rows" not in metrics


def test_an_eleventh_counter_takes_a_row_and_a_sow(monkeypatch):
    """One row in the table and one sow in a block: the counter is reduced
    over layers and microbatches by its row and is in ``Trainer.fit``'s
    metrics, with no line of ``train/`` knowing it."""
    from photon_tpu.train.trainer import Trainer

    monkeypatch.setitem(COUNTERS, "tokens_mixed", Counter("test/tokens_mixed", "sum", "add"))
    monkeypatch.setitem(COUNTERS, "widest_row", Counter("test/widest_row", "max", "max"))
    _sowing_blocks(monkeypatch, "tokens_mixed",
                   lambda x: jnp.asarray(x.shape[0] * x.shape[1], jnp.float32))
    cfg = tiny_preset("mpt-125m", batch=4, microbatch=2)
    out = Trainer(cfg, init_seed=0).fit([_tiny_tokens(cfg)], duration_steps=1)
    assert out["test/tokens_mixed"] == 2 * 4 * 32  # layers x rows x tokens
    assert "test/widest_row" not in out  # a row nobody sows adds no metric
