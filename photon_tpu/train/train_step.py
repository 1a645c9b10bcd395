"""The jitted training step — photon-tpu's replacement for the Composer
Trainer's inner loop (reference: ``trainer.fit`` hot loop,
``photon/clients/llm_client_functions.py:206`` → Composer → torch/NCCL).

One function, traced once: microbatch scan (grad accumulation) → grad mean →
clip → optimizer → param update. Under ``jit`` over a Mesh, XLA inserts all
DP/FSDP/TP collectives on ICI (SURVEY.md §2.3-2.4). Causal-LM cross-entropy
with next-token shift; loss in fp32.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import flax.struct
import jax
import jax.numpy as jnp
import optax

from photon_tpu.models.mpt import MPTModel
from photon_tpu.models.step import COUNTERS, EXPERT_ROWS
from photon_tpu.utils.profiling import GRAD_NORM_SCOPE

# The step's stages as ``jax.named_scope``s: they reach every operation's
# ``op_name`` metadata (forward, transpose and recomputation alike), which is
# where a profiler trace's reader finds them after any refactor.
FORWARD_BACKWARD_SCOPE = "train_step/forward_backward"
#: the chunked cross-entropy head: its forward loop, which also forms the
#: head's gradients, and the backward's scaling of them
LOSS_HEAD_SCOPE = "train_step/loss_head"
#: ``tx.update`` + ``apply_updates``
OPTIMIZER_SCOPE = "train_step/optimizer"


@flax.struct.dataclass
class TrainState:
    """Carried across steps and across federated rounds (the analog of the
    persistent Composer Trainer state, ``worker/worker.py:207,254``)."""

    step: jax.Array  # int32 — local step counter (timestamp.batch analog)
    params: Any
    opt_state: Any


def _output_embedding(model: MPTModel, params) -> jax.Array:
    """``[vocab, d_model]`` output projection weights (tied wte or lm_head)."""
    if model.cfg.tie_embeddings:
        return params["wte"]["embedding"]
    return params["lm_head"]["kernel"].T


def _ce_chunk_loop(x, emb, targets, chunk: int, logits_scaling: float, with_grads: bool):
    """The head's one loop over ``chunk``-token pieces of ``x [N, d]`` against
    ``emb [vocab, d]`` (both in the compute dtype): the sum of CE and, with
    ``with_grads``, its gradients by ``x`` and ``emb`` at unit cotangent
    (``None`` otherwise). A piece's logits are formed once; the statistics
    and the gradient's two products all read that one tensor."""
    n, d = x.shape
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad))
    mask = (jnp.arange(n_chunks * chunk) < n).astype(jnp.float32)
    pieces = (x.reshape(n_chunks, chunk, d), targets.reshape(n_chunks, chunk),
              mask.reshape(n_chunks, chunk))

    def piece(carry, xtm):
        total, demb = carry
        xc, tc, mc = xtm
        logits = jax.lax.dot_general(
            xc, emb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if logits_scaling != 1.0:
            logits = logits / logits_scaling
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) == tc[:, None]
        gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        total = total + jnp.sum((lse - gold) * mc)
        if not with_grads:
            return (total, demb), None
        dlogits = (jnp.exp(logits - lse[:, None]) - hit) * (mc / logits_scaling)[:, None]
        dlogits = dlogits.astype(x.dtype)
        dxc = jnp.dot(dlogits, emb, preferred_element_type=jnp.float32)
        demb = demb + jax.lax.dot_general(
            dlogits, xc, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return (total, demb), dxc.astype(x.dtype)

    demb0 = jnp.zeros(emb.shape, jnp.float32) if with_grads else None
    (total, demb), dx = jax.lax.scan(piece, (jnp.zeros([], jnp.float32), demb0), pieces)
    if not with_grads:
        return total, None, None
    # ``emb``'s gradient waits for the optimizer through the whole backward of
    # the blocks, so it waits in ``emb``'s dtype, as XLA's transpose of the
    # product had it wait: rounded once, after the float32 sum over pieces. The
    # barrier ties that rounding to ``dx``, which the backward needs first;
    # without it the compiler rounds last and the float32 sum does the waiting
    # (glm-4.7-flash-ep8: 158 MB more at the step's peak, 43 MB under the chip).
    dx, demb = jax.lax.optimization_barrier(
        (dx.reshape(n_chunks * chunk, d)[:n], demb.astype(emb.dtype)))
    return total, dx, demb


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ce_sum(x, emb, targets, chunk, logits_scaling):
    return _ce_chunk_loop(x, emb, targets, chunk, logits_scaling, with_grads=False)[0]


def _ce_sum_fwd(x, emb, targets, chunk, logits_scaling):
    total, dx, demb = _ce_chunk_loop(x, emb, targets, chunk, logits_scaling, with_grads=True)
    return total, (dx, demb)


def _ce_sum_bwd(chunk, logits_scaling, grads, g):
    dx, demb = grads
    return (g * dx).astype(dx.dtype), (g * demb).astype(demb.dtype), None


_ce_sum.defvjp(_ce_sum_fwd, _ce_sum_bwd)


def _chunked_ce_sum(
    model: MPTModel, params, hidden: jax.Array, targets: jax.Array, chunk: int
) -> jax.Array:
    """Sum of next-token CE over ``chunk``-token pieces, never holding more
    than one piece's logits.

    A piece's fp32 logits are ``chunk x vocab x 4`` bytes of HBM (412.6 MB
    at 2,048 x 50,368). The bf16 product writes them, with fp32 accumulation
    and the row maximum from the same pass; the sum of exponentials and the
    gold logit read them once. Under differentiation the same loop goes on to
    the head's two gradients at unit cotangent (``jax.custom_vjp``): ``d =
    (softmax - onehot) * mask / logits_scaling`` in fp32, cast to the hidden
    state's dtype (the MXU's default precision takes an fp32 operand in one
    bf16 pass anyway), then ``dx = d . E`` and ``dE += d^T . x`` with fp32
    accumulation, ``dE`` summed over pieces in fp32. XLA writes no ``d``: each
    of the two products re-derives it from the logits inside its own fusion,
    so a piece's logits cross HBM four times (one write, three reads). The
    backward only scales the two gradients by the cotangent: nothing is
    recomputed, and a caller that takes no gradient (the eval step) runs the
    loop without the gradient half.
    """
    b, s, d = hidden.shape
    emb = _output_embedding(model, params).astype(hidden.dtype)  # [vocab, d]
    return _ce_sum(hidden.reshape(b * s, d), emb, targets.reshape(b * s), chunk,
                   model.cfg.logits_scaling)


def collect_counters(sown: Any) -> dict[str, Any]:
    """An ``intermediates`` collection as the step's counters, by sown key:
    every per-layer value over the layers of all stacks as its row of
    ``models/step.COUNTERS`` says (a sum or a maximum, both float32 scalars, or
    ``{stack: [layers, ...]}`` under the name of the stack that sowed it, which
    a sow's path starts with). A key without a row raises. Empty where nothing
    was sown."""
    out: dict[str, Any] = {}
    worst: dict[str, list[jax.Array]] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(sown or {}):
        keys = [getattr(k, "key", None) for k in path]
        key = next((k for k in keys if k in COUNTERS), None)
        if key is None:
            raise KeyError(f"sown at {keys} and has no row in models/step.COUNTERS")
        leaf = jnp.asarray(leaf, jnp.float32)
        rule = COUNTERS[key].layers
        if rule == "sum":
            out[key] = out.get(key, 0.0) + jnp.sum(leaf)
        elif rule == "max":
            worst.setdefault(key, []).append(jnp.max(leaf))
        else:
            out.setdefault(key, {})[keys[0]] = leaf.reshape(-1, leaf.shape[-1])
    return {**out, **{key: jnp.max(jnp.stack(v)) for key, v in worst.items()}}


def collect_moe_aux(variables: Any) -> jax.Array:
    """Sum the per-layer ``moe_aux`` sows out of an ``intermediates``
    collection (and ONLY those — other sown diagnostics must not leak
    into the objective). The pipeline's stage scan, which applies blocks
    outside ``_apply_collecting_aux``, takes its aux loss from here."""
    return collect_counters(variables).get("moe_aux", jnp.zeros([], jnp.float32))


_MERGE = {"add": jnp.add, "max": jnp.maximum, "mean": jnp.add}


def _merge_counters(a: dict, b: dict) -> dict:
    """Two microbatches' counters as one step's, each by its row's rule (a
    mean is a sum until the step divides it)."""
    return {k: jax.tree.map(_MERGE[COUNTERS[k].microbatches], a[k], b[k]) for k in a}


def _apply_collecting_aux(model: MPTModel, params, tokens, **kwargs):
    """``model.apply`` that also returns what its blocks sowed
    (``models/step.COUNTERS``; nothing for most models): the counters, and the
    sum of those that join the objective, each under its weight. Plain
    inference applies leave ``intermediates`` immutable, so a sow is a no-op
    there."""
    out, variables = model.apply(
        {"params": params}, tokens, mutable=["intermediates"], **kwargs
    )
    counters = collect_counters(variables.get("intermediates", {}))
    aux = jnp.zeros([], jnp.float32)
    for key, total in counters.items():
        weight = COUNTERS[key].weight
        if weight is not None:
            aux = aux + (getattr(model.cfg, weight) if isinstance(weight, str)
                         else weight) * total
    return out, aux, counters


def _make_loss_and_counters_fn(model: MPTModel, loss_chunk_tokens: int) -> Callable:
    def loss_fn(params, tokens: jax.Array):
        """``(loss, counters)``: mean next-token cross entropy over
        ``[B, S] int32`` tokens plus what the blocks sowed for the objective,
        and everything they sowed, by sown key."""
        if loss_chunk_tokens:
            hidden, aux, counters = _apply_collecting_aux(
                model, params, tokens, return_hidden=True
            )
            with jax.named_scope(LOSS_HEAD_SCOPE):
                ce_sum = _chunked_ce_sum(
                    model, params, hidden[:, :-1], tokens[:, 1:], loss_chunk_tokens
                )
            return ce_sum / (tokens.shape[0] * (tokens.shape[1] - 1)) + aux, counters
        logits, aux, counters = _apply_collecting_aux(model, params, tokens)
        targets = tokens[:, 1:]
        logits = logits[:, :-1]
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), targets
        )
        return jnp.mean(ce) + aux, counters

    return loss_fn


def make_loss_fn(model: MPTModel, loss_chunk_tokens: int = 2048) -> Callable:
    """``(params, tokens) -> loss`` (the step's own loss without its counters)."""
    fn = _make_loss_and_counters_fn(model, loss_chunk_tokens)
    return lambda params, tokens: fn(params, tokens)[0]


def _balance_router_bias(params, expert_rows: dict[str, jax.Array], speed: float):
    """Every expert stack's selection bias (``router_bias [layers, E]``, which
    the optimizer leaves alone: it has no gradient) moved one step against
    the loads this step routed to that stack (``expert_rows[stack]``)."""
    from photon_tpu.ops.moe import balanced_router_bias

    def move(path, leaf):
        if getattr(path[-1], "key", None) != "router_bias":
            return leaf
        rows = expert_rows[path[0].key]
        return balanced_router_bias(leaf, rows, speed).astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(move, params)


def make_train_step(
    model: MPTModel,
    tx: optax.GradientTransformation,
    n_microbatches: int = 1,
    loss_chunk_tokens: int = 2048,
) -> Callable:
    """Build the pure train-step fn ``(state, tokens) -> (state, metrics)``.

    ``tokens`` is ``[global_batch, seq]``; with ``n_microbatches > 1`` the
    batch is scanned in chunks and gradients averaged — the deterministic
    analog of the reference's ``device_train_microbatch_size`` grad
    accumulation (``conf/llm_config/mpt-125m.yaml:80-81``).
    """
    loss_fn = _make_loss_and_counters_fn(model, loss_chunk_tokens)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def forward_backward(state: TrainState, tokens: jax.Array):
        if n_microbatches > 1:
            b = tokens.shape[0]
            if b % n_microbatches:
                raise ValueError(f"batch {b} not divisible by {n_microbatches} microbatches")
            micro = tokens.reshape(n_microbatches, b // n_microbatches, tokens.shape[1])

            def body(carry, mb):
                loss_acc, grad_acc, counters_acc = carry
                (loss, counters), grads = grad_fn(state.params, mb)
                return (loss_acc + loss, jax.tree.map(jnp.add, grad_acc, grads),
                        _merge_counters(counters_acc, counters)), None

            zero_grads = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            zero_counters = jax.tree.map(
                lambda c: jnp.zeros(c.shape, c.dtype),
                jax.eval_shape(lambda p, mb: loss_fn(p, mb)[1], state.params, micro[0]))
            (loss_sum, grad_sum, counters), _ = jax.lax.scan(
                body, (jnp.zeros([], jnp.float32), zero_grads, zero_counters), micro)
            loss = loss_sum / n_microbatches
            grads = jax.tree.map(lambda g: g / n_microbatches, grad_sum)
            counters = {k: v / n_microbatches if COUNTERS[k].microbatches == "mean" else v
                        for k, v in counters.items()}
        else:
            (loss, counters), grads = grad_fn(state.params, tokens)
        return loss, grads, counters

    def train_step(state: TrainState, tokens: jax.Array):
        with jax.named_scope(FORWARD_BACKWARD_SCOPE):
            loss, grads, counters = forward_backward(state, tokens)
        # the two logged norms under a scope of their own (the clip inside
        # ``tx`` takes the same gradient norm under the optimizer's scope; XLA
        # computes it once and keeps this one's name: PERF.md section 5)
        with jax.named_scope(GRAD_NORM_SCOPE):
            grad_norm = optax.global_norm(grads)
        with jax.named_scope(OPTIMIZER_SCOPE):
            updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
            if EXPERT_ROWS in counters and model.cfg.moe_bias_update_speed:
                new_params = _balance_router_bias(
                    new_params, counters[EXPERT_ROWS], model.cfg.moe_bias_update_speed)
            new_state = TrainState(
                step=state.step + 1, params=new_params, opt_state=new_opt_state)
        with jax.named_scope(GRAD_NORM_SCOPE):
            param_norm = optax.global_norm(new_params)
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "param_norm": param_norm,
            **{COUNTERS[k].metric: v for k, v in counters.items() if COUNTERS[k].metric},
        }
        return new_state, metrics

    return train_step


def make_eval_step(model: MPTModel, loss_chunk_tokens: int = 2048) -> Callable:
    """``(params, tokens) -> (sum_ce, n_tokens)`` for loss aggregation across
    eval batches (reference: ``llm_eval`` collecting ``eval_metric_values``,
    ``clients/llm_client_functions.py:231-353``)."""
    def eval_step(params, tokens: jax.Array):
        n_tok = tokens.shape[0] * (tokens.shape[1] - 1)
        if loss_chunk_tokens:
            hidden = model.apply({"params": params}, tokens, return_hidden=True)
            ce_sum = _chunked_ce_sum(
                model, params, hidden[:, :-1], tokens[:, 1:], loss_chunk_tokens
            )
            return ce_sum, jnp.asarray(n_tok, jnp.int32)
        logits = model.apply({"params": params}, tokens)
        targets = tokens[:, 1:]
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), targets
        )
        return jnp.sum(ce), jnp.asarray(ce.size, jnp.int32)

    return eval_step


def init_train_state(model: MPTModel, tx: optax.GradientTransformation, params: Any) -> TrainState:
    return TrainState(step=jnp.zeros([], jnp.int32), params=params, opt_state=tx.init(params))
