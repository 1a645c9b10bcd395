"""Pipeline parallelism: a GPipe-style stage schedule over a ``pipe`` mesh
axis — TPU-native scale-out the reference does not have (its in-client
parallelism is DDP/FSDP/TP via Composer, ``trainer_utils.py:1640-1720``;
there is no pipeline path anywhere in ``/root/reference``).

Design (the "How to Scale Your Model" pipelining recipe, built on JAX's
partial-manual ``shard_map``):

- The stacked block params (leading ``[n_layers]`` axis from ``nn.scan``)
  are sharded over ``pipe`` — each stage owns a contiguous slab of
  ``n_layers / pipe`` layers (``parallel/sharding.py`` rules). No second
  parameter layout exists: the SAME TrainState, checkpoint format, and
  optimizer tree serve pipe=1 and pipe>1.
- ``jax.shard_map(..., axis_names={"pipe"})`` makes only the pipe axis
  manual; ``data``/``fsdp``/``tensor`` stay under GSPMD *inside* the
  region, so pipeline composes with batch/weight sharding without any
  hand-written collectives for those axes.
- The schedule is a ``lax.scan`` over ``n_micro + P - 1`` ticks: stage 0
  feeds embedded microbatch ``t``, stages hand activations forward with a
  single ``lax.ppermute`` per tick, and the last stage runs the final
  norm + (chunked) cross-entropy for microbatch ``t - (P-1)``. Bubble
  fraction is the textbook ``(P-1)/(n_micro+P-1)``.
- ``jax.value_and_grad`` runs *inside* the manual region: autodiff
  transposes the ``ppermute`` into the reverse rotation, so the backward
  pipeline needs no extra code. Gradients of stage-local slabs stay
  stage-local (they ARE the pipe shard); gradients of pipe-replicated
  params (embeddings, final norm, lm head) are ``psum``-merged over pipe.

Numerical contract: identical loss/gradients to the non-pipelined
``make_train_step`` with the same ``n_microbatches`` grad accumulation
(``tests/test_pipeline.py`` asserts equivalence on the virtual mesh).
"""

from __future__ import annotations

from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, PartitionSpec as P

from photon_tpu.config.schema import ModelConfig
from photon_tpu.models.mpt import MPTBlock, MPTModel, _norm
from photon_tpu.train.train_step import (
    TrainState,
    _chunked_ce_sum,
    _output_embedding,
    collect_moe_aux,
)


def _batch_constrain(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Pin activations to the plain batch sharding inside the pipeline's
    partial-manual region. Left to itself, GSPMD's strategy search picks
    exotic half-sharded layouts for the embed gather / CE take_along_axis
    under the manual ``pipe`` subgroup and then aborts in
    ``spmd_partitioner_util.cc`` grouping (a hard CHECK, not an error);
    constraining the producers to batch-over-(data,fsdp) keeps it on the
    well-trodden path."""
    from jax.sharding import NamedSharding

    from photon_tpu.parallel.sharding import _fit_spec

    spec = _fit_spec(
        P(("data", "fsdp", "expert"), *([None] * (x.ndim - 1))), x.shape, mesh
    )
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _embed(cfg: ModelConfig, params: Any, tokens: jax.Array, mesh: Mesh) -> jax.Array:
    """Token (+ learned positional) embedding — same modules/math as
    ``MPTModel.__call__`` (reused flax modules, applied to the subtree)."""
    compute = jnp.dtype(cfg.compute_dtype)
    x = nn.Embed(
        cfg.vocab_size, cfg.d_model, dtype=compute,
        param_dtype=jnp.dtype(cfg.param_dtype),
    ).apply({"params": {"embedding": params["wte"]["embedding"]}}, tokens)
    if cfg.learned_pos_emb and not cfg.alibi and not cfg.rope:
        x = x + params["wpe"][None, : tokens.shape[1], :].astype(compute)
    return _batch_constrain(x, mesh)


def _final_norm(cfg: ModelConfig, params: Any, x: jax.Array) -> jax.Array:
    return _norm(cfg, "ln_f").apply({"params": params["ln_f"]}, x)


def _tail_ce_mean(
    model: MPTModel, params: Any, hidden: jax.Array, tokens: jax.Array,
    chunk: int,
) -> jax.Array:
    """Mean next-token CE from post-``ln_f`` hidden states (the last
    pipeline stage's tail — mirrors ``make_loss_fn``'s two paths)."""
    cfg = model.cfg
    n_tok = tokens.shape[0] * (tokens.shape[1] - 1)
    if chunk:
        return _chunked_ce_sum(
            model, params, hidden[:, :-1], tokens[:, 1:], chunk
        ) / n_tok
    compute = jnp.dtype(cfg.compute_dtype)
    emb = _output_embedding(model, params).astype(compute)  # [vocab, d]
    logits = hidden.astype(compute) @ emb.T
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1].astype(jnp.float32), tokens[:, 1:]
    )
    return jnp.mean(ce)


def _stage_apply(cfg: ModelConfig, slab: Any, x: jax.Array):
    """Run this stage's ``[Lp, ...]`` layer slab (scan over local layers).
    Returns ``(y, aux)`` where ``aux`` is the stage's summed MoE
    load-balance loss (0.0 for dense models) — the pipeline collects the
    per-layer ``moe_aux`` sows explicitly because the stage scan applies
    blocks outside flax's ``nn.scan`` plumbing.

    With ``cfg.remat`` the pipeline remats at BOTH levels: the tick
    checkpoint saves only the stage-boundary activation per tick, and the
    per-layer checkpoint here makes the tick's own backward recompute one
    layer at a time. The second level is what bounds the XLA attention's
    ``[b, h, s, s]`` score matrices (pipe stages run the non-flash
    attention; without per-layer remat a single tick's backward would
    hold every local layer's score matrix at once — ~26 GiB for the 1B
    recipe's 12-layer stage at seq 2048)."""
    block = MPTBlock(cfg)

    def body(carry, layer_params):
        x, aux_acc = carry
        y, variables = block.apply(
            {"params": layer_params}, x, mutable=["intermediates"]
        )
        aux_acc = aux_acc + collect_moe_aux(variables.get("intermediates", {}))
        return (y, aux_acc), None

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable,
            prevent_cse=False,
        )
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros([], jnp.float32)), slab)
    return x, aux


def make_pipeline_train_step(
    model: MPTModel,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    n_microbatches: int,
    loss_chunk_tokens: int = 2048,
) -> Callable:
    """Pipelined ``(state, tokens) -> (state, metrics)``; drop-in for
    :func:`photon_tpu.train.train_step.make_train_step` when
    ``mesh.pipe > 1``. ``n_microbatches`` is both the grad-accumulation
    granularity and the pipeline depth-filling factor."""
    cfg = model.cfg
    n_stages = mesh.shape["pipe"]
    if cfg.n_layers % n_stages:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by pipe={n_stages}")
    n_micro = n_microbatches
    compute = jnp.dtype(cfg.compute_dtype)

    def shard_fn(blocks: Any, others: Any, micro_tokens: jax.Array):
        # blocks: {"block": ...} leaves [Lp, ...] — this stage's slab
        # (manual over pipe); others: rest of the param tree, replicated
        # over pipe; micro_tokens: [n_micro, mb, seq] replicated over pipe.
        idx = jax.lax.axis_index("pipe")
        ticks = n_micro + n_stages - 1
        mb, seq = micro_tokens.shape[1:]

        # per-tick token schedule, gathered with STATIC indices outside the
        # scan (an in-body dynamic_index_in_dim over the microbatch stack
        # trips an XLA partitioner CHECK at some shapes under the
        # partial-manual region); the scan then consumes them as xs
        feed_idx = np.clip(np.arange(ticks), 0, n_micro - 1)
        exit_idx = np.clip(np.arange(ticks) - (n_stages - 1), 0, n_micro - 1)

        def loss_of(blocks, others):
            full = dict(others, blocks=blocks)  # for the tied lm head

            def tick(carry, xs):
                buf, ce_sum = carry
                t, tok_in, tok_out = xs
                # stage 0 feeds microbatch t (bubble ticks feed a dead
                # microbatch whose loss contribution is masked out below).
                # Known inefficiency, kept deliberately: every stage
                # computes the embed and the CE tail and masks the result
                # — (P-1)/P of that compute is wasted. Replacing the
                # where-masks with lax.cond (which WOULD skip the dead
                # branches: the predicates are uniform per device) crashes
                # XLA's SPMD partitioner inside the partial-manual region,
                # the same CHECK-abort family the embed sharding
                # constraint works around (_batch_constrain).
                x = jnp.where(idx == 0, _embed(cfg, others, tok_in, mesh), buf)
                y, stage_aux = _stage_apply(cfg, blocks["block"], x)
                # last stage: microbatch t-(P-1) exits the pipe this tick
                ce = _tail_ce_mean(
                    model, full, _final_norm(cfg, others, y), tok_out,
                    loss_chunk_tokens,
                )
                live = (idx == n_stages - 1) & (t >= n_stages - 1)
                ce_sum = ce_sum + jnp.where(live, ce, 0.0)
                # this stage processed microbatch t-idx this tick; its MoE
                # aux counts only when that microbatch is real (not a
                # pipeline bubble)
                carried = (t >= idx) & (t - idx < n_micro)
                ce_sum = ce_sum + jnp.where(
                    carried, cfg.moe_aux_weight * stage_aux, 0.0
                )
                buf = jax.lax.ppermute(
                    y, "pipe",
                    [(i, (i + 1) % n_stages) for i in range(n_stages)],
                )
                return (buf, ce_sum), None

            carry0 = (
                jnp.zeros((mb, seq, cfg.d_model), compute),
                jnp.zeros([], jnp.float32),
            )
            tick_fn = tick
            if cfg.remat:
                # GPipe-standard rematerialization: save only the carried
                # stage-boundary activation per tick; recompute the whole
                # tick (layer slab + CE tail) in the backward
                tick_fn = jax.checkpoint(
                    tick, policy=jax.checkpoint_policies.nothing_saveable,
                    prevent_cse=False,
                )
            (_, ce_sum), _ = jax.lax.scan(
                tick_fn, carry0,
                (jnp.arange(ticks), micro_tokens[feed_idx],
                 micro_tokens[exit_idx]),
            )
            # the LOCAL masked loss — zero on every stage but the last. Do
            # NOT psum here: grad seeds are 1 on every device, so inside a
            # manual region autodiff effectively differentiates the SUM of
            # per-device outputs — a psum inside the differentiated
            # function would scale every gradient by n_stages. The sum of
            # these local outputs IS the global loss.
            return ce_sum / n_micro

        loss_local, (g_blocks, g_others) = jax.value_and_grad(
            loss_of, argnums=(0, 1)
        )(blocks, others)
        loss = jax.lax.psum(loss_local, "pipe")  # value only, outside grad
        # stage-local slab grads stay sharded over pipe; contributions to
        # pipe-replicated params (wte/wpe/ln_f/lm_head) differ per stage
        # (stage 0: embed path, last stage: head path) — merge them
        g_others = jax.tree.map(lambda g: jax.lax.psum(g, "pipe"), g_others)
        return loss, g_blocks, g_others

    # manual over `pipe` only; replication checking off because the
    # per-stage losses are deliberately device-varying
    pipelined = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P("pipe"), P(), P()),
        out_specs=(P(), P("pipe"), P()),
        axis_names={"pipe"},
        check_vma=False,
    )

    def train_step(state: TrainState, tokens: jax.Array):
        b = tokens.shape[0]
        if b % n_micro:
            raise ValueError(f"batch {b} not divisible by {n_micro} microbatches")
        micro = tokens.reshape(n_micro, b // n_micro, tokens.shape[1])
        others = {k: v for k, v in state.params.items() if k != "blocks"}
        loss, g_blocks, g_others = pipelined(state.params["blocks"], others, micro)
        grads = dict(g_others, blocks=g_blocks)
        grad_norm = optax.global_norm(grads)
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(
            step=state.step + 1, params=new_params, opt_state=new_opt_state
        )
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
            "param_norm": optax.global_norm(new_params),
        }
        return new_state, metrics

    return train_step
