"""Trainer: the persistent per-client training runtime.

Replaces the reference's Composer ``Trainer`` assembly + reuse machinery
(``photon/clients/trainer_utils.py:1117-1721``, ``TrainerMutableAttributes``
``:172-202``): one object owning the jitted sharded train step, the sharded
:class:`TrainState`, and the host loop. Persistent across federated rounds —
optimizer state and the step counter survive, matching the reference's
``external_trainer`` reuse semantics (``worker/worker.py:207,254``).

TPU-first: a "client" is a mesh slice driven by ONE pjit'd step; DP/FSDP/TP
collectives are XLA-inserted over ICI. Parameter exchange with the federation
layer goes through the flat-ndarray codec (host side), mirroring the
reference's FSDP gather/scatter at round boundaries (``utils.py:247-319``).
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Any, Callable, Iterable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from photon_tpu import telemetry
from photon_tpu.codec import ParamsMetadata, params_from_ndarrays, params_to_ndarrays
from photon_tpu.config.schema import Config
from photon_tpu.models.mpt import MPTModel, init_params
from photon_tpu.models.step import step_attrs
from photon_tpu.optim import build_optimizer
from photon_tpu.parallel.mesh import make_mesh
from photon_tpu.parallel.sharding import batch_spec, state_shardings
from photon_tpu.train.train_step import (
    TrainState,
    init_train_state,
    make_eval_step,
    make_train_step,
)
from photon_tpu.utils.profiling import (
    CLIENT_FINAL_LOSS,
    CLIENT_FIT_SET_PARAMETERS_TIME,
    CLIENT_FIT_TIME,
    CLIENT_LR,
    CLIENT_STEPS,
    CLIENT_TOKENS_PER_SEC,
    EVENT_SPEED_MONITOR_PEAK,
    FENCE_SPANS,
    TRAINER_FENCE_SPAN,
    TRAINER_GET_PARAMETERS_SPAN,
    TRAINER_NEXT_BATCH_SPAN,
    TRAINER_SET_PARAMETERS_SPAN,
    TRAINER_STEPS_SPAN,
    SpeedMonitor,
)


def _set_opt_count(opt_state: Any, step: int) -> Any:
    """Return ``opt_state`` with every ``count`` field (optax's step counter
    in AdoptState / ScaleByAdamState / ...) set to ``step``."""

    def visit(path, leaf):
        last = path[-1] if path else None
        name = getattr(last, "name", getattr(last, "key", None))
        if name == "count":
            return jnp.asarray(step, leaf.dtype if hasattr(leaf, "dtype") else jnp.int32)
        return leaf

    return jax.tree_util.tree_map_with_path(visit, opt_state)


class Trainer:
    def __init__(
        self,
        cfg: Config,
        mesh=None,
        params: Any | None = None,
        init_seed: int | None = None,
    ) -> None:
        self.cfg = cfg
        # mesh-driven attn_impl fallbacks (pipe→xla, sequence→ring) happen
        # HERE, at step construction — never inside Config.validate(), so
        # cfg.model stays the operator's config of record
        from photon_tpu.config.schema import effective_model_config

        # heterogeneity-aware layout auto-tune (ISSUE 14b): a trainer built
        # WITHOUT an explicit mesh derives (data, fsdp, tensor, pipe) from
        # the analytic cost model over its local device slice — the
        # per-client entry point that replaces hand-set mesh knobs on
        # uneven fleets. An explicit ``mesh=`` always wins (callers that
        # pin devices, e.g. the collective runner, keep full control).
        mesh_cfg = cfg.mesh
        self.layout_autotune: dict | None = None
        if mesh is None and cfg.photon.mesh_autotune:
            from photon_tpu.parallel.autotune import autotune_layout

            t0 = time.monotonic()
            micro = cfg.train.device_microbatch_size
            best = autotune_layout(
                cfg.model, devices=jax.local_devices(),
                global_batch_size=cfg.train.global_batch_size,
                microbatch=micro if isinstance(micro, int) else 0,
                # 'auto' microbatch probes against the NON-pipelined step
                # (the combination Config.validate rejects) — never let
                # the tuner pick a pipelined layout the probe can't build
                max_pipe=None if isinstance(micro, int) else 1,
            )
            mesh_cfg = dataclasses.replace(
                best.mesh, surplus_devices=cfg.mesh.surplus_devices
            )
            self.layout_autotune = {
                "mesh": mesh_cfg,
                "search_s": time.monotonic() - t0,
                "est_step_s": best.est_step_s,
            }
            mesh = make_mesh(mesh_cfg, devices=jax.local_devices())

        self.model = MPTModel(effective_model_config(cfg.model, mesh_cfg))
        self.tx, self.lr_schedule = build_optimizer(cfg.optimizer, cfg.scheduler)
        self.mesh = mesh if mesh is not None else make_mesh(cfg.mesh)

        self._last_set_time = 0.0

        if params is None:
            params = init_params(cfg.model, seed=cfg.seed if init_seed is None else init_seed)
        host_state = init_train_state(self.model, self.tx, params)
        self._shardings = state_shardings(host_state, self.mesh)
        self._batch_sharding = NamedSharding(self.mesh, batch_spec(self.mesh))

        # device_microbatch_size is PER DEVICE (reference:
        # ``device_train_microbatch_size``); a scan step processes
        # micro × dp_degree global rows, where dp_degree covers the batch-
        # sharded mesh axes (data and fsdp)
        # batch rows shard over data+fsdp+expert (parallel/sharding.py
        # batch_spec): every axis that splits the batch counts toward the
        # per-device row accounting
        dp_degree = (self.mesh.shape["data"] * self.mesh.shape["fsdp"]
                     * self.mesh.shape.get("expert", 1))
        # batch/device-count adaptation (reference:
        # ``photon/clients/llm_config_functions.py:865-900`` rounds the batch
        # to the visible device count, with a warning): a global batch not
        # divisible by the batch-sharded mesh degree is rounded DOWN to the
        # nearest multiple so the jitted step's batch sharding is exact
        gbs = cfg.train.global_batch_size
        if gbs % dp_degree:
            adapted = max((gbs // dp_degree) * dp_degree, dp_degree)
            warnings.warn(
                f"global_batch_size {gbs} not divisible by data-parallel degree "
                f"{dp_degree}; adapted to {adapted}",
                stacklevel=2,
            )
            cfg.train.global_batch_size = adapted
        micro = cfg.train.device_microbatch_size
        probed_step = None
        if micro == "auto":
            # OOM-adaptive probe (reference:
            # ``device_train_microbatch_size: auto``,
            # ``photon/clients/trainer_utils.py:972-978``)
            micro, probed_step = self._probe_microbatch(host_state, dp_degree)
        else:
            # a microbatch larger than the per-device batch would silently
            # run one oversized scan chunk — clamp it to the batch
            clamped = min(micro, cfg.train.global_batch_size // dp_degree)
            if clamped != micro:
                warnings.warn(
                    f"device_microbatch_size {micro} exceeds the per-device "
                    f"batch {cfg.train.global_batch_size // dp_degree}; "
                    f"clamped to {clamped}",
                    stacklevel=2,
                )
            micro = clamped
        self.device_microbatch_size = micro
        rows_per_scan = micro * dp_degree
        # dp_degree-multiple adaptation alone is not enough: the scan needs
        # the batch to split into EQUAL micro*dp_degree chunks, so round down
        # again to a multiple of rows_per_scan (>= one chunk)
        if cfg.train.global_batch_size % rows_per_scan:
            adapted = max(
                (cfg.train.global_batch_size // rows_per_scan) * rows_per_scan,
                rows_per_scan,
            )
            warnings.warn(
                f"global_batch_size {cfg.train.global_batch_size} not divisible "
                f"by microbatch rows-per-scan {rows_per_scan} "
                f"(micro {micro} x dp {dp_degree}); adapted to {adapted}",
                stacklevel=2,
            )
            cfg.train.global_batch_size = adapted
        self.effective_global_batch_size = cfg.train.global_batch_size
        n_micro = cfg.train.global_batch_size // rows_per_scan
        assert n_micro * rows_per_scan == cfg.train.global_batch_size
        self._n_micro = n_micro
        # the step's static numbers, told by the model where the shapes are known
        self._step_attrs = step_attrs(self.model.cfg, cfg.train.global_batch_size)

        self.state: TrainState = jax.tree.map(
            lambda leaf, sh: jax.device_put(leaf, sh), host_state, self._shardings
        )
        if probed_step is not None:
            jitted_train = probed_step  # reuse the winner's compile
        else:
            if self.mesh.shape.get("pipe", 1) > 1:
                # GPipe-style stage schedule over the pipe axis; same
                # TrainState/sharding/checkpoint layout, different step fn
                from photon_tpu.parallel.pipeline import make_pipeline_train_step

                step_fn = make_pipeline_train_step(
                    self.model, self.tx, self.mesh, n_microbatches=n_micro,
                    loss_chunk_tokens=cfg.train.loss_chunk_tokens,
                )
            else:
                step_fn = make_train_step(
                    self.model, self.tx, n_microbatches=n_micro,
                    loss_chunk_tokens=cfg.train.loss_chunk_tokens,
                )
            jitted_train = jax.jit(
                step_fn,
                in_shardings=(self._shardings, self._batch_sharding),
                out_shardings=(self._shardings, None),
                donate_argnums=0,
            )
        jitted_eval = jax.jit(
            make_eval_step(self.model, loss_chunk_tokens=cfg.train.loss_chunk_tokens),
            in_shardings=(self._shardings.params, self._batch_sharding),
        )

        # tracing may build shard_map regions (ring attention) that need the
        # concrete mesh — publish it for the duration of each call
        from photon_tpu.parallel.context import use_mesh

        def _train(state, batch):
            with use_mesh(self.mesh):
                return jitted_train(state, batch)

        def _eval(params, batch):
            with use_mesh(self.mesh):
                return jitted_eval(params, batch)

        self._train_step = _train
        self._eval_step = _eval
        self._jitted_train = jitted_train

        # MFU/throughput monitor, peak auto-detected from THIS trainer's
        # mesh devices (ISSUE 4 satellite: the old hardcoded v5e default
        # mis-scaled MFU on every other chip); the chosen peak is recorded
        # as a telemetry event so a run's MFU numbers carry their basis
        mesh_devices = self.mesh.devices
        self.speed_monitor = SpeedMonitor(
            cfg.model,
            n_chips=int(mesh_devices.size),
            device_kind=getattr(mesh_devices.flat[0], "device_kind", ""),
        )
        telemetry.emit_event(
            EVENT_SPEED_MONITOR_PEAK,
            device_kind=self.speed_monitor.device_kind,
            peak_flops_per_chip=self.speed_monitor.peak_flops_per_chip,
            n_chips=self.speed_monitor.n_chips,
        )

    def lower_train_step(self):
        """The jitted train step this trainer runs, lowered at its own state
        and batch shapes — ``.compile().as_text()`` shows what is in the
        program (e.g. ``tpu_custom_call`` for the Pallas kernels)."""
        from photon_tpu.parallel.context import use_mesh

        tokens = jax.ShapeDtypeStruct(
            (self.effective_global_batch_size, self.cfg.model.max_seq_len),
            np.int32,
        )
        with use_mesh(self.mesh):
            return self._jitted_train.lower(self.state, tokens)

    # ------------------------------------------------------------------
    # auto microbatch probe
    # ------------------------------------------------------------------

    @staticmethod
    def _is_oom(e: Exception) -> bool:
        from photon_tpu.utils.profiling import is_oom

        return is_oom(e)

    def _probe_microbatch(self, host_state: TrainState, dp_degree: int):
        """Largest power-of-2 per-device microbatch that compiles AND executes
        one real (donated) train step without exhausting HBM (reference:
        ``device_train_microbatch_size: auto`` halving on CUDA OOM,
        ``photon/clients/trainer_utils.py:972-978``).

        Each candidate builds a fresh device state so the probe's memory
        profile matches the real step exactly; the probe state is freed before
        the persistent one is created. Returns ``(microbatch, jitted_step)``
        so the winner's (possibly minutes-long) compile is reused for the
        persistent train step instead of being paid twice.
        """
        from photon_tpu.parallel.context import use_mesh

        cfg = self.cfg
        per_device_rows = max(1, cfg.train.global_batch_size // dp_degree)
        if cfg.train.auto_microbatch_cap:
            per_device_rows = min(per_device_rows, cfg.train.auto_microbatch_cap)
        cand = 1 << (per_device_rows.bit_length() - 1)  # largest pow2 <= rows
        seq = cfg.model.max_seq_len
        last_err: Exception | None = None
        probed_any = False
        # stage through host numpy: device_put of an already-correctly-sharded
        # device array is a no-copy alias, and donating an alias would delete
        # the very buffers the persistent state is built from afterwards
        host_state = jax.tree.map(np.asarray, host_state)
        while cand >= 1:
            rows = cand * dp_degree
            if cfg.train.global_batch_size % rows:
                cand //= 2  # scan needs equal chunks
                continue
            n_micro = max(1, cfg.train.global_batch_size // rows)
            probed_any = True
            try:
                step = jax.jit(
                    make_train_step(
                        self.model, self.tx, n_microbatches=n_micro,
                        loss_chunk_tokens=cfg.train.loss_chunk_tokens,
                    ),
                    in_shardings=(self._shardings, self._batch_sharding),
                    out_shardings=(self._shardings, None),
                    donate_argnums=0,
                )
                state = jax.tree.map(
                    lambda leaf, sh: jax.device_put(leaf, sh), host_state, self._shardings
                )
                tokens = jax.device_put(
                    np.zeros((cfg.train.global_batch_size, seq), np.int32),
                    self._batch_sharding,
                )
                with use_mesh(self.mesh):
                    new_state, _ = step(state, tokens)
                jax.block_until_ready(new_state)
                del state, new_state, tokens
                return cand, step
            except Exception as e:  # noqa: BLE001 — only OOM is retryable
                # free the failed candidate's device buffers BEFORE the next
                # (smaller) candidate allocates its own full TrainState, or
                # every retry probes under ~2x state HBM pressure
                state = new_state = tokens = None  # noqa: F841 — drop refs
                if not self._is_oom(e):
                    raise
                last_err = e
                cand //= 2
        if not probed_any:
            raise ValueError(
                "auto microbatch: no power-of-2 per-device microbatch divides "
                f"global_batch_size={cfg.train.global_batch_size} over "
                f"dp_degree={dp_degree}; set device_microbatch_size explicitly"
            )
        from photon_tpu.utils.profiling import dump_memory_profile

        dump = dump_memory_profile(
            getattr(cfg.photon, "save_path", ".") or ".", "auto_microbatch"
        )
        raise RuntimeError(
            f"auto microbatch: even microbatch 1 exhausts device memory"
            + (f" (memory profile: {dump})" if dump else "")
            + f": {last_err}"
        )

    # ------------------------------------------------------------------
    # training / eval loops
    # ------------------------------------------------------------------

    def fit(
        self,
        batches: Iterable[np.ndarray],
        duration_steps: int,
        log_every: int = 0,
        callback: Callable[[int, dict[str, float]], None] | None = None,
    ) -> dict[str, float]:
        """Run ``duration_steps`` steps (reference:
        ``trainer.fit(duration=local_steps)``, ``llm_client_functions.py:206``).

        Returns summary metrics including the reference's KPI names
        (``client/fit_time``, BASELINE.md KPI table).
        """
        import itertools

        from photon_tpu.data.prefetch import PrefetchIterator

        # prefetch EXACTLY duration_steps batches: the islice bound means the
        # background thread never over-advances a resumable loader's state
        it: Iterator[np.ndarray] = PrefetchIterator(
            itertools.islice(iter(batches), duration_steps), depth=2
        )
        t0 = time.monotonic()
        losses: list[float] = []
        last_metrics: dict[str, float] = {}
        tokens_seen = 0

        def log(i: int, metrics: dict) -> None:
            nonlocal last_metrics
            last_metrics = {k: float(v) for k, v in metrics.items()}
            losses.append(last_metrics["loss"])
            if callback:
                callback(i, last_metrics)

        metrics: dict = {}
        try:
            with telemetry.span(TRAINER_STEPS_SPAN, steps=duration_steps,
                                **self._step_attrs.steps):
                for i in range(duration_steps):
                    try:
                        with telemetry.span(TRAINER_NEXT_BATCH_SPAN):
                            batch = next(it)
                    except StopIteration:
                        raise ValueError(
                            f"batch stream exhausted at step {i}/{duration_steps}"
                        ) from None
                    tokens_seen += int(np.prod(batch.shape))
                    self.state, metrics = self._train_step(self.state, batch)
                    if log_every and (i + 1) % log_every == 0 and i + 1 < duration_steps:
                        log(i, metrics)
        finally:
            it.close()
        # the timed window closes on the WHOLE state plus the host fetch of
        # the last step's loss: dispatch is asynchronous and buffers become
        # ready one by one, so blocking on .step alone would return before
        # params/opt_state finish and wall-time would undercount
        with telemetry.span(TRAINER_FENCE_SPAN):
            jax.block_until_ready(self.state)
            if duration_steps:
                log(duration_steps - 1, metrics)
                # the last step's counters where a trace's reader finds them
                # (they came with the loss: no new sync), each family's on a
                # span of its own beside the model's static counts
                for name, measured in FENCE_SPANS.items():
                    if not any(m in last_metrics for m in measured.values()):
                        continue
                    static = self._step_attrs.fence.get(name, {})
                    last_metrics.update(
                        {measured[a]: v for a, v in static.items() if a in measured})
                    attrs = {a: last_metrics[m] for a, m in measured.items()}
                    with telemetry.span(name, **{**attrs, **static}):
                        pass
        dt = time.monotonic() - t0
        return {
            **last_metrics,
            # throughput/mfu against the auto-detected chip peak (EMA'd)
            **self.speed_monitor.update(tokens_seen, dt),
            CLIENT_FIT_TIME: dt,
            CLIENT_FIT_SET_PARAMETERS_TIME: self._last_set_time,
            CLIENT_STEPS: float(duration_steps),
            CLIENT_TOKENS_PER_SEC: tokens_seen / dt if dt > 0 else 0.0,
            CLIENT_FINAL_LOSS: losses[-1] if losses else float("nan"),
            CLIENT_LR: float(self.lr_schedule(self.step - 1)),
        }

    def evaluate(self, batches: Iterable[np.ndarray], max_batches: int = 0) -> dict[str, float]:
        """Mean CE over the eval stream (reference: ``llm_eval``,
        ``llm_client_functions.py:231-353``)."""
        t0 = time.monotonic()
        total_ce, total_tok = 0.0, 0
        for i, batch in enumerate(batches):
            if max_batches and i >= max_batches:
                break
            ce_sum, n = self._eval_step(self.state.params, batch)
            total_ce += float(ce_sum)
            total_tok += int(n)
        if total_tok == 0:
            raise ValueError("evaluate: empty eval stream")
        loss = total_ce / total_tok
        return {
            "eval/loss": loss,
            "eval/perplexity": float(np.exp(min(loss, 30.0))),
            "eval/tokens": float(total_tok),
            "eval/time": time.monotonic() - t0,
        }

    # ------------------------------------------------------------------
    # parameter plane (round boundaries)
    # ------------------------------------------------------------------

    @property
    def step(self) -> int:
        return int(self.state.step)

    def get_parameters(self) -> tuple[ParamsMetadata, list[np.ndarray]]:
        """Gather sharded params to host as the canonical flat list
        (reference: ``get_trainable_params_dict`` with summon_full_params,
        ``photon/utils.py:247-319`` — here XLA gathers, codec orders)."""
        with telemetry.span(TRAINER_GET_PARAMETERS_SPAN):
            return params_to_ndarrays(self.state.params)

    def set_parameters(self, metadata: ParamsMetadata, arrays: list[np.ndarray]) -> None:
        """Scatter a flat ndarray list into the sharded state (reference:
        ``set_trainer_params_from_ndarrays``, ``photon/utils.py:481-540``)."""
        with telemetry.span(TRAINER_SET_PARAMETERS_SPAN,
                            nbytes=metadata.total_bytes) as sp:
            new_params = params_from_ndarrays(self.state.params, metadata, arrays)
            new_params = jax.tree.map(
                lambda leaf, sh: jax.device_put(np.asarray(leaf), sh),
                new_params,
                self._shardings.params,
            )
            self.state = self.state.replace(params=new_params)
        # the span's own timer is the client/fit_set_parameters_time KPI
        self._last_set_time = sp.seconds

    def get_opt_state_arrays(self) -> tuple[ParamsMetadata, list[np.ndarray]]:
        """Flatten optimizer state to the canonical (metadata, arrays) form —
        client checkpoints persist the full TrainState (reference: Composer
        checkpoint includes optimizer state, ``llm_config_functions.py:642-764``)."""
        from photon_tpu.codec import params_to_ndarrays

        return params_to_ndarrays(self.state.opt_state)

    def set_opt_state_arrays(self, metadata: ParamsMetadata, arrays: list[np.ndarray]) -> None:
        from photon_tpu.codec import params_from_ndarrays

        host_opt = params_from_ndarrays(self.state.opt_state, metadata, arrays)
        # preserve original leaf dtypes (counters are int32; npz round-trips
        # shapes/dtypes so this is a safety cast only for () scalars)
        new_opt = jax.tree.map(
            lambda new, old, sh: jax.device_put(
                np.asarray(new, dtype=old.dtype).reshape(np.shape(old)), sh
            ),
            host_opt,
            self.state.opt_state,
            self._shardings.opt_state,
        )
        self.state = self.state.replace(opt_state=new_opt)

    def _moment_trees(self):
        """Locate (first, second) moment pytrees in the chained opt state
        (AdoptState.m/.v or optax ScaleByAdamState.mu/.nu)."""
        found = {}

        def visit(node):
            if hasattr(node, "m") and hasattr(node, "v"):
                found.setdefault("m1", node.m)
                found.setdefault("m2", node.v)
            elif hasattr(node, "mu") and hasattr(node, "nu"):
                found.setdefault("m1", node.mu)
                found.setdefault("m2", node.nu)
            elif isinstance(node, dict):
                for sub in node.values():
                    visit(sub)
            elif hasattr(node, "inner_states"):  # optax MultiTransformState
                visit(node.inner_states)
            elif hasattr(node, "inner_state"):  # optax MaskedState / wrappers
                visit(node.inner_state)
            elif isinstance(node, (tuple, list)):
                for sub in node:
                    visit(sub)

        visit(self.state.opt_state)
        if "m1" not in found:
            raise RuntimeError("optimizer state carries no recognizable moments")
        return found["m1"], found["m2"]

    @staticmethod
    def _is_masked(leaf) -> bool:
        import optax

        return isinstance(leaf, optax.MaskedNode)

    def get_momenta(self) -> tuple[list[np.ndarray], list[np.ndarray]]:
        """First/second optimizer moments as flat lists in codec order
        (reference momenta export when ``aggregate_momenta``,
        ``clients/utils.py:514-652``). Frozen params (``freeze_patterns`` →
        optax MaskedNode with no state) report zero moments."""
        from photon_tpu.codec import params_to_ndarrays

        m1_tree, m2_tree = self._moment_trees()

        p_leaves, p_def = jax.tree_util.tree_flatten(self.state.params)

        def densify(tree):
            m_leaves = jax.tree_util.tree_flatten(tree, is_leaf=self._is_masked)[0]
            if len(m_leaves) != len(p_leaves):
                raise RuntimeError("moment tree does not mirror the param tree")
            dense = [
                np.zeros(np.shape(p), np.float32) if self._is_masked(m) else m
                for p, m in zip(p_leaves, m_leaves)
            ]
            return jax.tree_util.tree_unflatten(p_def, dense)

        return params_to_ndarrays(densify(m1_tree))[1], params_to_ndarrays(densify(m2_tree))[1]

    def set_momenta(self, m1: list[np.ndarray], m2: list[np.ndarray]) -> None:
        """Inject server-aggregated moments into the live optimizer state
        (reference ``set_optimizer_state``, ``clients/utils.py:257-402``).
        ``m1``/``m2`` are in codec (sorted-name) order; values for frozen
        params (MaskedNode slots) are ignored."""
        from photon_tpu.codec import unflatten_params

        m1_tree, m2_tree = self._moment_trees()
        # codec order → param-tree order
        dense_m1 = jax.tree.leaves(unflatten_params(self.state.params, list(m1)))
        dense_m2 = jax.tree.leaves(unflatten_params(self.state.params, list(m2)))

        def build_value_map(tree, dense):
            leaves = jax.tree_util.tree_flatten(tree, is_leaf=self._is_masked)[0]
            if len(leaves) != len(dense):
                raise RuntimeError("moment tree does not mirror the param tree")
            return {
                id(old): new
                for old, new in zip(leaves, dense)
                if not self._is_masked(old)
            }

        values = build_value_map(m1_tree, dense_m1)
        values.update(build_value_map(m2_tree, dense_m2))

        def replace(leaf, sh):
            new = values.get(id(leaf))
            if new is None:
                return leaf
            return jax.device_put(np.asarray(new, dtype=leaf.dtype).reshape(np.shape(leaf)), sh)

        new_opt = jax.tree.map(replace, self.state.opt_state, self._shardings.opt_state)
        self.state = self.state.replace(opt_state=new_opt)

    def reset_optimizer(self) -> None:
        """Drop optimizer state, keep params/step (reference reset knob:
        ``load_ignore_keys`` optimizer globs, ``clients/utils.py:229-238``)."""
        opt_state = self.tx.init(jax.tree.map(np.asarray, self.state.params))
        opt_state = jax.tree.map(
            lambda leaf, sh: jax.device_put(leaf, sh), opt_state, self._shardings.opt_state
        )
        self.state = self.state.replace(opt_state=opt_state)

    def set_step(self, step: int) -> None:
        """Inject cumulative server steps into the local step counter AND the
        optimizer's internal ``count`` (which drives the lr schedule and
        ADOPT/Adam bias correction) so training continues mid-schedule across
        rounds (reference: ``server_steps_cumulative`` → optimizer step
        injection, ``clients/utils.py:332-341``)."""
        new_opt = _set_opt_count(self.state.opt_state, step)
        self.state = self.state.replace(
            step=jnp.asarray(step, jnp.int32), opt_state=new_opt
        )
