"""ClientRuntime: executes fit/eval tasks for client ids on this host's chips.

Role parity with the reference's Worker + client train entry
(``photon/worker/worker.py:209-293``, ``clients/llm_client_functions.py``):

- ONE persistent :class:`Trainer` reused across rounds and cids — optimizer
  state and jit caches survive (reference ``external_trainer`` reuse,
  ``worker.py:207,254``). TPU-first: no per-GPU process gang; JAX owns every
  chip of the host through one mesh.
- Per-cid data loaders with resumable state (reference: per-client MDS
  streams, ``llm_config_functions.py:388-436``; dataset state resets,
  ``clients/utils.py:177-254``).
- ``server_steps_cumulative`` is injected into the optimizer step counter so
  lr schedule/bias correction continue mid-schedule (``clients/utils.py:332-341``).
- Post-round: pseudo-gradient L2 norm telemetry and client-state bookkeeping
  (``clients/utils.py:514-652``).
- Optional client checkpoints with skip-if-done round resume
  (``llm_config_functions.py:642-764``).
"""

from __future__ import annotations

import math
import pathlib
import time
import zlib
from typing import Any

import numpy as np


def _stable_seed(*parts) -> int:
    """Deterministic across processes/runs (Python ``hash`` is salted per
    process, which would desync spawned node agents)."""
    return zlib.crc32("/".join(str(p) for p in parts).encode()) & 0x7FFFFFFF

from photon_tpu import chaos, telemetry
from photon_tpu.checkpoint.client import ClientCheckpointManager
from photon_tpu.codec import ParamsMetadata
from photon_tpu.config.schema import Config
from photon_tpu.data import LoaderState, ShardedDataset, StreamingLoader, make_synthetic_dataset
from photon_tpu.federation.configs import EvaluateRoundConfig, FitRoundConfig
from photon_tpu.federation.messages import ClientState, EvaluateIns, EvaluateRes, FitIns, FitRes
from photon_tpu.federation.transport import ParamTransport
from photon_tpu.strategy.aggregation import diff_sumsq
from photon_tpu.train.trainer import Trainer
from photon_tpu.utils.profiling import (
    CLIENT_ENCODE_SPAN,
    CLIENT_EVALUATE_SPAN,
    CLIENT_FIT_DELAY_FACTOR,
    CLIENT_FIT_INIT_TIME,
    CLIENT_FIT_SPAN,
    CLIENT_PACKAGE_SPAN,
    CLIENT_PARAM_NORM,
    CLIENT_PSEUDO_GRAD_NORM,
    CLIENT_PSEUDO_GRAD_NORM_SPAN,
    CLIENT_RESOLVE_PARAMS_SPAN,
    CLIENT_SKIPPED_ROUND,
    CLIENT_TRAIN_SPAN,
    TRANSPORT_UNMAP_SPAN,
)


class ClientRuntime:
    def __init__(
        self,
        cfg: Config,
        transport: ParamTransport,
        node_id: str = "node0",
        ckpt_mgr: ClientCheckpointManager | None = None,
        mesh=None,
    ) -> None:
        self.cfg = cfg
        self.transport = transport
        self.node_id = node_id
        self.ckpt_mgr = ckpt_mgr
        # ``mesh`` pins the trainer to specific devices — required under
        # jax.distributed, where the default mesh would span other
        # processes' non-addressable devices (collective_round passes the
        # process-local devices)
        self.trainer = Trainer(cfg, mesh=mesh)
        self._loaders: dict[tuple[int, str], StreamingLoader] = {}
        self._histories: dict[int, Any] = {}  # per-cid metric history
        self._current_params: tuple[ParamsMetadata, list[np.ndarray]] | None = None
        self._personal: dict[int, list[np.ndarray]] = {}  # per-cid personalized layers

    def _history(self, cid: int):
        """Per-cid metric history; wandb runs (when configured) are named
        ``{run_uuid}_client_{cid}`` (reference: per-client run naming,
        ``photon/clients/llm_config_functions.py:767-862``)."""
        if cid not in self._histories:
            from photon_tpu.metrics.history import History, client_run_name, make_wandb_run

            self._histories[cid] = History(
                make_wandb_run(
                    self.cfg.wandb_project, client_run_name(self.cfg.run_uuid, cid)
                )
            )
        return self._histories[cid]

    # -- data ------------------------------------------------------------
    def _loader(self, cid: int, split: str, batch_size: int) -> StreamingLoader:
        key = (cid, split)
        if key not in self._loaders:
            ds_cfg = self.cfg.dataset
            if ds_cfg.synthetic or not ds_cfg.local_path:
                root = pathlib.Path(self.cfg.photon.save_path) / "synthetic" / f"client_{cid}" / split
                if not (root / "index.json").exists():
                    make_synthetic_dataset(
                        str(root),
                        n_samples=max(4 * batch_size, 64),
                        seq_len=self.cfg.model.max_seq_len,
                        vocab_size=self.cfg.model.vocab_size,
                        seed=_stable_seed(cid, split),
                    )
                ds = ShardedDataset(root)
            else:
                # reference stream assignment: streams[cid % n]
                # (``llm_config_functions.py:388-436``); n_streams=0 keeps the
                # 1:1 client_{cid} layout from the conversion pipeline
                stream = cid % ds_cfg.n_streams if ds_cfg.n_streams > 0 else cid
                ds = ShardedDataset(pathlib.Path(ds_cfg.local_path) / f"client_{stream}" / split)
            self._loaders[key] = StreamingLoader(
                ds,
                batch_size=batch_size,
                seed=ds_cfg.shuffle_seed + cid,
                shuffle=ds_cfg.shuffle and split == ds_cfg.split_train,
            )
        return self._loaders[key]

    # -- params ----------------------------------------------------------
    def set_broadcast_params(self, ptr) -> None:
        """Cache the round's global params (reference: NM params shm write,
        ``client_app.py:104-115``). The broadcast doubles as the wire
        codec's delta base: this round's fit results upload as
        ``w_new − w_global`` against exactly these arrays. On the shm plane
        they are read-only views of the server's segment: holding them holds
        its mapping, and letting the previous round's go here is what lets
        its pages go."""
        self._rebind_params(ptr)

    def _rebind_params(self, ptr) -> None:
        """The params behind ``ptr`` in the previous ones' place, here and as
        the codec's delta base. Once the read has returned, the previous arrays
        are dropped under a span of their own: on the shm plane these are the
        last references to the old segment's mapping, and the un-map is 0.4 s
        of a 9 s mpt-125m round (PERF.md section 5)."""
        current = self.transport.get(ptr)
        with telemetry.span(TRANSPORT_UNMAP_SPAN, push=False, mode=ptr.kind):
            self.transport.set_reference(None)
            self._current_params = None
        self._current_params = current
        self.transport.set_reference(current[1])

    def _resolve_params(self, ptr) -> tuple[ParamsMetadata, list[np.ndarray]]:
        if ptr is not None:
            self._rebind_params(ptr)
        if self._current_params is None:
            raise RuntimeError("no parameters: neither FitIns pointer nor prior broadcast")
        return self._current_params

    def _error_with_oom_dump(self, e: Exception, tag: str) -> str:
        """Error string for a failed fit/eval; on OOM, writes the device
        memory profile to save_path and references it (the
        MemorySnapshot/OOMObserver analog, ``trainer_utils.py:721-729``)."""
        from photon_tpu.utils.profiling import dump_memory_profile, is_oom

        dump = dump_memory_profile(self.cfg.photon.save_path, tag) if is_oom(e) else None
        return f"{type(e).__name__}: {e}" + (f" [memory profile: {dump}]" if dump else "")

    # -- fit -------------------------------------------------------------
    def fit(self, ins: FitIns, cid: int) -> FitRes:
        # umbrella span (client/fit — NOT the client/fit_time KPI name,
        # which is the train loop alone): covers init, resolve, train,
        # encode, package, and the failure path, so an errored fit shows
        # its true cost on the timeline.
        with telemetry.span(CLIENT_FIT_SPAN, round=ins.server_round, cid=cid,
                            node=self.node_id):
            return self._fit_guarded(ins, cid)

    def _fit_guarded(self, ins: FitIns, cid: int) -> FitRes:
        t_start = time.monotonic()
        try:
            return self._fit_inner(ins, cid, t_start)
        except Exception as e:  # noqa: BLE001 — worker-level failure isolation
            # reference: exception → error result so the node can retry the
            # cid elsewhere (``worker.py:427-448``); on OOM also dump the
            # device memory profile (MemorySnapshot/OOMObserver analog,
            # ``trainer_utils.py:721-729``)
            return FitRes(
                server_round=ins.server_round, cid=cid, params=None,
                error=self._error_with_oom_dump(e, f"fit_cid{cid}"),
            )

    def _fit_inner(self, ins: FitIns, cid: int, t_start: float) -> FitRes:
        cfg = self.cfg
        # validated per-round knobs: a typo'd key raises (surfaced as an error
        # FitRes) instead of silently no-opping (reference pydantic FitConfig,
        # ``clients/configs.py:55-214``)
        knobs = FitRoundConfig.from_dict(ins.config)
        state_in = ClientState.from_dict(ins.client_states[cid]) if cid in ins.client_states else ClientState(cid)
        target_step = ins.server_steps_cumulative + ins.local_steps

        # skip-if-done: post-round client checkpoint already exists
        if (
            self.ckpt_mgr is not None
            and knobs.client_checkpoints
            and self.ckpt_mgr.should_skip_round(cid, target_step)
        ):
            pm, pa, opt, extra = self.ckpt_mgr.load(cid, target_step)
            return self._package_result(
                ins, cid, state_in, pm, pa,
                n_samples=ins.local_steps * cfg.train.global_batch_size,
                metrics={CLIENT_SKIPPED_ROUND: 1.0},
                t_start=t_start,
            )

        with telemetry.span(CLIENT_RESOLVE_PARAMS_SPAN, cid=cid,
                            round=ins.server_round):
            meta, arrays = self._resolve_params(ins.params)

        # momenta piggybacking: [params|m1|m2] payloads (reference
        # ``manipulate_pre_training_ndarrays``, ``clients/utils.py:405-511``)
        from photon_tpu.train.param_ops import (
            extend_with_momenta,
            has_momenta,
            personalize_layers,
            randomize_layers,
            split_momenta,
        )

        carry_momenta = has_momenta(meta)
        if carry_momenta:
            base_meta, params_in, m1_in, m2_in = split_momenta(meta, arrays)
        else:
            base_meta, params_in, m1_in, m2_in = meta, list(arrays), None, None

        params_touched = bool(knobs.personalize_patterns or knobs.randomize_patterns)
        if knobs.personalize_patterns:
            params_in = personalize_layers(
                base_meta, params_in, self._personal.get(cid), knobs.personalize_patterns
            )
        if knobs.randomize_patterns:
            params_in = randomize_layers(
                base_meta, params_in, knobs.randomize_patterns,
                seed=_stable_seed(cid, ins.server_round),
            )

        self.trainer.set_parameters(base_meta, params_in)
        # ``initial`` exists only to difference the pseudo-grad norm below.
        # When no personalize/randomize knob touched the params, params_in
        # still aliases the cached broadcast arrays — which nothing CAN
        # mutate (read-only views on the shm plane; set_parameters
        # device_puts; fit returns FRESH host arrays) — so the ~full-model
        # copy (~500 MB/client/round at 125M) is skipped and the norm is
        # computed against the held broadcast reference.
        initial = [a.copy() for a in params_in] if params_touched else params_in

        # reset knobs (reference: ``load_ignore_keys`` globs, ``clients/utils.py:219-249``)
        if knobs.reset_optimizer:
            self.trainer.reset_optimizer()
        elif carry_momenta:
            self.trainer.set_momenta(m1_in, m2_in)
        self.trainer.set_step(ins.server_steps_cumulative)

        fresh = (cid, cfg.dataset.split_train) not in self._loaders
        loader = self._loader(cid, cfg.dataset.split_train, cfg.train.global_batch_size)
        if knobs.reset_dataset_state:
            loader.reset()
        elif knobs.loader_state is not None:
            loader.load_state_dict(knobs.loader_state[cid])
        elif fresh and state_in.samples_cumulative > 0:
            # node restart / server resume: a fresh loader fast-forwards to the
            # client's cumulative sample position so the data order matches an
            # uninterrupted run (reference: resumable streaming dataset state,
            # ``clients/utils.py:177-254`` reset_dataset_state semantics)
            loader.skip_samples(state_in.samples_cumulative)

        t_fit0 = time.monotonic()
        # chaos "mid-fit": params are on device, the loader is positioned,
        # the train loop is about to burn steps — dying here loses real work
        # and leaves loader/optimizer state only the re-fit can rebuild
        from photon_tpu.chaos import crash_point

        crash_point("mid-fit", ins.server_round, self.node_id)
        with telemetry.span(CLIENT_TRAIN_SPAN, cid=cid, round=ins.server_round,
                            local_steps=ins.local_steps):
            fit_metrics = self.trainer.fit(
                loader, ins.local_steps, log_every=cfg.train.log_interval
            )
        # reference KPI decomposition (``llm_client_functions.py:161-209``):
        # init = everything before the train loop (knob validation, param
        # resolution, momenta split, personalization, loader build/fast-
        # forward); fit_time = the loop. Trainer.fit itself reports
        # client/fit_set_parameters_time as the device hand-off alone —
        # the runtime must not widen that definition (round-4 review).
        fit_metrics[CLIENT_FIT_INIT_TIME] = t_fit0 - t_start

        out_meta, out_arrays = self.trainer.get_parameters()
        n_samples = ins.local_steps * cfg.train.global_batch_size

        # pseudo-gradient telemetry (reference: ``post_process_client_result``
        # L2 norms, ``clients/utils.py:599-619``)
        pool = self.transport.host_pool
        with telemetry.span(CLIENT_PSEUDO_GRAD_NORM_SPAN, cid=cid,
                            round=ins.server_round, threads=pool.threads):
            delta_sq, out_sq = diff_sumsq(out_arrays, initial, pool)
            fit_metrics[CLIENT_PSEUDO_GRAD_NORM] = math.sqrt(delta_sq)
            fit_metrics[CLIENT_PARAM_NORM] = math.sqrt(out_sq)

        if knobs.personalize_patterns:
            self._personal[cid] = [a.copy() for a in out_arrays]
        if carry_momenta:
            m1_out, m2_out = self.trainer.get_momenta()
            out_meta, out_arrays = extend_with_momenta(out_meta, out_arrays, m1_out, m2_out)

        if self.ckpt_mgr is not None and knobs.client_checkpoints:
            om, oa = self.trainer.get_opt_state_arrays()
            self.ckpt_mgr.save(
                cid, target_step, out_meta, out_arrays, om, oa,
                extra_state={"loader": loader.state_dict()},
            )

        return self._package_result(
            ins, cid, state_in, out_meta, out_arrays, n_samples, fit_metrics, t_start
        )

    def _package_result(
        self,
        ins: FitIns,
        cid: int,
        state_in: ClientState,
        meta: ParamsMetadata,
        arrays: list[np.ndarray],
        n_samples: int,
        metrics: dict[str, float],
        t_start: float,
    ) -> FitRes:
        wall = time.monotonic() - t_start
        inj = chaos.active()
        if inj is not None:
            # chaos fit slowdown (ISSUE 18): report the deterministic
            # per-client factor so the async runner's simulated clock
            # scales this fit's duration by it —
            # heterogeneous-hardware skew without actually sleeping
            f = inj.fit_delay_plan(cid)
            if f != 1.0:
                metrics = {**metrics, CLIENT_FIT_DELAY_FACTOR: f}
        if inj is not None and inj.nan_delta_plan(ins.server_round, cid):
            # chaos numeric poison (ISSUE 10): one NaN element in the
            # client's outgoing delta — the trainer's own arrays are never
            # mutated, only the copy that ships. Downstream, the aggregate
            # norm goes NaN and the health sentinel must flip /statusz.
            poisoned = np.array(arrays[0])
            poisoned.reshape(-1)[:1] = np.nan
            arrays = [poisoned, *arrays[1:]]
        # uplink payloads go through the wire codec when one is configured
        # (delta against this round's broadcast, EF residuals keyed by cid);
        # the encode span covers codec + plane write — the upload leg of the
        # client timeline
        with telemetry.span(CLIENT_ENCODE_SPAN, cid=cid, round=ins.server_round):
            ptr = self.transport.put(
                f"fit-r{ins.server_round}-c{cid}-{self.node_id}", meta, arrays,
                compress=True, key=cid,
            )
        with telemetry.span(CLIENT_PACKAGE_SPAN, cid=cid, round=ins.server_round):
            new_state = ClientState(
                cid=cid,
                steps_cumulative=state_in.steps_cumulative + ins.local_steps,
                samples_cumulative=state_in.samples_cumulative + n_samples,
                last_round=ins.server_round,
                wall_time_s=state_in.wall_time_s + wall,
            )
            metrics = dict(metrics)
            metrics["node_training_time_s"] = wall
            self._history(cid).record(ins.server_round, metrics)
        return FitRes(
            server_round=ins.server_round,
            cid=cid,
            params=ptr,
            n_samples=n_samples,
            metrics=metrics,
            client_state=new_state.to_dict(),
        )

    # -- eval ------------------------------------------------------------
    def evaluate(self, ins: EvaluateIns, cid: int) -> EvaluateRes:
        with telemetry.span(CLIENT_EVALUATE_SPAN, round=ins.server_round,
                            cid=cid, node=self.node_id):
            return self._evaluate_inner(ins, cid)

    def _evaluate_inner(self, ins: EvaluateIns, cid: int) -> EvaluateRes:
        try:
            # validate knobs BEFORE the expensive compute (matches the fit
            # path's fail-fast at the top of _fit_inner)
            eval_knobs = EvaluateRoundConfig.from_dict(ins.config)
            meta, arrays = self._resolve_params(ins.params)
            from photon_tpu.train.param_ops import has_momenta, split_momenta

            if has_momenta(meta):
                meta, arrays, _, _ = split_momenta(meta, arrays)
            self.trainer.set_parameters(meta, arrays)
            cfg = self.cfg
            loader = self._loader(cid, cfg.dataset.split_eval, cfg.train.global_batch_size)
            loader.reset()  # every eval round scores the same fixed window
            n_batches = ins.max_batches or cfg.train.eval_batches
            batches = [next(loader) for _ in range(n_batches)]
            out = self.trainer.evaluate(batches)
            if eval_knobs.use_unigram_metrics:
                uni = self._unigram_metrics(cid, batches, out["eval/loss"])
                if not uni and not eval_knobs.allow_unigram_failures:
                    raise FileNotFoundError(
                        f"unigram freq dict missing for client {cid} and "
                        "allow_unigram_failures is False"
                    )
                out.update(uni)
            return EvaluateRes(
                server_round=ins.server_round,
                cid=cid,
                loss=out["eval/loss"],
                n_samples=int(out["eval/tokens"]),
                metrics=out,
            )
        except Exception as e:  # noqa: BLE001
            return EvaluateRes(
                server_round=ins.server_round, cid=cid,
                error=self._error_with_oom_dump(e, f"eval_cid{cid}"),
            )

    def _unigram_metrics(
        self, cid: int, batches: list[np.ndarray], model_ce: float
    ) -> dict[str, float]:
        """Unigram-normalized eval metrics when the client's freq dict exists
        (reference: unigram metric registration ``trainer_utils.py:278-327``,
        freq-dict fetch/merge ``llm_config_functions.py:971-1109``)."""
        from photon_tpu.data.unigram import FREQ_FILENAME, load_freq_dict
        from photon_tpu.metrics.unigram import unigram_log_probs_from_counts

        if not self.cfg.dataset.local_path:
            return {}
        freq_path = (
            pathlib.Path(self.cfg.dataset.local_path)
            / f"client_{cid}"
            / self.cfg.dataset.split_train
            / FREQ_FILENAME
        )
        if not freq_path.exists():
            return {}
        logp = unigram_log_probs_from_counts(
            load_freq_dict(freq_path), self.cfg.model.vocab_size
        )
        tot, n = 0.0, 0
        for b in batches:
            targets = np.asarray(b)[:, 1:]
            tot += float(-logp[targets].sum())
            n += targets.size
        uni_ce = tot / max(n, 1)
        norm = model_ce - uni_ce
        return {
            "eval/PureUnigramCrossEntropy": uni_ce,
            "eval/UnigramNormalizedLanguageCrossEntropy": norm,
            "eval/UnigramNormalizedPerplexity": float(np.exp(np.clip(norm, -30.0, 30.0))),
        }

    # -- lifecycle -------------------------------------------------------
    def loader_states(self) -> dict[str, Any]:
        return {f"{cid}/{split}": ld.state_dict() for (cid, split), ld in self._loaders.items()}

    def close(self) -> None:
        self.transport.cleanup()
