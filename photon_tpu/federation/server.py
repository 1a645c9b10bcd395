"""ServerApp: the federated round loop.

Role parity with ``photon/server_app.py`` + ``photon/server/fit_utils.py`` /
``evaluate_utils.py`` / ``server_util.py``:

- deterministic client sampling via ``random.Random(sample_seed)``, with
  PRNG fast-forward on resume so the sampled sequence is identical to an
  uninterrupted run (``server_app.py:124,187-193,295``);
- sliding-window scheduling: one outstanding cid per node, refilled as
  replies arrive, replies consumed as a generator (``server_util.py:65-202``);
- streaming aggregation: client tensors are fetched, folded into the running
  average, and freed one at a time (``fit_utils.py:92-217``);
- failure budget: failed cids are retried once on another node; more than
  ``accept_failures_cnt`` failures raises :class:`TooManyFailuresError`
  unless ``ignore_failed_rounds`` (``fit_utils.py:198-210,257-288``);
- round checkpoints + resume (negative indexing) + GC; client-state merge and
  ``server_steps_cumulative`` bookkeeping; round-time KPI metrics under the
  reference's names (BASELINE.md KPI table).
"""

from __future__ import annotations

import pathlib
import random
import time
import uuid as uuid_mod
from collections import deque
from typing import Callable, Iterator

import numpy as np

from photon_tpu import chaos, telemetry
from photon_tpu.analysis.runtime import steady_point
from photon_tpu.checkpoint.server import ServerCheckpointManager
from photon_tpu.codec import ParamsMetadata
from photon_tpu.config.schema import Config
from photon_tpu.federation.driver import Driver
from photon_tpu.federation.membership import LivenessTracker, hello_backoff_total
from photon_tpu.federation.messages import (
    Ack,
    Broadcast,
    EvaluateIns,
    EvaluateRes,
    FitIns,
    FitRes,
)
from photon_tpu.federation.transport import ParamTransport
from photon_tpu.metrics.history import History
from photon_tpu.strategy import dispatch_strategy
from photon_tpu.strategy.base import ClientResult
from photon_tpu.strategy.metrics import GradientNoiseScale
from photon_tpu.utils.hostpool import HostPool
from photon_tpu.utils.profiling import (
    BROADCAST_POST_TIME,
    BROADCAST_PRE_TIME,
    CHECKPOINT_TIME,
    CKPT_ASYNC_WRITE_S,
    CKPT_BARRIER_WAIT_S,
    COMPILES_TOTAL,
    EVAL_ROUND_FAILED,
    EVAL_ROUND_SPAN,
    FIT_ROUND_TIME,
    HBM_BYTES_IN_USE,
    HBM_PEAK_BYTES,
    ROUND_FAILED,
    ROUND_SPAN,
    ROUND_TIME,
    SAMPLE_CLIENTS_SPAN,
    STEPS_CUMULATIVE,
    CLIENT_PSEUDO_GRAD_NORM,
    PSEUDO_GRAD_NORM,
)


class TooManyFailuresError(RuntimeError):
    """Round failure budget exceeded (reference: ``server_util.py:31``)."""


def centralized_warm_start(store, run_uuid: str):
    """Initial global params from another run's centralized checkpoint
    (reference: ``get_centralized_run_parameters``, ``init_utils.py:43-125``).
    Returns ``(metadata, arrays)`` of the latest centralized step."""
    from photon_tpu.centralized import CENTRAL_CID
    from photon_tpu.checkpoint.client import ClientCheckpointManager

    mgr = ClientCheckpointManager(store, run_uuid)
    steps = mgr.steps(CENTRAL_CID)
    if not steps:
        raise FileNotFoundError(f"run {run_uuid!r} has no centralized checkpoints")
    return mgr.load_params_only(CENTRAL_CID, steps[-1])


class ServerApp:
    def __init__(
        self,
        cfg: Config,
        driver: Driver,
        transport: ParamTransport,
        ckpt_mgr: ServerCheckpointManager | None = None,
        history: History | None = None,
        initial_params: tuple[ParamsMetadata, list[np.ndarray]] | None = None,
    ) -> None:
        self.cfg = cfg
        self.driver = driver
        self.transport = transport
        self.ckpt_mgr = ckpt_mgr
        self.history = history or History()
        self.strategy = dispatch_strategy(cfg.fl)
        # ONE bounded pool (``photon.host_threads``) serves the whole host
        # plane: codec per-layer encode/decode, the per-array aggregation
        # fold, and the one-client decode-ahead all draw from it
        self.host_pool = HostPool(cfg.photon.host_threads)
        transport.host_pool = self.host_pool
        self.strategy.host_pool = self.host_pool
        if transport.codec is not None:
            # compressed fit results flow to the strategy UNdecoded; the
            # streaming aggregation dequantizes one client at a time through
            # this hook (the codec's reference is pinned per round by
            # broadcast_parameters). The per-layer decode fans back into the
            # shared pool — safe because aggregation runs at most ONE such
            # blocking lookahead task at a time (see utils/hostpool.py).
            codec = transport.codec
            self.strategy.payload_decoder = (
                lambda p: codec.decode(p, pool=self.host_pool)
            )
        self._wire_snapshot = transport.stats.snapshot()
        # fail fast on a typo'd per-round knob instead of shipping it to
        # every client each round (reference pydantic FitConfig validation,
        # ``clients/configs.py:55-214``)
        from photon_tpu.federation.configs import EvaluateRoundConfig, FitRoundConfig

        FitRoundConfig.from_dict(cfg.fl.fit_config)
        EvaluateRoundConfig.from_dict(cfg.fl.eval_config)
        self.gns = GradientNoiseScale()
        # elastic membership: ping-sweep liveness between rounds + mid-round
        # readmission in the sliding window (ISSUE 3 tentpole); the chaos
        # injector installs process-globally (None when chaos is off, which
        # also clears any injector a previous config left behind)
        mem = cfg.photon.membership
        self.membership = LivenessTracker(
            suspect_after_misses=mem.suspect_after_misses,
            dead_after_misses=mem.dead_after_misses,
            ping_timeout_s=mem.ping_timeout_s,
        )
        crash_fn = None
        if cfg.photon.chaos.enabled and cfg.photon.chaos.crash_phase:
            from photon_tpu.federation.driver import InProcessDriver

            if isinstance(driver, InProcessDriver):
                # in-process nodes ARE the server process: a real crash here
                # would os._exit the whole run with no budget/respawn story.
                # Neuter crash injection (the other fault sites still fire);
                # process-kill scenarios need --multiprocess or TCP nodes.
                import warnings

                warnings.warn(
                    "chaos.crash_phase with the in-process driver would kill "
                    "the server itself — crash injection disabled (use the "
                    "multiprocess or TCP driver for kill scenarios)",
                    stacklevel=2,
                )
                crash_fn = lambda code: None  # noqa: E731
        chaos.install(cfg.photon.chaos, scope="server", crash_fn=crash_fn)
        # telemetry plane (ISSUE 4 tentpole): the server's tracer holds the
        # MERGED timeline — its own round-phase spans plus client spans
        # shipped back on fit/eval results. Events write through to JSONL
        # immediately (they must survive a crash); the Perfetto trace is
        # rendered at end of run. Same install discipline as chaos: a
        # disabled config clears any tracer a previous config left behind.
        tel = cfg.photon.telemetry
        self.telemetry_dir = tel.dir or str(
            pathlib.Path(cfg.photon.save_path) / "telemetry"
        )
        telemetry.install(
            tel,
            scope="server",
            events_path=(
                str(pathlib.Path(self.telemetry_dir) / f"events-{cfg.run_uuid}.jsonl")
                if tel.enabled
                else None
            ),
            # on-demand jax.profiler artifacts land beside trace-{run}.json
            profile_dir=self.telemetry_dir,
        )
        self._prom = None
        self.server_steps_cumulative = 0
        self.client_states: dict[int, dict] = {}
        self.start_round = 1
        self._rng = random.Random(cfg.fl.sample_seed)
        self._rounds_sampled = 0
        self._last_broadcast: Broadcast | None = None

        if initial_params is None:
            from photon_tpu.models.mpt import init_params
            from photon_tpu.codec import params_to_ndarrays

            initial_params = params_to_ndarrays(init_params(cfg.model, seed=cfg.seed))
        self.metadata, params = initial_params
        if cfg.fl.aggregate_momenta:
            # payloads become [params|m1|m2]; the strategies aggregate the
            # momenta sections with the same weighted average (reference:
            # zero momenta appended at init, ``clients/utils.py:739-868``)
            from photon_tpu.train.param_ops import extend_with_momenta, has_momenta

            if not has_momenta(self.metadata):
                self.metadata, params = extend_with_momenta(self.metadata, params)
        self.strategy.initialize(params)

    # ------------------------------------------------------------------
    # resume / checkpoint
    # ------------------------------------------------------------------
    def try_resume(self) -> int | None:
        """Restore from ``cfg.photon.resume_round`` if set; returns the
        restored round (reference: ``init_utils.py:226``, ``s3_utils.py:551-727``)."""
        if self.ckpt_mgr is None or self.cfg.photon.resume_round is None:
            return None
        keys = self.strategy.state_keys
        rnd = self.ckpt_mgr.resolve_resume_round(self.cfg.photon.resume_round, keys)
        metadata, params, strategy_state, server_state = self.ckpt_mgr.load_round(rnd, keys)
        self.metadata = metadata
        self.strategy.initialize(params, strategy_state)
        self.server_steps_cumulative = int(server_state.get("server_steps_cumulative", 0))
        self.client_states = {int(k): v for k, v in server_state.get("client_states", {}).items()}
        self.history = History.from_dict(server_state.get("history", {}), self.history._wandb)
        if "gns" in server_state:
            self.gns.load_state_dict(server_state["gns"])
        # PRNG fast-forward keeps the client-sample sequence identical
        # (reference: ``server_app.py:187-193``)
        consumed = int(server_state.get("rounds_sampled", rnd))
        for _ in range(consumed):
            self._sample_clients()
        self.start_round = rnd + 1
        return rnd

    def save_checkpoint(self, server_round: int) -> None:
        if self.ckpt_mgr is None:
            return
        assert self.strategy.current_parameters is not None
        # the control-state snapshot is built NOW (client_states keeps
        # mutating as later rounds merge results); the tensors themselves
        # are safe to hand to a background writer by reference — strategies
        # rebind, never mutate in place (see save_round_async)
        server_state = {
            "server_steps_cumulative": self.server_steps_cumulative,
            "client_states": dict(self.client_states),
            "history": self.history.to_dict(),
            "rounds_sampled": self._rounds_sampled,
            "gns": self.gns.state_dict(),
            "run_uuid": self.cfg.run_uuid,
            "saved_at": time.time(),
        }
        if self.cfg.photon.async_checkpoint:
            # round N's write overlaps round N+1's broadcast + client fits;
            # barrier at the next save/resume/shutdown (ISSUE 2 tentpole #4)
            self.ckpt_mgr.save_round_async(
                server_round,
                self.metadata,
                self.strategy.current_parameters,
                self.strategy.state_for_checkpoint(),
                server_state,
                cleanup_keep=(self.cfg.photon.keep_checkpoints, self.strategy.state_keys),
            )
            return
        self.ckpt_mgr.save_round(
            server_round,
            self.metadata,
            self.strategy.current_parameters,
            self.strategy.state_for_checkpoint(),
            server_state,
        )
        self.ckpt_mgr.cleanup(self.cfg.photon.keep_checkpoints, self.strategy.state_keys)

    # ------------------------------------------------------------------
    # round mechanics
    # ------------------------------------------------------------------
    def _sample_clients(self) -> list[int]:
        """Sample ``n_clients_per_round`` of ``n_total_clients`` (reference:
        ``random.Random(seed).sample``, ``server_app.py:295``)."""
        self._rounds_sampled += 1
        return sorted(
            self._rng.sample(range(self.cfg.fl.n_total_clients), self.cfg.fl.n_clients_per_round)
        )

    def broadcast_parameters(self, server_round: int,
                             phase: str = BROADCAST_PRE_TIME) -> float:
        """Push current global params to every node; returns elapsed seconds
        (reference: ``broadcast_parameters_to_nodes``, ``broadcast_utils.py:60-201``).
        ``phase`` names the span (``BROADCAST_PRE_TIME`` before the fits,
        ``BROADCAST_POST_TIME`` before a federated eval); its timer is the
        KPI of the same name."""
        with telemetry.span(phase, round=server_round) as sp:
            assert self.strategy.current_parameters is not None
            ptr = self.transport.put(
                f"bcast-r{server_round}-{uuid_mod.uuid4().hex[:8]}",
                self.metadata,
                self.strategy.current_parameters,
            )
            # the broadcast IS the round's delta base — pin it so compressed
            # client results (w_new − w_global) decode against the right arrays
            self.transport.set_reference(self.strategy.current_parameters)
            msg = Broadcast(server_round, ptr)
            acks = self.driver.broadcast(msg, on_stale=self._free_stale_reply)
            for a in acks.values():
                self._ingest_result_telemetry(a)
            # a node dying AT broadcast time is an elasticity event, not a fatal
            # error: it leaves the registry (TCP) or respawns paramless
            # (multiprocess) and the rejoin scan re-broadcasts when it returns.
            # Only a LIVE node rejecting the payload is a real failure.
            bad = [
                nid for nid, a in acks.items()
                if not a.ok and "node died" not in (a.detail or "")
            ]
            if bad:
                raise RuntimeError(f"broadcast failed on nodes {bad}: {[acks[n].detail for n in bad]}")
            # free the PREVIOUS round's segment only now: nodes have MAPPED the
            # new payload (ack'd) and dropped their views of the old one when
            # ``set_broadcast_params`` rebound them. The free removes the old
            # segment's name; its pages go with the last view of it, so one
            # still held anywhere stays valid (reference: Ray GC thread /
            # per-round shm unlink, ``utils.py:73-144``)
            if self._last_broadcast is not None:
                self.transport.free(self._last_broadcast.params)
            self._last_broadcast = msg
        return sp.seconds

    def free_transport(self) -> None:
        """Release the live broadcast segment + any transport leftovers; call
        when the round loop ends."""
        if self._last_broadcast is not None:
            self.transport.free(self._last_broadcast.params)
            self._last_broadcast = None
        self.transport.cleanup()
        self.host_pool.close()

    def _free_stale_reply(self, reply) -> None:
        """Free transport segments carried by a late/stale reply (a FitRes
        arriving after its cid was charged to the budget, or draining during
        the between-rounds ping sweep) so it can't leak shm/objects. The
        reply's piggybacked telemetry is ingested first — a quarantined
        node's late spans are exactly the struggling-node evidence the
        timeline exists to show."""
        for res in (reply if isinstance(reply, list) else [reply]):
            self._ingest_result_telemetry(res)
            ptr = getattr(res, "params", None)
            if ptr is not None:
                self.transport.free(ptr)

    def _membership_round_start(self, server_round: int) -> None:
        """Between-rounds liveness maintenance: register the driver's current
        registry (readmitting reappeared ids) and, on sweep rounds, drive the
        ping sweep that moves silent nodes through suspect → dead."""
        mem = self.cfg.photon.membership
        if (
            mem.enabled
            and mem.ping_interval_rounds
            and server_round % mem.ping_interval_rounds == 0
        ):
            # sweep performs the register_present pass itself
            self.membership.sweep(self.driver, on_stale=self._free_stale_reply)
        else:
            self.membership.register_present(self.driver.node_ids())

    def _membership_metrics(self) -> dict[str, float]:
        return self.membership.round_metrics(
            hello_backoff_s=hello_backoff_total(self.driver.hello_stats())
        )

    def _sliding_window(
        self,
        server_round: int,
        cids: list[int],
        make_ins: Callable[[list[int]], object],
        timeout: float,
    ) -> Iterator[object]:
        """One outstanding cid per node; failed cids retried once elsewhere
        (reference: ``message_collaborative`` + node-side requeue)."""
        queue: deque[int] = deque(cids)
        retried: set[int] = set()
        inflight: dict[int, tuple[str, int]] = {}
        free: deque[str] = deque(self.driver.node_ids())
        failures: list[tuple[int, str]] = []
        # nodes whose request timed out, keyed by the stale message id: they
        # are still chewing on the abandoned request, so they stay OUT of
        # rotation until that stale reply drains (else the next cid lands on
        # a wedged node and times out too, cascading into the budget)
        suspect: dict[int, str] = {}
        # nodes written off as wedged-for-good after a full extra drain
        # window: kept out of the elastic-rejoin scan below until their
        # stale reply finally drains (proof they recovered)
        wedged: set[str] = set()
        # mids already consumed this window: a chaos-duplicated reply frame
        # carries the SAME ParamPointer as the copy the aggregation is
        # decoding — it must be dropped, never "freed" out from under the
        # decode-ahead pipeline
        consumed: set[int] = set()

        while queue or inflight:
            # elastic membership: a node id the driver lists but no
            # scheduling structure tracks just (re)joined mid-round — a TCP
            # re-HELLO after crash/redial, or a brand-new registration. It
            # has no round params, so re-send the current broadcast (its ack
            # drains through the stale-mid guard; socket ordering puts it
            # before any FitIns we schedule next) and put it in rotation
            # (generalizes the respawn re-send below to every join path).
            tracked = set(free)
            tracked.update(n for n, _ in inflight.values())
            tracked.update(suspect.values())
            tracked.update(wedged)
            for nid in self.driver.node_ids():
                if nid not in tracked:
                    if self._last_broadcast is not None:
                        self.driver.send(nid, self._last_broadcast)
                    free.append(nid)
                    if nid in self.membership.nodes:
                        # a KNOWN node came back — that's a readmission; a
                        # brand-new registration joining mid-round is
                        # scale-up, not churn, and must not inflate the KPI
                        self.membership.note_readmitted(nid)
                    else:
                        self.membership.touch(nid)
            while queue and free:
                nid, cid = free.popleft(), queue.popleft()
                mid = self.driver.send(nid, make_ins([cid]))
                inflight[mid] = (nid, cid)
            if not inflight and not suspect:
                # every node died: the remaining cids can never be scheduled —
                # count them against the failure budget instead of spinning
                failures.extend((cid, "no live nodes") for cid in queue)
                queue.clear()
                break
            try:
                nid, mid, reply = self.driver.recv_any(timeout=timeout)
            except TimeoutError:
                # stalled work (ADVICE r1 / VERDICT r2 weak #5, ADVICE r3):
                # the timed-out cids go through the same retried-once path as
                # error replies, and their nodes are quarantined in `suspect`
                # — a node still processing an abandoned request would only
                # time out the next cid too
                live = set(self.driver.node_ids())
                for mid, (n, cid) in inflight.items():
                    if cid not in retried and live:
                        retried.add(cid)
                        queue.append(cid)
                    else:
                        failures.append((cid, f"timeout after {timeout}s on node {n}"))
                    if n in live:
                        suspect[mid] = n
                if not inflight and suspect:
                    # this timeout was a pure drain-wait on quarantined nodes
                    # that still haven't replied after a whole extra window —
                    # consider them wedged for good and stop waiting on them
                    # (the `wedged` set keeps the rejoin scan from cycling
                    # them straight back into rotation)
                    wedged.update(suspect.values())
                    suspect.clear()
                inflight.clear()
                if not free and queue and not suspect:
                    # no node can ever pick the retries up
                    failures.extend((cid, "no live nodes") for cid in queue)
                    queue.clear()
                continue
            if mid not in inflight:
                if mid in consumed:
                    # duplicate delivery of an already-processed reply: the
                    # first copy owns the segment lifecycle — drop, don't free
                    continue
                # stale correlation id (e.g. a FitRes arriving after its cid
                # was charged to the budget on timeout): free any transport
                # segment it carries so late replies don't leak shm/objects,
                # and return the now-drained node to rotation. Mark the mid
                # consumed FIRST — a chaos-duplicated copy of this same
                # frame must not free the tag a second time (the retried
                # cid may have rewritten it by then)
                consumed.add(mid)
                self._free_stale_reply(reply)
                drained = suspect.pop(mid, None)
                if drained is None and nid in wedged:
                    # a written-off node finally answered: it recovered
                    wedged.discard(nid)
                    drained = nid
                if drained is not None and drained in self.driver.node_ids():
                    stale_died = any(
                        isinstance(r, Ack) and "node died" in (r.detail or "")
                        for r in (reply if isinstance(reply, list) else [reply])
                    )
                    if stale_died and self._last_broadcast is not None:
                        # the drain was a re-HELLO dead-letter, not a real
                        # late reply: the restarted process has no round
                        # params — re-send before the next cid lands there
                        self.driver.send(drained, self._last_broadcast)
                        self.membership.note_readmitted(drained)
                    free.append(drained)
                continue
            _, cid = inflight.pop(mid)
            consumed.add(mid)
            replies = reply if isinstance(reply, list) else [reply]
            node_died = any(
                isinstance(res, Ack) and "node died" in (res.detail or "") for res in replies
            )
            if node_died and nid in self.driver.node_ids():
                # respawned under the same id (MultiprocessDriver restart, or
                # a TCP re-HELLO whose stale requests were dead-lettered): it
                # has no round params — re-send the broadcast before any
                # retry lands there (its ack is drained by the `mid not in
                # inflight` guard above), then keep scheduling onto it
                if self._last_broadcast is not None:
                    self.driver.send(nid, self._last_broadcast)
                free.append(nid)
                self.membership.note_readmitted(nid)
            elif not node_died:
                free.append(nid)
            # else: node is gone for good (TCP driver) — drop it from rotation
            for res in replies:
                err = res.detail if isinstance(res, Ack) else getattr(res, "error", None)
                if isinstance(res, Ack) or err:
                    if (
                        err
                        and "no parameters" in err
                        and nid in self.driver.node_ids()
                        and self._last_broadcast is not None
                    ):
                        # an externally-restarted node re-HELLO'd under its
                        # old id: the socket came back but the process lost
                        # the round broadcast — re-send it so the next cid
                        # scheduled there can actually run
                        self.driver.send(nid, self._last_broadcast)
                    if cid not in retried and len(self.driver.node_ids()) > 0:
                        retried.add(cid)
                        queue.append(cid)
                    else:
                        failures.append((cid, err or "unknown"))
                    continue
                yield res

        if failures:
            if len(failures) > self.cfg.fl.accept_failures_cnt:
                raise TooManyFailuresError(
                    f"round {server_round}: {len(failures)} failures "
                    f"(budget {self.cfg.fl.accept_failures_cnt}): {failures}"
                )

    # ------------------------------------------------------------------
    # rounds
    # ------------------------------------------------------------------
    def fit_round(self, server_round: int) -> dict[str, float]:
        t_round = time.monotonic()
        with telemetry.span(SAMPLE_CLIENTS_SPAN, round=server_round):
            cids = self._sample_clients()
        local_steps = self.cfg.fl.local_steps

        def make_ins(cid_batch: list[int]) -> FitIns:
            return FitIns(
                server_round=server_round,
                cids=cid_batch,
                params=None,  # nodes use the round's broadcast
                local_steps=local_steps,
                server_steps_cumulative=self.server_steps_cumulative,
                client_states={c: self.client_states[c] for c in cid_batch if c in self.client_states},
                config=dict(self.cfg.fl.fit_config),
            )

        per_client_sq: list[float] = []
        per_client_n: list[int] = []

        def results() -> Iterator[ClientResult]:
            for res in self._sliding_window(server_round, cids, make_ins, timeout=self.cfg.fl.fit_timeout_s):
                assert isinstance(res, FitRes)
                # merge piggybacked client telemetry into the server-held
                # timeline/event log (None fields when telemetry is off)
                self._ingest_result_telemetry(res)
                # decode=False: compressed payloads stay compressed until the
                # streaming aggregation folds them in, one client at a time
                _, arrays = self.transport.get(res.params, decode=False)
                if res.client_state:
                    self.client_states[res.cid] = res.client_state
                g = res.metrics.get(CLIENT_PSEUDO_GRAD_NORM)
                if g is not None:
                    per_client_sq.append(float(g) ** 2)
                    per_client_n.append(res.n_samples)
                yield ClientResult(res.cid, arrays, res.n_samples, res.metrics)
                self.transport.free(res.params)

        # the fit-wait span covers scheduling + client fits + streaming
        # aggregation; its timer is the fit_round_time KPI
        with telemetry.span(FIT_ROUND_TIME, round=server_round,
                            n_cids=len(cids)) as sp:
            new_params, metrics = self.strategy.aggregate_fit(server_round, results())
        metrics[FIT_ROUND_TIME] = sp.seconds
        del new_params  # strategy.current_parameters already updated

        agg_sq = metrics.get(PSEUDO_GRAD_NORM, 0.0) ** 2
        metrics.update(self.gns.update(per_client_sq, per_client_n, agg_sq, sum(per_client_n)))

        self.server_steps_cumulative += local_steps
        metrics[STEPS_CUMULATIVE] = float(self.server_steps_cumulative)
        metrics[ROUND_TIME] = time.monotonic() - t_round
        # bytes-on-wire: drain-since-last-fit semantics — every byte is
        # counted exactly once (a post-fit eval broadcast lands in the NEXT
        # round's numbers), so History.cumulative over the wire keys is the
        # exact run total
        metrics.update(self.transport.stats.metrics_since(self._wire_snapshot))
        self._wire_snapshot = self.transport.stats.snapshot()
        return metrics

    def evaluate_round(self, server_round: int) -> dict[str, float]:
        """Federated eval over all clients (reference: ``evaluate_round``,
        ``evaluate_utils.py:232``; evaluates every client, not a sample)."""
        cids = list(range(self.cfg.fl.n_total_clients))

        def make_ins(cid_batch: list[int]) -> EvaluateIns:
            return EvaluateIns(
                server_round=server_round,
                cids=cid_batch,
                params=None,
                max_batches=self.cfg.train.eval_batches,
                config=dict(self.cfg.fl.eval_config),
            )

        results = []
        with telemetry.span(EVAL_ROUND_SPAN, round=server_round):
            for res in self._sliding_window(server_round, cids, make_ins, timeout=self.cfg.fl.eval_timeout_s):
                assert isinstance(res, EvaluateRes)
                self._ingest_result_telemetry(res)
                results.append((res.n_samples, res.loss, res.metrics))
        loss, metrics = self.strategy.aggregate_evaluate(server_round, results)
        return metrics

    @staticmethod
    def _ingest_result_telemetry(res) -> None:
        """Fold a reply's piggybacked spans/events (FitRes, EvaluateRes, or
        Ack) into the server-held merged timeline (a None check per reply
        when telemetry is off)."""
        telemetry.ingest(getattr(res, "spans", None), getattr(res, "events", None))

    # ------------------------------------------------------------------
    def run(self, n_rounds: int | None = None) -> History:
        """The full driver loop (reference: ``server_app.main`` round loop,
        ``server_app.py:279-405``)."""
        cfg = self.cfg
        n_rounds = n_rounds if n_rounds is not None else cfg.fl.n_rounds
        resumed = self.try_resume()
        if resumed is None and self.ckpt_mgr is not None and cfg.photon.restore_run_uuid:
            self.ckpt_mgr.import_run(cfg.photon.restore_run_uuid, self.strategy.state_keys)
            self.cfg.photon.resume_round = -1
            resumed = self.try_resume()
        if resumed is None and self.ckpt_mgr is not None and cfg.photon.checkpoint:
            self.save_checkpoint(0)  # round-0 checkpoint (reference: initialize_round)

        # optional Prometheus /metrics + /statusz + /debug/profile endpoint
        # over the live History + observatory (photon.telemetry.prom_port;
        # stdlib HTTP, no dependency)
        if cfg.photon.telemetry.enabled and cfg.photon.telemetry.prom_port:
            from photon_tpu.telemetry.prom import PromServer

            self._prom = PromServer(
                self.history, cfg.photon.telemetry.prom_port,
                hub=telemetry.metrics_active(),
                health=telemetry.health_active(),
                profiler=telemetry.profiler_active(),
            )
            self._prom.start()
        # photon.telemetry.profile_rounds: arm the on-demand controller so
        # the capture covers the FIRST N rounds (startup compile + steady
        # state — the window the pjit-scaling playbook says to look at)
        prof = telemetry.profiler_active()
        if prof is not None and cfg.photon.telemetry.profile_rounds > 0:
            from photon_tpu.telemetry.introspect import ProfileBusyError

            try:
                prof.request(cfg.photon.telemetry.profile_rounds, tag="startup")
            except ProfileBusyError:
                import warnings

                warnings.warn(
                    "telemetry.profile_rounds: a capture is already armed — "
                    "skipping the startup profile",
                    stacklevel=2,
                )

        if cfg.fl.eval_interval_rounds and self.start_round == 1:
            t_pre = self.broadcast_parameters(0)
            try:
                m = self.evaluate_round(0)
            except TooManyFailuresError:
                if not cfg.fl.ignore_failed_rounds:
                    raise
                m = {EVAL_ROUND_FAILED: 1.0}
            m[BROADCAST_PRE_TIME] = t_pre
            self.history.record(0, m)

        try:
            self._round_loop(cfg, n_rounds)
        finally:
            # shutdown barrier: the last round's background checkpoint write
            # must land (and surface any error) before the loop returns —
            # but a failed write must not leak the transport's shm segments
            # or the pool, so free_transport runs regardless
            try:
                if self.ckpt_mgr is not None:
                    self.ckpt_mgr.wait_pending()
            finally:
                self.free_transport()
                try:
                    self.export_telemetry()
                except Exception:  # noqa: BLE001 — the trace must never take
                    # the run down with it (nor mask the real error): a full
                    # disk or unwritable dir costs the timeline, not History
                    import warnings

                    warnings.warn("telemetry trace export failed", stacklevel=2)
        return self.history

    def export_telemetry(self) -> str | None:
        """Render the merged Perfetto/Chrome trace (server + ingested client
        spans, events as instant markers) and stop the /metrics endpoint.
        Returns the trace path, or None when telemetry is off. Idempotent —
        the round loop calls it at shutdown; tests may call it directly."""
        if self._prom is not None:
            self._prom.close()
            self._prom = None
        prof = telemetry.profiler_active()
        if prof is not None:
            # a capture armed for more rounds than the run had must still
            # flush its artifact (stop_trace) — the trailing profile_tick
            # only closes an exactly-full window
            prof.close()
        tr = telemetry.active()
        if tr is None:
            return None
        from photon_tpu.telemetry.export import write_chrome_trace

        log = telemetry.events_active()
        path = pathlib.Path(self.telemetry_dir) / f"trace-{self.cfg.run_uuid}.json"
        return write_chrome_trace(
            path,
            tr.snapshot(),
            events=log.snapshot() if log is not None else None,
            metadata={
                "run_uuid": self.cfg.run_uuid,
                "dropped_spans": tr.dropped,
            },
        )

    def _round_loop(self, cfg: Config, n_rounds: int) -> None:
        for rnd in range(self.start_round, n_rounds + 1):
            self.run_round(rnd)
        # close an armed-for-more-rounds-than-the-run-had capture cleanly
        telemetry.profile_tick(ROUND_SPAN)

    def run_round(self, rnd: int) -> None:
        """One whole round, as :meth:`run`'s loop runs it: broadcast, fits,
        aggregation, server update, eval and checkpoint where due, the
        round's History line. A caller that drives rounds itself owns what
        :meth:`run` does around the loop: the round-0 checkpoint before the
        first round, and ``ckpt_mgr.wait_pending()`` + ``free_transport()``
        after the last."""
        # on-demand profiling unit boundary (telemetry/introspect.py):
        # an armed capture starts at the next round start and stops N
        # round starts later — one None check when nothing is armed
        telemetry.profile_tick(ROUND_SPAN)
        # one umbrella span per round (server/round — NOT the
        # round_time KPI name, which measures a narrower window): every
        # phase span below — and, via Envelope.trace, every client-side
        # fit/eval span — parents under it in the merged timeline
        with telemetry.span(ROUND_SPAN, round=rnd):
            self._one_round(self.cfg, rnd)
        # retrace-sentinel hook (analysis/runtime.py): a None check
        # when disabled; under the e2e fixture a steady-state round
        # that recompiles is billed to its round boundary
        steady_point(ROUND_SPAN)

    def _one_round(self, cfg: Config, rnd: int) -> None:
        if cfg.photon.refresh_period and rnd > 1 and (rnd - 1) % cfg.photon.refresh_period == 0:
            from photon_tpu.federation.messages import Query

            self.driver.broadcast(Query("refresh"), on_stale=self._free_stale_reply)
        # liveness sweep BEFORE the broadcast: readmitted nodes are back
        # in the registry when broadcast_parameters fans out, so a
        # crash-and-rejoin between rounds needs no special re-send
        self._membership_round_start(rnd)
        t_pre = self.broadcast_parameters(rnd)
        try:
            metrics = self.fit_round(rnd)
        except TooManyFailuresError:
            if not cfg.fl.ignore_failed_rounds:
                raise
            failed = {ROUND_FAILED: 1.0}
            failed.update(self._membership_metrics())
            self._observe_round_health(rnd, failed)
            self.history.record(rnd, failed)
            return
        metrics[BROADCAST_PRE_TIME] = t_pre
        metrics.update(self._membership_metrics())

        if cfg.fl.eval_interval_rounds and rnd % cfg.fl.eval_interval_rounds == 0:
            t_post = self.broadcast_parameters(rnd, BROADCAST_POST_TIME)
            try:
                metrics.update(self.evaluate_round(rnd))
            except TooManyFailuresError:
                # one flaky client during fed eval must not kill a
                # failure-tolerant run (reference: evaluate_round sits
                # inside the ignore_failed_rounds wrap, ``fit_utils.py``)
                if not cfg.fl.ignore_failed_rounds:
                    raise
                metrics[EVAL_ROUND_FAILED] = 1.0
            metrics[BROADCAST_POST_TIME] = t_post

        if (
            self.ckpt_mgr is not None
            and cfg.photon.checkpoint
            and rnd % cfg.photon.checkpoint_interval == 0
        ):
            # the span covers only what the round loop BLOCKS on (snapshot +
            # enqueue + barrier); the background write itself renders as a
            # separate ckpt_async_write_s span overlapping the next round
            with telemetry.span(CHECKPOINT_TIME, round=rnd) as sp:
                self.save_checkpoint(rnd)
            # checkpoint_time (the span's own timer) = what the round loop
            # was BLOCKED on: snapshot + enqueue, plus — when the store is
            # slower than a round — the barrier wait for round N-1's write,
            # reported separately below so slow-store regimes are visible.
            # The write itself overlaps the next round and reports as
            # CKPT_ASYNC_WRITE_S one round later.
            metrics[CHECKPOINT_TIME] = sp.seconds
            metrics[CKPT_ASYNC_WRITE_S] = float(self.ckpt_mgr.last_async_write_s)
            if self.cfg.photon.async_checkpoint:
                metrics[CKPT_BARRIER_WAIT_S] = float(
                    self.ckpt_mgr.last_barrier_wait_s
                )

        self._observe_round_health(rnd, metrics)
        self.history.record(rnd, metrics)

    def _observe_round_health(self, rnd: int, metrics: dict) -> None:
        """Run-health observatory hooks at the round boundary (ISSUE 10):
        round-phase timings into typed histograms, HBM live/peak + backend
        compile count sampled into the metrics dict AND the hub (program-
        cache misses and memory growth become scrapeable KPIs), then the
        NaN/Inf health sentinel over the assembled dict. One None check per
        plane when telemetry is off."""
        hub = telemetry.metrics_active()
        if hub is not None:
            from photon_tpu.telemetry.introspect import sample_device_plane

            for key in (ROUND_TIME, FIT_ROUND_TIME, BROADCAST_PRE_TIME,
                        CHECKPOINT_TIME):
                v = metrics.get(key)
                if v is not None:
                    hub.histogram(key).observe(float(v))
            sample_device_plane(
                metrics, hub, hbm_key=HBM_BYTES_IN_USE,
                peak_key=HBM_PEAK_BYTES, compiles_key=COMPILES_TOTAL,
            )
        health = telemetry.health_active()
        if health is not None:
            health.check_round_metrics(rnd, metrics)
            hbm = metrics.get(HBM_BYTES_IN_USE)
            if hbm is not None:
                health.note_hbm_sample(hbm)
