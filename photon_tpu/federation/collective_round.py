"""Federated rounds over XLA collectives — the TPU-native comm stack.

Consumer of ``photon.comm_stack.collective`` (SURVEY §7 stage 6, the marquee
path): where the driver topology moves every client's parameters through a
pointer plane (shm / objstore) and averages on the server host
(``strategy/aggregation.py``), slices that are part of one
``jax.distributed`` job aggregate over a hierarchical ``(clients, replica)``
mesh (``parallel/collective_agg.py``) — intra-slice over ICI, cross-slice
over DCN, optionally int8-quantized on the DCN leg
(``comm_stack.collective_quantization``), with the server optimizer fused
into the same SPMD program when ``collective_device_optimizer`` is on — no
host round-trip, no object store; the replicated result doubles as the next
round's broadcast (reference upload/download + broadcast:
``s3_utils.py:730-1115``, ``broadcast_utils.py:60-201``).

Topology: multi-controller SPMD. Every process runs THIS SAME loop over its
local clients; there is no server process. Each controller holds a replica
of the strategy and applies the identical deterministic update
(``Strategy.apply_average``) to the psum'd average, so all replicas march in
lockstep.

**Elastic rounds (ISSUE 8).** The classic NCCL-gang tradeoff — bandwidth
for elasticity — used to make client failures here fatal. The runner now
buys the elasticity back with a straggler/degradation ladder:

1. **Stage deadlines** — every collective stage (context handshake/stack,
   exchange, update) gets an absolute deadline derived from
   ``comm_stack.collective_stage_timeout_s`` on an injectable clock, so a
   dead or byte-dripping participant can never wedge the round (0 keeps
   the original wedge-forever semantics).
2. **Gang reconfiguration** — a failed client fit or a
   :class:`~photon_tpu.federation.membership.LivenessTracker`
   live→suspect/dead edge drops the participant from the round's cohort;
   the runner rebuilds the (clients, replica) mesh over the survivors,
   re-stacks, and re-runs the fold with FedAvg weights renormalized over
   the surviving sample counts (the weighted average divides by the
   cohort's Σn, so renormalization is by construction). A missed stage
   deadline fails the *attempt*: the retry runs over the then-current
   surviving cohort — shrunk only if the liveness plane has ruled someone
   out in the meantime, because a deadline alone cannot attribute the
   wedge to a participant — bounded by the retry budget before degrading.
   Cohort meshes and their programs are cached (bounded LRU), and a
   legitimate first-time reconfiguration compile is budgeted against the
   PR 6 retrace sentinel via ``absorb_compiles``. Reconfiguration is
   **round-scoped**: every round starts from the full cohort again, so a
   readmitted client is back at full strength the round after it returns
   (it never "rejoins a torn gang").
3. **Quorum + host fallback** — below ``comm_stack.collective_quorum``
   (surviving fraction of ``fl.n_total_clients``), or once
   ``collective_retry_budget`` reconfiguration attempts are exhausted, the
   round degrades to the bit-exact host-plane ``aggregate_inplace`` fold
   (PR 2) over whichever deltas landed — recorded as a degraded round
   (``server/collective_degraded_rounds``), never an aborted run.

Cohort agreement caveat (multi-controller): the cohort is computed from
this controller's local observations (fit results + liveness states). All
controllers of one gang must observe the same cohort to stay in lockstep —
feed every controller's tracker from a shared control plane (e.g. the TCP
driver's ping sweep). A divergent cohort wedges the exchange, which the
stage deadline converts into a local host fallback; single-controller runs
(one process, many local clients) are consistent by construction.

Client training itself reuses ``ClientRuntime`` end to end (persistent
Trainer, per-cid loaders, reset knobs, step injection), so data order and
numerics match the driver path exactly — asserted by
``tests/test_collective_round.py``.

Launch (one line per host/slice, mirroring the reference's multi-node flow
``scripts/fed_125m_example.sh:104-137``):

    python -m photon_tpu.federation.collective_round \
        --coordinator host0:1234 --num-processes 2 --process-id {0,1} \
        --config /shared/run/config.yaml
"""

from __future__ import annotations

import threading
import time
import warnings
from typing import Callable, Sequence

import jax
import numpy as np

from photon_tpu import telemetry
from photon_tpu.analysis.runtime import absorb_compiles, steady_point
from photon_tpu.chaos import crash_point
from photon_tpu.codec import params_to_ndarrays
from photon_tpu.compression.quantize import (
    COLLECTIVE_QUANTIZATIONS,
    DEFAULT_BLOCK,
)
from photon_tpu.config.schema import Config
from photon_tpu.federation.client_runtime import ClientRuntime
from photon_tpu.federation.membership import LIVE, LivenessTracker
from photon_tpu.federation.messages import FitIns
from photon_tpu.utils.profiling import (
    ADAPTER_COHORTS,
    ADAPTER_COHORTS_DEGRADED,
    ADAPTER_WIRE_BYTES,
    AUTOPILOT_KNOB_QUANT_LEVEL,
    AUTOPILOT_KNOB_STAGE_TIMEOUT_S,
    COLLECTIVE_AGG_TIME,
    COLLECTIVE_DEGRADED_ROUNDS,
    COLLECTIVE_EXCHANGE_TIME,
    COLLECTIVE_RECONFIG_TIME,
    COLLECTIVE_STACK_TIME,
    COLLECTIVE_STRAGGLER_FRAC,
    COLLECTIVE_STRAGGLERS,
    COLLECTIVE_UPDATE_TIME,
    COLLECTIVE_WIRE_BYTES,
    EVAL_LOSS,
    EVAL_SAMPLES,
    EVENT_ADAPTER_COHORT_DEGRADED,
    EVENT_COLLECTIVE_DEGRADED,
    EVENT_COLLECTIVE_RECONFIG,
    EVENT_COLLECTIVE_STRAGGLER,
    FIT_ROUND_TIME,
    HBM_BYTES_IN_USE,
    HBM_PEAK_BYTES,
    COMPILES_TOTAL,
    LAYOUT_EST_STEP_S,
    LAYOUT_SEARCH_TIME,
    OPT_ALLGATHER_TIME,
    OPT_SHARD_FRAC,
    ROUND_FAILED,
    ROUND_TIME,
    STEPS_CUMULATIVE,
)
from photon_tpu.federation.transport import ParamTransport
from photon_tpu.metrics.history import History
from photon_tpu.parallel.collective_agg import (
    CLIENT_AXIS,
    DeviceAggregationPlane,
    evict_mesh_programs,
    hierarchical_weighted_average,
    make_hierarchical_mesh,
    mesh_replica,
    modeled_cross_slice_bytes,
)
from photon_tpu.strategy import dispatch_strategy


class StageDeadlineError(RuntimeError):
    """A collective stage missed its absolute deadline
    (``comm_stack.collective_stage_timeout_s``). The stage's work may still
    be running on its (daemon) worker thread — the wedged collective cannot
    be cancelled from Python — but the round moves on through the
    reconfiguration ladder instead of wedging with it."""

    def __init__(self, stage: str, waited_s: float) -> None:
        super().__init__(
            f"collective stage {stage!r} missed its deadline "
            f"(waited {waited_s:.3f}s)"
        )
        self.stage = stage
        self.waited_s = waited_s


def partition_cids(n_total_clients: int, num_processes: int, process_id: int) -> list[int]:
    """Contiguous, process-ordered cid partition. The order is load-bearing:
    global stacked row ``i`` must live on the i-th device of the client mesh,
    and mesh devices enumerate process 0's devices first."""
    per = n_total_clients // num_processes
    rem = n_total_clients % num_processes
    start = process_id * per + min(process_id, rem)
    count = per + (1 if process_id < rem else 0)
    return list(range(start, start + count))


class CollectiveFedRunner:
    """Multi-controller federated loop: local fits → psum average → replica
    strategy update, every round, on every process.

    Launch assumption: ONE chip per process (the standard TPU multi-controller
    shape). The client trainer is pinned to ``jax.local_devices()[0]``; on a
    multi-chip-per-process slice the extra local chips would only hold psum
    rows while fits run serially on chip 0 — launch one process per chip
    instead (e.g. ``--num_processes == slice chip count``)."""

    def __init__(
        self,
        cfg: Config,
        process_cids: Sequence[int],
        mesh=None,
        clock: Callable[[], float] = time.monotonic,
        liveness: LivenessTracker | None = None,
    ) -> None:
        if not cfg.photon.comm_stack.collective:
            raise ValueError("CollectiveFedRunner requires photon.comm_stack.collective=true")
        if cfg.fl.n_clients_per_round != cfg.fl.n_total_clients:
            # lockstep psum = full participation by construction; a sampled
            # subset is the driver topology's feature. Fail loudly instead of
            # silently training more clients than the config states.
            raise ValueError(
                f"collective mode trains ALL clients every round; "
                f"n_clients_per_round={cfg.fl.n_clients_per_round} != "
                f"n_total_clients={cfg.fl.n_total_clients} (use the driver "
                "topology for client sampling)"
            )
        self.cfg = cfg
        self.process_cids = list(process_cids)
        self._local_cids = frozenset(self.process_cids)
        if not self.process_cids:
            raise ValueError(
                "this process owns no clients — launch with num_processes <= "
                "n_total_clients so every controller contributes psum rows"
            )
        cs = cfg.photon.comm_stack
        self.quantization = cs.collective_quantization
        self.q8_block = cs.collective_q8_block or DEFAULT_BLOCK
        #: injectable clock (the PR 3 backoff-test pattern): all stage
        #: deadlines are absolute times on THIS clock, so deadline
        #: bookkeeping is unit-testable without sleeping
        self.clock = clock
        self.stage_timeout_s = float(cs.collective_stage_timeout_s)
        self.quorum = float(cs.collective_quorum)
        self.retry_budget = int(cs.collective_retry_budget)
        # per-cohort LoRA personalization (ISSUE 13): derive the trainer-
        # side knobs from photon.adapters BEFORE any Trainer/model is
        # built (the ClientRuntime below constructs the lora-enabled
        # model; the optimizer freezes every non-adapter param)
        self._adapters_enabled = bool(cfg.photon.adapters.enabled)
        if self._adapters_enabled:
            from photon_tpu.adapters.federated import configure_adapter_training

            configure_adapter_training(cfg)
        mem = cfg.photon.membership
        #: per-client liveness state machine (pseudo node id ``client{cid}``):
        #: fed by fit outcomes here, and — multi-controller — by whatever
        #: shared control plane the operator wires in. A client whose state
        #: is not LIVE is excluded from the round's cohort.
        self.liveness = liveness if liveness is not None else LivenessTracker(
            suspect_after_misses=mem.suspect_after_misses,
            dead_after_misses=mem.dead_after_misses,
            ping_timeout_s=mem.ping_timeout_s,
            clock=clock,
        )
        # elasticity bookkeeping (ISSUE 8)
        self.stragglers_total = 0
        self.degraded_rounds_total = 0
        self.reconfigs_total = 0
        #: round → which aggregation path produced it ("collective" |
        #: "collective_reconfigured" | "host_fallback" | "failed"); rides
        #: the control-state checkpoint so resume knows each round's lineage
        self.aggregation_paths: dict[int, str] = {}
        self._cohort_meshes: dict[tuple[int, ...], object] = {}
        #: deadline-abandoned stage workers that may still be running (their
        #: XLA compile events land whenever they land — absorbed, not billed)
        self._abandoned_workers: list[threading.Thread] = []
        self.mesh = mesh if mesh is not None else self._default_mesh()
        # inline transport: params never leave this process except via psum
        self.transport = ParamTransport("inline")
        from photon_tpu.parallel.mesh import single_device_mesh

        # the client trainer must live on THIS process's devices only —
        # jax.devices() is global under jax.distributed
        self.runtime = ClientRuntime(
            cfg,
            self.transport,
            node_id=f"collective{jax.process_index()}",
            mesh=single_device_mesh(jax.local_devices()[0]),
        )
        self.strategy = dispatch_strategy(cfg.fl)
        from photon_tpu.models.mpt import init_params

        self.meta, initial = params_to_ndarrays(init_params(cfg.model, seed=cfg.seed))
        if cfg.fl.aggregate_momenta:
            # payloads become [params|m1|m2] exactly as in the driver
            # topology (ServerApp init): clients key off has_momenta(meta),
            # the psum averages the momenta sections like any other arrays,
            # and apply_average's length check keeps the replicas honest
            from photon_tpu.train.param_ops import extend_with_momenta, has_momenta

            if not has_momenta(self.meta):
                self.meta, initial = extend_with_momenta(self.meta, initial)
        self.strategy.initialize(initial)
        # adapter mode: split the (base + fresh lora) init payload — the
        # base is FROZEN for the whole run and broadcast per cohort with
        # that cohort's adapter; per-cohort server optimizers live on the
        # AdapterTrainPlane (host — adapter payloads are ~1000x smaller
        # than the model, so the host update is noise next to the fits)
        self.adapter_plane = None
        if self._adapters_enabled:
            from photon_tpu.adapters.federated import AdapterTrainPlane
            from photon_tpu.adapters.lora import split_adapter

            base_meta, base_arrays, _, _ = split_adapter(self.meta, initial)
            self.adapter_plane = AdapterTrainPlane(cfg, base_meta, base_arrays)
        # second-moment rows must leave the server >= 0 (clients sqrt them):
        # true at fp32, but q8 rounding noise turns the exactly-zero
        # pseudo-gradient of idle m2 elements tiny-nonzero and the adaptive
        # server rules then step them negative (NaN by round 3, observed).
        # Both optimizer paths clamp these rows on the q8 policy only — at
        # `off` the invariant holds by construction and clamping would break
        # the bit-exact parity pins.
        from photon_tpu.train.param_ops import M2_PREFIX

        self._nonneg_rows = tuple(
            i for i, n in enumerate(self.meta.names) if n.startswith(M2_PREFIX)
        )
        # device-resident server optimizer (parallel/collective_agg.py): the
        # whole average → pseudo-grad → update round runs as one fused SPMD
        # program with optimizer state on device; the host strategy replica
        # stays the broadcast/checkpoint mirror (synced after every round)
        self.device_plane = (
            DeviceAggregationPlane(
                self.mesh, self.strategy,
                quantization=self.quantization, block=self.q8_block,
                nonneg_rows=self._nonneg_rows, sharded=cs.collective_zero1,
            )
            if cs.collective_device_optimizer
            else None
        )
        # heterogeneity-aware layout auto-tune (ISSUE 14b): rank the legal
        # (data, fsdp, tensor, pipe) layouts for ONE client slice
        # (collective_replica ICI ranks) with the analytic cost model and
        # record the search into every round's metrics, so the History
        # carries what the model predicts for this hardware (the driver
        # topology's Trainer additionally USES the tuned layout when built
        # without an explicit mesh — see train/trainer.py)
        self._layout_metrics: dict[str, float] = {}
        if cfg.photon.mesh_autotune:
            from photon_tpu.parallel.autotune import autotune_layout

            t0 = time.monotonic()
            try:
                best = autotune_layout(
                    cfg.model,
                    n_devices=max(1, cs.collective_replica),
                    global_batch_size=cfg.train.global_batch_size,
                )
                self._layout_metrics = {
                    LAYOUT_SEARCH_TIME: time.monotonic() - t0,
                    LAYOUT_EST_STEP_S: float(best.est_step_s),
                }
            except ValueError as e:
                # this probe only feeds the server/layout_* KPIs — the
                # collective plane does not consume the layout, so "no
                # legal layout for this slice shape" must not kill a run
                # that would train fine (the loud-error contract belongs
                # to the Trainer path, which does consume it)
                warnings.warn(
                    f"layout auto-tune probe skipped: {e}", stacklevel=2
                )
        self.history = History()
        self.server_steps_cumulative = 0
        # per-client control state (sample/step counters), exactly as the
        # driver topology's ServerApp keeps it: rides FitIns so a fresh
        # loader after a restart fast-forwards to the client's cumulative
        # sample position (ClientRuntime fit), and rides the checkpoint so
        # resume replays the same data order
        self.client_states: dict[int, dict] = {}
        # SLO autopilot knobs (ISSUE 19): the collective plane owns the
        # stage deadline and the DCN quantization level — registered here
        # so the controller actuates through the bounds-checked setters
        ap = telemetry.autopilot_active()
        if ap is not None:
            ap.register_knob(
                AUTOPILOT_KNOB_STAGE_TIMEOUT_S,
                lambda: self.stage_timeout_s,
                self.set_stage_timeout_s,
            )
            ap.register_knob(
                AUTOPILOT_KNOB_QUANT_LEVEL,
                lambda: self.quantization,
                self.set_quantization,
                levels=COLLECTIVE_QUANTIZATIONS,
            )
        self._warmup_collective()

    # -- runtime-mutable knobs (ISSUE 19) ------------------------------
    def set_stage_timeout_s(self, timeout_s: float) -> None:
        """Runtime-mutable stage deadline: the autopilot tightens this when
        the straggler fraction's p90 breaches. Loud reject, never a silent
        clamp — 0 would restore wedge-forever semantics mid-run, which no
        controller should ever do to a live gang."""
        t = float(timeout_s)
        if not np.isfinite(t) or t <= 0.0:
            raise ValueError(
                f"set_stage_timeout_s needs a finite timeout > 0, got "
                f"{timeout_s!r}"
            )
        self.stage_timeout_s = t

    def set_quantization(self, quantization: str) -> None:
        """Runtime quantization escalation (ISSUE 19): when the wire-bytes
        counter trends up, the autopilot steps ``off`` → ``q8`` on the DCN
        leg. The fused device-optimizer program bakes the codec in, so the
        switch rebuilds the :class:`DeviceAggregationPlane` from the host
        strategy replica — the checkpoint authority, synced after every
        round — under an ``absorb_compiles`` window (a deliberate
        reconfiguration compile, not a retrace bug)."""
        if quantization not in COLLECTIVE_QUANTIZATIONS:
            raise ValueError(
                f"unknown collective quantization {quantization!r}, "
                f"expected one of {COLLECTIVE_QUANTIZATIONS}"
            )
        if quantization == self.quantization:
            return
        self.quantization = quantization
        if self.device_plane is not None:
            cs = self.cfg.photon.comm_stack
            with absorb_compiles("collective/requantize"):
                self.device_plane = DeviceAggregationPlane(
                    self.mesh, self.strategy,
                    quantization=self.quantization, block=self.q8_block,
                    nonneg_rows=self._nonneg_rows,
                    sharded=cs.collective_zero1,
                )

    def _warmup_collective(self) -> None:
        """Establish the cross-process collective context BEFORE the first
        round's fits: context initialization has a hard handshake deadline
        (Gloo: 30 s on CPU), and round-boundary arrival skew easily exceeds
        it when the first fit compiles. All controllers construct the runner
        near-simultaneously, so a tiny psum here creates the context while
        everyone is at the same line; later collectives reuse it and wait as
        long as the slowest controller needs."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = self.cfg.fl.n_total_clients
        sharding = NamedSharding(self.mesh, P(CLIENT_AXIS))
        ones = jax.make_array_from_process_local_data(
            sharding, np.ones(len(self.process_cids), np.int32), (n,)
        )
        probe = jax.make_array_from_process_local_data(
            sharding, np.ones((len(self.process_cids), 1), np.float32), (n, 1)
        )
        def _probe():
            avg = hierarchical_weighted_average([probe], ones, self.mesh)
            np.asarray(avg[0])  # block: the context exists once this returns

        # the context handshake is a collective stage like any other: a
        # controller that never shows up must not wedge construction forever
        self._run_stage("handshake", _probe, self._stage_deadline())

    def _default_mesh(self):
        """Client mesh whose device order matches :func:`partition_cids`:
        row i of the stacked arrays must land on devices ADDRESSABLE by the
        process that owns cid i, and every process must contribute exactly
        ``len(process_cids) × collective_replica`` devices —
        ``jax.devices()[:n]`` breaks both whenever local device counts
        differ from local cid counts (e.g. 2 hosts x 4 chips with 4
        clients). With ``collective_replica > 1`` each client row widens to
        its slice's ICI ranks (the hierarchical topology)."""
        n_total = self.cfg.fl.n_total_clients
        replica = self.cfg.photon.comm_stack.collective_replica
        n_proc = jax.process_count()
        devices = []
        for p in range(n_proc):
            want = len(partition_cids(n_total, n_proc, p)) * replica
            local = [d for d in jax.devices() if d.process_index == p]
            if len(local) < want:
                raise ValueError(
                    f"process {p} owns {want} device slots ({replica} per "
                    f"client) but only {len(local)} devices — rebalance "
                    "clients, lower collective_replica, or add devices"
                )
            devices.extend(local[:want])
        return make_hierarchical_mesh(n_total, replica, devices)

    # -- stage deadlines (ISSUE 8a) ------------------------------------
    def _stage_deadline(self) -> float | None:
        """Absolute deadline for ONE collective stage on the injected
        clock, or None when deadlines are off."""
        if self.stage_timeout_s <= 0:
            return None
        return self.clock() + self.stage_timeout_s

    def _run_stage(self, stage: str, fn: Callable[[], object],
                   deadline: float | None):
        """Run one collective stage under its absolute deadline.

        With a deadline armed the stage body runs on a named daemon worker
        thread and the caller waits at most the remaining budget — an
        XLA collective that never completes (dead peer, byte-dripping DCN
        link) cannot be cancelled from Python, so on a miss the worker is
        abandoned (daemon, it dies with the process) and
        :class:`StageDeadlineError` routes the round into the
        reconfiguration ladder. Deadline arithmetic uses the injected
        clock; the thread join is bounded by the same remaining budget.
        """
        if deadline is None:
            return fn()
        start = self.clock()
        if deadline - start <= 0:
            raise StageDeadlineError(stage, 0.0)
        result: dict[str, object] = {}

        def _target() -> None:
            try:
                result["value"] = fn()
            except BaseException as e:  # noqa: BLE001 — re-raised by the caller
                result["error"] = e

        th = threading.Thread(
            target=_target, name=f"collective-{stage}", daemon=True
        )
        th.start()
        # the deadline is judged on the INJECTED clock; the join itself
        # waits real time in short slices (th.join's timeout is wall-clock,
        # which need not be the injected clock's time base)
        while th.is_alive():
            remaining = deadline - self.clock()
            if remaining <= 0:
                # the worker may still be inside an XLA compile whose
                # monitoring event lands at ANY later time — tracked so the
                # round-end sentinel point can absorb it (see run_round)
                self._abandoned_workers.append(th)
                raise StageDeadlineError(stage, self.clock() - start)
            th.join(timeout=min(remaining, 0.05))
        if "error" in result:
            raise result["error"]  # type: ignore[misc]
        return result.get("value")

    # -- cohorts (ISSUE 8b) --------------------------------------------
    @staticmethod
    def _client_node_id(cid: int) -> str:
        return f"client{cid}"

    #: bound on cached survivor-cohort meshes: every distinct cohort pins a
    #: mesh AND its compiled aggregation programs (device memory), and a
    #: churny fleet can visit many subsets over a long run. LRU: the least
    #: recently used cohort is evicted with its programs; revisiting it
    #: later recompiles (absorbed — partial cohorts always run under
    #: ``absorb_compiles``).
    MAX_COHORT_MESHES = 32

    def _cohort_mesh(self, cohort: tuple[int, ...]):
        """(clients, replica) mesh over the cohort's rows of the full mesh.
        Meshes are cached per cohort (bounded LRU) so the aggregation
        program caches (keyed per mesh object) hit on every later round
        with the same survivors — only the FIRST round over a new cohort
        compiles, and that compile is budgeted via ``absorb_compiles``."""
        if len(cohort) == self.cfg.fl.n_total_clients:
            return self.mesh
        mesh = self._cohort_meshes.get(cohort)
        if mesh is None:
            while len(self._cohort_meshes) >= self.MAX_COHORT_MESHES:
                old_cohort = next(iter(self._cohort_meshes))
                evict_mesh_programs(self._cohort_meshes.pop(old_cohort))
            devs = np.asarray(self.mesh.devices)
            if devs.ndim == 1:
                devs = devs[:, None]
            sub = list(devs[list(cohort), :].reshape(-1))
            mesh = make_hierarchical_mesh(len(cohort), mesh_replica(self.mesh), sub)
        else:
            del self._cohort_meshes[cohort]  # re-insert: LRU recency order
        self._cohort_meshes[cohort] = mesh
        return mesh

    def _surviving_cohort(self, landed: dict[int, tuple[list[np.ndarray], int]]
                          ) -> tuple[int, ...]:
        """The GLOBAL surviving cohort as this controller observes it: every
        cid except (a) our own clients whose fits failed (``landed`` only
        ever holds this process's cids — another controller's clients are
        presumed fine unless the shared liveness plane says otherwise) and
        (b) any cid whose liveness state is not LIVE — a mid-round
        live→suspect/dead edge excludes a client even if its fit result
        arrived (its node may be dying under it)."""
        out = []
        for cid in range(self.cfg.fl.n_total_clients):
            if cid in self._local_cids and cid not in landed:
                continue  # we watched this client's fit fail
            h = self.liveness.nodes.get(self._client_node_id(cid))
            if h is None or h.state == LIVE:
                out.append(cid)
        return tuple(out)

    # ------------------------------------------------------------------
    def _stack_local(self, rows: list[list[np.ndarray]], mesh=None,
                     n_global: int | None = None) -> list[jax.Array]:
        """Per-layer: process-local ``[n_local, ...]`` rows → global
        ``[n_clients, ...]`` client-axis-sharded arrays (on ``mesh``, which
        defaults to the full-cohort mesh)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = mesh if mesh is not None else self.mesh
        sharding = NamedSharding(mesh, P(CLIENT_AXIS))
        n_global = (n_global if n_global is not None
                    else self.cfg.fl.n_total_clients)
        out = []
        for li in range(len(rows[0])):
            local = np.stack([r[li] for r in rows])
            gshape = (n_global,) + local.shape[1:]
            out.append(
                jax.make_array_from_process_local_data(sharding, local, gshape)
            )
        return out

    def run_round(self, server_round: int) -> dict[str, float]:
        if self.adapter_plane is not None:
            return self._run_round_adapters(server_round)
        t_round = time.monotonic()
        cfg = self.cfg

        # "broadcast": every controller already holds the replica params
        ptr = self.transport.put(
            f"collective-bcast-r{server_round}", self.meta, self.strategy.current_parameters
        )
        self.runtime.set_broadcast_params(ptr)

        # matches the driver topology's definition: fit_round_time spans the
        # client fits AND the aggregation (server.py fit_round)
        t_fit = time.monotonic()
        landed: dict[int, tuple[list[np.ndarray], int]] = {}
        for cid in self.process_cids:
            ins = FitIns(
                server_round=server_round,
                cids=[cid],
                params=None,
                local_steps=cfg.fl.local_steps,
                server_steps_cumulative=self.server_steps_cumulative,
                client_states=(
                    {cid: self.client_states[cid]} if cid in self.client_states else {}
                ),
                config=dict(cfg.fl.fit_config),
            )
            res = self.runtime.fit(ins, cid)
            nid = self._client_node_id(cid)
            if res.error:
                # elastic rounds (ISSUE 8): a failed/crashed client is a
                # straggler dropped from THIS round's cohort, not a fatal
                # error — reconfiguration is round-scoped, so it is
                # re-attempted (and readmitted) next round
                self.liveness.observe_miss(nid)
                telemetry.emit_event(
                    EVENT_COLLECTIVE_STRAGGLER, round=server_round, cid=cid,
                    reason="fit_error", detail=res.error[:200],
                )
                warnings.warn(
                    f"collective round {server_round}: cid {cid} failed "
                    f"({res.error.splitlines()[0][:120]}) — dropped from the "
                    "round's cohort",
                    stacklevel=2,
                )
                continue
            self.liveness.observe_alive(nid)
            if res.client_state:
                self.client_states[res.cid] = res.client_state
            _, arrays = self.transport.get(res.params)
            landed[cid] = (arrays, res.n_samples)
            self.transport.free(res.params)

        crash_point("pre-exchange", server_round, self.runtime.node_id)

        t_agg = time.monotonic()
        metrics, path, stragglers, reconfig_s = self._aggregate_elastic(
            server_round, landed
        )
        if metrics is None:
            # nothing landed: the round is recorded failed (params and the
            # step counter unchanged) and the run CONTINUES — never aborted
            warnings.warn(
                f"collective round {server_round}: no client deltas landed — "
                "round recorded failed, parameters unchanged",
                stacklevel=2,
            )
            metrics = {
                ROUND_FAILED: 1.0,
                COLLECTIVE_STACK_TIME: 0.0,
                COLLECTIVE_EXCHANGE_TIME: 0.0,
                COLLECTIVE_UPDATE_TIME: 0.0,
                COLLECTIVE_WIRE_BYTES: 0.0,
            }
        else:
            self.server_steps_cumulative += cfg.fl.local_steps
        if self.device_plane is not None and path in (
            "collective_reconfigured", "host_fallback"
        ):
            # the round ran OFF the fused plane (survivors fold / host
            # fold applied on the host strategy): push the result back so
            # the device-resident state re-enters lockstep for next round
            # (absorb: the first reseed's device_puts may compile)
            with absorb_compiles("collective/reseed"):
                self.device_plane.reseed_from(self.strategy)

        metrics[COLLECTIVE_STRAGGLERS] = float(stragglers)
        metrics[COLLECTIVE_DEGRADED_ROUNDS] = (
            1.0 if path == "host_fallback" else 0.0
        )
        metrics[COLLECTIVE_RECONFIG_TIME] = reconfig_s
        metrics[COLLECTIVE_AGG_TIME] = time.monotonic() - t_agg
        metrics[FIT_ROUND_TIME] = time.monotonic() - t_fit
        metrics[STEPS_CUMULATIVE] = float(self.server_steps_cumulative)
        metrics[ROUND_TIME] = time.monotonic() - t_round
        metrics.update(self._layout_metrics)
        self.stragglers_total += stragglers
        if path == "host_fallback":
            self.degraded_rounds_total += 1
        self.aggregation_paths[server_round] = path
        self._observe_collective_health(server_round, metrics, path, stragglers)
        self.history.record(server_round, metrics)
        if self._abandoned_workers:
            # a deadline-abandoned worker may have been mid-compile when it
            # was disowned; its compile event lands whenever the thread gets
            # there (possibly during the host fallback, after every
            # absorb_compiles window closed). Forgive this round's interval
            # rather than billing a behaviorally-correct degraded round as a
            # retrace bug; detection is back at full strength once the
            # abandoned threads die.
            with absorb_compiles("collective/abandoned"):
                pass
            self._abandoned_workers = [
                t for t in self._abandoned_workers if t.is_alive()
            ]
        steady_point("collective/round")
        return metrics

    def _observe_collective_health(self, server_round: int, metrics: dict,
                                   path: str, stragglers: int) -> None:
        """Run-health observatory hooks at the collective round boundary
        (ISSUE 10): stage timings into typed histograms, modeled wire bytes
        into a counter, HBM/compile sampling, then the health watchers —
        the NaN sentinel on the aggregate and the straggler-percentile /
        degraded-budget watchers over the PR 8 ladder. One None check per
        plane when telemetry is off."""
        hub = telemetry.metrics_active()
        if hub is not None:
            from photon_tpu.telemetry.introspect import sample_device_plane

            for key in (COLLECTIVE_STACK_TIME, COLLECTIVE_EXCHANGE_TIME,
                        COLLECTIVE_UPDATE_TIME, COLLECTIVE_AGG_TIME,
                        ROUND_TIME):
                v = metrics.get(key)
                if v is not None:
                    hub.histogram(key).observe(float(v))
            wire = metrics.get(COLLECTIVE_WIRE_BYTES)
            if wire:
                hub.counter(COLLECTIVE_WIRE_BYTES).inc(float(wire))
            # the autopilot's straggler-deadline rule reduces p90 over this
            # gauge's window (ISSUE 19)
            hub.gauge(COLLECTIVE_STRAGGLER_FRAC).set(
                stragglers / max(1, self.cfg.fl.n_total_clients)
            )
            sample_device_plane(
                metrics, hub, hbm_key=HBM_BYTES_IN_USE,
                peak_key=HBM_PEAK_BYTES, compiles_key=COMPILES_TOTAL,
            )
        health = telemetry.health_active()
        if health is not None:
            health.check_round_metrics(server_round, metrics)
            health.check_collective_round(
                server_round,
                stragglers=stragglers,
                n_total=self.cfg.fl.n_total_clients,
                degraded=(path == "host_fallback"),
                failed=bool(metrics.get(ROUND_FAILED)),
            )
            hbm = metrics.get(HBM_BYTES_IN_USE)
            if hbm is not None:
                health.note_hbm_sample(hbm)
        ap = telemetry.autopilot_active()
        if ap is not None:
            ap.tick("collective")

    # -- the straggler/degradation ladder (ISSUE 8) --------------------
    def _aggregate_elastic(
        self,
        server_round: int,
        landed: dict[int, tuple[list[np.ndarray], int]],
    ) -> tuple[dict[str, float] | None, str, int, float]:
        """Aggregate over whoever survived: collective → (reconfigured)
        collective → host fallback. Returns ``(metrics | None, path,
        stragglers, reconfig_seconds)``; ``None`` metrics = nothing landed.
        """
        n_total = self.cfg.fl.n_total_clients
        # liveness-excluded clients whose deltas DID land are stragglers too
        for cid in sorted(set(landed) - set(self._surviving_cohort(landed))):
            telemetry.emit_event(
                EVENT_COLLECTIVE_STRAGGLER, round=server_round, cid=cid,
                reason="liveness",
            )
        attempts = 0
        reconfig_s = 0.0
        degraded_reason = None
        while True:
            cohort = self._surviving_cohort(landed)
            if not cohort or not any(cid in landed for cid in cohort):
                # no local deltas at all: this controller has nothing to
                # fold (and nothing to contribute to a gang that, by the
                # cohort-agreement caveat, its peers will also tear down).
                # Stragglers = clients actually missing from the cohort —
                # peers' live clients are not miscounted on a local wipeout
                return None, "failed", n_total - len(cohort), reconfig_s
            if len(cohort) < self.quorum * n_total:
                degraded_reason = (
                    f"below quorum: {len(cohort)}/{n_total} surviving < "
                    f"{self.quorum}"
                )
                break
            if attempts > self.retry_budget:
                degraded_reason = (
                    f"retry budget exhausted ({self.retry_budget} reconfig "
                    "attempts)"
                )
                break
            t0 = time.monotonic()
            # rollback point: an attempt can fail AFTER its fused run
            # committed (exchange landed, update stage missed its deadline)
            # — without the restore, the retry would apply the optimizer
            # step a second time on the once-stepped state
            snap = (self.device_plane.snapshot()
                    if self.device_plane is not None else None)
            try:
                if len(cohort) < n_total:
                    # a survivors-cohort program is a legitimate steady-state
                    # compile the first time this cohort appears — budget it
                    # against the retrace sentinel instead of tripping it
                    with absorb_compiles("collective/reconfig"):
                        metrics = self._collective_attempt(
                            server_round, cohort, landed
                        )
                    path = "collective_reconfigured"
                else:
                    metrics = self._collective_attempt(server_round, cohort, landed)
                    path = "collective"
                return metrics, path, n_total - len(cohort), reconfig_s
            except StageDeadlineError as e:
                reason, stage = str(e), e.stage
            except Exception as e:  # noqa: BLE001 — a torn gang surfaces as
                # a distributed-runtime error as often as a hang; both route
                # into the same reconfigure-or-degrade ladder (bounded by
                # the retry budget, so a genuine bug still surfaces — as a
                # loudly-warned degraded round with the error attached)
                reason, stage = f"{type(e).__name__}: {e}", "exchange"
            attempts += 1
            reconfig_s += time.monotonic() - t0
            self.reconfigs_total += 1
            if self.device_plane is not None:
                # an abandoned fused attempt may still be running on its
                # worker thread: its late commit must not tear the plane —
                # and whatever it DID commit rolls back to the attempt's
                # snapshot so the retry (or the host fallback's reseed)
                # starts from the pre-round state
                self.device_plane.abandon()
                self.device_plane.restore(snap)
            telemetry.emit_event(
                EVENT_COLLECTIVE_RECONFIG, round=server_round,
                attempt=attempts, stage=stage, cohort=len(cohort),
                reason=reason[:200],
            )
            warnings.warn(
                f"collective round {server_round}: attempt {attempts} failed "
                f"at stage {stage!r} ({reason.splitlines()[0][:160]}) — "
                f"reconfiguring ({self.retry_budget - attempts + 1} retries "
                "left before host fallback)",
                stacklevel=2,
            )
        # -- degrade: the bit-exact host-plane fold over landed deltas ----
        # reuse the cohort the loop just validated: recomputing here could
        # diverge under a concurrently-fed liveness tracker (ping sweep on
        # another thread) and hand the fallback an empty fold — aborting on
        # exactly the path that exists to never abort
        telemetry.emit_event(
            EVENT_COLLECTIVE_DEGRADED, round=server_round,
            cohort=len(cohort), reason=degraded_reason,
        )
        warnings.warn(
            f"collective round {server_round}: degrading to the host-plane "
            f"fold over {len(cohort)}/{n_total} clients ({degraded_reason})",
            stacklevel=2,
        )
        metrics = self._host_fallback(server_round, cohort, landed)
        return metrics, "host_fallback", n_total - len(cohort), reconfig_s

    def _collective_attempt(
        self,
        server_round: int,
        cohort: tuple[int, ...],
        landed: dict[int, tuple[list[np.ndarray], int]],
    ) -> dict[str, float]:
        """One aggregation attempt over ``cohort``, each stage under its
        deadline. Full cohort + device optimizer → the fused plane (exactly
        the PR 7 program). Partial cohort → the (optionally quantized)
        average over the survivors mesh with FedAvg weights renormalized by
        construction (Σn runs over cohort rows only), then the host
        strategy update — the fused plane's state re-enters via
        ``reseed_from`` afterwards."""
        cfg = self.cfg
        n_total = cfg.fl.n_total_clients
        full = len(cohort) == n_total
        mesh = self._cohort_mesh(cohort)
        local_cids = [cid for cid in cohort if cid in landed]
        rows = [landed[cid][0] for cid in local_cids]
        ns = [landed[cid][1] for cid in local_cids]
        from jax.sharding import NamedSharding, PartitionSpec as P

        with telemetry.span(COLLECTIVE_STACK_TIME):
            t_stage = time.monotonic()

            def _stack():
                stacked = self._stack_local(rows, mesh, len(cohort))
                ns_global = jax.make_array_from_process_local_data(
                    NamedSharding(mesh, P(CLIENT_AXIS)),
                    np.asarray(ns, np.int32),
                    (len(cohort),),
                )
                return stacked, ns_global

            stacked, ns_global = self._run_stage(
                "stack", _stack, self._stage_deadline()
            )
            stack_s = time.monotonic() - t_stage

        if self.device_plane is not None and full:
            # fused path: average + pseudo-grad + server optimizer as ONE
            # jitted SPMD program, state resident on device
            with telemetry.span(COLLECTIVE_EXCHANGE_TIME):
                t_stage = time.monotonic()
                # epoch captured HERE (caller thread): an abandon issued
                # while the worker is still ramping up must not be missed
                epoch = self.device_plane.current_epoch()

                def _exchange():
                    crash_point("mid-exchange", server_round, self.runtime.node_id)
                    return self.device_plane.run_round(
                        stacked, ns_global,
                        lr=self.strategy.effective_lr(n_total), epoch=epoch,
                    )

                metrics = self._run_stage(
                    "exchange", _exchange, self._stage_deadline()
                )
                exchange_s = time.monotonic() - t_stage
            crash_point("pre-update", server_round, self.runtime.node_id)
            with telemetry.span(COLLECTIVE_UPDATE_TIME):
                t_stage = time.monotonic()

                # the worker only FETCHES (the wedge-able device→host IO);
                # the host-mirror mutation happens on the caller thread
                # after the stage returns, so a deadline-abandoned worker
                # can never mutate the strategy underneath a retry or the
                # host fallback when it eventually completes
                def _fetch():
                    return (self.device_plane.params_host(),
                            self.device_plane.state_host(),
                            self.device_plane.t)

                params_host, state_host, t = self._run_stage(
                    "update", _fetch, self._stage_deadline()
                )
                # host mirror: the next broadcast and any checkpoint read
                # strategy.current_parameters (replicated outputs → every
                # controller fetches identical values)
                self.strategy.current_parameters = params_host
                self.strategy.restore_optimizer_state(state_host, t=t)
                self.strategy.server_round = server_round
                update_s = time.monotonic() - t_stage
            # ZeRO-1 observability (ISSUE 14a): how much of the server
            # state this rank holds, and what the post-update params
            # all-gather cost inside the fetch above
            metrics[OPT_SHARD_FRAC] = self.device_plane.shard_fraction()
            metrics[OPT_ALLGATHER_TIME] = self.device_plane.last_allgather_s
        else:
            # host-optimizer path (and every partial-cohort attempt): the
            # collective carries the (optionally quantized) average; the
            # strategy replica updates on host. Σn rides the same SPMD
            # program as one extra psum output — a separate collective per
            # round would double the rendezvous cost
            with telemetry.span(COLLECTIVE_EXCHANGE_TIME):
                t_stage = time.monotonic()

                def _exchange():
                    crash_point("mid-exchange", server_round, self.runtime.node_id)
                    avg_dev, total_dev = hierarchical_weighted_average(
                        stacked, ns_global, mesh,
                        quantization=self.quantization, block=self.q8_block,
                        return_total=True,
                    )
                    # wait for the collective HERE so exchange_time means
                    # the same thing on both optimizer paths (the device
                    # path blocks on its scalar fetches inside run_round);
                    # the device→host payload copy belongs to the update
                    # bucket, mirroring the device path's sync_strategy
                    jax.block_until_ready(avg_dev)
                    return avg_dev, total_dev

                avg_dev, total_dev = self._run_stage(
                    "exchange", _exchange, self._stage_deadline()
                )
                exchange_s = time.monotonic() - t_stage
            crash_point("pre-update", server_round, self.runtime.node_id)
            with telemetry.span(COLLECTIVE_UPDATE_TIME):
                t_stage = time.monotonic()

                # worker fetches only (see the device path above): the pure-
                # numpy strategy update runs on the caller thread, so an
                # abandoned fetch can never apply a stale round later
                def _fetch():
                    avg = [np.asarray(a) for a in avg_dev]
                    n_samples = int(np.asarray(total_dev))
                    return avg, n_samples

                avg, n_samples = self._run_stage(
                    "update", _fetch, self._stage_deadline()
                )
                metrics = self._apply_average_host(
                    server_round, avg, n_samples, len(cohort)
                )
                update_s = time.monotonic() - t_stage

        metrics[COLLECTIVE_STACK_TIME] = stack_s
        metrics[COLLECTIVE_EXCHANGE_TIME] = exchange_s
        metrics[COLLECTIVE_UPDATE_TIME] = update_s
        metrics[COLLECTIVE_WIRE_BYTES] = float(
            modeled_cross_slice_bytes(
                [int(np.prod(r.shape, dtype=np.int64)) for r in rows[0]],
                len(cohort),
                replica=mesh_replica(mesh),
                quantization=self.quantization,
                block=self.q8_block,
            )
        )
        return metrics

    def _apply_average_host(
        self, server_round: int, avg: list[np.ndarray], n_samples: int,
        n_clients: int,
    ) -> dict[str, float]:
        """Host half of the non-fused paths: strategy update on the
        (collectively or host-) averaged payload, with the q8-policy
        second-moment clamp (see ``__init__``: the invariant must hold on
        every path of a q8 run — prior q8 rounds leave idle m2 elements
        tiny-positive, so even an exact fold can be stepped negative)."""
        metrics = self.strategy.apply_average(
            server_round, avg, n_samples, n_clients
        )
        if self.quantization == "q8":
            # apply_average returns fresh arrays, so in-place is safe
            for i in self._nonneg_rows:
                p = self.strategy.current_parameters[i]
                np.maximum(p, 0.0, out=p)
        return metrics

    def _host_fallback(
        self,
        server_round: int,
        cohort: tuple[int, ...],
        landed: dict[int, tuple[list[np.ndarray], int]],
    ) -> dict[str, float]:
        """The degradation floor: the host-plane streaming fold (PR 2) over
        whichever deltas landed — bit-exact with ``aggregate_inplace`` fed
        the same surviving subset because it IS that fold. No collective
        rendezvous, so a torn gang cannot wedge it; on a multi-controller
        gang each controller folds its LOCAL survivors — the cohort also
        names peers' clients whose deltas never land here (see the module
        docstring's cohort-agreement caveat)."""
        from photon_tpu.strategy.aggregation import aggregate_inplace

        with telemetry.span(COLLECTIVE_EXCHANGE_TIME, degraded=True):
            t0 = time.monotonic()
            avg, n_samples = aggregate_inplace(
                (landed[cid] for cid in cohort if cid in landed)
            )
            fold_s = time.monotonic() - t0
        with telemetry.span(COLLECTIVE_UPDATE_TIME, degraded=True):
            t1 = time.monotonic()
            metrics = self._apply_average_host(
                server_round, avg, n_samples, len(cohort)
            )
            update_s = time.monotonic() - t1
        metrics[COLLECTIVE_STACK_TIME] = 0.0
        metrics[COLLECTIVE_EXCHANGE_TIME] = fold_s
        metrics[COLLECTIVE_UPDATE_TIME] = update_s
        # nothing crossed a slice boundary this round
        metrics[COLLECTIVE_WIRE_BYTES] = 0.0
        return metrics

    # -- per-cohort adapter rounds (ISSUE 13) ---------------------------
    def _cohort_broadcast_ptrs(self, tag: str, server_round: int) -> dict:
        """One merged (base + cohort adapter) payload per cohort this
        process serves — the per-cohort 'broadcast'. Keyed by cohort name
        (None = the identity-adapter payload for cohortless cids)."""
        plane = self.adapter_plane
        ptrs: dict = {}
        for cid in self.process_cids:
            name = plane.cohort_of.get(cid)
            if name not in ptrs:
                meta_c, arrays_c = plane.broadcast_payload(cid)
                ptrs[name] = self.transport.put(
                    f"adapter-{tag}-r{server_round}-{name or '__base__'}",
                    meta_c, arrays_c,
                )
        return ptrs

    def _run_round_adapters(self, server_round: int) -> dict[str, float]:
        """One personalization round: per-cohort broadcast → local adapter
        fits on the frozen base → ALL cohorts' reductions fused into ONE
        grouped program on the PR 7 plane → per-cohort server-optimizer
        updates, under the same elastic ladder as the global rounds."""
        t_round = time.monotonic()
        cfg = self.cfg
        plane = self.adapter_plane
        ptrs = self._cohort_broadcast_ptrs("bcast", server_round)

        t_fit = time.monotonic()
        landed: dict[int, tuple[list[np.ndarray], int]] = {}
        for cid in self.process_cids:
            ins = FitIns(
                server_round=server_round,
                cids=[cid],
                params=ptrs[plane.cohort_of.get(cid)],
                local_steps=cfg.fl.local_steps,
                server_steps_cumulative=self.server_steps_cumulative,
                client_states=(
                    {cid: self.client_states[cid]} if cid in self.client_states else {}
                ),
                config=dict(cfg.fl.fit_config),
            )
            res = self.runtime.fit(ins, cid)
            nid = self._client_node_id(cid)
            if res.error:
                self.liveness.observe_miss(nid)
                telemetry.emit_event(
                    EVENT_COLLECTIVE_STRAGGLER, round=server_round, cid=cid,
                    reason="fit_error", detail=res.error[:200],
                )
                warnings.warn(
                    f"adapter round {server_round}: cid {cid} failed "
                    f"({res.error.splitlines()[0][:120]}) — dropped from the "
                    "round's cohort",
                    stacklevel=2,
                )
                continue
            self.liveness.observe_alive(nid)
            if res.client_state:
                self.client_states[res.cid] = res.client_state
            meta, arrays = self.transport.get(res.params)
            # ONLY the adapter rows ever reach the exchange: the base is
            # frozen (exactly-zero optimizer updates) and never moves
            landed[cid] = (plane.extract_adapter(meta, arrays), res.n_samples)
            self.transport.free(res.params)
        for ptr in ptrs.values():
            self.transport.free(ptr)

        crash_point("pre-exchange", server_round, self.runtime.node_id)

        t_agg = time.monotonic()
        metrics, path, stragglers, reconfig_s = self._aggregate_elastic_adapters(
            server_round, landed
        )
        if metrics is None:
            warnings.warn(
                f"adapter round {server_round}: no client deltas landed — "
                "round recorded failed, every cohort's adapter unchanged",
                stacklevel=2,
            )
            metrics = {
                ROUND_FAILED: 1.0,
                COLLECTIVE_STACK_TIME: 0.0,
                COLLECTIVE_EXCHANGE_TIME: 0.0,
                COLLECTIVE_UPDATE_TIME: 0.0,
                COLLECTIVE_WIRE_BYTES: 0.0,
                ADAPTER_WIRE_BYTES: 0.0,
                ADAPTER_COHORTS: 0.0,
                ADAPTER_COHORTS_DEGRADED: float(plane.n_cohorts),
            }
        else:
            self.server_steps_cumulative += cfg.fl.local_steps

        metrics[COLLECTIVE_STRAGGLERS] = float(stragglers)
        metrics[COLLECTIVE_DEGRADED_ROUNDS] = (
            1.0 if path == "host_fallback" else 0.0
        )
        metrics[COLLECTIVE_RECONFIG_TIME] = reconfig_s
        metrics[COLLECTIVE_AGG_TIME] = time.monotonic() - t_agg
        metrics[FIT_ROUND_TIME] = time.monotonic() - t_fit
        metrics[STEPS_CUMULATIVE] = float(self.server_steps_cumulative)
        metrics[ROUND_TIME] = time.monotonic() - t_round
        self.stragglers_total += stragglers
        if path == "host_fallback":
            self.degraded_rounds_total += 1
        self.aggregation_paths[server_round] = path
        self._observe_collective_health(server_round, metrics, path, stragglers)
        self.history.record(server_round, metrics)
        if self._abandoned_workers:
            # same forgiveness as the global path: a deadline-abandoned
            # worker's late compile event must not bill a correct round
            with absorb_compiles("collective/abandoned"):
                pass
            self._abandoned_workers = [
                t for t in self._abandoned_workers if t.is_alive()
            ]
        steady_point("collective/round")
        return metrics

    def _aggregate_elastic_adapters(
        self,
        server_round: int,
        landed: dict[int, tuple[list[np.ndarray], int]],
    ) -> tuple[dict[str, float] | None, str, int, float]:
        """The PR 8 ladder over GROUPED aggregation: fused multi-cohort
        reduction → (reconfigured) retry → per-cohort host fold. The
        failure unit stays the client; the DEGRADATION unit is the
        cohort — a cohort whose members all died skips its update while
        every other cohort proceeds."""
        n_total = self.cfg.fl.n_total_clients
        for cid in sorted(set(landed) - set(self._surviving_cohort(landed))):
            telemetry.emit_event(
                EVENT_COLLECTIVE_STRAGGLER, round=server_round, cid=cid,
                reason="liveness",
            )
        attempts = 0
        reconfig_s = 0.0
        degraded_reason = None
        while True:
            cohort = self._surviving_cohort(landed)
            if not cohort or not any(cid in landed for cid in cohort):
                return None, "failed", n_total - len(cohort), reconfig_s
            if len(cohort) < self.quorum * n_total:
                degraded_reason = (
                    f"below quorum: {len(cohort)}/{n_total} surviving < "
                    f"{self.quorum}"
                )
                break
            if attempts > self.retry_budget:
                degraded_reason = (
                    f"retry budget exhausted ({self.retry_budget} reconfig "
                    "attempts)"
                )
                break
            t0 = time.monotonic()
            # rollback point: a grouped attempt can fail after SOME cohort
            # updates applied (update-stage deadline mid-loop) — the retry
            # must start every cohort from the round's entry state
            snap = self.adapter_plane.strategies.snapshot()
            try:
                if len(cohort) < n_total:
                    with absorb_compiles("collective/reconfig"):
                        metrics = self._grouped_attempt(
                            server_round, cohort, landed
                        )
                    path = "collective_reconfigured"
                else:
                    metrics = self._grouped_attempt(server_round, cohort, landed)
                    path = "collective"
                return metrics, path, n_total - len(cohort), reconfig_s
            except StageDeadlineError as e:
                reason, stage = str(e), e.stage
            except Exception as e:  # noqa: BLE001 — same stance as the
                # global ladder: torn gangs surface as runtime errors as
                # often as hangs
                reason, stage = f"{type(e).__name__}: {e}", "exchange"
            self.adapter_plane.strategies.restore(snap)
            attempts += 1
            reconfig_s += time.monotonic() - t0
            self.reconfigs_total += 1
            telemetry.emit_event(
                EVENT_COLLECTIVE_RECONFIG, round=server_round,
                attempt=attempts, stage=stage, cohort=len(cohort),
                reason=reason[:200],
            )
            warnings.warn(
                f"adapter round {server_round}: attempt {attempts} failed "
                f"at stage {stage!r} ({reason.splitlines()[0][:160]}) — "
                f"reconfiguring ({self.retry_budget - attempts + 1} retries "
                "left before host fallback)",
                stacklevel=2,
            )
        telemetry.emit_event(
            EVENT_COLLECTIVE_DEGRADED, round=server_round,
            cohort=len(cohort), reason=degraded_reason,
        )
        warnings.warn(
            f"adapter round {server_round}: degrading to the per-cohort "
            f"host fold over {len(cohort)}/{n_total} clients "
            f"({degraded_reason})",
            stacklevel=2,
        )
        metrics = self._grouped_host_fallback(server_round, cohort, landed)
        return metrics, "host_fallback", n_total - len(cohort), reconfig_s

    def _grouped_attempt(
        self,
        server_round: int,
        cohort: tuple[int, ...],
        landed: dict[int, tuple[list[np.ndarray], int]],
    ) -> dict[str, float]:
        """One fused grouped-reduction attempt over ``cohort``: every
        client's adapter row weighted into its own cohort's slot, ONE
        collective rendezvous for all K cohorts (not K allreduces), each
        stage under its deadline; the per-cohort server updates run on the
        caller thread after the fetch stage returns (the abandoned-worker
        discipline of the global path)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from photon_tpu.parallel.collective_agg import grouped_weighted_average
        from photon_tpu.strategy.grouped import cohort_onehot

        plane = self.adapter_plane
        mesh = self._cohort_mesh(cohort)
        local_cids = [cid for cid in cohort if cid in landed]
        rows = [landed[cid][0] for cid in local_cids]
        ns = [landed[cid][1] for cid in local_cids]
        onehot_local = cohort_onehot(
            local_cids, plane.cohort_of, plane.cohort_names
        )

        with telemetry.span(COLLECTIVE_STACK_TIME):
            t_stage = time.monotonic()

            def _stack():
                stacked = self._stack_local(rows, mesh, len(cohort))
                sharding = NamedSharding(mesh, P(CLIENT_AXIS))
                ns_global = jax.make_array_from_process_local_data(
                    sharding, np.asarray(ns, np.int32), (len(cohort),)
                )
                oh_global = jax.make_array_from_process_local_data(
                    sharding, onehot_local,
                    (len(cohort), plane.n_cohorts),
                )
                return stacked, ns_global, oh_global

            stacked, ns_global, oh_global = self._run_stage(
                "stack", _stack, self._stage_deadline()
            )
            stack_s = time.monotonic() - t_stage

        with telemetry.span(COLLECTIVE_EXCHANGE_TIME):
            t_stage = time.monotonic()

            def _exchange():
                crash_point("mid-exchange", server_round, self.runtime.node_id)
                avgs, totals = grouped_weighted_average(
                    stacked, ns_global, oh_global, mesh,
                    quantization=self.quantization, block=self.q8_block,
                )
                jax.block_until_ready(totals)
                return avgs, totals

            avgs, totals = self._run_stage(
                "exchange", _exchange, self._stage_deadline()
            )
            exchange_s = time.monotonic() - t_stage
        crash_point("pre-update", server_round, self.runtime.node_id)
        with telemetry.span(COLLECTIVE_UPDATE_TIME):
            t_stage = time.monotonic()

            # worker FETCHES only; the strategy mutation happens on the
            # caller thread, so an abandoned worker can never apply a
            # stale round later
            def _fetch():
                return ([np.asarray(a) for a in avgs], np.asarray(totals))

            avgs_host, totals_host = self._run_stage(
                "update", _fetch, self._stage_deadline()
            )
            counts: dict[str, int] = {n: 0 for n in plane.cohort_names}
            for cid in cohort:
                name = plane.cohort_of.get(cid)
                if name is not None:
                    counts[name] += 1
            folds = {}
            for name in plane.cohort_names:
                k = plane.strategies.index_of(name)
                n_samples = int(round(float(totals_host[k])))
                if n_samples > 0:
                    folds[name] = (
                        [a[k] for a in avgs_host], n_samples,
                        max(counts[name], 1),
                    )
            metrics = self._apply_cohort_updates(server_round, cohort, folds)
            update_s = time.monotonic() - t_stage

        metrics[COLLECTIVE_STACK_TIME] = stack_s
        metrics[COLLECTIVE_EXCHANGE_TIME] = exchange_s
        metrics[COLLECTIVE_UPDATE_TIME] = update_s
        wire = float(
            modeled_cross_slice_bytes(
                plane.adapter_sizes(),
                len(cohort),
                replica=mesh_replica(mesh),
                quantization=self.quantization,
                block=self.q8_block,
            )
        )
        metrics[COLLECTIVE_WIRE_BYTES] = wire
        metrics[ADAPTER_WIRE_BYTES] = wire
        return metrics

    def _apply_cohort_updates(
        self,
        server_round: int,
        cohort: tuple[int, ...],
        folds: dict[str, tuple[list[np.ndarray], int, int]],
    ) -> dict[str, float]:
        """Per-cohort server-optimizer updates from ``{cohort: (avg, Σn,
        n_clients)}``. A configured cohort ABSENT from ``folds`` had no
        surviving member: its adapter stays frozen, and the degradation is
        scoped to exactly that cohort (event + health alert — never the
        round)."""
        from photon_tpu.utils.profiling import (
            EFFECTIVE_LR,
            N_CLIENTS,
            N_SAMPLES,
            PARAM_NORM,
            PSEUDO_GRAD_NORM,
        )

        plane = self.adapter_plane
        updated = 0
        total_samples = 0.0
        g2 = p2 = 0.0
        lr = 0.0
        for name in plane.cohort_names:
            fold = folds.get(name)
            if fold is None:
                self._note_cohort_degraded(server_round, name)
                continue
            avg_c, n_samples, n_clients = fold
            m = plane.strategies.apply_average(
                server_round, name, avg_c, n_samples, n_clients
            )
            updated += 1
            total_samples += m.get(N_SAMPLES, float(n_samples))
            g2 += m.get(PSEUDO_GRAD_NORM, 0.0) ** 2
            p2 += m.get(PARAM_NORM, 0.0) ** 2
            lr = m.get(EFFECTIVE_LR, lr)
        return {
            N_CLIENTS: float(len(cohort)),
            N_SAMPLES: total_samples,
            EFFECTIVE_LR: lr,
            # aggregate norms across cohorts (per-cohort values would
            # collide in one KPI dict): the l2 of the CONCATENATED
            # pseudo-gradients / adapter params
            PSEUDO_GRAD_NORM: float(np.sqrt(g2)),
            PARAM_NORM: float(np.sqrt(p2)),
            ADAPTER_COHORTS: float(updated),
            ADAPTER_COHORTS_DEGRADED: float(plane.n_cohorts - updated),
        }

    def _note_cohort_degraded(self, server_round: int, name: str) -> None:
        telemetry.emit_event(
            EVENT_ADAPTER_COHORT_DEGRADED, round=server_round, cohort=name,
            reason="no surviving members",
        )
        warnings.warn(
            f"adapter round {server_round}: cohort {name!r} has no "
            "surviving members — its adapter is unchanged this round",
            stacklevel=3,
        )
        health = telemetry.health_active()
        if health is not None:
            health.note_cohort_degraded(
                round=server_round, cohort=name,
                reason="no surviving members",
            )

    def _grouped_host_fallback(
        self,
        server_round: int,
        cohort: tuple[int, ...],
        landed: dict[int, tuple[list[np.ndarray], int]],
    ) -> dict[str, float]:
        """Degradation floor of the adapter ladder: the per-cohort host
        streaming fold (``strategy/grouped.grouped_host_fold`` — it IS
        ``aggregate_inplace`` per cohort, so a degraded personalization
        round is bit-exact with the host plane fed the same survivors)."""
        from photon_tpu.strategy.grouped import grouped_host_fold

        plane = self.adapter_plane
        with telemetry.span(COLLECTIVE_EXCHANGE_TIME, degraded=True):
            t0 = time.monotonic()
            folds = grouped_host_fold(
                {cid: landed[cid] for cid in cohort if cid in landed},
                plane.cohort_of,
            )
            fold_s = time.monotonic() - t0
        with telemetry.span(COLLECTIVE_UPDATE_TIME, degraded=True):
            t1 = time.monotonic()
            metrics = self._apply_cohort_updates(server_round, cohort, folds)
            update_s = time.monotonic() - t1
        metrics[COLLECTIVE_STACK_TIME] = 0.0
        metrics[COLLECTIVE_EXCHANGE_TIME] = fold_s
        metrics[COLLECTIVE_UPDATE_TIME] = update_s
        # nothing crossed a slice boundary this round
        metrics[COLLECTIVE_WIRE_BYTES] = 0.0
        metrics[ADAPTER_WIRE_BYTES] = 0.0
        return metrics

    # -- checkpoint bridge --------------------------------------------------
    def state_for_checkpoint(self):
        """Strategy state ready to serialize. On the device-optimizer path
        the state already mirrors to the host strategy after every round
        (:meth:`DeviceAggregationPlane.sync_strategy`), so this is exactly
        ``Strategy.state_for_checkpoint`` — same keys, same ``_t`` handling
        — and a checkpoint written here resumes through
        :meth:`load_server_state` on either path.

        Adapter mode (ISSUE 13): the dict carries one ``adapter__{cohort}``
        entry (the cohort's A/B factors) plus ``astate__{cohort}__{key}``
        entries per server-optimizer state tensor list — all riding the
        same ``save_round`` npz + manifest-CRC machinery, so torn-round
        detection, GC and the serving watcher apply unchanged."""
        if self.adapter_plane is not None:
            from photon_tpu.adapters.checkpoint import (
                adapter_key,
                adapter_state_key,
            )

            st = self.adapter_plane.strategies
            adapters = st.adapters_for_checkpoint()
            opt = st.state_for_checkpoint()
            out = {}
            for name in st.names:
                out[adapter_key(name)] = adapters[name]
                for skey, tensors in opt[name].items():
                    out[adapter_state_key(name, skey)] = tensors
            return out
        return self.strategy.state_for_checkpoint()

    def checkpoint_state_keys(self) -> tuple[str, ...]:
        """The state-key list round validity/resume checks need (global
        mode: the strategy's ``state_keys``; adapter mode: every
        per-cohort adapter + optimizer-state npz)."""
        if self.adapter_plane is not None:
            from photon_tpu.adapters.checkpoint import adapter_state_keys

            return adapter_state_keys(
                self.adapter_plane.cohort_names,
                self.adapter_plane.strategies.state_keys,
            )
        return tuple(self.strategy.state_keys)

    def save_checkpoint(self, mgr, server_round: int) -> None:
        """Write this round through ``ServerCheckpointManager.save_round``
        (manifest written last — the serving hot-swap watcher only ever
        sees completed rounds). Adapter mode saves the FROZEN base as the
        params object and the per-cohort adapters/optimizer state as
        state objects; ``load_adapter_bank`` / :meth:`resume_from` are the
        inverses."""
        if self.adapter_plane is not None:
            meta = self.adapter_plane.base_meta
            params = self.adapter_plane.base_arrays
        else:
            meta, params = self.meta, self.strategy.current_parameters
        mgr.save_round(
            server_round, meta, params,
            strategy_state=self.state_for_checkpoint(),
            server_state={"server_round": server_round,
                          **self.control_state_for_checkpoint()},
        )

    def resume_from(self, mgr, resume_round: int = -1) -> int:
        """Resolve (checksum-verified) + load + re-seed; returns the
        resumed round number."""
        keys = self.checkpoint_state_keys()
        rnd = mgr.resolve_resume_round(resume_round, keys)
        _, params, state, server_state = mgr.load_round(rnd, keys)
        self.load_server_state(params, state, server_state)
        return rnd

    def control_state_for_checkpoint(self) -> dict:
        """The non-tensor control snapshot a resume needs alongside the
        strategy state — same vocabulary as ``ServerApp.save_checkpoint``'s
        ``server_state`` (client sample counters drive loader fast-forward
        after a restart). ``aggregation_paths`` records which aggregation
        path produced each round ("collective" | "collective_reconfigured"
        | "host_fallback" | "failed") so a resume — and anyone auditing the
        manifest-checksummed checkpoint chain (PR 3) — can tell a degraded
        round's parameters from a full-cohort collective's."""
        out = {
            "server_steps_cumulative": self.server_steps_cumulative,
            "client_states": dict(self.client_states),
            "aggregation_paths": {
                int(r): p for r, p in self.aggregation_paths.items()
            },
        }
        if self.adapter_plane is not None:
            # per-cohort adaptive step counters: bias correction stays
            # continuous per cohort across a resume
            out["adapter_t"] = self.adapter_plane.strategies.t_counters()
        return out

    def load_server_state(self, parameters, state=None, control=None) -> None:
        """Resume: re-seed the strategy replica (and, when enabled, the
        device plane) from checkpointed parameters + optimizer state. The
        adaptive strategies' ``_t`` rides ``state`` exactly as in the
        driver topology, so bias correction stays continuous across the
        restart; ``control`` (:meth:`control_state_for_checkpoint`) restores
        the step counter and the per-client loader positions.

        Adapter mode: ``parameters`` is the frozen BASE; ``state`` carries
        the per-cohort ``adapter__*`` / ``astate__*`` entries written by
        :meth:`state_for_checkpoint`."""
        if self.adapter_plane is not None:
            from photon_tpu.adapters.checkpoint import (
                adapter_key,
                adapter_state_key,
            )

            plane = self.adapter_plane
            plane.base_arrays = [np.asarray(p, np.float32) for p in parameters]
            state = state or {}
            adapters: dict[str, list[np.ndarray]] = {}
            opt: dict[str, dict[str, list[np.ndarray]]] = {}
            for name in plane.cohort_names:
                key = adapter_key(name)
                if key not in state:
                    raise ValueError(
                        f"checkpoint carries no adapter for cohort {name!r} "
                        f"(key {key!r}) — cohort map changed since the save?"
                    )
                adapters[name] = state[key]
                opt[name] = {
                    skey: state[adapter_state_key(name, skey)]
                    for skey in plane.strategies.state_keys
                    if adapter_state_key(name, skey) in state
                }
            t = {
                str(k): int(v)
                for k, v in ((control or {}).get("adapter_t", {}) or {}).items()
            }
            plane.strategies.initialize(adapters, opt, t=t)
        else:
            self.strategy.initialize(parameters, state)
        if control:
            self.server_steps_cumulative = int(
                control.get("server_steps_cumulative", self.server_steps_cumulative)
            )
            self.client_states = {
                int(k): v for k, v in control.get("client_states", {}).items()
            }
            self.aggregation_paths = {
                int(k): str(v)
                for k, v in control.get("aggregation_paths", {}).items()
            }
        if self.device_plane is not None:
            self.device_plane = DeviceAggregationPlane(
                self.mesh, self.strategy,
                quantization=self.quantization, block=self.q8_block,
                nonneg_rows=self._nonneg_rows,
                sharded=self.cfg.photon.comm_stack.collective_zero1,
            )

    def evaluate_round(self, server_round: int) -> dict[str, float]:
        """Fed eval over the collective: every controller scores its clients
        on the post-aggregation replica params, then the sample-weighted
        loss rides the same psum machinery as the fit averages (reference:
        ``evaluate_round`` → ``aggregate_evaluate``,
        ``server/evaluate_utils.py:33-158``)."""
        from photon_tpu.federation.messages import EvaluateIns
        from jax.sharding import NamedSharding, PartitionSpec as P

        eval_ptrs: dict = {}
        if self.adapter_plane is not None:
            # personalization: every client scores its OWN cohort's
            # (base + adapter) params — eval measures the model the
            # cohort actually gets served
            eval_ptrs = self._cohort_broadcast_ptrs("eval", server_round)
        else:
            ptr = self.transport.put(
                f"collective-eval-r{server_round}", self.meta, self.strategy.current_parameters
            )
            self.runtime.set_broadcast_params(ptr)
        losses: list[np.ndarray] = []
        ns: list[int] = []
        for cid in self.process_cids:
            ins = EvaluateIns(
                server_round=server_round, cids=[cid],
                params=(eval_ptrs[self.adapter_plane.cohort_of.get(cid)]
                        if self.adapter_plane is not None else None),
                config=dict(self.cfg.fl.eval_config),
            )
            res = self.runtime.evaluate(ins, cid)
            nid = self._client_node_id(cid)
            if res.error:
                # elastic eval (ISSUE 8): a failed eval client scores with
                # ZERO weight — the full-mesh program still runs (no
                # reconfiguration compile for an eval), and a zero-n row
                # drops out of the weighted mean exactly
                self.liveness.observe_miss(nid)
                telemetry.emit_event(
                    EVENT_COLLECTIVE_STRAGGLER, round=server_round, cid=cid,
                    reason="eval_error", detail=res.error[:200],
                )
                warnings.warn(
                    f"collective eval round {server_round}: cid {cid} failed "
                    f"({res.error.splitlines()[0][:120]}) — scored with zero "
                    "weight",
                    stacklevel=2,
                )
                losses.append(np.asarray([0.0], np.float32))
                ns.append(0)
                continue
            self.liveness.observe_alive(nid)
            losses.append(np.asarray([res.loss], np.float32))
            ns.append(res.n_samples)
        for ptr in eval_ptrs.values():
            self.transport.free(ptr)

        # losses are [1]-vectors — quantizing them would be all cost, no
        # byte savings, so eval always rides the fp32 exchange. The
        # exchange runs under the same stage deadline as a fit round's: a
        # dead peer must not wedge the eval that follows a survived round
        def _exchange():
            loss_global = self._stack_local([[l] for l in losses])[0]
            ns_global = jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, P(CLIENT_AXIS)),
                np.asarray(ns, np.int32),
                (self.cfg.fl.n_total_clients,),
            )
            avg, total = hierarchical_weighted_average(
                [loss_global], ns_global, self.mesh, return_total=True
            )
            return float(np.asarray(avg[0])[0]), float(np.asarray(total))

        try:
            loss, total = self._run_stage(
                "eval-exchange", _exchange, self._stage_deadline()
            )
        except Exception as e:  # noqa: BLE001 — same stance as the fit
            # ladder: a torn gang surfaces as a hang (deadline) or a
            # distributed-runtime error; eval has no retry budget, it falls
            # straight back to the local weighted mean (cohort-agreement
            # caveat: multi-controller, this is this controller's slice)
            warnings.warn(
                f"collective eval round {server_round}: exchange failed "
                f"({type(e).__name__}: {e}) — falling back to the local "
                "weighted mean",
                stacklevel=2,
            )
            local_n = int(sum(ns))
            loss = (
                float(np.dot([float(l[0]) for l in losses], ns)) / local_n
                if local_n else 0.0
            )
            total = float(local_n)
        if total == 0:
            warnings.warn(
                f"collective eval round {server_round}: no eval samples "
                "landed — eval skipped",
                stacklevel=2,
            )
            metrics = {EVAL_SAMPLES: 0.0}
            self.history.record(server_round, metrics)
            return metrics
        metrics = {EVAL_LOSS: loss, EVAL_SAMPLES: total}
        self.history.record(server_round, metrics)
        return metrics

    def run(self, n_rounds: int | None = None) -> History:
        n_rounds = n_rounds if n_rounds is not None else self.cfg.fl.n_rounds
        every = self.cfg.fl.eval_interval_rounds
        if every:
            # round-0 baseline on the initial parameters — the driver
            # topology records it (server.py run()) and eval-curve parity
            # across planes needs the same starting point
            self.evaluate_round(0)
        for rnd in range(1, n_rounds + 1):
            self.run_round(rnd)
            if every and rnd % every == 0:
                self.evaluate_round(rnd)
        return self.history


def main(argv: list[str] | None = None) -> None:
    import argparse
    import json

    ap = argparse.ArgumentParser(
        prog="photon_tpu.federation.collective_round",
        description="multi-controller federated rounds over XLA collectives",
    )
    ap.add_argument("--coordinator", required=True, help="host:port of process 0")
    ap.add_argument("--num-processes", type=int, required=True)
    ap.add_argument("--process-id", type=int, required=True)
    ap.add_argument("--config", required=True, help="resolved config YAML")
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args(argv)

    from photon_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    jax.distributed.initialize(
        args.coordinator, num_processes=args.num_processes, process_id=args.process_id
    )
    cfg = Config.from_yaml(args.config)
    cfg.photon.comm_stack.collective = True
    cfg.validate()
    cids = partition_cids(cfg.fl.n_total_clients, args.num_processes, args.process_id)
    runner = CollectiveFedRunner(cfg, cids)
    history = runner.run(args.rounds)
    out = {"rounds": args.rounds or cfg.fl.n_rounds, "process_id": args.process_id}
    for key in ("server/round_time", "server/pseudo_grad_norm", "server/steps_cumulative"):
        latest = history.latest(key)
        if latest is not None:
            out[key] = latest
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
