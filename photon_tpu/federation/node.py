"""NodeAgent: per-host agent executing federation tasks on a ClientRuntime.

Role parity with the reference's NodeManagerApp + ClientApp handlers
(``photon/node_manager/node_manager_app.py``, ``photon/client_app.py``), with
the worker-process gang deleted: JAX already owns every chip of the host via
one mesh, so the node IS the training executor (SURVEY.md §7 design stance).

The agent serves a request loop over a duplex connection (mp.Pipe or a
socket): FitIns / EvaluateIns / Broadcast / Query envelopes in, result
envelopes out. ``Query("refresh")`` rebuilds the runtime — the analog of the
reference's periodic worker restart (``client_app.py:175-177``).
"""

from __future__ import annotations

import traceback
from typing import Any, Callable

from photon_tpu import chaos, telemetry
from photon_tpu.config.schema import Config
from photon_tpu.federation.client_runtime import ClientRuntime
from photon_tpu.federation.messages import (
    Ack,
    Broadcast,
    Envelope,
    EvaluateIns,
    EvaluateRes,
    FitIns,
    FitRes,
    Query,
)
from photon_tpu.federation.transport import ParamTransport
from photon_tpu.utils.profiling import NODE_SET_BROADCAST_SPAN


class NodeAgent:
    def __init__(
        self,
        cfg: Config,
        node_id: str,
        make_transport: Callable[[], ParamTransport],
        make_ckpt_mgr: Callable[[], Any] | None = None,
    ) -> None:
        self.cfg = cfg
        self.node_id = node_id
        self._make_transport = make_transport
        self._make_ckpt_mgr = make_ckpt_mgr
        self.runtime = self._build_runtime()

    def _build_runtime(self) -> ClientRuntime:
        return ClientRuntime(
            self.cfg,
            self._make_transport(),
            node_id=self.node_id,
            ckpt_mgr=self._make_ckpt_mgr() if self._make_ckpt_mgr else None,
        )

    # -- dispatch --------------------------------------------------------
    def handle(self, msg: Any) -> Any:
        if isinstance(msg, FitIns):
            chaos.crash_point("pre-fit", msg.server_round, self.node_id)
            return [self.runtime.fit(msg, cid) for cid in msg.cids]
        if isinstance(msg, EvaluateIns):
            return [self.runtime.evaluate(msg, cid) for cid in msg.cids]
        if isinstance(msg, Broadcast):
            try:
                with telemetry.span(NODE_SET_BROADCAST_SPAN,
                                    round=msg.server_round, node=self.node_id):
                    self.runtime.set_broadcast_params(msg.params)
                return Ack(ok=True, node_id=self.node_id)
            except Exception as e:  # noqa: BLE001
                return Ack(ok=False, detail=f"{type(e).__name__}: {e}", node_id=self.node_id)
        if isinstance(msg, Query):
            return self._query(msg)
        return Ack(ok=False, detail=f"unknown message {type(msg).__name__}", node_id=self.node_id)

    def _query(self, q: Query) -> Ack:
        if q.action == "ping":
            return Ack(ok=True, node_id=self.node_id)
        if q.action == "refresh":
            # worker-refresh analog: drop runtime (jit caches, loaders), rebuild
            states = self.runtime.loader_states()
            self.runtime.close()
            self.runtime = self._build_runtime()
            del states  # loaders rebuild from FitIns-provided state
            return Ack(ok=True, node_id=self.node_id)
        if q.action == "free_resources":
            self.runtime.transport.cleanup()
            return Ack(ok=True, node_id=self.node_id)
        if q.action == "shutdown":
            self.runtime.close()
            return Ack(ok=True, detail="bye", node_id=self.node_id)
        return Ack(ok=False, detail=f"unknown query {q.action!r}", node_id=self.node_id)

    def _piggyback_telemetry(self, reply: Any) -> None:
        """Drain this process's completed spans + buffered events onto the
        outgoing reply (the server ingests them into the merged timeline).
        Fit/eval results are the main channel; single Acks (broadcast,
        ping, shutdown) carry the buffers too, so a node that is never
        sampled for a fit still flushes its reconnect events and
        transport-leg spans on every ping sweep. Only for piggyback-mode
        tracers — an in-process node shares the SERVER's tracer, where
        draining would momentarily pull server spans out of the export
        buffer."""
        tr = telemetry.active()
        if tr is None or not tr.piggyback:
            return
        if isinstance(reply, list):
            carriers = [r for r in reply if isinstance(r, (FitRes, EvaluateRes))]
            carrier = carriers[-1] if carriers else None
        elif isinstance(reply, Ack):
            carrier = reply
        else:
            carrier = None
        if carrier is None:
            return
        carrier.spans = tr.drain()
        carrier.events = telemetry.drain_events()

    # -- serving loop (child process entry) ------------------------------
    def serve(self, conn) -> bool:
        """Blocking loop over a Connection-like object with send/recv.

        Returns True after a clean ``shutdown`` query, False when the peer
        vanished (EOF / corrupt frame) — the distinction is what lets the
        TCP supervisor (``tcp.run_node``) redial on connection loss instead
        of mistaking it for an orderly exit.

        Requests are deduplicated by ``msg_id`` (driver mids are unique
        monotonic counters): a chaos-duplicated / network-repeated FitIns
        must not run the fit twice — the second run would double-advance
        per-cid loader/optimizer state and silently skip training data."""
        from collections import deque

        recent: deque[int] = deque(maxlen=256)
        recent_set: set[int] = set()
        while True:
            try:
                env: Envelope = conn.recv()
            except EOFError:
                # a corrupt or unpicklable frame arrives as CorruptFrameError
                # (an EOFError): a broken stream like any EOF — hand control
                # back so the supervisor redials instead of dying for good
                return False
            if env.msg_id in recent_set:
                continue  # duplicate delivery: the first reply stands
            if len(recent) == recent.maxlen:
                recent_set.discard(recent[0])
            recent.append(env.msg_id)
            recent_set.add(env.msg_id)
            try:
                # envelope trace context = the sending server span: spans
                # opened while handling parent to it across the process
                # boundary (a no-op context when telemetry is off)
                with telemetry.attach(env.trace):
                    reply = self.handle(env.msg)
            except Exception as e:  # noqa: BLE001 — never kill the loop silently
                reply = Ack(
                    ok=False,
                    detail=f"{type(e).__name__}: {e}\n{traceback.format_exc()}",
                    node_id=self.node_id,
                )
            self._piggyback_telemetry(reply)
            if isinstance(reply, list) and any(isinstance(r, FitRes) for r in reply):
                # work done, result not yet on the wire — the nastiest crash
                # window (the server must charge the cid to its budget AND
                # the rejoined node must not double-report)
                chaos.crash_point(
                    "pre-reply", getattr(env.msg, "server_round", 0), self.node_id
                )
            conn.send(Envelope(reply, env.msg_id))
            if isinstance(env.msg, Query) and env.msg.action == "shutdown":
                return True


def node_process_main(cfg_json: str, node_id: str, conn, platform: str | None, n_cpu_devices: int) -> None:
    """Entry point for a spawned node process (reference:
    ``flower-client-app`` process). Platform is pinned before first backend
    use — tests force CPU with N virtual devices."""
    import jax

    if platform:
        jax.config.update("jax_platforms", platform)
        if platform == "cpu" and n_cpu_devices > 1:
            jax.config.update("jax_num_cpu_devices", n_cpu_devices)
    from photon_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()

    cfg = Config.from_json(cfg_json)
    chaos.install(cfg.photon.chaos, scope=node_id)
    # spawned node: buffer spans/events locally, ship them back piggybacked
    # on fit/eval results (the server holds the merged timeline)
    telemetry.install(cfg.photon.telemetry, scope=node_id, piggyback=True)
    store = None
    if cfg.photon.comm_stack.objstore or cfg.photon.checkpoint:
        from photon_tpu.checkpoint.store import FileStore

        store = FileStore(cfg.photon.save_path + "/store")

    def make_transport() -> ParamTransport:
        mode = "objstore" if cfg.photon.comm_stack.objstore else "shm"
        return ParamTransport(mode, store=store, compression=cfg.photon.compression,
                              host_threads=cfg.photon.host_threads)

    def make_ckpt():
        from photon_tpu.checkpoint.client import ClientCheckpointManager

        return ClientCheckpointManager(store, cfg.run_uuid) if store else None

    agent = NodeAgent(cfg, node_id, make_transport, make_ckpt)
    agent.serve(conn)
