"""Fleet supervision: the replica-side control agent + N-replica spawn.

The router↔replica control plane is the federation stack reused whole
(ISSUE 16): a replica dials the router's :class:`TcpServerDriver` and
HELLOs exactly like a federation node (``federation/tcp.py``), then
answers ``Query`` actions over the CRC-framed socket:

- ``ping``          — liveness ack (LivenessTracker.sweep compatible)
- ``fleet_report``  — data port + cohorts + round + the batcher's
  :meth:`load_report` (the router's routing/liveness signal, one
  round-trip for both)
- ``drain``         — flip the frontend to draining and start the
  batcher drain in the background (the ack must not wait on it: a
  blocked control loop would look like a dead replica)
- ``hotswap``       — run one CheckpointWatcher poll (the PR 10 quiesce
  swap; zero dropped requests), reply with {swapped, round}
- ``shutdown``      — ack and exit the agent loop cleanly

Connection loss redials with the same jittered-backoff supervisor
``run_node`` uses (``ReconnectPolicy`` + re-HELLO + ``tcp/reconnect``
events) — the PR 3/8 machinery IS the control plane, not new code.

Two fleet shapes:

- :class:`InProcessFleet` — N replicas as threads in one process (the
  tests' emulated fleet: one jax compile cache, no port races).
  ``kill_replica`` emulates SIGKILL: both planes go silent mid-flight,
  nothing is drained.
- :class:`FleetSupervisor` — N real daemon subprocesses
  (``python -m photon_tpu.serve --fleet-connect``), SIGKILL-able for the
  chaos e2e, SIGTERM-drained on close.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
import zlib
from typing import Any

from photon_tpu import telemetry
from photon_tpu.federation.membership import ReconnectPolicy
from photon_tpu.federation.messages import Ack, Envelope, Query
from photon_tpu.federation.tcp import HELLO_KIND, SocketConn
from photon_tpu.utils.profiling import COMPILES_TOTAL, EVENT_TCP_RECONNECT


class ReplicaAgent:
    """Control-plane agent thread inside one serving replica.

    Owns nothing but the socket: the batcher/frontend/watcher are the
    daemon's, passed in. ``drain_timeout_s`` bounds the background drain
    a ``drain`` query starts."""

    def __init__(self, control_addr: str, replica_id: str, *,
                 batcher: Any, frontend: Any, watcher: Any = None,
                 policy: ReconnectPolicy | None = None,
                 drain_timeout_s: float = 30.0) -> None:
        self.control_addr = control_addr
        self.replica_id = replica_id
        self.batcher = batcher
        self.frontend = frontend
        self.watcher = watcher
        self.drain_timeout_s = drain_timeout_s
        self.policy = policy or ReconnectPolicy(
            base_s=0.1, max_s=2.0, jitter=0.25,
            rng=__import__("random").Random(zlib.crc32(replica_id.encode())),
        )
        self._stop = threading.Event()
        self._conn: SocketConn | None = None
        self._thread: threading.Thread | None = None

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "ReplicaAgent":
        self._thread = threading.Thread(
            target=self._supervise, name=f"photon-fleet-agent-{self.replica_id}",
            daemon=True,
        )
        self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        """Orderly local stop (the clean path is the router's shutdown
        query; this covers teardown when the router is already gone)."""
        self._stop.set()
        conn = self._conn
        if conn is not None:
            conn.close()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None

    def kill(self) -> None:
        """Emulated SIGKILL (in-process fleets): the control socket dies
        mid-stream and the supervisor loop never redials — the router
        sees exactly what a killed process looks like."""
        self._stop.set()
        conn = self._conn
        if conn is not None:
            conn.close()

    # -- supervisor loop (run_node shape) ---------------------------------
    def _supervise(self) -> None:
        host, _, port = self.control_addr.rpartition(":")
        attempt = 0
        reconnects = 0
        backoff_total = 0.0
        while not self._stop.is_set():
            try:
                sock = socket.create_connection((host, int(port)), timeout=10)
            except OSError:
                attempt += 1
                if self.policy.exhausted(attempt):
                    return
                d = self.policy.delay(attempt - 1)
                backoff_total += d
                self._stop.wait(d)
                continue
            attempt = 0
            conn = SocketConn(sock)
            self._conn = conn
            clean = False
            try:
                conn.send({
                    "kind": HELLO_KIND,
                    "node_id": self.replica_id,
                    "reconnects": reconnects,
                    "backoff_s": backoff_total,
                })
                clean = self._serve(conn)
            except OSError:
                clean = False
            finally:
                conn.close()
                self._conn = None
            if clean or self._stop.is_set():
                return
            # router went away: back off, redial, re-HELLO — the same
            # supervisor contract as federation nodes
            reconnects += 1
            d = self.policy.delay(0)
            backoff_total += d
            telemetry.emit_event(
                EVENT_TCP_RECONNECT, node=self.replica_id,
                reconnects=reconnects, backoff_s=d,
                backoff_total_s=backoff_total,
            )
            self._stop.wait(d)

    def _serve(self, conn: SocketConn) -> bool:
        while True:
            try:
                env: Envelope = conn.recv()
            except EOFError:
                return False  # torn stream (incl. corrupt frame): redial
            msg = env.msg
            if isinstance(msg, Query):
                try:
                    reply = self._handle(msg)
                except Exception as e:  # noqa: BLE001 — never kill the loop
                    reply = Ack(ok=False, detail=f"{type(e).__name__}: {e}",
                                node_id=self.replica_id)
            else:
                reply = Ack(ok=False,
                            detail=f"unexpected {type(msg).__name__}",
                            node_id=self.replica_id)
            conn.send(Envelope(reply, env.msg_id))
            if isinstance(msg, Query) and msg.action == "shutdown":
                return True

    # -- query handlers ----------------------------------------------------
    def _handle(self, q: Query) -> Ack:
        if q.action in ("ping", "shutdown"):
            return Ack(ok=True, node_id=self.replica_id)
        if q.action == "fleet_report":
            return Ack(ok=True, node_id=self.replica_id,
                       detail=json.dumps(self.report()))
        if q.action == "drain":
            self.frontend.mark_draining()
            threading.Thread(
                target=self.batcher.drain, args=(self.drain_timeout_s,),
                name=f"photon-fleet-drain-{self.replica_id}", daemon=True,
            ).start()
            return Ack(ok=True, node_id=self.replica_id)
        if q.action == "restart":
            # soft restart (ISSUE 19): quiesce in place, not process death.
            # The frontend 503s while the batcher recycles (bounded drain +
            # cache/pool flush); serving resumes on the same engine. The ack
            # must not wait on the drain — same contract as ``drain``.
            self.frontend.mark_draining()
            threading.Thread(
                target=self._recycle,
                name=f"photon-fleet-restart-{self.replica_id}", daemon=True,
            ).start()
            return Ack(ok=True, node_id=self.replica_id)
        if q.action == "hotswap":
            if self.watcher is None:
                return Ack(ok=False, detail="no hot-swap watcher",
                           node_id=self.replica_id)
            outcome = self.watcher.poll_once()
            return Ack(ok=True, node_id=self.replica_id, detail=json.dumps({
                "swapped": outcome == "swapped",
                "outcome": outcome,
                "round": self.batcher.engine.loaded_round,
            }))
        return Ack(ok=False, detail=f"unknown action {q.action!r}",
                   node_id=self.replica_id)

    def _recycle(self) -> None:
        try:
            self.batcher.recycle(self.drain_timeout_s)
        finally:
            self.frontend.draining = False

    def report(self) -> dict:
        eng = self.batcher.engine
        cohorts: list = []
        if getattr(eng, "adapter_pool", None) is not None:
            cohorts = list(eng.adapter_pool.cohorts())
        rep = {
            "host": self.frontend.host,
            "port": self.frontend.port,
            "cohorts": cohorts,
            "round": eng.loaded_round if eng.loaded_round is not None else -1,
        }
        rep.update(self.batcher.load_report())
        # replica health + compile telemetry ride the same round-trip
        # (ISSUE 19): the router's autopilot decides restarts from these
        health = telemetry.health_active()
        if health is not None:
            plane = health.statusz().get("planes", {}).get("serve")
            if plane is not None:
                rep["health"] = {
                    "status": plane.get("status"),
                    "reason": plane.get("reason"),
                }
        hub = telemetry.metrics_active()
        if hub is not None:
            rep["compiles"] = float(hub.counter(COMPILES_TOTAL).value)
        return rep


class InProcessFleet:
    """N replica engines as threads behind one router, one process.

    The emulated fleet ``tests/test_router.py`` runs on: every
    replica is a full engine + batcher + HTTP frontend + control agent —
    only the process boundary is emulated. Same-config replicas share
    the jax compile cache, so N engines compile once.

    ``params_for(i)`` defaults to sharing one params tree across
    replicas (placement must never change outputs, so identical params
    are the oracle condition)."""

    def __init__(self, cfg, params, *, mode: str = "affinity",
                 loaded_round: int | None = None,
                 adapter_bank: dict | None = None) -> None:
        from photon_tpu.serve.engine import PagedEngine
        from photon_tpu.serve.frontend import ServeFrontend
        from photon_tpu.serve.router import FleetRouter
        from photon_tpu.serve.scheduler import ContinuousBatcher

        self.cfg = cfg
        sc = cfg.photon.serve
        fc = sc.fleet
        self.router = FleetRouter(
            fc, block_size=sc.block_size, mode=mode,
            kill_hook=self.kill_replica,
        )
        control_addr = f"{fc.host}:{self.router.control_port}"
        self.replicas: dict[str, dict] = {}
        for i in range(fc.replicas):
            rid = f"replica{i}"
            engine = PagedEngine(cfg, params, loaded_round=loaded_round,
                                 adapter_bank=adapter_bank)
            batcher = ContinuousBatcher(
                engine,
                max_queue=sc.max_queue,
                prefill_token_budget=sc.prefill_token_budget,
                default_eos_id=sc.eos_id if sc.eos_id >= 0 else None,
                speculative=sc.speculative,
            ).start()
            frontend = ServeFrontend(
                batcher, host=fc.host, port=0,
                max_new_tokens_cap=sc.max_new_tokens,
            )
            frontend.start()
            agent = ReplicaAgent(
                control_addr, rid, batcher=batcher, frontend=frontend,
                drain_timeout_s=sc.drain_timeout_s,
            ).start()
            self.replicas[rid] = {
                "engine": engine, "batcher": batcher,
                "frontend": frontend, "agent": agent, "killed": False,
            }

    def start(self, timeout: float = 60.0) -> int:
        """Start the router (after every replica HELLOed + reported) and
        return its data-plane port."""
        port = self.router.start()
        self.router.wait_for_replicas(timeout=timeout)
        return port

    @property
    def url(self) -> str:
        return f"http://{self.cfg.photon.serve.fleet.host}:{self.router.port}"

    def kill_replica(self, rid: str) -> None:
        """Emulated SIGKILL: both planes go silent at once — the HTTP
        frontend closes (connects refuse), the control agent's socket
        dies without a goodbye, and nothing drains. In-flight requests on
        THIS replica are lost (that is the point); survivors see nothing."""
        rep = self.replicas.get(rid)
        if rep is None or rep["killed"]:
            return
        rep["killed"] = True
        rep["agent"].kill()
        rep["frontend"].close()
        rep["batcher"].close(timeout=1.0)

    def close(self) -> None:
        self.router.close()
        for rep in self.replicas.values():
            if rep["killed"]:
                continue
            rep["agent"].stop()
            rep["frontend"].close()
            rep["batcher"].close()


class FleetSupervisor:
    """N real serving daemons as subprocesses (the production shape).

    Each child is ``python -m photon_tpu.serve --fleet-connect
    HOST:PORT --replica-id rN --port 0`` — today's daemon unchanged plus
    a control agent; the bound data port reaches the router over the
    control plane, so N children race no ports. ``kill_replica`` is a
    real ``SIGKILL`` (the chaos e2e's mid-traffic death); ``close`` is
    SIGTERM per child — each daemon's own graceful-drain path."""

    def __init__(self, config_path: str, control_addr: str, n_replicas: int,
                 *, extra_args: tuple = (), env: dict | None = None) -> None:
        self.procs: dict[str, subprocess.Popen] = {}
        for i in range(n_replicas):
            rid = f"replica{i}"
            cmd = [
                sys.executable, "-m", "photon_tpu.serve",
                "--config", config_path, "--enable",
                "--port", "0",
                "--fleet-connect", control_addr,
                "--replica-id", rid,
                *extra_args,
            ]
            self.procs[rid] = subprocess.Popen(
                cmd, env=dict(os.environ, **(env or {})),
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            )

    def kill_replica(self, rid: str) -> None:
        """SIGKILL — no drain, no goodbye; the router's liveness ladder
        is what notices."""
        p = self.procs.get(rid)
        if p is not None and p.poll() is None:
            p.kill()

    def alive(self) -> list[str]:
        return sorted(r for r, p in self.procs.items() if p.poll() is None)

    def close(self, timeout: float = 30.0) -> None:
        for p in self.procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.monotonic() + timeout
        for p in self.procs.values():
            left = max(0.1, deadline - time.monotonic())
            try:
                p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=5)
            if p.stdout is not None:
                p.stdout.close()
