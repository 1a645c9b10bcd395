"""Fleet router: N-replica scale-out serving on state locality (ISSUE 16).

One front door over N engine replicas — each today's serving daemon
unchanged, on its own port. The router owns PLACEMENT only; it never
touches tokens, so routed greedy completions are bit-exact against a
single-engine oracle by construction (the per-slot purity the serving
engine already pins batch-mate independence on).

Placement policy, in precedence order (:class:`AffinityRouter`):

1. **cohort affinity** — a request carrying ``cohort`` pins sticky to one
   replica (first placement via rendezvous hashing over the live set), so
   that replica's adapter pool stays hot for its tenant set. A pin on a
   dead replica re-pins to a survivor (``fleet/cohort_repin``).
2. **prefix affinity** — the chain-hash digest of the prompt's first
   ``prefix_affinity_blocks`` full blocks (``serve/prefix.py``: digest j
   identifies the WHOLE prefix through block j) rendezvous-hashes over
   live replicas, so shared-system-prompt traffic converges on the
   replica whose prefix cache already holds those KV blocks — no routing
   table, no coordination, stable under membership churn (HRW moves only
   the keys that lived on the dead replica).
3. **power-of-two-choices** — no affinity key: sample two live replicas,
   place on the lower queue depth (live-slot fraction, then id, break
   ties). The classic exponential improvement over random with only a
   cheap cached load signal (:meth:`ContinuousBatcher.load_report`).

Control plane = the CRC-framed ``federation/tcp.py`` stack, reused whole:
replicas dial in and HELLO like federation nodes (redial supervisor,
backoff, re-HELLO — ``serve/fleet.py``), the router polls a
``fleet_report`` query per replica per cycle (the reply carries the data
port, cohorts, round, and load report), and a missed report walks the
:class:`LivenessTracker` ladder exactly like a missed ping: live →
suspect → dead → readmitted. Death re-pins cohorts, degrades the
``fleet`` health plane (``alert/fleet_replica_dead``), and takes the
replica out of placement; in-flight requests on survivors are untouched.
A connect failure BEFORE any response byte reroutes to a survivor;
after bytes flow the error surfaces to the client (never silently
replayed — generation is not idempotent under temperature sampling).

Data plane = HTTP proxy (stdlib ``http.client``), chunked streaming
passed through chunk-by-chunk so token streaming survives the hop.
"""

from __future__ import annotations

import dataclasses
import hashlib
import http.client
import json
import random
import threading
import time
import warnings
import weakref
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable

from photon_tpu import chaos, telemetry
from photon_tpu.federation.membership import DEAD, LivenessTracker
from photon_tpu.federation.messages import Ack, Query
from photon_tpu.federation.tcp import TcpServerDriver
from photon_tpu.metrics.history import History
from photon_tpu.serve.prefix import prefix_hashes
from photon_tpu.telemetry.prom import negotiate_exposition, render_exposition
from photon_tpu.utils.profiling import (
    ALERT_HBM_GROWTH,
    EVENT_FLEET_COHORT_REPIN,
    EVENT_FLEET_REPLICA_DEAD,
    EVENT_FLEET_REPLICA_UP,
    EVENT_FLEET_ROLLING_SWAP,
    ROUTER_COHORT_REPINS,
    ROUTER_PROXY_ERRORS,
    ROUTER_REPLICAS_DEAD,
    ROUTER_REPLICAS_LIVE,
    ROUTER_REPLICAS_SUSPECT,
    ROUTER_REQUESTS_TOTAL,
    ROUTER_REROUTES,
    ROUTER_ROUTED_COHORT,
    ROUTER_ROUTED_P2C,
    ROUTER_ROUTED_PREFIX,
    SERVE_FLEET_REPLICAS,
    SERVE_FLEET_ROLLING_SWAPS,
)


class NoReplicasError(RuntimeError):
    """No live replica can take a placement — the fleet is down/draining."""


def rendezvous_pick(key: bytes, candidates: list[str]) -> str:
    """Highest-random-weight (rendezvous) hash: every caller agrees on the
    winner for ``key`` without shared state, and removing a candidate
    moves ONLY the keys that lived on it — exactly the stability a
    prefix-cache placement needs across replica churn."""
    if not candidates:
        raise NoReplicasError("rendezvous over an empty replica set")
    return max(
        candidates,
        key=lambda rid: hashlib.blake2b(
            key + b"|" + rid.encode(), digest_size=8
        ).digest(),
    )


@dataclasses.dataclass
class ReplicaState:
    """What the router knows about one replica (from its fleet reports)."""

    replica_id: str
    host: str = ""
    port: int = 0  # data-plane HTTP port; 0 = not yet reported
    cohorts: tuple = ()
    loaded_round: int = -1
    queue_depth: int = 0
    live_slot_frac: float = 0.0
    draining: bool = False

    def to_dict(self) -> dict:
        return {
            "host": self.host, "port": self.port,
            "cohorts": list(self.cohorts), "round": self.loaded_round,
            "queue_depth": self.queue_depth,
            "live_slot_frac": self.live_slot_frac,
            "draining": self.draining,
        }


class AffinityRouter:
    """The pure placement policy — no sockets, unit-testable in isolation.

    Callers pass the CURRENT live set and load snapshot; the only state
    held here is the sticky cohort → replica pin map. ``mode="random"``
    is the control: uniform placement, affinity machinery bypassed (what a
    serving cell would measure affinity against; ROADMAP R8 iii).
    """

    def __init__(self, *, block_size: int, prefix_affinity_blocks: int = 4,
                 cohort_affinity: bool = True, mode: str = "affinity",
                 rng: random.Random | None = None) -> None:
        self.block_size = block_size
        self.prefix_affinity_blocks = prefix_affinity_blocks
        self.cohort_affinity = cohort_affinity
        self.mode = mode
        self.rng = rng or random.Random(0x5EED)
        self.pins: dict[str, str] = {}  # cohort -> replica id

    def prefix_key(self, prompt: list[int] | None) -> bytes | None:
        """The routing key: the LAST chain-hash digest of the prompt's
        first ``prefix_affinity_blocks`` full blocks — it identifies the
        whole shared prefix, so two prompts share a key iff they share
        every routed block (``serve/prefix.py`` chain property)."""
        if (self.prefix_affinity_blocks <= 0 or prompt is None
                or len(prompt) < self.block_size):
            return None
        hashes = prefix_hashes(
            list(prompt), self.block_size, limit=self.prefix_affinity_blocks
        )
        return hashes[-1] if hashes else None

    def route(self, prompt: list[int] | None, cohort: str | None,
              live: list[str],
              loads: dict[str, ReplicaState]) -> tuple[str, str]:
        """Place one request: ``(replica_id, reason)`` with reason one of
        ``cohort``/``prefix``/``p2c``/``random``. ``live`` must be the
        caller's current live set (sorted for determinism)."""
        if not live:
            raise NoReplicasError("no live replicas")
        if self.mode == "random":
            return self.rng.choice(live), "random"
        if cohort and self.cohort_affinity:
            pinned = self.pins.get(cohort)
            if pinned not in live:
                pinned = rendezvous_pick(b"cohort|" + cohort.encode(), live)
                self.pins[cohort] = pinned
            return pinned, "cohort"
        key = self.prefix_key(prompt)
        if key is not None:
            return rendezvous_pick(b"prefix|" + key, live), "prefix"
        return self._p2c(live, loads), "p2c"

    def _p2c(self, live: list[str], loads: dict[str, ReplicaState]) -> str:
        if len(live) == 1:
            return live[0]
        a, b = self.rng.sample(live, 2)

        def load_key(rid: str) -> tuple:
            st = loads.get(rid)
            if st is None:
                return (0, 0.0, rid)
            return (st.queue_depth, st.live_slot_frac, rid)

        return min(a, b, key=load_key)

    def repin_dead(self, dead: str, live: list[str]) -> list[tuple[str, str]]:
        """Move every cohort pinned to ``dead`` onto a survivor; returns
        ``[(cohort, new_replica), ...]``. With no survivors the pins drop
        (the next placement re-pins when the fleet recovers)."""
        moved: list[tuple[str, str]] = []
        for cohort, rid in list(self.pins.items()):
            if rid != dead:
                continue
            if live:
                new = rendezvous_pick(b"cohort|" + cohort.encode(), live)
                self.pins[cohort] = new
                moved.append((cohort, new))
            else:
                del self.pins[cohort]
        return moved


class FleetRouter:
    """The router tier: control-plane supervisor + HTTP front door.

    Threads: one poll loop owning ALL driver send/recv traffic (load
    reports double as liveness pings), plus the stdlib HTTP handler
    threads proxying requests. The two never share the control socket —
    :meth:`rolling_hotswap`/:meth:`drain_fleet` serialize against the
    poll loop on ``_ctl_lock``.
    """

    def __init__(self, fleet_cfg, *, block_size: int,
                 mode: str = "affinity",
                 request_timeout_s: float = 120.0,
                 kill_hook: Callable[[str], None] | None = None) -> None:
        self.fc = fleet_cfg
        self.request_timeout_s = request_timeout_s
        #: chaos replica-kill effector (ISSUE 16): the supervisor wires
        #: this to SIGKILL the victim's process; None = no kill capability
        self.kill_hook = kill_hook
        self.driver = TcpServerDriver(
            fleet_cfg.host, fleet_cfg.control_port,
            expected_nodes=fleet_cfg.replicas,
        )
        self.tracker = LivenessTracker(
            ping_timeout_s=fleet_cfg.report_timeout_s
        )
        self.policy = AffinityRouter(
            block_size=block_size,
            prefix_affinity_blocks=fleet_cfg.prefix_affinity_blocks,
            cohort_affinity=fleet_cfg.cohort_affinity,
            mode=mode,
        )
        self.replicas: dict[str, ReplicaState] = {}
        self.history = History()
        # cumulative routing counters (lock-guarded; mirrored into the
        # History as router/* KPIs each poll tick)
        self.requests_total = 0
        self.routed_prefix = 0
        self.routed_cohort = 0
        self.routed_p2c = 0
        self.reroutes = 0
        self.proxy_errors = 0
        self.cohort_repins = 0
        self.rolling_swaps = 0
        self._lock = threading.Lock()  # replicas + pins + counters
        self._ctl_lock = threading.Lock()  # exclusive driver send/recv use
        self._last_states: dict[str, str] = {}
        # replica-restart autopilot state (ISSUE 19): last reported compile
        # total + consecutive-growth streak per replica, and the restarts
        # approved during a poll cycle — executed AFTER the control lock is
        # released (the restart query needs it; issuing inside the ingest
        # would deadlock)
        self._compiles: dict[str, float] = {}
        self._compile_streaks: dict[str, int] = {}
        self._pending_restarts: list[str] = []
        self._tick = 0
        self._stop = threading.Event()
        self._poll_thread: threading.Thread | None = None
        self._httpd: ThreadingHTTPServer | None = None
        self._http_thread: threading.Thread | None = None
        self.port = fleet_cfg.port
        self.draining = False

    # -- control plane ----------------------------------------------------
    @property
    def control_port(self) -> int:
        return self.driver.port

    def wait_for_replicas(self, timeout: float = 60.0) -> None:
        """Block until ``fleet.replicas`` HELLOed, then poll once so every
        replica's data port is known before the first placement."""
        self.driver.wait_for_nodes(timeout=timeout)
        deadline = time.monotonic() + timeout
        ready: list[ReplicaState] = []
        while time.monotonic() < deadline:
            self.poll_once()
            with self._lock:
                ready = [r for r in self.replicas.values() if r.port]
            if len(ready) >= self.fc.replicas:
                return
            time.sleep(0.05)
        raise TimeoutError(
            f"only {len(ready)}/{self.fc.replicas} replicas reported a "
            "data port"
        )

    def poll_once(self) -> None:
        """One control cycle: a ``fleet_report`` query per registered
        replica. A reply refreshes that replica's load/port/cohorts and
        counts as a liveness ack; a miss walks the LivenessTracker ladder
        — the load poll IS the ping sweep, one wire round-trip for both."""
        with self._ctl_lock:
            present = self.driver.node_ids()
            self.tracker.register_present(present)
            pending = {
                self.driver.send(nid, Query("fleet_report")): nid
                for nid in present
            }
            deadline = time.monotonic() + self.fc.report_timeout_s
            while pending:
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                try:
                    nid, mid, reply = self.driver.recv_any(timeout=left)
                except TimeoutError:
                    break
                if mid not in pending:
                    continue  # stale late reply from a previous cycle
                pnid = pending.pop(mid)
                if isinstance(reply, Ack) and reply.ok:
                    self._ingest_report(pnid, reply)
                    self.tracker.observe_alive(pnid)
                else:
                    self.tracker.observe_miss(pnid)
            for nid in pending.values():
                self.tracker.observe_miss(nid)
            for nid in set(self.tracker.nodes) - set(present):
                self.tracker.observe_miss(nid)
        self._apply_transitions()
        self._record_kpis()
        self._drain_restarts()

    def _drain_restarts(self) -> None:
        """Execute the poll cycle's autopilot-approved replica restarts —
        outside the cycle's control-lock hold (the restart query
        re-acquires it per exchange)."""
        pending, self._pending_restarts = self._pending_restarts, []
        for nid in pending:
            ack = self._query(nid, "restart", self.fc.report_timeout_s)
            if ack is None or not ack.ok:
                warnings.warn(
                    f"fleet restart of {nid} was not acknowledged",
                    stacklevel=2,
                )

    def _ingest_report(self, nid: str, reply: Ack) -> None:
        try:
            rep = json.loads(reply.detail or "{}")
        except json.JSONDecodeError:
            return
        with self._lock:
            first = nid not in self.replicas or not self.replicas[nid].port
            st = self.replicas.setdefault(nid, ReplicaState(replica_id=nid))
            st.host = str(rep.get("host", st.host or self.fc.host))
            st.port = int(rep.get("port", st.port))
            st.cohorts = tuple(rep.get("cohorts") or ())
            st.loaded_round = int(rep.get("round", -1))
            st.queue_depth = int(rep.get("queue_depth", 0))
            st.live_slot_frac = float(rep.get("live_slot_frac", 0.0))
            st.draining = bool(rep.get("draining", False))
        if first and st.port:
            telemetry.emit_event(
                EVENT_FLEET_REPLICA_UP, replica=nid, port=st.port,
                round=st.loaded_round,
            )
        # restart triggers (ISSUE 19): a consecutive compile-growth streak
        # or an HBM-growth-degraded serve plane marks the replica for a
        # soft restart. The AUTOPILOT owns the decision (per-replica
        # cooldown + decision event); execution waits for _drain_restarts
        ap = telemetry.autopilot_active()
        if ap is not None:
            reason = None
            observed = 1.0
            compiles = rep.get("compiles")
            if compiles is not None:
                prev = self._compiles.get(nid)
                self._compiles[nid] = float(compiles)
                streak = (
                    self._compile_streaks.get(nid, 0) + 1
                    if prev is not None and float(compiles) > prev
                    else 0
                )
                self._compile_streaks[nid] = streak
                limit = int(getattr(ap.cfg, "replica_compile_streak", 0))
                if limit > 0 and streak >= limit:
                    reason, observed = "compile_growth", float(streak)
            health = rep.get("health") or {}
            if health.get("status") not in (None, "ok") \
                    and health.get("reason") == ALERT_HBM_GROWTH:
                reason, observed = ALERT_HBM_GROWTH, 1.0
            if reason is not None and ap.request_replica_restart(
                    nid, reason, observed=observed):
                self._compile_streaks[nid] = 0
                self._pending_restarts.append(nid)

    def _apply_transitions(self) -> None:
        """Edge-detect the tracker states: a replica newly DEAD re-pins
        its cohorts and degrades the fleet plane; a fully-live fleet
        resolves it."""
        states = {nid: h.state for nid, h in self.tracker.nodes.items()}
        newly_dead = [
            nid for nid, s in states.items()
            if s == DEAD and self._last_states.get(nid) != DEAD
        ]
        self._last_states = states
        for nid in newly_dead:
            self._on_replica_dead(nid)
        if states and all(s != DEAD for s in states.values()):
            h = telemetry.health_active()
            if h is not None:
                h.resolve("fleet", reason="all replicas live")

    def _on_replica_dead(self, nid: str) -> None:
        live = self.live_replicas(exclude=(nid,))
        with self._lock:
            moved = self.policy.repin_dead(nid, live)
            self.cohort_repins += len(moved)
        telemetry.emit_event(
            EVENT_FLEET_REPLICA_DEAD, replica=nid, survivors=len(live),
        )
        for cohort, new in moved:
            telemetry.emit_event(
                EVENT_FLEET_COHORT_REPIN, cohort=cohort,
                **{"from": nid, "to": new},
            )
        h = telemetry.health_active()
        if h is not None:
            h.note_fleet_replica_dead(
                replica=nid, survivors=len(live), repinned=len(moved),
            )

    def live_replicas(self, exclude: tuple = ()) -> list[str]:
        """Replica ids placements may target: tracker-not-dead, data port
        known, not draining. Sorted — placement must be deterministic
        given the same membership."""
        states = {nid: h.state for nid, h in self.tracker.nodes.items()}
        with self._lock:
            return sorted(
                nid for nid, st in self.replicas.items()
                if st.port and not st.draining and nid not in exclude
                and states.get(nid, DEAD) != DEAD
            )

    def _record_kpis(self) -> None:
        counts = self.tracker.counts()
        with self._lock:
            self._tick += 1
            self.history.record(self._tick, {
                ROUTER_REQUESTS_TOTAL: float(self.requests_total),
                ROUTER_ROUTED_PREFIX: float(self.routed_prefix),
                ROUTER_ROUTED_COHORT: float(self.routed_cohort),
                ROUTER_ROUTED_P2C: float(self.routed_p2c),
                ROUTER_REROUTES: float(self.reroutes),
                ROUTER_PROXY_ERRORS: float(self.proxy_errors),
                ROUTER_COHORT_REPINS: float(self.cohort_repins),
                ROUTER_REPLICAS_LIVE: float(counts["live"]),
                ROUTER_REPLICAS_SUSPECT: float(counts["suspect"]),
                ROUTER_REPLICAS_DEAD: float(counts["dead"]),
                SERVE_FLEET_REPLICAS: float(len(self.replicas)),
                SERVE_FLEET_ROLLING_SWAPS: float(self.rolling_swaps),
            })

    def _poll_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.poll_once()
            except Exception as e:  # noqa: BLE001 — a poll must not kill the router
                warnings.warn(f"fleet poll failed: {type(e).__name__}: {e}",
                              stacklevel=2)
            self._stop.wait(self.fc.report_poll_s)

    def _query(self, nid: str, action: str, timeout: float) -> Ack | None:
        """One request/reply exchange with a replica, serialized against
        the poll loop (exclusive driver ownership per operation); stale
        replies from a timed-out poll are discarded by mid match."""
        with self._ctl_lock:
            mid = self.driver.send(nid, Query(action))
            deadline = time.monotonic() + timeout
            while True:
                left = deadline - time.monotonic()
                if left <= 0:
                    return None
                try:
                    rnid, rmid, reply = self.driver.recv_any(timeout=left)
                except TimeoutError:
                    return None
                if rmid == mid:
                    return reply if isinstance(reply, Ack) else None

    # -- fleet operations --------------------------------------------------
    def rolling_hotswap(self, timeout_s: float = 60.0) -> list[dict]:
        """One hot-swap pass across the fleet, strictly one replica at a
        time: each replica polls its store and (if a newer verified round
        exists) quiesces + swaps before the next is asked — so at most one
        replica is ever mid-swap and the fleet never loses more than one
        replica's capacity to round tracking. Zero requests drop: the
        per-replica swap point is the PR 10 quiesce (request_swap)."""
        results: list[dict] = []
        for nid in self.live_replicas():
            reply = self._query(nid, "hotswap", timeout=timeout_s)
            res = {"replica": nid, "ok": False}
            if reply is not None and reply.ok:
                try:
                    res.update(json.loads(reply.detail or "{}"))
                except json.JSONDecodeError:
                    pass
                res["ok"] = True
                telemetry.emit_event(
                    EVENT_FLEET_ROLLING_SWAP, replica=nid,
                    swapped=bool(res.get("swapped")),
                    round=res.get("round", -1),
                )
            results.append(res)
        with self._lock:
            self.rolling_swaps += 1
        return results

    def drain_fleet(self, timeout_s: float = 5.0) -> None:
        """Flip every replica to draining (new work sheds at each edge
        while in-flight slots finish) and stop accepting at the router."""
        self.draining = True
        for nid in self.live_replicas():
            self._query(nid, "drain", timeout=timeout_s)

    # -- placement + proxy (data plane) ------------------------------------
    def place(self, prompt: list[int] | None, cohort: str | None,
              exclude: tuple = ()) -> tuple[str, str]:
        """Pick a replica for one request and count the reason."""
        live = self.live_replicas(exclude=exclude)
        with self._lock:
            rid, reason = self.policy.route(
                prompt, cohort, live, self.replicas
            )
            if not exclude:
                self.requests_total += 1
            if reason == "prefix":
                self.routed_prefix += 1
            elif reason == "cohort":
                self.routed_cohort += 1
            elif reason == "p2c":
                self.routed_p2c += 1
            n_requests = self.requests_total
        inj = chaos.active()
        if inj is not None and self.kill_hook is not None and not exclude:
            victim = inj.replica_kill_plan(n_requests, live)
            if victim is not None:
                self.kill_hook(victim)
        return rid, reason

    # -- HTTP front door ---------------------------------------------------
    def start(self) -> int:
        router = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *args) -> None:
                pass

            def _json(self, code: int, obj: dict,
                      extra_headers: dict | None = None) -> None:
                body = (json.dumps(obj) + "\n").encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (extra_headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def _discard_body(self) -> None:
                try:
                    n = int(self.headers.get("Content-Length", 0) or 0)
                except ValueError:
                    n = 0
                if n > 0:
                    self.rfile.read(n)

            def do_GET(self) -> None:  # noqa: N802 — http.server API
                path = self.path.rstrip("/")
                if path == "/healthz":
                    self._json(200, router.fleet_status())
                elif path == "/metrics":
                    want_om, ctype = negotiate_exposition(
                        self.headers.get("Accept")
                    )
                    body = render_exposition(
                        router.history, telemetry.metrics_active(),
                        exemplars=want_om,
                    ).encode()
                    if want_om:
                        body += b"# EOF\n"
                    self.send_response(200)
                    self.send_header("Content-Type", ctype)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                elif path == "/statusz":
                    h = telemetry.health_active()
                    payload = (h.statusz() if h is not None
                               else {"status": "ok", "planes": {},
                                     "alerts": [], "telemetry": "off"})
                    payload["fleet"] = router.fleet_status()["fleet"]
                    self._json(200, payload)
                else:
                    self._discard_body()
                    self._json(404, {"error": f"no route {self.path!r}"})

            def do_POST(self) -> None:  # noqa: N802 — http.server API
                path = self.path.rstrip("/")
                if path != "/generate":
                    self._discard_body()
                    self._json(404, {"error": f"no route {self.path!r}"})
                    return
                if router.draining:
                    self._discard_body()
                    self._json(503, {"error": "fleet draining"},
                               {"Retry-After": "5"})
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    raw = self.rfile.read(n)
                    body = json.loads(raw or b"{}")
                except (ValueError, json.JSONDecodeError) as e:
                    self._json(400, {"error": f"bad JSON body: {e}"})
                    return
                tokens = body.get("tokens")
                if not (isinstance(tokens, list)
                        and all(isinstance(t, int) for t in tokens)):
                    tokens = None  # text prompts route by cohort/p2c
                cohort = body.get("cohort")
                if cohort is not None and not isinstance(cohort, str):
                    self._json(400, {"error": "'cohort' must be a string"})
                    return
                router._proxy(self, raw, tokens, cohort)

        class _Server(ThreadingHTTPServer):
            # daemon handler threads + bounded explicit join, exactly the
            # frontend's drain discipline (serve/frontend.py)
            def process_request(self, request, client_address):
                t = threading.Thread(
                    target=self.process_request_thread,
                    args=(request, client_address),
                    name="photon-router-handler", daemon=True,
                )
                self._handler_threads.add(t)
                t.start()

            def join_handlers(self, timeout_s: float) -> bool:
                deadline = time.monotonic() + timeout_s
                for t in list(self._handler_threads):
                    t.join(timeout=max(0.0, deadline - time.monotonic()))
                return all(not t.is_alive() for t in self._handler_threads)

        self._httpd = _Server((self.fc.host, self.fc.port), Handler)
        self._httpd._handler_threads = weakref.WeakSet()
        self.port = self._httpd.server_address[1]
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, name="photon-router-http",
            daemon=True,
        )
        self._http_thread.start()
        self._poll_thread = threading.Thread(
            target=self._poll_loop, name="photon-router-poll", daemon=True
        )
        self._poll_thread.start()
        return self.port

    def fleet_status(self) -> dict:
        counts = self.tracker.counts()
        states = {nid: h.state for nid, h in self.tracker.nodes.items()}
        with self._lock:
            replicas = {
                nid: dict(st.to_dict(), state=states.get(nid, "unknown"))
                for nid, st in self.replicas.items()
            }
            routed = {
                "requests": self.requests_total,
                "prefix": self.routed_prefix,
                "cohort": self.routed_cohort,
                "p2c": self.routed_p2c,
                "reroutes": self.reroutes,
                "proxy_errors": self.proxy_errors,
                "cohort_repins": self.cohort_repins,
                "rolling_swaps": self.rolling_swaps,
            }
            pins = dict(self.policy.pins)
        return {
            "status": "draining" if self.draining else "ok",
            "fleet": {
                "replicas": replicas,
                "live": counts["live"], "suspect": counts["suspect"],
                "dead": counts["dead"],
                "pins": pins,
                "routed": routed,
            },
        }

    def _proxy(self, handler, raw_body: bytes, tokens: list[int] | None,
               cohort: str | None) -> None:
        """Route + forward one /generate. Connect-phase failures reroute
        to a survivor (up to ``route_retries`` alternates); once response
        bytes flow, errors surface to the client."""
        tried: list[str] = []
        for _attempt in range(self.fc.route_retries + 1):
            try:
                rid, _reason = self.place(tokens, cohort,
                                          exclude=tuple(tried))
            except NoReplicasError:
                break
            with self._lock:
                st = self.replicas.get(rid)
                dest = (st.host or self.fc.host, st.port) if st else None
            if dest is None:
                tried.append(rid)
                continue
            conn = http.client.HTTPConnection(
                dest[0], dest[1], timeout=self.request_timeout_s
            )
            try:
                conn.request(
                    "POST", "/generate", body=raw_body,
                    headers={"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
            except OSError:
                # connect/send failed before any response byte: safe to
                # re-place on a survivor (nothing was admitted)
                conn.close()
                tried.append(rid)
                with self._lock:
                    self.reroutes += 1
                continue
            try:
                self._relay(handler, resp)
            finally:
                conn.close()
            return
        with self._lock:
            self.proxy_errors += 1
        handler._json(503, {"error": "no live replica accepted the request"},
                      {"Retry-After": "5"})

    @staticmethod
    def _relay(handler, resp) -> None:
        """Copy a replica response to the client, preserving chunked
        streaming (token-by-token) when the replica streamed."""
        chunked = (resp.getheader("Transfer-Encoding") or "").lower() == "chunked"
        handler.send_response(resp.status)
        ctype = resp.getheader("Content-Type")
        if ctype:
            handler.send_header("Content-Type", ctype)
        ra = resp.getheader("Retry-After")
        if ra:
            handler.send_header("Retry-After", ra)
        if chunked:
            handler.send_header("Transfer-Encoding", "chunked")
            handler.end_headers()
            while True:
                # read1 returns per-chunk as the replica flushes — the
                # streaming cadence survives the hop
                data = resp.read1(65536)
                if not data:
                    break
                handler.wfile.write(
                    f"{len(data):x}\r\n".encode() + data + b"\r\n"
                )
            handler.wfile.write(b"0\r\n\r\n")
        else:
            data = resp.read()
            handler.send_header("Content-Length", str(len(data)))
            handler.end_headers()
            handler.wfile.write(data)

    # -- lifecycle ---------------------------------------------------------
    def close(self, handler_join_s: float = 0.0) -> None:
        """Stop the poll loop and HTTP server, then shut the control
        plane down — the driver's shutdown query lets replica agents exit
        their supervisor loops instead of redialing a gone router
        forever."""
        self._stop.set()
        if self._poll_thread is not None:
            self._poll_thread.join(timeout=self.fc.report_timeout_s + 5)
            self._poll_thread = None
        if self._httpd is not None:
            self._httpd.shutdown()
            if handler_join_s > 0:
                self._httpd.join_handlers(handler_join_s)
            self._httpd.server_close()
            self._httpd = None
        if self._http_thread is not None:
            self._http_thread.join(timeout=5)
            self._http_thread = None
        self.driver.shutdown()
