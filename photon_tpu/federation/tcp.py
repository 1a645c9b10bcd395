"""TCP control plane: the multi-host driver.

Role parity with the reference's Flower gRPC SuperLink (server ⇄ node
messaging across machines, ``server_util.py:144-202``; nodes dial in and the
server waits for them, ``wait_for_nodes_to_connect`` ``server_util.py:35``).
TPU-first there is no external broker: the server listens, node agents dial
in and announce a node_id, and envelopes flow as length-prefixed pickles.

Trust model: same as the reference's RecordSets (pickled configs between our
own processes on a private network) — do NOT expose the port publicly.

Bulk tensors do NOT travel on this socket (messages carry
:class:`ParamPointer`s); pair with the objstore transport on shared/durable
storage, or the DCN collective path.

Usage::

    # server host
    driver = TcpServerDriver("0.0.0.0", 9777, expected_nodes=2)
    driver.wait_for_nodes(timeout=300)
    app = ServerApp(cfg, driver, transport, ...)

    # each node host
    python -m photon_tpu.federation.tcp --connect SERVER:9777 \
        --node-id node0 --config run.yaml
"""

from __future__ import annotations

import argparse
import pickle
import selectors
import socket
import struct
import threading
import time
import warnings
import zlib
from collections import deque
from typing import Any

from photon_tpu import chaos, telemetry
from photon_tpu.federation.driver import Driver
from photon_tpu.federation.membership import ReconnectPolicy
from photon_tpu.federation.messages import Ack, Envelope, Query
from photon_tpu.utils.profiling import (
    EVENT_TCP_CORRUPT_FRAME,
    EVENT_TCP_RECONNECT,
    TCP_RECV_BYTES,
    TCP_RECV_SPAN,
    TCP_SEND_BYTES,
    TCP_SEND_SPAN,
)

# frame header: payload length + CRC32 of the payload. The checksum exists
# for the chaos corruption injector and for real bit-rot alike: a corrupt
# frame must surface as a broken CONNECTION (stream framing is unusable
# after it), never as a silently unpickled wrong object.
_FRAME = struct.Struct("<QI")
HELLO_KIND = "__hello__"
#: bound on the accept loop's HELLO read: a connected-but-silent peer is
#: dropped (it redials) instead of monopolizing accepts or pinning
#: shutdown's accept-thread join
_HELLO_TIMEOUT_S = 2.0


class CorruptFrameError(EOFError):
    """Frame failed its CRC32. Subclasses EOFError deliberately: every
    caller already tears the connection down on EOF, which is the only safe
    response once the byte stream can't be trusted."""


class SocketConn:
    """Length+CRC-prefixed pickle framing over a stream socket,
    Connection-like (``send``/``recv``/``close``) so :meth:`NodeAgent.serve`
    runs unchanged."""

    def __init__(self, sock: socket.socket) -> None:
        self.sock = sock
        #: absolute time.monotonic() bound on a whole recv() (header AND
        #: payload). A plain settimeout resets per sock.recv, so a slow-drip
        #: peer (1 byte per timeout) never trips it; the deadline shrinks.
        self.deadline: float | None = None
        try:
            self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except OSError:
            pass  # e.g. AF_UNIX socketpair in tests
        self._rlock = threading.Lock()
        self._wlock = threading.Lock()

    def send(self, obj: Any) -> None:
        data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
        header = _FRAME.pack(len(data), zlib.crc32(data))
        repeat = 1
        inj = chaos.active()
        if inj is not None and isinstance(obj, Envelope):
            # chaos targets Envelopes only: HELLO/registration frames stay
            # exempt so membership control can't be wedged by the injector
            plan = inj.tcp_plan()
            if plan.drop:
                return
            if plan.delay_s:
                time.sleep(plan.delay_s)
            if plan.corrupt:
                # flip a payload bit AFTER the CRC was computed — the
                # receiver's checksum is what must catch it
                data = inj.corrupt_bytes(data)
            if plan.duplicate:
                repeat = 2
        # the send leg is a span (telemetry plane): nbytes + wall time of
        # the syscall path, so a slow/buffer-bound control-plane write is
        # attributable on the timeline. Measured around the lock + sendall
        # — contention IS part of the leg the caller experiences.
        with telemetry.span(TCP_SEND_SPAN, push=False, nbytes=len(data)):
            with self._wlock:
                for _ in range(repeat):
                    self.sock.sendall(header + data)
        # frame-size distribution (typed hub, ISSUE 10): a control-plane
        # payload quietly growing past the MB mark is a design regression
        # the per-span nbytes attr can't aggregate
        telemetry.metric_observe(TCP_SEND_BYTES, len(data))

    def _read_exact(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            if self.deadline is not None:
                remaining = self.deadline - time.monotonic()
                if remaining <= 0:
                    raise socket.timeout("recv deadline exceeded")
                self.sock.settimeout(remaining)
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise EOFError("peer closed")
            buf.extend(chunk)
        return bytes(buf)

    def recv(self) -> Any:
        with self._rlock:
            n, crc = _FRAME.unpack(self._read_exact(_FRAME.size))
            # the recv leg span starts AFTER the header lands: everything
            # before it is idle wait for the peer, which would drown the
            # actual transport cost (payload read + unpickle) on a timeline
            with telemetry.span(TCP_RECV_SPAN, push=False, nbytes=n):
                data = self._read_exact(n)
            telemetry.metric_observe(TCP_RECV_BYTES, n)
        if zlib.crc32(data) != crc:
            # the teardown this forces is a structured event: correlate the
            # connection loss with whatever round span was active
            telemetry.emit_event(EVENT_TCP_CORRUPT_FRAME, nbytes=n)
            raise CorruptFrameError(f"frame CRC mismatch ({n} bytes)")
        try:
            return pickle.loads(data)
        except Exception as exc:
            # CRC-valid but undecodable: a version-skewed peer (renamed
            # class/module) raises ModuleNotFoundError/AttributeError, not
            # UnpicklingError. The stream can't be trusted any more than a
            # corrupt one — same remedy, tear the connection down.
            telemetry.emit_event(EVENT_TCP_CORRUPT_FRAME, nbytes=n)
            raise CorruptFrameError(f"frame unpicklable ({n} bytes): {exc!r}") from exc

    def close(self) -> None:
        try:
            self.sock.close()
        except OSError:
            pass


class TcpServerDriver(Driver):
    """Server side: accepts node registrations, routes envelopes."""

    def __init__(self, host: str, port: int, expected_nodes: int) -> None:
        self.expected_nodes = expected_nodes
        self._nodes: dict[str, SocketConn] = {}
        self._inflight: dict[str, list[int]] = {}
        # replies synthesized for sends to dead/unknown nodes, drained by
        # recv_any before touching sockets
        self._dead_letters: deque[tuple[str, int, Ack]] = deque()
        # node-reported supervisor stats from the latest HELLO
        # ({"reconnects": int, "backoff_s": float} per node id)
        self._hello_stats: dict[str, dict] = {}
        self._lock = threading.Lock()
        self._mid = iter(range(1 << 62))
        self._listener = socket.create_server((host, port))
        self._accepting = True
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="photon-tcp-accept", daemon=True
        )
        self._accept_thread.start()

    @property
    def port(self) -> int:
        return self._listener.getsockname()[1]

    def _accept_loop(self) -> None:
        while self._accepting:
            try:
                sock, _ = self._listener.accept()
            except OSError:
                return
            conn = SocketConn(sock)
            # the HELLO read is deadline-bounded: an accepted-but-silent or
            # byte-dripping peer (wedged node, SYN-scan, delayed frame) must
            # neither monopolize the accept loop nor pin shutdown's join. 2s
            # is 40x the default chaos tcp_delay_max_s; socket.timeout is an
            # OSError, so a too-slow peer just gets dropped and redials.
            conn.deadline = time.monotonic() + _HELLO_TIMEOUT_S
            try:
                hello = conn.recv()
            except (EOFError, OSError):  # incl. CorruptFrameError/timeout
                conn.close()
                continue
            # full validation BEFORE any keyed access: a version-skewed or
            # buggy client's HELLO must drop one connection, never KeyError
            # the accept thread to death (the server would silently stop
            # registering reconnections forever)
            if not (
                isinstance(hello, dict)
                and hello.get("kind") == HELLO_KIND
                and hello.get("node_id") is not None
            ):
                conn.close()
                continue
            conn.deadline = None
            sock.settimeout(None)  # registered conns block under the selector
            node_id = str(hello["node_id"])
            with self._lock:
                old = self._nodes.get(node_id)
                self._nodes[node_id] = conn
                # requests in flight on the replaced socket are gone for
                # good (the node restarted or lost the connection carrying
                # them) — drain them as dead-letter failures NOW instead of
                # letting the sliding window eat a full fit_timeout_s. The
                # "node died" detail routes the scheduler through its
                # rejoin path: re-broadcast, back into rotation.
                stale = self._inflight.get(node_id, [])
                self._inflight[node_id] = []
                for mid in stale:
                    self._dead_letters.append(
                        (node_id, mid,
                         Ack(ok=False, detail="node died: reconnected mid-request",
                             node_id=node_id))
                    )
                try:
                    rc = int(hello.get("reconnects", 0))
                    bo = float(hello.get("backoff_s", 0.0))
                except (TypeError, ValueError):
                    rc, bo = 0, 0.0  # skewed client: bad stats, fine node
                self._hello_stats[node_id] = {"reconnects": rc, "backoff_s": bo}
            if old is not None:
                old.close()  # reconnection replaces the stale socket

    def hello_stats(self) -> dict[str, dict]:
        with self._lock:
            return {nid: dict(s) for nid, s in self._hello_stats.items()}

    def wait_for_nodes(self, timeout: float = 300.0, poll: float = 0.2) -> None:
        """Block until ``expected_nodes`` registered (reference:
        ``wait_for_nodes_to_connect``, ``server_util.py:35``)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if len(self._nodes) >= self.expected_nodes:
                    return
            time.sleep(poll)
        with self._lock:
            have = sorted(self._nodes)
        raise TimeoutError(f"only {len(have)}/{self.expected_nodes} nodes connected: {have}")

    # -- Driver interface ------------------------------------------------
    def node_ids(self) -> list[str]:
        with self._lock:
            return sorted(self._nodes)

    def send(self, node_id: str, msg: Any) -> int:
        mid = next(self._mid)
        with self._lock:
            conn = self._nodes.get(node_id)
            if conn is None:
                # node died and was dropped from the registry, but a caller
                # (e.g. the sliding window's free list) still holds its id —
                # synthesize a dead-node reply instead of raising KeyError
                # and crashing the round loop the failure budget is meant to
                # survive
                self._dead_letters.append(
                    (node_id, mid, Ack(ok=False, detail="node died", node_id=node_id))
                )
                return mid
            self._inflight[node_id].append(mid)
        try:
            # trace context rides the envelope across the socket so the
            # node's spans parent to the sending server span
            conn.send(Envelope(msg, mid, trace=telemetry.current_context()))
        except OSError:
            pass  # surfaced as a dead-node reply in recv_any
        return mid

    def recv_any(self, timeout: float | None = None) -> tuple[str, int, Any]:
        deadline = None if timeout is None else time.monotonic() + timeout
        sel = selectors.DefaultSelector()
        try:
            while True:
                with self._lock:
                    if self._dead_letters:
                        return self._dead_letters.popleft()
                    watched = {
                        nid: conn
                        for nid, conn in self._nodes.items()
                        if self._inflight.get(nid)
                    }
                if not watched:
                    raise TimeoutError("recv_any: nothing in flight")
                for nid, conn in watched.items():
                    try:
                        sel.register(conn.sock, selectors.EVENT_READ, (nid, conn))
                    except (ValueError, OSError, KeyError):
                        # _accept_loop closed this socket during a node
                        # reconnection between our snapshot and register —
                        # skip it; the next loop iteration re-snapshots
                        continue
                left = None if deadline is None else max(0.0, deadline - time.monotonic())
                ready = sel.select(timeout=left)
                for key in list(sel.get_map().values()):
                    sel.unregister(key.fileobj)
                if not ready:
                    raise TimeoutError("recv_any: timeout")
                nid, conn = ready[0][0].data
                try:
                    env: Envelope = conn.recv()
                except (EOFError, OSError):
                    # (CorruptFrameError lands here too, via EOFError — CRC
                    # failure or unpicklable payload alike: once a frame
                    # can't be trusted the stream offset is untrusted and
                    # the connection must die)
                    with self._lock:
                        if self._nodes.get(nid) is conn:
                            # genuinely dead: evict and fail everything it
                            # still owed us
                            mids = self._inflight.get(nid, [])
                            self._inflight[nid] = []
                            del self._nodes[nid]
                        else:
                            # EOF on a STALE socket the accept loop already
                            # replaced — the replacement's in-flight mids are
                            # not ours to fail (they were dead-lettered at
                            # re-HELLO time; new requests ride the new conn)
                            mids = []
                    conn.close()
                    if mids:
                        # dead node: synthesized failures, like
                        # MultiprocessDriver; ALL in-flight mids drain (first
                        # returned now, the rest as dead letters) so a multi-
                        # request window never waits a timeout per orphan
                        with self._lock:
                            for mid in mids[1:]:
                                self._dead_letters.append(
                                    (nid, mid, Ack(ok=False, detail="node died", node_id=nid))
                                )
                        return nid, mids[0], Ack(ok=False, detail="node died", node_id=nid)
                    continue
                with self._lock:
                    if env.msg_id in self._inflight.get(nid, []):
                        self._inflight[nid].remove(env.msg_id)
                return nid, env.msg_id, env.msg
        finally:
            sel.close()

    def shutdown(self, ack_timeout: float = 5.0) -> None:
        self._accepting = False
        try:
            self._listener.close()
        except OSError:
            pass
        # closing the listener EBADFs the blocking accept() and the bounded
        # HELLO read wakes within _HELLO_TIMEOUT_S, so the join is prompt
        # (thread-ownership audit: every thread has an owner that joins it)
        self._accept_thread.join(timeout=_HELLO_TIMEOUT_S + 3)
        with self._lock:
            nodes = list(self._nodes.items())
        for nid, conn in nodes:
            try:
                conn.send(Envelope(Query("shutdown"), next(self._mid)))
            except OSError:
                pass
        # wait for each node's shutdown ack before closing: an immediate
        # close can RST before the node's reply lands, making its agent
        # treat clean shutdown as a server crash and redial for minutes
        for nid, conn in nodes:
            try:
                # absolute deadline, not settimeout: a byte-dripping node
                # would reset a per-recv timeout forever (same hole the
                # HELLO read closes) and pin shutdown past ack_timeout
                conn.deadline = time.monotonic() + ack_timeout
                conn.recv()
            except (OSError, EOFError):
                pass
            conn.close()
        with self._lock:
            self._nodes.clear()
            self._inflight.clear()
            self._hello_stats.clear()


def run_node(
    server_addr: str,
    node_id: str,
    cfg_json: str,
    retries: int | None = None,
    sleep=time.sleep,
) -> None:
    """Node-side supervisor: dial the server, serve the agent loop, and on
    socket loss reconnect with jittered exponential backoff + re-HELLO
    (reference: ``flower-client-app`` pointed at DRIVER_API_ADDRESS — whose
    gRPC channel reconnects under the hood; here the supervision is
    explicit and its backoff is config/test-visible).

    Every HELLO carries the supervisor's cumulative stats
    (``reconnects``/``backoff_s``); the server surfaces them as the
    ``server/reconnect_backoff_s`` KPI. ``retries`` overrides
    ``membership.reconnect_max_attempts`` and shares its contract:
    ``0 = retry forever`` (NOT the pre-supervisor "fail immediately" —
    callers wanting fail-fast pass 1). ``sleep`` is injectable for tests; a
    clean ``shutdown`` query ends the loop.
    """
    import random as random_mod

    from photon_tpu.config.schema import Config
    from photon_tpu.federation.node import NodeAgent
    from photon_tpu.federation.transport import ParamTransport

    host, _, port = server_addr.rpartition(":")
    cfg = Config.from_json(cfg_json)
    chaos.install(cfg.photon.chaos, scope=node_id)
    # node-side telemetry buffers (no files): spans + events ship back to
    # the server piggybacked on fit/eval results
    telemetry.install(cfg.photon.telemetry, scope=node_id, piggyback=True)

    store = None
    if cfg.photon.comm_stack.objstore:
        from photon_tpu.checkpoint.store import FileStore

        store = FileStore(cfg.photon.save_path + "/store")

    def make_transport() -> ParamTransport:
        mode = "objstore" if cfg.photon.comm_stack.objstore else "shm"
        return ParamTransport(mode, store=store, compression=cfg.photon.compression,
                              host_threads=cfg.photon.host_threads)

    make_ckpt_mgr = None
    if store is not None and cfg.photon.checkpoint:
        # client checkpoints (skip-if-done / mid-round resume) need the
        # same store the server GCs (reference: client Composer ckpts in
        # the shared save_folder, ``llm_config_functions.py:642-764``)
        from photon_tpu.checkpoint import ClientCheckpointManager

        def make_ckpt_mgr():
            return ClientCheckpointManager(store, cfg.run_uuid)

    policy = ReconnectPolicy.from_config(
        cfg.photon.membership,
        rng=random_mod.Random(zlib.crc32(node_id.encode())),
    )
    if retries is not None:
        policy.max_attempts = retries
    agent = NodeAgent(cfg, node_id, make_transport, make_ckpt_mgr=make_ckpt_mgr)
    attempt = 0  # consecutive failed dials; a successful dial resets it
    reconnects = 0
    backoff_total = 0.0
    while True:
        try:
            sock = socket.create_connection((host, int(port)), timeout=10)
        except OSError:
            attempt += 1
            if policy.exhausted(attempt):
                raise ConnectionError(
                    f"could not reach server at {server_addr} after {attempt} dials "
                    f"({backoff_total:.1f}s total backoff)"
                )
            d = policy.delay(attempt - 1)
            backoff_total += d
            sleep(d)
            continue
        attempt = 0
        conn = SocketConn(sock)
        clean = False
        try:
            # the HELLO itself can hit a reset (server accepted via the
            # listener backlog, then died): that is a connection loss like
            # any other, not a supervisor crash
            conn.send({
                "kind": HELLO_KIND,
                "node_id": node_id,
                "reconnects": reconnects,
                "backoff_s": backoff_total,
            })
            clean = agent.serve(conn)
        except OSError:
            clean = False  # send failed mid-reply: same as connection loss
        except Exception as e:  # noqa: BLE001 — supervisor hardening (ISSUE 8)
            # a crash that escapes the agent loop OUTSIDE per-message
            # handling (reply pickling, telemetry piggyback, a collective
            # stage a hybrid runtime drives) used to kill the supervisor
            # outright — the node left the federation forever over one bad
            # round. Treat it as a torn connection: log, back off, redial
            # and re-HELLO into the NEXT round; the server dead-letters
            # whatever it still had in flight on the old socket.
            warnings.warn(
                f"node {node_id}: agent loop crashed "
                f"({type(e).__name__}: {e}) — redialing into the next round",
                stacklevel=2,
            )
            clean = False
        finally:
            conn.close()
        if clean:
            return  # orderly shutdown query
        # server went away (or a corrupt frame killed the stream): back
        # off, then redial + re-HELLO. The server's accept loop replaces
        # our stale registration and dead-letters anything it still had in
        # flight on the old socket.
        reconnects += 1
        d = policy.delay(0)
        backoff_total += d
        # buffered node-side event; rides the next fit/eval result back to
        # the server's JSONL log
        telemetry.emit_event(
            EVENT_TCP_RECONNECT, node=node_id, reconnects=reconnects,
            backoff_s=d, backoff_total_s=backoff_total,
        )
        sleep(d)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description="photon-tpu TCP node agent")
    ap.add_argument("--connect", required=True, metavar="HOST:PORT")
    ap.add_argument("--node-id", required=True)
    ap.add_argument("--config", required=True, help="resolved config YAML")
    args = ap.parse_args(argv)
    from photon_tpu.config.schema import Config
    from photon_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    cfg = Config.from_yaml(args.config)
    run_node(args.connect, args.node_id, cfg.to_json())


if __name__ == "__main__":
    main()
