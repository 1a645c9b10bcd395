"""The seeded fault injector and its process-global installation.

Determinism contract: an injector's fault schedule is a pure function of
``(ChaosConfig.seed, scope)`` and the *order* of hook calls in its process.
``scope`` is the node id (or ``"server"``), so a multi-process run replays
the same faults per process across reruns even though processes interleave
nondeterministically with each other.

Hook sites call :func:`active` (a module-global read) and do nothing when it
returns ``None`` — the disabled path is provably a no-op, which is what lets
``photon.chaos`` exist in the tree without taxing the round's host path.
"""

from __future__ import annotations

import dataclasses
import os
import random
import zlib
from typing import Callable

from photon_tpu import telemetry
from photon_tpu.utils.profiling import CHAOS_EVENT_PREFIX

# resolved lazily to avoid a config<->chaos import cycle: config/schema.py
# validates ChaosConfig fields, chaos only reads them

# fit-handling phases (node processes) + collective-round phases (the
# controller loop in ``federation/collective_round.py`` — ISSUE 8): a crash
# at pre-exchange/mid-exchange/pre-update is a participant dying around the
# gang's collective stages, the failure shape the elastic ladder absorbs
_PHASES = (
    "pre-fit", "mid-fit", "pre-reply",
    "pre-exchange", "mid-exchange", "pre-update",
)


@dataclasses.dataclass
class TcpFaultPlan:
    """One envelope send's fate (all fields independent draws)."""

    drop: bool = False
    delay_s: float = 0.0
    duplicate: bool = False
    corrupt: bool = False


@dataclasses.dataclass
class StoreFaultPlan:
    """One object-store access's fate (writes AND reads share the shape).

    On a write: ``partial`` = the temp file lands but never renames into
    place (torn upload); ``bitflip`` = one payload bit flips before the
    otherwise-atomic write. On a read: ``partial`` = a short/truncated read
    (half the bytes come back); ``bitflip`` = one bit of the returned bytes
    flips (bad RAM / NFS page) while the object at rest stays intact. Both
    directions must be caught by the same defense — checksums — never by a
    silently-garbage load.
    """

    delay_s: float = 0.0
    # write the temp file but never rename it into place — the torn-write /
    # crash-mid-upload shape the atomic-rename protocol is meant to mask
    partial: bool = False
    # flip one payload bit BEFORE the (otherwise atomic, durable) write —
    # lands a well-formed object with wrong bytes; only checksums catch it
    bitflip: bool = False


def _scope_seed(seed: int, scope: str) -> int:
    return (int(seed) ^ zlib.crc32(scope.encode())) & 0x7FFFFFFF


class FaultInjector:
    """Draws fault plans from a seeded stream; one instance per process.

    ``crash_fn`` is injectable for unit tests; the default ``os._exit(137)``
    is deliberately un-catchable from Python — no ``finally`` blocks, no
    atexit, exactly like SIGKILL landing mid-instruction.
    """

    def __init__(self, cfg, scope: str = "", crash_fn: Callable[[int], None] | None = None) -> None:
        self.cfg = cfg
        self.scope = scope
        self.rng = random.Random(_scope_seed(cfg.seed, scope))
        self.crash_fn = crash_fn or (lambda code: os._exit(code))
        # per-plan counters so tests can assert the schedule fired
        self.counts: dict[str, int] = {
            "tcp_drop": 0, "tcp_delay": 0, "tcp_duplicate": 0, "tcp_corrupt": 0,
            "store_slow": 0, "store_partial": 0, "store_bitflip": 0,
            "store_read_slow": 0, "store_read_partial": 0,
            "store_read_bitflip": 0, "crash": 0, "nan_delta": 0,
            "replica_kill": 0, "fit_delay": 0,
            "serve_stall": 0, "hbm_ramp": 0,
        }
        # total CORRUPTING store faults (partial/bitflip, reads + writes)
        # fired, bounded by cfg.store_fault_max (0 = unlimited) — "corrupt
        # exactly N objects" scenarios without seed-hunting; delays neither
        # consume nor are blocked by the budget
        self._store_faults = 0

    def _fired(self, kind: str, **attrs) -> None:
        """Count a fired fault + structured telemetry event with trace
        correlation (``chaos/{kind}`` in the JSONL event log, so a dropped
        frame or slow write is attributable to the exact round/fit span it
        hit). The emit is a None check when telemetry is off — the chaos
        plane must not tax itself."""
        self.counts[kind] += 1
        telemetry.emit_event(CHAOS_EVENT_PREFIX + kind, scope=self.scope, **attrs)

    # -- TCP control plane ----------------------------------------------
    def tcp_plan(self) -> TcpFaultPlan:
        c = self.cfg
        plan = TcpFaultPlan()
        if c.tcp_drop_p and self.rng.random() < c.tcp_drop_p:
            plan.drop = True
            self._fired("tcp_drop")
            return plan  # a dropped frame can't also be delayed/duplicated
        if c.tcp_delay_p and self.rng.random() < c.tcp_delay_p:
            plan.delay_s = self.rng.uniform(0.0, c.tcp_delay_max_s)
            self._fired("tcp_delay", delay_s=plan.delay_s)
        if c.tcp_duplicate_p and self.rng.random() < c.tcp_duplicate_p:
            plan.duplicate = True
            self._fired("tcp_duplicate")
        if c.tcp_corrupt_p and self.rng.random() < c.tcp_corrupt_p:
            plan.corrupt = True
            self._fired("tcp_corrupt")
        return plan

    def corrupt_bytes(self, data: bytes) -> bytes:
        """Flip one bit at a seeded offset (never a no-op)."""
        if not data:
            return data
        buf = bytearray(data)
        i = self.rng.randrange(len(buf))
        buf[i] ^= 1 << self.rng.randrange(8)
        return bytes(buf)

    # -- object store ----------------------------------------------------
    def _store_capped(self) -> bool:
        mx = int(getattr(self.cfg, "store_fault_max", 0))
        return mx > 0 and self._store_faults >= mx

    def _store_plan(self, prefix: str) -> StoreFaultPlan:
        """One object-store access's fate; ``prefix`` keys the counters
        (``store_`` for writes, ``store_read_`` for reads — same
        probability knobs, separate fired-counter streams). The
        ``store_fault_max`` cap gates CORRUPTING faults only
        (partial/bitflip): a delay neither consumes the budget nor is
        blocked by it, so "corrupt exactly N objects" stays deterministic
        even with ``store_slow_p`` armed alongside."""
        c = self.cfg
        plan = StoreFaultPlan()
        if c.store_slow_p and self.rng.random() < c.store_slow_p:
            plan.delay_s = self.rng.uniform(0.0, c.store_slow_max_s)
            self._fired(f"{prefix}slow", delay_s=plan.delay_s)
        if self._store_capped():
            return plan
        if c.store_partial_p and self.rng.random() < c.store_partial_p:
            plan.partial = True
            self._fired(f"{prefix}partial")
        elif c.store_bitflip_p and self.rng.random() < c.store_bitflip_p:
            plan.bitflip = True
            self._fired(f"{prefix}bitflip")
        if plan.partial or plan.bitflip:
            self._store_faults += 1
        return plan

    def store_plan(self) -> StoreFaultPlan:
        """One object-store WRITE's fate (``FileStore.put``)."""
        return self._store_plan("store_")

    def store_read_plan(self) -> StoreFaultPlan:
        """One object-store READ's fate: same probability knobs as the
        write side, separate counters (ISSUE 8 satellite — ``FileStore.get``
        and ``get_to_file`` honor the plan like ``put`` does)."""
        return self._store_plan("store_read_")

    # -- numeric poison (ISSUE 10) ---------------------------------------
    def nan_delta_plan(self, server_round: int, cid: int) -> bool:
        """True when this client's fit delta should be NaN-poisoned as it
        is packaged (``nan_delta_round`` matches, and ``nan_delta_cid`` is
        -1 or this cid). Deterministic — no probability draw: the health
        sentinel e2e needs the poison at exactly one round."""
        c = self.cfg
        r = int(getattr(c, "nan_delta_round", 0))
        if not r or server_round != r:
            return False
        want = int(getattr(c, "nan_delta_cid", -1))
        if want >= 0 and cid != want:
            return False
        self._fired("nan_delta", server_round=server_round, cid=cid)
        return True

    # -- per-client fit slowdown (ISSUE 18) -------------------------------
    def fit_delay_plan(self, cid: int) -> float:
        """This client's fit-duration slowdown factor (>= 1.0; 1.0 = none).

        Deterministic — no sequential draw: the factor is a pure function
        of ``(seed, scope, cid)``, independent of hook-call order, so the
        async runner's induced 4x skew replays identically across runs and
        across a synchronous and an asynchronous run. ``fit_delay_cid``
        pins the full factor on exactly one client (the "one 4x-slow
        client" scenario);
        -1 gives every client a seeded factor in [1, factor].
        """
        c = self.cfg
        factor = float(getattr(c, "fit_delay_factor", 0.0) or 0.0)
        if factor <= 1.0:
            return 1.0
        want = int(getattr(c, "fit_delay_cid", -1))
        if want >= 0:
            if cid != want:
                return 1.0
            f = factor
        else:
            rng = random.Random(
                _scope_seed(c.seed, f"{self.scope}/fit_delay/{cid}")
            )
            f = 1.0 + (factor - 1.0) * rng.random()
        self._fired("fit_delay", cid=cid, factor=round(f, 4))
        return f

    # -- serve fault storm (ISSUE 19) ------------------------------------
    def serve_stall_plan(self, tokens: int) -> float:
        """Seconds to stall this serve tick: ``serve_stall_per_token_s``
        times the tokens the tick's engine step carried (chunk + emitted).
        Deterministic — no probability draw: the SLO-autopilot storm needs
        the slowdown proportional to the work the controller's budget knob
        actually bounds, every tick, identical with the controller on or off."""
        c = self.cfg
        per = float(getattr(c, "serve_stall_per_token_s", 0.0) or 0.0)
        if per <= 0.0 or tokens <= 0:
            return 0.0
        delay = per * tokens
        self._fired("serve_stall", tokens=int(tokens),
                    delay_s=round(delay, 6))
        return delay

    def hbm_ramp_plan(self) -> float:
        """The multiplicative HBM inflation for this serve device sample:
        the n-th call returns ``serve_hbm_ramp_frac * n`` — strictly
        monotone growth that latches the health plane's HBM watcher within
        one sample window, without real memory pressure. 0.0 = off."""
        c = self.cfg
        frac = float(getattr(c, "serve_hbm_ramp_frac", 0.0) or 0.0)
        if frac <= 0.0:
            return 0.0
        n = self.counts["hbm_ramp"] + 1
        self._fired("hbm_ramp", sample=n)
        return frac * n

    # -- fleet replica kill (ISSUE 16) -----------------------------------
    def replica_kill_plan(self, requests_routed: int,
                          live_replicas: list[str]) -> str | None:
        """The replica id to SIGKILL now, or None. Fires exactly once, when
        the router's cumulative placement count reaches
        ``replica_kill_after_requests``; ``replica_kill_id`` pins the
        victim, else the seeded stream picks one of ``live_replicas``
        (sorted — the draw must not depend on caller ordering).
        Deterministic — no probability draw: the fleet chaos e2e needs one
        death at one known point in the traffic."""
        c = self.cfg
        n = int(getattr(c, "replica_kill_after_requests", 0))
        if not n or self.counts["replica_kill"] or requests_routed < n:
            return None
        want = str(getattr(c, "replica_kill_id", ""))
        if want:
            victim = want if want in live_replicas else None
        else:
            victim = (self.rng.choice(sorted(live_replicas))
                      if live_replicas else None)
        if victim is None:
            return None
        self._fired("replica_kill", replica=victim,
                    requests_routed=requests_routed)
        return victim

    # -- node crash ------------------------------------------------------
    def maybe_crash(self, phase: str, server_round: int = 0, node_id: str = "") -> None:
        c = self.cfg
        if not c.crash_phase or c.crash_phase != phase:
            return
        if c.crash_round and server_round != c.crash_round:
            return
        if c.crash_node_id and node_id and node_id != c.crash_node_id:
            return
        if c.crash_marker:
            # the marker survives the process the crash kills: a respawned
            # node (same config) sees it and stays up, making "SIGKILL the
            # node exactly once" a deterministic, testable event
            try:
                fd = os.open(c.crash_marker, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
            except FileExistsError:
                return  # already crashed once
            except OSError:
                return  # unreachable marker path: fail open (no crash)
            os.close(fd)
        # _fired BEFORE the kill: with a test-injected crash_fn the event is
        # observable; with the real os._exit a buffered node-side event is
        # lost with the process — exactly what SIGKILL semantics promise
        self._fired("crash", phase=phase, server_round=server_round,
                    node_id=node_id)
        self.crash_fn(137)


# -- process-global installation ----------------------------------------

_INJECTOR: FaultInjector | None = None


def install(cfg, scope: str = "", crash_fn: Callable[[int], None] | None = None) -> FaultInjector | None:
    """Install (or clear) the process-global injector from a ChaosConfig.

    ``cfg=None`` or ``cfg.enabled=False`` uninstalls — constructing a
    ServerApp with chaos off always leaves a clean process, so test
    pollution across configs is impossible.
    """
    global _INJECTOR
    if cfg is None or not getattr(cfg, "enabled", False):
        _INJECTOR = None
        return None
    _INJECTOR = FaultInjector(cfg, scope=scope, crash_fn=crash_fn)
    return _INJECTOR


def uninstall() -> None:
    global _INJECTOR
    _INJECTOR = None


def active() -> FaultInjector | None:
    """The installed injector, or None — the single check every hook makes."""
    return _INJECTOR


def crash_point(phase: str, server_round: int = 0, node_id: str = "") -> None:
    """Hook site for node-process crash phases (no-op unless installed)."""
    inj = _INJECTOR
    if inj is not None:
        inj.maybe_crash(phase, server_round, node_id)


def validate_chaos_config(cfg) -> None:
    """Schema-side validation (called from ``Config.validate``)."""
    for name in (
        "tcp_drop_p", "tcp_delay_p", "tcp_duplicate_p", "tcp_corrupt_p",
        "store_slow_p", "store_partial_p", "store_bitflip_p",
    ):
        v = getattr(cfg, name)
        if not 0.0 <= v <= 1.0:
            raise ValueError(f"chaos.{name} must be in [0, 1], got {v}")
    if cfg.tcp_delay_max_s < 0 or cfg.store_slow_max_s < 0:
        raise ValueError("chaos delay bounds must be >= 0")
    if cfg.crash_phase and cfg.crash_phase not in _PHASES:
        raise ValueError(
            f"chaos.crash_phase must be one of {_PHASES} or '', got {cfg.crash_phase!r}"
        )
    if cfg.crash_round < 0:
        raise ValueError(f"chaos.crash_round must be >= 0, got {cfg.crash_round}")
    if getattr(cfg, "nan_delta_round", 0) < 0:
        raise ValueError(
            f"chaos.nan_delta_round must be >= 0 (0 = off), got "
            f"{cfg.nan_delta_round}"
        )
    if getattr(cfg, "store_fault_max", 0) < 0:
        raise ValueError(
            f"chaos.store_fault_max must be >= 0 (0 = unlimited), got "
            f"{cfg.store_fault_max}"
        )
    if getattr(cfg, "replica_kill_after_requests", 0) < 0:
        raise ValueError(
            f"chaos.replica_kill_after_requests must be >= 0 (0 = off), got "
            f"{cfg.replica_kill_after_requests}"
        )
    fd = float(getattr(cfg, "fit_delay_factor", 0.0))
    if fd != 0.0 and fd < 1.0:
        raise ValueError(
            f"chaos.fit_delay_factor must be 0 (off) or >= 1 (a slowdown), "
            f"got {fd}"
        )
    if getattr(cfg, "fit_delay_cid", -1) < -1:
        raise ValueError(
            f"chaos.fit_delay_cid must be >= -1 (-1 = seeded per-client), "
            f"got {cfg.fit_delay_cid}"
        )
    if getattr(cfg, "serve_stall_per_token_s", 0.0) < 0.0:
        raise ValueError(
            f"chaos.serve_stall_per_token_s must be >= 0 (0 = off), got "
            f"{cfg.serve_stall_per_token_s}"
        )
    if getattr(cfg, "serve_hbm_ramp_frac", 0.0) < 0.0:
        raise ValueError(
            f"chaos.serve_hbm_ramp_frac must be >= 0 (0 = off), got "
            f"{cfg.serve_hbm_ramp_frac}"
        )
