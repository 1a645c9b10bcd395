"""Device-plane introspection: HBM accounting, compile counters, profiling.

The pjit/TPUv4 scaling work (PAPERS.md) treats compile time and memory
behavior as first-class performance signals; in this repo they were
test-only (the PR 6 retrace sentinel) or post-mortem-only
(``dump_memory_profile`` after an OOM). This module makes them
*scrapeable*:

- :func:`device_memory` — jax per-device memory stats (live bytes, peak)
  sampled at round and serve-tick boundaries into
  ``server/hbm_bytes_in_use`` / ``serve/hbm_*`` gauges, so a ballooning
  footprint is a dashboard line, not a surprise RESOURCE_EXHAUSTED;
- :class:`CompileCounter` — the same ``backend_compile_duration``
  monitoring event the retrace sentinel counts (fires per REAL compile,
  never on a cache hit), kept as a process-cumulative count feeding the
  ``*/backend_compiles_total`` counter — program-cache misses become a
  KPI instead of a test assertion;
- :class:`ProfileController` — on-demand ``jax.profiler`` capture: arm it
  for N round/tick units (``photon.telemetry.profile_rounds``, or
  ``POST /debug/profile``), the next unit boundary starts the trace, the
  N-th after it stops, artifacts land beside ``trace-{run}.json``.

All of it installs/uninstalls with the telemetry plane; disabled hook
sites are one ``None`` check (``telemetry.profile_tick`` /
``telemetry.metrics_active``).
"""

from __future__ import annotations

import pathlib
import threading
import time
from typing import Any

#: the jax monitoring event that fires once per real backend compile
#: (shared with analysis/runtime.py's RetraceSentinel)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def device_memory(device: Any | None = None) -> dict[str, int] | None:
    """Live/peak device-memory bytes for the first local device (or the
    given one). Returns None where the backend doesn't report (CPU,
    emulators) — callers skip the KPIs rather than recording zeros that
    would read as "no memory in use"."""
    try:
        if device is None:
            import jax

            device = jax.local_devices()[0]
        stats = device.memory_stats()
    except Exception:  # noqa: BLE001 — introspection must never cost a round
        return None
    if not stats or "bytes_in_use" not in stats:
        return None
    live = int(stats["bytes_in_use"])
    return {
        "bytes_in_use": live,
        "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", live)),
    }


class CompileCounter:
    """Process-cumulative backend-compile count via jax monitoring."""

    def __init__(self) -> None:
        self.count = 0

    # duration listeners receive (event, secs[, **kwargs])
    def _on_event(self, event: str, *args, **kwargs) -> None:
        if event == _COMPILE_EVENT:
            self.count += 1


_COMPILE_COUNTER: CompileCounter | None = None


def install_compile_counter() -> CompileCounter | None:
    """Register the monitoring listener (idempotent: re-install replaces).
    Returns None where jax (or its monitoring module) is unavailable —
    the observatory degrades to "no compile KPI", never to an error."""
    global _COMPILE_COUNTER
    uninstall_compile_counter()
    try:
        from jax import monitoring
    except ImportError:
        return None
    c = CompileCounter()
    monitoring.register_event_duration_secs_listener(c._on_event)
    _COMPILE_COUNTER = c
    return c


def uninstall_compile_counter() -> None:
    global _COMPILE_COUNTER
    c = _COMPILE_COUNTER
    if c is not None:
        from jax import monitoring

        monitoring.unregister_event_duration_listener(c._on_event)
    _COMPILE_COUNTER = None


def compile_count() -> int | None:
    """Cumulative backend compiles this process, or None when the counter
    isn't installed (telemetry off, or no jax)."""
    c = _COMPILE_COUNTER
    return c.count if c is not None else None


def sample_device_plane(metrics: dict, hub, *, hbm_key: str, peak_key: str,
                        compiles_key: str) -> None:
    """Shared round/tick-boundary sampler: HBM live/peak + cumulative
    backend compiles into both the caller's KPI dict (History) and the
    typed hub (gauges + a monotone counter). Key names are the caller's
    registry constants (``server/*`` at round boundaries, ``serve/*`` at
    scheduler ticks). Skips silently where the backend doesn't report."""
    mem = device_memory()
    if mem is not None:
        metrics[hbm_key] = float(mem["bytes_in_use"])
        metrics[peak_key] = float(mem["peak_bytes_in_use"])
        hub.gauge(hbm_key).set(metrics[hbm_key])
        hub.gauge(peak_key).set(metrics[peak_key])
    n = compile_count()
    if n is not None:
        metrics[compiles_key] = float(n)
        hub.counter(compiles_key).inc_to(n)


class ProfileBusyError(RuntimeError):
    """A profile capture is already armed or active (HTTP 409)."""


class ProfileController:
    """On-demand ``jax.profiler`` capture over N round/tick units.

    :meth:`request` arms a capture; the product loops' unit boundaries
    (``telemetry.profile_tick`` in the server round loop and the serve
    scheduler) drive it: the first boundary after arming starts the trace,
    the N-th after that stops it. One capture at a time; artifacts land in
    ``{out_dir}/profile-{tag}-{seq}/`` (TensorBoard xplane format).

    ``profiler`` is injectable for tests; the default resolves
    ``jax.profiler`` lazily at start time. Profiler failures disarm and
    are recorded on :attr:`last_error` — a broken profiler must never take
    the round loop with it.
    """

    def __init__(self, out_dir: str, profiler: Any | None = None,
                 clock=time.time) -> None:
        self.out_dir = str(out_dir)
        self._profiler = profiler
        self._clock = clock
        self._lock = threading.Lock()
        self._pending = 0  # units requested, capture not yet started
        self._active_left = 0  # boundaries left until stop
        self._active_dir: str | None = None
        self._active_tag = ""
        self._seq = 0
        self.completed: list[dict] = []
        self.last_error: str | None = None

    # -- arming ------------------------------------------------------------
    def request(self, n_units: int, tag: str = "ondemand") -> dict:
        """Arm a capture for ``n_units`` upcoming units. Raises
        :class:`ProfileBusyError` when one is already armed/active, and
        ValueError on a non-positive unit count."""
        n = int(n_units)
        if n < 1:
            raise ValueError(f"profile units must be >= 1, got {n_units}")
        with self._lock:
            if self._pending or self._active_left:
                raise ProfileBusyError(
                    "a profile capture is already armed or active"
                )
            self._pending = n
            self._active_tag = "".join(
                ch for ch in str(tag) if ch.isalnum() or ch in "-_"
            ) or "ondemand"
        return {"armed_units": n, "tag": self._active_tag}

    # -- the product-loop boundary hook -----------------------------------
    def tick(self, label: str) -> None:
        """One unit boundary. Cheap when idle: two int reads, no lock."""
        if not (self._pending or self._active_left):
            return
        with self._lock:
            if self._pending:
                n, self._pending = self._pending, 0
                self._seq += 1
                out = (pathlib.Path(self.out_dir)
                       / f"profile-{self._active_tag}-{self._seq}")
                if self._start(str(out)):
                    self._active_left = n
                    self._active_dir = str(out)
                return
            if self._active_left:
                self._active_left -= 1
                if self._active_left == 0:
                    self._stop(label)

    def _start(self, out: str) -> bool:
        try:
            if self._profiler is None:
                import jax

                self._profiler = jax.profiler
            pathlib.Path(out).mkdir(parents=True, exist_ok=True)
            self._profiler.start_trace(out)
            return True
        except Exception as e:  # noqa: BLE001 — never take the loop down
            self.last_error = f"{type(e).__name__}: {e}"
            return False

    def _stop(self, label: str) -> None:
        try:
            self._profiler.stop_trace()
            self.completed.append({
                "dir": self._active_dir,
                "tag": self._active_tag,
                "stopped_at": label,
                "ts": self._clock(),
            })
        except Exception as e:  # noqa: BLE001
            self.last_error = f"{type(e).__name__}: {e}"
        self._active_dir = None

    def close(self) -> None:
        """Force-stop an active capture (telemetry uninstall / end of run)
        so a trace armed for more rounds than the run had still flushes."""
        with self._lock:
            self._pending = 0
            if self._active_left:
                self._active_left = 0
                self._stop("close")

    def status(self) -> dict:
        with self._lock:
            return {
                "armed_units": self._pending,
                "active_units_left": self._active_left,
                "active_dir": self._active_dir,
                "completed": list(self.completed),
                "last_error": self.last_error,
            }
