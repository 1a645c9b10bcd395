"""One run of one cell: what every kind of traffic shares.

``execute`` resolves the cell's files by name, hands a :class:`Run` to the
driver of the traffic's kind, reduces the trace of a traced run, calls the
cell's per-layer readers and builds the result line. Drivers call into the
program; everything that measures lives here and in the files beside it.
"""

from __future__ import annotations

import contextlib
import gc
import json
import math
import pathlib
import shutil
import statistics
import time

from benchmark.spec import Cell, Spec

HERE = pathlib.Path(__file__).resolve().parent


class NoAcceleratorError(RuntimeError):
    """No chip, too few chips, or a chip whose peaks are not on record."""


def load_peaks(device_kind: str, path: pathlib.Path | None = None) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in ``peaks.json`` is an error, never a default."""
    table = json.loads((path or HERE / "peaks.json").read_text())
    for entry in table["chips"]:
        if device_kind in entry["device_kinds"]:
            return entry
    raise NoAcceleratorError(
        f"device_kind {device_kind!r} is not in benchmark/peaks.json: add its "
        "published peaks with their source before measuring on it")


def require_chips(n_chips: int) -> tuple[list, dict]:
    import jax

    devices = jax.devices()
    if devices[0].platform == "cpu":
        raise NoAcceleratorError(
            "JAX found no accelerator (platform 'cpu'): the benchmark has no "
            "CPU fallback")
    if len(devices) < n_chips:
        raise NoAcceleratorError(
            f"the cell asks for {n_chips} chip(s), JAX found {len(devices)}")
    return devices[:n_chips], load_peaks(devices[0].device_kind)


def device_peak_bytes(device) -> int:
    """The most of a chip's memory that was taken at once, as JAX reports it:
    the allocator's peak (arrays) plus the peak reserved for the temporaries
    of the programs that ran (on a TPU those are reserved apart and are not
    in ``peak_bytes_in_use``; measured on a v5e, PERF.md)."""
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0))


class CompileClock:
    """Programs JAX built or loaded, and the seconds that took, as its own
    monitoring events count them (a persistent-cache hit counts as a program
    with a short time: inside a timed window either is a fault)."""

    def __init__(self) -> None:
        import jax.monitoring

        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += secs
            self.programs += 1

    def close(self) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_event)


class Run:
    """What a driver is given, and where it leaves what it measured."""

    def __init__(self, *, cell: Cell, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, work_dir: pathlib.Path,
                 devices: list, peaks: dict, t_process: float) -> None:
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = int(seed), float(seconds), trace
        self.work_dir, self.devices, self.peaks = work_dir, devices, peaks
        self.t_process = t_process
        self.clock = CompileClock()
        self.spans: list[tuple[str, float, float]] = []  # name, start, end
        self.counters: dict[str, float] = {}
        self.samples: dict[str, list[float]] = {}
        self.checks: list[dict] = []
        self.end_to_end: dict[str, float] = {}
        self.attempted = 0
        self.failed = 0
        self.setup_s: float | None = None
        self.setup_compile_seconds: float | None = None
        self.window: tuple[float, float] | None = None
        self.compiles_in_window: int | None = None
        self.memory_peak_bytes = 0
        self.trace_dir: pathlib.Path | None = None
        self.trace_window: tuple[float, float] | None = None
        self._trace_stop_at: float | None = None
        self._trace_stop_s = 0.0

    # -- spans, counters, samples ------------------------------------------
    @contextlib.contextmanager
    def span(self, name: str):
        """A host span around a call into one layer; in a traced run it is
        also written into the profiler's trace, on the device's clock."""
        import jax

        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.monotonic()))

    def sample(self, name: str, value: float) -> None:
        self.samples.setdefault(name, []).append(float(value))

    def span_seconds(self, name: str) -> list[float]:
        lo, hi = self.window or (float("-inf"), float("inf"))
        return [e - s for n, s, e in self.spans if n == name and s >= lo and e <= hi]

    # -- the timed window --------------------------------------------------
    @contextlib.contextmanager
    def timed_window(self):
        """Everything before this is set-up. Nothing may compile inside."""
        import jax

        gc.collect()
        gc.freeze()  # set-up's garbage is not collected inside the window
        self.setup_compile_seconds = self.clock.seconds
        programs_before = self.clock.programs
        if self.trace:
            self.trace_dir = self.work_dir / "trace"
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host spans only: traces stay small
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=options)
            self._trace_stop_at = time.monotonic() + float(
                self.traffic.get("trace_seconds", self.seconds))
        t0 = time.monotonic()
        if self.trace:
            self.trace_window = (t0, t0)
        self.setup_s = t0 - self.t_process
        try:
            yield t0
        finally:
            t1 = time.monotonic()
            self.window = (t0, t1)
            self.stop_trace_if_due(force=True)
            self.compiles_in_window = self.clock.programs - programs_before
            self.memory_peak_bytes = max(map(device_peak_bytes, self.devices),
                                         default=0)
            gc.unfreeze()

    def stop_trace_if_due(self, force: bool = False) -> None:
        """Drivers call this between units of work: a trace covers the first
        ``trace_seconds`` of the window, not all of it (traces are large)."""
        import jax

        if self._trace_stop_at is None:
            return
        now = time.monotonic()
        if force or now >= self._trace_stop_at:
            jax.profiler.stop_trace()
            self.trace_window = (self.trace_window[0], now)
            self._trace_stop_at = None
            self._trace_stop_s = time.monotonic() - now

    def window_over(self, t0: float) -> bool:
        """Whether ``--seconds`` have passed since ``t0``, the profiler's stop
        apart (many seconds where the trace is long): a traced window holds
        the units of work that a timed one holds, so what is compared for
        ``correct`` is the same in both."""
        return time.monotonic() - t0 - self._trace_stop_s >= self.seconds

    # -- correctness -------------------------------------------------------
    def check(self, name: str, value: float, limit: float, *,
              at_least: bool = False) -> bool:
        """One number compared beside its limit; every one is printed."""
        value = float(value)
        ok = (value >= limit) if at_least else (value <= limit)
        ok = bool(ok and value == value)  # NaN never passes
        self.checks.append({"check": name, "value": value, "limit": limit,
                            "rule": ">=" if at_least else "<=", "ok": ok})
        return ok

    def check_loss_fell(self, before: float, losses: list[float], units: str) -> None:
        """``loss_fell``: the loss before the window less the median loss of
        the window's ``units`` (``fits``, ``rounds``) ``a`` to ``b - 1``,
        where ``[a, b]`` is the traffic file's ``loss_fall_<units>``. On one
        seed it is one number whatever else the window holds, and no one unit
        decides it. A window that holds fewer than ``b`` fails
        ``window_<units>`` and reads no ``loss_fell``: whatever it has
        instead would be the accident of its length."""
        a, b = self.traffic[f"loss_fall_{units}"]
        if not 0 <= a <= b - 3:
            raise ValueError(f"loss_fall_{units} {[a, b]}: a median needs three "
                             "or more, [a, b] with 0 <= a <= b - 3")
        print(json.dumps({"loss_before": before, f"window_{units}_losses": losses}),
              flush=True)
        if self.check(f"window_{units}", len(losses), b, at_least=True):
            self.check("loss_fell", before - statistics.median(losses[a:b]),
                       self.traffic["limits"]["loss_fall_min"], at_least=True)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c["ok"] for c in self.checks)


def median(values: list[float]) -> float | None:
    return statistics.median(values) if values else None


def percentile(values: list[float], q: float) -> float | None:
    """The ``q``-th percentile (0-100) by linear interpolation."""
    if not values:
        return None
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def execute(spec: Spec, workload: str, seed: int, seconds: float, trace: bool,
            *, t_process: float, devices_and_peaks=None,
            log=print, keep_work: bool = False) -> dict:
    """Run one cell once and return the result line as a dict.

    ``devices_and_peaks`` is for the tests under ``benchmark/tests``, which
    drive a run on the CPU at a toy size with peaks of their own; ``run.py``
    never passes it, so the command itself cannot run without a chip."""
    parts, run = prepare(spec, workload, seed, seconds, trace,
                         t_process=t_process, devices_and_peaks=devices_and_peaks)
    try:
        parts["driver"].run(run)
    finally:
        run.clock.close()
    return finish(spec, parts, run, log=log, keep_work=keep_work)


def prepare(spec: Spec, workload: str, seed: int, seconds: float, trace: bool,
            *, t_process: float, devices_and_peaks=None):
    """Resolve the cell's files, look for the chips, place the compile
    cache and make the :class:`Run` a driver is given."""
    parts = spec.resolve(workload)
    cell, traffic = parts["cell"], parts["traffic"]
    if devices_and_peaks is None:
        devices, peaks = require_chips(cell.chips)
        from photon_tpu.utils.compile_cache import use_compile_cache

        import jax

        use_compile_cache()
        # small programs too: a warm run finds every program in the cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    else:
        devices, peaks = devices_and_peaks
    work_dir = spec.root / ".bench_work" / cell.name
    shutil.rmtree(work_dir, ignore_errors=True)
    work_dir.mkdir(parents=True)
    run = Run(cell=cell, config=parts["config"], traffic=traffic, seed=seed,
              seconds=seconds, trace=trace, work_dir=work_dir,
              devices=devices, peaks=peaks, t_process=t_process)
    return parts, run


def finish(spec: Spec, parts: dict, run: Run, *, log=print,
           keep_work: bool = False) -> dict:
    """The result line of a run whose driver has returned."""
    cell, traffic, devices = parts["cell"], parts["traffic"], run.devices
    if run.window is None or run.setup_s is None:
        raise RuntimeError(f"driver {traffic['kind']!r} opened no timed window")
    run.check("compiles_in_window", run.compiles_in_window, 0)
    for c in run.checks:
        log(json.dumps(c))

    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(run.memory_peak_bytes),
    }
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": {}, "device": device}
    if not run.trace:
        values = dict(run.end_to_end, setup_s=run.setup_s)
        for m in parts["end_to_end"]:
            if values.get(m.name) is None:
                raise RuntimeError(f"driver {traffic['kind']!r} reported no "
                                   f"{m.name} in {cell.name}")
            result["metrics"][m.name] = {"value": values[m.name], "unit": m.unit}
    else:
        from benchmark.trace.reduce import reduce_trace

        reduction = reduce_trace(
            run.trace_dir, [d.id for d in devices],
            window_s=run.trace_window[1] - run.trace_window[0])
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = {"device_ops": reduction["device_ops"][:10],
                               "idle_gaps": reduction["idle_gaps"][:10]}
        for m in spec.cell_per_layer(cell):
            value = parts["per_layer"][m.name].read(run, reduction)
            if value is not None:
                result["metrics"][m.name] = {"value": float(value), "unit": m.unit}
    if not keep_work:
        shutil.rmtree(run.work_dir, ignore_errors=True)
    # last in the line: what a record of a run that was not correct keeps
    result["checks"] = {c["check"]: {"value": finite(c["value"]), "limit": c["limit"],
                                     "ok": c["ok"]} for c in run.checks}
    return result


def finite(value: float) -> float | str:
    """A number as JSON can hold it: ``nan`` and ``inf`` by name."""
    return value if math.isfinite(value) else repr(value)

