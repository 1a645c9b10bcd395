"""A profiler trace (``.xplane.pb``) reduced to what the metrics read.

- device busy seconds: the union of the intervals in which an operation ran
  on a chip, averaged over the chips used; the traced window's length;
- seconds by device operation, as SELF time: an operation that encloses
  others on its line (a ``while`` around its body) is charged only what its
  children do not cover, so sums never count a nanosecond twice;
- the longest idle gaps of the busiest-read chip, each named by the shortest
  host event that covers most of it (the harness's ``TraceAnnotation`` spans
  are host events on the same clock).

Read with ``jax.profiler.ProfileData`` and nothing else.
"""

from __future__ import annotations

import pathlib
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OPS_LINE = "XLA Ops"
# lines of a device plane that repeat the operations at a coarser grain
COARSE_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                "Framework Name Scope", "Source code")
SCOPE_STATS = ("tf_op", "hlo_op", "long_name", "name_scope", "kernel_details")
N_GAPS = 10


def find_xplane(trace_dir: pathlib.Path | str) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def short_name(name: str) -> str:
    """``%fusion.3 = f32[..] fusion(...)`` -> ``fusion.3``; on a TPU an
    operation's event carries its whole HLO text as its name."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def _events(line) -> list[tuple[float, float, str, str]]:
    """``(start, end, short name, detail)``; the detail is the full event
    name and its scope stats, for a reader's pattern to search."""
    out = []
    for e in line.events:
        detail = e.name
        for key, value in e.stats:
            if key in SCOPE_STATS and isinstance(value, str):
                detail += " " + value
        out.append((float(e.start_ns), float(e.start_ns + e.duration_ns),
                    short_name(e.name), detail))
    out.sort(key=lambda ev: (ev[0], -ev[1]))
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def self_times(events) -> list[tuple[str, str, float]]:
    """``(name, scope, self_ns)`` per event of one line; events sorted by
    start, enclosing first."""
    out = []
    stack: list[list] = []  # [end, name, scope, duration, covered_by_children]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, scope, dur, covered = stack.pop()
            out.append((name, scope, max(dur - covered, 0.0)))

    for lo, hi, name, scope in events:
        close(lo)
        if stack:
            stack[-1][4] += min(hi, stack[-1][0]) - lo
        stack.append([hi, name, scope, hi - lo, 0.0])
    close(float("inf"))
    return out


def _device_lines(plane):
    lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
    if lines:
        return lines
    return [ln for ln in plane.lines if ln.name not in COARSE_LINES]


def _name_gap(lo: float, hi: float, host_events) -> str:
    best, best_len = "(no host event)", float("inf")
    for h_lo, h_hi, name in host_events:
        overlap = min(hi, h_hi) - max(lo, h_lo)
        if overlap >= 0.5 * (hi - lo) and (h_hi - h_lo) < best_len:
            best, best_len = name, h_hi - h_lo
    return best


def reduce_trace(trace_dir, device_ids=None, window_s: float | None = None) -> dict:
    """See the module docstring. ``device_ids`` picks the chips the cell
    used (all device planes when None); ``window_s`` defaults to the span
    from the first to the last event in the trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_xplane(trace_dir)))
    per_device: dict[int, dict] = {}
    host_events: list[tuple[float, float, str]] = []
    first, last = float("inf"), float("-inf")
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for lo, hi, name, _ in _events(line):
                        host_events.append((lo, hi, name))
                        first, last = min(first, lo), max(last, hi)
            continue
        dev = int(m.group(2))
        if device_ids is not None and dev not in device_ids:
            continue
        spans, ops = [], {}
        for line in _device_lines(plane):
            events = _events(line)
            spans += [(lo, hi) for lo, hi, _, _ in events]
            for name, scope, ns in self_times(events):
                entry = ops.setdefault(name, {"name": name, "scope": scope,
                                              "seconds": 0.0, "count": 0})
                entry["seconds"] += ns * 1e-9
                entry["count"] += 1
        busy = union(spans)
        if busy:
            first, last = min(first, busy[0][0]), max(last, busy[-1][1])
        per_device[dev] = {"busy": busy, "ops": ops,
                           "busy_s": sum(hi - lo for lo, hi in busy) * 1e-9}
    if not per_device:
        raise RuntimeError("the trace has no device plane: no operation ran "
                           "on a chip inside the traced window")
    if window_s is None:
        window_s = (last - first) * 1e-9

    ops_total: dict[str, dict] = {}
    for d in per_device.values():
        for name, entry in d["ops"].items():
            tot = ops_total.setdefault(name, dict(entry, seconds=0.0, count=0))
            tot["seconds"] += entry["seconds"] / len(per_device)
            tot["count"] += entry["count"]
    ops_sorted = sorted(ops_total.values(), key=lambda e: -e["seconds"])

    # gaps on the first chip used: between busy intervals and at both ends
    dev0 = per_device[min(per_device)]
    edges = [first] + [t for iv in dev0["busy"] for t in iv] + [last]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    by_name: dict[str, float] = {}
    for lo, hi in gaps[:200]:
        name = _name_gap(lo, hi, host_events)
        by_name[name] = by_name.get(name, 0.0) + (hi - lo) * 1e-9

    return {
        "window_s": float(window_s),
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / len(per_device),
        "n_devices": len(per_device),
        "ops": ops_sorted,
        "device_ops": [[e["name"], e["seconds"]] for e in ops_sorted[:N_GAPS]],
        "idle_gaps": [[n, s] for n, s in sorted(by_name.items(),
                                                key=lambda kv: -kv[1])[:N_GAPS]],
        "longest_gap_s": (gaps[0][1] - gaps[0][0]) * 1e-9 if gaps else 0.0,
    }


def op_seconds(reduction: dict, pattern: str) -> tuple[float, int]:
    """Summed self seconds and count of the operations whose full event text
    (HLO text and scope stats) matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [e for e in reduction["ops"] if rx.search(e["scope"])]
    return sum(e["seconds"] for e in hits), sum(e["count"] for e in hits)
