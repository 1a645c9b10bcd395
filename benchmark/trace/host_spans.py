"""The host plane of a profiler trace, read by the program's span names.

The program writes each of its phases into any open profiler session as a
``jax.profiler.TraceAnnotation`` (``photon_tpu/telemetry``): a host event on
the device trace's clock, on the line of the thread that ran it, its
attributes as stats. The harness's own ``Run.span`` writes the same kind of
event. This module returns those events and nothing else of the host plane
(the runtime's own events, ``PjitFunction(...)`` and the like, are not
spans): a span is an event whose name is lower-case words joined by ``/``.

- ``self_s`` is a span's duration minus the part that child spans on the
  same line cover; a ``leaf`` has no child span.
- Spans on other lines (pool workers, the checkpoint writer) are found by
  time: ``inside(spans, outer)`` is every span of any line that lies within
  ``outer``'s interval.

A program without such spans (a parent commit) gives an empty list or lists
without the names asked for; every function then returns 0 or ``None`` and
raises nothing. Read with ``jax.profiler.ProfileData`` and nothing else.
"""

from __future__ import annotations

import dataclasses
import functools
import pathlib
import re
import statistics

from benchmark.trace.reduce import find_xplane, union

SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(/[a-z0-9_]+)+$")
#: spans of threads nothing waits for: they overlap the round's own work, so
#: they cover none of it (the writer of the previous round's checkpoint)
BACKGROUND = ("server/ckpt_async_write_s",)


@dataclasses.dataclass(frozen=True)
class HostSpan:
    name: str
    start_s: float
    end_s: float
    line: int  # index of the thread's line in the host plane
    stats: dict
    self_s: float
    leaf: bool
    parent: str | None  # name of the enclosing span on the same line

    @property
    def seconds(self) -> float:
        return self.end_s - self.start_s


def _nest(events: list[tuple[float, float, str, dict]], line: int) -> list[HostSpan]:
    """One line's events, sorted by start with the enclosing one first, as
    spans with self time, leafness and parent."""
    out: list[HostSpan] = []
    stack: list[list] = []  # [end, name, start, stats, covered, n_children, parent]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, start, stats, covered, n_children, parent = stack.pop()
            out.append(HostSpan(name, start, end, line, stats,
                                max(end - start - covered, 0.0),
                                n_children == 0, parent))

    for lo, hi, name, stats in events:
        close(lo)
        parent = None
        if stack:
            stack[-1][4] += min(hi, stack[-1][0]) - lo
            stack[-1][5] += 1
            parent = stack[-1][1]
        stack.append([hi, name, lo, stats, 0.0, 0, parent])
    close(float("inf"))
    return out


def spans_of(planes) -> list[HostSpan]:
    """Every span of the host planes of an opened trace, sorted by start."""
    out: list[HostSpan] = []
    n_line = 0
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                       e.name, dict(e.stats))
                      for e in line.events if SPAN_NAME.match(e.name)]
            events.sort(key=lambda ev: (ev[0], -ev[1]))
            out += _nest(events, n_line)
            n_line += 1
    out.sort(key=lambda s: (s.start_s, -s.end_s))
    return out


@functools.lru_cache(maxsize=2)
def _read(path: str) -> tuple[HostSpan, ...]:
    from jax.profiler import ProfileData

    return tuple(spans_of(ProfileData.from_file(path).planes))


def host_spans(trace_dir: pathlib.Path | str | None) -> list[HostSpan]:
    """The spans of the trace under ``trace_dir`` (``Run.trace_dir``); an
    untraced run (``None``) has none."""
    if trace_dir is None:
        return []
    return list(_read(str(find_xplane(trace_dir))))


def named(spans, *names: str) -> list[HostSpan]:
    return [s for s in spans if s.name in names]


def inside(spans, outer: HostSpan) -> list[HostSpan]:
    """Spans of any line that lie within ``outer``'s interval, ``outer``
    itself left out."""
    return [s for s in spans if s is not outer
            and s.start_s >= outer.start_s and s.end_s <= outer.end_s]


def per_unit(spans, unit: str, value) -> float | None:
    """The median, over the trace's ``unit`` spans (the harness's
    ``server/round``), of ``value(spans inside that unit, the unit)``;
    ``None`` when the trace holds no such unit or ``value`` finds nothing."""
    values = [value(inside(spans, u), u) for u in named(spans, unit)]
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def named_self_seconds(*names: str):
    """``value`` for :func:`per_unit`: the summed self seconds of the spans
    of these names, ``None`` where there is none of them."""
    def value(members, _unit):
        hits = named(members, *names)
        return sum(s.self_s for s in hits) if hits else None
    return value


def unattributed_seconds(members, unit: HostSpan) -> float | None:
    """``unit``'s seconds that no leaf span of any line covers: host work
    that has no name yet. ``None`` where the program wrote no span at all."""
    leaves = [(max(s.start_s, unit.start_s), min(s.end_s, unit.end_s))
              for s in members if s.leaf and s.name not in BACKGROUND]
    if not leaves:
        return None
    return unit.seconds - sum(hi - lo for lo, hi in union(leaves))


def table(spans) -> list[dict]:
    """Seconds by span name, the largest self time first: for reading a
    trace by hand (``tools/span_table.py``)."""
    rows: dict[str, dict] = {}
    for s in spans:
        row = rows.setdefault(s.name, {"span": s.name, "count": 0, "seconds": 0.0,
                                       "self_s": 0.0, "lines": set()})
        row["count"] += 1
        row["seconds"] += s.seconds
        row["self_s"] += s.self_s
        row["lines"].add(s.line)
    out = sorted(rows.values(), key=lambda r: -r["self_s"])
    for row in out:
        row["lines"] = sorted(row["lines"])
    return out
