"""The scope of each device operation of a profiler trace.

On a TPU an operation's event carries its HLO instruction text as its name
and nothing of ``jax.named_scope``: the whole ``op_name``
(``jit(train_step)/train_step/optimizer/...``) is the ``tf_op`` stat of the
event's METADATA in the ``.xplane.pb``, which ``jax.profiler.ProfileData``
does not hand out with the event (seen on a v5e, jax 0.9.0; PERF.md). So
this module reads the metadata from the file itself: the few fields of the
XSpace protobuf it needs, decoded by hand (no generated module is among the
benchmark's dependencies).

``op_names`` maps an instruction's short name (``fusion.382``, as
``reduce.short_name`` gives it) to the ``op_name``s recorded for it;
``scope_seconds`` sums, from the reducer's self times, the operations whose
``op_name`` matches a pattern. A trace without such stats (another backend,
a program without scopes) gives an empty map and 0 seconds.
"""

from __future__ import annotations

import functools
import pathlib
import re

from benchmark.trace import host_spans
from benchmark.trace.reduce import DEVICE_PLANE, find_xplane, short_name

OP_NAME_STAT = "tf_op"
# field numbers of tsl/profiler/protobuf/xplane.proto
_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_METADATA, _XPLANE_STAT_METADATA = 2, 4, 5
_MAP_VALUE = 2
_XEVENTMETADATA_NAME, _XEVENTMETADATA_STATS = 2, 5
_XSTATMETADATA_ID, _XSTATMETADATA_NAME = 1, 2
_XSTAT_METADATA_ID, _XSTAT_STR, _XSTAT_BYTES, _XSTAT_REF = 1, 5, 6, 7


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return value, i


def _fields(buf: bytes):
    """``(field number, value)`` of one message: an int for a varint, the
    bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield number, value


def _first(buf: bytes, number: int, default=None):
    return next((v for n, v in _fields(buf) if n == number), default)


def _plane_op_names(plane: bytes) -> dict[str, set[str]]:
    stat_names: dict[int, str] = {}
    metadata: list[bytes] = []
    for number, value in _fields(plane):
        if number == _XPLANE_STAT_METADATA:
            entry = _first(value, _MAP_VALUE, b"")
            stat_names[_first(entry, _XSTATMETADATA_ID, 0)] = _first(
                entry, _XSTATMETADATA_NAME, b"").decode(errors="replace")
        elif number == _XPLANE_EVENT_METADATA:
            metadata.append(_first(value, _MAP_VALUE, b""))
    out: dict[str, set[str]] = {}
    for entry in metadata:
        name = _first(entry, _XEVENTMETADATA_NAME, b"").decode(errors="replace")
        for number, stat in _fields(entry):
            if number != _XEVENTMETADATA_STATS:
                continue
            fields = dict(_fields(stat))
            if stat_names.get(fields.get(_XSTAT_METADATA_ID)) != OP_NAME_STAT:
                continue
            text = fields.get(_XSTAT_STR, fields.get(_XSTAT_BYTES))
            if text is None:  # an interned string: the name of another stat
                text = stat_names.get(fields.get(_XSTAT_REF), "").encode()
            out.setdefault(short_name(name), set()).add(
                text.decode(errors="replace"))
    return out


@functools.lru_cache(maxsize=2)
def _read(path: str) -> dict[str, frozenset[str]]:
    out: dict[str, set[str]] = {}
    for number, plane in _fields(pathlib.Path(path).read_bytes()):
        if number != _XSPACE_PLANES:
            continue
        name = _first(plane, _XPLANE_NAME, b"").decode(errors="replace")
        if DEVICE_PLANE.match(name):
            for op, names in _plane_op_names(plane).items():
                out.setdefault(op, set()).update(names)
    return {op: frozenset(names) for op, names in out.items()}


def op_names(trace_dir: pathlib.Path | str | None) -> dict[str, frozenset[str]]:
    """Short instruction name -> the ``op_name``s its events' metadata hold,
    over the device planes of the trace under ``trace_dir``."""
    if trace_dir is None:
        return {}
    return _read(str(find_xplane(trace_dir)))


def scope_seconds(reduction: dict, names: dict, pattern: str) -> tuple[float, int]:
    """Summed self seconds and count of the reduced trace's operations whose
    ``op_name`` matches ``pattern``."""
    rx = re.compile(pattern)
    hits = [e for e in reduction["ops"]
            if any(rx.search(n) for n in names.get(e["name"], ()))]
    return sum(e["seconds"] for e in hits), sum(e["count"] for e in hits)


def device_ms_per_step(run, reduction, pattern: str) -> float | None:
    """Device milliseconds per optimizer step of the operations whose
    ``op_name`` matches ``pattern``: their self time in the reduced trace
    over the steps the trace holds (one ``trainer/next_batch`` span of the
    program each). ``None`` where either is missing."""
    seconds, _ = scope_seconds(reduction, op_names(run.trace_dir), pattern)
    steps = len(host_spans.named(host_spans.host_spans(run.trace_dir),
                                 "trainer/next_batch"))
    if not seconds or not steps:
        return None
    return 1000.0 * seconds / steps
