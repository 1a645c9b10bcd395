"""Streaming in-place weighted aggregation.

Reference semantics (``photon/strategy/aggregation.py:44-118``): consume
client results one at a time from a generator — only one client's tensors are
materialized beyond the running average at any moment — maintaining

    x_i = x_i * (n_prev / n_new) + y_i * (n_cur / n_new)

per layer, where ``n_prev`` is the sample count already folded in, ``n_cur``
the incoming client's count, ``n_new = n_prev + n_cur``. Mathematically equal
to the sample-weighted mean but O(1) in memory w.r.t. client count.

Host-plane pipeline (PR 2): the fold is a FUSED single pass — each incoming
array is rescaled into the fp64 accumulator chunk by chunk, so the full-
payload ``y.astype(np.float64)`` temporary of the two-pass fold (one extra
fp64 model copy per client, ~1 GB at the 125M recipe) never exists; the peak
transient is one ``_FOLD_CHUNK``-element chunk per worker. With a
:class:`~photon_tpu.utils.hostpool.HostPool` the per-array folds run in
parallel and the NEXT client's payload is fetched + decoded on the pool
while the current one folds (bounded lookahead of 1). That relaxes the
memory contract from "running average + 1 client" to "running average + 2
clients" — still O(1) in client count. Every mode (serial, threads=1,
threads=N) applies identical per-element operations in identical order, so
the averaged result is BIT-IDENTICAL across configurations.
"""

from __future__ import annotations

import math
from typing import Callable, Iterable, Iterator, Sequence

import numpy as np

from photon_tpu import telemetry
from photon_tpu.utils.hostpool import HostPool
from photon_tpu.utils.profiling import AGG_DECODE_TIME, AGG_FOLD_TIME

#: elements per fold chunk (~8 MB of fp64 transient): large enough that the
#: ufunc dominates the Python loop, small enough that per-worker transients
#: stay invisible next to the accumulator
_FOLD_CHUNK = 1 << 20


def _fold_into(acc: np.ndarray, y: np.ndarray, w_prev: float, w_cur: float) -> None:
    """``acc = acc * w_prev + y * w_cur`` as one chunked in-place pass.

    Element-for-element this applies exactly the operations of the classic
    two-pass fold (``acc *= w_prev; acc += y.astype(f64) * w_cur``) — same
    multiplies, same add, same order — so results are bit-identical while
    the full-array fp64 upcast of ``y`` is never materialized."""
    flat_acc = acc.reshape(-1)
    if not np.may_share_memory(flat_acc, acc):
        # reshape COPIED (non-contiguous acc): the in-place fold below would
        # mutate the copy and silently drop this client's contribution
        raise ValueError("_fold_into needs a C-contiguous accumulator")
    flat_y = np.asarray(y).reshape(-1)
    for off in range(0, flat_acc.size, _FOLD_CHUNK):
        sl = slice(off, off + _FOLD_CHUNK)
        a = flat_acc[sl]
        a *= w_prev
        t = flat_y[sl].astype(np.float64)
        t *= w_cur
        a += t
        del t  # else two chunk temps coexist across the loop boundary


def flat_views(arrays: Iterable[np.ndarray]) -> list[np.ndarray]:
    """1-d views for reading (a non-contiguous array is copied, so never
    write through these)."""
    return [np.asarray(a).reshape(-1) for a in arrays]


def map_chunks(fn: Callable[[int, slice], object], sizes: Sequence[int],
               pool: HostPool) -> list:
    """``fn(i, sl)`` for every ``_FOLD_CHUNK``-element chunk ``sl`` of every
    array ``i`` (``sizes`` are element counts), over ``pool`` as the fold
    is: inline with ``threads == 1``. Results come back in (array, chunk)
    order whatever ran where, so a sum over them does not depend on
    scheduling. A chunk and not an array is the unit of work: the embedding
    is a third of mpt-125m and would be one worker's task."""
    return pool.map(lambda piece: fn(*piece), [
        (i, slice(off, off + _FOLD_CHUNK))
        for i, n in enumerate(sizes) for off in range(0, n, _FOLD_CHUNK)])


def chunk_buffers(pool: HostPool, dtype, count: int) -> list[np.ndarray]:
    """``count`` chunk-sized scratch arrays of ``dtype`` that belong to the
    calling thread and to ``pool``, and outlive the call: a pass over the
    model then maps no fresh pages for its temporaries (first touch of
    fresh pages, not arithmetic, was the cost of the whole-model
    expressions this replaces)."""
    held = pool.scratch.__dict__.setdefault(np.dtype(dtype).str, [])
    while len(held) < count:
        held.append(np.empty(_FOLD_CHUNK, dtype))
    return held[:count]


def _sumsq_chunk(pool: HostPool, chunk: np.ndarray) -> float:
    """Sum of squares of one chunk, accumulated in float64."""
    (wide,) = chunk_buffers(pool, np.float64, 1)
    # not np.dot: BLAS threads of its own under the pool's cost 5x here
    return float(np.sum(np.square(chunk, out=wide[:chunk.size], dtype=np.float64)))


def sumsq(arrays: Iterable[np.ndarray], pool: HostPool | None = None) -> float:
    """Float64 sum of squares over a list of arrays, chunk by chunk: no
    float64 copy of an array ever exists. No ``pool`` is an inline one."""
    pool = pool or HostPool(1)
    flats = flat_views(arrays)
    return math.fsum(map_chunks(
        lambda i, sl: _sumsq_chunk(pool, flats[i][sl]), [f.size for f in flats], pool))


def diff_sumsq(xs: Sequence[np.ndarray], ys: Sequence[np.ndarray],
               pool: HostPool | None = None) -> tuple[float, float]:
    """``(sum((x - y)**2), sum(x**2))`` in one pass over both lists: the
    difference in the arrays' own precision, as ``x - y`` gives it, and both
    sums in float64. The whole difference is never held. Used for the
    server's pseudo-gradient and parameter norms (``x`` the global weights,
    ``y`` the average) and for the client's (``x`` what it trained, ``y``
    what it was sent)."""
    if len(xs) != len(ys):
        raise ValueError(f"{len(xs)} arrays against {len(ys)}")
    pool = pool or HostPool(1)
    fx, fy = flat_views(xs), flat_views(ys)

    def one(i: int, sl: slice) -> tuple[float, float]:
        x, y = fx[i][sl], fy[i][sl]
        (d,) = chunk_buffers(pool, np.result_type(x, y), 1)
        return (_sumsq_chunk(pool, np.subtract(x, y, out=d[:x.size])),
                _sumsq_chunk(pool, x))

    parts = map_chunks(one, [f.size for f in fx], pool)
    return math.fsum(p[0] for p in parts), math.fsum(p[1] for p in parts)


def aggregate_inplace(
    results: Iterable[tuple[object, int]],
    decode: Callable[[object], list[np.ndarray]] | None = None,
    pool: HostPool | None = None,
    timings: dict[str, float] | None = None,
) -> tuple[list[np.ndarray], int]:
    """Streaming sample-weighted mean over ``(arrays, n_samples)`` results.

    Returns (averaged arrays, total samples). The first result's arrays are
    copied (fp64 accumulate is deliberate — matches the reference's float
    numpy accumulation and keeps the running rescale stable).

    A result's first element may also be a compressed payload
    (:class:`photon_tpu.compression.CompressedPayload`) when ``decode`` is
    given: each payload is dequantized HERE, one client at a time, so memory
    stays O(1) in client count.

    ``pool`` (a :class:`HostPool` with ``threads > 1``) enables the
    pipelined path: per-array folds run in parallel and ONE lookahead
    worker pulls + decodes the next result while the current one folds —
    only that single worker ever advances the ``results`` iterator, so
    generators with side effects (the server's sliding-window stream)
    need no locking. Peak residency: running average + the folding client
    + the decoded-ahead client.

    ``timings`` (optional dict) accumulates ``decode_s`` (decode seconds
    only, summed across workers — the blocking wait for a client's reply is
    deliberately excluded) and ``fold_s`` (fold seconds)."""

    def _arrays(item) -> list[np.ndarray]:
        if isinstance(item, (list, tuple)):
            return list(item)
        if decode is None:
            raise TypeError(
                f"aggregate_inplace got a {type(item).__name__} result but "
                "no decode callback — pass decode= to consume compressed "
                "payload streams"
            )
        return decode(item)

    t_decode = [0.0]
    t_fold = [0.0]
    it: Iterator = iter(results)
    # per-client decode/fold windows are spans under whatever round span is
    # open on the CALLING thread: decode-ahead runs on a pool worker with an
    # empty context stack, so the parent is captured here. Each span's own
    # timer is what accumulates into the KPI of the same name.
    trace_parent = telemetry.current_context()
    n_seen = [0]

    def fold_span():
        return telemetry.span(AGG_FOLD_TIME, parent=trace_parent)

    def _fetch_decode() -> tuple[list[np.ndarray], int] | None:
        """Pull + decode the next result (runs on the pool when pipelined;
        returns None at stream end — StopIteration must not cross the
        future boundary). Only the DECODE is timed: ``next(it)`` blocks on
        the driver until a client finishes its local fit, and charging
        minutes of client training to ``agg_decode_time`` would drown the
        host-work decomposition the KPI exists for."""
        try:
            item, n_cur = next(it)
        except StopIteration:
            return None
        with telemetry.span(AGG_DECODE_TIME, parent=trace_parent,
                            client_index=n_seen[0]) as sp:
            arrays = _arrays(item)
        t_decode[0] += sp.seconds
        # per-client decode seconds as a DISTRIBUTION (typed hub): a fat
        # tail here is one slow client's payload, invisible in the summed
        # KPI the same seconds accumulate into
        telemetry.metric_observe(AGG_DECODE_TIME, sp.seconds)
        n_seen[0] += 1
        return arrays, n_cur

    first = _fetch_decode()
    if first is None:
        raise ValueError("aggregate_inplace: empty results")
    arrays, n_total = first
    if n_total <= 0:
        raise ValueError(f"non-positive n_samples {n_total}")

    # np.array, not asarray: the accumulator is OURS to write, and the first
    # payload is not (a read-only view of the sender's segment on the shm
    # plane, the sender's own arrays on the inline one) — an already-fp64
    # payload would pass through asarray as itself. order="C": _fold_into
    # relies on acc.reshape(-1) being a VIEW of the accumulator
    with fold_span() as sp:
        if pool is not None:
            acc = pool.map(lambda a: np.array(a, dtype=np.float64, order="C"), arrays)
        else:
            acc = [np.array(a, dtype=np.float64, order="C") for a in arrays]
    t_fold[0] += sp.seconds

    pipelined = pool is not None and pool.pipelined
    pending = pool.submit(_fetch_decode) if pipelined else None
    try:
        while True:
            cur = pending.result() if pipelined else _fetch_decode()
            if cur is None:
                pending = None
                break
            if pipelined:
                # decode-ahead: client k+1 fetches/dequantizes on the pool
                # while client k folds below (bounded lookahead of 1)
                pending = pool.submit(_fetch_decode)
            arrays, n_cur = cur
            if n_cur <= 0:
                raise ValueError(f"non-positive n_samples {n_cur}")
            if len(arrays) != len(acc):
                # a shorter payload would fold PARTIALLY (acc tail never
                # rescaled by w_prev for this client) — e.g. a momenta-
                # extended checkpoint replayed into a momenta-less run
                raise ValueError(
                    f"result has {len(arrays)} arrays, accumulator {len(acc)} "
                    "(momenta mismatch between payloads?)"
                )
            n_new = n_total + n_cur
            w_prev = n_total / n_new
            w_cur = n_cur / n_new
            with fold_span() as sp:
                if pool is not None:
                    pool.map(
                        lambda i, _a=arrays, _wp=w_prev, _wc=w_cur: _fold_into(
                            acc[i], _a[i], _wp, _wc
                        ),
                        range(len(acc)),
                    )
                else:
                    for a, y in zip(acc, arrays):
                        _fold_into(a, y, w_prev, w_cur)
            t_fold[0] += sp.seconds
            telemetry.metric_observe(AGG_FOLD_TIME, sp.seconds)
            n_total = n_new
    except BaseException:
        if pending is not None:
            # best-effort: a queued lookahead is cancelled; a RUNNING one is
            # left to finish on the (daemon-friendly) pool — the stream it
            # holds belongs to a round that is already failing
            pending.cancel()
        raise

    with fold_span() as sp:
        if pool is not None:
            out = pool.map(lambda a: a.astype(np.float32), acc)
        else:
            out = [a.astype(np.float32) for a in acc]
    t_fold[0] += sp.seconds
    if timings is not None:
        timings["decode_s"] = timings.get("decode_s", 0.0) + t_decode[0]
        timings["fold_s"] = timings.get("fold_s", 0.0) + t_fold[0]
    return out, n_total


def weighted_loss_avg(results: Iterable[tuple[int, float]]) -> float:
    """Sample-weighted mean loss (reference: flwr's ``weighted_loss_avg`` used
    by ``evaluate_utils.py:33-158``)."""
    results = list(results)
    total = sum(n for n, _ in results)
    if total == 0:
        raise ValueError("weighted_loss_avg: zero total samples")
    return float(sum(n * loss for n, loss in results) / total)


def weighted_average_metrics(
    results: Iterable[tuple[int, dict[str, float]]],
) -> dict[str, float]:
    """Sample-weighted mean of per-client scalar metric dicts (reference:
    ``strategy/aggregation.py:172`` ``weighted_average``).

    Single pass over the results: per-key numerator and denominator
    accumulate together (the old per-key recompute was O(keys × clients)
    passes over the result list). Keys carried only by zero-weight clients
    are dropped rather than dividing by zero."""
    num: dict[str, float] = {}
    den: dict[str, int] = {}
    for n, m in results:
        for k, v in m.items():
            num[k] = num.get(k, 0.0) + n * v
            den[k] = den.get(k, 0) + n
    return {k: float(num[k] / den[k]) for k in num if den[k] > 0}
