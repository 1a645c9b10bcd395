"""Continuous batching: bounded admission queue + slot-level scheduling.

The serving loop (one driver thread) interleaves two phases forever:

1. **admit** — pop FIFO from the bounded queue into free slots while the
   paged pool can cover each request's worst-case block reservation.
   Admission itself is CHEAP (``engine.begin``: reserve blocks, install
   the table row — no model compute); the prompt's tokens then prefill
   through the step phase's chunk stream;
2. **step** — ONE unified mixed chunked-prefill engine step
   (``engine.mixed_step``): every decoding slot advances one token AND
   the oldest prefilling request's next prompt chunk — at most
   ``prefill_token_budget`` tokens — rides in the same program. A prompt
   larger than the budget is SPLIT across consecutive steps, so decode
   cadence (TPOT) is bounded by one budget-sized chunk, never by a whole
   giant prompt (the PR 5 "one over-budget prompt admits alone" carve-out
   let a single 4x-budget prompt stall every in-flight decode for its
   full prefill — ``tests/test_ragged_attention.py`` pins the fix). Rows
   that hit their EOS or ``max_new_tokens`` are evicted immediately and
   their blocks/slot recycled, so the next iteration's admit phase refills
   mid-flight: the refill is what continuous batching has over serving in
   whole waves. With speculative decoding on (``serve.speculative``,
   ISSUE 15), every decoding row may additionally carry up to K
   drafter-proposed tokens, verified in the SAME step — the accepted
   prefix plus one model token all emit at once,
   under a per-tick draft budget composed with ``prefill_token_budget``
   and an accept-rate EWMA that throttles K down to plain decode on
   incompressible traffic (``serve/draft.py``).

Backpressure is reject-not-buffer: :meth:`ContinuousBatcher.submit` raises
:class:`QueueFullError` when ``max_queue`` requests are already waiting —
the HTTP frontend maps it to 429 so load sheds at the edge instead of
growing an unbounded deque. Admission is strictly FIFO: a head request
that doesn't fit (no slot / not enough free blocks) BLOCKS later arrivals
rather than being overtaken (no starvation of big requests).

Telemetry: per-request ``serve/request`` umbrella spans with
queue/prefill/decode children (emitted at completion into the installed
tracer, if any), and the ``serve/*`` KPIs from the registry recorded into
a :class:`History` every scheduler tick — rendered by the frontend's
``/metrics`` via ``telemetry/prom.py``.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from photon_tpu import chaos, telemetry
from photon_tpu.analysis.runtime import steady_point
from photon_tpu.metrics.history import History
from photon_tpu.serve.engine import PagedEngine
from photon_tpu.utils.profiling import (
    AUTOPILOT_ACTION_RECLAIM,
    AUTOPILOT_KNOB_PREFILL_BUDGET,
    AUTOPILOT_KNOB_SPEC_K_MAX,
    EVENT_HOTSWAP_SWAPPED,
    SERVE_ADAPTER_COHORTS,
    SERVE_ADAPTER_EVICTIONS,
    SERVE_ADAPTER_HIT_RATE,
    SERVE_ADAPTER_LOADS,
    SERVE_ADAPTER_RESIDENTS,
    SERVE_ATTN_CTX_BLOCKS,
    SERVE_ATTN_LIVE_FRAC,
    SERVE_ATTN_RAGGED,
    SERVE_CHUNK_SPLIT_PROMPTS,
    SERVE_CHUNK_STEPS,
    SERVE_CHUNK_TOKENS,
    SERVE_COMPILES_TOTAL,
    SERVE_DECODE_SPAN,
    SERVE_EVICTIONS,
    SERVE_HBM_BYTES_IN_USE,
    SERVE_HBM_PEAK_BYTES,
    SERVE_HOTSWAP_ROUND,
    SERVE_HOTSWAP_SWAP_LATENCY_S,
    SERVE_HOTSWAP_SWAP_SPAN,
    SERVE_HOTSWAP_SWAPS_TOTAL,
    SERVE_PREFILL_SPAN,
    SERVE_PREFIX_EVICTIONS,
    SERVE_PREFIX_HIT_RATE,
    SERVE_PREFIX_SHARED_BLOCKS,
    SERVE_PREFIX_TOKENS_CACHED,
    SERVE_QUEUE_DEPTH,
    SERVE_QUEUE_SPAN,
    SERVE_QUEUE_WAIT_S,
    SERVE_REJECTED,
    SERVE_REQUEST_SPAN,
    SERVE_SLOT_OCCUPANCY,
    SERVE_SPEC_ACCEPT_RATE,
    SERVE_SPEC_ACCEPTED,
    SERVE_SPEC_DRAFTED,
    SERVE_SPEC_K,
    SERVE_SPEC_STEPS,
    SERVE_TOKENS_PER_S,
    SERVE_TPOT_S,
    SERVE_TTFT_S,
)


class QueueFullError(RuntimeError):
    """Admission queue at ``max_queue`` — the HTTP frontend's 429."""


class DrainingError(RuntimeError):
    """The batcher is draining (SIGTERM received) — new submissions are
    refused; the HTTP frontend maps this to 503 + ``Retry-After``."""


@dataclass
class ServeRequest:
    """One generation request and its streaming output channel."""

    rid: int
    prompt: list[int]
    max_new_tokens: int
    temperature: float = 0.0
    seed: int = 0
    eos_id: int | None = None
    #: adapter cohort (ISSUE 13): decode through this cohort's LoRA pages;
    #: None = the bare base model
    cohort: str | None = None
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    generated: list[int] = field(default_factory=list)
    error: str | None = None
    finished: bool = False
    _out: "queue.Queue[int | None]" = field(default_factory=queue.Queue)

    def stream(self, timeout: float = 60.0):
        """Yield generated token ids as they land; StopIteration on finish.
        Raises RuntimeError if the request failed server-side."""
        while True:
            tok = self._out.get(timeout=timeout)
            if tok is None:
                if self.error:
                    raise RuntimeError(self.error)
                return
            yield tok

    def result(self, timeout: float = 60.0) -> list[int]:
        """Block until completion; the full generated-token list."""
        for _ in self.stream(timeout=timeout):
            pass
        return self.generated

    @property
    def ttft_s(self) -> float:
        return max(0.0, self.t_first - self.t_submit)


class ContinuousBatcher:
    """Single-driver-thread scheduler over a :class:`PagedEngine`: freed
    slots are refilled mid-flight."""

    def __init__(self, engine: PagedEngine, *, max_queue: int = 64,
                 prefill_token_budget: int = 2048,
                 default_eos_id: int | None = None,
                 history: History | None = None,
                 speculative=None, drafter=None) -> None:
        self.engine = engine
        self.max_queue = max_queue
        self.prefill_token_budget = prefill_token_budget
        self.default_eos_id = default_eos_id
        # self-drafted speculative decoding (ISSUE 15, serve/draft.py):
        # `speculative` is a SpeculativeConfig (photon.serve.speculative);
        # `drafter` overrides the default NGramDrafter (tests, learned
        # drafters). Silently ineligible for MoE — batch-global expert
        # capacity breaks the per-row purity the verification leans on
        # (the prefix cache makes the same call)
        self._spec = None
        self._drafter = None
        self._spec_budget = 0
        spec_on = speculative is not None and getattr(speculative, "enabled",
                                                      False)
        if spec_on and getattr(getattr(engine, "mc", None), "mlp",
                               None) == "moe":
            spec_on = False
        if spec_on:
            from photon_tpu.serve.draft import NGramDrafter, SpecController

            self._drafter = drafter if drafter is not None else NGramDrafter(
                speculative.max_ngram, speculative.min_ngram
            )
            self._spec = SpecController(
                speculative.k, accept_floor=speculative.accept_floor,
                ewma_alpha=speculative.ewma_alpha,
                probe_ticks=speculative.probe_ticks,
            )
            self._spec_budget = speculative.draft_budget
        self.history = history if history is not None else History()
        self._queue: deque[ServeRequest] = deque()
        self._running: dict[int, ServeRequest] = {}  # slot -> request
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._stop = False
        self._draining = False
        self._thread: threading.Thread | None = None
        self._rid = itertools.count()
        self._tick = 0
        # cumulative counters (read by /healthz and the KPI tick)
        self.rejected = 0
        self.evictions = 0
        self.completed = 0
        self.swaps = 0
        # chunked-prefill counters (ISSUE 12): steps that carried a
        # chunk, tokens prefilled through the chunk stream, prompts whose
        # suffix exceeded one budget (the split that protects TPOT)
        self.chunk_steps = 0
        self.chunk_tokens = 0
        self.chunk_split_prompts = 0
        # live checkpoint hot-swap (ISSUE 11): (params, round, done-event,
        # t_request) staged by request_swap, applied by the driver thread
        # at the swap point — between decode steps, with zero active slots
        self._pending_swap: tuple | None = None
        # FIFO-audit ring (tests assert order); bounded — a serving daemon
        # must not grow per-request state forever
        self.admitted_order: deque[int] = deque(maxlen=4096)
        #: per-KPI History cap: /metrics only ever renders the LATEST value,
        #: so old ticks are trimmed rather than accumulating ~50 tuples/s
        #: of resident growth for the lifetime of the server
        self.max_kpi_ticks = 4096
        #: device-plane introspection cadence: HBM/compile stats are
        #: sampled every N scheduler ticks, not every tick
        self.device_sample_ticks = 64
        # SLO autopilot knobs (ISSUE 19): registered at construction so the
        # controller only ever drives a batcher that actually exists; the
        # current values become the declared optima relax probes toward
        ap = telemetry.autopilot_active()
        if ap is not None:
            ap.register_knob(AUTOPILOT_KNOB_PREFILL_BUDGET,
                             lambda: self.prefill_token_budget,
                             self.set_prefill_token_budget, integer=True)
            if self._spec is not None:
                ap.register_knob(AUTOPILOT_KNOB_SPEC_K_MAX,
                                 lambda: self._spec.k_max,
                                 self._spec.set_k_max, integer=True)
            ap.register_action(
                AUTOPILOT_ACTION_RECLAIM,
                lambda: self.reclaim_memory(int(ap.cfg.reclaim_free_blocks)),
            )

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "ContinuousBatcher":
        self._thread = threading.Thread(
            target=self._loop, name="photon-serve-batcher", daemon=True
        )
        self._thread.start()
        return self

    def close(self, timeout: float = 30.0) -> None:
        with self._work:
            self._stop = True
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            self._thread = None

    @property
    def draining(self) -> bool:
        with self._lock:
            return self._draining

    def drain(self, timeout_s: float = 30.0) -> bool:
        """Graceful shutdown (ISSUE 8 satellite): stop ADMITTING new
        requests immediately (:meth:`submit` raises :class:`DrainingError`
        → HTTP 503), let everything already accepted — queued AND running —
        finish, bounded by ``timeout_s`` (``photon.serve.drain_timeout_s``),
        then stop the scheduler; anything still unfinished at the bound is
        failed by ``_drain_on_stop`` ("server shutting down"). Returns True
        when the drain completed with zero dropped requests."""
        with self._work:
            self._draining = True
            # a swap staged just before the drain is ABANDONED, not applied:
            # applying would churn params under in-flight requests, while
            # leaving it staged would keep admission paused and starve the
            # queued requests the drain promises to finish. The watcher's
            # waiter unblocks and sees the round unchanged.
            pending, self._pending_swap = self._pending_swap, None
            self._work.notify_all()
        if pending is not None:
            pending[2].set()
        deadline = time.monotonic() + timeout_s
        drained = False
        while time.monotonic() < deadline:
            with self._lock:
                if not self._queue and not self._running:
                    drained = True
                    break
            time.sleep(0.01)
        self.close()
        return drained

    # -- runtime-mutable knobs + actuators (ISSUE 19) ----------------------
    def set_prefill_token_budget(self, budget: int) -> None:
        """Runtime-mutable chunk budget: the SLO autopilot shrinks this
        under queue saturation (cheaper ticks → decode keeps its cadence
        while the backlog drains) and probes it back toward the declared
        value when the breach clears. One lock acquisition; out-of-range
        values are rejected loudly, never clamped silently."""
        b = int(budget)
        if b < 1:
            raise ValueError(
                f"prefill_token_budget must be >= 1, got {budget}"
            )
        with self._lock:
            self.prefill_token_budget = b

    def reclaim_memory(self, min_free_blocks: int = 8) -> tuple[float, float]:
        """HBM-pressure actuator: evict unpinned prefix-cache entries until
        the paged pool covers ``min_free_blocks``, then shrink the adapter
        pool's unpinned LRU residents. Safe under live traffic — both paths
        skip anything a running slot still references. Returns the pool's
        ``(free_blocks_before, free_blocks_after)`` for the decision
        record."""
        eng = self.engine
        alloc = getattr(eng, "allocator", None)
        before = float(alloc.free_blocks) if alloc is not None else 0.0
        pc = getattr(eng, "prefix_cache", None)
        if pc is not None:
            pc.ensure_free(int(min_free_blocks))
        pool = getattr(eng, "adapter_pool", None)
        if pool is not None:
            pool.shrink()
        after = float(alloc.free_blocks) if alloc is not None else before
        return before, after

    def recycle(self, timeout_s: float = 30.0) -> bool:
        """Soft restart (the fleet autopilot's "drain and restart" leg):
        pause admission, wait — bounded — for queued and running work to
        finish, reclaim engine caches (prefix flush + adapter LRU shrink),
        then resume admission. Unlike :meth:`drain` the driver thread
        KEEPS RUNNING, so the replica re-enters rotation without a process
        restart. Returns True when the engine fully quiesced inside the
        bound (the cache reclaim happens either way: both paths are safe
        against pinned state)."""
        with self._work:
            if self._stop:
                return False
            self._draining = True
            self._work.notify_all()
        deadline = time.monotonic() + timeout_s
        idle = False
        while time.monotonic() < deadline:
            with self._lock:
                if not self._queue and not self._running:
                    idle = True
                    break
            time.sleep(0.01)
        try:
            pc = getattr(self.engine, "prefix_cache", None)
            if pc is not None:
                pc.flush()
            pool = getattr(self.engine, "adapter_pool", None)
            if pool is not None:
                pool.shrink()
        finally:
            with self._work:
                self._draining = False
                self._work.notify_all()
        return idle

    # -- live checkpoint hot-swap (ISSUE 11) ------------------------------
    def request_swap(self, params, loaded_round: int | None = None,
                     adapter_bank: dict | None = None) -> threading.Event:
        """Stage a parameter swap; returns an Event set once the driver
        thread has applied it. Ordering guarantees (docs/serving.md):
        admission pauses (queued/new requests wait — nothing is dropped),
        running slots finish their generations on the OLD params, then the
        swap is one reference assignment and the prefix cache flushes.
        ``adapter_bank`` (ISSUE 13) rides the same staged tuple, so base
        params and per-cohort adapters swap ATOMICALLY at the quiesced
        point — a request can never decode new-base KV through old-base
        adapters. A draining/stopped batcher refuses
        (:class:`DrainingError`) — the watcher retries after the drain
        decision is final."""
        with self._work:
            if self._stop or self._draining:
                raise DrainingError("batcher draining/stopped: swap refused")
            if self._pending_swap is not None:
                raise RuntimeError("a param swap is already pending")
            done = threading.Event()
            self._pending_swap = (params, loaded_round, done,
                                  time.monotonic(), adapter_bank)
            self._work.notify_all()
        return done

    @property
    def swap_pending(self) -> bool:
        with self._lock:
            return self._pending_swap is not None

    def _maybe_swap(self) -> None:
        """The swap point: driver thread only, between decode steps. Fires
        exactly when a swap is staged and no slot is active (admission is
        paused while one is staged, so the engine quiesces in at most the
        longest running request's remaining steps)."""
        with self._lock:
            if self._pending_swap is None or self._running:
                return
            # CLAIM the swap under the lock: a drain() racing in after this
            # point finds nothing to abandon, so exactly one of {apply,
            # abandon} ever happens and done fires exactly once
            params, rnd, done, t0, bank = self._pending_swap
            self._pending_swap = None
        try:
            if bank is not None:
                self.engine.set_params(params, loaded_round=rnd,
                                       adapter_bank=bank)
            else:
                self.engine.set_params(params, loaded_round=rnd)
        except BaseException:
            # a failed apply must still release the waiter (it observes the
            # unchanged round and reports the abandon) — otherwise the
            # watcher wedges in 'pending' forever. The re-raise reaches the
            # loop's handler, which fails in-flight requests loudly (the
            # engine's param state is unknown after a partial swap).
            done.set()
            raise
        latency = time.monotonic() - t0
        with self._lock:
            self.swaps += 1
        tr = telemetry.active()
        if tr is not None:
            tr.add_span(SERVE_HOTSWAP_SWAP_SPAN, time.time() - latency,
                        latency, round=-1 if rnd is None else int(rnd))
        telemetry.metric_observe(SERVE_HOTSWAP_SWAP_LATENCY_S, latency)
        telemetry.emit_event(
            EVENT_HOTSWAP_SWAPPED,
            round=-1 if rnd is None else int(rnd),
            latency_s=round(latency, 6),
        )
        done.set()

    # -- submission (any thread) ------------------------------------------
    def submit(self, prompt: list[int], max_new_tokens: int, *,
               temperature: float = 0.0, seed: int = 0,
               eos_id: int | None = None,
               cohort: str | None = None) -> ServeRequest:
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if not self.engine.fits(len(prompt), max_new_tokens):
            raise ValueError(
                f"request needs {len(prompt)}+{max_new_tokens} tokens — over "
                f"this server's context capacity"
            )
        if cohort is not None:
            # reject unknown cohorts at SUBMIT (the frontend's 400), not at
            # admission: a queued unknown-cohort request could never admit
            # and would FIFO head-block the queue forever
            has = getattr(self.engine, "has_cohort", None)
            if has is None or not has(cohort):
                pool = getattr(self.engine, "adapter_pool", None)
                known = pool.cohorts() if pool is not None else []
                raise ValueError(
                    f"unknown adapter cohort {cohort!r} — this server "
                    f"serves {known}"
                )
        # eos_id: None → server default; negative → explicitly no EOS
        eos = self.default_eos_id if eos_id is None else (
            None if eos_id < 0 else int(eos_id)
        )
        req = ServeRequest(
            rid=next(self._rid), prompt=list(prompt),
            max_new_tokens=max_new_tokens, temperature=temperature, seed=seed,
            eos_id=eos, cohort=cohort, t_submit=time.monotonic(),
        )
        with self._work:
            if self._stop:
                raise RuntimeError("batcher is shut down")
            if self._draining:
                raise DrainingError("server draining: not accepting new requests")
            if len(self._queue) >= self.max_queue:
                self.rejected += 1
                raise QueueFullError(
                    f"admission queue full ({self.max_queue} waiting)"
                )
            self._queue.append(req)
            self._work.notify_all()
        return req

    @property
    def queue_depth(self) -> int:
        with self._lock:
            return len(self._queue)

    def load_report(self) -> dict:
        """Cheap point-in-time load signal (ISSUE 16): queue length,
        live-slot fraction, draining flag — the router's power-of-two-
        choices input, served on /healthz and over the fleet control
        plane. One lock acquisition, no engine work."""
        with self._lock:
            return {
                "queue_depth": len(self._queue),
                "live_slot_frac": len(self._running) / self.engine.n_slots,
                "draining": self._draining or self._stop,
            }

    def spec_stats(self) -> dict | None:
        """Speculative-decoding counters for /healthz (None when off).
        Lock-snapshotted like :meth:`stats` — the HTTP handler thread
        must not observe a half-applied observe() update."""
        if self._spec is None:
            return None
        with self._lock:
            return {
                "drafted": self._spec.drafted,
                "accepted": self._spec.accepted,
                "spec_steps": self._spec.spec_steps,
                "accept_ewma": round(self._spec.ewma, 4),
                "k": self._spec.k_effective(),
            }

    def stats(self) -> dict[str, float]:
        with self._lock:
            out = {
                SERVE_QUEUE_DEPTH: float(len(self._queue)),
                SERVE_SLOT_OCCUPANCY: len(self._running) / self.engine.n_slots,
                SERVE_EVICTIONS: float(self.evictions),
                SERVE_REJECTED: float(self.rejected),
                SERVE_HOTSWAP_SWAPS_TOTAL: float(self.swaps),
                SERVE_CHUNK_STEPS: float(self.chunk_steps),
                SERVE_CHUNK_TOKENS: float(self.chunk_tokens),
                SERVE_CHUNK_SPLIT_PROMPTS: float(self.chunk_split_prompts),
            }
            if self._spec is not None:
                out[SERVE_SPEC_DRAFTED] = float(self._spec.drafted)
                out[SERVE_SPEC_ACCEPTED] = float(self._spec.accepted)
                out[SERVE_SPEC_STEPS] = float(self._spec.spec_steps)
                out[SERVE_SPEC_ACCEPT_RATE] = round(self._spec.ewma, 4)
                out[SERVE_SPEC_K] = float(self._spec.k_effective())
            # getattr: fake/minimal engines (tests, alternative backends)
            # need not carry the checkpoint- or prefix-plane attributes
            rnd = getattr(self.engine, "loaded_round", None)
            if rnd is not None:
                out[SERVE_HOTSWAP_ROUND] = float(rnd)
            attn = getattr(self.engine, "attn_stats", None)
            if attn is not None:
                a = attn()
                out[SERVE_ATTN_CTX_BLOCKS] = a["ctx_blocks"]
                out[SERVE_ATTN_LIVE_FRAC] = a["live_frac"]
                out[SERVE_ATTN_RAGGED] = a["ragged"]
        pc = getattr(self.engine, "prefix_cache", None)
        if pc is not None:
            out[SERVE_PREFIX_HIT_RATE] = pc.hit_rate
            out[SERVE_PREFIX_SHARED_BLOCKS] = float(len(pc))
            out[SERVE_PREFIX_EVICTIONS] = float(pc.evictions)
            out[SERVE_PREFIX_TOKENS_CACHED] = float(pc.tokens_cached)
        ast = getattr(self.engine, "adapter_stats", None)
        if ast is not None and (a := ast()) is not None:
            out[SERVE_ADAPTER_RESIDENTS] = a["residents"]
            out[SERVE_ADAPTER_COHORTS] = a["cohorts"]
            out[SERVE_ADAPTER_LOADS] = a["loads"]
            out[SERVE_ADAPTER_EVICTIONS] = a["evictions"]
            out[SERVE_ADAPTER_HIT_RATE] = a["hit_rate"]
        return out

    # -- driver loop -------------------------------------------------------
    def _loop(self) -> None:
        while True:
            with self._work:
                while (not self._stop and not self._queue
                       and not self._running and self._pending_swap is None):
                    self._work.wait(timeout=0.5)
                if self._stop:
                    break
            try:
                self._maybe_swap()
                self._admit_phase()
                self._step_phase()
            except Exception as e:  # noqa: BLE001 — fail loudly, not silently
                self._fail_all(f"{type(e).__name__}: {e}")
            self._record_tick()
            # retrace-sentinel hook (analysis/runtime.py): one None check
            # when no sentinel is installed; under the e2e fixture it bills
            # any steady-state compile to the tick that caused it — the
            # machine-checked form of "admission never retraces"
            steady_point("serve/tick")
            # on-demand profiling unit boundary (POST /debug/profile arms a
            # capture over N ticks); one None check when nothing is armed
            telemetry.profile_tick("serve/tick")
        self._drain_on_stop()

    def _admit_phase(self) -> None:
        if self.swap_pending:
            # quiesce toward the swap point: nothing new starts on params
            # about to be replaced; queued requests wait (never dropped)
            # and running slots drain through the step phase
            return
        while True:
            with self._lock:
                head = self._queue[0] if self._queue else None
            if head is None:
                return
            slot = self.engine.free_slot()
            # cohort kwarg only when the request names one: fake/minimal
            # engines (tests, alternative backends) need not grow the
            # adapter-plane signature
            extra = {} if head.cohort is None else {"cohort": head.cohort}
            if slot is None or not self.engine.can_admit(
                len(head.prompt), head.max_new_tokens, prompt=head.prompt,
                **extra,
            ):
                return  # FIFO head-blocking: nobody overtakes
            with self._lock:
                req = self._queue.popleft()
            req.t_admit = time.monotonic()
            try:
                # admission is the CHEAP half now (reserve + table row):
                # the prompt itself prefills through the step phase's
                # chunk stream, budget-bounded per step
                self.engine.begin(
                    slot, req.prompt, req.max_new_tokens,
                    temperature=req.temperature, seed=req.seed, **extra,
                )
            except Exception as e:  # noqa: BLE001 — fail THIS request, keep serving
                # engine.begin is transactional (blocks freed, slot released)
                # — only this request dies, and its client gets the error
                # instead of a timeout
                req.finished = True
                req.error = f"admission failed: {type(e).__name__}: {e}"
                req.t_first = req.t_done = time.monotonic()
                self._emit_spans(req)
                req._out.put(None)
                continue
            self.admitted_order.append(req.rid)
            if self._drafter is not None:
                self._drafter.begin(slot, req.prompt)
            with self._lock:
                self._running[slot] = req
            if self.engine.pending_tokens(slot) > self.prefill_token_budget:
                self.chunk_split_prompts += 1

    def _step_phase(self) -> None:
        """One mixed chunked-prefill step: all decoding slots advance one
        token; the OLDEST prefilling request (FIFO by rid — admission
        order) contributes its next chunk, at most
        ``prefill_token_budget`` tokens. Chunks serialize across
        requests (one prompt chunks at a time — its chunk widths then
        depend only on its own length and the budget, which is what
        keeps the step-shape bucket set deterministic), while decode
        rows ride along EVERY step: a giant prompt can delay a decode
        token by one chunk, never by a whole prefill."""
        with self._lock:
            running = dict(self._running)
        if not running:
            return
        chunk = None
        prefilling = [(slot, req) for slot, req in running.items()
                      if self.engine.pending_tokens(slot) > 0]
        if prefilling:
            slot, _ = min(prefilling, key=lambda it: it[1].rid)
            chunk = (slot, min(self.engine.pending_tokens(slot),
                               self.prefill_token_budget))
            self.chunk_steps += 1
            self.chunk_tokens += chunk[1]
        t0 = time.monotonic()
        if self._spec is None:
            nxt, emitted = self.engine.mixed_step(chunk)
            out = nxt[:, None]
            n_em = emitted.astype(int)
        else:
            drafts = self._collect_drafts(running, chunk)
            out, n_em = self.engine.spec_step(chunk, drafts)
            self._spec.observe(
                sum(len(d) for d in drafts.values()),
                # accepted drafts per row = emissions minus the bonus
                sum(max(0, int(n_em[s]) - 1) for s in drafts),
            )
        dt = time.monotonic() - t0
        # chaos serve storm (ISSUE 19): a deterministic per-token stall
        # amplifies the compute-proportional cost of this tick, so the
        # autopilot's budget shrink measurably protects decode cadence
        inj = chaos.active()
        if inj is not None:
            stall = inj.serve_stall_plan(
                (chunk[1] if chunk else 0) + sum(int(x) for x in n_em)
            )
            if stall > 0.0:
                time.sleep(stall)
        n_tokens = 0
        for slot in sorted(running):
            n = int(n_em[slot])
            if n < 1:
                continue  # mid-prefill: nothing to stream yet
            req = self._running.get(slot)
            if req is None or req.finished:
                continue
            if not req.generated:
                req.t_first = time.monotonic()  # the request's FIRST token
            burst = []
            for j in range(n):
                tok = int(out[slot, j])
                burst.append(tok)
                n_tokens += 1
                self._push_token(slot, req, tok)
                if req.finished:
                    # EOS / max_new landed mid-burst: the tail of the
                    # burst is discarded (its KV sits behind the evicted
                    # slot's recycled blocks — never readable)
                    break
            if self._drafter is not None and not req.finished:
                self._drafter.observe(slot, burst)
        if dt > 0 and n_tokens:
            self.history.record(self._tick, {SERVE_TOKENS_PER_S: n_tokens / dt})

    def _collect_drafts(self, running: dict, chunk) -> dict[int, list[int]]:
        """Per-tick draft assembly (ISSUE 15): ask the throttle for this
        step's depth, then the drafter for each DECODING slot's guess,
        under a per-tick token budget composed with the prefill budget —
        a step already carrying a C-token chunk drafts at most
        ``min(draft_budget, prefill_token_budget - C)`` so the grid's
        total token work stays bounded by the same knob that bounds
        chunks. Each row's depth is also capped at ``remaining - 1``
        (drafting past max_new_tokens would verify tokens the request
        can never emit)."""
        k_eff = self._spec.next_k()
        if k_eff < 1:
            return {}
        budget = self._spec_budget
        if chunk is not None:
            budget = min(budget, self.prefill_token_budget - chunk[1])
        if budget < 1:
            return {}
        drafts: dict[int, list[int]] = {}
        for slot, req in sorted(running.items()):
            if req.finished or self.engine.pending_tokens(slot) > 0:
                continue
            k_s = min(k_eff, req.max_new_tokens - len(req.generated) - 1,
                      budget)
            if k_s < 1:
                continue
            d = self._drafter.propose(slot, k_s)
            if d:
                drafts[slot] = d
                budget -= len(d)
                if budget < 1:
                    break
        return drafts

    def _push_token(self, slot: int, req: ServeRequest, tok: int) -> None:
        req.generated.append(tok)
        req._out.put(tok)
        if (req.eos_id is not None and tok == req.eos_id) \
                or len(req.generated) >= req.max_new_tokens:
            self._finish(slot, req)

    def _finish(self, slot: int, req: ServeRequest,
                error: str | None = None) -> None:
        req.finished = True
        req.error = error
        req.t_done = time.monotonic()
        self.engine.evict(slot)
        if self._drafter is not None:
            self._drafter.end(slot)
        with self._lock:
            self._running.pop(slot, None)
            self.evictions += 1
            if error is None:
                self.completed += 1
        if error is None:
            self.history.record(self._tick, {SERVE_TTFT_S: req.ttft_s})
        ctx = self._emit_spans(req)
        self._observe_request(req, ctx, error)
        req._out.put(None)

    def _fail_all(self, msg: str) -> None:
        """An engine error poisons every in-flight request (their cache
        state is unknown) — fail them loudly and keep serving the queue."""
        with self._lock:
            running = list(self._running.items())
        for slot, req in running:
            self._finish(slot, req, error=msg)

    def _drain_on_stop(self) -> None:
        with self._lock:
            queued, self._queue = list(self._queue), deque()
            running = list(self._running.items())
            # a swap the stopped loop will never apply: unblock its waiter
            # (it observes the unchanged round and reports the abandon)
            pending, self._pending_swap = self._pending_swap, None
        if pending is not None:
            pending[2].set()
        for slot, req in running:
            self._finish(slot, req, error="server shutting down")
        for req in queued:
            req.finished = True
            req.error = "server shutting down"
            req._out.put(None)

    # -- telemetry ---------------------------------------------------------
    def _record_tick(self) -> None:
        self._tick += 1
        stats = self.stats()
        hub = telemetry.metrics_active()
        if hub is not None:
            # typed twins of the tick KPIs: gauges for the point-in-time
            # numbers, cumulative counters for the monotone ones (the
            # History bridge keeps serving the per-tick series)
            hub.gauge(SERVE_QUEUE_DEPTH).set(stats[SERVE_QUEUE_DEPTH])
            hub.gauge(SERVE_SLOT_OCCUPANCY).set(stats[SERVE_SLOT_OCCUPANCY])
            hub.counter(SERVE_EVICTIONS).inc_to(stats[SERVE_EVICTIONS])
            hub.counter(SERVE_REJECTED).inc_to(stats[SERVE_REJECTED])
            hub.counter(SERVE_HOTSWAP_SWAPS_TOTAL).inc_to(
                stats[SERVE_HOTSWAP_SWAPS_TOTAL])
            hub.counter(SERVE_CHUNK_STEPS).inc_to(stats[SERVE_CHUNK_STEPS])
            hub.counter(SERVE_CHUNK_TOKENS).inc_to(stats[SERVE_CHUNK_TOKENS])
            hub.counter(SERVE_CHUNK_SPLIT_PROMPTS).inc_to(
                stats[SERVE_CHUNK_SPLIT_PROMPTS])
            if SERVE_SPEC_DRAFTED in stats:
                hub.counter(SERVE_SPEC_DRAFTED).inc_to(
                    stats[SERVE_SPEC_DRAFTED])
                hub.counter(SERVE_SPEC_ACCEPTED).inc_to(
                    stats[SERVE_SPEC_ACCEPTED])
                hub.counter(SERVE_SPEC_STEPS).inc_to(stats[SERVE_SPEC_STEPS])
                hub.gauge(SERVE_SPEC_ACCEPT_RATE).set(
                    stats[SERVE_SPEC_ACCEPT_RATE])
                hub.gauge(SERVE_SPEC_K).set(stats[SERVE_SPEC_K])
            if SERVE_ATTN_CTX_BLOCKS in stats:
                hub.gauge(SERVE_ATTN_CTX_BLOCKS).set(
                    stats[SERVE_ATTN_CTX_BLOCKS])
                hub.gauge(SERVE_ATTN_LIVE_FRAC).set(
                    stats[SERVE_ATTN_LIVE_FRAC])
                hub.gauge(SERVE_ATTN_RAGGED).set(stats[SERVE_ATTN_RAGGED])
            if SERVE_HOTSWAP_ROUND in stats:
                hub.gauge(SERVE_HOTSWAP_ROUND).set(stats[SERVE_HOTSWAP_ROUND])
            if SERVE_ADAPTER_RESIDENTS in stats:
                hub.gauge(SERVE_ADAPTER_RESIDENTS).set(
                    stats[SERVE_ADAPTER_RESIDENTS])
                hub.gauge(SERVE_ADAPTER_COHORTS).set(
                    stats[SERVE_ADAPTER_COHORTS])
                hub.gauge(SERVE_ADAPTER_HIT_RATE).set(
                    stats[SERVE_ADAPTER_HIT_RATE])
                hub.counter(SERVE_ADAPTER_LOADS).inc_to(
                    stats[SERVE_ADAPTER_LOADS])
                hub.counter(SERVE_ADAPTER_EVICTIONS).inc_to(
                    stats[SERVE_ADAPTER_EVICTIONS])
            if SERVE_PREFIX_HIT_RATE in stats:
                hub.gauge(SERVE_PREFIX_HIT_RATE).set(
                    stats[SERVE_PREFIX_HIT_RATE])
                hub.gauge(SERVE_PREFIX_SHARED_BLOCKS).set(
                    stats[SERVE_PREFIX_SHARED_BLOCKS])
                hub.counter(SERVE_PREFIX_EVICTIONS).inc_to(
                    stats[SERVE_PREFIX_EVICTIONS])
                hub.counter(SERVE_PREFIX_TOKENS_CACHED).inc_to(
                    stats[SERVE_PREFIX_TOKENS_CACHED])
            if (self._tick - 1) % self.device_sample_ticks == 0:
                # HBM live/peak + backend compiles, sampled sparsely — a
                # per-tick memory_stats() call would tax the decode cadence
                from photon_tpu.telemetry.introspect import sample_device_plane

                sample_device_plane(
                    stats, hub, hbm_key=SERVE_HBM_BYTES_IN_USE,
                    peak_key=SERVE_HBM_PEAK_BYTES,
                    compiles_key=SERVE_COMPILES_TOTAL,
                )
        health = telemetry.health_active()
        if health is not None:
            health.check_serve_tick(
                queue_depth=int(stats[SERVE_QUEUE_DEPTH]),
                max_queue=self.max_queue,
            )
            hbm = stats.get(SERVE_HBM_BYTES_IN_USE)
            # chaos HBM-pressure ramp (ISSUE 19): strictly-monotone
            # inflation of the sample (synthesized when the backend
            # reports none) so the growth watcher latches deterministically
            inj = chaos.active()
            if inj is not None:
                ramp = inj.hbm_ramp_plan()
                if ramp > 0.0:
                    hbm = (hbm if hbm is not None else 1.0) * (1.0 + ramp)
            if hbm is not None:
                health.note_hbm_sample(hbm, plane="serve")
        self.history.record(self._tick, stats)
        for series in self.history.rounds.values():
            if len(series) > self.max_kpi_ticks:
                del series[: len(series) - self.max_kpi_ticks]
        # SLO autopilot (ISSUE 19): the serve plane's evaluation point —
        # one None check when disabled, a period-gated rule sweep when on
        ap = telemetry.autopilot_active()
        if ap is not None:
            ap.tick("serve", max_queue=self.max_queue)

    def _observe_request(self, req: ServeRequest, ctx: tuple | None,
                         error: str | None) -> None:
        """Per-request latency DISTRIBUTIONS into the typed hub (ISSUE 10):
        TTFT, queue wait, and TPOT (decode seconds per output token after
        the first). The exemplar is the request's umbrella span, so a fat
        bucket links straight to the slow request's timeline. One None
        check when telemetry is off; failed requests don't pollute the
        latency histograms."""
        hub = telemetry.metrics_active()
        if hub is None or error is not None:
            return
        hub.histogram(SERVE_TTFT_S).observe(req.ttft_s, exemplar=ctx)
        if req.t_admit:
            hub.histogram(SERVE_QUEUE_WAIT_S).observe(
                max(0.0, req.t_admit - req.t_submit), exemplar=ctx
            )
        n = len(req.generated)
        if n > 1 and req.t_done > req.t_first:
            hub.histogram(SERVE_TPOT_S).observe(
                (req.t_done - req.t_first) / (n - 1), exemplar=ctx
            )

    def _emit_spans(self, req: ServeRequest) -> tuple | None:
        """Request phases as completed spans: a ``serve/request`` umbrella
        with queue/prefill/decode children. Wall-epoch anchored at emit
        time (phase boundaries were captured on the monotonic clock).
        Returns the umbrella's ``(trace_id, span_id)`` for exemplar use,
        or None when telemetry is off."""
        tr = telemetry.active()
        if tr is None:
            return None
        now_wall, now_mono = time.time(), time.monotonic()

        def wall(t_mono: float) -> float:
            return now_wall - (now_mono - t_mono)

        umbrella = tr.add_span(
            SERVE_REQUEST_SPAN, wall(req.t_submit), req.t_done - req.t_submit,
            rid=req.rid, n_prompt=len(req.prompt), n_generated=len(req.generated),
            error=req.error or "",
        )
        parent = (umbrella.trace_id, umbrella.span_id)
        for name, a, b in (
            (SERVE_QUEUE_SPAN, req.t_submit, req.t_admit or req.t_done),
            (SERVE_PREFILL_SPAN, req.t_admit, req.t_first),
            (SERVE_DECODE_SPAN, req.t_first, req.t_done),
        ):
            if a and b >= a:
                tr.add_span(name, wall(a), b - a, parent=parent, rid=req.rid)
        return parent


def serve_history_kpis(history: History) -> dict[str, float]:
    """Latest value of every serve KPI in ``history`` (healthz payload)."""
    return {
        k: v
        for k in (SERVE_TTFT_S, SERVE_TOKENS_PER_S, SERVE_QUEUE_DEPTH,
                  SERVE_SLOT_OCCUPANCY, SERVE_EVICTIONS, SERVE_REJECTED,
                  SERVE_HOTSWAP_SWAPS_TOTAL, SERVE_HOTSWAP_ROUND,
                  SERVE_PREFIX_HIT_RATE, SERVE_PREFIX_SHARED_BLOCKS,
                  SERVE_SPEC_ACCEPT_RATE, SERVE_SPEC_ACCEPTED,
                  SERVE_SPEC_DRAFTED)
        if (v := history.latest(k)) is not None
    }
