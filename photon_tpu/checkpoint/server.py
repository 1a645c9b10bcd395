"""Server round checkpoints: save/resume/GC/cross-run import.

Reference semantics (``photon/server/s3_utils.py``):
- layout ``{run_uuid}/server/{round}/``: ``state.bin`` (pickled control
  state: history, client_state, server_steps_cumulative, rng round counter) +
  ``current_server_parameters.npz`` + one ``{key}.npz`` per strategy
  ``state_keys`` (``:348-548``);
- a round is *valid* only if parameters and every declared state key are
  present (``:215-272``) — partial uploads are never resumed from;
- ``resume_round`` negative indexes from the latest valid round
  (``:1261-1318``);
- GC keeps the newest N rounds (``cleanup_checkpoints :1611-1641``);
- cross-run import copies an old run's checkpoints into a new run_uuid
  (``copy_old_checkpoints_to_new_run :1478-1608``).
"""

from __future__ import annotations

import json
import threading
import time
import warnings
import zlib
from typing import Any

import numpy as np

from photon_tpu import telemetry
from photon_tpu.checkpoint.serialization import (
    arrays_to_npz,
    bytes_to_state,
    npz_to_arrays,
    state_to_bytes,
)
from photon_tpu.checkpoint.store import ObjectStore
from photon_tpu.codec import ParamsMetadata
from photon_tpu.utils.profiling import CKPT_ASYNC_WRITE_S

PARAMS_FILE = "current_server_parameters.npz"
STATE_FILE = "state.bin"
# per-object CRC32s, written LAST: presence marks the round complete, the
# checksums let resume detect a bit-flipped/torn object and fall back to the
# previous valid round instead of resuming garbage
MANIFEST_FILE = "manifest.json"


class ServerCheckpointManager:
    def __init__(self, store: ObjectStore, run_uuid: str) -> None:
        self.store = store
        self.run_uuid = run_uuid
        # async round writer (PR 2): at most ONE background write in flight;
        # save/resume/load barrier on it so readers never race a writer
        self._pending: threading.Thread | None = None
        self._pending_error: BaseException | None = None
        self._last_async_write_s = 0.0
        self._last_barrier_wait_s = 0.0
        # per-round checksum-verification memo (own run only): a completed
        # round's bytes never legitimately change, so each round is read
        # back and CRC'd at most once per manager lifetime — this keeps the
        # GC's corruption-awareness (cleanup must not count a corrupt round
        # toward `keep`) from re-reading every kept round every round
        self._verify_cache: dict[int, bool] = {}

    # -- async writer ----------------------------------------------------
    @property
    def last_async_write_s(self) -> float:
        """Duration of the most recently COMPLETED background write (0.0
        until one completes — round N's metrics see round N-1's write)."""
        return self._last_async_write_s

    @property
    def last_barrier_wait_s(self) -> float:
        """How long the latest :meth:`save_round_async` blocked on the
        PREVIOUS round's write (0.0 when the store is faster than a round;
        grows exactly when async checkpointing stops hiding the write)."""
        return self._last_barrier_wait_s

    def wait_pending(self) -> None:
        """Barrier: join any in-flight background write; re-raise its error
        (a silently dropped checkpoint failure would surface only at a
        much later resume)."""
        th = self._pending
        if th is not None:
            th.join()
            self._pending = None
        err, self._pending_error = self._pending_error, None
        if err is not None:
            raise RuntimeError("async checkpoint write failed") from err

    def save_round_async(
        self,
        server_round: int,
        metadata: ParamsMetadata,
        parameters: list[np.ndarray],
        strategy_state: dict[str, list[np.ndarray]] | None = None,
        server_state: dict[str, Any] | None = None,
        cleanup_keep: tuple[int, tuple[str, ...]] | None = None,
    ) -> float:
        """Snapshot + enqueue a :meth:`save_round` on a background writer;
        returns the (cheap) snapshot/enqueue seconds.

        The barrier with any previous in-flight write runs FIRST, so writes
        stay ordered and at most one round's write is ever outstanding. The
        snapshot is shallow — list/dict containers are copied, array objects
        are not: the strategies rebind list slots with fresh arrays each
        round and never mutate an ndarray in place, so the captured arrays
        are immutable from the writer's point of view. ``cleanup_keep``
        (``(keep, state_keys)``) runs the GC on the writer thread after the
        round lands."""
        t_barrier = time.monotonic()
        self.wait_pending()
        self._last_barrier_wait_s = time.monotonic() - t_barrier
        params = list(parameters)
        state = {k: list(v) for k, v in (strategy_state or {}).items()}
        server = dict(server_state or {})
        # the writer thread has no span context of its own: capture the
        # enqueuing round's context NOW so the background write renders as a
        # child of the round that requested it (telemetry plane)
        trace_ctx = telemetry.current_context()
        t_enqueue = time.monotonic()

        def _write() -> None:
            # the span's own timer is the ckpt_async_write_s KPI: the window
            # is the same, so it is measured once
            span = telemetry.span(CKPT_ASYNC_WRITE_S, parent=trace_ctx,
                                  round=server_round)
            try:
                with span as sp:
                    self.save_round(server_round, metadata, params, state, server)
                    if cleanup_keep is not None:
                        keep, keys = cleanup_keep
                        self.cleanup(keep, keys)
            except BaseException as e:  # noqa: BLE001 — re-raised at the barrier
                self._pending_error = e
            self._last_async_write_s = sp.seconds

        th = threading.Thread(
            target=_write, name=f"ckpt-write-r{server_round}", daemon=True
        )
        self._pending = th
        th.start()
        return time.monotonic() - t_enqueue

    # -- keys ------------------------------------------------------------
    def _round_prefix(self, server_round: int, run_uuid: str | None = None) -> str:
        return f"{run_uuid or self.run_uuid}/server/{server_round}"

    # -- save ------------------------------------------------------------
    def save_round(
        self,
        server_round: int,
        metadata: ParamsMetadata,
        parameters: list[np.ndarray],
        strategy_state: dict[str, list[np.ndarray]] | None = None,
        server_state: dict[str, Any] | None = None,
    ) -> None:
        prefix = self._round_prefix(server_round)
        # a resumed run rewrites rounds above the resume point: any memoized
        # verdict for the old bytes is stale now
        self._verify_cache.pop(server_round, None)
        manifest: dict[str, int] = {}

        def _put(name: str, data: bytes) -> None:
            self.store.put(f"{prefix}/{name}", data)
            manifest[name] = zlib.crc32(data)

        # manifest.json last: its presence marks the round complete only
        # after params/momenta/state landed (writes are atomic per object),
        # and its checksums are what resume verifies
        _put(PARAMS_FILE, arrays_to_npz(metadata, parameters))
        for key, tensors in (strategy_state or {}).items():
            # per-layer state aligns 1:1 with the (already canonically sorted)
            # param names; odd-length state (e.g. FedAdam's step counter) gets
            # zero-padded index names so npz's alphabetical order == list order
            names = (
                metadata.names
                if len(tensors) == len(metadata.names)
                else [f"{i:06d}" for i in range(len(tensors))]
            )
            meta = ParamsMetadata.from_ndarrays(names, tensors)
            _put(f"{key}.npz", arrays_to_npz(meta, tensors))
        _put(STATE_FILE, state_to_bytes(server_state or {}))
        self.store.put(
            f"{prefix}/{MANIFEST_FILE}",
            json.dumps({"version": 1, "crc32": manifest}).encode(),
        )

    # -- discovery -------------------------------------------------------
    def list_rounds(self, run_uuid: str | None = None) -> list[int]:
        prefix = f"{run_uuid or self.run_uuid}/server"
        rounds: set[int] = set()
        for key in self.store.list(prefix):
            parts = key.split("/")
            if len(parts) >= 3 and parts[-3] == "server":
                try:
                    rounds.add(int(parts[-2]))
                except ValueError:
                    continue
        return sorted(rounds)

    def is_valid_round(
        self,
        server_round: int,
        state_keys: tuple[str, ...] = (),
        run_uuid: str | None = None,
        verify_checksums: bool = False,
    ) -> bool:
        """Presence check (cheap: GC and discovery run it every round);
        ``verify_checksums=True`` additionally CRCs every object against the
        round manifest — the resume path pays that read cost so it never
        resumes a bit-flipped/torn checkpoint."""
        prefix = self._round_prefix(server_round, run_uuid)
        needed = [f"{prefix}/{PARAMS_FILE}", f"{prefix}/{STATE_FILE}"]
        needed += [f"{prefix}/{k}.npz" for k in state_keys]
        if not all(self.store.exists(k) for k in needed):
            return False
        if not verify_checksums:
            return True
        return self.verify_round(server_round, state_keys, run_uuid)

    def verify_round(
        self, server_round: int, state_keys: tuple[str, ...] = (), run_uuid: str | None = None
    ) -> bool:
        """CRC32-check every object listed in the round's manifest. Rounds
        written before the manifest existed verify vacuously (presence was
        their only contract). Results for THIS run are memoized — completed
        rounds are immutable, and a cached False stays False."""
        del state_keys  # the manifest lists exactly what the round wrote
        own = run_uuid is None or run_uuid == self.run_uuid
        if own and server_round in self._verify_cache:
            return self._verify_cache[server_round]
        prefix = self._round_prefix(server_round, run_uuid)
        mkey = f"{prefix}/{MANIFEST_FILE}"
        ok = True
        if self.store.exists(mkey):  # pre-manifest checkpoints verify vacuously
            try:
                manifest = json.loads(self.store.get(mkey).decode())
                for name, crc in manifest.get("crc32", {}).items():
                    if zlib.crc32(self.store.get(f"{prefix}/{name}")) != int(crc):
                        ok = False
                        break
            except (OSError, ValueError, KeyError):
                ok = False  # unreadable/torn manifest = invalid round
        if own:
            self._verify_cache[server_round] = ok
        return ok

    def valid_rounds(self, state_keys: tuple[str, ...] = ()) -> list[int]:
        return [r for r in self.list_rounds() if self.is_valid_round(r, state_keys)]

    def latest_complete_round(self, run_uuid: str | None = None) -> int | None:
        """Newest round whose MANIFEST object is present, or None.

        The cheap poll for the serving hot-swap watcher (ISSUE 11): the
        manifest is written LAST and object writes are atomic, so its
        presence alone marks the round's objects all landed — a torn or
        in-flight round (params up, manifest not yet) is never reported.
        Pure presence scan: no object reads, no checksum work — the
        watcher pays :meth:`verify_round`'s read-back only once per NEW
        candidate, not per poll. (Pre-manifest legacy rounds are invisible
        here by design; a tracking watcher wants completed rounds of a
        LIVE run, which always writes manifests.)"""
        for r in reversed(self.list_rounds(run_uuid)):
            key = f"{self._round_prefix(r, run_uuid)}/{MANIFEST_FILE}"
            if self.store.exists(key):
                return r
        return None

    def resolve_resume_round(self, resume_round: int, state_keys: tuple[str, ...] = ()) -> int:
        """Non-negative → that round (validated, incl. checksums). Negative →
        index from the latest valid round: −1 = latest, −2 = one before, ...
        (reference: ``s3_utils.py:1261-1318``). A round whose objects fail
        the manifest checksums is SKIPPED (with a warning) and the index
        falls back to the previous checksum-valid round — resuming garbage
        is strictly worse than resuming older."""
        self.wait_pending()  # resume must see every completed async write
        valid = self.valid_rounds(state_keys)
        if not valid:
            raise FileNotFoundError(f"no valid checkpoints for run {self.run_uuid!r}")
        if resume_round >= 0:
            if resume_round not in valid:
                raise FileNotFoundError(
                    f"round {resume_round} is not a valid checkpoint (valid: {valid})"
                )
            if not self.verify_round(resume_round, state_keys):
                raise FileNotFoundError(
                    f"round {resume_round} checkpoint failed checksum verification "
                    "(corrupt object); pick another round or a negative index"
                )
            return resume_round
        want = -resume_round
        seen_ok = 0
        for r in reversed(valid):
            if not self.verify_round(r, state_keys):
                warnings.warn(
                    f"round {r} checkpoint failed checksum verification — "
                    "skipping it for resume",
                    stacklevel=2,
                )
                # health plane (ISSUE 10): a corrupt round the resume path
                # survived is still a storage incident /statusz must show
                from photon_tpu import telemetry

                health = telemetry.health_active()
                if health is not None:
                    health.note_store_corruption(
                        round=r, run_uuid=self.run_uuid, stage="resume",
                    )
                continue
            seen_ok += 1
            if seen_ok == want:
                return r
        raise FileNotFoundError(
            f"resume_round {resume_round} but only {seen_ok} checksum-valid rounds"
        )

    # -- load ------------------------------------------------------------
    def load_round(
        self, server_round: int, state_keys: tuple[str, ...] = ()
    ) -> tuple[ParamsMetadata, list[np.ndarray], dict[str, list[np.ndarray]], dict[str, Any]]:
        self.wait_pending()  # never read a round a writer may still be landing
        prefix = self._round_prefix(server_round)
        metadata, parameters = npz_to_arrays(self.store.get(f"{prefix}/{PARAMS_FILE}"))
        strategy_state: dict[str, list[np.ndarray]] = {}
        for key in state_keys:
            _, tensors = npz_to_arrays(self.store.get(f"{prefix}/{key}.npz"))
            strategy_state[key] = tensors
        server_state = bytes_to_state(self.store.get(f"{prefix}/{STATE_FILE}"))
        return metadata, parameters, strategy_state, server_state

    def load_round_params(
        self, server_round: int
    ) -> tuple[ParamsMetadata, list[np.ndarray]]:
        """Params-only load for serving/eval consumers (ISSUE 5 satellite):
        reads ONLY ``current_server_parameters.npz`` — no strategy momenta,
        no pickled control state — so an inference engine never materializes
        the dead Adam moments a full :meth:`load_round` would (2x the param
        bytes for FedAdam/FedYogi runs)."""
        self.wait_pending()  # never read a round a writer may still be landing
        prefix = self._round_prefix(server_round)
        return npz_to_arrays(self.store.get(f"{prefix}/{PARAMS_FILE}"))

    def load_state_npz(
        self, server_round: int, key: str
    ) -> tuple[ParamsMetadata, list[np.ndarray]]:
        """Read ONE ``{key}.npz`` state object from a round — the
        adapter-bank load path (ISSUE 13): serving consumers fetch the
        per-cohort adapter objects without touching the pickled control
        state or any optimizer moments."""
        self.wait_pending()  # never read a round a writer may still be landing
        prefix = self._round_prefix(server_round)
        return npz_to_arrays(self.store.get(f"{prefix}/{key}.npz"))

    # -- GC / import -----------------------------------------------------
    def cleanup(self, keep: int, state_keys: tuple[str, ...] = ()) -> list[int]:
        """Delete all but the newest ``keep`` valid rounds; invalid (partial)
        rounds older than the newest valid one are removed too. Returns the
        deleted round numbers.

        ``keep`` counts CHECKSUM-valid rounds (memoized — one read-back per
        round per manager lifetime): a bit-flipped newest round must not
        push the good rounds the resume fallback needs out of the window.
        Corrupt/partial rounds newer than the newest good one are kept as
        forensics; older ones are garbage."""
        valid = [
            r for r in self.valid_rounds(state_keys) if self.verify_round(r, state_keys)
        ]
        keep_set = set(valid[-keep:]) if keep > 0 else set(valid)
        deleted = []
        for r in self.list_rounds():
            if r not in keep_set and (r in valid or (valid and r < valid[-1])):
                self.store.delete(self._round_prefix(r))
                self._verify_cache.pop(r, None)
                deleted.append(r)
        return deleted

    def import_run(self, old_run_uuid: str, state_keys: tuple[str, ...] = ()) -> list[int]:
        """Copy every valid round of ``old_run_uuid`` into this run
        (reference: ``copy_old_checkpoints_to_new_run``)."""
        imported = []
        for r in self.list_rounds(old_run_uuid):
            if not self.is_valid_round(r, state_keys, old_run_uuid):
                continue
            src = self._round_prefix(r, old_run_uuid)
            dst = self._round_prefix(r)
            for key in self.store.list(src):
                rel = key[len(src) :].lstrip("/")
                self.store.copy(key, f"{dst}/{rel}")
            self._verify_cache.pop(r, None)  # fresh bytes under this run
            imported.append(r)
        return imported
