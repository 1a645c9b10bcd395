"""Named shared-memory plane: zero-copy parameter hand-off on one host.

Role parity with the reference's shm codec (``photon/shm/utils.py``): model
weights travel between processes as one flat buffer + metadata, never through
the control-plane message payload (SURVEY.md "big architectural idea").

Design differences (deliberate, TPU-host-native):
- Segments are plain files in ``/dev/shm`` accessed via ``mmap`` — tmpfs
  pages, same zero-copy properties as POSIX ``shm_open``, but *no*
  ``multiprocessing.resource_tracker`` involvement, which removes the entire
  class of premature-unlink bugs the reference monkeypatches around
  (bpo-38119 workaround, ``shm/utils.py:403-429``).
- The segment is self-describing: a fixed header (magic, payload length,
  metadata length, commit flag) precedes the metadata JSON and the raw
  array bytes, so readers need only the name. The commit flag is written
  last, after the whole payload, into a private staging file that is then
  renamed over the name, making the write-then-spin-wait protocol
  race-free without locks (single-writer / multi-reader, reference
  protocol: ``worker.py:241-252`` spin-wait).
- The writer maps nothing: it ``os.pwrite``s the metadata and each array
  at its offset, one after another (the reference's ``set_parameters_shm``
  copies through a mapping on threads, ``shm/utils.py:626-651``). A store
  through a new mapping takes a page fault for each 4 KiB of a new tmpfs
  file, ~121,000 of them for mpt-125m's 0.49 GB, and where a fault is dear
  that is 1.03 s against 0.22 s for the same bytes through ``write()``;
  chunks of 8-64 MiB on 2-8 threads write no faster, 0.22-0.24 s (PERF.md
  section 6, PR 47). A full ``/dev/shm`` is an ``OSError(ENOSPC)`` in the
  writer, not a ``SIGBUS``.

Layout: ``[16B header][metadata JSON, space-padded][payload bytes]``.
Header: magic ``u32``, version ``u32``, meta_len ``u32``, committed ``u32``.
``meta_len`` counts the padding, which puts the payload on a 64-byte
boundary of the file (and so of the page-aligned mapping); inside the
payload every array starts on a multiple of its dtype's alignment. Readers
map read-only: what they hand out are views, never copies.
"""

from __future__ import annotations

import mmap
import os
import pathlib
import pickle
import struct
import time
from collections.abc import Iterable
from typing import Any

import numpy as np

from photon_tpu.codec import ParamsMetadata

SHM_DIR = pathlib.Path(os.environ.get("PHOTON_SHM_DIR", "/dev/shm"))
_MAGIC = 0x50484F54  # "PHOT"
_VERSION = 1
_HEADER = struct.Struct("<IIII")
_PAYLOAD_ALIGN = 64  # a cache line; what a zero-copy ``device_put`` asks for
_MAP_POPULATE = getattr(mmap, "MAP_POPULATE", 0)  # Linux; elsewhere pages fault in

# name suffixes (reference: ``shm/constants.py:5-12`` `{uuid}+suffix` scheme)
PARAMS_SUFFIX = "-params"
CONFIG_SUFFIX = "-config"
METRICS_SUFFIX = "-metrics"
RESULT_SUFFIX = "-result"


def _path(name: str) -> pathlib.Path:
    if "/" in name or name.startswith("."):
        raise ValueError(f"bad shm name {name!r}")
    return SHM_DIR / f"photon-{name}"


class ShmSegment:
    """A segment mapped read-only; use the module-level helpers for one-shot IO."""

    def __init__(self, name: str, populate: bool = False):
        self.name = name
        # read-only: a write through a reader's view raises instead of
        # changing the segment under its other readers. ``populate`` (for a
        # reader that will touch every page) fills the page table in the one
        # call: where a fault is dear, 0.03 s for 0.5 GB against 0.87 s of
        # faults under the first read pass, a ``device_put`` included
        # (PERF.md section 6, PR 32)
        flags = mmap.MAP_SHARED | (_MAP_POPULATE if populate else 0)
        fd = os.open(_path(name), os.O_RDONLY)
        try:
            total = os.fstat(fd).st_size
            self.mm = mmap.mmap(fd, total, flags=flags, prot=mmap.PROT_READ)
        finally:
            os.close(fd)
        magic, version, _, _ = _HEADER.unpack_from(self.mm, 0)
        if magic != _MAGIC or version != _VERSION:
            raise ValueError(f"segment {name!r} has bad header")

    # -- header ---------------------------------------------------------
    @property
    def committed(self) -> bool:
        return _HEADER.unpack_from(self.mm, 0)[3] == 1

    @property
    def meta_len(self) -> int:
        return _HEADER.unpack_from(self.mm, 0)[2]

    def payload(self) -> memoryview:
        return memoryview(self.mm)[_HEADER.size + self.meta_len :]

    def body(self) -> memoryview:
        return memoryview(self.mm)[_HEADER.size :]

    def close(self) -> None:
        self.mm.close()


def _pwrite_all(fd: int, buf, offset: int) -> None:
    """``os.pwrite`` may write short (Linux caps one call near 2 GiB): go on
    until the whole buffer is in the file."""
    view = memoryview(buf)
    while view.nbytes:
        n = os.pwrite(fd, view, offset)
        view = view[n:]
        offset += n


def _write_segment(
    name: str, meta_bytes: bytes, parts: Iterable[tuple[int, Any]], payload_len: int
) -> None:
    """Create the committed segment ``name``: ``parts`` are (offset inside
    the payload, contiguous byte buffer).

    The bytes go into a private staging file, the header that says
    ``committed`` last, and the file is then renamed over the final name:
    readers (wait_for / read_params) only ever map a fully-committed segment,
    with no window where a stale committed=1 header fronts new bytes. A
    failure (``ENOSPC`` on a full tmpfs included) unlinks the staging file
    and leaves whatever was committed under the name as it was."""
    final = _path(name)
    tmp = final.parent / (final.name + f".tmp-{os.getpid()}")
    body = _HEADER.size + len(meta_bytes)
    try:
        fd = os.open(tmp, os.O_CREAT | os.O_TRUNC | os.O_WRONLY, 0o600)
        try:
            # the whole extent whatever the parts cover: a zero-size last
            # array or an alignment gap is a hole, which reads as zeros
            os.ftruncate(fd, body + payload_len)
            _pwrite_all(fd, _HEADER.pack(_MAGIC, _VERSION, 0, 0) + meta_bytes, 0)
            for off, raw in parts:
                _pwrite_all(fd, raw, body + off)
            _pwrite_all(fd, _HEADER.pack(_MAGIC, _VERSION, len(meta_bytes), 1), 0)
        finally:
            os.close(fd)
        os.rename(tmp, final)
    except BaseException:
        tmp.unlink(missing_ok=True)
        raise


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _array_offsets(metadata: ParamsMetadata) -> tuple[list[int], int]:
    """Where each array starts inside the payload, and the payload's length:
    arrays are packed, except that one starts on the next multiple of its
    dtype's alignment (a payload of one dtype has no gaps)."""
    offsets, off = [], 0
    for dtype, nbytes in zip(metadata.dtypes, metadata.nbytes_each):
        align = np.dtype(dtype).alignment
        off = -(-off // align) * align
        offsets.append(off)
        off += nbytes
    return offsets, off


def write_params(name: str, metadata: ParamsMetadata, arrays: list[np.ndarray]) -> None:
    """Serialize the flat array list into the named segment and commit."""
    metadata.validate_arrays(arrays)
    meta_bytes = metadata.to_json().encode()
    # trailing spaces (JSON ignores them, ``meta_len`` delimits them) put the
    # payload on a _PAYLOAD_ALIGN boundary, so readers' views are aligned
    meta_bytes += b" " * (-(_HEADER.size + len(meta_bytes)) % _PAYLOAD_ALIGN)
    offsets, payload_len = _array_offsets(metadata)
    parts = (
        (off, np.ascontiguousarray(a).reshape(-1).view(np.uint8))
        for a, off in zip(arrays, offsets)
    )
    _write_segment(name, meta_bytes, parts, payload_len)


def read_params(name: str) -> tuple[ParamsMetadata, list[np.ndarray]]:
    """Map the segment and return (metadata, arrays).

    The arrays are read-only views of the mapping, and the mapping lives as
    long as any of them does: unlinking the segment, or renaming a new one
    over its name, removes the NAME, and the pages go when the last view
    dies (tmpfs keeps an unlinked file while it is mapped). So a reader
    never needs a private copy to outlive the writer's clean-up; whoever
    wants to write copies first."""
    seg = ShmSegment(name, populate=True)
    if not seg.committed:
        seg.close()
        raise BlockingIOError(f"segment {name!r} not committed yet")
    meta = ParamsMetadata.from_json(bytes(seg.body()[: seg.meta_len]).decode())
    payload = seg.payload()
    arrays = [
        np.frombuffer(
            payload, dtype=np.dtype(dtype), count=int(np.prod(shape, dtype=np.int64)), offset=off
        ).reshape(shape)
        for shape, dtype, off in zip(meta.shapes, meta.dtypes, _array_offsets(meta)[0])
    ]
    return meta, arrays


# ---------------------------------------------------------------------------
# pickled blobs (configs, metric dicts) + scalars
# ---------------------------------------------------------------------------


def write_blob(name: str, obj: Any) -> None:
    """Pickled object cell (reference: ``set_dict_configsrecord_shm``,
    ``shm/utils.py:432-522``)."""
    data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    _write_segment(name, b"", [(0, data)], len(data))


def read_blob(name: str) -> Any:
    seg = ShmSegment(name)
    try:
        if not seg.committed:
            raise BlockingIOError(f"segment {name!r} not committed yet")
        return pickle.loads(bytes(seg.payload()))
    finally:
        seg.close()


def write_scalar(name: str, value: float) -> None:
    """Scalar cell (reference: n_samples/eval_loss cells, ``shm/utils.py:271-369``)."""
    write_blob(name, float(value))


def read_scalar(name: str) -> float:
    return float(read_blob(name))


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------


def wait_for(name: str, timeout: float = 60.0, poll: float = 0.01) -> None:
    """Block until the segment exists and is committed (reference spin-wait:
    ``worker.py:241-252``)."""
    deadline = time.monotonic() + timeout
    path = _path(name)
    while time.monotonic() < deadline:
        if path.exists():
            try:
                seg = ShmSegment(name)
                ok = seg.committed
                seg.close()
                if ok:
                    return
            except (ValueError, OSError):
                pass
        time.sleep(poll)
    raise TimeoutError(f"shm segment {name!r} not ready after {timeout}s")


def unlink(name: str, missing_ok: bool = True) -> None:
    try:
        _path(name).unlink()
    except FileNotFoundError:
        if not missing_ok:
            raise


def sweep_stale_tmp() -> int:
    """Unlink ``photon-*.tmp-<pid>`` temp segments whose writer pid is dead.

    :func:`write_params`/:func:`write_blob` stage into a pid-suffixed temp
    file and rename on commit; a node SIGKILLed mid-write leaks the temp
    segment in ``/dev/shm`` forever (tmpfs pages pinned until reboot).
    Called at :class:`ParamTransport` startup — by then the leaking pid is
    either alive (leave its in-flight write alone) or gone (reap it).
    """
    n = 0
    for p in SHM_DIR.glob("photon-*.tmp-*"):
        pid_s = p.name.rpartition(".tmp-")[2]
        if not pid_s.isdigit():
            continue
        pid = int(pid_s)
        if pid == os.getpid():
            continue  # our own in-flight write
        try:
            os.kill(pid, 0)
            continue  # writer still alive: the rename may yet land
        except ProcessLookupError:
            pass  # dead writer: orphaned segment
        except PermissionError:
            continue  # pid exists under another uid
        try:
            p.unlink()
            n += 1
        except OSError:
            pass
    return n


def cleanup_stale(prefix: str = "") -> int:
    """Remove leftover segments (reference: ``clean_stale_shared_memory`` /
    streaming-shm leak cleanup, ``clients/utils.py:655-673``)."""
    n = 0
    for p in SHM_DIR.glob(f"photon-{prefix}*"):
        try:
            p.unlink()
            n += 1
        except OSError:
            pass
    return n
