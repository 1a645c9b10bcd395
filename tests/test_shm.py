"""shm plane tests: codec roundtrip, commit protocol, cross-process hand-off
(reference behavioral oracle: single-writer/single-reader + spin-wait,
``photon/shm/utils.py``)."""

import errno
import gc
import multiprocessing as mp
import os
import tracemalloc
import uuid

import numpy as np
import pytest

from photon_tpu.codec import ParamsMetadata
from photon_tpu.shm import (
    read_blob,
    read_params,
    read_scalar,
    unlink,
    wait_for,
    write_blob,
    write_params,
    write_scalar,
)
from photon_tpu.shm.plane import (
    _HEADER,
    _MAGIC,
    _VERSION,
    _array_offsets,
    _path,
    cleanup_stale,
    sweep_stale_tmp,
)
from tests._helpers import is_readonly_view, shm_mappings


@pytest.fixture
def name():
    n = f"test-{uuid.uuid4().hex[:8]}"
    yield n
    unlink(n)


def _arrays():
    rng = np.random.default_rng(0)
    return [
        rng.normal(size=(4, 8)).astype(np.float32),
        rng.integers(0, 100, (3,)).astype(np.int64),
        rng.normal(size=(2, 2, 2)).astype(np.float32),
    ]


def test_params_roundtrip(name):
    arrays = _arrays()
    meta = ParamsMetadata.from_ndarrays(["a", "b", "c"], arrays)
    write_params(name, meta, arrays)
    meta2, arrays2 = read_params(name)
    assert meta2 == meta
    for a, b in zip(arrays, arrays2):
        np.testing.assert_array_equal(a, b)


def test_zero_copy_views_stable_across_rewrite(name):
    """Rewrites swap the file atomically (rename): existing zero-copy views
    keep the OLD snapshot; fresh reads see the new one."""
    arrays = _arrays()
    meta = ParamsMetadata.from_ndarrays(["a", "b", "c"], arrays)
    write_params(name, meta, arrays)
    _, views = read_params(name)
    mutated = [a * 2 for a in arrays]
    write_params(name, meta, mutated)
    np.testing.assert_array_equal(views[0], arrays[0])  # old mapping intact
    _, fresh = read_params(name)
    np.testing.assert_array_equal(fresh[0], mutated[0])


def test_read_before_commit_raises(name):
    # what the writer's staging file holds before its last write
    _path(name).write_bytes(_HEADER.pack(_MAGIC, _VERSION, 0, 0) + bytes(64))
    with pytest.raises(BlockingIOError):
        read_params(name)


def test_wait_for_timeout():
    with pytest.raises(TimeoutError):
        wait_for(f"never-{uuid.uuid4().hex[:6]}", timeout=0.2, poll=0.05)


def test_blob_and_scalar(name):
    write_blob(name, {"cid": 3, "cfg": [1, 2, 3]})
    assert read_blob(name) == {"cid": 3, "cfg": [1, 2, 3]}
    write_scalar(name, 42.5)
    assert read_scalar(name) == 42.5


def _child(name: str, q) -> None:
    wait_for(name, timeout=20)
    meta, arrays = read_params(name)
    q.put((meta.names, [float(a.sum()) for a in arrays]))


def test_cross_process_handoff(name):
    """Writer parent, spin-waiting reader child (the NodeManager↔Worker
    pattern, ``node_manager_app.py:516-539``)."""
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    child = ctx.Process(target=_child, args=(name, q))
    child.start()
    arrays = _arrays()
    meta = ParamsMetadata.from_ndarrays(["a", "b", "c"], arrays)
    write_params(name, meta, arrays)
    names, sums = q.get(timeout=30)
    child.join(timeout=10)
    assert names == ("a", "b", "c")
    np.testing.assert_allclose(sums, [float(a.sum()) for a in arrays], rtol=1e-6)


def test_cleanup_stale():
    n = f"stale-{uuid.uuid4().hex[:8]}"
    write_blob(n, 1)
    assert cleanup_stale("stale-") >= 1
    from photon_tpu.shm.plane import _path

    assert not _path(n).exists()


@pytest.mark.chaos
def test_sweep_stale_tmp_reaps_dead_writers_only():
    """A node SIGKILLed mid-write leaks a pid-suffixed temp segment; the
    transport-startup sweep reaps it iff the writer pid is dead — a live
    writer's in-flight temp file must survive."""
    import subprocess

    from photon_tpu.shm.plane import SHM_DIR

    proc = subprocess.Popen(["sleep", "0"])
    proc.wait()  # reaped: the pid is guaranteed dead (not just a zombie)
    tag = uuid.uuid4().hex[:8]
    orphan = SHM_DIR / f"photon-{tag}-params.tmp-{proc.pid}"
    own = SHM_DIR / f"photon-{tag}-own.tmp-{os.getpid()}"
    orphan.write_bytes(b"torn")
    own.write_bytes(b"inflight")
    try:
        assert sweep_stale_tmp() >= 1
        assert not orphan.exists()
        assert own.exists()  # our own pid is alive: left alone
    finally:
        orphan.unlink(missing_ok=True)
        own.unlink(missing_ok=True)


@pytest.mark.chaos
def test_transport_startup_sweeps_orphans():
    import subprocess

    from photon_tpu.federation.transport import ParamTransport
    from photon_tpu.shm.plane import SHM_DIR

    proc = subprocess.Popen(["sleep", "0"])
    proc.wait()
    orphan = SHM_DIR / f"photon-{uuid.uuid4().hex[:8]}.tmp-{proc.pid}"
    orphan.write_bytes(b"torn")
    try:
        t = ParamTransport("shm")
        t.cleanup()
        assert not orphan.exists()
    finally:
        orphan.unlink(missing_ok=True)


# ---------------------------------------------------------------------------
# the writer: ``os.pwrite`` into a staging file, the committed header last,
# then a rename; it maps nothing (ISSUE 47)
# ---------------------------------------------------------------------------


def _payload(kind: str) -> list[np.ndarray]:
    import ml_dtypes

    rng = np.random.default_rng(1)
    if kind == "float32":
        return _arrays()[::2] + [rng.normal(size=(7,)).astype(np.float32)]
    if kind == "mixed_with_gaps":  # 3 bytes, a gap of 1, 10 bytes, a gap of 2, then float64
        return [rng.integers(-9, 9, (3,)).astype(np.int8),
                rng.normal(size=(5,)).astype(ml_dtypes.bfloat16), rng.normal(size=(4,))]
    if kind == "zero_size_last":  # the float64's offset is past the last byte written
        return [np.arange(3, dtype=np.int8), np.zeros((0, 4), np.float64)]
    if kind == "zero_d":
        return [np.float32(2.5).reshape(()), np.asarray(7, dtype=np.int64)]
    if kind == "non_contiguous":
        a = rng.normal(size=(6, 10)).astype(np.float32)
        return [a.T, a[::2, 1::3], np.arange(12, dtype=np.int64)[::-1]]
    if kind == "above_64MiB":
        return [np.arange(20_000_000, dtype=np.float32), np.ones(3, np.float64)]  # 80 MB
    raise KeyError(kind)


@pytest.mark.parametrize(
    "kind",
    ["float32", "mixed_with_gaps", "zero_size_last", "zero_d", "non_contiguous", "above_64MiB"],
)
def test_writer_lays_out_every_kind_of_payload(name, kind):
    arrays = _payload(kind)
    meta = ParamsMetadata.from_ndarrays([f"p{i}" for i in range(len(arrays))], arrays)
    write_params(name, meta, arrays)
    with open(_path(name), "rb") as f:
        meta_len = _HEADER.unpack(f.read(_HEADER.size))[2]
    assert (_HEADER.size + meta_len) % 64 == 0
    # the whole extent, even where the last array is empty or a gap ends it
    assert _path(name).stat().st_size == _HEADER.size + meta_len + _array_offsets(meta)[1]
    meta2, views = read_params(name)
    assert meta2 == meta
    assert views[0].ctypes.data % 64 == 0
    for a, v in zip(arrays, views):
        assert v.flags.aligned, (v.dtype, v.ctypes.data)
        assert v.shape == a.shape and v.dtype == a.dtype
        assert v.tobytes() == a.tobytes()  # bit for bit, bfloat16 included


@pytest.mark.parametrize("most", [7, 1 << 20])
def test_short_writes_still_round_trip(name, most, monkeypatch):
    """Linux caps one ``write`` near 2 GiB; here every call writes short."""
    real, calls = os.pwrite, []

    def short_pwrite(fd, data, offset):
        calls.append(len(data))
        return real(fd, memoryview(data)[:most], offset)

    n = 3 * most // 4 + 5  # float32s: 3 x ``most`` and a tail
    arrays = [np.arange(n, dtype=np.float32), np.arange(5, dtype=np.int8),
              np.arange(n, dtype=np.float64)[::-1]]
    meta = ParamsMetadata.from_ndarrays(["a", "b", "c"], arrays)
    with monkeypatch.context() as m:
        m.setattr(os, "pwrite", short_pwrite)
        write_params(name, meta, arrays)
    assert max(calls) > most  # some call did ask for more than it got
    meta2, views = read_params(name)
    assert meta2 == meta
    for a, v in zip(arrays, views):
        np.testing.assert_array_equal(a, v)


def _staging_files(name: str) -> list:
    return list(_path(name).parent.glob(f"photon-{name}.tmp-*"))


def test_full_tmpfs_raises_and_leaves_the_committed_segment(name, monkeypatch):
    """A full ``/dev/shm`` is ``ENOSPC`` from ``write()`` (through a mapping
    it was a ``SIGBUS``): no staging file stays, the old segment still reads."""
    old = _arrays()
    meta = ParamsMetadata.from_ndarrays(["a", "b", "c"], old)
    write_params(name, meta, old)
    real, calls = os.pwrite, []

    def full_pwrite(fd, data, offset):
        calls.append(offset)
        if len(calls) == 3:  # header + metadata, the first array, then no room
            assert _staging_files(name)
            raise OSError(errno.ENOSPC, os.strerror(errno.ENOSPC))
        return real(fd, data, offset)

    with monkeypatch.context() as m, pytest.raises(OSError) as e:
        m.setattr(os, "pwrite", full_pwrite)
        write_params(name, meta, [a * 2 for a in old])
    assert e.value.errno == errno.ENOSPC
    assert not _staging_files(name)
    _, views = read_params(name)
    for a, v in zip(old, views):
        np.testing.assert_array_equal(a, v)


def test_staging_file_reads_uncommitted_until_the_last_write(name, monkeypatch):
    real, seen = os.pwrite, []

    def watching_pwrite(fd, data, offset):
        (staging,) = _staging_files(name)
        with open(staging, "rb") as f:
            seen.append(_HEADER.unpack(f.read(_HEADER.size)))
        return real(fd, data, offset)

    arrays = _arrays()
    with monkeypatch.context() as m:
        m.setattr(os, "pwrite", watching_pwrite)
        write_params(name, ParamsMetadata.from_ndarrays(["a", "b", "c"], arrays), arrays)
    # before the header + metadata nothing is there; before each of the three
    # arrays and before the committed header a reader sees ``committed == 0``
    assert seen == [(0, 0, 0, 0)] + 4 * [(_MAGIC, _VERSION, 0, 0)]
    assert not _staging_files(name)
    read_params(name)  # and the renamed file is committed


def test_writer_maps_nothing(name, monkeypatch):
    """What a CPU cannot time: no page of a new segment is faulted through a
    mapping, because the write side has none."""
    import mmap

    def no_mapping(*a, **k):
        raise AssertionError("the writer mapped the segment")

    arrays = _arrays()
    meta = ParamsMetadata.from_ndarrays(["a", "b", "c"], arrays)
    with monkeypatch.context() as m:
        m.setattr(mmap, "mmap", no_mapping)
        write_params(name, meta, arrays)
        write_blob(name + "-blob", {"k": 1})
    try:
        assert read_blob(name + "-blob") == {"k": 1}
    finally:
        unlink(name + "-blob")
    _, views = read_params(name)
    for a, v in zip(arrays, views):
        assert is_readonly_view(v)
        np.testing.assert_array_equal(a, v)


# ---------------------------------------------------------------------------
# readers get views of the mapping: read-only, aligned, with a life of their
# own (ISSUE 32)
# ---------------------------------------------------------------------------


def _mapped(name: str) -> int:
    return len(shm_mappings(name))


def test_views_are_read_only(name):
    arrays = _arrays()
    write_params(name, ParamsMetadata.from_ndarrays(["a", "b", "c"], arrays), arrays)
    _, views = read_params(name)
    for v in views:
        assert is_readonly_view(v)
        with pytest.raises(ValueError):
            v[...] = 0
        with pytest.raises(ValueError):
            v.setflags(write=True)  # the mapping itself is read-only
    _, again = read_params(name)  # another reader of the same segment
    for a, b in zip(arrays, again):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("json_len", range(100, 171))
def test_payload_and_views_are_aligned_whatever_the_metadata_length(name, json_len):
    """The payload used to start at ``16 + len(JSON)``: aligned one length
    in four. Lengths 100-170 cover every residue of 64 (and of 4)."""
    # 20 bytes, then 3: the float64 that follows needs a byte of padding
    arrays = [np.arange(5, dtype=np.float32), np.arange(3, dtype=np.int8),
              np.arange(4, dtype=np.float64)]
    bare = len(ParamsMetadata.from_ndarrays(["", "b", "c"], arrays).to_json())
    names = ["n" * (json_len - bare), "b", "c"]
    meta = ParamsMetadata.from_ndarrays(names, arrays)
    assert len(meta.to_json()) == json_len
    write_params(name, meta, arrays)
    raw = _path(name).read_bytes()
    meta_len = _HEADER.unpack_from(raw)[2]
    assert (_HEADER.size + meta_len) % 64 == 0
    assert raw[_HEADER.size : _HEADER.size + meta_len].rstrip(b" ") == meta.to_json().encode()
    meta2, views = read_params(name)
    assert meta2 == meta
    assert views[0].ctypes.data % 64 == 0  # the mapping is page-aligned
    for a, v in zip(arrays, views):
        assert v.flags.aligned, (v.dtype, v.ctypes.data)
        np.testing.assert_array_equal(a, v)


def test_old_layout_segment_still_reads(name):
    """A segment whose JSON is not padded (what the writer produced before
    the payload was aligned) reads to the same values; its views are as
    aligned as its offset lets them be."""
    arrays = [np.arange(6, dtype=np.float32).reshape(2, 3), np.arange(4, dtype=np.float32)]
    meta = ParamsMetadata.from_ndarrays(["aa", "b"], arrays)
    meta_bytes = meta.to_json().encode()
    assert (_HEADER.size + len(meta_bytes)) % 4 != 0  # the unaligned three in four
    _path(name).write_bytes(
        _HEADER.pack(_MAGIC, _VERSION, len(meta_bytes), 1) + meta_bytes
        + b"".join(a.tobytes() for a in arrays)
    )
    meta2, views = read_params(name)
    assert meta2 == meta
    assert not views[0].flags.aligned and not views[0].flags.writeable
    for a, v in zip(arrays, views):
        np.testing.assert_array_equal(a, v)


def test_mapping_lives_exactly_as_long_as_its_views(name):
    arrays = _arrays()
    meta = ParamsMetadata.from_ndarrays(["a", "b", "c"], arrays)
    write_params(name, meta, arrays)
    assert _mapped(name) == 0  # the writer's mapping is closed
    _, views = read_params(name)
    assert _mapped(name) == 1
    unlink(name)  # the name goes, the pages stay while mapped
    assert not _path(name).exists()
    np.testing.assert_array_equal(views[2], arrays[2])
    last = views[1]
    del views
    gc.collect()
    assert _mapped(name) == 1  # one view is enough to hold it
    np.testing.assert_array_equal(last, arrays[1])
    del last
    gc.collect()
    assert _mapped(name) == 0


def test_transport_views_survive_free_and_a_reput_of_the_tag(name):
    from photon_tpu.federation.transport import ParamTransport

    arrays = _arrays()
    meta = ParamsMetadata.from_ndarrays(["a", "b", "c"], arrays)
    tr = ParamTransport("shm")
    try:
        ptr = tr.put(name, meta, arrays)
        _, views = tr.get(ptr)
        assert all(map(is_readonly_view, views))
        tr.free(ptr)
        for a, v in zip(arrays, views):
            np.testing.assert_array_equal(a, v)
        ptr2 = tr.put(name, meta, [a * 3 for a in arrays])  # same tag, new file
        _, fresh = tr.get(ptr2)
        for a, v, f in zip(arrays, views, fresh):
            np.testing.assert_array_equal(a, v)  # the old contents
            np.testing.assert_array_equal(a * 3, f)
        assert _mapped(name) == 2
        del views, fresh, v, f
        gc.collect()
        assert _mapped(name) == 0
    finally:
        tr.cleanup()


def test_transport_get_allocates_nothing_model_sized(name):
    """64 MB through ``get`` on the shm plane: two mmaps and a JSON parse.
    (The parent's ``copy=True`` default allocated the payload again.)"""
    from photon_tpu.federation.transport import ParamTransport

    big = [np.ones(8 << 20, np.float32), np.ones(8 << 20, np.float32)]  # 2 x 32 MB
    meta = ParamsMetadata.from_ndarrays(["w0", "w1"], big)
    tr = ParamTransport("shm")
    try:
        ptr = tr.put(name, meta, big)
        tracemalloc.start()
        try:
            _, views = tr.get(ptr)
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        assert peak < 1 << 20, f"get allocated {peak} bytes"
        assert float(views[1][-1]) == 1.0
    finally:
        tr.cleanup()
