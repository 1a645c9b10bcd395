"""Bulk-tensor transport plane: resolve :class:`ParamPointer`s.

Reference three-way (``photon/server/s3_utils.py:730-1115``): shm (single
host, zero-copy), S3 (durable, cross-host), Ray object store (cross-process).
Here:

- ``shm``      — named tmpfs segments (``photon_tpu/shm``), single host;
- ``objstore`` — the checkpoint object store (file/NFS/mounted bucket);
- ``inline``   — tensors inside the message (tests, tiny models only).

A fourth, TPU-native path — aggregation as a cross-slice collective over
DCN — lives in ``photon_tpu/parallel/collective_agg.py`` and bypasses
pointers entirely (SURVEY.md §7 stage 6 "marquee feature").

Wire compression (``photon_tpu/compression``): with a ``compression=``
policy, :meth:`put` can encode a payload through the delta/top-k/int8 codec
pipeline. The compressed bytes ride the SAME planes as a single uint8 blob;
the pointer's ``metadata_json`` keeps the original (names, shapes, dtypes)
contract and grows a back-compatible ``codec`` field describing the wire
form. Bytes-on-wire accounting (raw vs. actual, both directions) accumulates
in :attr:`stats` for the round metrics.
"""

from __future__ import annotations

import json

import numpy as np

from photon_tpu import telemetry
from photon_tpu.checkpoint.store import ObjectStore
from photon_tpu.checkpoint.serialization import arrays_to_npz, npz_to_arrays
from photon_tpu.codec import ParamsMetadata
from photon_tpu.compression import CompressedPayload, make_codec
from photon_tpu.federation.messages import ParamPointer
from photon_tpu.shm import plane as shm
from photon_tpu.utils.hostpool import HostPool
from photon_tpu.utils.profiling import (
    TRANSPORT_FREE_SPAN,
    TRANSPORT_GET_SPAN,
    TRANSPORT_PUT_SPAN,
    WireStats,
)

#: reserved layer name carrying a serialized CompressedPayload through the
#: planes (never collides with model paths, which are "/"-joined pytree keys)
_BLOB_NAME = "__pcmp_blob__"


def _blob_metadata(nbytes: int) -> ParamsMetadata:
    return ParamsMetadata(
        names=(_BLOB_NAME,), shapes=((nbytes,),), dtypes=("uint8",)
    )


class ParamTransport:
    """Writer/reader of parameter payloads behind pointers.

    ``mode`` selects the plane (reference: ``photon.comm_stack{s3,shm,ray}``
    config, ``base_schema.py:11-28``); ``compression`` a wire-codec policy
    (a :class:`~photon_tpu.config.schema.CompressionConfig`, a policy
    string, or an existing :class:`~photon_tpu.compression.Codec`).
    """

    def __init__(
        self,
        mode: str = "shm",
        store: ObjectStore | None = None,
        compression=None,
        host_threads: int = 1,
    ) -> None:
        if mode not in ("shm", "objstore", "inline"):
            raise ValueError(f"unknown transport mode {mode!r}")
        if mode == "objstore" and store is None:
            raise ValueError("objstore transport needs a store")
        self.mode = mode
        self.store = store
        if mode == "shm":
            # reap temp segments a SIGKILLed writer left behind — a
            # crash-and-rejoin node must not ratchet /dev/shm toward ENOSPC
            shm.sweep_stale_tmp()
        self.codec = make_codec(compression)
        self.stats = WireStats()
        # shared bounded pool for the codec's per-layer encode/decode
        # (``photon.host_threads``; 1 = inline/serial, 0 = auto). ServerApp
        # replaces this with ITS pool so aggregation fold, decode-ahead and
        # codec work all draw from one bounded worker set.
        self.host_pool = HostPool(host_threads)
        self._owned: list[str] = []  # shm segments we created (for cleanup)

    # -- compression -----------------------------------------------------
    def set_reference(self, arrays: list[np.ndarray] | None) -> None:
        """Pin the round's global params as the codec's delta base (no-op
        without a codec)."""
        if self.codec is not None:
            self.codec.set_reference(arrays)

    # -- write -----------------------------------------------------------
    def put(
        self,
        tag: str,
        metadata: ParamsMetadata,
        arrays: list[np.ndarray],
        compress: bool = False,
        key=None,
    ) -> ParamPointer:
        """Write a payload and return its pointer.

        ``compress=True`` routes through the codec (when one is configured;
        silently raw otherwise so policy "off" needs no call-site changes);
        ``key`` names the error-feedback residual stream — the client id.
        """
        if compress and self.codec is not None:
            payload = self.codec.encode(metadata, arrays, key=key,
                                        pool=self.host_pool)
            blob = np.frombuffer(payload.to_bytes(), dtype=np.uint8)
            self.stats.record_sent(metadata.total_bytes, blob.nbytes)
            meta_d = json.loads(metadata.to_json())
            meta_d["codec"] = {
                "policy": payload.policy,
                "version": payload.version,
                "wire_nbytes": int(blob.nbytes),
            }
            ptr = self._put_raw(tag, _blob_metadata(blob.nbytes), [blob],
                                metadata.total_bytes)
            return ParamPointer(ptr.kind, ptr.locator, json.dumps(meta_d),
                                inline=ptr.inline)
        self.stats.record_sent(metadata.total_bytes, metadata.total_bytes)
        return self._put_raw(tag, metadata, arrays, metadata.total_bytes)

    def _put_raw(
        self, tag: str, metadata: ParamsMetadata, arrays: list[np.ndarray],
        nbytes: int,
    ) -> ParamPointer:
        """The plane write alone (the codec's encode, when there is one, is
        the caller's span): ``nbytes`` is the payload before the codec,
        ``wire_nbytes`` what is written."""
        with telemetry.span(TRANSPORT_PUT_SPAN, push=False, mode=self.mode,
                            nbytes=nbytes, wire_nbytes=metadata.total_bytes):
            return self._write(tag, metadata, arrays)

    def _write(
        self, tag: str, metadata: ParamsMetadata, arrays: list[np.ndarray]
    ) -> ParamPointer:
        if self.mode == "shm":
            shm.write_params(tag, metadata, arrays)
            self._owned.append(tag)
            return ParamPointer("shm", tag, metadata.to_json())
        if self.mode == "objstore":
            assert self.store is not None
            key = f"transport/{tag}.npz"
            # durable=False: transport objects are deleted at round end —
            # fsyncing a model-sized payload per client per round would put
            # a disk flush on the hot path for zero crash-safety gain
            self.store.put(key, arrays_to_npz(metadata, arrays), durable=False)
            self._owned.append(key)
            return ParamPointer("objstore", key, metadata.to_json())
        return ParamPointer("inline", "", metadata.to_json(), inline=[np.asarray(a) for a in arrays])

    # -- read ------------------------------------------------------------
    def get(
        self,
        ptr: ParamPointer,
        timeout: float = 120.0,
        decode: bool = True,
    ) -> tuple[ParamsMetadata, list[np.ndarray] | CompressedPayload]:
        """Resolve a pointer to ``(metadata, arrays)``.

        The arrays are the caller's to READ and to keep: on the shm plane
        they are read-only views of the mapped segment, which outlive
        :meth:`free` and a later :meth:`put` under the same tag (the mapping
        goes when the last of them does); on the inline plane they alias the
        sender's. Whoever needs to write copies first.

        For codec-compressed pointers, ``decode=False`` returns
        ``(metadata, CompressedPayload)`` instead — the streaming
        aggregation path dequantizes one client at a time so only the
        running average plus ONE decoded client is ever resident.
        """
        meta_d = json.loads(ptr.metadata_json)
        metadata = ParamsMetadata.from_dict(meta_d)
        codec_info = meta_d.get("codec")
        if codec_info is None:
            self.stats.record_recv(metadata.total_bytes, metadata.total_bytes)
            return self._get_raw(ptr, metadata, timeout)
        _, (blob,) = self._get_raw(
            ptr, _blob_metadata(int(codec_info["wire_nbytes"])), timeout
        )
        payload = CompressedPayload.from_bytes(bytes(blob))
        self.stats.record_recv(metadata.total_bytes, payload.wire_nbytes)
        if not decode:
            return metadata, payload
        if self.codec is None:
            raise RuntimeError(
                f"pointer {ptr.locator!r} carries a {codec_info['policy']} "
                "payload but this transport has no codec — construct it with "
                "the run's CompressionConfig"
            )
        arrays = self.codec.decode(payload, pool=self.host_pool)
        metadata.validate_arrays(arrays)
        return metadata, arrays

    def _get_raw(
        self, ptr: ParamPointer, metadata: ParamsMetadata, timeout: float
    ) -> tuple[ParamsMetadata, list[np.ndarray]]:
        """The plane read alone (waiting for the object included; decoding a
        compressed payload is the caller's span). ``copied_nbytes`` is what
        the read materialises on the host: the payload out of the object
        store's npz, nothing where the arrays are views (shm) or aliases
        (inline)."""
        copied = metadata.total_bytes if ptr.kind == "objstore" else 0
        with telemetry.span(TRANSPORT_GET_SPAN, push=False, mode=ptr.kind,
                            copied_nbytes=copied,
                            wire_nbytes=metadata.total_bytes):
            return self._read(ptr, metadata, timeout)

    def _read(
        self, ptr: ParamPointer, metadata: ParamsMetadata, timeout: float
    ) -> tuple[ParamsMetadata, list[np.ndarray]]:
        if ptr.kind == "shm":
            shm.wait_for(ptr.locator, timeout=timeout)
            got_meta, arrays = shm.read_params(ptr.locator)
            metadata.validate_arrays(arrays)
            return got_meta, arrays
        if ptr.kind == "objstore":
            assert self.store is not None, "objstore pointer but transport has no store"
            self.store.wait_for(ptr.locator, timeout=timeout)
            got_meta, arrays = npz_to_arrays(self.store.get(ptr.locator))
            metadata.validate_arrays(arrays)
            return got_meta, arrays
        if ptr.kind == "inline":
            arrays = [np.asarray(a) for a in ptr.inline or []]
            metadata.validate_arrays(arrays)
            return metadata, arrays
        raise ValueError(f"unknown pointer kind {ptr.kind!r}")

    # -- lifecycle -------------------------------------------------------
    def free(self, ptr: ParamPointer) -> None:
        """Release the payload behind a pointer (reference: Ray GC thread /
        shm unlink after round, ``utils.py:73-144``)."""
        with telemetry.span(TRANSPORT_FREE_SPAN, push=False, mode=ptr.kind):
            if ptr.kind == "shm":
                shm.unlink(ptr.locator)
            elif ptr.kind == "objstore" and self.store is not None:
                self.store.delete(ptr.locator)

    def cleanup(self) -> None:
        for name in self._owned:
            if self.mode == "shm":
                shm.unlink(name)
            elif self.mode == "objstore" and self.store is not None:
                self.store.delete(name)
        self._owned.clear()
        self.host_pool.close()  # reusable: next submit rebuilds the executor
