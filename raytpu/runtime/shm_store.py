"""ctypes binding for the C++ shared-memory object store.

Reference analogue: the plasma client (``src/ray/object_manager/plasma/
client.cc``) — but our store is a passive shm arena (see
``src/store/shm_store.cc`` header comment), so the "client" is just the
mapping plus a handful of O(1) calls. Reads are zero-copy: ``get`` returns
a SerializedValue whose buffer is a memoryview into the mapping, pinned by
the store refcount until the view is garbage collected; ``sv.pin`` lets the
deserializer extend that pin to the arrays it hands out (see
``serialization.deserialize``), so a view outlives even a producer-side
delete (the C side defers the free until the last release).

Writes are serialize-into-place: ``create(oid, size)`` returns a memoryview
of the final-size region, the caller writes the wire bytes directly into
the mapping (``serialization.serialize_into``), and ``seal`` publishes
atomically. ``abort`` reclaims a created-but-unsealed region when a
receive/transfer dies half-way — the region was never visible.
"""

from __future__ import annotations

import ctypes
import mmap
import os
import weakref
from typing import Optional

from raytpu.core.errors import ObjectStoreFullError
from raytpu.core.ids import ObjectID
from raytpu.core.native import lib_path
from raytpu.runtime.serialization import (
    SerializedValue, serialize_into, wire_size_of,
)

_lib = None


def _load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(lib_path("libshmstore.so"))
        lib.shm_store_open.restype = ctypes.c_void_p
        lib.shm_store_open.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int]
        lib.shm_store_close.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.shm_store_create.restype = ctypes.c_int64
        lib.shm_store_create.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.shm_store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.shm_store_abort.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.shm_store_get.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint64)]
        lib.shm_store_get2.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.shm_store_release.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.shm_store_release_gen.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.shm_store_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.shm_store_delete.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
        lib.shm_store_used_bytes.restype = ctypes.c_uint64
        lib.shm_store_used_bytes.argtypes = [ctypes.c_void_p]
        lib.shm_store_capacity.restype = ctypes.c_uint64
        lib.shm_store_capacity.argtypes = [ctypes.c_void_p]
        lib.shm_store_num_objects.restype = ctypes.c_uint64
        lib.shm_store_num_objects.argtypes = [ctypes.c_void_p]
        lib.shm_store_fd.restype = ctypes.c_int
        lib.shm_store_fd.argtypes = [ctypes.c_void_p]
        lib.shm_store_map_size.restype = ctypes.c_uint64
        lib.shm_store_map_size.argtypes = [ctypes.c_void_p]
        lib.shm_store_set_no_evict.argtypes = [ctypes.c_void_p,
                                               ctypes.c_int]
        _lib = lib
    return _lib


class SharedMemoryStore:
    """One node's shared-memory arena (create on the daemon, attach from
    workers by name)."""

    def __init__(self, capacity: int = 1 << 30, name: Optional[str] = None,
                 create: bool = True, table_slots: int = 1 << 16):
        lib = _load()
        self.name = name or f"/raytpu-store-{os.getpid()}"
        self._lib = lib
        self._handle = lib.shm_store_open(
            self.name.encode(), capacity, table_slots, 1 if create else 0
        )
        if not self._handle:
            raise ObjectStoreFullError(
                f"failed to open shm store {self.name} (capacity={capacity})"
            )
        self._owner = create
        # The arena is loss-proof by default (C-side no_evict=1): a full
        # arena FAILS the put — the MemoryStore front spills to disk —
        # instead of LRU-evicting the ONLY copy of a task result (a silent
        # eviction leaves a phantom location at the head that drivers poll
        # until timeout). set_no_evict(False) opts into cache semantics.
        # A Python-side mmap view of the same segment for zero-copy reads.
        fd = lib.shm_store_fd(self._handle)
        self._map = mmap.mmap(fd, lib.shm_store_map_size(self._handle))
        self._mv = memoryview(self._map)
        self._closed = False

    def set_no_evict(self, enable: bool) -> None:
        """Loss-proof (default) vs cache semantics: with eviction enabled
        a full arena LRU-discards sealed objects — only safe when every
        object is re-fetchable elsewhere."""
        self._lib.shm_store_set_no_evict(self._handle, 1 if enable else 0)

    # -- object plane ---------------------------------------------------------

    def create(self, oid: ObjectID, size: int) -> memoryview:
        """Allocate a final-size region for in-place writes; returns the
        writable mapping view. Nothing is visible until :meth:`seal`."""
        off = self._lib.shm_store_create(self._handle, oid.binary(), size)
        if off < 0:
            raise ObjectStoreFullError(
                f"shm store cannot fit object of {size} bytes "
                f"(used {self.used_bytes()}/{self.capacity()})"
            )
        return self._mv[off : off + size]

    def seal(self, oid: ObjectID) -> None:
        """Publish a created region atomically (create→write→seal)."""
        if self._lib.shm_store_seal(self._handle, oid.binary()) != 0:
            raise ObjectStoreFullError(f"seal failed for {oid.hex()}")

    def abort(self, oid: ObjectID) -> bool:
        """Reclaim a created-but-unsealed region (failed receive). The
        region was never visible; its bytes return to the free list."""
        return self._lib.shm_store_abort(self._handle, oid.binary()) == 0

    def put(self, oid: ObjectID, value) -> None:
        """Serialize into place: allocate the exact wire size, write
        ``[4-byte header len][header][buffers]`` straight into the mapping,
        seal. ``value`` is a SerializedValue or SerializedPlan — no
        intermediate flattened blob either way."""
        blob_len = wire_size_of(value)
        dst = self.create(oid, blob_len)
        try:
            serialize_into(value, dst)
        except BaseException:
            dst.release()
            self.abort(oid)
            raise
        dst.release()
        self.seal(oid)

    def get(self, oid: ObjectID) -> SerializedValue:
        off = ctypes.c_int64()
        size = ctypes.c_uint64()
        gen = ctypes.c_uint64()
        rc = self._lib.shm_store_get2(
            self._handle, oid.binary(), ctypes.byref(off), ctypes.byref(size),
            ctypes.byref(gen),
        )
        if rc != 0:
            raise KeyError(f"object {oid.hex()} not in shm store")
        view = self._mv[off.value : off.value + size.value]
        sv = SerializedValue.from_buffer(view)
        # Keep the object pinned while this SerializedValue is alive; the
        # release names the generation it pinned, so a stale finalize can
        # never unpin a successor object reusing the key. Releases go
        # through a weakref to this store so finalizers firing after
        # close() (interpreter shutdown with live views) are no-ops
        # instead of calls on a freed handle.
        store_ref = weakref.ref(self)
        key = oid.binary()
        weakref.finalize(sv, _release, store_ref, key, gen.value)

        def _pin(obj) -> None:
            """Extend the pin to ``obj`` (e.g. a deserialized array view):
            takes one more store ref, released when ``obj`` dies."""
            st = store_ref()
            if st is None or st._closed:
                raise KeyError(f"shm store closed; cannot pin {oid.hex()}")
            o2, s2, g2 = ctypes.c_int64(), ctypes.c_uint64(), ctypes.c_uint64()
            if st._lib.shm_store_get2(st._handle, key, ctypes.byref(o2),
                                      ctypes.byref(s2), ctypes.byref(g2)) != 0:
                raise KeyError(f"object {oid.hex()} vanished from shm store")
            weakref.finalize(obj, _release, store_ref, key, g2.value)

        sv.pin = _pin
        return sv

    def contains(self, oid: ObjectID) -> bool:
        return bool(self._lib.shm_store_contains(self._handle, oid.binary()))

    def delete(self, oid: ObjectID, force: bool = False) -> bool:
        return self._lib.shm_store_delete(
            self._handle, oid.binary(), 1 if force else 0) == 0

    # -- stats ----------------------------------------------------------------

    def used_bytes(self) -> int:
        return self._lib.shm_store_used_bytes(self._handle)

    def capacity(self) -> int:
        return self._lib.shm_store_capacity(self._handle)

    def num_objects(self) -> int:
        return self._lib.shm_store_num_objects(self._handle)

    # -- lifecycle ------------------------------------------------------------

    def close(self, unlink: Optional[bool] = None) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self._mv.release()
            self._map.close()
        except (BufferError, ValueError):
            pass  # live zero-copy views; the OS cleans the mapping on exit
        self._lib.shm_store_close(
            self._handle, 1 if (self._owner if unlink is None else unlink) else 0
        )
        self._handle = None

    def __del__(self):
        try:
            self.close()
        except BaseException:
            pass


def _release(store_ref, key: bytes, gen: int) -> None:
    try:
        st = store_ref()
        if st is None or st._closed:
            return
        st._lib.shm_store_release_gen(st._handle, key, gen)
    except BaseException:
        pass


def attach(name: str) -> SharedMemoryStore:
    """Attach to an existing segment created by another process."""
    return SharedMemoryStore(capacity=0, name=name, create=False)
