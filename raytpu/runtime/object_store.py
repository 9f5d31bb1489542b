"""In-process object store: the memory-store half of the object plane.

Reference analogue: ``src/ray/core_worker/store_provider/memory_store/`` —
small objects live in the worker's memory store; large ones go to the
shared-memory store (our C++ plasma-equivalent in ``src/store/``, bound via
:mod:`raytpu.runtime.shm_store`). This class fronts both: values under the
inline threshold stay here; larger values are created in shared memory and
fetched zero-copy.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from raytpu.core.config import cfg
from raytpu.core.errors import GetTimeoutError
from raytpu.core.ids import ObjectID
from raytpu.runtime.serialization import (
    ZEROCOPY, SerializedPlan, SerializedValue,
)
from raytpu.util.failpoints import failpoint


class MemoryStore:
    """Thread-safe oid → SerializedValue map with blocking gets.

    Overflow spills to disk (reference: ``local_object_manager.h:41``
    spill-to-external-storage): when the shared-memory arena rejects a
    large object, or the heap exceeds its budget
    (``object_store_memory_bytes * object_spilling_threshold``), values
    move to files under ``object_store_fallback_directory`` and are
    restored transparently on access — a pipeline whose working set
    exceeds store memory finishes instead of dying.
    """

    def __init__(self, shm=None):
        self._objects: Dict[ObjectID, SerializedValue] = {}
        self._cv = threading.Condition()
        self._shm = shm  # optional SharedMemoryStore for large objects
        self._spilled: Dict[ObjectID, str] = {}  # oid -> file path
        self._spill_dir: Optional[str] = None
        self._heap_bytes = 0  # running total; keeps the budget check O(1)
        self._evict_lock = threading.Lock()  # one evictor at a time
        # Called (outside the lock) after each put — the scheduler hooks this
        # for dependency wakeups (reference: dependency_manager.cc).
        self.on_put = None

    # -- spill plumbing -------------------------------------------------------

    def _spill_path(self, oid: ObjectID) -> str:
        import os
        import tempfile

        if self._spill_dir is None:
            base = cfg.object_store_fallback_directory or os.path.join(
                tempfile.gettempdir(), "raytpu_spill")
            self._spill_dir = os.path.join(base, str(os.getpid()))
            os.makedirs(self._spill_dir, exist_ok=True)
        return os.path.join(self._spill_dir, oid.hex())

    def _spill(self, oid: ObjectID, value: SerializedValue,
               register: bool = True) -> Optional[str]:
        """Write the wire bytes to disk; returns the path (or None on I/O
        failure). ``register=False`` lets the evictor defer the _spilled
        entry until it has re-checked the object wasn't deleted meanwhile.

        Segments stream sequentially — [len][header][buffers…] — never a
        flattened to_bytes() blob: spilling happens exactly when memory is
        scarce, and doubling the peak right then is how an evictor OOMs
        the process it is trying to save."""
        try:
            path = self._spill_path(oid)
            with open(path, "wb") as f:
                f.write(len(value.header).to_bytes(4, "little"))
                f.write(value.header)
                for b in value.buffers:
                    f.write(b.cast("B") if b.format != "B" else b)
        except OSError:
            return None
        if register:
            with self._cv:
                self._spilled[oid] = path
                self._cv.notify_all()
        return path

    def _restore(self, oid: ObjectID) -> Optional[SerializedValue]:
        with self._cv:
            path = self._spilled.get(oid)
        if path is None:
            return None
        try:
            with open(path, "rb") as f:
                return SerializedValue.from_buffer(f.read())
        except OSError:
            return None

    def _maybe_evict_heap(self) -> None:
        """Spill largest heap objects until back under budget (called with
        nothing held; best effort)."""
        budget = int(cfg.object_store_memory_bytes
                     * cfg.object_spilling_threshold)
        import os

        # Serialize evictors: two threads picking the same victim would
        # race file registration vs unlink and could lose the only copy.
        with self._evict_lock:
            while True:
                with self._cv:
                    if self._heap_bytes <= budget or not self._objects:
                        return
                    victim = max(
                        self._objects,
                        key=lambda o: self._objects[o].total_bytes())
                    value = self._objects[victim]
                path = self._spill(victim, value, register=False)
                if path is None:
                    return
                with self._cv:
                    # Register + drop the heap copy only if THIS value is
                    # still current — a concurrent delete must not
                    # resurrect it, and a concurrent overwrite put() must
                    # not be shadowed by the stale file.
                    if self._objects.get(victim) is value:
                        self._spilled[victim] = path
                        self._objects.pop(victim, None)
                        self._heap_bytes -= value.total_bytes()
                        path = None
                if path is not None:
                    try:
                        os.unlink(path)
                    except OSError:
                        pass

    def put(self, oid: ObjectID, value) -> None:
        """Store a SerializedValue — or a SerializedPlan, in which case a
        large object is serialized INTO the shm mapping (create at exact
        wire size, write header+buffers in place, seal) with no
        intermediate flattened blob."""
        failpoint("object.put.pre")
        plan = value if isinstance(value, SerializedPlan) else None
        if plan is not None:
            value = plan.sv
        big = value.total_bytes() > cfg.max_direct_call_object_size
        stored = False
        if self._shm is not None and big:
            try:
                self._shm.put(oid, plan if plan is not None else value)
                with self._cv:
                    self._cv.notify_all()
                stored = True
            except Exception:
                # Shm full: spill big objects straight to disk rather than
                # ballooning the daemon heap.
                stored = self._spill(oid, value) is not None
        if not stored:
            import os

            with self._cv:
                prev = self._objects.get(oid)
                if prev is not None:
                    self._heap_bytes -= prev.total_bytes()
                self._objects[oid] = value
                self._heap_bytes += value.total_bytes()
                stale = self._spilled.pop(oid, None)
                self._cv.notify_all()
            if stale is not None:  # overwrite: drop the outdated file
                try:
                    os.unlink(stale)
                except OSError:
                    pass
            self._maybe_evict_heap()
        if self.on_put is not None:
            self.on_put(oid)

    def put_may_block(self, value: SerializedValue) -> bool:
        """Whether :meth:`put` of ``value`` may wait on more than this
        store's lock: a value over the inline limit is sealed into shared
        memory or spilled to disk, and a heap over its budget spills
        before the put returns. An event loop stores what cannot block
        itself and hands the rest to a thread."""
        size = value.total_bytes()
        return (size > cfg.max_direct_call_object_size
                or self._heap_bytes + size > int(
                    cfg.object_store_memory_bytes
                    * cfg.object_spilling_threshold))

    def begin_receive(self, oid: ObjectID, size: int) -> "_Receive":
        """Open a streaming receive destination of known wire size: each
        chunk writes its range directly into the final location (the shm
        mapping when the object is large and the arena has room, a heap
        bytearray otherwise). ``seal()`` publishes atomically; ``abort()``
        reclaims a half-written region — nothing is visible in between."""
        return _Receive(self, oid, size)

    def contains(self, oid: ObjectID) -> bool:
        with self._cv:
            if oid in self._objects or oid in self._spilled:
                return True
        return self._shm is not None and self._shm.contains(oid)

    def get(self, oid: ObjectID, timeout: Optional[float] = None) -> SerializedValue:
        # One flat retry loop (an unreadable spill file loops back to
        # waiting, same deadline) — the old tail-recursive retry could, in
        # principle, recurse once per raced delete until the stack went.
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            spilled = False
            with self._cv:
                while True:
                    sv = self._objects.get(oid)
                    if sv is not None:
                        return sv
                    if oid in self._spilled:
                        spilled = True
                        break  # restore outside the lock
                    if self._shm is not None and self._shm.contains(oid):
                        break  # fetch outside the lock
                    remaining = None if deadline is None else deadline - time.monotonic()
                    if remaining is not None and remaining <= 0:
                        raise GetTimeoutError(f"object {oid.hex()} not ready")
                    self._cv.wait(timeout=remaining if remaining is None else min(remaining, 0.5))
            if spilled:
                sv = self._restore(oid)
                if sv is not None:
                    return sv
                # Unreadable file (raced with delete / lost disk): drop the
                # stale entry so the retry can't loop on the same branch.
                with self._cv:
                    self._spilled.pop(oid, None)
                continue  # re-enter the wait with the original deadline
            return self._shm.get(oid)

    def try_get(self, oid: ObjectID) -> Optional[SerializedValue]:
        with self._cv:
            sv = self._objects.get(oid)
        if sv is not None:
            return sv
        sv = self._restore(oid)
        if sv is not None:
            return sv
        if self._shm is not None and self._shm.contains(oid):
            return self._shm.get(oid)
        return None

    def delete(self, oids: List[ObjectID]) -> None:
        import os

        spilled_paths = []
        with self._cv:
            for oid in oids:
                prev = self._objects.pop(oid, None)
                if prev is not None:
                    self._heap_bytes -= prev.total_bytes()
                path = self._spilled.pop(oid, None)
                if path is not None:
                    spilled_paths.append(path)
        for path in spilled_paths:
            try:
                os.unlink(path)
            except OSError:
                pass
        if self._shm is not None:
            for oid in oids:
                try:
                    self._shm.delete(oid)
                except Exception:
                    pass

    def spilled_path(self, oid: ObjectID) -> Optional[str]:
        """Path of a spilled object's wire file (the file IS the wire
        layout) — lets the transfer sender mmap it and serve chunk reads
        as slices instead of a read() per chunk."""
        with self._cv:
            return self._spilled.get(oid)

    def spilled_wire_size(self, oid: ObjectID) -> Optional[int]:
        """Wire-layout size of a spilled object, without reading it (the
        spill file IS the wire layout)."""
        import os

        with self._cv:
            path = self._spilled.get(oid)
        if path is None:
            return None
        try:
            return os.path.getsize(path)
        except OSError:
            return None

    def spilled_wire_range(self, oid: ObjectID, offset: int,
                           length: int) -> Optional[bytes]:
        """Serve a byte range straight from the spill file — chunked
        transfers of spilled objects must not re-materialize the whole
        value per chunk."""
        with self._cv:
            path = self._spilled.get(oid)
        if path is None:
            return None
        try:
            with open(path, "rb") as f:
                f.seek(offset)
                return f.read(length)
        except OSError:
            return None

    def teardown_spill(self) -> None:
        """Remove this process's spill directory (shutdown path)."""
        import shutil

        with self._cv:
            d = self._spill_dir
            self._spill_dir = None
            self._spilled.clear()
        if d is not None:
            shutil.rmtree(d, ignore_errors=True)

    def keys(self) -> List[ObjectID]:
        """All locally-held object ids (heap + spilled + shared memory) —
        used to re-announce locations after a control-plane restart."""
        with self._cv:
            out = list(self._objects.keys())
            out.extend(o for o in self._spilled if o not in self._objects)
        if self._shm is not None:
            try:
                out.extend(self._shm.keys())
            except Exception:
                pass
        return out

    def size(self) -> int:
        with self._cv:
            return len(self._objects)

    def used_bytes(self) -> int:
        with self._cv:
            return sum(v.total_bytes() for v in self._objects.values())


class _Receive:
    """A streaming receive in flight (see MemoryStore.begin_receive).

    Lifecycle mirrors the shm create→seal protocol: the destination is
    allocated at final size up front, chunk writes land in place, and only
    ``seal()`` publishes. ``abort()`` (idempotent, also safe after seal)
    returns a half-written shm region to the free list — a receiver dying
    mid-transfer leaks nothing and the key is immediately creatable again.
    """

    __slots__ = ("_store", "oid", "size", "_dst", "_buf", "_done", "in_shm")

    def __init__(self, store: MemoryStore, oid: ObjectID, size: int):
        self._store = store
        self.oid = oid
        self.size = size
        self._dst: Optional[memoryview] = None
        self._buf: Optional[bytearray] = None
        self._done = False
        shm = store._shm
        if (ZEROCOPY and shm is not None
                and size > cfg.max_direct_call_object_size):
            try:
                self._dst = shm.create(oid, size)
            except Exception:
                self._dst = None  # full / key exists: heap fallback
        if self._dst is None:
            self._buf = bytearray(size)
        self.in_shm = self._dst is not None

    def write(self, offset: int, data) -> int:
        """Write one chunk's range straight into the destination."""
        n = len(data)
        if offset < 0 or offset + n > self.size:
            raise ValueError(
                f"chunk [{offset}, {offset + n}) outside object of "
                f"{self.size} bytes")
        if self._dst is not None:
            self._dst[offset : offset + n] = data
        else:
            self._buf[offset : offset + n] = data
        return n

    def seal(self) -> None:
        """Publish atomically (store waiters wake, on_put fires)."""
        if self._done:
            return
        self._done = True
        store = self._store
        if self._dst is not None:
            self._dst.release()
            self._dst = None
            store._shm.seal(self.oid)
            with store._cv:
                store._cv.notify_all()
            if store.on_put is not None:
                store.on_put(self.oid)
        else:
            buf = self._buf
            self._buf = None
            store.put(self.oid, SerializedValue.from_buffer(buf))

    def abort(self) -> None:
        """Reclaim the destination; the object was never visible."""
        if self._done:
            return
        self._done = True
        if self._dst is not None:
            self._dst.release()
            self._dst = None
            try:
                self._store._shm.abort(self.oid)
            except Exception:
                pass  # arena already closed (shutdown)
        self._buf = None
