"""Worker runtime — task execution and object ownership.

Reference analogue: ``src/ray/core_worker/core_worker.h:291`` (CoreWorker)
and the Cython execution callback (``python/ray/_raylet.pyx:1721``). The
Worker owns: the reference counter, the memory/shm store front, arg
resolution, task execution (deserialize args → call → store returns), and
error wrapping (user exceptions become stored TaskError values so gets
raise remotely-thrown errors; reference: RayTaskError plumbing).
"""

from __future__ import annotations

import functools
import threading
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

# pyarrow's FIRST import must happen on a process's main thread: importing
# it from a task thread intermittently segfaults in this environment
# (native init race observed reliably with `pa.table` built shortly after
# an in-thread first import). Every process that executes tasks imports
# this module from its main thread, so force the import here; tasks and
# the data layer then only ever see the already-initialized module.
try:
    import pyarrow  # noqa: F401
except Exception:  # optional at runtime — the data layer degrades
    pass

from raytpu.core.errors import TaskCancelledError, TaskError
from raytpu.core.ids import JobID, NodeID, ObjectID, WorkerID, _Counter
from raytpu.runtime import context as ctx_mod
from raytpu.runtime.object_ref import ObjectRef
from raytpu.runtime.object_store import MemoryStore
from raytpu.runtime.refcount import ReferenceCounter
from raytpu.runtime.serialization import (
    SerializedValue,
    contained_refs,
    deserialize,
    serialize,
)
from raytpu.runtime.task_spec import ArgKind, TaskSpec


class Worker:
    """The per-process runtime object (one per worker/driver process)."""

    def __init__(self, job_id: JobID, node_id: NodeID, store: MemoryStore):
        self.worker_id = WorkerID.from_random()
        self.job_id = job_id
        self.node_id = node_id
        self.store = store
        self.reference_counter = ReferenceCounter(
            on_out_of_scope=self._on_out_of_scope
        )
        self.put_counter = _Counter()
        self._function_cache: Dict[bytes, Callable] = {}
        self._cancelled: set = set()
        self._cancel_lock = threading.Lock()
        # Streaming-generator state per producing task: produced/acked
        # counters for backpressure plus the buffer pins the producer holds
        # on unconsumed elements (reference: ObjectRefStream,
        # task_manager.h:98).
        self._streams: Dict[TaskID, dict] = {}
        self._streams_cv = threading.Condition()
        # Cluster worker hook: ship each stream element to the node daemon
        # as it is produced (set by worker_proc.main).
        self.on_stream_element: Optional[Callable[[ObjectID], None]] = None
        # Cluster nodes set this: results whose owner is a REMOTE driver
        # must not be freed by the local refcount (the owner's handles are
        # not visible here; the owner sends an explicit free instead —
        # reference: owner-based object lifetime, reference_count.h:61).
        self.pin_owned = False
        # Elements of async actors' streams (their ends included) stored
        # on the actor's event loop, and through the default executor.
        self.stream_puts_inline = 0
        self.stream_puts_executor = 0

    # -- ownership ------------------------------------------------------------

    def _on_out_of_scope(self, oid: ObjectID) -> None:
        if self.pin_owned:
            # Cluster node: locally-visible refs don't own this object; only
            # the owner's explicit free (free_object RPC) may delete it.
            return
        self._delete_object(oid)

    def _delete_object(self, oid: ObjectID) -> None:
        """Delete a stored value AND drop the stored_in edges it holds on
        contained refs (the pairing for add_stored_in — without it, refs
        inside deleted objects stay pinned forever)."""
        sv = self.store.try_get(oid)
        if sv is not None:
            try:
                for rb in contained_refs(sv):
                    inner = ObjectRef.from_binary(rb)
                    self.reference_counter.remove_stored_in(inner.id, oid)
            except Exception:
                pass
        self.store.delete([oid])

    def put_object(self, value: Any, oid: Optional[ObjectID] = None,
                   creating_task=None, sv=None) -> ObjectRef:
        if isinstance(value, ObjectRef):
            raise TypeError("put() on an ObjectRef is disallowed (same as reference)")
        if sv is None:
            sv = serialize(value)
        if oid is None:
            oid = ObjectID.for_put(self.worker_id, self.put_counter.next())
        self.reference_counter.add_owned_object(
            oid, creating_task=creating_task, size=sv.total_bytes()
        )
        for rb in contained_refs(sv):
            inner = ObjectRef.from_binary(rb)
            self.reference_counter.add_stored_in(inner.id, oid)
        self.store.put(oid, sv)
        return ObjectRef(oid, owner=self.worker_id.binary())

    def put_serialized(self, oid: ObjectID, sv: SerializedValue,
                       creating_task=None) -> None:
        self.reference_counter.add_owned_object(
            oid, creating_task=creating_task, size=sv.total_bytes()
        )
        for rb in contained_refs(sv):
            inner = ObjectRef.from_binary(rb)
            self.reference_counter.add_stored_in(inner.id, oid)
        self.store.put(oid, sv)
        # Fire-and-forget: if every handle to this return object was dropped
        # before the task finished, nothing will ever trigger deletion — free
        # it now (including the stored_in edges just added).
        if not self.pin_owned and self.reference_counter.is_unreferenced(oid):
            self._delete_object(oid)

    # -- streaming generators -------------------------------------------------

    def stream_ack(self, task_id: TaskID, consumed: int) -> None:
        """Consumer progress report: element ``consumed-1`` was taken.
        Unblocks a producer waiting on backpressure and releases the
        buffer pin the producer held on that element."""
        release = []
        with self._streams_cv:
            st = self._streams.get(task_id)
            if st is None:  # finished/closed stream; pins already handled
                return
            if consumed > st["acked"]:
                st["acked"] = consumed
                self._streams_cv.notify_all()
            if consumed in st["pinned"]:
                st["pinned"].discard(consumed)
                release.append(consumed)
        for i in release:
            self.reference_counter.remove_local_ref(
                ObjectID.for_task_return(task_id, i))

    def stream_close(self, task_id: TaskID, consumed: int) -> None:
        """Consumer abandoned the stream: stop the producer, drop the pins
        on everything it never took."""
        with self._streams_cv:
            st = self._streams.pop(task_id, None)
            if st is None:
                return
            st["closed"] = True
            pinned = sorted(st["pinned"])
            st["pinned"] = set()
            self._streams_cv.notify_all()
        for i in pinned:
            self.reference_counter.remove_local_ref(
                ObjectID.for_task_return(task_id, i))

    def _stream_begin(self, tid: TaskID) -> dict:
        st = {"produced": 0, "acked": 0, "closed": False, "pinned": set()}
        with self._streams_cv:
            self._streams[tid] = st
        return st

    def _stream_put(self, spec: TaskSpec, st: dict, n: int, value,
                    sv: Optional[SerializedValue] = None) -> bool:
        """Store element ``n`` (0-based) at return index ``n+1`` (``sv``:
        the value, if the caller has serialized it). Returns False when
        the consumer closed the stream — the producer must stop (otherwise
        an abandoned infinite generator runs forever, pinning every
        element)."""
        oid = ObjectID.for_task_return(spec.task_id, n + 1)
        with self._streams_cv:
            if st["closed"]:
                return False
            if not self.pin_owned:
                # Buffer pin: no consumer handle exists yet; without this
                # the fire-and-forget check in put_serialized frees the
                # element immediately. Recorded in `pinned` under the lock
                # so ack/close release exactly the pins that exist.
                st["pinned"].add(n + 1)
                self.reference_counter.add_local_ref(oid)
        self.put_serialized(oid, serialize(value) if sv is None else sv,
                            creating_task=spec.task_id)
        if self.on_stream_element is not None:
            self.on_stream_element(oid)
        with self._streams_cv:
            st["produced"] = n + 1
        return True

    def _stream_finish(self, spec: TaskSpec, st: dict, n: int,
                       sv: Optional[SerializedValue] = None) -> None:
        from raytpu.runtime.generator import StreamEnd

        done_oid = ObjectID.for_task_return(spec.task_id, 0)
        self.put_serialized(
            done_oid, serialize(StreamEnd(n)) if sv is None else sv,
            creating_task=spec.task_id)
        if self.on_stream_element is not None:
            self.on_stream_element(done_oid)
        # Cluster workers pin nothing (pin_owned): drop the state now so
        # long-lived workers don't accumulate one entry per stream. Local
        # producers keep it until the consumer's stream_close releases the
        # element pins.
        if self.pin_owned:
            with self._streams_cv:
                if self._streams.get(spec.task_id) is st:
                    self._streams.pop(spec.task_id, None)

    def _backpressured(self, spec: TaskSpec, st: dict, n: int) -> bool:
        with self._streams_cv:
            return (spec.backpressure > 0
                    and not st["closed"]
                    and not self.is_cancelled(spec.task_id)
                    and n - st["acked"] >= spec.backpressure)

    def _run_stream(self, spec: TaskSpec, iterator) -> Optional[BaseException]:
        """Drain a generator task: store element ``i`` at return index
        ``i+1`` as produced, then a StreamEnd at index 0. Returns the
        user/cancel error, if any (stored by the caller's policy at index
        0 — the completion slot doubles as the failure slot)."""
        tid = spec.task_id
        st = self._stream_begin(tid)
        n = 0
        try:
            for value in iterator:
                if self.is_cancelled(tid):
                    return TaskCancelledError(f"task {spec.name} cancelled")
                if not self._stream_put(spec, st, n, value):
                    break  # consumer closed the stream
                n += 1
                with self._streams_cv:
                    while (spec.backpressure > 0
                           and not st["closed"]
                           and not self.is_cancelled(tid)
                           and n - st["acked"] >= spec.backpressure):
                        self._streams_cv.wait(timeout=0.1)
        except BaseException as e:  # noqa: BLE001
            self._stream_abandon(tid, st)
            return e if isinstance(e, TaskError) else TaskError.from_exception(
                spec.name, e)
        self._stream_finish(spec, st, n)
        return None

    def _stream_abandon(self, tid: TaskID, st: dict) -> None:
        """Error-path cleanup: cluster workers hold no pins, so the state
        entry must not outlive the failed task (long-lived pooled workers
        would leak one per failed stream)."""
        if self.pin_owned:
            with self._streams_cv:
                if self._streams.get(tid) is st:
                    self._streams.pop(tid, None)

    async def _store_async(self, value, store: Callable) -> Any:
        """``store(sv)``, ``sv`` being ``value`` serialized or None, for
        an async actor's stream: on the actor's event loop where the
        store cannot block, else on the default executor. It cannot where
        this worker forwards nothing (a cluster worker's
        ``on_stream_element`` is an RPC to its node daemon) and the store
        keeps the serialized value in this process's memory (a larger
        one seals shared memory or spills to disk). A pool thread and
        two wake-ups of the loop an element were most of what a stream
        of small values, an LLM replica's token ids, cost."""
        import asyncio

        sv = None
        if self.on_stream_element is None:
            sv = serialize(value)
            if not self.store.put_may_block(sv):
                self.stream_puts_inline += 1
                return store(sv)
        self.stream_puts_executor += 1
        return await asyncio.get_running_loop().run_in_executor(
            None, store, sv)

    async def _run_stream_async(self, spec: TaskSpec,
                                aiterator) -> Optional[BaseException]:
        """Async-actor variant of :meth:`_run_stream` — drains an async (or
        sync) generator on the actor's event loop without blocking it for
        backpressure waits."""
        import asyncio

        from raytpu.runtime.generator import StreamEnd

        tid = spec.task_id
        st = self._stream_begin(tid)
        n = 0
        loop = asyncio.get_event_loop()
        try:
            if hasattr(aiterator, "__aiter__"):
                async for value in aiterator:
                    if self.is_cancelled(tid):
                        return TaskCancelledError(
                            f"task {spec.name} cancelled")
                    if not await self._store_async(value, functools.partial(
                            self._stream_put, spec, st, n, value)):
                        break
                    n += 1
                    while self._backpressured(spec, st, n):
                        await asyncio.sleep(0.02)
            else:
                # Sync generator on an async actor: every next() runs user
                # compute — drain it on the executor so health checks and
                # concurrent requests stay live.
                it = iter(aiterator)

                def _next():
                    try:
                        return True, next(it)
                    except StopIteration:
                        return False, None

                while True:
                    ok, value = await loop.run_in_executor(None, _next)
                    if not ok:
                        break
                    if self.is_cancelled(tid):
                        return TaskCancelledError(
                            f"task {spec.name} cancelled")
                    if not await self._store_async(value, functools.partial(
                            self._stream_put, spec, st, n, value)):
                        break
                    n += 1
                    while self._backpressured(spec, st, n):
                        await asyncio.sleep(0.02)
        except BaseException as e:  # noqa: BLE001
            self._stream_abandon(tid, st)
            return e if isinstance(e, TaskError) else TaskError.from_exception(
                spec.name, e)
        await self._store_async(StreamEnd(n), functools.partial(
            self._stream_finish, spec, st, n))
        return None

    # -- cancellation ---------------------------------------------------------

    def cancel(self, task_id) -> None:
        with self._cancel_lock:
            self._cancelled.add(task_id)

    def is_cancelled(self, task_id) -> bool:
        with self._cancel_lock:
            return task_id in self._cancelled

    # -- execution ------------------------------------------------------------

    def load_function(self, blob: bytes) -> Callable:
        fn = self._function_cache.get(blob)
        if fn is None:
            fn = cloudpickle.loads(blob)
            self._function_cache[blob] = fn
        return fn

    def load_spec_function(self, spec: TaskSpec) -> Callable:
        """Pickled payload, or a cross-language ``module:qual.name``
        reference resolved by import (reference: cross-language function
        descriptors — C++/Java callers can't cloudpickle Python)."""
        if spec.function_blob:
            return self.load_function(spec.function_blob)
        if spec.function_ref:
            fn = self._function_cache.get(spec.function_ref)
            if fn is None:
                import importlib

                module, _, qual = spec.function_ref.partition(":")
                if not module or not qual:
                    raise ValueError(
                        f"function_ref must be 'module:qualname', got "
                        f"{spec.function_ref!r}")
                obj = importlib.import_module(module)
                for part in qual.split("."):
                    obj = getattr(obj, part)
                fn = self._function_cache[spec.function_ref] = obj
            return fn
        raise ValueError(f"task {spec.name!r} carries no function")

    def resolve_args(self, spec: TaskSpec,
                     get_fn: Callable[[ObjectID], SerializedValue]):
        """Deserialize inline args; fetch + deserialize top-level refs.

        Reference semantics: only *top-level* ObjectRef args are resolved to
        values; refs nested inside structures pass through as refs.
        """
        values: List[Any] = []
        for arg in spec.args:
            if arg.kind == ArgKind.REF:
                ref = ObjectRef.from_binary(arg.data)
                sv = get_fn(ref.id)
                val = deserialize(sv)
                if isinstance(val, TaskError):
                    raise val
                values.append(val)
            else:
                values.append(deserialize(SerializedValue.from_buffer(arg.data)))
        nkw = len(spec.kwargs_keys)
        if nkw:
            pos, kwvals = values[:-nkw], values[-nkw:]
            kwargs = dict(zip(spec.kwargs_keys, kwvals))
        else:
            pos, kwargs = values, {}
        return pos, kwargs

    def execute_task(self, spec: TaskSpec,
                     get_fn: Callable[[ObjectID], SerializedValue],
                     actor_instance: Any = None,
                     store_errors: bool = True) -> Optional[BaseException]:
        """Run one task; store each return slot. Returns the error, if any.

        All outcomes (including user exceptions) are *stored* into the return
        objects so that any holder of the refs observes them — the reference
        stores RayTaskError values the same way (``task_manager.cc``
        ``MarkTaskReturnObjectsFailed``).
        """
        # Execution threads are REUSED (local soft pool; cluster workers'
        # asyncio default executor): one task's thread-local state
        # (collective membership etc.) must never leak into the next task
        # on the same thread. This is the shared execution core, so the
        # reset covers every executor.
        try:
            return self._execute_task_inner(spec, get_fn, actor_instance,
                                            store_errors)
        finally:
            ctx_mod.reset_task_scope()

    def _execute_task_inner(self, spec: TaskSpec,
                            get_fn: Callable[[ObjectID], SerializedValue],
                            actor_instance: Any = None,
                            store_errors: bool = True
                            ) -> Optional[BaseException]:
        return_ids = spec.return_ids()
        if self.is_cancelled(spec.task_id):
            err = TaskCancelledError(f"task {spec.name} cancelled")
            self._store_error(return_ids, spec, err)
            return err
        _maybe_store = (self._store_error if store_errors
                        else (lambda *a, **k: None))

        old_ctx = ctx_mod.current()
        new_ctx = ctx_mod.RuntimeContext(
            job_id=self.job_id,
            node_id=self.node_id,
            task_id=spec.task_id,
            actor_id=spec.actor_id
            or (spec.actor_creation.actor_id if spec.actor_creation else None),
            placement_group_id=(spec.scheduling.pg_id.binary()
                                if spec.scheduling.pg_id else None),
            attempt=spec.attempt,
        )
        ctx_mod.set_current(new_ctx)
        try:
            from raytpu.runtime_env import RuntimeEnvContext

            renv = RuntimeEnvContext(spec.runtime_env)
            renv.__enter__()
        except BaseException as e:  # invalid env: fail the task cleanly
            err = TaskError.from_exception(spec.name, e)
            _maybe_store(return_ids, spec, err)
            ctx_mod.set_current(old_ctx)
            return err
        try:
            args, kwargs = self.resolve_args(spec, get_fn)
            if spec.is_actor_task():
                if spec.method_name == "__raytpu_exec_compiled__":
                    # Compiled-DAG exec loop parked inside this actor
                    # (reference: do_exec_compiled_task,
                    # python/ray/dag/compiled_dag_node.py:90-110).
                    from raytpu.dag.compiled import _exec_compiled_loop

                    result = _exec_compiled_loop(actor_instance, *args)
                else:
                    method = getattr(actor_instance, spec.method_name)
                    result = method(*args, **kwargs)
            else:
                fn = self.load_spec_function(spec)
                result = fn(*args, **kwargs)
            if spec.streaming:
                # Iterate inside the runtime-env/context scope: generator
                # bodies run lazily, element by element.
                err = self._run_stream(spec, result)
                if err is not None:
                    _maybe_store(return_ids, spec, err)
                return err
        except BaseException as e:  # noqa: BLE001 — must capture everything
            err = e if isinstance(e, TaskError) else TaskError.from_exception(
                spec.name, e
            )
            _maybe_store(return_ids, spec, err)
            return err
        finally:
            renv.__exit__(None, None, None)
            ctx_mod.set_current(old_ctx)

        if spec.num_returns == 1:
            results = [result]
        elif spec.num_returns == 0:
            results = []
        else:
            results = list(result) if result is not None else []
            if len(results) != spec.num_returns:
                err = TaskError.from_exception(
                    spec.name,
                    ValueError(
                        f"expected {spec.num_returns} returns, got {len(results)}"
                    ),
                )
                _maybe_store(return_ids, spec, err)
                return err
        for oid, value in zip(return_ids, results):
            # A returned ObjectRef is stored as a value; get() resolves the
            # indirection one level (api.get).
            self.put_serialized(oid, serialize(value), creating_task=spec.task_id)
        return None

    def _store_error(self, return_ids, spec: TaskSpec, err: BaseException) -> None:
        sv = serialize(err)
        for oid in return_ids:
            self.put_serialized(oid, sv, creating_task=spec.task_id)

    def create_actor_instance(self, spec: TaskSpec,
                              get_fn) -> Any:
        """Instantiate the actor class from an actor-creation spec (raises on
        user error — caller stores the error)."""
        from raytpu.runtime_env import RuntimeEnvContext

        cls = self.load_spec_function(spec)
        args, kwargs = self.resolve_args(spec, get_fn)
        renv = RuntimeEnvContext(spec.runtime_env)
        old_ctx = ctx_mod.current()
        ctx_mod.set_current(
            ctx_mod.RuntimeContext(
                job_id=self.job_id,
                node_id=self.node_id,
                task_id=spec.task_id,
                actor_id=spec.actor_creation.actor_id,
                attempt=spec.attempt,
            )
        )
        try:
            with renv:
                return cls(*args, **kwargs)
        finally:
            ctx_mod.set_current(old_ctx)
