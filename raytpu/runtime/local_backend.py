"""Single-process backend: the whole fabric in one process.

This is the analogue of the reference's local-mode plus its single-node
data path, with real semantics: resource-gated scheduling (hybrid policy is
trivial with one node), dependency-triggered dispatch (reference:
``dependency_manager.cc``), per-actor ordered execution queues (reference:
``transport/actor_scheduling_queue.cc``), placement-group bundle
reservation with ICI-aware chip assignment, retries, and blocked-worker
resource release (a worker blocked in ``get`` returns its CPU — reference
raylet behavior for blocked workers).

Cluster mode (``raytpu.cluster``) runs the same Worker execution core in
separate processes; this backend is both the dev/test fabric and each
cluster worker's in-process engine.
"""

from __future__ import annotations

import asyncio
import inspect
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from raytpu.core.config import cfg
from raytpu.core.errors import (
    ActorDiedError,
    ActorError,
    PlacementGroupError,
    RayTpuError,
    TaskCancelledError,
    TaskError,
)
from raytpu.core.ids import ActorID, JobID, NodeID, ObjectID, PlacementGroupID, TaskID
from raytpu.core.resources import CPU, TPU, NodeResources, ResourceSet
from raytpu.core.topology import TpuTopology
from raytpu.runtime.object_ref import ObjectRef
from raytpu.runtime.object_store import MemoryStore
from raytpu.runtime.serialization import deserialize, serialize
from raytpu.runtime.task_spec import ArgKind, SchedulingKind, TaskSpec
from raytpu.runtime.worker import Worker
from raytpu.util import task_events


@dataclass
class _TaskRecord:
    spec: TaskSpec
    required: ResourceSet
    missing_deps: set
    state: str = "waiting"  # waiting -> ready -> running -> done
    released_while_blocked: int = 0
    # What a blocked task gave back: CPU only. Accelerator chips are never
    # released while blocked (reference: raylets return CPU for blocked
    # workers; GPU/TPU bindings are process-lifetime).
    blocked_subset: Optional[ResourceSet] = None


@dataclass
class _Bundle:
    index: int
    resources: ResourceSet
    node: NodeResources = None  # per-bundle reservation ledger
    chip_coords: List[Tuple[int, ...]] = field(default_factory=list)

    def __post_init__(self):
        if self.node is None:
            self.node = NodeResources(self.resources)


@dataclass
class _PlacementGroup:
    pg_id: PlacementGroupID
    bundles: List[_Bundle]
    strategy: str
    name: str = ""
    state: str = "created"  # created | removed


class _SoftThreadPool:
    """Grow-on-demand executor for task bodies.

    Thread-per-task semantics at pooled cost: an idle thread is reused,
    but a submit NEVER queues behind a busy one — a task blocked in
    raytpu.get must not delay an unrelated dispatch (the deadlock a
    fixed-size pool would reintroduce). Idle threads expire after
    ``idle_ttl``; the submit/expire race is linearized under one lock so
    a reserved work item can never be orphaned."""

    def __init__(self, name: str = "task-exec", idle_ttl: float = 10.0):
        self._q: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._idle = 0
        self._name = name
        self._ttl = idle_ttl
        self._seq = 0

    def submit(self, fn, *args) -> None:
        with self._lock:
            if self._idle > 0:
                self._idle -= 1
                self._q.put((fn, args))
                return
            self._seq += 1
            seq = self._seq
        threading.Thread(target=self._worker, args=(fn, args),
                         daemon=True, name=f"{self._name}-{seq}").start()

    def _worker(self, fn, args) -> None:
        from raytpu.runtime import context as ctx_mod

        while True:
            try:
                fn(*args)
            except Exception:  # task errors are handled inside _run_task;
                # anything reaching here is scheduler-state trouble —
                # surface it (the old thread-per-task model at least got
                # the default excepthook traceback).
                import logging
                import traceback

                logging.getLogger("raytpu").error(
                    "task execution thread raised:\n%s",
                    traceback.format_exc())
            # Reused threads must not leak one task's thread-locals
            # (collective group membership etc.) into the next.
            ctx_mod.reset_task_scope()
            fn = args = None  # don't pin the finished task while idle
            with self._lock:
                self._idle += 1
            try:
                fn, args = self._q.get(timeout=self._ttl)
                continue
            except queue.Empty:
                pass
            with self._lock:
                # A submit may have reserved us between the timeout and
                # this lock: drain it rather than orphaning the item.
                try:
                    fn, args = self._q.get_nowait()
                    continue
                except queue.Empty:
                    self._idle -= 1
                    return


class _ActorRuntime:
    """One live actor: a dedicated thread draining an ordered queue.

    Sync actors with max_concurrency>1 execute on an internal pool (dispatch
    order preserved, completion unordered — reference threaded actors).
    Async actors run an event loop; methods execute as asyncio tasks bounded
    by a semaphore (reference: async actors, ``max_concurrency``).
    """

    def __init__(self, backend: "LocalBackend", spec: TaskSpec):
        self.backend = backend
        self.creation_spec = spec
        self.actor_id = spec.actor_creation.actor_id
        self.max_concurrency = spec.actor_creation.max_concurrency
        self.concurrency_groups = dict(
            spec.actor_creation.concurrency_groups or {})
        self.is_async = spec.actor_creation.is_async
        self.name = spec.actor_creation.name
        self.namespace = spec.actor_creation.namespace
        self.detached = spec.actor_creation.lifetime_detached
        self.queue: "queue.Queue" = queue.Queue()
        self.state_lock = threading.Lock()  # guards dead + queue transitions
        self.dead = False
        self.death_reason = ""
        self.instance = None
        self.ready_event = threading.Event()
        self.creation_error: Optional[BaseException] = None
        self.num_handles = 0
        self.resources = ResourceSet(spec.resources)
        self.alloc_target: Optional[NodeResources] = None  # where resources came from
        self.thread = threading.Thread(
            target=self._run, name=f"actor-{self.actor_id.hex()[:8]}", daemon=True
        )

    def start(self):
        self.thread.start()

    def submit(self, spec: TaskSpec):
        if spec.concurrency_group and \
                spec.concurrency_group not in self.concurrency_groups:
            # Covers .options(concurrency_group=...) overrides that bypass
            # class-level validation — silently landing in the default pool
            # would drop the isolation the caller asked for.
            self.backend._fail_spec(spec, ActorError(
                f"actor {self.actor_id.hex()[:8]} has no concurrency group "
                f"{spec.concurrency_group!r}; declared: "
                f"{sorted(self.concurrency_groups) or '{}'}"))
            return
        with self.state_lock:
            if not self.dead:
                self.queue.put(spec)
                return
            reason = self.death_reason
        self.backend._fail_spec(
            spec, ActorDiedError(self.actor_id.hex(), reason)
        )

    def kill(self, reason: str = "killed via raytpu.kill"):
        if self.dead:
            return
        self.queue.put(("__kill__", reason))

    # -- internals -----------------------------------------------------------

    def _run(self):
        w = self.backend.worker
        try:
            self.instance = w.create_actor_instance(
                self.creation_spec, self.backend._get_serialized
            )
            # The creation task's return slot signals readiness (reference:
            # actor creation dummy object).
            w.put_serialized(
                self.creation_spec.return_ids()[0],
                serialize(None),
                creating_task=self.creation_spec.task_id,
            )
        except BaseException as e:  # noqa: BLE001
            err = e if isinstance(e, TaskError) else TaskError.from_exception(
                self.creation_spec.name, e
            )
            self.creation_error = err
            w._store_error(self.creation_spec.return_ids(), self.creation_spec, err)
            self._die(f"creation failed: {e}")
            self.ready_event.set()
            return
        self.ready_event.set()
        if task_events.enabled():
            task_events.emit("actor", self.actor_id.hex(),
                             task_events.TaskTransition.CREATED,
                             name=self.name,
                             attempt=self.creation_spec.attempt)

        if self.is_async:
            self._run_async_loop()
        elif self.max_concurrency > 1 or self.concurrency_groups:
            self._run_threaded()
        else:
            self._run_sync()

    def _run_sync(self):
        while True:
            item = self.queue.get()
            if isinstance(item, tuple) and item[0] == "__kill__":
                self._die(item[1])
                return
            self._execute(item)

    def _run_threaded(self):
        from concurrent.futures import ThreadPoolExecutor

        # One executor per concurrency group + the default pool: a saturated
        # group queues behind itself, never behind another group (reference:
        # ``transport/concurrency_group_manager.cc`` per-group executors).
        pools = {"": ThreadPoolExecutor(max_workers=self.max_concurrency)}
        for group, limit in self.concurrency_groups.items():
            pools[group] = ThreadPoolExecutor(max_workers=max(1, int(limit)))
        while True:
            item = self.queue.get()
            if isinstance(item, tuple) and item[0] == "__kill__":
                for pool in pools.values():
                    pool.shutdown(wait=False)
                self._die(item[1])
                return
            pool = pools.get(item.concurrency_group, pools[""])
            pool.submit(self._execute, item)

    def _run_async_loop(self):
        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)
        sems = {"": asyncio.Semaphore(self.max_concurrency)}
        for group, limit in self.concurrency_groups.items():
            sems[group] = asyncio.Semaphore(max(1, int(limit)))
        stop = loop.create_future()
        inflight: dict = {}

        async def handle(spec: TaskSpec):
            try:
                async with sems.get(spec.concurrency_group, sems[""]):
                    await self._execute_async(spec)
            finally:
                inflight.pop(spec.task_id, None)

        async def pump():
            while True:
                item = await loop.run_in_executor(None, self.queue.get)
                if isinstance(item, tuple) and item[0] == "__kill__":
                    stop.set_result(item[1])
                    return
                inflight[item.task_id] = item
                asyncio.ensure_future(handle(item))

        loop.create_task(pump())
        reason = loop.run_until_complete(stop)
        # Fail anything still in flight before abandoning the loop — their
        # return objects must observe the death (finding: async kill hang).
        for spec in list(inflight.values()):
            self.backend._fail_spec(
                spec, ActorDiedError(self.actor_id.hex(), reason)
            )
        loop.close()
        self._die(reason)

    def _execute(self, spec: TaskSpec):
        if spec.runtime_env is None:
            # An actor's runtime_env covers its whole lifetime (reference
            # semantics), not just __init__: method tasks inherit it.
            spec.runtime_env = self.creation_spec.runtime_env
        self.backend.worker.execute_task(
            spec, self.backend._get_serialized, actor_instance=self.instance
        )
        self.backend._task_finished(spec)

    async def _execute_async(self, spec: TaskSpec):
        w = self.backend.worker
        from raytpu.runtime import context as ctx_mod
        from raytpu.runtime_env import RuntimeEnvContext

        if spec.runtime_env is None:
            spec.runtime_env = self.creation_spec.runtime_env
        try:
            args, kwargs = w.resolve_args(spec, self.backend._get_serialized)
            method = getattr(self.instance, spec.method_name)
            ctx_mod.set_current(
                ctx_mod.RuntimeContext(
                    job_id=w.job_id, node_id=w.node_id,
                    task_id=spec.task_id, actor_id=self.actor_id,
                )
            )
            with RuntimeEnvContext(spec.runtime_env):
                result = method(*args, **kwargs)
                if inspect.isawaitable(result):
                    result = await result
                if spec.streaming:
                    err = await w._run_stream_async(spec, result)
                    if err is not None:
                        w._store_error(spec.return_ids(), spec, err)
                    self.backend._task_finished(spec)
                    return
        except BaseException as e:  # noqa: BLE001
            err = e if isinstance(e, TaskError) else TaskError.from_exception(
                spec.name, e
            )
            w._store_error(spec.return_ids(), spec, err)
            self.backend._task_finished(spec)
            return
        rids = spec.return_ids()
        if spec.num_returns == 1:
            w.put_serialized(rids[0], serialize(result), creating_task=spec.task_id)
        else:
            for oid, v in zip(rids, list(result or [])):
                w.put_serialized(oid, serialize(v), creating_task=spec.task_id)
        self.backend._task_finished(spec)

    def _die(self, reason: str):
        if task_events.enabled():
            task_events.emit("actor", self.actor_id.hex(),
                             task_events.TaskTransition.DEAD,
                             name=self.name, error=reason)
        with self.state_lock:
            self.dead = True
            self.death_reason = reason
            drained = []
            while True:
                try:
                    drained.append(self.queue.get_nowait())
                except queue.Empty:
                    break
        for item in drained:
            if isinstance(item, TaskSpec):
                self.backend._fail_spec(
                    item, ActorDiedError(self.actor_id.hex(), reason)
                )
        self.backend._actor_died(self)


class LocalBackend:
    def __init__(self, job_id: JobID, num_cpus: Optional[float] = None,
                 num_tpus: Optional[int] = None,
                 resources: Optional[Dict[str, float]] = None,
                 object_store=None):
        import os

        self.job_id = job_id
        self.node_id = NodeID.from_random()
        if num_cpus is None:
            num_cpus = os.cpu_count() or 1
        total = {CPU: num_cpus}
        if num_tpus is None:
            from raytpu.core.topology import detect_local_tpu

            num_tpus = detect_local_tpu()["chips"]
        if num_tpus:
            total[TPU] = num_tpus
        total.update(resources or {})
        self.node = NodeResources(ResourceSet(total))
        self.topology = TpuTopology(shape=(max(1, int(num_tpus)),)) if num_tpus else None
        self.store = MemoryStore(shm=object_store)
        self.store.on_put = self._on_object_available
        self.worker = Worker(job_id, self.node_id, self.store)

        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        # Thread spawn dominated the task hot path (~half the per-task
        # cost in profile); reuse execution threads instead.
        self._exec_threads = _SoftThreadPool()
        self._tasks: Dict[TaskID, _TaskRecord] = {}
        self._waiting_on: Dict[ObjectID, set] = {}  # oid -> task_ids
        # oid -> the events of the threads in wait_any_object_ready that
        # watch it (stream consumers), each woken by its own object's put
        self._obj_watch: Dict[ObjectID, List[threading.Event]] = {}
        self._ready: List[TaskID] = []
        self._running: Dict[TaskID, _TaskRecord] = {}
        self._actors: Dict[ActorID, _ActorRuntime] = {}
        self._named_actors: Dict[Tuple[str, str], ActorID] = {}
        self._pgs: Dict[PlacementGroupID, _PlacementGroup] = {}
        self._shutdown = False
        # Local actor-restart bookkeeping (cluster nodes defer to the
        # head's restart state machine instead).
        self._head_managed_restarts = False
        self._no_restart_kills: set = set()
        self._actor_restarts: Dict[ActorID, int] = {}
        self._dispatcher = threading.Thread(
            target=self._dispatch_loop, name="raytpu-dispatcher", daemon=True
        )
        self._dispatcher.start()
        self._task_events: List[dict] = []  # timeline feed

    # -- public backend interface --------------------------------------------

    def submit_task(self, spec: TaskSpec) -> List[ObjectRef]:
        refs = [
            ObjectRef(oid, owner=self.worker.worker_id.binary())
            for oid in spec.return_ids()
        ]
        required = self._required_resources(spec)
        missing = set()
        with self._lock:
            for arg in spec.args:
                if arg.kind == ArgKind.REF:
                    ref = ObjectRef.from_binary(arg.data)
                    self.worker.reference_counter.add_submitted_task_ref(ref.id)
                    if not self.store.contains(ref.id):
                        missing.add(ref.id)
                        self._waiting_on.setdefault(ref.id, set()).add(spec.task_id)
            for rb in spec.inline_refs:
                self.worker.reference_counter.add_submitted_task_ref(
                    ObjectRef.from_binary(rb).id)
            rec = _TaskRecord(spec=spec, required=required, missing_deps=missing)
            self._tasks[spec.task_id] = rec
            if not missing:
                rec.state = "ready"
                self._ready.append(spec.task_id)
                self._cv.notify_all()
        self._record_event(spec, "submitted")
        if task_events.enabled():
            parent = None
            try:
                from raytpu.runtime import context as _rt_ctx
                tid = _rt_ctx.current().task_id
                parent = tid.hex() if tid is not None else None
            except Exception:
                pass
            task_events.emit("task", spec.task_id.hex(),
                             task_events.TaskTransition.SUBMITTED,
                             name=spec.name, attempt=spec.attempt,
                             parent_task_id=parent)
        return refs

    def create_actor(self, spec: TaskSpec) -> None:
        """Actor creation flows through the scheduler like a task (resources
        are held for the actor's lifetime); reference: GcsActorScheduler.

        The actor runtime is registered eagerly so method calls submitted
        before creation completes simply queue (the reference buffers these
        in the actor submit queue the same way)."""
        runtime = self._make_actor_runtime(spec)
        name = spec.actor_creation.name
        with self._lock:
            if name:
                key = (spec.actor_creation.namespace, name)
                if key in self._named_actors:
                    raise ValueError(f"actor name {name!r} already taken")
                self._named_actors[key] = spec.actor_creation.actor_id
            self._actors[spec.actor_creation.actor_id] = runtime
        self.submit_task(spec)

    def submit_actor_task(self, spec: TaskSpec) -> List[ObjectRef]:
        refs = [
            ObjectRef(oid, owner=self.worker.worker_id.binary())
            for oid in spec.return_ids()
        ]
        for arg in spec.args:
            if arg.kind == ArgKind.REF:
                ref = ObjectRef.from_binary(arg.data)
                self.worker.reference_counter.add_submitted_task_ref(ref.id)
        for rb in spec.inline_refs:
            self.worker.reference_counter.add_submitted_task_ref(
                ObjectRef.from_binary(rb).id)
        with self._lock:
            actor = self._actors.get(spec.actor_id)
        if actor is None:
            self._fail_spec(spec, ActorDiedError(
                spec.actor_id.hex(), "actor not found or dead"))
            return refs
        # Wait for creation to finish off-thread; ordering is preserved by
        # the actor queue itself (reference: sequence numbers in
        # direct_actor_task_submitter.cc).
        actor.submit(spec)
        self._record_event(spec, "submitted")
        if task_events.enabled():
            task_events.emit("task", spec.task_id.hex(),
                             task_events.TaskTransition.SUBMITTED,
                             name=spec.name, attempt=spec.attempt)
        return refs

    def get_actor_handle_info(self, name: str, namespace: str):
        with self._lock:
            actor_id = self._named_actors.get((namespace, name))
            if actor_id is None:
                raise ValueError(f"no actor named {name!r} in {namespace!r}")
            runtime = self._actors.get(actor_id)
            creation = runtime.creation_spec if runtime else None
        if runtime is None:
            # Not yet scheduled or already dead; look in pending tasks.
            with self._lock:
                for rec in self._tasks.values():
                    ac = rec.spec.actor_creation
                    if ac is not None and ac.actor_id == actor_id:
                        creation = rec.spec
                        break
        if creation is None:
            raise ValueError(f"actor {name!r} is dead")
        return actor_id, creation

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True) -> None:
        with self._lock:
            actor = self._actors.get(actor_id)
            if no_restart:
                self._no_restart_kills.add(actor_id)
        if actor is not None:
            actor.kill()

    def actor_handle_added(self, actor_id: ActorID):
        with self._lock:
            a = self._actors.get(actor_id)
            if a is not None:
                a.num_handles += 1

    def actor_handle_removed(self, actor_id: ActorID):
        # No lock: ``ActorHandle.__del__`` calls this, and the collector
        # runs it on whatever thread allocates next, inside whatever
        # critical section that thread is in. Inside ``ObjectStore.put``
        # it waited here for this lock while ``wait_any_object_ready``
        # held it and waited for the store's: sixteen token streams hung
        # a serving run in 2 of 27 (PERF.md, PR 26). A dict read is
        # atomic under the interpreter's lock.
        a = self._actors.get(actor_id)
        if a is not None:
            a.num_handles -= 1
            if a.num_handles <= 0 and not a.detached and not a.dead:
                a.kill("all handles out of scope")

    # -- streaming generators (consumer-side plumbing) -------------------------

    def stream_ack(self, task_id: TaskID, consumed: int) -> None:
        self.worker.stream_ack(task_id, consumed)

    def stream_close(self, task_id: TaskID, consumed: int) -> None:
        self.worker.stream_close(task_id, consumed)

    def cancel_task(self, task_id: TaskID) -> None:
        self.worker.cancel(task_id)
        with self._lock:
            rec = self._tasks.get(task_id)
            if rec is not None and rec.state in ("waiting", "ready"):
                rec.state = "done"
                if task_id in self._ready:
                    self._ready.remove(task_id)
                self._fail_spec(
                    rec.spec,
                    TaskCancelledError(f"task {rec.spec.name} cancelled"),
                )

    # -- placement groups -----------------------------------------------------

    def create_placement_group(self, bundles: List[Dict[str, float]],
                               strategy: str, name: str = "") -> PlacementGroupID:
        pg_id = PlacementGroupID.from_random()
        bs = [_Bundle(i, ResourceSet(b)) for i, b in enumerate(bundles)]
        total = ResourceSet({})
        for b in bs:
            total = total + b.resources
        with self._lock:
            if strategy == "STRICT_SPREAD" and len(bs) > 1:
                raise PlacementGroupError(
                    "STRICT_SPREAD with >1 bundle cannot be satisfied on a "
                    "single node"
                )
            if not total.is_subset_of(self.node.available):
                raise PlacementGroupError(
                    f"placement group infeasible: needs {total.to_dict()}, "
                    f"available {self.node.available.to_dict()}"
                )
            self.node.allocate(total)
            # ICI-aware chip assignment: STRICT_PACK gets contiguous sub-boxes.
            if self.topology is not None:
                for b in bs:
                    chips = int(b.resources.get(TPU))
                    if chips:
                        coords = (
                            self.topology.allocate_subcube(chips)
                            if strategy in ("PACK", "STRICT_PACK")
                            else self.topology.allocate_any(chips)
                        )
                        if coords is None:
                            coords = self.topology.allocate_any(chips) or []
                        b.chip_coords = coords
            self._pgs[pg_id] = _PlacementGroup(pg_id, bs, strategy, name)
        return pg_id

    def remove_placement_group(self, pg_id: PlacementGroupID) -> None:
        with self._lock:
            pg = self._pgs.pop(pg_id, None)
            if pg is None:
                return
            pg.state = "removed"
            total = ResourceSet({})
            for b in pg.bundles:
                if b is None:  # cluster shard: bundle lives on another node
                    continue
                total = total + b.resources
                if self.topology is not None and b.chip_coords:
                    self.topology.release(b.chip_coords)
            self.node.release(total)

    def placement_group_info(self, pg_id: PlacementGroupID) -> Optional[dict]:
        with self._lock:
            pg = self._pgs.get(pg_id)
            if pg is None:
                return None
            return {
                "id": pg_id.hex(),
                "state": pg.state,
                "strategy": pg.strategy,
                "bundles": [b.resources.to_dict() for b in pg.bundles],
                "chip_coords": [b.chip_coords for b in pg.bundles],
            }

    # -- blocked-worker resource release --------------------------------------

    def task_blocked(self, task_id: TaskID) -> None:
        with self._lock:
            rec = self._running.get(task_id)
            if rec is not None and rec.released_while_blocked == 0:
                cpus = rec.required.get(CPU)
                if not cpus:
                    return
                rec.blocked_subset = ResourceSet({CPU: cpus})
                self._release_resources(rec, subset=rec.blocked_subset)
                rec.released_while_blocked += 1
                self._cv.notify_all()

    def task_unblocked(self, task_id: TaskID) -> None:
        with self._lock:
            rec = self._running.get(task_id)
            if rec is not None and rec.released_while_blocked > 0:
                rec.released_while_blocked -= 1
                self._allocate_resources(rec, force=True,
                                         subset=rec.blocked_subset)
                rec.blocked_subset = None

    # -- info -----------------------------------------------------------------

    def available_resources(self) -> Dict[str, float]:
        with self._lock:
            return self.node.available.to_dict()

    def cluster_resources(self) -> Dict[str, float]:
        with self._lock:
            return self.node.total.to_dict()

    def nodes(self) -> List[dict]:
        with self._lock:
            return [{
                "node_id": self.node_id.hex(),
                "alive": True,
                "resources": self.node.total.to_dict(),
                "available": self.node.available.to_dict(),
            }]

    def task_events(self) -> List[dict]:
        with self._lock:
            return list(self._task_events)

    def shutdown(self) -> None:
        with self._lock:
            self._shutdown = True
            self._cv.notify_all()
            actors = list(self._actors.values())
        for a in actors:
            a.kill("shutdown")
        try:
            self.store.teardown_spill()
        except Exception:
            pass

    # -- internals ------------------------------------------------------------

    def _get_serialized(self, oid: ObjectID):
        return self.store.get(oid)

    def _required_resources(self, spec: TaskSpec) -> ResourceSet:
        return ResourceSet(spec.resources)

    def _on_object_available(self, oid: ObjectID) -> None:
        with self._lock:
            # A put wakes the threads that watch its object and no
            # other: woken through one condition, 64 token streams made
            # 4,096 wake-ups a decode step, each for this lock, and the
            # dispatcher among them, so that a new request's probe
            # waited seconds for its turn (PERF.md, PR 52).
            for woken in self._obj_watch.pop(oid, ()):
                woken.set()
            waiters = self._waiting_on.pop(oid, None)
            if waiters:
                for tid in waiters:
                    rec = self._tasks.get(tid)
                    if rec is None or rec.state != "waiting":
                        continue
                    rec.missing_deps.discard(oid)
                    if not rec.missing_deps:
                        rec.state = "ready"
                        self._ready.append(tid)
                self._cv.notify_all()

    def wait_any_object_ready(self, refs, timeout: Optional[float] = None
                              ) -> bool:
        """Block until any of ``refs`` exists in the store (event-driven:
        the put hook wakes us — no polling; VERDICT r3 weak #5). Returns
        False on timeout. The caller waits on an event of its own, which
        only a put of one of its ``refs`` sets."""
        oids = [r.id for r in refs]
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        woken = threading.Event()
        with self._lock:
            if any(self.store.contains(o) for o in oids):
                return True
            for oid in oids:
                self._obj_watch.setdefault(oid, []).append(woken)
        try:
            while True:
                remaining = (5.0 if deadline is None
                             else deadline - time.monotonic())
                if remaining <= 0:
                    return False
                if woken.wait(remaining) or any(
                        self.store.contains(o) for o in oids):
                    return True
        finally:
            with self._lock:
                for oid in oids:
                    watchers = self._obj_watch.get(oid)
                    if watchers is not None and woken in watchers:
                        watchers.remove(woken)
                        if not watchers:
                            del self._obj_watch[oid]

    def _bundle_for(self, spec: TaskSpec) -> Optional[_Bundle]:
        sched = spec.scheduling
        if sched.kind != SchedulingKind.PLACEMENT_GROUP or sched.pg_id is None:
            return None
        pg = self._pgs.get(sched.pg_id)
        if pg is None:
            raise PlacementGroupError(f"placement group {sched.pg_id.hex()} gone")
        if sched.bundle_index >= 0:
            b = pg.bundles[sched.bundle_index]
            if b is None:
                # Cluster PG shard: this bundle lives on another node.
                raise PlacementGroupError(
                    f"bundle {sched.bundle_index} of pg "
                    f"{sched.pg_id.hex()} is not on this node")
            return b
        local = [b for b in pg.bundles if b is not None]
        for b in local:
            if b.node.can_fit(ResourceSet(spec.resources)):
                return b
        return local[0] if local else None

    def _try_allocate(self, rec: _TaskRecord) -> bool:
        bundle = self._bundle_for(rec.spec)
        if bundle is not None:
            if bundle.node.can_fit(rec.required):
                bundle.node.allocate(rec.required)
                return True
            return False
        if self.node.can_fit(rec.required):
            self.node.allocate(rec.required)
            return True
        if not rec.required.is_subset_of(self.node.total):
            # Infeasible forever — fail fast instead of hanging (the
            # reference raises after a warning period).
            self._fail_spec(rec.spec, TaskError.from_exception(
                rec.spec.name,
                ValueError(
                    f"task requires {rec.required.to_dict()} but node total is "
                    f"{self.node.total.to_dict()}"
                ),
            ))
            rec.state = "done"
            return False
        return False

    def _allocate_resources(self, rec: _TaskRecord, force: bool = False,
                            subset: Optional[ResourceSet] = None) -> None:
        bundle = self._bundle_for(rec.spec)
        target = bundle.node if bundle is not None else self.node
        target.allocate(subset if subset is not None else rec.required,
                        force=force)

    def _release_resources(self, rec: _TaskRecord,
                           subset: Optional[ResourceSet] = None) -> None:
        try:
            bundle = self._bundle_for(rec.spec)
        except Exception:
            # PG vanished while the task ran; its ledger died with it.
            return
        target = bundle.node if bundle is not None else self.node
        target.release(subset if subset is not None else rec.required)

    def _dispatch_loop(self):
        while True:
            with self._lock:
                while not self._shutdown and not self._ready:
                    self._cv.wait(timeout=0.5)
                if self._shutdown:
                    return
                dispatched = []
                # Requirement-identical skip: once a (resources,
                # scheduling-target) signature fails to allocate in this
                # scan, every later task with the SAME signature must fail
                # too (availability only shrinks mid-scan) — turns the
                # O(queue) rescans of a deep homogeneous backlog into
                # O(distinct signatures).
                failed_sigs: set = set()
                for tid in list(self._ready):
                    rec = self._tasks.get(tid)
                    if rec is None or rec.state != "ready":
                        self._ready.remove(tid)
                        continue
                    sched = rec.spec.scheduling
                    sig = (tuple(sorted(rec.required.to_dict().items())),
                           sched.kind,
                           sched.pg_id.binary() if sched.pg_id else None,
                           sched.bundle_index)
                    if sig in failed_sigs:
                        continue
                    try:
                        allocated = self._try_allocate(rec)
                    except Exception as e:
                        # e.g. PG removed/rerouted while queued — fail the
                        # task, never the scheduler thread.
                        self._ready.remove(tid)
                        rec.state = "done"
                        self._fail_spec(rec.spec, e if isinstance(
                            e, RayTpuError) else TaskError.from_exception(
                            rec.spec.name, e))
                        continue
                    if allocated:
                        self._ready.remove(tid)
                        rec.state = "running"
                        self._running[tid] = rec
                        dispatched.append(rec)
                    elif rec.state == "done":  # infeasible
                        self._ready.remove(tid)
                    else:
                        failed_sigs.add(sig)
                if not dispatched:
                    # Nothing fits right now; wait for a release.
                    self._cv.wait(timeout=0.05)
            for rec in dispatched:
                self._exec_threads.submit(self._run_task, rec)

    def _run_task(self, rec: _TaskRecord):
        spec = rec.spec
        self._record_event(spec, "running")
        if task_events.enabled():
            task_events.emit("task", spec.task_id.hex(),
                             task_events.TaskTransition.RUNNING,
                             name=spec.name, attempt=spec.attempt)
        if spec.is_actor_creation():
            with self._lock:
                runtime = self._actors.get(spec.actor_creation.actor_id)
                if runtime is None:  # killed before scheduling
                    self._release_resources(rec)
                    self._running.pop(spec.task_id, None)
                    rec.state = "done"
                    return
                bundle = self._bundle_for(spec)
                runtime.alloc_target = bundle.node if bundle else self.node
            runtime.start()
            runtime.ready_event.wait()
            # Resources stay allocated until the actor dies.
            with self._lock:
                self._running.pop(spec.task_id, None)
                rec.state = "done"
                self._cv.notify_all()
            self._record_event(spec, "finished")
            if task_events.enabled():
                task_events.emit("task", spec.task_id.hex(),
                                 task_events.TaskTransition.FINISHED,
                                 name=spec.name, attempt=spec.attempt)
            self._after_task(spec)
            return
        err = self._execute_plain(rec)
        retried = False
        if err is not None and self._should_retry(rec, err):
            retried = True
        elif err is not None:
            self.worker._store_error(spec.return_ids(), spec, err)
        if err is not None and task_events.enabled():
            # Emitted before the attempt counter moves so FAILED carries
            # the attempt that actually failed.
            task_events.emit("task", spec.task_id.hex(),
                             task_events.TaskTransition.FAILED,
                             name=spec.name, attempt=spec.attempt,
                             error=f"{type(err).__name__}: {err}"[:256])
        with self._lock:
            self._running.pop(spec.task_id, None)
            if rec.released_while_blocked == 0:
                self._release_resources(rec)
            else:
                # Task ended while blocked: only the CPU subset was given
                # back — release the accelerator remainder now.
                remainder = rec.required - (rec.blocked_subset
                                            or ResourceSet({}))
                if not remainder.is_empty():
                    self._release_resources(rec, subset=remainder)
            rec.released_while_blocked = 0
            rec.blocked_subset = None
            if retried:
                spec.attempt += 1
                rec.state = "ready"
                self._running.pop(spec.task_id, None)
                self._ready.append(spec.task_id)
            else:
                rec.state = "done"
            self._cv.notify_all()
        self._record_event(spec, "finished" if err is None else "failed")
        if task_events.enabled():
            if retried:
                task_events.emit("task", spec.task_id.hex(),
                                 task_events.TaskTransition.RETRIED,
                                 name=spec.name, attempt=spec.attempt)
            elif err is None:
                task_events.emit("task", spec.task_id.hex(),
                                 task_events.TaskTransition.FINISHED,
                                 name=spec.name, attempt=spec.attempt)
        if not retried:
            self._after_task(spec)

    def _execute_plain(self, rec: _TaskRecord) -> Optional[BaseException]:
        """Run one plain task; overridden by the cluster node backend to
        dispatch into a leased worker process (reference: worker lease +
        ``PushTask``)."""
        return self.worker.execute_task(rec.spec, self._get_serialized,
                                        store_errors=False)

    def _make_actor_runtime(self, spec: TaskSpec):
        """Actor runtime factory; the cluster node backend overrides this
        to host the actor in a dedicated worker process."""
        return _ActorRuntime(self, spec)

    def _should_retry(self, rec: _TaskRecord, err: BaseException) -> bool:
        from raytpu.core.errors import NodeDiedError, WorkerCrashedError

        spec = rec.spec
        if spec.attempt >= spec.max_retries:
            return False
        if isinstance(err, TaskCancelledError):
            return False
        if isinstance(err, (WorkerCrashedError, NodeDiedError)):
            # System failure: retry regardless of ``retry_exceptions``
            # (reference: TaskManager resubmits on worker/node death).
            return True
        # User exceptions retry only when opted in (reference:
        # ``retry_exceptions``); system failures always retry.
        return bool(spec.retry_exceptions)

    def _after_task(self, spec: TaskSpec):
        rc = self.worker.reference_counter
        for arg in spec.args:
            if arg.kind == ArgKind.REF:
                rc.remove_submitted_task_ref(ObjectRef.from_binary(arg.data).id)
        for rb in spec.inline_refs:
            rc.remove_submitted_task_ref(ObjectRef.from_binary(rb).id)
        with self._lock:
            self._tasks.pop(spec.task_id, None)

    def _fail_spec(self, spec: TaskSpec, err: BaseException):
        """Store an error into a spec's return objects AND release its
        submitted-arg refs (every failed-without-running path must end
        here, or arg objects leak pinned forever)."""
        self.worker._store_error(spec.return_ids(), spec, err)
        self._after_task(spec)

    def _task_finished(self, spec: TaskSpec):
        """Called by actor runtimes when an actor task completes."""
        self._record_event(spec, "finished")
        if task_events.enabled():
            task_events.emit("task", spec.task_id.hex(),
                             task_events.TaskTransition.FINISHED,
                             name=spec.name, attempt=spec.attempt)
        self._after_task(spec)

    def _actor_died(self, runtime: _ActorRuntime):
        with self._lock:
            self._actors.pop(runtime.actor_id, None)
            if runtime.name:
                self._named_actors.pop((runtime.namespace, runtime.name), None)
            if not runtime.resources.is_empty() and runtime.alloc_target is not None:
                try:
                    runtime.alloc_target.release(runtime.resources)
                except ValueError:
                    pass
            self._cv.notify_all()
        self._maybe_restart_actor(runtime)

    def _maybe_restart_actor(self, runtime) -> None:
        """Local-mode ``max_restarts`` (reference: GcsActorManager restart
        state machine, ``gcs_actor_manager.h:88``). Cluster nodes skip
        this — the head restarts actors so they can move to live nodes."""
        if self._head_managed_restarts or self._shutdown:
            return
        spec = runtime.creation_spec
        ac = spec.actor_creation
        aid = runtime.actor_id
        with self._lock:
            used = self._actor_restarts.get(aid, 0)
            no_restart = aid in self._no_restart_kills
            self._no_restart_kills.discard(aid)
        if (no_restart or runtime.creation_error is not None
                or runtime.death_reason in ("shutdown",
                                            "all handles out of scope")
                or used >= ac.max_restarts):
            return
        with self._lock:
            self._actor_restarts[aid] = used + 1
        spec.attempt += 1
        if task_events.enabled():
            task_events.emit("actor", aid.hex(),
                             task_events.TaskTransition.RESTARTED,
                             name=runtime.name, attempt=spec.attempt,
                             error=runtime.death_reason)
        try:
            self.create_actor(spec)
        except Exception:
            pass

    def _record_event(self, spec: TaskSpec, state: str):
        if not cfg.enable_timeline:
            return
        with self._lock:
            self._task_events.append({
                "task_id": spec.task_id.hex(),
                "name": spec.name,
                "state": state,
                "ts": time.time(),
                "actor_id": spec.actor_id.hex() if spec.actor_id else None,
            })
            if len(self._task_events) > cfg.task_events_buffer_size:
                del self._task_events[: len(self._task_events) // 2]
