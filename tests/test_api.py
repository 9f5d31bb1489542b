"""End-to-end API tests: tasks, actors, objects, placement groups
(reference analogues: ``python/ray/tests/test_basic*.py``,
``test_actor*.py``, ``test_placement_group*.py``)."""

import time
import raytpu.runtime.api

import numpy as np
import pytest


class TestTasks:
    def test_simple_task(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def add(a, b):
            return a + b

        assert raytpu.get(add.remote(1, 2)) == 3

    def test_kwargs(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def f(a, b=10, c=100):
            return a + b + c

        assert raytpu.get(f.remote(1, c=5)) == 16

    def test_chained_refs(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def inc(x):
            return x + 1

        ref = inc.remote(0)
        for _ in range(5):
            ref = inc.remote(ref)
        assert raytpu.get(ref) == 6

    def test_num_returns(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote(num_returns=3)
        def three():
            return 1, 2, 3

        a, b, c = three.remote()
        assert raytpu.get([a, b, c]) == [1, 2, 3]

    def test_task_error_propagates(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def boom():
            raise ValueError("bad")

        with pytest.raises(raytpu.TaskError) as ei:
            raytpu.get(boom.remote())
        assert "bad" in str(ei.value)

    def test_error_propagates_through_dependency(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def boom():
            raise ValueError("root cause")

        @raytpu.remote
        def use(x):
            return x

        with pytest.raises(raytpu.TaskError) as ei:
            raytpu.get(use.remote(boom.remote()))
        assert "root cause" in str(ei.value)

    def test_nested_tasks_no_deadlock(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def leaf(x):
            return x * 2

        @raytpu.remote
        def parent(x):
            import raytpu as r

            return r.get(leaf.remote(x)) + 1

        # 4 CPUs, 4 parents each blocking on a leaf: requires blocked-worker
        # resource release to finish.
        refs = [parent.remote(i) for i in range(4)]
        assert raytpu.get(refs) == [1, 3, 5, 7]

    def test_large_arg_via_store(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def total(x):
            return float(x.sum())

        x = np.ones(1_000_000, dtype=np.float32)  # 4MB > inline threshold
        assert raytpu.get(total.remote(x)) == 1_000_000.0

    def test_options_override(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def f():
            return 1

        assert raytpu.get(f.options(num_cpus=2, name="custom").remote()) == 1

    def test_invalid_option_rejected(self, raytpu_local):
        raytpu = raytpu_local
        with pytest.raises(ValueError):
            @raytpu.remote(bogus_option=1)
            def f():
                pass

    def test_direct_call_rejected(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def f():
            return 1

        with pytest.raises(TypeError):
            f()

    def test_retry_exceptions(self, raytpu_local):
        raytpu = raytpu_local
        marker = raytpu.put(0)

        @raytpu.remote(max_retries=3, retry_exceptions=True)
        def flaky():
            import raytpu as r
            from raytpu.runtime import context

            if context.current().attempt < 2:
                raise RuntimeError("transient")
            return "ok"

        assert raytpu.get(flaky.remote()) == "ok"

    def test_infeasible_task_fails_fast(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote(num_cpus=1000)
        def f():
            return 1

        with pytest.raises(raytpu.TaskError):
            raytpu.get(f.remote(), timeout=10)


class TestObjects:
    def test_put_get(self, raytpu_local):
        raytpu = raytpu_local
        ref = raytpu.put({"a": [1, 2, 3]})
        assert raytpu.get(ref) == {"a": [1, 2, 3]}

    def test_put_numpy_roundtrip(self, raytpu_local):
        raytpu = raytpu_local
        x = np.random.rand(100, 100)
        np.testing.assert_array_equal(raytpu.get(raytpu.put(x)), x)

    def test_put_objectref_rejected(self, raytpu_local):
        raytpu = raytpu_local
        with pytest.raises(TypeError):
            raytpu.put(raytpu.put(1))

    def test_get_timeout(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def slow():
            time.sleep(5)
            return 1

        with pytest.raises(raytpu.GetTimeoutError):
            raytpu.get(slow.remote(), timeout=0.2)

    def test_wait(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def f(t):
            time.sleep(t)
            return t

        fast = f.remote(0.01)
        slow = f.remote(2.0)
        ready, pending = raytpu.wait([fast, slow], num_returns=1, timeout=1.0)
        assert ready == [fast] and pending == [slow]

    def test_wait_timeout(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def never():
            time.sleep(60)

        ready, pending = raytpu.wait([never.remote()], timeout=0.1)
        assert not ready and len(pending) == 1


class TestActors:
    def test_counter(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        class Counter:
            def __init__(self, start=0):
                self.v = start

            def inc(self, by=1):
                self.v += by
                return self.v

        c = Counter.remote(10)
        assert raytpu.get(c.inc.remote()) == 11
        assert raytpu.get(c.inc.remote(5)) == 16

    def test_method_ordering(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        class Log:
            def __init__(self):
                self.items = []

            def append(self, x):
                self.items.append(x)

            def get(self):
                return self.items

        log = Log.remote()
        for i in range(20):
            log.append.remote(i)
        assert raytpu.get(log.get.remote()) == list(range(20))

    def test_actor_error_does_not_kill(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        class A:
            def bad(self):
                raise RuntimeError("x")

            def good(self):
                return "alive"

        a = A.remote()
        with pytest.raises(raytpu.TaskError):
            raytpu.get(a.bad.remote())
        assert raytpu.get(a.good.remote()) == "alive"

    def test_creation_error_propagates(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        class Broken:
            def __init__(self):
                raise ValueError("ctor failed")

            def m(self):
                return 1

        b = Broken.remote()
        with pytest.raises((raytpu.TaskError, raytpu.ActorDiedError)):
            raytpu.get(b.m.remote())

    def test_kill(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        class A:
            def m(self):
                return 1

        a = A.remote()
        assert raytpu.get(a.m.remote()) == 1
        raytpu.kill(a)
        time.sleep(0.2)
        with pytest.raises(raytpu.ActorDiedError):
            raytpu.get(a.m.remote())

    def test_named_actor(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        class Registry:
            def __init__(self):
                self.d = {}

            def set(self, k, v):
                self.d[k] = v

            def get(self, k):
                return self.d.get(k)

        Registry.options(name="reg", lifetime="detached").remote()
        h = raytpu.get_actor("reg")
        raytpu.get(h.set.remote("k", 42))
        assert raytpu.get(h.get.remote("k")) == 42

    def test_pass_handle_to_task(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        class Counter:
            def __init__(self):
                self.v = 0

            def inc(self):
                self.v += 1
                return self.v

        @raytpu.remote
        def bump(counter):
            import raytpu as r

            return r.get(counter.inc.remote())

        c = Counter.remote()
        raytpu.get(bump.remote(c))
        assert raytpu.get(bump.remote(c)) == 2

    def test_async_actor(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        class AsyncWorker:
            async def work(self, t):
                import asyncio

                await asyncio.sleep(t)
                return t

        a = AsyncWorker.remote()
        t0 = time.monotonic()
        refs = [a.work.remote(0.3) for _ in range(5)]
        assert raytpu.get(refs) == [0.3] * 5
        # Concurrent: 5 x 0.3s sleeps must overlap.
        assert time.monotonic() - t0 < 1.0

    def test_threaded_actor(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote(max_concurrency=4)
        class Sleeper:
            def nap(self, t):
                time.sleep(t)
                return t

        s = Sleeper.remote()
        t0 = time.monotonic()
        raytpu.get([s.nap.remote(0.3) for _ in range(4)])
        assert time.monotonic() - t0 < 1.0

    def test_concurrency_groups_isolated(self, raytpu_local):
        """Groups get their own executors: an `io`-group pair overlaps with
        itself and with the default group even at max_concurrency=1
        (reference: concurrency_group_manager.cc)."""
        raytpu = raytpu_local

        @raytpu.remote(concurrency_groups={"io": 2})
        class Worker:
            @raytpu.method(concurrency_group="io")
            def io(self, t):
                time.sleep(t)
                return "io"

            def compute(self, t):
                time.sleep(t)
                return "c"

        w = Worker.remote()
        t0 = time.monotonic()
        out = raytpu.get([w.io.remote(0.3), w.io.remote(0.3),
                          w.compute.remote(0.3)])
        assert out == ["io", "io", "c"]
        assert time.monotonic() - t0 < 0.9

    def test_concurrency_group_limit_enforced(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote(concurrency_groups={"one": 1})
        class Worker:
            @raytpu.method(concurrency_group="one")
            def slow(self, t):
                time.sleep(t)
                return t

        w = Worker.remote()
        t0 = time.monotonic()
        raytpu.get([w.slow.remote(0.25), w.slow.remote(0.25)])
        # Limit 1 serializes the group.
        assert time.monotonic() - t0 >= 0.45

    def test_undefined_concurrency_group_rejected(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        class Worker:
            @raytpu.method(concurrency_group="nope")
            def f(self):
                return 1

        with pytest.raises(ValueError, match="nope"):
            Worker.remote()

    def test_options_override_unknown_group_fails_call(self, raytpu_local):
        """Per-call .options(concurrency_group=...) bypasses class-level
        validation; the runtime must reject rather than silently routing
        to the default pool."""
        raytpu = raytpu_local

        @raytpu.remote(concurrency_groups={"io": 1})
        class Worker:
            def f(self):
                return 1

        w = Worker.remote()
        ok = w.f.options(concurrency_group="io").remote()
        assert raytpu.get(ok) == 1
        bad = w.f.options(concurrency_group="typo").remote()
        with pytest.raises(raytpu.ActorError, match="typo"):
            raytpu.get(bad)

    def test_async_actor_concurrency_groups(self, raytpu_local):
        import asyncio

        raytpu = raytpu_local

        @raytpu.remote(concurrency_groups={"solo": 1})
        class AsyncWorker:
            @raytpu.method(concurrency_group="solo")
            async def slow(self, t):
                await asyncio.sleep(t)
                return t

            async def fast(self):
                return "f"

        a = AsyncWorker.remote()
        t0 = time.monotonic()
        refs = [a.slow.remote(0.25), a.slow.remote(0.25), a.fast.remote()]
        assert raytpu.get(refs) == [0.25, 0.25, "f"]
        # solo group serializes; the default group is untouched.
        assert time.monotonic() - t0 >= 0.45


class TestPlacementGroups:
    def test_basic_pg(self, raytpu_local):
        raytpu = raytpu_local
        pg = raytpu.placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
        assert raytpu.get(pg.ready())
        assert pg.bundle_count == 2
        avail = raytpu.available_resources()
        assert avail["CPU"] == 2.0  # 4 - 2 reserved
        raytpu.remove_placement_group(pg)
        assert raytpu.available_resources()["CPU"] == 4.0

    def test_task_in_pg(self, raytpu_local):
        raytpu = raytpu_local
        pg = raytpu.placement_group([{"CPU": 2}], strategy="PACK")

        @raytpu.remote(num_cpus=2)
        def f():
            return "in-bundle"

        ref = f.options(placement_group=pg,
                        placement_group_bundle_index=0).remote()
        assert raytpu.get(ref) == "in-bundle"

    def test_infeasible_pg_raises(self, raytpu_local):
        raytpu = raytpu_local
        with pytest.raises(Exception):
            raytpu.placement_group([{"CPU": 1000}])

    def test_tpu_pg_contiguous_chips(self, raytpu_local_tpu):
        raytpu = raytpu_local_tpu
        pg = raytpu.placement_group([{"TPU": 4}], strategy="STRICT_PACK")
        coords = pg.chip_coords(0)
        assert len(coords) == 4
        # 1-D fabric of 8 chips: contiguity = consecutive indices
        idxs = sorted(c[0] for c in coords)
        assert idxs == list(range(idxs[0], idxs[0] + 4))

    def test_scheduling_strategy_object(self, raytpu_local):
        raytpu = raytpu_local
        from raytpu.util.scheduling_strategies import (
            PlacementGroupSchedulingStrategy,
        )

        pg = raytpu.placement_group([{"CPU": 1}])

        @raytpu.remote(num_cpus=1)
        def f():
            return 1

        ref = f.options(scheduling_strategy=PlacementGroupSchedulingStrategy(
            placement_group=pg, placement_group_bundle_index=0)).remote()
        assert raytpu.get(ref) == 1


class TestUtil:
    def test_actor_pool(self, raytpu_local):
        raytpu = raytpu_local
        from raytpu.util import ActorPool

        @raytpu.remote
        class Doubler:
            def double(self, x):
                return x * 2

        pool = ActorPool([Doubler.remote() for _ in range(2)])
        out = list(pool.map(lambda a, v: a.double.remote(v), range(8)))
        assert sorted(out) == [0, 2, 4, 6, 8, 10, 12, 14]

    def test_queue(self, raytpu_local):
        raytpu = raytpu_local
        from raytpu.util import Queue

        q = Queue(maxsize=2)
        q.put("a")
        q.put("b")
        assert q.full()
        assert q.get() == "a"
        assert q.get() == "b"
        assert q.empty()

    def test_dag_bind_execute(self, raytpu_local):
        raytpu = raytpu_local
        from raytpu.dag import InputNode

        @raytpu.remote
        def double(x):
            return x * 2

        @raytpu.remote
        def add(a, b):
            return a + b

        with InputNode() as inp:
            dag = add.bind(double.bind(inp), inp)
        assert raytpu.get(dag.execute(5)) == 15


class TestIntrospection:
    def test_cluster_resources(self, raytpu_local):
        raytpu = raytpu_local
        assert raytpu.cluster_resources()["CPU"] == 4.0
        assert len(raytpu.nodes()) == 1

    def test_runtime_context_in_task(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def who():
            import raytpu as r

            ctx = r.get_runtime_context()
            return ctx.get_task_id() is not None

        assert raytpu.get(who.remote())

    def test_timeline(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def f():
            return 1

        raytpu.get([f.remote() for _ in range(3)])
        trace = raytpu.timeline()
        assert len(trace) >= 3
        assert all(ev["ph"] == "X" for ev in trace)


class TestRefCounting:
    """Regression tests for ownership-ledger bugs found in review."""

    def test_nested_ref_in_inline_arg_pinned(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def use_list(lst):
            import raytpu as r

            return r.get(lst[0])

        x = raytpu.put("payload")
        ref = use_list.remote([x])
        del x  # only the inline-arg containment keeps it alive
        assert raytpu.get(ref, timeout=10) == "payload"

    def test_deeply_nested_ref_in_put_pinned(self, raytpu_local):
        raytpu = raytpu_local
        inner = raytpu.put("deep")
        outer = raytpu.put([[[[inner]]]])
        del inner
        got = raytpu.get(outer)
        assert raytpu.get(got[0][0][0][0], timeout=10) == "deep"

    def test_fire_and_forget_returns_freed(self, raytpu_local):
        raytpu = raytpu_local

        @raytpu.remote
        def produce():
            return "x" * 1000

        for _ in range(10):
            produce.remote()  # discard refs immediately
        import time as _t

        _t.sleep(1.0)
        backend = raytpu.runtime.api._backend_or_none()
        # All return objects must have been freed from the store.
        assert backend.store.size() <= 2

    def test_async_actor_kill_fails_inflight(self, raytpu_local):
        raytpu = raytpu_local
        import time as _t

        @raytpu.remote
        class Slow:
            async def slow(self):
                import asyncio

                await asyncio.sleep(30)

        a = Slow.remote()
        ref = a.slow.remote()
        _t.sleep(0.3)  # let it get in flight
        raytpu.kill(a)
        with pytest.raises(raytpu.ActorDiedError):
            raytpu.get(ref, timeout=10)

    def test_dead_actor_submit_releases_arg_refs(self, raytpu_local):
        raytpu = raytpu_local
        import time as _t

        @raytpu.remote
        class A:
            def m(self, x):
                return x

        a = A.remote()
        raytpu.get(a.m.remote(1))
        raytpu.kill(a)
        _t.sleep(0.3)
        big = raytpu.put("pinned?")
        with pytest.raises(raytpu.ActorDiedError):
            raytpu.get(a.m.remote(big), timeout=10)
        worker = raytpu.runtime.api._global_worker_or_none()
        rec = worker.reference_counter.get(big.id)
        assert rec is not None and rec.submitted_task_ref_count == 0


def test_dropping_an_actor_handle_never_waits_for_the_backend_lock(
        raytpu_local):
    """``ActorHandle.__del__`` runs wherever the collector runs it, e.g.
    inside ``ObjectStore.put`` while another thread holds the backend's
    lock and waits for the store's (``wait_any_object_ready``): it must
    take no lock, or a serving run with many token streams hangs."""
    import threading

    from raytpu.runtime import api

    @raytpu_local.remote
    class Box:
        def get(self):
            return 1

    a = Box.remote()
    assert raytpu_local.get(a.get.remote()) == 1
    backend = api._backend_or_none()
    backend.actor_handle_added(a._actor_id)  # the handle about to go away
    held, release, done = (threading.Event() for _ in range(3))

    def hold():
        with backend._lock:
            held.set()
            release.wait(10)

    holder = threading.Thread(target=hold, daemon=True)
    holder.start()
    assert held.wait(5)
    threading.Thread(
        target=lambda: (backend.actor_handle_removed(a._actor_id),
                        done.set()), daemon=True).start()
    try:
        assert done.wait(2), "actor_handle_removed waited for the lock"
    finally:
        release.set()
        holder.join(5)
    assert raytpu_local.get(a.get.remote()) == 1
