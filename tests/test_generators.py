"""Streaming generator tests — ``num_returns="streaming"``.

Reference analogue: ``python/ray/tests/test_streaming_generator.py`` over
``ObjectRefGenerator`` (``_raylet.pyx:272``) and ObjectRefStream
backpressure (``task_manager.h:98``).
"""

import time

import pytest

import raytpu
from raytpu.runtime.generator import ObjectRefGenerator


@pytest.fixture
def fabric():
    raytpu.shutdown()
    raytpu.init(num_cpus=4)
    yield raytpu
    raytpu.shutdown()


class TestStreamingTasks:
    def test_basic_iteration(self, fabric):
        @raytpu.remote(num_returns="streaming")
        def gen(n):
            for i in range(n):
                yield i * 10

        g = gen.remote(5)
        assert isinstance(g, ObjectRefGenerator)
        vals = [raytpu.get(ref) for ref in g]
        assert vals == [0, 10, 20, 30, 40]

    def test_empty_stream(self, fabric):
        @raytpu.remote(num_returns="streaming")
        def gen():
            if False:
                yield 1

        assert [raytpu.get(r) for r in gen.remote()] == []

    def test_incremental_delivery(self, fabric):
        """Early elements are consumable while the producer still runs."""
        @raytpu.remote(num_returns="streaming")
        def slow_gen():
            yield "fast"
            time.sleep(5.0)
            yield "slow"

        g = slow_gen.remote()
        t0 = time.monotonic()
        first = raytpu.get(next(g))
        elapsed = time.monotonic() - t0
        assert first == "fast"
        assert elapsed < 3.0, "first element waited for the whole task"
        assert raytpu.get(next(g)) == "slow"

    def test_error_mid_stream(self, fabric):
        @raytpu.remote(num_returns="streaming")
        def bad_gen():
            yield 1
            yield 2
            raise ValueError("stream broke")

        g = bad_gen.remote()
        assert raytpu.get(next(g)) == 1
        assert raytpu.get(next(g)) == 2
        with pytest.raises(raytpu.RayTpuError, match="stream broke"):
            next(g)

    def test_backpressure_pauses_producer(self, fabric):
        """With generator_backpressure_num_objects=2 the producer cannot
        run ahead of the consumer by more than 2 elements."""
        @raytpu.remote(num_returns="streaming",
                       generator_backpressure_num_objects=2)
        def counted():
            import raytpu as r
            for i in range(10):
                r.put(("produced", i))  # observable side effect per element
                yield i

        g = counted.remote()
        time.sleep(1.0)  # producer should stall at the backpressure cap
        from raytpu.runtime import api

        # Count elements present in the store before any consumption.
        from raytpu.core.ids import ObjectID

        backend = api._backend
        present = sum(
            1 for i in range(1, 11)
            if backend.store.contains(
                ObjectID.for_task_return(g.task_id, i)))
        assert present <= 3, f"producer ran ahead: {present} elements"
        vals = [raytpu.get(r) for r in g]
        assert vals == list(range(10))

    def test_stream_refs_survive_until_consumed(self, fabric):
        """Unconsumed elements stay alive (producer buffer pins), consumed
        refs behave like normal ObjectRefs."""
        @raytpu.remote(num_returns="streaming")
        def gen():
            for i in range(3):
                yield {"i": i}

        g = gen.remote()
        time.sleep(0.5)  # let the producer finish before we consume
        refs = list(g)
        assert [raytpu.get(r)["i"] for r in refs] == [0, 1, 2]
        # Refs re-read fine (values still pinned by our handles).
        assert raytpu.get(refs[0])["i"] == 0

    def test_next_ready_timeout(self, fabric):
        @raytpu.remote(num_returns="streaming")
        def slow():
            time.sleep(10)
            yield 1

        g = slow.remote()
        with pytest.raises(raytpu.GetTimeoutError):
            g.next_ready(timeout=0.3)


class TestStreamingActors:
    def test_actor_method_stream(self, fabric):
        @raytpu.remote
        class Tokenizer:
            def stream(self, text):
                for tok in text.split():
                    yield tok

        a = Tokenizer.remote()
        g = a.stream.options(num_returns="streaming").remote("a b c")
        assert [raytpu.get(r) for r in g] == ["a", "b", "c"]

    def test_method_decorator_streaming(self, fabric):
        @raytpu.remote
        class Gen:
            @raytpu.method(num_returns="streaming")
            def nums(self, n):
                for i in range(n):
                    yield i

        a = Gen.remote()
        assert [raytpu.get(r) for r in a.nums.remote(4)] == [0, 1, 2, 3]


class TestStreamingCluster:
    def test_cluster_stream_crosses_nodes(self):
        from raytpu.cluster import Cluster

        c = Cluster(num_nodes=1, node_resources={"num_cpus": 2})
        c.wait_for_nodes(1)
        raytpu.shutdown()
        raytpu.init(address=f"tcp://{c.address}")
        try:
            @raytpu.remote(num_returns="streaming")
            def gen(n):
                for i in range(n):
                    yield i * i

            g = gen.remote(6)
            vals = [raytpu.get(ref, timeout=60) for ref in g]
            assert vals == [0, 1, 4, 9, 16, 25]
        finally:
            raytpu.shutdown()
            c.shutdown()

    def test_cluster_stream_incremental(self):
        from raytpu.cluster import Cluster

        c = Cluster(num_nodes=1, node_resources={"num_cpus": 2})
        c.wait_for_nodes(1)
        raytpu.shutdown()
        raytpu.init(address=f"tcp://{c.address}")
        try:
            @raytpu.remote(num_returns="streaming")
            def slow_gen():
                yield "first"
                time.sleep(8.0)
                yield "last"

            g = slow_gen.remote()
            t0 = time.monotonic()
            assert raytpu.get(next(g), timeout=30) == "first"
            assert time.monotonic() - t0 < 6.0, \
                "first element waited for task completion"
            assert raytpu.get(next(g), timeout=30) == "last"
        finally:
            raytpu.shutdown()
            c.shutdown()


class TestStreamingConsumers:
    def test_dataset_from_generator(self, fabric):
        """A streaming task feeds iter_batches while still producing."""
        import numpy as np

        from raytpu import data as rdata

        @raytpu.remote(num_returns="streaming")
        def produce_blocks():
            for i in range(4):
                yield {"x": np.full(8, i, dtype=np.int64)}

        ds = rdata.from_generator(produce_blocks.remote())
        batches = list(ds.iter_batches(batch_size=8))
        assert len(batches) == 4
        assert [int(b["x"][0]) for b in batches] == [0, 1, 2, 3]

    def test_dataset_from_generator_with_transform(self, fabric):
        import numpy as np

        from raytpu import data as rdata

        @raytpu.remote(num_returns="streaming")
        def produce():
            for i in range(3):
                yield {"x": np.arange(4, dtype=np.int64) + 10 * i}

        ds = rdata.from_generator(produce.remote()).map_batches(
            lambda b: {"x": b["x"] * 2})
        total = sum(int(b["x"].sum()) for b in ds.iter_batches(batch_size=4))
        expected = 2 * sum(sum(range(4)) + 4 * 10 * i for i in range(3))
        assert total == expected


class TestServeStreaming:
    def test_handle_remote_streaming(self):
        import raytpu.serve as serve

        raytpu.shutdown()
        raytpu.init(num_cpus=4)
        try:
            @serve.deployment
            class Tokens:
                def __call__(self, prompt):
                    for tok in f"echo {prompt}".split():
                        yield tok + " "

            handle = serve.run(Tokens.bind(), name="stream-app",
                               route_prefix=None)
            chunks = list(handle.remote_streaming("hello"))
            assert "".join(chunks) == "echo hello "
        finally:
            import raytpu.serve as serve2

            serve2.shutdown()
            raytpu.shutdown()

    def test_http_sse_streams_incrementally(self):
        """SSE endpoint delivers early tokens before the handler finishes
        — the LM token-streaming story."""
        import requests as rq

        import raytpu.serve as serve

        raytpu.shutdown()
        raytpu.init(num_cpus=4)
        try:
            @serve.deployment
            class SlowTokens:
                def __call__(self, request):
                    yield "tok0"
                    time.sleep(4.0)
                    yield "tok1"

            serve.start(host="127.0.0.1", port=18439)
            serve.run(SlowTokens.bind(), name="sse", route_prefix="/gen")
            t0 = time.monotonic()
            first_at = None
            events = []
            with rq.get("http://127.0.0.1:18439/gen",
                        headers={"Accept": "text/event-stream"},
                        stream=True, timeout=30) as r:
                assert r.status_code == 200
                assert r.headers["Content-Type"].startswith(
                    "text/event-stream")
                for line in r.iter_lines():
                    if not line:
                        continue
                    text = line.decode()
                    if text.startswith("data: "):
                        events.append(text[len("data: "):])
                        if first_at is None:
                            first_at = time.monotonic() - t0
            assert events == ["tok0", "tok1", "[DONE]"]
            assert first_at is not None and first_at < 3.0, \
                f"first token took {first_at}s - not streamed"
        finally:
            import raytpu.serve as serve2

            serve2.shutdown()
            raytpu.shutdown()


class TestEventDrivenDelivery:
    """VERDICT r3 weak #5: consumption is notification-driven, not a poll
    loop — a stored element wakes the waiting consumer immediately."""

    def test_wait_any_object_ready_wakes_on_put(self, fabric):
        """The local-backend wait primitive returns promptly after the
        put, not after a poll-backoff interval."""
        import threading

        import numpy as np

        from raytpu.runtime import api
        from raytpu.runtime.object_ref import ObjectRef
        from raytpu.runtime.serialization import serialize
        from raytpu.core.ids import ObjectID, TaskID

        _, backend = api._worker_and_backend()
        oid = ObjectID.for_task_return(TaskID.from_random(), 1)
        put_at = {}

        def producer():
            time.sleep(0.15)
            put_at["t"] = time.monotonic()
            backend.store.put(oid, serialize(np.arange(4)))

        t = threading.Thread(target=producer)
        t.start()
        ok = backend.wait_any_object_ready(
            [ObjectRef(oid, _skip_refcount=True)], timeout=5.0)
        woke = time.monotonic()
        t.join()
        assert ok is True
        lat = woke - put_at["t"]
        assert lat < 0.05, f"wakeup took {lat * 1e3:.1f}ms - not event-driven"

    def test_a_put_wakes_the_threads_that_watch_it_and_no_other(
            self, fabric):
        """A waiter has an event of its own, which only a put of one of
        its objects sets: with one condition for all of them, 64 token
        streams woke 64 consumers for every token (PERF.md, PR 52)."""
        import threading

        import numpy as np

        from raytpu.runtime import api
        from raytpu.runtime.object_ref import ObjectRef
        from raytpu.runtime.serialization import serialize
        from raytpu.core.ids import ObjectID, TaskID

        _, backend = api._worker_and_backend()
        oids = [ObjectID.for_task_return(TaskID.from_random(), 1)
                for _ in range(3)]
        watched = [[oids[0]], [oids[0], oids[1]], [oids[2]]]
        woke = [None] * len(watched)

        def waiter(i):
            woke[i] = backend.wait_any_object_ready(
                [ObjectRef(o, _skip_refcount=True) for o in watched[i]],
                timeout=30.0)

        threads = [threading.Thread(target=waiter, args=(i,))
                   for i in range(len(watched))]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 5.0
        while sum(len(v) for v in backend._obj_watch.values()) < 4:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        events = {id(e) for v in backend._obj_watch.values() for e in v}
        assert len(events) == 3  # one a waiter, under each object it watches
        backend.store.put(oids[0], serialize(np.arange(4)))
        threads[0].join(5.0)
        threads[1].join(5.0)
        assert woke[:2] == [True, True]
        time.sleep(0.05)
        assert threads[2].is_alive() and woke[2] is None
        # Who has left watches nothing; who waits is still watched.
        assert list(backend._obj_watch) == [oids[2]]
        backend.store.put(oids[2], serialize(np.arange(4)))
        threads[2].join(5.0)
        assert woke[2] is True and not backend._obj_watch
        # An object that is there is not waited for, one that never
        # comes is waited for no longer than asked.
        assert backend.wait_any_object_ready(
            [ObjectRef(oids[2], _skip_refcount=True)], timeout=0.0) is True
        t0 = time.monotonic()
        assert backend.wait_any_object_ready(
            [ObjectRef(oids[1], _skip_refcount=True)], timeout=0.1) is False
        assert 0.09 <= time.monotonic() - t0 < 1.0 and not backend._obj_watch

    def test_stream_consume_latency(self, fabric):
        """Per-token delivery latency (yield -> consumer wakeup) stays in
        event-driven territory while the producer paces tokens out."""

        @raytpu.remote(num_returns="streaming")
        def tokens(n, gap):
            for _ in range(n):
                time.sleep(gap)
                yield time.monotonic()

        lats = []
        for ref in tokens.remote(8, 0.05):
            yielded_at = raytpu.get(ref)
            # consume timestamp minus produce timestamp includes store
            # write + wakeup + ref fetch
            lats.append(time.monotonic() - yielded_at)
        lats.sort()
        median = lats[len(lats) // 2]
        assert median < 0.04, \
            f"median token latency {median * 1e3:.1f}ms (lats={lats})"


class TestEventDrivenCluster:
    def test_cluster_wait_engages_head_push(self):
        """Driver-side wait_any_object_ready resolves via the head's
        object:: push (True), not the poll fallback (None)."""
        from raytpu.cluster import Cluster
        from raytpu.runtime import api
        from raytpu.runtime.object_ref import ObjectRef

        c = Cluster(num_nodes=1, node_resources={"num_cpus": 2})
        c.wait_for_nodes(1)
        raytpu.shutdown()
        raytpu.init(address=f"tcp://{c.address}")
        try:
            @raytpu.remote
            def late():
                time.sleep(0.5)
                return time.monotonic()

            ref = late.remote()
            _, backend = api._worker_and_backend()
            woke = backend.wait_any_object_ready(
                [ObjectRef(ref.id, _skip_refcount=True)], timeout=30.0)
            wake_at = time.monotonic()
            assert woke is True  # push path, not fallback
            produced_at = raytpu.get(ref, timeout=30)
            lat = wake_at - produced_at
            assert lat < 0.5, f"wakeup {lat * 1e3:.0f}ms after produce"
        finally:
            raytpu.shutdown()
            c.shutdown()


# -- async actors: an element is stored on the loop where that cannot block ---


def _stream_puts():
    """(on the loop, through the executor) so far, of this process's
    worker: where an async actor's stream elements were stored."""
    from raytpu.runtime import api

    worker, _ = api._worker_and_backend()
    return worker.stream_puts_inline, worker.stream_puts_executor


class TestAsyncActorStreamStore:
    """``Worker._run_stream_async``: a small element (and the stream's
    end) is serialized and put into the in-process store on the actor's
    event loop; what may block — a value over the store's inline limit,
    a worker that forwards each element to its node — keeps the
    executor (ISSUE 44)."""

    @staticmethod
    def _actor():
        @raytpu.remote
        class Source:
            def __init__(self):
                self.made = 0

            async def nums(self, n):
                for i in range(n):
                    self.made += 1
                    yield i

            async def blobs(self, sizes):
                for size in sizes:
                    yield b"x" * size

            def sync_nums(self, n):
                for i in range(n):
                    yield i

            async def made_so_far(self):
                return self.made

        return Source.remote()

    @pytest.mark.parametrize("method,n", [("nums", 1), ("nums", 40),
                                          ("sync_nums", 12)])
    def test_small_values_never_enter_an_executor_to_be_stored(
            self, fabric, method, n):
        a = self._actor()
        raytpu.get(a.made_so_far.remote())  # the actor is up
        inline0, pool0 = _stream_puts()
        g = getattr(a, method).options(num_returns="streaming").remote(n)
        assert [raytpu.get(r) for r in g] == list(range(n))  # in order
        with pytest.raises(StopIteration):  # the StreamEnd was read
            next(g)
        inline1, pool1 = _stream_puts()
        assert pool1 - pool0 == 0
        assert inline1 - inline0 == n + 1  # every element and the end

    def test_backpressure_holds_on_the_loop(self, fabric):
        a = self._actor()
        raytpu.get(a.made_so_far.remote())
        _, pool0 = _stream_puts()
        g = a.nums.options(num_returns="streaming",
                           generator_backpressure_num_objects=2).remote(10)
        time.sleep(0.6)  # the producer stalls at the cap
        assert raytpu.get(a.made_so_far.remote()) <= 3
        assert [raytpu.get(r) for r in g] == list(range(10))
        assert _stream_puts()[1] == pool0

    def test_stream_close_stops_the_producer_and_drops_its_pins(
            self, fabric):
        from raytpu.core.ids import ObjectID
        from raytpu.runtime import api

        a = self._actor()
        g = a.nums.options(num_returns="streaming",
                           generator_backpressure_num_objects=4
                           ).remote(10_000)
        taken = [raytpu.get(next(g)) for _ in range(3)]
        assert taken == [0, 1, 2]
        g.close()
        time.sleep(0.3)
        made = raytpu.get(a.made_so_far.remote())
        assert made < 20, "the producer ran on after stream_close"
        time.sleep(0.3)
        assert raytpu.get(a.made_so_far.remote()) == made
        store = api._backend.store
        deadline = time.monotonic() + 5
        left = None
        while time.monotonic() < deadline:
            left = [i for i in range(4, made + 1) if store.contains(
                ObjectID.for_task_return(g.task_id, i))]
            if not left:
                break
            time.sleep(0.05)
        assert not left, f"elements never taken stay pinned: {left}"

    def test_a_value_over_the_inline_limit_goes_through_the_executor(
            self, fabric):
        from raytpu.core.config import cfg

        big = cfg.max_direct_call_object_size + 1024
        a = self._actor()
        raytpu.get(a.made_so_far.remote())
        inline0, pool0 = _stream_puts()
        g = a.blobs.options(num_returns="streaming").remote([8, big, 8])
        assert [len(raytpu.get(r)) for r in g] == [8, big, 8]
        inline1, pool1 = _stream_puts()
        assert pool1 - pool0 == 1       # the large one alone
        assert inline1 - inline0 == 3   # two small ones and the end

    def test_a_worker_that_forwards_elements_keeps_the_executor(
            self, fabric):
        """A cluster worker ships each element to its node daemon
        (``on_stream_element``, an RPC): never on the loop."""
        import threading

        from raytpu.runtime import api

        worker, _ = api._worker_and_backend()
        a = self._actor()
        raytpu.get(a.made_so_far.remote())
        forwarded = []
        worker.on_stream_element = lambda oid: forwarded.append(
            (oid, threading.current_thread().name))
        try:
            inline0, pool0 = _stream_puts()
            g = a.nums.options(num_returns="streaming").remote(5)
            assert [raytpu.get(r) for r in g] == list(range(5))
            inline1, pool1 = _stream_puts()
        finally:
            worker.on_stream_element = None
        assert inline1 - inline0 == 0
        assert pool1 - pool0 == 6
        assert len(forwarded) == 6  # five elements and the end
        assert all(name.startswith("asyncio_") for _, name in forwarded), \
            forwarded
