"""Serve tests (reference analogue: python/ray/serve/tests/)."""

import asyncio
import threading
import time

import pytest

import raytpu
from raytpu import serve
from raytpu.serve._private.autoscaling_policy import (AutoscalingPolicyManager,
                                                      EnginePressure)
from raytpu.serve.config import AutoscalingConfig


@pytest.fixture
def serve_instance(raytpu_local):
    yield raytpu_local
    serve.shutdown()


@serve.deployment
class Doubler:
    def __call__(self, x):
        return 2 * x


@serve.deployment
class Adder:
    def __init__(self, increment):
        self.increment = increment

    def __call__(self, x):
        return x + self.increment

    def echo(self, x):
        return ("echo", x)


class TestServeBasics:
    def test_deploy_and_call(self, serve_instance):
        handle = serve.run(Doubler.bind(), name="app1", route_prefix=None)
        assert handle.remote(21).result() == 42

    def test_init_args_and_methods(self, serve_instance):
        handle = serve.run(Adder.bind(5), name="app2", route_prefix=None)
        assert handle.remote(10).result() == 15
        assert handle.echo.remote(3).result() == ("echo", 3)

    def test_function_deployment(self, serve_instance):
        @serve.deployment
        def square(x):
            return x * x

        handle = serve.run(square.bind(), name="fapp", route_prefix=None)
        assert handle.remote(9).result() == 81

    def test_multiple_replicas_spread_load(self, serve_instance):
        @serve.deployment(num_replicas=3)
        class WhoAmI:
            def __init__(self):
                self.me = id(self)

            def __call__(self, _):
                return self.me

        handle = serve.run(WhoAmI.bind(), name="mrep", route_prefix=None)
        seen = {handle.remote(i).result() for i in range(30)}
        assert len(seen) >= 2  # pow-2 routing uses more than one replica

    def test_status_and_delete(self, serve_instance):
        serve.run(Doubler.bind(), name="stapp", route_prefix=None)
        st = serve.status()
        assert st["stapp"]["deployments"]["Doubler"]["status"] == "RUNNING"
        serve.delete("stapp")
        assert "stapp" not in serve.status()

    def test_composition(self, serve_instance):
        @serve.deployment
        class Combiner:
            def __init__(self, doubler: serve.DeploymentHandle,
                         adder: serve.DeploymentHandle):
                self.doubler = doubler
                self.adder = adder

            def __call__(self, x):
                d = self.doubler.remote(x).result()
                return self.adder.remote(d).result()

        app = Combiner.bind(Doubler.bind(), Adder.bind(100))
        handle = serve.run(app, name="comp", route_prefix=None)
        assert handle.remote(7).result() == 114

    def test_reconfigure_user_config(self, serve_instance):
        @serve.deployment(user_config={"threshold": 1})
        class Configurable:
            def __init__(self):
                self.threshold = None

            def reconfigure(self, cfg):
                self.threshold = cfg["threshold"]

            def __call__(self, _):
                return self.threshold

        handle = serve.run(Configurable.bind(), name="cfg", route_prefix=None)
        assert handle.remote(0).result() == 1
        serve.run(Configurable.options(user_config={"threshold": 9}).bind(),
                  name="cfg", route_prefix=None)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if handle.remote(0).result() == 9:
                break
            time.sleep(0.1)
        assert handle.remote(0).result() == 9

    def test_get_deployment_handle(self, serve_instance):
        serve.run(Adder.bind(1), name="gdh", route_prefix=None)
        h = serve.get_deployment_handle("Adder", "gdh")
        assert h.remote(1).result() == 2


class TestReplicaReadiness:
    def test_a_slow_constructor_is_waited_for_not_killed(
            self, serve_instance, tmp_path):
        """A replica that loads a model and compiles for it starts in
        longer than any health-check deadline. It must get no traffic
        and no deadline until its constructor has finished; it used to
        count as RUNNING from birth and be replaced when its first
        health check, queued behind the constructor, timed out."""
        births = tmp_path / "births"

        @serve.deployment(health_check_period_s=0.1,
                          health_check_timeout_s=0.3)
        class SlowStart:
            def __init__(self):
                with open(births, "a") as f:
                    f.write("x")
                time.sleep(1.5)  # five health-check deadlines
                self.loaded = True

            def __call__(self, x):
                return self.loaded and x

        handle = serve.run(SlowStart.bind(), name="slow-start")
        # run() returned: the replica is constructed, once, and answers.
        assert births.read_text() == "x"
        assert handle.remote(7).result() == 7
        time.sleep(1.0)  # several more health-check periods
        assert births.read_text() == "x"


class TestAutoscalingPolicy:
    def test_scale_up_after_delay(self):
        cfg = AutoscalingConfig(min_replicas=1, max_replicas=10,
                                target_ongoing_requests=2.0,
                                upscale_delay_s=1.0, downscale_delay_s=2.0)
        mgr = AutoscalingPolicyManager(cfg)
        assert mgr.get_decision_num_replicas(20.0, 1, now=0.0) is None
        assert mgr.get_decision_num_replicas(20.0, 1, now=0.5) is None
        assert mgr.get_decision_num_replicas(20.0, 1, now=1.1) == 10

    def test_scale_down_hysteresis(self):
        cfg = AutoscalingConfig(min_replicas=1, max_replicas=10,
                                target_ongoing_requests=2.0,
                                upscale_delay_s=0.0, downscale_delay_s=5.0)
        mgr = AutoscalingPolicyManager(cfg)
        assert mgr.get_decision_num_replicas(0.0, 4, now=0.0) is None
        # Load returns before the delay elapses: decision cancelled.
        assert mgr.get_decision_num_replicas(8.0, 4, now=2.0) is None
        assert mgr.get_decision_num_replicas(0.0, 4, now=3.0) is None
        assert mgr.get_decision_num_replicas(0.0, 4, now=8.1) == 1

    def test_bounds_respected(self):
        cfg = AutoscalingConfig(min_replicas=2, max_replicas=4,
                                target_ongoing_requests=1.0,
                                upscale_delay_s=0.0, downscale_delay_s=0.0)
        mgr = AutoscalingPolicyManager(cfg)
        assert mgr.desired(100.0, 3) == 4
        assert mgr.desired(0.0, 3) == 2

    def test_e2e_autoscale_up(self, serve_instance):
        @serve.deployment(autoscaling_config=AutoscalingConfig(
            min_replicas=1, max_replicas=3, target_ongoing_requests=1.0,
            upscale_delay_s=0.1, downscale_delay_s=60.0))
        class Slow:
            def __call__(self, _):
                time.sleep(0.3)
                return "done"

        handle = serve.run(Slow.bind(), name="auto", route_prefix=None)
        results = []

        def fire():
            results.append(handle.remote(0).result())

        threads = [threading.Thread(target=fire) for _ in range(12)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 15
        scaled = False
        while time.monotonic() < deadline and not scaled:
            st = serve.status()
            if st["auto"]["deployments"]["Slow"]["running_replicas"] > 1:
                scaled = True
            time.sleep(0.1)
        for t in threads:
            t.join()
        assert scaled
        assert len(results) == 12


class TestEnginePressurePolicy:
    """Engine-pressure terms of the autoscaling policy: demand the
    router can't see (engine admission queues, KV occupancy, TTFT)."""

    def _mgr(self, **kw):
        cfg = AutoscalingConfig(
            min_replicas=1, max_replicas=10,
            target_ongoing_requests=100.0,  # request term stays inert
            target_engine_waiting=2.0, target_kv_utilization=0.8,
            upscale_delay_s=0.0, downscale_delay_s=0.0, **kw)
        return AutoscalingPolicyManager(cfg)

    def test_engine_waiting_drives_upscale(self):
        mgr = self._mgr()
        # One ongoing request reads as no load — but 8 requests queue
        # INSIDE the engines, invisible to request counting.
        assert mgr.desired(1.0, 1) == 1
        assert mgr.desired(1.0, 1, EnginePressure(waiting_requests=8.0)) == 4

    def test_kv_utilization_term_fires_only_above_target(self):
        mgr = self._mgr()
        assert mgr.desired(0.0, 2, EnginePressure(kv_utilization=0.5)) == 1
        # 96% page occupancy on 2 replicas: 2 * 0.96 / 0.8 -> 3.
        assert mgr.desired(0.0, 2, EnginePressure(kv_utilization=0.96)) == 3

    def test_ttft_term_disabled_unless_configured(self):
        assert self._mgr().desired(
            0.0, 2, EnginePressure(ttft_p95_s=30.0)) == 1
        mgr = self._mgr(target_ttft_s=0.5)
        assert mgr.desired(0.0, 2, EnginePressure(ttft_p95_s=2.0)) == 8

    def test_pressure_respects_hysteresis_windows(self):
        cfg = AutoscalingConfig(min_replicas=1, max_replicas=10,
                                target_ongoing_requests=100.0,
                                target_engine_waiting=1.0,
                                upscale_delay_s=1.0, downscale_delay_s=2.0)
        mgr = AutoscalingPolicyManager(cfg)
        deep = EnginePressure(waiting_requests=6.0)
        assert mgr.get_decision_num_replicas(
            0.0, 1, now=0.0, engine_pressure=deep) is None
        assert mgr.get_decision_num_replicas(
            0.0, 1, now=1.1, engine_pressure=deep) == 6
        # Drained engines shrink through the same (slower) window.
        assert mgr.get_decision_num_replicas(
            0.0, 6, now=2.0, engine_pressure=EnginePressure()) is None
        assert mgr.get_decision_num_replicas(
            0.0, 6, now=4.1, engine_pressure=EnginePressure()) == 1


class _ProbeRef:
    def __init__(self, qlen):
        self.qlen = qlen


class _ProbeMethod:
    def __init__(self, qlen):
        self.qlen = qlen

    def remote(self):
        return _ProbeRef(self.qlen)


class _FakeReplica:
    def __init__(self, qlen):
        self.get_queue_len = _ProbeMethod(qlen)


class _StubRaytpu:
    """raytpu.get stand-in: qlen=None simulates a probe that hangs
    until the router's PROBE_TIMEOUT_S budget expires."""

    @staticmethod
    def get(ref, timeout=None):
        if ref.qlen is None:
            raise TimeoutError("queue-len probe timed out")
        return ref.qlen


def _replica_set(replicas, max_ongoing=4):
    from raytpu.serve._private import router as router_mod

    rs = object.__new__(router_mod.ReplicaSet)
    rs._controller = None
    rs._full_name = "t#D"
    rs._max_ongoing = max_ongoing
    rs._lock = threading.Lock()
    rs._replicas = list(replicas)
    rs._version = 0
    rs._stopped = False
    rs._have_replicas = threading.Event()
    rs._have_replicas.set()
    return rs


class TestRouterProbeHardening:
    def test_timed_out_probe_never_wins_the_pick(self, monkeypatch):
        from raytpu.serve._private import router as router_mod

        monkeypatch.setattr(router_mod, "raytpu", _StubRaytpu)
        healthy = _FakeReplica(qlen=3)    # busy, but answering
        wedged = _FakeReplica(qlen=None)  # probe hangs
        rs = _replica_set([("r-ok", healthy), ("r-wedged", wedged)])
        # Power-of-two probes both every round; the wedged replica must
        # score WORST-queue (inf), so the busy-but-alive one wins every
        # pick — a hung replica that scored 0 would attract everything.
        for _ in range(10):
            assert rs.choose(timeout_s=5.0) is healthy

    def test_all_probes_failing_times_out_instead_of_guessing(
            self, monkeypatch):
        from raytpu.serve._private import router as router_mod

        monkeypatch.setattr(router_mod, "raytpu", _StubRaytpu)
        rs = _replica_set([("r-wedged", _FakeReplica(qlen=None))])
        # No healthy alternative: choose must keep backing off and
        # surface a timeout, never hand out the unprobeable replica.
        with pytest.raises(TimeoutError):
            rs.choose(timeout_s=0.3)


class TestRedeploy:
    def test_removed_deployment_is_dropped(self, serve_instance):
        app = Adder.bind(1)
        serve.run(app, name="rm", route_prefix=None)
        # Redeploy the app with a different deployment set.
        serve.run(Doubler.bind(), name="rm", route_prefix=None)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            deps = serve.status()["rm"]["deployments"]
            if "Adder" not in deps:
                break
            time.sleep(0.1)
        assert "Adder" not in serve.status()["rm"]["deployments"]

    def test_user_config_only_redeploy_keeps_replicas(self, serve_instance):
        @serve.deployment(user_config={"v": 1})
        class Stateful:
            def __init__(self):
                self.v = None
                self.created = time.monotonic()

            def reconfigure(self, cfg):
                self.v = cfg["v"]

            def __call__(self, _):
                return (self.v, self.created)

        handle = serve.run(Stateful.bind(), name="ucfg", route_prefix=None)
        v1, created1 = handle.remote(0).result()
        assert v1 == 1
        serve.run(Stateful.options(user_config={"v": 2}).bind(),
                  name="ucfg", route_prefix=None)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            v, created = handle.remote(0).result()
            if v == 2:
                break
            time.sleep(0.1)
        assert v == 2
        # Same replica instance (no restart): warm jit state preserved.
        assert created == created1


class TestScaleFromZero:
    def test_scale_from_zero(self, serve_instance):
        @serve.deployment(autoscaling_config=AutoscalingConfig(
            min_replicas=0, max_replicas=2, target_ongoing_requests=1.0,
            initial_replicas=0,
            # Nonzero delay: the demand signal must survive reconcile ticks
            # between the handle's ~1/s reports for hysteresis to elapse.
            upscale_delay_s=0.3, downscale_delay_s=60.0))
        class ColdStart:
            def __call__(self, x):
                return x + 1

        handle = serve.run(ColdStart.bind(), name="cold", route_prefix=None,
                           wait_for_ready_timeout_s=5.0)
        st = serve.status()
        assert st["cold"]["deployments"]["ColdStart"]["running_replicas"] == 0
        # First request triggers scale 0 -> 1 via handle demand report.
        assert handle.remote(41).result() == 42


class TestBatching:
    def test_batch_accumulates(self, serve_instance):
        @serve.deployment
        class Batched:
            def __init__(self):
                self.batch_sizes = []

            @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
            async def handle(self, items):
                self.batch_sizes.append(len(items))
                return [i * 10 for i in items]

            async def __call__(self, x):
                return await self.handle(x)

            def sizes(self):
                return self.batch_sizes

        handle = serve.run(Batched.bind(), name="batch", route_prefix=None)
        resps = [handle.remote(i) for i in range(8)]
        assert [r.result() for r in resps] == [i * 10 for i in range(8)]
        sizes = handle.sizes.remote().result()
        assert max(sizes) > 1  # batching actually happened

    def test_pad_batch_static_shape(self):
        """pad_batch_to_max keeps one batch shape for the jit program."""
        shapes = []

        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.05,
                     pad_batch_to_max=True)
        async def model(items):
            shapes.append(len(items))
            return [i + 1 for i in items]

        async def main():
            outs = await asyncio.gather(*[model(i) for i in range(6)])
            return outs

        outs = asyncio.new_event_loop().run_until_complete(main())
        assert outs == [i + 1 for i in range(6)]
        assert all(s == 4 for s in shapes)  # every flush saw the padded size

    def test_queue_registry_released_on_instance_gc(self):
        """Regression: the per-instance queue registry used to key by
        id(self) with a strong bound fn — entries (and the instances
        they captured) lived forever, and a recycled id() after GC
        could reuse a stale queue bound to a dead instance."""
        import gc

        class Holder:
            @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.01)
            async def handle(self, items):
                return [i + 1 for i in items]

        registry = Holder.handle._queues
        loop = asyncio.new_event_loop()
        try:
            h = Holder()
            assert loop.run_until_complete(h.handle(1)) == 2
            assert len(registry) == 1
            del h
            gc.collect()
            assert len(registry) == 0  # finalizer dropped the entry
            # A fresh instance gets a fresh queue and still works.
            h2 = Holder()
            assert loop.run_until_complete(h2.handle(5)) == 6
            assert len(registry) == 1
        finally:
            loop.close()

    def test_plain_function_batch_unaffected(self):
        @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.01)
        async def double(items):
            return [i * 2 for i in items]

        loop = asyncio.new_event_loop()
        try:
            assert loop.run_until_complete(double(3)) == 6
            assert len(double._queues) == 1  # the None (function) slot
        finally:
            loop.close()


class TestMultiplexSingleFlight:
    def test_concurrent_gets_share_one_load(self):
        """Regression: concurrent awaits for the same missing model must
        invoke the loader ONCE (single-flight), all returning its result."""
        from raytpu.serve.multiplex import _ModelCache

        calls = []

        async def loader(model_id):
            calls.append(model_id)
            await asyncio.sleep(0.05)  # wide race window
            return f"model:{model_id}"

        cache = _ModelCache(loader, capacity=2)

        async def main():
            return await asyncio.gather(*[cache.get("a") for _ in range(5)])

        outs = asyncio.new_event_loop().run_until_complete(main())
        assert outs == ["model:a"] * 5
        assert calls == ["a"]  # exactly one load
        assert not cache.pending  # no leaked in-flight entries

    def test_distinct_models_load_concurrently(self):
        from raytpu.serve.multiplex import _ModelCache

        in_flight = {"now": 0, "peak": 0}

        async def loader(model_id):
            in_flight["now"] += 1
            in_flight["peak"] = max(in_flight["peak"], in_flight["now"])
            await asyncio.sleep(0.05)
            in_flight["now"] -= 1
            return model_id

        cache = _ModelCache(loader, capacity=4)

        async def main():
            return await asyncio.gather(cache.get("a"), cache.get("b"))

        outs = asyncio.new_event_loop().run_until_complete(main())
        assert outs == ["a", "b"]
        assert in_flight["peak"] == 2  # not serialized by a global lock

    def test_failed_load_propagates_to_all_waiters_then_retries(self):
        from raytpu.serve.multiplex import _ModelCache

        calls = []

        async def loader(model_id):
            calls.append(model_id)
            await asyncio.sleep(0.02)
            if len(calls) == 1:
                raise RuntimeError("HBM OOM")
            return f"model:{model_id}"

        cache = _ModelCache(loader, capacity=2)

        async def main():
            results = await asyncio.gather(
                *[cache.get("a") for _ in range(3)], return_exceptions=True)
            retry = await cache.get("a")  # pending cleared -> clean retry
            return results, retry

        results, retry = asyncio.new_event_loop().run_until_complete(main())
        assert all(isinstance(r, RuntimeError) for r in results)
        assert retry == "model:a"
        assert calls == ["a", "a"]  # one shared failure + one retry

    def test_cache_registry_released_on_instance_gc(self):
        import gc

        class Holder:
            @serve.multiplexed(max_num_models_per_replica=2)
            async def get_model(self, model_id):
                return f"m:{model_id}"

        registry = Holder.get_model._caches
        loop = asyncio.new_event_loop()
        try:
            h = Holder()
            assert loop.run_until_complete(h.get_model("x")) == "m:x"
            assert len(registry) == 1
            del h
            gc.collect()
            assert len(registry) == 0
        finally:
            loop.close()


class TestMultiplex:
    def test_multiplexed_lru(self, serve_instance):
        @serve.deployment
        class MultiModel:
            def __init__(self):
                self.loads = []

            @serve.multiplexed(max_num_models_per_replica=2)
            async def get_model(self, model_id):
                self.loads.append(model_id)
                return f"model:{model_id}"

            async def __call__(self, _):
                mid = serve.get_multiplexed_model_id()
                model = await self.get_model(mid)
                return model

            def load_count(self):
                return self.loads

        handle = serve.run(MultiModel.bind(), name="mux", route_prefix=None)
        h_a = handle.options(multiplexed_model_id="a")
        h_b = handle.options(multiplexed_model_id="b")
        assert h_a.remote(0).result() == "model:a"
        assert h_b.remote(0).result() == "model:b"
        assert h_a.remote(0).result() == "model:a"  # cached
        loads = handle.load_count.remote().result()
        assert loads.count("a") == 1 and loads.count("b") == 1
        # Third model evicts LRU ("b" is fresher than "a"? "a" was re-read)
        h_c = handle.options(multiplexed_model_id="c")
        assert h_c.remote(0).result() == "model:c"
        assert h_b.remote(0).result() == "model:b"
        loads = handle.load_count.remote().result()
        assert loads.count("c") == 1 and loads.count("b") == 2


class TestHTTPProxy:
    def test_http_end_to_end(self, serve_instance):
        import requests as rq

        @serve.deployment
        class JsonEcho:
            def __call__(self, request: serve.Request):
                data = request.json()
                return {"path": request.path, "doubled": data["x"] * 2}

        serve.start(host="127.0.0.1", port=18432)
        serve.run(JsonEcho.bind(), name="http", route_prefix="/echo")
        r = rq.post("http://127.0.0.1:18432/echo", json={"x": 4}, timeout=10)
        assert r.status_code == 200
        assert r.json() == {"path": "/echo", "doubled": 8}
        r404 = rq.get("http://127.0.0.1:18432/nope", timeout=10)
        assert r404.status_code == 404
        rh = rq.get("http://127.0.0.1:18432/-/healthz", timeout=10)
        assert rh.text == "ok"

    def test_http_error_maps_to_500(self, serve_instance):
        import requests as rq

        @serve.deployment
        class Boom:
            def __call__(self, request):
                raise ValueError("kaboom")

        serve.start(host="127.0.0.1", port=18433)
        serve.run(Boom.bind(), name="boom", route_prefix="/boom")
        r = rq.get("http://127.0.0.1:18433/boom", timeout=10)
        assert r.status_code == 500
        assert "kaboom" in r.text


class TestReplicaFaultTolerance:
    def test_replica_replaced_after_death(self, serve_instance):
        @serve.deployment(num_replicas=1, health_check_period_s=0.2)
        class Fragile:
            def __call__(self, _):
                return "alive"

            def die(self):
                import os
                os._exit  # marker; real kill below via controller handle
                return None

        handle = serve.run(Fragile.bind(), name="ft", route_prefix=None)
        assert handle.remote(0).result() == "alive"
        # Kill the replica actor out from under the controller.
        controller = raytpu.get_actor("SERVE_CONTROLLER")
        reps = raytpu.get(
            controller.get_running_replicas.remote("ft#Fragile"))
        assert len(reps) == 1
        raytpu.kill(reps[0][1])
        deadline = time.monotonic() + 15
        ok = False
        while time.monotonic() < deadline:
            try:
                if handle.remote(0).result(timeout_s=2) == "alive":
                    reps2 = raytpu.get(
                        controller.get_running_replicas.remote("ft#Fragile"))
                    if reps2 and reps2[0][0] != reps[0][0]:
                        ok = True
                        break
            except Exception:
                pass
            time.sleep(0.2)
        assert ok, "controller did not replace the dead replica"


class TestASGIIngress:
    def test_asgi_app_serves_http(self, serve_instance):
        """A bare ASGI app (the protocol every Python web framework
        speaks) runs inside the replica and serves over the proxy."""
        import json as _json

        import requests as rq

        async def asgi_app(scope, receive, send):
            assert scope["type"] == "http"
            msg = await receive()
            body = msg.get("body", b"")
            payload = {
                "path": scope["path"],
                "method": scope["method"],
                "root_path": scope["root_path"],
                "query": scope["query_string"].decode(),
                "echo": body.decode() if body else None,
            }
            await send({
                "type": "http.response.start",
                "status": 201,
                "headers": [(b"content-type", b"application/json"),
                            (b"x-served-by", b"raytpu-asgi")],
            })
            await send({"type": "http.response.body",
                        "body": _json.dumps(payload).encode()})

        @serve.deployment
        @serve.ingress(asgi_app)
        class AsgiServer:
            pass

        serve.start(host="127.0.0.1", port=18441)
        serve.run(AsgiServer.bind(), name="asgi", route_prefix="/svc")
        r = rq.post("http://127.0.0.1:18441/svc/predict?k=v",
                    data="hi", timeout=15)
        assert r.status_code == 201
        assert r.headers["x-served-by"] == "raytpu-asgi"
        out = r.json()
        assert out["path"] == "/predict"
        assert out["root_path"] == "/svc"
        assert out["method"] == "POST"
        assert out["query"] == "k=v"
        assert out["echo"] == "hi"

        # Non-ASGI deployments on the same proxy still use the
        # Request-namedtuple contract.
        @serve.deployment
        class Plain:
            def __call__(self, request):
                return {"plain": True}

        serve.run(Plain.bind(), name="plain", route_prefix="/plain")
        r2 = rq.get("http://127.0.0.1:18441/plain", timeout=15)
        assert r2.json() == {"plain": True}


class TestGrpcIngress:
    """gRPC proxy (reference: Serve's gRPC ingress over serve.proto; ours
    is a generic byte service, no protoc plugin required)."""

    def test_grpc_unary_and_stream(self, serve_instance):
        import json as _json

        import grpc

        serve.start(host="127.0.0.1", port=18455, grpc_port=18456)

        @serve.deployment
        class Predictor:
            def __call__(self, request):
                payload = request.json()
                return {"doubled": payload["x"] * 2}

        @serve.deployment
        class Tokens:
            def __call__(self, request):
                for i in range(4):
                    yield f"tok{i}"

        serve.run(Predictor.bind(), name="pred", route_prefix="/predict")
        serve.run(Tokens.bind(), name="toks", route_prefix="/tokens")

        ch = grpc.insecure_channel("127.0.0.1:18456")
        call = ch.unary_unary("/raytpu.serve/Call")
        out = call(_json.dumps({"x": 21}).encode(),
                   metadata=(("route", "/predict"),), timeout=30)
        assert _json.loads(out) == {"doubled": 42}

        stream = ch.unary_stream("/raytpu.serve/Stream")
        chunks = [c for c in stream(b"", metadata=(("route", "/tokens"),),
                                    timeout=30)]
        assert chunks == [b"tok0", b"tok1", b"tok2", b"tok3"]

        # Unknown route -> NOT_FOUND, not a hang.
        with pytest.raises(grpc.RpcError) as err:
            call(b"{}", metadata=(("route", "/nope"),), timeout=10)
        assert err.value.code() == grpc.StatusCode.NOT_FOUND
        ch.close()
