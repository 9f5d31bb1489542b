"""End-to-end LLM serving tests: tiny Llama behind ``LLMDeployment``,
tokens streaming through assign_request_streaming/ObjectRefGenerator
while the sequence still decodes, staggered requests provably sharing
decode iterations, and client-side cancellation freeing KV pages."""

import dataclasses
import threading
import time

import jax.numpy as jnp
import pytest

import raytpu
from raytpu import serve
from raytpu.models.llama import Llama, LlamaConfig, init_params
from raytpu.serve.config import AutoscalingConfig

LCFG = dataclasses.replace(LlamaConfig.tiny(), dtype=jnp.float32,
                           attn_impl="reference", remat=False)
ENGINE_OPTIONS = {"page_size": 8, "max_num_seqs": 4, "max_model_len": 64}


@pytest.fixture
def serve_instance(raytpu_local):
    yield raytpu_local
    serve.shutdown()


@pytest.fixture(scope="module")
def reference():
    """Greedy reference decode over the SAME weights the replica builds
    (init is deterministic in the seed)."""
    model = Llama(LCFG)
    params = init_params(model, LCFG, seed=0, batch=1)

    def decode(prompt, n_new):
        toks = list(prompt)
        outs = []
        for _ in range(n_new):
            logits = model.apply({"params": params}, jnp.asarray([toks]))
            tok = int(jnp.argmax(logits[0, len(toks) - 1]))
            toks.append(tok)
            outs.append(tok)
        return outs

    return decode


def _deploy(name):
    app = serve.LLMDeployment.bind(model="llama", engine_options=ENGINE_OPTIONS,
                                   seed=0)
    return serve.run(app, name=name, route_prefix=None)


class TestLLMServeE2E:
    def test_staggered_streams_share_decode_and_match_reference(
            self, serve_instance, reference):
        """The acceptance test: two staggered requests with different
        prompt/output lengths stream correct greedy tokens, share decode
        iterations, and the decode step compiled once per bucket."""
        handle = _deploy("llm-e2e")
        pa, pb = list(range(1, 12)), [7, 3, 9]
        arrivals = {}
        results = {}

        def consume(tag, prompt, n):
            toks = []
            for tok in handle.generate.remote_streaming(
                    prompt, max_new_tokens=n):
                toks.append(tok)
                arrivals.setdefault(tag, []).append(time.monotonic())
            results[tag] = toks

        # a's output is long enough that it is still decoding (on the
        # replica's background stepping loop) when b's request crosses
        # the wire — the overlap the sharing assertions below need.
        ta = threading.Thread(target=consume, args=("a", pa, 48))
        ta.start()
        # Stagger: b arrives after a already started decoding, so its
        # prefill must merge with a's in-flight decode (Orca-style).
        while "a" not in arrivals:
            time.sleep(0.05)
        tb = threading.Thread(target=consume, args=("b", pb, 5))
        tb.start()
        ta.join(timeout=180)
        tb.join(timeout=180)
        assert not ta.is_alive() and not tb.is_alive()

        # Streamed greedy tokens match the non-batched reference decode.
        assert results["a"] == reference(pa, 48)
        assert results["b"] == reference(pb, 5)
        # Tokens streamed incrementally (arrived over time, not at once).
        spread_a = arrivals["a"][-1] - arrivals["a"][0]
        assert spread_a > 0

        stats = handle.stats.remote().result()
        # Provably shared decode iterations: some step decoded batch 2...
        assert max(stats["decode_batch_hist"]) >= 2
        # ...and batch composition changed (solo steps happened too),
        assert 1 in stats["decode_batch_hist"]
        # yet each decode bucket compiled exactly once.
        assert stats["decode_compiles"]
        assert all(n == 1 for n in stats["decode_compiles"].values())
        assert all(n == 1 for n in stats["prefill_compiles"].values())
        # Both sequences retired: all KV pages back in the pool.
        assert stats["running"] == 0 and stats["waiting"] == 0
        assert stats["kv_utilization"] == 0.0

    def test_tokens_arrive_before_sequence_finishes(self, serve_instance,
                                                    reference):
        handle = _deploy("llm-early")
        gen = handle.generate.remote_streaming(list(range(1, 9)),
                                               max_new_tokens=10)
        first = next(gen)
        # First token in hand while the replica still decodes the rest.
        stats = handle.stats.remote().result()
        assert stats["running"] + stats["waiting"] >= 1
        rest = list(gen)
        assert [first] + rest == reference(list(range(1, 9)), 10)

    def test_client_cancellation_frees_kv_pages(self, serve_instance):
        handle = _deploy("llm-cancel")
        gen = handle.generate.remote_streaming(list(range(1, 9)),
                                               max_new_tokens=40)
        got = [next(gen), next(gen), next(gen)]
        assert len(got) == 3
        gen.close()
        # close() propagates: consumer -> stream_close -> producer drain
        # stops -> replica pushes GeneratorExit into generate() -> its
        # finally aborts the request, freeing the sequence's pages.
        # Cleanup is eventually-prompt (GC-driven fallback), so poll.
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            stats = handle.stats.remote().result()
            if (stats["running"] == 0 and stats["waiting"] == 0
                    and stats["kv_utilization"] == 0.0):
                break
            time.sleep(0.25)
        assert stats["running"] == 0 and stats["waiting"] == 0
        assert stats["kv_utilization"] == 0.0
        # The aborted request decoded far fewer than max_new_tokens.
        assert stats["decode_tokens"] < 40

    def test_shared_system_prompt_prefills_shared_pages_once(
            self, serve_instance, reference):
        """THE prefix-cache acceptance count: three streams share a
        16-token system prompt (2 full pages at page_size 8); the
        shared pages prefill exactly once, every later stream pays only
        its tail — proven on raytpu_infer_prefill_tokens_total."""
        from raytpu.inference import engine as engine_mod
        from raytpu.inference import prefix_cache as pc_mod

        handle = _deploy("llm-prefix")
        system = list(range(1, 17))
        prompts = [system + tail for tail in
                   ([31, 32, 33], [41, 42, 43], [51, 52, 53])]

        before = engine_mod._prefill_tokens_total.value
        hits_before = pc_mod._hit_tokens_total.value
        # Stream 1 runs to completion first: its prefill registers the
        # system-prompt pages before the other streams are admitted.
        first = list(handle.generate.remote_streaming(prompts[0],
                                                      max_new_tokens=4))
        assert first == reference(prompts[0], 4)

        results = {}

        def consume(i):
            results[i] = list(handle.generate.remote_streaming(
                prompts[i], max_new_tokens=4))

        threads = [threading.Thread(target=consume, args=(i,))
                   for i in (1, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert results[1] == reference(prompts[1], 4)
        assert results[2] == reference(prompts[2], 4)
        # Stream 1 paid all 19 tokens; streams 2 and 3 grafted the two
        # shared pages and paid only their 3-token tails: 19 + 3 + 3.
        assert engine_mod._prefill_tokens_total.value - before == 25
        assert pc_mod._hit_tokens_total.value - hits_before == 32
        stats = handle.stats.remote().result()
        assert stats["prefix_cache"]["hits"] >= 2

    def test_infer_metrics_exported(self, serve_instance):
        from raytpu.inference import engine as engine_mod

        handle = _deploy("llm-metrics")
        out = list(handle.generate.remote_streaming([1, 2, 3],
                                                    max_new_tokens=4))
        assert len(out) == 4
        # Local-backend replicas share this process, so the module-level
        # raytpu_infer_* metrics observed the replica's engine loop.
        assert engine_mod._decode_tokens_total.value >= 3
        assert engine_mod._prefill_tokens_total.value >= 3


class TestReplicaSteppingLoop:
    """The replica-owned background stepping loop, proven on a directly
    instantiated replica callable (``LLMDeployment._target`` is the
    undecorated class) — no consumer thread ever steps the engine."""

    def test_tokens_decode_without_consumer_pulling(self, reference):
        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        try:
            gen = dep.generate(list(range(1, 9)), max_new_tokens=8)
            first = next(gen)
            # Nobody pulls from here on — the loop's daemon thread must
            # run the sequence to completion entirely on its own.
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                st = dep.stats()
                if st["running"] == 0 and st["waiting"] == 0:
                    break
                time.sleep(0.05)
            assert st["running"] == 0 and st["waiting"] == 0
            # The remaining tokens were buffered; draining is instant
            # and the stream is still byte-identical to the reference.
            rest = list(gen)
            assert [first] + rest == reference(list(range(1, 9)), 8)
        finally:
            dep.shutdown()

    def test_idle_loop_maintains_pressure_snapshot(self):
        from raytpu.inference import engine as engine_mod

        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        try:
            list(dep.generate([1, 2, 3], max_new_tokens=2))
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                p = dep.engine_pressure()
                # The loop publishes the idle snapshot and zeroes the
                # gauges on its first parked tick — poll for both.
                if (p["running_requests"] == 0.0
                        and p["kv_utilization"] == 0.0
                        and engine_mod._decode_tps_gauge.value == 0.0):
                    break
                time.sleep(0.05)
            assert p["running_requests"] == 0.0
            assert p["waiting_requests"] == 0.0
            assert p["kv_utilization"] == 0.0
            assert p["ttft_p95_s"] > 0.0  # recent-window history kept
            # Idle ticks also zero the throughput gauges, so scrapes
            # between bursts never read the last busy step as live.
            assert engine_mod._decode_tps_gauge.value == 0.0
            assert engine_mod._prefill_tps_gauge.value == 0.0
        finally:
            dep.shutdown()


class TestSteppingLoopStepLog:
    """The loop's own phases in the step's record, and its death made
    visible (ISSUE 24)."""

    def test_the_replica_names_what_stopped_its_interpreter(self):
        """ISSUE 57: a collection while the replica serves is in the
        step log's ``pauses``, on the steps' clock, and in the totals of
        ``stats()``; the ordinals reach the replica's records."""
        import gc

        from raytpu.inference import engine as engine_mod

        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        try:
            assert dep.step_log(pauses=True) == {"steps": [], "pauses": []}
            before = dep.stats()["host_pauses"]["gc"]["2"]["count"]
            published = engine_mod._gc_collections_total.value
            stream = dep.generate([1, 2, 3], max_new_tokens=6)
            first = next(iter(stream))
            gc.collect()
            tokens = [first] + list(stream)
            assert len(tokens) == 6
            deadline = time.monotonic() + 10
            while len(dep.step_log()) < 7 and time.monotonic() < deadline:
                time.sleep(0.01)
            log = dep.step_log(pauses=True)
            stats = dep.stats()
        finally:
            dep.shutdown()
        assert log["steps"] == dep.step_log()
        full = [p for p in log["pauses"] if p[3]["generation"] == 2]
        assert full and all(p[0] == "host.gc" for p in log["pauses"])
        assert all(p[2] > log["steps"][0]["start"] for p in log["pauses"])
        # This thread collected once, and it is not the one that steps.
        assert any(p[3]["stepping"] is False for p in full)
        totals = stats["host_pauses"]["gc"]["2"]
        assert totals["count"] >= before + 1 and totals["seconds"] > 0
        assert totals["longest_s"] >= max(p[2] - p[1] for p in full)
        # A step's end hands the collections to the metrics pipeline.
        assert engine_mod._gc_collections_total.value > published
        assert engine_mod._gc_pause_total.value > 0
        assert [(s["dispatched"], s["fetched"]) for s in log["steps"]] == [
            (0, 0), (1, 0), (2, 1), (3, 2), (4, 3), (5, 4), (0, 5)]
        assert all(0 <= s["cpu_s"] <= s["end"] - s["start"]
                   for s in log["steps"])

    def test_step_log_carries_the_loops_phases_and_takes_no_lock(self):
        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        try:
            assert dep.step_log() == []
            tokens = list(dep.generate([1, 2, 3], max_new_tokens=4))
            assert len(tokens) == 4
            deadline = time.monotonic() + 10
            while len(dep.step_log()) < 5 and time.monotonic() < deadline:
                time.sleep(0.01)
            # With the engine lock held by someone else, as a step
            # holds it for as long as a compile.
            got = []
            with dep._cv:
                reader = threading.Thread(
                    target=lambda: got.append(dep.step_log()))
                reader.start()
                reader.join(timeout=5)
                assert not reader.is_alive(), "step_log waited for the lock"
            (steps,) = got
        finally:
            dep.shutdown()
        # One prefill, three decodes, and the last one's fetch.
        assert len(steps) == 5
        assert dep.step_log(last=2) == steps[-2:]
        assert dep.step_log(last=0) == []
        for step in steps:
            names = [p[0] for p in step["phases"]]
            assert names[0] == "serve.llm.lock_wait"
            assert names[1] == "infer.schedule"
            wait = step["phases"][0]
            assert wait[1] <= wait[2] <= step["start"]
        assert [s["decodes"] for s in steps] == [0, 1, 1, 1, 0]
        assert [s["ahead"] for s in steps] == [0, 0, 1, 1, 0]
        # A step's tokens are those of the decode the step before
        # dispatched. They are published while the next step's decode
        # runs on the device, as that step's wait begins (the first
        # decode fetches none: the step after it has nothing to
        # publish); the last step's, which no launch follows, at once.
        published = [[p for p in s["phases"] if p[0] == "serve.llm.publish"]
                     for s in steps]
        assert [len(p) for p in published] == [0, 1, 0, 1, 2]
        for step, found in zip(steps[1:], published[1:]):
            if not found:
                continue
            at = {p[0]: p for p in step["phases"]}
            wait, publish = at["infer.decode.wait"], found[0]
            if "infer.decode.launch" in at:
                assert at["infer.decode.launch"][2] <= wait[1]
            assert wait[1] <= publish[1] and publish[2] <= wait[2]
        last = steps[-1]["phases"][-1]
        assert last[0] == "serve.llm.publish"
        assert steps[-1]["end"] <= last[1] <= last[2]
        for a, b in zip(steps, steps[1:]):
            # The gap between steps holds the next step's wait.
            assert a["end"] <= b["phases"][0][1]

    def test_a_stream_reads_its_tokens_without_the_engine_lock(self):
        """Tokens wait in the stream's own queue: a consumer that took
        the engine lock for each one queued behind a whole step, and
        the loop behind sixteen consumers."""
        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        try:
            gen = dep.generate([1, 2, 3], max_new_tokens=4)
            got = [next(gen)]
            deadline = time.monotonic() + 10
            while len(dep.step_log()) < 4 and time.monotonic() < deadline:
                time.sleep(0.01)
            with dep._cv:  # as a step holds it
                reader = threading.Thread(
                    target=lambda: got.extend(next(gen) for _ in range(3)))
                reader.start()
                reader.join(timeout=5)
                assert not reader.is_alive(), "a token waited for the lock"
            assert len(got) == 4
            assert list(gen) == []
        finally:
            dep.shutdown()

    def test_a_step_that_raises_ends_every_stream(self, monkeypatch):
        """An exception out of ``engine.step()`` used to kill the loop
        in silence and leave every stream waiting for ever (it cost
        PR 23 44 chip-minutes)."""
        from raytpu.inference.engine import InferenceEngine

        step = InferenceEngine.step
        calls = {"n": 0}

        def step_that_dies(self):
            calls["n"] += 1
            if calls["n"] > 3:
                calls["died"] = time.monotonic()
                with self.recorder.step("infer.step", {"decodes": 0}):
                    raise MemoryError("RESOURCE_EXHAUSTED: the program "
                                      "does not load")
            return step(self)

        monkeypatch.setattr(InferenceEngine, "step", step_that_dies)
        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        ended = {}

        def consume(tag, prompt):
            seen = []
            try:
                for tok in dep.generate(prompt, max_new_tokens=40):
                    seen.append(tok)
                ended[tag] = ("finished", seen, time.monotonic())
            except RuntimeError as e:
                ended[tag] = (e, seen, time.monotonic())

        threads = [threading.Thread(target=consume, args=(t, p))
                   for t, p in (("a", [1, 2, 3]), ("b", [4, 5, 6, 7]))]
        try:
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
            assert not any(th.is_alive() for th in threads), \
                "a stream is still waiting on a dead loop"
            died_at = dep.step_log()[-1]
            # The loop stopped; nothing waits, old or new.
            dep._step_thread.join(timeout=2)
            assert not dep._step_thread.is_alive()
            t0 = time.monotonic()
            with pytest.raises(RuntimeError, match="step loop died"):
                list(dep.generate([9, 9], max_new_tokens=2))
            assert time.monotonic() - t0 < 2.0
        finally:
            dep.shutdown()
        assert calls["n"] == 4
        for tag in ("a", "b"):
            err, seen, at = ended[tag]
            assert at - calls["died"] < 2.0
            assert isinstance(err, RuntimeError), ended[tag]
            assert "RESOURCE_EXHAUSTED" in str(err)
            assert isinstance(err.__cause__, MemoryError)
            assert 1 <= len(seen) < 40  # what was decoded was delivered
        assert "RESOURCE_EXHAUSTED" in died_at["error"]
        assert died_at["phases"][0][0] == "serve.llm.lock_wait"


class TestEnginePressureAutoscaling:
    def test_engine_queue_scales_replicas_up_then_down(self, serve_instance):
        """Admission-queue depth inside a max_num_seqs=1 engine —
        invisible to request counting (target_ongoing_requests is set
        absurdly high) — drives replica count up through the REAL
        controller/policy path, and the drained engines scale back."""
        app = serve.LLMDeployment.options(
            autoscaling_config=AutoscalingConfig(
                min_replicas=1, max_replicas=3,
                target_ongoing_requests=1000.0,  # request term inert
                target_engine_waiting=1.0,
                upscale_delay_s=0.1, downscale_delay_s=0.5),
        ).bind(model="llama",
               engine_options={"page_size": 8, "max_num_seqs": 1,
                               "max_model_len": 32},
               seed=0)
        handle = serve.run(app, name="llm-auto", route_prefix=None)
        stop = threading.Event()
        tokens = {}

        def fire(i):
            # Sustained load: keep streaming until the fleet has grown,
            # so the engine's admission queue stays deep for as many
            # reconcile ticks as the hysteresis window needs.
            tokens[i] = 0
            while not stop.is_set():
                out = list(handle.generate.remote_streaming(
                    [i + 1, i + 2, i + 3], max_new_tokens=24))
                assert len(out) == 24
                tokens[i] += len(out)

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        scaled_up = False
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not scaled_up:
            st = serve.status()
            reps = st["llm-auto"]["deployments"]["LLMDeployment"]
            scaled_up = reps["running_replicas"] > 1
            time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join(timeout=180)
        assert scaled_up
        assert all(tokens[i] > 0 for i in range(6))
        # Drained: every engine idle, pressure gone — the same policy
        # path (short downscale window) shrinks the fleet back to min.
        scaled_down = False
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and not scaled_down:
            st = serve.status()
            reps = st["llm-auto"]["deployments"]["LLMDeployment"]
            scaled_down = reps["running_replicas"] == 1
            time.sleep(0.25)
        assert scaled_down


class TestSteppingLoopHandsTheLockOver:
    def test_tokens_stream_and_requests_join_while_the_engine_is_busy(
            self, monkeypatch):
        """The stepping loop takes the engine lock again the instant it
        drops it. Consumers and new requests must still get their turn
        between steps: with a lock that lets the loop barge, no token
        reached a consumer and nobody was admitted until the engine ran
        dry."""
        from raytpu.inference.engine import InferenceEngine

        step = InferenceEngine.step

        def device_paced_step(self):
            out = step(self)
            time.sleep(0.02)  # a device step, the lock held throughout
            return out

        monkeypatch.setattr(InferenceEngine, "step", device_paced_step)
        dep = serve.LLMDeployment._target(
            model="llama", engine_options=ENGINE_OPTIONS, seed=0)
        arrivals = {"a": [], "b": []}

        def consume(tag, prompt, n):
            for _ in dep.generate(prompt, max_new_tokens=n):
                arrivals[tag].append(time.monotonic())

        try:
            list(dep.generate([5, 6], max_new_tokens=2))  # compile
            ta = threading.Thread(target=consume,
                                  args=("a", list(range(1, 12)), 40))
            ta.start()
            deadline = time.monotonic() + 60
            while not arrivals["a"] and time.monotonic() < deadline:
                time.sleep(0.005)
            tb = threading.Thread(target=consume, args=("b", [7, 3, 9], 5))
            tb.start()
            ta.join(timeout=120)
            tb.join(timeout=120)
            assert not ta.is_alive() and not tb.is_alive()
            hist = dep.stats()["decode_batch_hist"]
        finally:
            dep.shutdown()
        assert len(arrivals["a"]) == 40 and len(arrivals["b"]) == 5
        # a's tokens arrived as they were decoded (40 steps of >= 20 ms),
        # not in one burst at the end...
        assert arrivals["a"][-1] - arrivals["a"][0] > 0.4
        # ...and b, sent after a's first token, was admitted into a's
        # decode instead of waiting for a to finish.
        assert arrivals["b"][0] < arrivals["a"][-1]
        assert max(hist) >= 2


# -- a stream that can be awaited (ISSUE 44) ------------------------------------


def _consume_async(dep, requests, take=None):
    """Each of ``requests`` (``generate``'s arguments) admitted from a
    pool thread and consumed with ``async for`` on one event loop, as the
    serve replica does. Returns each stream's tokens or the exception
    that ended it; ``take`` tokens in, a stream is closed."""
    import asyncio

    async def one(args, kwargs):
        loop = asyncio.get_running_loop()
        stream = await loop.run_in_executor(
            None, lambda: dep.generate(*args, **kwargs))
        seen = []
        try:
            async for tok in stream:
                seen.append(tok)
                if take is not None and len(seen) == take:
                    await stream.aclose()
        except RuntimeError as e:
            return e, seen
        return None, seen

    async def main():
        return await asyncio.gather(*(one(a, k) for a, k in requests))

    return asyncio.run(main())


class TestAwaitedTokenStream:
    """``LLMDeployment.generate`` returns a stream for ``for`` and for
    ``async for``; consumed by a coroutine it waits on its loop, and a
    step's tokens reach all such streams through one call onto it."""

    @pytest.mark.parametrize("sampling", [
        {}, {"temperature": 0.9, "top_k": 8, "seed": 5}],
        ids=["greedy", "sampled"])
    def test_for_and_async_for_give_the_same_tokens(self, sampling):
        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        try:
            prompt = list(range(1, 9))
            plain = list(dep.generate(prompt, max_new_tokens=12, **sampling))
            (err, awaited), = _consume_async(
                dep, [((prompt,), dict(max_new_tokens=12, **sampling))])
            assert err is None and awaited == plain and len(plain) == 12
            stats = dep.stats()
            assert stats["published_tokens"] == 24
            # The thread's went through its queue, the coroutine's not.
            assert stats["published_queued"] == 12
            assert stats["publish_loop_calls"] == 12
            assert dep._streams == {}
        finally:
            dep.shutdown()

    def test_a_streams_last_token_comes_a_call_after_its_last_dispatch(
            self, reference):
        """The engine keeps one decode in flight: a stream's last token
        is fetched by the call after the one that dispatched its row,
        which may dispatch nothing. Every token arrives, in order, and
        the end is sent once, at once where no launch is left to wait
        for."""
        from raytpu.inference import serving

        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        try:
            ends, send = [], dep._send

            def counted(sends):
                ends.extend(stream for stream, items in sends
                            if items[-1] is serving._END)
                return send(sends)

            dep._send = counted
            prompts = [[3, 1, 4, 1, 5], [9, 2, 6, 5, 3, 5, 8]]
            new = (9, 4)
            requests = [((p,), dict(max_new_tokens=n))
                        for p, n in zip(prompts, new)]
            ended = _consume_async(dep, requests)
            for (err, seen), prompt, n in zip(ended, prompts, new):
                assert err is None and seen == reference(prompt, n)
            assert len(ends) == len(set(map(id, ends))) == 2
            assert dep._streams == {} and dep._held == []
            stats = dep.stats()
            assert stats["published_tokens"] == sum(new)
            assert stats["running"] == 0 and stats["decodes_ahead"] > 0
            assert not dep._engine.has_unfinished()
            steps = dep.step_log()
            # The last step dispatched no decode and fetched one: it
            # published the step before's tokens as its wait began, and
            # its own, the stream's last, as soon as it had returned.
            last = steps[-1]
            assert last["decodes"] == 0 and last["ahead"] == 0
            names = [p[0] for p in last["phases"]]
            assert "infer.decode.launch" not in names
            assert names.count("serve.llm.publish") == 2
            assert names[-1] == "serve.llm.publish"
            assert [p["tokens"] for p in last["publishes"]] == [1, 1]
            # The shorter stream ended while the other ran on: its end
            # went out in the wait of the step after its last fetch.
            assert sum(s["ahead"] for s in steps) >= sum(new) - 2 - 6
        finally:
            dep.shutdown()

    def test_a_stream_is_consumed_one_way(self):
        import asyncio

        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        try:
            stream = dep.generate([1, 2, 3], max_new_tokens=4)

            async def first():
                return await stream.__anext__()

            loop = asyncio.new_event_loop()
            try:
                assert isinstance(loop.run_until_complete(first()), int)
                with pytest.raises(RuntimeError, match="awaits this stream"):
                    next(stream)
                loop.run_until_complete(stream.aclose())
            finally:
                loop.close()
            assert dep.stats()["running"] == 0
        finally:
            dep.shutdown()

    def test_eight_streams_through_serve_are_the_engines_and_none_queues(
            self, serve_instance, reference):
        # Prompts of five chunks: a stream's first token is steps away
        # when the replica's loop first awaits it, so none is queued.
        app = serve.LLMDeployment.bind(
            model="llama", seed=0,
            engine_options=dict(ENGINE_OPTIONS, prefill_chunk=8))
        handle = serve.run(app, name="llm-awaited", route_prefix=None)
        prompts = [[(7 * i + j) % 200 + 1 for j in range(36 + i % 3)]
                   for i in range(8)]
        got = {}

        def consume(i):
            got[i] = list(handle.generate.remote_streaming(
                prompts[i], max_new_tokens=4))

        threads = [threading.Thread(target=consume, args=(i,))
                   for i in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=180)
        assert not any(th.is_alive() for th in threads)
        for i in range(8):
            assert got[i] == reference(prompts[i], 4), i
        stats = handle.stats.remote().result()
        assert stats["published_tokens"] == 32
        assert stats["published_queued"] == 0
        # One call onto the loop for each step that published, whatever
        # the number of streams it published to.
        steps = handle.step_log.remote().result()
        publishes = [p for s in steps for p in s.get("publishes", ())]
        assert len(steps) < 64  # the log holds them all
        assert sum(p["tokens"] for p in publishes) == 32
        assert all(p["loop_calls"] == 1 and p["queued"] == 0
                   for p in publishes)
        assert stats["publish_loop_calls"] == len(publishes) < 32
        assert max(p["tokens"] for p in publishes) > 1

    def test_a_client_that_leaves_frees_its_pages_at_once(self):
        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        try:
            (err, seen), = _consume_async(
                dep, [(([1, 2, 3, 4, 5],), dict(max_new_tokens=40))], take=2)
            assert err is None and len(seen) == 2
            # ``aclose`` returned: the abort has run, behind the step that
            # held the lock, and not at some later collection.
            stats = dep.stats()
            assert stats["running"] == 0 and stats["waiting"] == 0
            assert stats["kv_utilization"] == 0.0
            assert dep._streams == {}
            steps = len(dep.step_log())
            time.sleep(0.2)
            assert len(dep.step_log()) - steps <= 1  # nothing left to run
        finally:
            dep.shutdown()

    def test_a_step_that_raises_ends_every_awaited_stream(self, monkeypatch):
        from raytpu.inference.engine import InferenceEngine

        step = InferenceEngine.step
        calls = {"n": 0}

        def step_that_dies(self):
            calls["n"] += 1
            if calls["n"] > 3:
                with self.recorder.step("infer.step", {"decodes": 0}):
                    raise MemoryError("RESOURCE_EXHAUSTED: the program "
                                      "does not load")
            return step(self)

        monkeypatch.setattr(InferenceEngine, "step", step_that_dies)
        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        try:
            t0 = time.monotonic()
            ended = _consume_async(dep, [
                (([1, 2, 3],), dict(max_new_tokens=40)),
                (([4, 5, 6, 7],), dict(max_new_tokens=40))])
            assert time.monotonic() - t0 < 60
            for err, seen in ended:
                assert isinstance(err, RuntimeError), (err, seen)
                assert "RESOURCE_EXHAUSTED" in str(err)
                assert isinstance(err.__cause__, MemoryError)
                assert len(seen) < 40
            assert sum(len(seen) for _, seen in ended) >= 1
            with pytest.raises(RuntimeError, match="step loop died"):
                dep.generate([9, 9], max_new_tokens=2)
        finally:
            dep.shutdown()

    def test_shutdown_ends_an_awaited_stream(self):
        import asyncio

        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)

        async def main():
            loop = asyncio.get_running_loop()
            stream = await loop.run_in_executor(
                None, lambda: dep.generate([1, 2, 3], max_new_tokens=60))
            seen = [await stream.__anext__()]
            await loop.run_in_executor(None, dep.shutdown)
            async for tok in stream:
                seen.append(tok)
            return seen

        assert 1 <= len(asyncio.run(main())) < 60

    def test_a_verify_steps_two_tokens_arrive_in_order(self):
        """A model that drafts for itself yields a sequence one token or
        two a step: both go to the stream in one call, in order."""
        dep = serve.LLMDeployment._target(
            model="exaone_moe", engine_options=dict(
                page_size=4, max_num_seqs=4, max_model_len=96))
        try:
            requests = [(([5 + i, 6, 7, 8, 9],),
                         dict(max_new_tokens=24, temperature=1.0, seed=3 + i))
                        for i in range(3)]
            plain = [list(dep.generate(*a, **k)) for a, k in requests]
            awaited_from = time.perf_counter()
            ended = _consume_async(dep, requests)
            assert [seen for _, seen in ended] == plain
            assert all(err is None for err, _ in ended)
            assert all(len(p) == 24 for p in plain)
            # Some step gave a stream two tokens, and published them in
            # the one call of the step that followed.
            steps = [s for s in dep.step_log() if s["start"] > awaited_from]
            doubles = [i for i, s in enumerate(steps[:-1])
                       if s.get("emitted", 0) > s.get("decodes", 0) > 0]
            assert doubles, "no draft was kept: the test holds nothing"
            for i in doubles:
                publish = steps[i + 1]["publishes"][0]
                assert publish == {"tokens": steps[i]["emitted"],
                                   "loop_calls": 1, "queued": 0}
        finally:
            dep.shutdown()

    def test_no_pool_thread_runs_for_a_token(self, serve_instance,
                                             monkeypatch):
        """The mechanism's point, without a clock: sixteen streams for K
        decode steps submit to the replica's executor once a request
        (its admission) and to the loops' default executors what a
        request costs the actor, whatever K is. With ``next()`` on the
        executor and the element's store on the default one it was two
        submissions a token."""
        import collections
        from concurrent.futures import ThreadPoolExecutor

        submitted = collections.Counter()
        submit = ThreadPoolExecutor.submit

        def counting_submit(self, fn, *args, **kwargs):
            submitted[self._thread_name_prefix.split("-")[0]] += 1
            return submit(self, fn, *args, **kwargs)

        monkeypatch.setattr(ThreadPoolExecutor, "submit", counting_submit)
        app = serve.LLMDeployment.options(max_ongoing_requests=32).bind(
            model="llama", seed=0, engine_options=dict(
                ENGINE_OPTIONS, max_num_seqs=16))
        handle = serve.run(app, name="llm-no-pool", route_prefix=None)

        def run(new_tokens):
            before = collections.Counter(submitted)
            got = {}

            def consume(i):
                got[i] = list(handle.generate.remote_streaming(
                    [i + 1, 2, 3], max_new_tokens=new_tokens))

            threads = [threading.Thread(target=consume, args=(i,))
                       for i in range(16)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=180)
            assert all(len(got[i]) == new_tokens for i in range(16)), got
            return submitted - before

        run(4)  # programs compiled, executors made
        short, long = run(6), run(36)
        assert short["replica"] == long["replica"] == 16
        # 16 x 30 more tokens: the default executors saw the requests
        # (and what the controller's loops asked meanwhile), no token.
        assert abs(long["asyncio"] - short["asyncio"]) <= 16, (short, long)
        assert long["asyncio"] <= 4 * 16, (short, long)

    def test_a_thread_whose_wait_ran_out_keeps_what_a_step_then_published(
            self):
        """The deployment lets go of a stream when it sends its end; a
        consumer thread whose wait ran out meanwhile finds the request
        gone and must still read what is in its queue."""
        import queue

        dep = serve.LLMDeployment._target(engine_options=ENGINE_OPTIONS)
        try:
            stream = dep.generate([1, 2, 3], max_new_tokens=3)
            deadline = time.monotonic() + 60
            while dep._streams and time.monotonic() < deadline:
                time.sleep(0.01)
            assert dep._streams == {}  # all three published, and the end

            class RanOutOnce:
                def __init__(self, real):
                    self.real, self.ran_out = real, False

                def get(self, timeout=None):
                    if not self.ran_out:
                        self.ran_out = True
                        raise queue.Empty
                    return self.real.get(timeout=timeout)

                def put(self, item):
                    self.real.put(item)

            stream._queue = RanOutOnce(stream._queue)
            assert len(list(stream)) == 3
        finally:
            dep.shutdown()


class TestDeploymentOptionsFromTheConstructor:
    """``Deployment.bind`` asks the target what its constructor's
    arguments imply (``LLMDeployment.deployment_options``): a replica
    takes as many requests as its engine seats, and a caller that reaches
    the deployment through ``bind`` alone states the rest in
    ``engine_options["serve_options"]``."""

    @staticmethod
    def _config(dep=None, **engine_options):
        dep = serve.LLMDeployment if dep is None else dep
        return dep.bind(
            model="llama", engine_options=engine_options
        )._ingress.deployment.config

    @pytest.mark.parametrize("seats, cap", [
        (None, 100), (4, 100), (64, 100), (100, 100), (101, 101),
        (128, 128)])
    def test_a_replica_takes_as_many_requests_as_its_engine_seats(
            self, seats, cap):
        options = {} if seats is None else {"max_num_seqs": seats}
        cfg = self._config(**options)
        assert cfg.max_ongoing_requests == cap
        # Nothing else moved: the serve layer's own defaults.
        assert cfg.health_check_timeout_s == 30.0
        assert cfg.health_check_period_s == 2.0
        assert serve.LLMDeployment.config.max_ongoing_requests == 100

    def test_engine_options_as_the_third_positional_argument(self):
        app = serve.LLMDeployment.bind("llama", None, {"max_num_seqs": 128})
        assert app._ingress.deployment.config.max_ongoing_requests == 128

    def test_serve_options_are_the_deployments_and_not_the_engines(self):
        cfg = self._config(
            max_num_seqs=128,
            serve_options={"health_check_timeout_s": 600.0})
        assert cfg.health_check_timeout_s == 600.0
        assert cfg.max_ongoing_requests == 128
        cfg = self._config(
            max_num_seqs=128, serve_options={"max_ongoing_requests": 256})
        assert cfg.max_ongoing_requests == 256
        # The engine never sees the key.
        dep = serve.LLMDeployment._target(
            model="llama", engine_options=dict(
                ENGINE_OPTIONS,
                serve_options={"health_check_timeout_s": 600.0}))
        try:
            assert dep._engine.scheduler.max_num_seqs == 4
        finally:
            dep.shutdown()

    def test_what_options_set_is_kept(self):
        dep = serve.LLMDeployment.options(max_ongoing_requests=7,
                                          health_check_timeout_s=5.0)
        cfg = self._config(dep, max_num_seqs=128, serve_options={
            "health_check_timeout_s": 600.0})
        assert cfg.max_ongoing_requests == 7
        assert cfg.health_check_timeout_s == 5.0

    def test_a_target_without_the_method_binds_as_before(self):
        @serve.deployment(max_ongoing_requests=3)
        class Plain:
            def __init__(self, engine_options=None):
                pass

        node = Plain.bind(engine_options={"max_num_seqs": 128})._ingress
        assert node.deployment is Plain
        assert node.deployment.config.max_ongoing_requests == 3

    def test_an_unknown_serve_option_is_refused_at_bind(self):
        with pytest.raises((ValueError, AttributeError)):
            self._config(serve_options={"no_such_option": 1})

    def test_more_streams_than_the_serve_layers_cap_all_decode_at_once(
            self, serve_instance):
        """104 clients of an engine with 104 seats: under the serve
        layer's cap of 100 the replica's semaphore held four back until
        others finished (and, where requests last longer than the
        router's 30 s, timed them out)."""
        n = 104
        app = serve.LLMDeployment.bind(
            model="llama", seed=0, engine_options=dict(
                ENGINE_OPTIONS, max_num_seqs=n, decode_buckets=[n]))
        handle = serve.run(app, name="llm-seats", route_prefix=None)
        got = {}

        def consume(i):
            got[i] = list(handle.generate.remote_streaming(
                [i % 200 + 1, 7, 9], max_new_tokens=40))

        threads = [threading.Thread(target=consume, args=(i,))
                   for i in range(n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        assert not any(th.is_alive() for th in threads)
        assert all(len(got[i]) == 40 for i in range(n))
        hist = handle.stats.remote().result()["decode_batch_hist"]
        assert max(int(k) for k in hist) == n, hist


class _Burst:
    """An async stream of ``range(n)`` that has up to three more chunks
    ready after every one it gives."""

    def __init__(self, n):
        from collections import deque

        self.items = deque(range(n))

    def __aiter__(self):
        return self

    async def __anext__(self):
        if not self.items:
            raise StopAsyncIteration
        return self.items.popleft()

    def take_ready(self):
        return tuple(self.items.popleft()
                     for _ in range(min(3, len(self.items))))


class TestChunksTravelTogether:
    """A stream that has more than one chunk ready sends them in one
    object (``ChunkBatch``), and the consumer gets them one by one: an
    LLM replica whose loop lags its engine catches up (128 streams of a
    12 ms step: 4,100 of 10,500 tokens a second delivered before, 8,900
    after, here on the CPU with the engine faked)."""

    def test_take_ready_gives_what_was_delivered_and_leaves_the_end(self):
        import asyncio

        from raytpu.inference.serving import _END, TokenStream, _deliver

        class Dep:
            @staticmethod
            def _raise_if_dead():
                pass

        async def body():
            stream = TokenStream(Dep, "r")
            assert stream.take_ready() == ()
            stream._offer((1,), {})           # queued: no loop awaits yet
            assert await stream.__anext__() == 1
            by_loop = {}
            assert not stream._offer((2, 3), by_loop)
            assert not stream._offer((4, _END), by_loop)
            for batch in by_loop.values():
                _deliver(batch)
            assert await stream.__anext__() == 2
            assert stream.take_ready() == (3, 4)
            assert stream.take_ready() == ()
            with pytest.raises(StopAsyncIteration):
                await stream.__anext__()

        asyncio.run(body())

    def test_the_consumer_gets_a_batch_one_by_one(self):
        from raytpu.serve.handle import (ChunkBatch,
                                         DeploymentResponseGenerator)

        objects = [ChunkBatch((1, 2, 3)), 4, (5, 6), ChunkBatch((7,))]
        gen = DeploymentResponseGenerator(iter(objects))
        import raytpu.serve.handle as handle_mod

        real_get = handle_mod.raytpu.get
        handle_mod.raytpu.get = lambda ref: ref
        try:
            # A handler's own tuple is a chunk, not a batch.
            assert list(gen) == [1, 2, 3, 4, (5, 6), 7]
            assert gen._n == 6
        finally:
            handle_mod.raytpu.get = real_get

    def test_a_stream_with_chunks_ready_sends_fewer_objects(
            self, serve_instance):
        @serve.deployment
        class Streams:
            def burst(self, n):
                return _Burst(n)

        handle = serve.run(Streams.bind(), name="bursts", route_prefix=None)
        gen = handle.burst.remote_streaming(10)
        assert list(gen) == list(range(10))
        # 4 + 4 + 2 chunks in three objects.
        assert gen._gen._idx == 3
        assert gen._n == 10

    def test_llm_streams_that_lag_are_whole_and_in_order(
            self, serve_instance):
        """Eight clients that take their tokens late: the loop's streams
        hold several tokens when their consumers come back, and every
        client still receives its request's tokens, all and in order
        (greedy: what the same replica gave a client that kept pace)."""
        app = serve.LLMDeployment.bind(
            model="llama", seed=0,
            engine_options=dict(ENGINE_OPTIONS, max_num_seqs=8))
        handle = serve.run(app, name="llm-lag", route_prefix=None)
        prompts = [[(5 * i + j) % 200 + 1 for j in range(6)]
                   for i in range(8)]
        got = {}

        def consume(key, i, lag):
            out = []
            for tok in handle.generate.remote_streaming(
                    prompts[i], max_new_tokens=16):
                out.append(tok)
                if lag and len(out) % 8 == 1:
                    time.sleep(0.2)   # the engine runs on meanwhile
            got[key, i] = out

        for lag in (False, True):
            threads = [threading.Thread(target=consume, args=(lag, i, lag))
                       for i in range(8)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=300)
            assert not any(th.is_alive() for th in threads)
        for i in range(8):
            assert len(got[True, i]) == 16
            assert got[True, i] == got[False, i], i
