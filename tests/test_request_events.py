"""Request-centric serving observability (PR r20): lifecycle timelines,
TTFT/TPOT/goodput attribution, tenant-aware serve SLOs.

Covers the PR's contracts:

- ``kind="request"`` events share the task-event ring (never-blocking,
  strict-wire-safe) and fold into head records carrying deployment and
  tenant; one request id yields a stitched multi-process waterfall;
- exact TTFT/TPOT/goodput counter accounting under staggered concurrent
  streams, including preempt-recompute and mid-stream-failure waste;
- the prefix-summary advertisement piggybacks on health-check replies
  and reaches routers via a change-only long-poll push;
- ``tsdb.serve_slo_preset_rules`` expands per-tenant TTFT presets into
  alert rules that fire on the breaching tenant only;
- lint rule RTP021 (transition coverage + one-flag-check emission
  purity) bites on planted violations and passes the live tree.
"""

import ast
import bisect
import json
import threading
import time
import types

import pytest

import raytpu
from raytpu.util import serve_slo, task_events, tsdb
from raytpu.util.task_events import RequestTransition, TaskEventStore


@pytest.fixture
def req_recorder():
    """Armed request recorder with a fresh ring; restores defaults."""
    task_events.clear()
    task_events.enable_request_events()
    yield task_events
    task_events.disable_request_events(env=True)
    task_events.clear()


def _slo_snapshot():
    """Deltas, not absolutes: the serve_slo instruments are module-level
    and accumulate across tests in the process."""
    return {
        "delivered": dict(serve_slo.tokens_delivered._values),
        "wasted": dict(serve_slo.tokens_wasted._values),
        "ttft": {k: len(v) for k, v in
                 serve_slo.ttft_hist.observations_by_tag.items()},
        "tpot": {k: len(v) for k, v in
                 serve_slo.tpot_hist.observations_by_tag.items()},
        "e2e": {k: len(v) for k, v in
                serve_slo.e2e_hist.observations_by_tag.items()},
        "queue": {k: len(v) for k, v in
                  serve_slo.queue_hist.observations_by_tag.items()},
    }


def _counter_delta(before, after):
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v - before.get(k, 0.0)}


# -- ring + store -------------------------------------------------------------


class TestRequestRing:
    def test_vocabulary_is_complete_and_closed(self):
        assert set(RequestTransition.ALL) == {
            "RECEIVED", "ROUTED", "QUEUED", "ADMITTED", "PREFILL_START",
            "PREFILL_END", "HANDOFF_START", "HANDOFF_END", "FIRST_TOKEN",
            "PREEMPTED", "RESUMED", "FINISHED", "ABORTED", "FAILED"}
        assert "request" in task_events.KINDS

    def test_disabled_emit_is_noop(self):
        task_events.clear()
        assert not task_events.request_events_enabled()
        task_events.emit_request("r1", RequestTransition.RECEIVED,
                                 deployment="d", tenant="t")
        assert task_events.get_events() == []

    def test_request_flag_is_independent_of_task_flag(self, req_recorder):
        # A serving cluster can record request timelines without paying
        # for the task/actor/object firehose...
        assert not task_events.enabled()
        assert task_events.request_events_enabled()
        # ...but the shippers drain when EITHER class is armed.
        assert task_events.ship_enabled()

    def test_event_shape_and_wire_safety(self, req_recorder):
        task_events.emit_request(
            "r1", RequestTransition.ROUTED, deployment="app#Dep",
            tenant="acme",
            data={"replica": "rid-1", "matched_prefix_pages": 3})
        (ev,) = task_events.get_events()
        assert ev["kind"] == "request" and ev["id"] == "r1"
        assert ev["transition"] == "ROUTED"
        assert ev["deployment"] == "app#Dep" and ev["tenant"] == "acme"
        assert ev["data"] == {"replica": "rid-1",
                              "matched_prefix_pages": 3}
        json.dumps(ev)  # JSON-encodable end to end
        # Heartbeat batches ship over the strict (pickle-free) wire.
        from raytpu.cluster import wire

        assert wire.loads(wire.dumps([ev], allow_pickle=False),
                          allow_pickle=False) == [ev]

    def test_store_folds_timeline_with_tenant_overlay(self, req_recorder):
        base = time.time()
        store = TaskEventStore()
        # Arrival order scrambled across "processes"; the record's state
        # overlay and the detail timeline must follow event wall time.
        evs = []
        for i, tr in enumerate([RequestTransition.RECEIVED,
                                RequestTransition.ROUTED,
                                RequestTransition.QUEUED,
                                RequestTransition.FINISHED]):
            evs.append({"kind": "request", "id": "aabbccdd", "attempt": 0,
                        "transition": tr, "ts": base + i, "mono": float(i),
                        "node_id": f"n{i}", "worker_id": "",
                        "deployment": "app#Dep", "tenant": "acme"})
        store.add_batch([evs[3], evs[0]])
        store.add_batch([evs[2], evs[1]])
        (rec,) = store.list("request", limit=0)
        assert rec["state"] == "FINISHED"
        assert rec["deployment"] == "app#Dep" and rec["tenant"] == "acme"
        assert rec["num_events"] == 4
        detail = store.get("request", "aabb")  # unique prefix lookup
        assert [e["transition"] for e in detail["events"]] == [
            "RECEIVED", "ROUTED", "QUEUED", "FINISHED"]


# -- SLO instruments (unit) ---------------------------------------------------


class TestServeSLOInstruments:
    def test_zero_tokens_book_nothing(self):
        before = _slo_snapshot()
        serve_slo.delivered(0, "d", "t")
        serve_slo.wasted("abort", 0, "d", "t")
        after = _slo_snapshot()
        assert _counter_delta(before["delivered"], after["delivered"]) == {}
        assert _counter_delta(before["wasted"], after["wasted"]) == {}

    def test_tenant_defaults_and_cause_tagging(self):
        before = _slo_snapshot()
        serve_slo.delivered(3, "dep", "")
        serve_slo.wasted("preempt_recompute", 2, "dep", "acme")
        after = _slo_snapshot()
        assert _counter_delta(before["delivered"], after["delivered"]) \
            == {("dep", "default"): 3.0}
        assert _counter_delta(before["wasted"], after["wasted"]) \
            == {("preempt_recompute", "dep", "acme"): 2.0}


# -- scheduler seams: preemption waste + PREEMPTED/RESUMED --------------------


class TestPreemptRecomputeWaste:
    def make(self, pages):
        from raytpu.inference import PagedKVCache, Scheduler

        cache = PagedKVCache(num_layers=1, num_pages=pages, page_size=4,
                             num_kv_heads=1, head_dim=1)
        return cache, Scheduler(cache, max_num_seqs=8, max_model_len=64)

    def seq(self, rid, prompt_len, tenant="acme"):
        from raytpu.inference import Sequence

        s = Sequence(request_id=rid,
                     prompt=list(range(1, prompt_len + 1)))
        s.deployment = "app#Dep"
        s.tenant = tenant
        return s

    def test_preemption_books_wasted_tokens_and_timeline(self,
                                                         req_recorder):
        cache, sched = self.make(pages=5)  # 4 usable
        a, b = self.seq("ra", 8), self.seq("rb", 7)
        before = _slo_snapshot()
        sched.add(a)
        sched.add(b)
        assert sched.schedule().prefills == [a, b]
        a.cached_len, b.cached_len = 8, 7
        a.generated.append(1)
        b.generated.append(4)
        # a needs a 3rd page for token 9; none free -> b (youngest) is
        # preempted-to-recompute.
        plan = sched.schedule()
        assert plan.preempted == [b]
        after = _slo_snapshot()
        # b's generated token will be re-prefilled: pure waste,
        # attributed to b's deployment and tenant.
        assert _counter_delta(before["wasted"], after["wasted"]) == {
            ("preempt_recompute", "app#Dep", "acme"): 1.0}
        trs = [(e["id"], e["transition"])
               for e in task_events.get_events()]
        assert ("rb", "PREEMPTED") in trs
        assert ("ra", "ADMITTED") in trs and ("rb", "ADMITTED") in trs
        # Finish a; b re-admits as RESUMED (it has generated tokens).
        sched.finish(a, "stop")
        sched.schedule()
        trs = [(e["id"], e["transition"])
               for e in task_events.get_events()]
        assert ("ra", "FINISHED") in trs
        assert ("rb", "RESUMED") in trs

    def test_abort_in_waiting_emits_aborted(self, req_recorder):
        _, sched = self.make(pages=9)
        a = self.seq("rw", 4)
        sched.add(a)
        assert sched.abort("rw")
        (ev,) = [e for e in task_events.get_events()
                 if e["transition"] == "ABORTED"]
        assert ev["id"] == "rw" and ev["tenant"] == "acme"

    def test_disabled_scheduler_path_emits_nothing(self):
        task_events.clear()
        assert not task_events.request_events_enabled()
        _, sched = self.make(pages=9)
        a = self.seq("rq", 4)
        sched.add(a)
        sched.schedule()
        sched.finish(a, "stop")
        assert task_events.get_events() == []


# -- serve E2E: waterfall + exact goodput accounting --------------------------


@pytest.fixture
def serve_instance(raytpu_local):
    from raytpu import serve

    yield raytpu_local
    serve.shutdown()


def _deploy(name):
    from raytpu import serve

    app = serve.LLMDeployment.bind(
        model="llama",
        engine_options={"page_size": 8, "max_num_seqs": 4,
                        "max_model_len": 64},
        seed=0)
    return serve.run(app, name=name, route_prefix=None)


class TestServeRequestE2E:
    def test_waterfall_slos_and_goodput_ledger(self, serve_instance,
                                               req_recorder, capsys):
        """The acceptance test: one request id stitches into a full
        lifecycle waterfall, and TTFT/TPOT/e2e/queue plus the delivered
        counter land under the request's deployment+tenant tags."""
        from raytpu.state import api as state
        from raytpu.util import tenancy

        handle = _deploy("llm-obs")
        before = _slo_snapshot()
        with tenancy.tenant_scope("acme"):
            gen = handle.generate.remote_streaming(
                list(range(1, 9)), max_new_tokens=6)
            rid = gen.request_id
            assert rid  # router stamped identity onto the stream
            toks = list(gen)
        assert len(toks) == 6
        after = _slo_snapshot()
        dep = "llm-obs#LLMDeployment"

        rec = state.get_request_timeline(rid)
        assert rec is not None
        got = [e["transition"] for e in rec["events"]]
        # FIRST_TOKEN may legitimately precede PREFILL_END (sampling
        # happens inside the final prefill dispatch), so assert set
        # membership plus the orderings that ARE contractual.
        assert set(got) >= {"RECEIVED", "ROUTED", "QUEUED", "ADMITTED",
                            "PREFILL_START", "FIRST_TOKEN", "PREFILL_END",
                            "FINISHED"}
        assert got.index("RECEIVED") < got.index("ROUTED") \
            < got.index("QUEUED") < got.index("ADMITTED") \
            < got.index("PREFILL_START") < got.index("FIRST_TOKEN")
        assert got[-1] == "FINISHED"
        assert rec["deployment"] == dep and rec["tenant"] == "acme"
        fin = [e for e in rec["events"]
               if e["transition"] == "FINISHED"][0]
        assert fin["data"]["tokens_out"] == 6

        # Unique-prefix lookup (what the CLI user pastes).
        assert state.get_request_timeline(rid[:8])["id"] == rec["id"]
        rows = state.list_serve_requests(deployment=dep)
        assert [r["id"] for r in rows] == [rid]
        assert rows[0]["state"] == "FINISHED"
        assert rows[0]["tenant"] == "acme"

        # Goodput ledger + SLO histograms, exactly once per request.
        key = (dep, "acme")
        assert _counter_delta(before["delivered"],
                              after["delivered"]) == {key: 6.0}
        for series in ("ttft", "tpot", "e2e", "queue"):
            assert _counter_delta(before[series], after[series]) \
                == {key: 1}, series

        # The CLI waterfall renders the same stitched record.
        from raytpu.scripts import cli

        args = types.SimpleNamespace(address=None, detail=rid[:8],
                                     deployment=None, tenant=None,
                                     state=None, limit=100, json=False)
        assert cli._cmd_serve(args) == 0
        out = capsys.readouterr().out
        assert rid[:8] in out
        for tr in ("RECEIVED", "ROUTED", "FIRST_TOKEN", "FINISHED"):
            assert tr in out

    def test_staggered_streams_attribute_counters_exactly(
            self, serve_instance, req_recorder):
        """Two concurrent streams under different tenants: per-tenant
        delivered counts are exact and each request observes TTFT/TPOT
        exactly once — no cross-talk between overlapping requests."""
        from raytpu.util import tenancy

        handle = _deploy("llm-stagger")
        before = _slo_snapshot()
        results, started = {}, threading.Event()

        def consume(tag, tenant, n):
            with tenancy.tenant_scope(tenant):
                toks = []
                for tok in handle.generate.remote_streaming(
                        list(range(1, 10)), max_new_tokens=n):
                    toks.append(tok)
                    started.set()
                results[tag] = toks

        ta = threading.Thread(target=consume, args=("a", "acme", 24))
        ta.start()
        started.wait(timeout=60)  # b overlaps a's in-flight decode
        tb = threading.Thread(target=consume, args=("b", "free", 5))
        tb.start()
        ta.join(timeout=120)
        tb.join(timeout=120)
        assert not ta.is_alive() and not tb.is_alive()
        assert len(results["a"]) == 24 and len(results["b"]) == 5

        after = _slo_snapshot()
        dep = "llm-stagger#LLMDeployment"
        assert _counter_delta(before["delivered"], after["delivered"]) \
            == {(dep, "acme"): 24.0, (dep, "free"): 5.0}
        for series in ("ttft", "tpot", "e2e"):
            assert _counter_delta(before[series], after[series]) == {
                (dep, "acme"): 1, (dep, "free"): 1}, series
        # Nothing was wasted: delivered tokens == decoded tokens.
        assert _counter_delta(before["wasted"], after["wasted"]) == {}

    def test_cancellation_closes_timeline_as_aborted(self, serve_instance,
                                                     req_recorder):
        from raytpu.state import api as state

        handle = _deploy("llm-cancel")
        gen = handle.generate.remote_streaming(list(range(1, 9)),
                                               max_new_tokens=48)
        rid = gen.request_id
        next(gen)
        gen.close()
        deadline = time.monotonic() + 30
        rec = None
        while time.monotonic() < deadline:
            rec = state.get_request_timeline(rid)
            if rec and rec["state"] == "ABORTED":
                break
            time.sleep(0.1)
        assert rec is not None and rec["state"] == "ABORTED"


class TestEngineKnowsLiveSet:
    """Satellite: ``_engine_knows`` is an O(1) look-up among the live
    streams, and it still tells streams apart correctly when requests
    are aborted out of band (the behavior the old O(n) waiting+running
    scan provided)."""

    def _dep(self):
        from raytpu import serve

        return serve.LLMDeployment._target(
            engine_options={"page_size": 8, "max_num_seqs": 4,
                            "max_model_len": 64}, seed=0)

    def test_live_set_tracks_lifecycle_and_abort_ends_stream(self):
        from raytpu.serve._private import replica as replica_mod

        dep = self._dep()
        token = replica_mod._request_context.set(
            {"request_id": "known-rid", "deployment": "d", "tenant": ""})
        try:
            it = dep.generate(list(range(1, 9)), max_new_tokens=64)
            first = next(it)  # the request was admitted under its id
        finally:
            replica_mod._request_context.reset(token)
        assert first is not None
        assert dep._engine_knows("known-rid")
        assert dep.abort("known-rid")
        rest = list(it)  # terminates well before 64 tokens
        assert len(rest) < 63
        assert not dep._engine_knows("known-rid")

    def test_completed_request_leaves_no_residue(self):
        dep = self._dep()
        toks = list(dep.generate(list(range(1, 6)), max_new_tokens=3))
        assert len(toks) == 3
        assert dep._streams == {}


# -- chaos: producer dies mid-stream ------------------------------------------


class TestChaosMidStreamFailure:
    def test_client_seam_books_failed_and_waste(self, raytpu_local,
                                                req_recorder):
        """The replica process vanishes mid-stream: the client-side
        generator closes the timeline with FAILED and books every
        token already received as wasted — they bought nothing, the
        consumer restarts from scratch."""
        from raytpu.serve.handle import DeploymentResponseGenerator

        refs = [raytpu.put(t) for t in (11, 22, 33)]

        class DyingRefGen:
            _raytpu_request_meta = {"request_id": "chaos-1",
                                    "deployment": "app#Dep",
                                    "tenant": "acme"}

            def __init__(self):
                self._it = iter(refs)

            def __iter__(self):
                return self

            def __next__(self):
                try:
                    return next(self._it)
                except StopIteration:
                    raise RuntimeError("worker died (actor lost)")

        before = _slo_snapshot()
        gen = DeploymentResponseGenerator(DyingRefGen())
        assert gen.request_id == "chaos-1"
        got = []
        with pytest.raises(RuntimeError):
            for v in gen:
                got.append(v)
        assert got == [11, 22, 33]
        fails = [e for e in task_events.get_events()
                 if e["transition"] == "FAILED"]
        assert len(fails) == 1
        assert fails[0]["id"] == "chaos-1"
        assert fails[0]["data"]["tokens_received"] == 3
        assert "worker died" in fails[0]["error"]
        after = _slo_snapshot()
        assert _counter_delta(before["wasted"], after["wasted"]) == {
            ("abort", "app#Dep", "acme"): 3.0}
        # Re-pulling the dead stream must not double-book.
        with pytest.raises(RuntimeError):
            next(gen)
        assert len([e for e in task_events.get_events()
                    if e["transition"] == "FAILED"]) == 1
        assert _counter_delta(before["wasted"], _slo_snapshot()["wasted"]) \
            == {("abort", "app#Dep", "acme"): 3.0}


# -- prefix-summary push (satellite 1) ----------------------------------------


class TestPrefixSummaryPush:
    def test_controller_publishes_only_on_change(self):
        from raytpu.serve._private.controller import ServeController

        published = []
        fake = types.SimpleNamespace(
            notify_changed=lambda key, snap: published.append((key, snap)))
        r1 = types.SimpleNamespace(replica_id="r1", healthy=True,
                                   prefix_summary={"digests": [1]})
        r2 = types.SimpleNamespace(replica_id="r2", healthy=False,
                                   prefix_summary={"digests": [2]})
        r3 = types.SimpleNamespace(replica_id="r3", healthy=True,
                                   prefix_summary=None)
        state = types.SimpleNamespace(
            replicas={"r1": r1, "r2": r2, "r3": r3},
            last_prefix_snapshot=None, full_name="app#Dep")
        pub = ServeController._publish_prefix_summaries
        pub(fake, state)
        # Unhealthy replicas and replicas that never advertised are
        # excluded from the push (routers fall back to unicast probes).
        assert published == [("prefix::app#Dep",
                              {"r1": {"digests": [1]}})]
        pub(fake, state)  # steady state: zero long-poll wakeups
        assert len(published) == 1
        r1.prefix_summary = {"digests": [1, 9]}
        pub(fake, state)
        assert published[-1] == ("prefix::app#Dep",
                                 {"r1": {"digests": [1, 9]}})

    def test_router_pushed_summary_staleness_bound(self):
        from raytpu.cluster import constants as tuning
        from raytpu.serve._private.router import ReplicaSet

        rs = object.__new__(ReplicaSet)  # skip the poll thread
        rs._lock = threading.Lock()
        now = time.monotonic()
        rs._pushed_summaries = {
            "fresh": (now, {"digests": [1]}),
            "stale": (now - tuning.PREFIX_PUSH_MAX_AGE_S - 1.0,
                      {"digests": [2]}),
        }
        assert rs.pushed_summary("fresh") == {"digests": [1]}
        assert rs.pushed_summary("stale") is None  # unicast fallback
        assert rs.pushed_summary("missing") is None

    def test_health_reply_reaches_long_poll_subscribers(self,
                                                        serve_instance):
        """E2E: replicas piggyback their prefix summary on the health
        reply; within a couple of health periods the controller pushes
        a ``prefix::<deployment>`` snapshot any long-poll client can
        observe. Any callable exposing ``prefix_summary`` rides the
        advertisement — a stub keeps this off the LLM compile path."""
        from raytpu import serve
        from raytpu.serve._private.controller import CONTROLLER_NAME

        @serve.deployment
        class Advertiser:
            def prefix_summary(self):
                return {"digests": [7], "kv_utilization": 0.25}

        serve.run(Advertiser.bind(), name="llm-pp", route_prefix=None)
        controller = raytpu.get_actor(CONTROLLER_NAME)
        key = "prefix::llm-pp#Advertiser"
        deadline = time.monotonic() + 30
        snap, version = None, -1
        while time.monotonic() < deadline:
            updates = raytpu.get(
                controller.listen_for_change.remote({key: version}))
            if key not in updates:
                continue
            version = updates[key].snapshot_id
            snap = updates[key].object_snapshot
            # The first publication may precede the first health reply
            # (an empty snapshot); wait for the advertised summary.
            if snap:
                break
        assert isinstance(snap, dict) and snap
        summary = next(iter(snap.values()))
        assert isinstance(summary, dict)


# -- per-tenant SLO alert presets ---------------------------------------------


class TestServeSLOAlerts:
    def test_preset_expansion(self):
        rules = tsdb.serve_slo_preset_rules("acme=0.5; free-tier=2",
                                            for_s=45.0)
        assert len(rules) == 2
        assert all(r.metric == "raytpu_serve_ttft_seconds" for r in rules)
        assert rules[0].tags == {"tenant": "acme"}
        assert rules[0].op == ">" and rules[0].threshold == 0.5
        assert rules[0].agg == "p95" and rules[0].for_s == 45.0
        assert rules[1].tags == {"tenant": "free-tier"}
        assert tsdb.serve_slo_preset_rules("") == []

    def test_malformed_preset_raises(self):
        with pytest.raises(ValueError):
            tsdb.serve_slo_preset_rules("acme")
        with pytest.raises(ValueError):
            tsdb.serve_slo_preset_rules("acme=")
        with pytest.raises(ValueError):
            tsdb.serve_slo_preset_rules("acme=fast")

    @staticmethod
    def _ttft_frame(proc, seq, ts, tenant, obs):
        bounds = (0.05, 0.25, 1.0, 5.0)
        counts = [0] * (len(bounds) + 1)
        for v in obs:
            counts[bisect.bisect_left(bounds, v)] += 1
        row = ["h", "raytpu_serve_ttft_seconds",
               ["deployment", "tenant"], ["app#Dep", tenant],
               list(bounds), counts, float(sum(obs)), len(obs)]
        return [proc, seq, ts, [row]]

    def test_alert_fires_for_breaching_tenant_only(self):
        """E2E through the real evaluator: sustained p95 TTFT breach on
        one tenant fires exactly that tenant's preset rule."""
        t = [1000.0]
        store = tsdb.MetricStore(max_bytes=1_000_000, fine_step_s=1.0,
                                 fine_slots=120, coarse_step_s=2.0,
                                 coarse_slots=100, clock=lambda: t[0])
        fired = []
        ev = tsdb.AlertEvaluator(
            store, tsdb.serve_slo_preset_rules("slow=0.5;fast=0.5",
                                               for_s=5.0),
            on_fire=lambda r, v: fired.append((r.tags["tenant"], v)))
        for dt in range(12):
            ts = 1000.0 + dt
            store.push([self._ttft_frame("w:a", dt + 1, ts, "slow",
                                         [3.0, 3.0, 3.0])])
            store.push([self._ttft_frame("w:b", dt + 1, ts, "fast",
                                         [0.01, 0.01, 0.01])])
            t[0] = ts
            ev.tick()
        assert len(fired) == 1
        tenant, value = fired[0]
        assert tenant == "slow" and value > 0.5
        assert ev.firing()


# -- lint: RTP021 -------------------------------------------------------------


class TestRequestCoverageLint:
    def _rule(self):
        from raytpu.analysis.rules.request_coverage import RequestCoverage

        return RequestCoverage()

    def test_live_tree_is_clean(self):
        from raytpu.analysis.core import run_lint

        result = run_lint(select=["RTP021"], use_baseline=False)
        assert result.files_scanned > 10
        assert not result.findings, "\n".join(
            str(f) for f in result.findings)

    def test_unguarded_emission_is_flagged(self):
        from raytpu.analysis.core import run_rule_on_source

        src = ("from raytpu.util import task_events\n"
               "def f(rid):\n"
               "    task_events.emit_request(rid, 'RECEIVED')\n")
        (f,) = run_rule_on_source(self._rule(), src)
        assert "outside" in f.message

    def test_double_flag_check_is_flagged(self):
        from raytpu.analysis.core import run_rule_on_source

        src = ("from raytpu.util.task_events import (emit_request,\n"
               "    request_events_enabled)\n"
               "def f(rid):\n"
               "    if request_events_enabled() and "
               "request_events_enabled():\n"
               "        emit_request(rid, 'RECEIVED')\n")
        (f,) = run_rule_on_source(self._rule(), src)
        assert "called 2 times" in f.message

    def test_guarded_and_combined_guard_are_clean(self):
        from raytpu.analysis.core import run_rule_on_source

        src = ("from raytpu.util import task_events\n"
               "def f(rid, ok):\n"
               "    if task_events.request_events_enabled() and ok:\n"
               "        task_events.emit_request(rid, 'RECEIVED')\n"
               "    if task_events.request_events_enabled():\n"
               "        task_events.emit_request(rid, 'FINISHED')\n")
        assert run_rule_on_source(self._rule(), src) == []

    def test_coverage_gap_is_flagged_on_finalize(self):
        from raytpu.analysis.core import run_rule_on_source
        from raytpu.analysis.rules.request_coverage import (
            request_transitions_referenced,
        )

        src = ("from raytpu.util import task_events\n"
               "from raytpu.util.task_events import RequestTransition\n"
               "def f(rid):\n"
               "    if task_events.request_events_enabled():\n"
               "        task_events.emit_request(\n"
               "            rid, RequestTransition.FINISHED)\n")
        found = run_rule_on_source(self._rule(), src, whole_tree=True)
        missing = {f.message.split()[0] for f in found}
        assert "RequestTransition.FINISHED" not in missing
        assert len(found) == len(RequestTransition.ALL) - 1
        # and the reference scanner itself sees through both forms
        tree = ast.parse(
            "a = RequestTransition.QUEUED\n"
            "b = task_events.RequestTransition.PREEMPTED\n")
        assert request_transitions_referenced(tree) == {"QUEUED",
                                                        "PREEMPTED"}
