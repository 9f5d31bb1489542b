"""End-to-end distributed tracing (ISSUE: observability tentpole).

Covers the Dapper-style context (``"tc"`` riding RPC frames next to the
deadline's ``"d"``), span recording into the bounded per-process ring
buffer, cross-process propagation through the real RpcClient/RpcServer
stack, chrome-trace assembly with per-process tracks and flow arrows,
the built-in RPC latency / retry metrics, and the cost pin: a disabled
span site is one module-flag check plus a shared no-op context manager.

The AST lint at the bottom (same shape as TestNoHardcodedTimeouts in
test_resilience.py) pins the structural invariant that EVERY registered
RPC handler runs inside the server span in ``RpcServer._dispatch`` —
new dispatch paths must keep the span wrapping or the lint bites.
"""

import ast
import os
import pathlib
import threading
import time

import pytest

from raytpu.util import tracing
from raytpu.util.tracing import TraceContext


@pytest.fixture
def traced():
    """Arm tracing for one test; restore the disabled default after."""
    tracing.clear_spans()
    tracing.enable_tracing(sample_rate=1.0)
    yield tracing
    tracing.disable_tracing()
    tracing.clear_spans()


def _by_name(name):
    return [s for s in tracing.get_spans() if s["name"] == name]


# -- TraceContext wire format -------------------------------------------------


class TestTraceContext:
    def test_root_and_child_identity(self):
        root = TraceContext.root()
        assert root.parent_span_id is None and root.sampled
        kid = root.child()
        assert kid.trace_id == root.trace_id
        assert kid.span_id != root.span_id
        assert kid.parent_span_id == root.span_id
        assert kid.sampled

    def test_wire_roundtrip(self):
        root = TraceContext.root()
        w = root.to_wire()
        # Primitives only — must encode on strict (allow_pickle=False)
        # surfaces like the driver proxy.
        assert w == [root.trace_id, root.span_id, 1]
        back = TraceContext.from_wire(w)
        assert back.trace_id == root.trace_id
        assert back.span_id == root.span_id
        assert back.sampled is True
        # parent_span_id never rides: the receiver's parent IS the
        # sender's span id.
        assert back.parent_span_id is None

    def test_unsampled_rides_as_zero(self):
        tc = TraceContext.root(sampled=False)
        assert tc.to_wire()[2] == 0
        assert TraceContext.from_wire(tc.to_wire()).sampled is False

    @pytest.mark.parametrize("bad", [
        None, [], [1, 2, 3], ["only-one"], "xy", 42,
        [b"bytes", b"bytes", 1],
    ])
    def test_malformed_wire_is_none(self, bad):
        assert TraceContext.from_wire(bad) is None


# -- span recording -----------------------------------------------------------


class TestSpanRecording:
    def test_records_real_pid_tid(self, traced):
        with tracing.span("unit.a"):
            pass
        (rec,) = _by_name("unit.a")
        assert rec["pid"] == os.getpid() != 0
        assert rec["tid"] == threading.get_native_id() != 0
        assert rec["duration_s"] >= 0
        assert rec["error"] is None

    def test_nesting_builds_parent_chain(self, traced):
        with tracing.span("outer"):
            with tracing.span("inner"):
                pass
        (outer,) = _by_name("outer")
        (inner,) = _by_name("inner")
        assert inner["trace_id"] == outer["trace_id"]
        assert inner["parent_span_id"] == outer["span_id"]
        assert outer["parent_span_id"] is None

    def test_attrs_dict_mutation_is_recorded(self, traced):
        with tracing.span("unit.attrs") as attrs:
            attrs["node"] = "n1"
        (rec,) = _by_name("unit.attrs")
        assert rec["attributes"] == {"node": "n1"}

    def test_error_captured_and_propagated(self, traced):
        with pytest.raises(ValueError):
            with tracing.span("unit.err"):
                raise ValueError("boom")
        (rec,) = _by_name("unit.err")
        assert "ValueError" in rec["error"]

    def test_sample_rate_zero_propagates_but_records_nothing(self, traced):
        tracing.enable_tracing(sample_rate=0.0)
        with tracing.span("unsampled"):
            ctx = tracing.current_trace()
            assert ctx is not None and ctx.sampled is False
            with tracing.span("unsampled.child"):
                pass
        assert tracing.get_spans() == []

    def test_disabled_yields_shared_noop(self):
        assert not tracing.enabled()
        s = tracing.span("whatever")
        assert s is tracing._NOOP_SPAN
        with tracing.span("x") as attrs:
            attrs["k"] = "v"  # writable, never read
        assert tracing.get_spans() == []
        assert tracing.current_trace() is None

    def test_ring_buffer_is_bounded(self, traced):
        cap = tracing._spans.maxlen
        assert cap == tracing._BUFFER >= 16
        for i in range(cap + 10):
            with tracing.span(f"fill.{i}"):
                pass
        spans = tracing.get_spans()
        assert len(spans) == cap
        # Oldest were evicted.
        assert spans[0]["name"] == "fill.10"

    def test_run_with_trace_reanchors(self, traced):
        tc = TraceContext.root()

        def job():
            cur = tracing.current_trace()
            assert cur.trace_id == tc.trace_id
            return 99

        assert tracing.run_with_trace(tc, "bridged", job) == 99
        (rec,) = _by_name("bridged")
        assert rec["trace_id"] == tc.trace_id
        assert rec["parent_span_id"] == tc.span_id
        # The anchor was scoped to the call.
        assert tracing.current_trace() is None

    def test_traced_decorator(self, traced):
        @tracing.traced("deco.fn")
        def add(a, b):
            return a + b

        assert add(2, 3) == 5
        assert len(_by_name("deco.fn")) == 1

    def test_dump_payload_shape(self, traced):
        tracing.set_process_identity("testproc", "abc123")
        try:
            with tracing.span("dumped"):
                pass
            d = tracing.dump()
            assert d["identity"] == ["testproc", "abc123"]
            assert d["pid"] == os.getpid()
            assert any(s["name"] == "dumped" for s in d["spans"])
        finally:
            tracing.set_process_identity("proc", "")


# -- cross-process propagation through the real RPC stack ---------------------


@pytest.fixture
def rpc_pair():
    """One in-process RpcServer + RpcClient; the handler records the
    ambient trace it observed (re-anchored by ``_dispatch``)."""
    from raytpu.cluster.protocol import RpcClient, RpcServer

    seen = {}
    srv = RpcServer("127.0.0.1", 0)

    def echo(peer, x):
        seen["tc"] = tracing.current_trace()
        return x

    srv.register("echo", echo)
    addr = srv.start()
    cli = RpcClient(addr)
    yield cli, seen, addr
    cli.close()
    srv.stop()


class TestRpcPropagation:
    def test_tc_rides_frame_and_parents_server_span(self, traced, rpc_pair):
        cli, seen, addr = rpc_pair
        with tracing.span("root"):
            assert cli.call("echo", 7) == 7
        (root,) = _by_name("root")
        (client,) = _by_name("rpc.client.echo")
        (server,) = _by_name("rpc.server.echo")
        assert client["trace_id"] == server["trace_id"] == root["trace_id"]
        assert client["parent_span_id"] == root["span_id"]
        # Server dispatch re-anchored the wire tc: its span is the
        # client span's child even though both live in this process.
        assert server["parent_span_id"] == client["span_id"]
        assert seen["tc"].trace_id == root["trace_id"]
        assert client["attributes"]["peer"] == addr

    def test_client_latency_histogram_tagged_method_peer(self, traced,
                                                         rpc_pair):
        cli, _seen, addr = rpc_pair
        from raytpu.util import resilience

        with tracing.span("root"):
            cli.call("echo", 1)
        hist = resilience._metrics.get("raytpu_rpc_client_latency_seconds")
        assert hist, "traced call must register the latency histogram"
        samples = hist.observations_by_tag.get(("echo", addr))
        assert samples and all(s >= 0 for s in samples)

    def test_explicit_trace_param(self, traced, rpc_pair):
        cli, seen, _addr = rpc_pair
        tc = TraceContext.root()
        assert tracing.current_trace() is None
        cli.call("echo", 1, trace=tc)
        assert seen["tc"].trace_id == tc.trace_id

    def test_unsampled_context_propagates_recording_nothing(self, traced,
                                                            rpc_pair):
        cli, seen, _addr = rpc_pair
        tc = TraceContext.root(sampled=False)
        token = tracing.set_current_trace(tc)
        try:
            cli.call("echo", 1)
        finally:
            tracing.reset_current_trace(token)
        assert seen["tc"] is not None
        assert seen["tc"].sampled is False
        assert seen["tc"].trace_id == tc.trace_id
        assert not [s for s in tracing.get_spans()
                    if s["trace_id"] == tc.trace_id]

    def test_disabled_hop_still_forwards_tc(self, rpc_pair):
        # An untraced intermediary must not sever the chain: with tracing
        # disabled the ambient tc still rides the frame verbatim.
        cli, seen, _addr = rpc_pair
        assert not tracing.enabled()
        tc = TraceContext.root()
        token = tracing.set_current_trace(tc)
        try:
            cli.call("echo", 1)
        finally:
            tracing.reset_current_trace(token)
        assert seen["tc"] is not None
        assert seen["tc"].trace_id == tc.trace_id
        assert seen["tc"].span_id == tc.span_id  # forwarded, not re-spanned
        assert tracing.get_spans() == []


# -- timeline assembly --------------------------------------------------------


def _fake_dump(kind, ident, pid, spans):
    return {"identity": [kind, ident], "pid": pid, "spans": spans}


def _fake_span(name, trace_id, span_id, parent, pid, tid=7, start=1.0):
    return {"name": name, "trace_id": trace_id, "span_id": span_id,
            "parent_span_id": parent, "start": start, "duration_s": 0.5,
            "pid": pid, "tid": tid, "attributes": {}, "error": None}


class TestTimelineAssembly:
    def test_span_event_carries_real_pid_tid(self, traced):
        with tracing.span("evt"):
            pass
        (rec,) = _by_name("evt")
        evt = tracing._span_event(rec)
        assert evt["ph"] == "X"
        assert evt["pid"] == os.getpid() != 0
        assert evt["tid"] == threading.get_native_id() != 0
        assert evt["args"]["trace_id"] == rec["trace_id"]

    def test_tracks_flows_and_metadata(self, tmp_path):
        t = "t" * 32
        head = _fake_dump("head", "", 111, [
            _fake_span("sched.decide", t, "s1", None, 111)])
        node = _fake_dump("node", "ab12", 222, [
            _fake_span("task.execute", t, "s2", "s1", 222),
            _fake_span("object.pull", t, "s3", "s2", 222)])
        out = str(tmp_path / "trace.json")
        events = tracing.assemble_timeline([head, node], out)

        meta = {e["pid"]: e["args"]["name"]
                for e in events if e.get("ph") == "M"}
        assert meta == {1: "head (pid 111)", 2: "node:ab12 (pid 222)"}

        spans = {e["name"]: e for e in events if e.get("ph") == "X"}
        assert spans["sched.decide"]["pid"] == 1
        assert spans["task.execute"]["pid"] == 2

        flows = [e for e in events if e.get("cat") == "flow"]
        # Exactly one cross-process edge (s1 -> s2): an "s" on the head
        # track and an "f" on the node track, joined by the child span id.
        # s2 -> s3 is same-track nesting and draws itself.
        assert {(e["ph"], e["pid"]) for e in flows} == {("s", 1), ("f", 2)}
        assert all(e["id"] == "s2" for e in flows)

        import json
        with open(out) as f:
            assert json.load(f) == events

    def test_garbage_dumps_skipped(self):
        events = tracing.assemble_timeline(
            [None, "junk", {"identity": None, "spans": None}])
        assert [e for e in events if e.get("ph") == "X"] == []

    def test_cluster_timeline_falls_back_to_local(self, traced):
        # Not connected to any cluster: still yields this process's spans.
        with tracing.span("local.only"):
            pass
        events = tracing.cluster_timeline()
        names = [e["name"] for e in events if e.get("ph") == "X"]
        assert "local.only" in names


# -- disabled-path cost pin ---------------------------------------------------


class TestDisabledOverhead:
    def _per_call(self, fn, n=20000, repeats=5):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(n):
                fn()
            best = min(best, time.perf_counter() - t0)
        return best / n

    def test_disabled_span_site_is_flag_check_cheap(self):
        assert not tracing.enabled()
        tracing.clear_spans()

        def site():
            with tracing.span("bench.site"):
                pass

        def flag():
            if tracing.enabled():
                pass  # pragma: no cover

        site_s = self._per_call(site)
        flag_s = self._per_call(flag)
        assert tracing.get_spans() == []
        # Loose CI-safe pins: a disabled span site must stay within a
        # small constant of a bare flag check (shared no-op context
        # manager, nothing allocates) and be microseconds-cheap in
        # absolute terms.
        assert site_s < 10e-6, f"disabled span site {site_s * 1e6:.2f}us"
        assert site_s < 30 * max(flag_s, 1e-8), (
            f"span {site_s * 1e9:.0f}ns vs flag {flag_s * 1e9:.0f}ns")

    def test_phase_site_without_session_or_tracing_is_cheap(self):
        """A phase always stamps its record, so it cannot be a no-op:
        two clock reads, one list, and a ``TraceAnnotation`` that is a
        flag check while no profiler session runs. Measured 1.2-1.3 us
        here; the engine opens eight a decode step. Loose, CI-safe."""
        import jax  # noqa: F401  (so the annotation is entered too)

        assert not tracing.enabled()
        rec = tracing.StepRecorder(maxlen=4)

        def site():
            with rec.phase("bench.phase"):
                pass

        with rec.step("bench.step"):
            site_s = self._per_call(site, n=5000)
        assert tracing._annotation_type() is not None
        assert len(rec.log()["steps"][0]["phases"]) == 5 * 5000
        assert tracing.get_spans() == []
        assert site_s < 20e-6, f"phase site {site_s * 1e6:.2f}us"


# -- step records: one record per step, phases on three clocks ----------------


class TestStepRecorder:
    def _step(self, rec, name="t.step", **fields):
        with rec.step(name, dict(fields)) as st:
            with rec.phase("t.a"):
                with rec.phase("t.a.inner") as inner:
                    inner.attrs["k"] = 1
            with rec.phase("t.b"):
                pass
        return st

    def test_phases_are_ordered_and_nest_inside_their_step(self):
        rec = tracing.StepRecorder()
        self._step(rec, decodes=3)
        (step,) = rec.log()["steps"]
        assert step["decodes"] == 3
        assert [p[0] for p in step["phases"]] == ["t.a", "t.a.inner", "t.b"]
        (a, inner, b) = step["phases"]
        assert step["start"] <= a[1] <= inner[1] <= inner[2] <= a[2] \
            <= b[1] <= b[2] <= step["end"]
        # Plain data: a reader may keep or change what it was given.
        step["phases"][0][0] = "changed"
        assert rec.log()["steps"][0]["phases"][0][0] == "t.a"

    def test_a_phase_outside_a_step_goes_to_the_next_or_the_last(self):
        rec = tracing.StepRecorder()
        with rec.phase("t.before"):
            pass
        self._step(rec)
        with rec.phase("t.after", after=True):
            pass
        with rec.phase("t.before"):
            pass
        self._step(rec)
        first, second = rec.log()["steps"]
        assert [p[0] for p in first["phases"]] == [
            "t.before", "t.a", "t.a.inner", "t.b", "t.after"]
        assert first["phases"][0][2] <= first["start"]
        assert first["phases"][-1][1] >= first["end"]
        assert [p[0] for p in second["phases"]][0] == "t.before"
        assert "t.after" not in [p[0] for p in second["phases"]]

    def test_log_since_filters_and_reports_the_oldest_start(self):
        rec = tracing.StepRecorder()
        empty = rec.log()
        assert (empty["oldest_start"], empty["steps"]) == (None, [])
        for i in range(4):
            self._step(rec, i=i)
        everything = rec.log()
        assert [s["i"] for s in everything["steps"]] == [0, 1, 2, 3]
        oldest = everything["steps"][0]["start"]
        assert everything["oldest_start"] == oldest
        later = rec.log(since=everything["steps"][1]["end"])
        assert [s["i"] for s in later["steps"]] == [2, 3]
        assert later["oldest_start"] == oldest  # the ring's, not the cut's
        assert rec.log(since=time.perf_counter())["steps"] == []

    def test_the_ring_is_bounded_and_the_oldest_start_moves_with_it(self):
        rec = tracing.StepRecorder(maxlen=8)
        for i in range(18):
            self._step(rec, i=i, decodes=i)
        assert len(rec) == 8
        log = rec.log()
        assert [s["i"] for s in log["steps"]] == list(range(10, 18))
        assert log["oldest_start"] == log["steps"][0]["start"]
        assert rec.values("decodes") == list(range(10, 18))
        assert [r.fields["i"] for r in rec.tail(3)] == [15, 16, 17]

    def test_a_phase_is_also_a_ring_span_with_the_same_attributes(
            self, traced):
        rec = tracing.StepRecorder()
        st = self._step(rec, decodes=2)
        step, inner = _by_name("t.step")[0], _by_name("t.a.inner")[0]
        # The record's fields: what the site gave and the thread's CPU time.
        assert step["attributes"] == {"decodes": 2,
                                      "cpu_s": step["attributes"]["cpu_s"]}
        assert inner["attributes"] == {"k": 1}
        a = _by_name("t.a")[0]
        assert inner["parent_span_id"] == a["span_id"]
        assert a["parent_span_id"] == step["span_id"]
        # The span's monotonic clock is the record's: they lie on one axis.
        record = rec.log()["steps"][0]
        assert step["t0"] <= record["start"] <= record["end"] \
            <= step["t0"] + step["duration_s"]
        assert st.t0 == record["start"] and st.t1 == record["end"]

    def test_a_step_that_raises_keeps_its_record_and_names_the_error(
            self, traced):
        rec = tracing.StepRecorder()
        with pytest.raises(ValueError):
            with rec.step("t.step"):
                with rec.phase("t.a"):
                    raise ValueError("boom")
        (step,) = rec.log()["steps"]
        assert "boom" in step["error"]
        assert step["phases"][0][2] >= step["phases"][0][1] > 0
        assert "boom" in _by_name("t.step")[0]["error"]
        assert rec.open is None

    def test_ring_spans_carry_a_monotonic_t0_beside_the_wall_clock(
            self, traced):
        before = time.perf_counter()
        with tracing.span("t.span"):
            pass
        (s,) = _by_name("t.span")
        assert before <= s["t0"] <= time.perf_counter()
        assert abs(s["start"] - time.time()) < 60

    def test_tracing_does_not_import_jax(self):
        import subprocess
        import sys

        code = ("import sys\n"
                "from raytpu.util import tracing\n"
                "rec = tracing.StepRecorder()\n"
                "with rec.step('s'):\n"
                "    with rec.phase('p'):\n"
                "        pass\n"
                "assert 'jax' not in sys.modules, 'jax was imported'\n"
                "print(len(rec))\n")
        out = subprocess.run([sys.executable, "-c", code], timeout=120,
                             capture_output=True, text=True,
                             cwd=str(pathlib.Path(__file__).parent.parent))
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "1"


# -- host pauses: what stopped the interpreter, on the step log's clock ---------


class TestHostPauses:
    """ISSUE 57: the collector's pauses in one bounded ring a process,
    installed by the first recorder built, and a step's own CPU time."""

    def test_a_full_collection_inside_a_step_leaves_one_entry(self):
        import gc

        rec = tracing.StepRecorder()
        before = tracing.host_pause_totals()["gc"]["2"]["count"]
        with rec.step("t.step") as st:
            with rec.phase("t.a"):
                gc.collect()
        log = rec.log(since=st.t0)
        (pause,) = [p for p in log["pauses"] if p[1] >= st.t0]
        kind, t0, t1, attrs = pause
        assert kind == "host.gc" and attrs["generation"] == 2
        assert st.t0 <= t0 <= t1 <= st.t1
        assert attrs["stepping"] is True and attrs["collected"] >= 0
        totals = tracing.host_pause_totals()["gc"]["2"]
        assert totals["count"] == before + 1
        assert totals["longest_s"] >= t1 - t0 > 0
        assert totals["seconds"] >= totals["longest_s"]
        # Plain data: a reader may change what it was given.
        attrs["generation"] = 7
        assert tracing.host_pauses(st.t0)[0][3]["generation"] == 2

    def test_pauses_honour_since_in_the_log_and_in_the_module(self):
        import gc

        rec = tracing.StepRecorder()
        gc.collect()
        cut = time.perf_counter()
        gc.collect()
        later = rec.log(since=cut)["pauses"]
        assert len(later) == 1 and later[0][2] > cut
        everything = tracing.host_pauses()
        assert len(everything) >= 2 and everything[-1] == later[0]
        assert [p[1] for p in everything] == sorted(
            p[1] for p in everything)  # oldest first
        assert tracing.host_pauses(time.perf_counter()) == []

    def test_a_short_young_collection_is_counted_and_not_kept(self):
        import gc

        tracing.StepRecorder()
        counted = tracing.host_pause_totals()["gc"]["0"]["count"]
        kept = len(tracing.host_pauses())
        cut = time.perf_counter()
        gc.collect(0)
        assert tracing.host_pause_totals()["gc"]["0"]["count"] == counted + 1
        # Kept only had it taken over a millisecond (a loaded machine).
        new = tracing.host_pauses(cut)
        assert all(p[2] - p[1] > tracing.GC_KEPT_OVER_S for p in new)
        assert len(tracing.host_pauses()) == kept + len(new)

    def test_a_thread_that_does_not_step_is_told_apart(self):
        import gc
        import threading

        rec = tracing.StepRecorder()
        with rec.step("t.step"):
            pass
        cut = time.perf_counter()
        other = threading.Thread(target=gc.collect)
        other.start()
        other.join()
        (pause,) = tracing.host_pauses(cut)
        assert pause[3]["stepping"] is False

    def test_the_ring_is_bounded(self, monkeypatch):
        import collections
        import gc

        tracing.StepRecorder()
        assert tracing._pauses.maxlen == tracing.PAUSE_RING
        monkeypatch.setattr(tracing, "_pauses", collections.deque(maxlen=4))
        for _ in range(7):
            gc.collect()
        assert len(tracing.host_pauses()) == 4

    def test_the_callback_is_installed_once_and_not_by_import(self):
        import gc
        import subprocess
        import sys

        for _ in range(3):
            tracing.StepRecorder()
        assert gc.callbacks.count(tracing._on_gc) == 1
        code = ("import gc\n"
                "import raytpu\n"
                "from raytpu.util import tracing\n"
                "assert tracing._on_gc not in gc.callbacks\n"
                "gc.collect()\n"
                "assert tracing.host_pauses() == []\n"
                "tracing.StepRecorder()\n"
                "gc.collect()\n"
                "print(gc.callbacks.count(tracing._on_gc),"
                " len(tracing.host_pauses()))\n")
        out = subprocess.run([sys.executable, "-c", code], timeout=120,
                             capture_output=True, text=True,
                             cwd=str(pathlib.Path(__file__).parent.parent))
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == ["1", "1"]

    def test_a_full_collection_enters_the_profilers_annotation(
            self, monkeypatch):
        import gc

        seen = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                seen.append(("enter", self.name))

            def __exit__(self, *exc):
                seen.append(("exit", self.name))

        tracing.StepRecorder()
        monkeypatch.setattr(tracing, "_trace_annotation", Annotation)
        gc.collect(0)
        assert seen == []  # a young collection is no event of the trace
        gc.collect()
        assert seen == [("enter", "host.gc"), ("exit", "host.gc")]

    def test_what_is_unpublished_is_handed_out_once(self):
        import gc

        tracing.StepRecorder()
        tracing.gc_unpublished()
        assert tracing.gc_unpublished() is None
        gc.collect()
        gc.collect(0)
        counts, seconds = tracing.gc_unpublished()
        assert counts[2] == 1 and counts[0] >= 1 and seconds > 0
        assert tracing.gc_unpublished() is None

    def test_a_steps_cpu_time_lies_inside_its_wall_time(self):
        rec = tracing.StepRecorder()
        with rec.step("t.step", {"decodes": 1}):
            with rec.phase("t.launch"):
                sum(range(20000))
            with rec.phase("t.wait", cpu="wait_cpu_s"):
                time.sleep(0.02)
            with rec.phase("t.wait", cpu="wait_cpu_s"):
                sum(range(20000))
        (step,) = rec.log()["steps"]
        wall = step["end"] - step["start"]
        assert 0 < step["wait_cpu_s"] <= step["cpu_s"] <= wall
        # The sleep is wall time and no CPU time: off the CPU.
        assert wall - step["cpu_s"] >= 0.015
        waits = [t1 - t0 for name, t0, t1 in step["phases"]
                 if name == "t.wait"]
        assert step["wait_cpu_s"] <= sum(waits)
        # A phase with no step open has no record to count into.
        with rec.phase("t.early", cpu="wait_cpu_s"):
            pass

    def test_kept_values_are_the_rings_and_cost_no_walk(self):
        kept = tracing.StepRecorder(maxlen=8, keep=("decodes",))
        walked = tracing.StepRecorder(maxlen=8)
        for i in range(21):
            for rec in (kept, walked):
                with rec.step("t.step", {"decodes": i % 3, "i": i}):
                    pass
            assert kept.values("decodes") == walked.values("decodes")
        assert kept.values("decodes") == [
            i % 3 for i in range(13, 21) if i % 3]
        assert len(kept._kept["decodes"]) <= 8
        assert kept.values("i") == list(range(13, 21))  # not kept: walked


# -- metrics satellites -------------------------------------------------------


class TestMetricsFallback:
    def test_histogram_keeps_per_tag_series(self):
        from raytpu.util.metrics import Histogram

        h = Histogram("test_tracing_hist_tags", "x", tag_keys=("k",))
        h.observe(1.0, tags={"k": "a"})
        h.observe(2.0, tags={"k": "b"})
        h.observe(3.0, tags={"k": "a"})
        # Flat view stays back-compatible; per-tag no longer collapses.
        assert h.observations == [1.0, 2.0, 3.0]
        assert h.observations_by_tag == {("a",): [1.0, 3.0],
                                         ("b",): [2.0]}

    def test_gauge_value_deterministic(self):
        from raytpu.util.metrics import Gauge

        g = Gauge("test_tracing_gauge_plain", "x")
        g.set(3.0)
        assert g.value == 3.0
        assert g.values == {(): 3.0}

        gt = Gauge("test_tracing_gauge_tagged", "x", tag_keys=("k",))
        gt.set(5.0, tags={"k": "a"})
        gt.set(7.0, tags={"k": "b"})
        assert gt.values == {("a",): 5.0, ("b",): 7.0}

    def test_retry_counter_increments_per_error_type(self):
        from raytpu.util import resilience

        attempts = {"n": 0}

        def flaky():
            attempts["n"] += 1
            if attempts["n"] < 3:
                raise ConnectionResetError("nope")
            return "ok"

        counter = resilience._metric(
            "counter", "raytpu_retries_total",
            "retry attempts across resilience policies", ("error",))
        before = counter.value if counter else 0
        pol = resilience.RetryPolicy(max_attempts=3, seed=1,
                                     sleep=lambda s: None)
        assert pol.run(flaky) == "ok"
        assert counter is not None
        assert counter.value == before + 2


# -- AST lint: every RPC handler runs inside the server span ------------------


class TestServerSpanLint:
    """Thin wrapper over RTP002 (raytpu/analysis/rules/server_span.py) —
    the ad-hoc ``_unspanned_handler_calls`` scan migrated into the lint
    framework; this keeps the invariant visible from the tracing suite
    and proves the rule still bites."""

    def test_rpc_dispatch_is_span_wrapped(self):
        from raytpu.analysis.core import run_lint
        from raytpu.analysis.rules.server_span import handler_call_sites

        result = run_lint(select=["RTP002"], use_baseline=False)
        assert not result.findings, (
            "RPC handler invoked outside tracing.span in _dispatch — "
            "every registered handler must run inside the server span:\n  "
            + "\n  ".join(str(f) for f in result.findings))
        # The invariant is only meaningful if dispatch sites exist.
        pkg = pathlib.Path(__file__).resolve().parent.parent / \
            "raytpu" / "cluster"
        total = []
        for path in sorted(pkg.glob("*.py")):
            t, _ = handler_call_sites(ast.parse(path.read_text()))
            total.extend(t)
        assert total, "expected at least one _dispatch handler call site"

    def test_lint_catches_planted_violation(self):
        from raytpu.analysis.core import run_rule_on_source
        from raytpu.analysis.rules.server_span import ServerSpan

        src = ("async def _dispatch(self, peer, frame):\n"
               "    handler = self._handlers.get(frame.get('m'))\n"
               "    result = handler(peer)\n")
        assert len(run_rule_on_source(ServerSpan(), src)) == 1

        fixed = ("async def _dispatch(self, peer, frame):\n"
                 "    handler = self._handlers.get(frame.get('m'))\n"
                 "    with tracing.span('rpc.server.x'):\n"
                 "        result = handler(peer)\n")
        assert run_rule_on_source(ServerSpan(), fixed) == []


# -- cross-process integration ------------------------------------------------


@pytest.mark.slow
class TestClusterTracing:
    """One trace id across driver -> head -> node -> worker, assembled
    into a single chrome trace with flow arrows (ISSUE acceptance)."""

    @pytest.fixture(scope="class")
    def traced_cluster(self):
        from raytpu.cluster import Cluster

        os.environ[tracing.ENV_VAR] = "1"
        tracing.enable_tracing(sample_rate=1.0)
        tracing.clear_spans()
        c = Cluster(num_nodes=1,
                    node_resources={"num_cpus": 4, "num_tpus": 0})
        c.wait_for_nodes(1)
        yield c
        c.shutdown()
        tracing.disable_tracing()
        tracing.clear_spans()
        os.environ.pop(tracing.ENV_VAR, None)
        os.environ.pop(tracing.SAMPLE_ENV_VAR, None)

    @pytest.fixture
    def driver(self, traced_cluster):
        import raytpu

        raytpu.shutdown()
        raytpu.init(address=f"tcp://{traced_cluster.address}")
        yield raytpu
        raytpu.shutdown()

    def test_one_trace_spans_three_processes(self, driver):
        import raytpu

        @raytpu.remote
        def probe():
            return os.getpid()

        with tracing.span("test.root"):
            worker_pid = raytpu.get(probe.remote(), timeout=60)
        assert worker_pid != os.getpid()
        (root,) = [s for s in tracing.get_spans()
                   if s["name"] == "test.root"]
        trace_id = root["trace_id"]

        # Driver-side chain exists: submit under the root.
        local = [s for s in tracing.get_spans()
                 if s["trace_id"] == trace_id]
        assert any(s["name"] == "task.submit" for s in local)

        # Fan the cluster's buffers in; retry briefly — the worker's
        # span lands after its reply frame is already on the wire.
        deadline = time.monotonic() + 30
        while True:
            from raytpu.runtime import api as _api
            dumps = list(_api._backend_or_none().trace_dump())
            dumps.append(tracing.dump())
            ours = [(d, s) for d in dumps for s in d.get("spans", ())
                    if s.get("trace_id") == trace_id]
            pids = {d["pid"] for d, _s in ours}
            names = {s["name"] for _d, s in ours}
            if len(pids) >= 3 and "worker.task.run" in names:
                break
            if time.monotonic() > deadline:
                pytest.fail(f"trace never spanned 3 processes: "
                            f"pids={pids} names={names}")
            time.sleep(0.5)

        # Parent links stitch across processes: every non-root span's
        # parent exists somewhere in the trace.
        by_id = {s["span_id"]: s for _d, s in ours}
        orphans = [s["name"] for _d, s in ours
                   if s["parent_span_id"]
                   and s["parent_span_id"] not in by_id]
        assert not orphans, f"dangling parent links: {orphans}"

        # The worker's execution span descends from the driver's root.
        def depth_to_root(s, hops=0):
            while s.get("parent_span_id") and hops < 50:
                nxt = by_id.get(s["parent_span_id"])
                if nxt is None:
                    return None
                s, hops = nxt, hops + 1
            return s

        (wspan,) = [s for _d, s in ours if s["name"] == "worker.task.run"]
        assert depth_to_root(wspan)["span_id"] == root["span_id"]

        # Assembled timeline: per-process tracks + cross-process arrows.
        events = tracing.assemble_timeline(dumps)
        labels = [e["args"]["name"] for e in events if e.get("ph") == "M"]
        assert any(lbl.startswith("node") for lbl in labels)
        assert any(lbl.startswith("worker") for lbl in labels)
        flows = [e for e in events if e.get("cat") == "flow"]
        assert any(e["ph"] == "s" for e in flows)
        assert any(e["ph"] == "f" for e in flows)

    def test_latency_histogram_after_workload(self, driver):
        import raytpu

        from raytpu.util import resilience

        @raytpu.remote
        def noop():
            return 1

        with tracing.span("metrics.root"):
            raytpu.get(noop.remote(), timeout=60)
        hist = resilience._metrics.get("raytpu_rpc_client_latency_seconds")
        assert hist, "traced workload must populate the latency histogram"
        methods = {k[0] for k in hist.observations_by_tag}
        assert "submit_task" in methods or "schedule" in methods \
            or "get_object" in methods, methods

    def test_unsampled_trace_records_nothing_cluster_wide(self, driver):
        import raytpu

        @raytpu.remote
        def quiet():
            return 2

        tc = TraceContext.root(sampled=False)
        token = tracing.set_current_trace(tc)
        try:
            raytpu.get(quiet.remote(), timeout=60)
        finally:
            tracing.reset_current_trace(token)
        time.sleep(1.0)  # let worker-side buffers settle
        from raytpu.runtime import api as _api
        dumps = list(_api._backend_or_none().trace_dump())
        dumps.append(tracing.dump())
        leaked = [s["name"] for d in dumps for s in d.get("spans", ())
                  if s.get("trace_id") == tc.trace_id]
        assert not leaked, f"unsampled trace recorded spans: {leaked}"
