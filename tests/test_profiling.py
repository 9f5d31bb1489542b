"""Continuous profiling & performance attribution.

Covers the PR's contracts:

- collapsed-stack folding: same-stack frames across threads merge
  deterministically (thread-pool serials never churn a diff flamegraph);
  merge/diff are sorted-key stable;
- profile shipping: snapshot frames carry per-origin monotonic seq;
  drain/requeue/discard keep the watermark drop accounting exact across
  failed and lost ships (the metrics-shipping contract, applied to
  profiles); buffer overflow drops oldest-first and counts; the
  ``profile.snapshot`` failpoint suppresses a burst without queueing;
- head ProfileStore: seq dedup on reship, malformed-frame rejection,
  per-proc ring + global byte-cap FIFO eviction, dead-proc tombstones
  dropping node/driver/worker rings and rejecting late frames, revive,
  time-windowed merge and recent-vs-baseline diff, per-proc drop rows;
- step attribution: StepProfiler emits the step-time histogram always
  and the MFU gauge only when per-step FLOPs are known (explicit or
  cached per bucket via ``ensure_flops``); peak-FLOPs env override;
- RPC stage timing: with profiling enabled the server dispatch path
  lands recv/decode/queue/handler/encode/send observations into the
  ``raytpu_rpc_stage_seconds{stage,method}`` histogram; disabled, it
  records nothing;
- alert tag selectors: ``metric{tenant=a} > N`` parses, keys the
  evaluator state uniquely, and fires only on the selected series;
- E2E (slow): a 2-node cluster with ``RAYTPU_PROFILE_CONTINUOUS=1``
  answers ``profile_query`` with one merged flamegraph containing
  frames from head, node, and worker processes;
- chaos (slow): SIGKILLing a node mid-profile-ship leaves the store
  consistent — the dead node's procs are tombstoned out and the
  counters still reconcile with the per-proc rows.
"""

import os
import subprocess
import sys
import threading
import time

import pytest

import raytpu
from raytpu.util import failpoints, metrics, profiler, tsdb
from raytpu.util.profstore import ProfileStore
from raytpu.util import stepprof


@pytest.fixture
def prof():
    """Enabled profiler with a clean ship buffer and a fixed identity;
    restores (and disables) on exit."""
    profiler.reset_prof_shipping()
    profiler.enable_profiling()
    old_id = metrics._proc_id[0]
    metrics.set_shipper_identity("node:aaaaaaaaaaaa")
    yield profiler
    profiler.reset_prof_shipping()
    profiler.disable_profiling()
    failpoints.clear()
    metrics._proc_id[0] = old_id


class _Busy:
    """A background thread with a recognizable stack: ``sample_for``
    skips the calling thread, so single-threaded tests see nothing
    without one of these."""

    def __enter__(self):
        self._stop = threading.Event()

        def _spin_target_raytpu_test():
            while not self._stop.is_set():
                sum(i * i for i in range(200))

        self._t = threading.Thread(target=_spin_target_raytpu_test,
                                   name="prof-busy", daemon=True)
        self._t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._t.join(timeout=5)
        return False


def _frame(proc, seq, ts, collapsed=None, samples=1, window=0.1):
    return [proc, seq, ts, dict(collapsed or {"a;b": samples}),
            samples, window]


def _poll(fn, timeout=60.0, period=0.25):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        last = fn()
        if last:
            return last
        time.sleep(period)
    return last


# -- collapsed-stack folding (regression: cross-thread merge) ----------------


class TestFoldThreads:
    def test_same_stack_folds_across_threads(self):
        out = profiler.fold_threads({
            "MainThread;a (f:1);b (f:2)": 3,
            "ThreadPoolExecutor-0_1;a (f:1);b (f:2)": 2,
            "ThreadPoolExecutor-0_2;a (f:1);c (f:3)": 1,
        })
        assert out == {"a (f:1);b (f:2)": 5, "a (f:1);c (f:3)": 1}
        assert list(out) == sorted(out)  # deterministic order

    def test_fold_is_total_preserving(self):
        src = {"t1;x;y": 4, "t2;x;y": 6, "t3;z": 1}
        once = profiler.fold_threads(src)
        assert sum(once.values()) == sum(src.values())

    def test_merge_collapsed_deterministic_and_folding(self):
        a = {"t1;x;y": 1, "t2;x;y": 2}
        b = {"t9;x;y": 3, "t9;z": 4}
        merged = profiler.merge_collapsed([a, b], fold=True)
        assert merged == {"x;y": 6, "z": 4}
        assert profiler.merge_collapsed([b, a], fold=True) == merged

    def test_diff_collapsed_signed_and_zero_elided(self):
        d = profiler.diff_collapsed({"a": 5, "b": 2, "c": 1},
                                    {"a": 2, "b": 2, "d": 3})
        assert d == {"a": 3, "c": 1, "d": -3}  # b==0 elided


# -- shipping: snapshot / drain / requeue / discard --------------------------


class TestProfShipping:
    def test_snapshot_enqueues_identified_frame(self, prof):
        with _Busy():
            assert profiler.prof_snapshot(window_s=0.2, hz=100)
        frames, dropped = profiler.prof_drain()
        assert dropped == 0
        assert len(frames) == 1
        proc, seq, ts, collapsed, samples, window_s = frames[0]
        assert proc == "node:aaaaaaaaaaaa"
        assert seq == 1
        assert samples > 0 and collapsed
        assert any("_spin_target_raytpu_test" in k for k in collapsed)
        # fold_threads already applied: no thread-name prefix survives.
        assert not any(k.startswith("prof-busy;") for k in collapsed)

    def test_seq_is_monotonic_per_process(self, prof):
        with _Busy():
            assert profiler.prof_snapshot(window_s=0.1, hz=100)
            assert profiler.prof_snapshot(window_s=0.1, hz=100)
        frames, _ = profiler.prof_drain()
        assert [f[1] for f in frames] == [1, 2]

    def test_requeue_preserves_order_and_drop_watermark(self, prof):
        with _Busy():
            for _ in range(3):
                assert profiler.prof_snapshot(window_s=0.05, hz=100)
        frames, dropped = profiler.prof_drain()
        assert len(frames) == 3 and dropped == 0
        profiler.prof_requeue(frames, dropped)   # ship failed
        again, dropped2 = profiler.prof_drain()
        assert [f[1] for f in again] == [f[1] for f in frames]
        assert dropped2 == 0

    def test_discard_reowes_lost_frames_exactly_once(self, prof):
        with _Busy():
            for _ in range(2):
                assert profiler.prof_snapshot(window_s=0.05, hz=100)
        frames, dropped = profiler.prof_drain()
        profiler.prof_discard(frames, dropped)   # lost in flight
        with _Busy():
            assert profiler.prof_snapshot(window_s=0.05, hz=100)
        more, dropped2 = profiler.prof_drain()
        assert len(more) == 1
        assert dropped2 == len(frames)           # every loss, exactly once
        _, dropped3 = profiler.prof_drain()
        assert dropped3 == 0                     # and never again

    def test_buffer_overflow_drops_oldest_and_counts(self, prof,
                                                     monkeypatch):
        monkeypatch.setattr(profiler, "_PROF_BUFFER_MAX", 2)
        with _Busy():
            for _ in range(4):
                assert profiler.prof_snapshot(window_s=0.05, hz=100)
        frames, dropped = profiler.prof_drain()
        assert len(frames) == 2
        assert dropped == 2
        assert [f[1] for f in frames] == [3, 4]  # oldest dropped first

    def test_ingest_relays_frames_and_upstream_drops(self, prof):
        f = _frame("worker:aaaaaaaaaaaa.bbbbbbbbbbbb", 1, 1000.0)
        profiler.prof_ingest([f], dropped=3)
        frames, dropped = profiler.prof_drain()
        assert frames == [f]
        assert dropped == 3

    def test_snapshot_failpoint_drops_without_queueing(self, prof):
        failpoints.cfg("profile.snapshot", "drop", env=False)
        try:
            with _Busy():
                assert not profiler.prof_snapshot(window_s=0.05, hz=100)
            assert profiler.prof_pending() == 0
            frames, dropped = profiler.prof_drain()
            assert frames == [] and dropped == 0
        finally:
            failpoints.off("profile.snapshot")

    def test_peek_is_nondestructive(self, prof):
        f = _frame("node:aaaaaaaaaaaa", 1, 1000.0)
        profiler.prof_ingest([f])
        assert profiler.prof_peek() == [f]
        assert profiler.prof_pending() == 1      # still there

    def test_disabled_flag_is_one_boolean(self, prof):
        profiler.disable_profiling()
        assert not profiler.profiling_enabled()
        profiler.enable_profiling()
        assert profiler.profiling_enabled()


# -- head-side ProfileStore ---------------------------------------------------


def _pstore(**over):
    t = over.pop("t", [1000.0])
    kw = dict(max_bytes=1_000_000, ring_slots=8, clock=lambda: t[0])
    kw.update(over)
    return ProfileStore(**kw), t


class TestProfileStore:
    def test_push_dedups_reshipped_frames(self):
        store, _ = _pstore()
        f = _frame("node:aaaaaaaaaaaa", 1, 1000.0, {"a;b": 5}, samples=5)
        assert store.push([f]) == 1
        assert store.push([f]) == 0              # requeued-and-reshipped
        st = store.stats()
        assert st["frames_applied"] == 1
        assert st["frames_deduped"] == 1
        assert store.merged(60.0, now=1001.0)["samples"] == 5

    def test_malformed_frames_counted_not_fatal(self):
        store, _ = _pstore()
        bad = [["node:a", "x", 1.0, {}, 1, 0.1],       # non-int seq
               ["node:a", 1, 1.0, "notadict", 1, 0.1],  # bad collapsed
               ["short"]]
        assert store.push(bad) == 0
        assert store.stats()["frames_dropped"] == 3

    def test_ring_slots_cap_per_proc(self):
        store, _ = _pstore(ring_slots=3)
        for i in range(5):
            store.push([_frame("node:aaaaaaaaaaaa", i + 1,
                               1000.0 + i, {"s": 1})])
        st = store.stats()
        assert st["frames"] == 3
        assert st["frames_evicted"] == 2
        # The survivors are the newest: the merged window over
        # everything sums only 3 samples.
        assert store.merged(600.0, now=1010.0)["samples"] == 3

    def test_byte_cap_evicts_globally_oldest_fifo(self):
        store, _ = _pstore(max_bytes=400, ring_slots=100)
        big = {f"stack-{i:03d};leaf": 1 for i in range(10)}
        for i in range(6):
            proc = "node:aaaaaaaaaaaa" if i % 2 else "node:bbbbbbbbbbbb"
            store.push([_frame(proc, i // 2 + 1, 1000.0 + i, big)])
        st = store.stats()
        assert st["bytes"] <= 400
        assert st["frames_evicted"] > 0
        # The oldest timestamps went first: every survivor is newer
        # than every evicted slot.
        rows = store.proc_rows()
        assert sum(r["frames"] for r in rows) == st["frames"]

    def test_tombstone_drops_node_scoped_procs_and_rejects_late(self):
        store, _ = _pstore()
        node = "aaaaaaaaaaaa"
        store.push([
            _frame(f"node:{node}", 1, 1000.0),
            _frame(f"worker:{node}.bbbbbbbbbbbb", 1, 1000.0),
            _frame(f"driver:{node}", 1, 1000.0),
            _frame("node:cccccccccccc", 1, 1000.0),
        ])
        removed = store.mark_proc_dead(node)
        assert removed == 3
        st = store.stats()
        assert st["dead_procs"] == [node]
        assert {r["proc"] for r in store.proc_rows()} == \
            {"node:cccccccccccc"}
        # A late frame from the dead node is rejected, not applied.
        assert store.push([_frame(f"node:{node}", 2, 1001.0)]) == 0
        assert store.stats()["frames_rejected"] == 1
        # Revive (node re-registered) and shipping resumes.
        store.revive_proc(node)
        assert store.push([_frame(f"node:{node}", 3, 1002.0)]) == 1

    def test_merged_window_filters_by_time_and_proc(self):
        store, _ = _pstore()
        store.push([_frame("node:aaaaaaaaaaaa", 1, 900.0, {"old": 1}),
                    _frame("node:aaaaaaaaaaaa", 2, 995.0, {"new": 2},
                           samples=2),
                    _frame("node:bbbbbbbbbbbb", 1, 996.0, {"new": 4},
                           samples=4)])
        res = store.merged(10.0, now=1000.0)
        assert res["collapsed"] == {"new": 6}
        assert res["procs"] == ["node:aaaaaaaaaaaa", "node:bbbbbbbbbbbb"]
        only_b = store.merged(10.0, procs=["node:bbbbbbbbbbbb"],
                              now=1000.0)
        assert only_b["collapsed"] == {"new": 4}

    def test_diff_is_recent_minus_baseline(self):
        store, _ = _pstore()
        store.push([_frame("node:aaaaaaaaaaaa", 1, 850.0,
                           {"steady": 5, "gone": 3}, samples=8),
                    _frame("node:aaaaaaaaaaaa", 2, 950.0,
                           {"steady": 5, "spike": 7}, samples=12)])
        res = store.diff(recent_s=100.0, now=1000.0)
        assert res["delta"] == {"gone": -3, "spike": 7}

    def test_upstream_drops_attributed_per_proc(self):
        store, _ = _pstore()
        store.note_upstream_drops(4, proc="node:aaaaaaaaaaaa")
        store.note_upstream_drops(2)
        assert store.stats()["upstream_drops"] == 6
        rows = {r["proc"]: r for r in store.proc_rows()}
        assert rows["node:aaaaaaaaaaaa"]["dropped"] == 4


# -- step-level attribution ---------------------------------------------------


class TestStepProfiler:
    def test_observe_step_emits_hist_and_mfu_with_flops(self, monkeypatch):
        monkeypatch.setattr(stepprof, "device_peak_flops", lambda: 1e12)
        sp = stepprof.StepProfiler("train")
        sp.observe_step(0.5, flops=1e11)         # 1e11/0.5/1e12 = 0.2
        assert sp._mfu.value == pytest.approx(0.2)
        sp.observe_step(0.0)                     # no-op, not a crash
        sp.observe_step(0.1)                     # hist only: gauge holds
        assert sp._mfu.value == pytest.approx(0.2)

    def test_mfu_clamped_to_one(self, monkeypatch):
        monkeypatch.setattr(stepprof, "device_peak_flops", lambda: 1e6)
        sp = stepprof.StepProfiler("infer")
        sp.observe_step(0.001, flops=1e9)
        assert sp._mfu.value == 1.0

    def test_ensure_flops_caches_per_key(self):
        sp = stepprof.StepProfiler("train")
        calls = []

        def thunk():
            calls.append(1)
            return 3e9

        assert sp.ensure_flops(("decode", 128, 4), thunk) == 3e9
        assert sp.ensure_flops(("decode", 128, 4), thunk) == 3e9
        assert len(calls) == 1                   # compile-frequency only
        # A failing thunk caches None (no retry storm on the hot path).
        assert sp.ensure_flops(("bad",), lambda: 1 / 0) is None
        assert sp.ensure_flops(("bad",), lambda: 99.0) is None

    def test_mark_interval_timing(self):
        sp = stepprof.StepProfiler("train")
        assert sp.mark() is None                 # first call: no interval
        time.sleep(0.01)
        dt = sp.mark()
        assert dt is not None and dt > 0

    def test_peaks_come_from_the_one_table(self):
        from raytpu.core.chip_specs import chip_spec, generation_spec

        v5e = chip_spec("TPU v5 lite")  # what a v5e chip reports
        assert (v5e.generation, v5e.bf16_flops, v5e.hbm_bytes_per_s,
                v5e.hbm_bytes) == ("v5e", 197e12, 819e9, 16e9)
        assert generation_spec("v5litepod") is v5e
        for unknown in ("cpu", "TPU v9", ""):
            with pytest.raises(ValueError, match="device_kind"):
                chip_spec(unknown)
        with pytest.raises(ValueError, match="generation"):
            generation_spec("v9")

    def test_unknown_device_publishes_no_mfu(self):
        # The CPU has no published peak: the lookup raises, and the
        # always-on gauge stays silent instead of guessing one.
        with pytest.raises(ValueError):
            stepprof.device_peak_flops()
        sp = stepprof.StepProfiler("train")
        before = sp._mfu.value
        sp.observe_step(0.5, flops=1e11)
        assert sp._mfu.value == before

    def test_cost_analysis_flops_positive_or_none(self):
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        f = jax.jit(lambda x: (x @ x).sum())
        got = stepprof.cost_analysis_flops(f, jnp.ones((16, 16)))
        assert got is None or got > 0

    def test_step_profiler_singleton_per_kind(self):
        assert stepprof.step_profiler("train") is \
            stepprof.step_profiler("train")
        assert stepprof.step_profiler("train") is not \
            stepprof.step_profiler("infer")
        with pytest.raises(ValueError):
            stepprof.StepProfiler("batch")


# -- RPC stage timing ---------------------------------------------------------


class TestRpcStageTiming:
    def _counts(self):
        from raytpu.cluster import protocol

        if not protocol._stage_hist:
            return {}
        return {t: len(v) for t, v
                in protocol._stage_hist[0].observations_by_tag.items()}

    def test_stages_recorded_when_enabled(self, prof):
        from raytpu.cluster import protocol
        from raytpu.cluster.protocol import RpcClient, RpcServer

        before = self._counts()
        srv = RpcServer()
        srv.register("add", lambda peer, a, b: a + b)
        addr = srv.start()
        cli = RpcClient(addr)
        try:
            # Stage timing is 1-in-N duty-cycled: run several full
            # sampling periods so timed dispatches are guaranteed.
            for i in range(protocol._STAGE_SAMPLE_EVERY * 3):
                assert cli.call("add", i, 1) == i + 1
        finally:
            cli.close()
            srv.stop()
        after = self._counts()
        # Tag tuples follow tag_keys order: (stage, method).
        grew = {t for t in after
                if after[t] > before.get(t, 0)}
        stages = {stage for stage, method in grew if method == "add"}
        # Every dispatch stage landed for the instrumented method.
        assert {"recv", "decode", "queue", "handler",
                "encode"} <= stages
        assert all(stage in ("recv", "decode", "queue", "handler",
                             "encode", "send") for stage, _ in grew)

    def test_no_stage_observations_when_disabled(self, prof):
        from raytpu.cluster.protocol import RpcClient, RpcServer

        profiler.disable_profiling()
        before = self._counts()
        srv = RpcServer()
        srv.register("add", lambda peer, a, b: a + b)
        addr = srv.start()
        cli = RpcClient(addr)
        try:
            for i in range(3):
                assert cli.call("add", i, 1) == i + 1
        finally:
            cli.close()
            srv.stop()
        assert self._counts() == before


# -- alert-rule tag selectors -------------------------------------------------


class TestAlertTenantSelector:
    def _store(self):
        t = [1000.0]
        return tsdb.MetricStore(max_bytes=1_000_000, fine_step_s=1.0,
                                fine_slots=60, coarse_step_s=5.0,
                                coarse_slots=60, clock=lambda: t[0]), t

    @staticmethod
    def _gframe(proc, seq, ts, name, val, keys=(), vals=()):
        return [proc, seq, ts, [["g", name, list(keys), list(vals), val]]]

    def test_selector_parses_and_names_uniquely(self):
        rules = tsdb.parse_alert_rules(
            "raytpu_tenant_queued{tenant=acme} > 100 for 30s; "
            "raytpu_tenant_queued{tenant=blue} > 100 for 30s; "
            "raytpu_tenant_queued > 500")
        assert [r.tags for r in rules] == \
            [{"tenant": "acme"}, {"tenant": "blue"}, {}]
        assert len({r.name for r in rules}) == 3
        assert "{tenant=acme}" in rules[0].name
        # Quotes are accepted; malformed selectors are loud.
        q = tsdb.parse_alert_rules('m{tenant="x"} > 1')[0]
        assert q.tags == {"tenant": "x"}
        with pytest.raises(ValueError):
            tsdb.parse_alert_rules("m{tenant} > 1")

    def test_selector_fires_only_on_matching_series(self):
        store, t = self._store()
        fired, resolved = [], []
        rules = tsdb.parse_alert_rules(
            "raytpu_tenant_queued{tenant=a} > 5 for 0s")
        ev = tsdb.AlertEvaluator(store, rules,
                                 on_fire=lambda r, v: fired.append((r, v)),
                                 on_resolve=lambda r, v:
                                 resolved.append(r))
        g = self._gframe
        # Tenant b is way over threshold; tenant a is under: no fire.
        store.push([g("node:aaaaaaaaaaaa", 1, 1000.0,
                      "raytpu_tenant_queued", 2.0, ["tenant"], ["a"]),
                    g("node:aaaaaaaaaaaa", 2, 1000.0,
                      "raytpu_tenant_queued", 99.0, ["tenant"], ["b"])])
        ev.tick()
        assert not fired
        # Tenant a breaches: exactly one fire, at tenant a's value.
        t[0] = 1001.0
        store.push([g("node:aaaaaaaaaaaa", 3, 1001.0,
                      "raytpu_tenant_queued", 7.0, ["tenant"], ["a"])])
        ev.tick()
        assert len(fired) == 1
        rule, val = fired[0]
        assert rule.tags == {"tenant": "a"} and val == 7.0
        # Clearing tenant a resolves; tenant b stays irrelevant.
        t[0] = 1002.0
        store.push([g("node:aaaaaaaaaaaa", 4, 1002.0,
                      "raytpu_tenant_queued", 1.0, ["tenant"], ["a"])])
        ev.tick()
        assert resolved and resolved[0].tags == {"tenant": "a"}


# -- E2E: 2-node cluster with continuous profiling on -------------------------


_FAST_PROFILE_ENV = {
    "RAYTPU_PROFILE_CONTINUOUS": "1",
    "RAYTPU_PROFILE_PERIOD_S": "1.0",
    "RAYTPU_PROFILE_WINDOW_S": "0.3",
    "RAYTPU_PROFILE_HZ": "50",
}


@pytest.fixture
def profiled_cluster_env():
    old = {k: os.environ.get(k) for k in _FAST_PROFILE_ENV}
    os.environ.update(_FAST_PROFILE_ENV)
    profiler.enable_profiling()
    profiler.reset_prof_shipping()
    yield
    for k, v in old.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    profiler.stop_continuous()
    profiler.disable_profiling()
    profiler.reset_prof_shipping()


@pytest.mark.slow
class TestContinuousProfilingE2E:
    def test_merged_flamegraph_spans_all_layers(self, profiled_cluster_env):
        from raytpu.cluster.cluster_utils import Cluster
        from raytpu.cluster.protocol import RpcClient

        metrics.enable_metrics_ship(env=True)
        cluster = Cluster()
        head = None
        try:
            cluster.add_node(num_cpus=2, num_tpus=0)
            cluster.add_node(num_cpus=2, num_tpus=0)
            cluster.wait_for_nodes(2)
            raytpu.init(address=cluster.address)
            head = RpcClient(cluster.address)

            @raytpu.remote
            def spin(n):
                acc = 0
                deadline = time.monotonic() + 2.0
                while time.monotonic() < deadline:
                    acc += sum(i * i for i in range(500))
                return n

            # Keep workers busy long enough for several duty cycles.
            futs = [spin.remote(i) for i in range(8)]

            def _layers():
                res = head.call("profile_query", "merged", 600.0)
                ps = set(res.get("procs", ()))
                ok = ("head" in ps
                      and any(p.startswith("node:") for p in ps)
                      and any(p.startswith("worker:") for p in ps))
                return res if ok and res["collapsed"] else None

            res = _poll(_layers, timeout=90)
            assert raytpu.get(futs, timeout=60) == list(range(8))
            assert res, "merged flamegraph missing a process layer"
            assert res["samples"] > 0
            assert sum(res["collapsed"].values()) > 0
            # Stage-timing series reached the cluster TSDB.
            assert _poll(lambda: [
                s for s in head.call("metrics_series",
                                     "raytpu_rpc_stage_seconds")
                if s["tags"].get("stage")], timeout=60)
            # Per-proc inventory behind `raytpu top --profile`.
            stats = head.call("profile_stats")
            assert stats["store"]["frames"] >= len(stats["procs"]) > 0
            # CLI renders the store's merged view from a cold process.
            out = subprocess.run(
                [sys.executable, "-m", "raytpu", "profile",
                 "--continuous", "--address", cluster.address,
                 "--out", "-"],
                capture_output=True, text=True, timeout=60)
            assert out.returncode == 0, out.stderr
            assert any(" " in ln and ln.rsplit(" ", 1)[-1].isdigit()
                       for ln in out.stdout.splitlines())
            # Diff mode answers too (possibly empty delta, but shaped).
            diff = head.call("profile_query", "diff", 600.0, 0.0, 30.0)
            assert "delta" in diff and "recent" in diff
        finally:
            if head is not None:
                head.close()
            raytpu.shutdown()
            cluster.shutdown()


@pytest.mark.slow
class TestProfilingChaos:
    def test_node_sigkill_mid_ship_keeps_store_consistent(
            self, profiled_cluster_env):
        from raytpu.cluster.cluster_utils import Cluster
        from raytpu.cluster.protocol import RpcClient

        metrics.enable_metrics_ship(env=True)
        cluster = Cluster()
        head = None
        try:
            h1 = cluster.add_node(num_cpus=2, num_tpus=0)
            cluster.add_node(num_cpus=2, num_tpus=0)
            cluster.wait_for_nodes(2)
            raytpu.init(address=cluster.address)
            head = RpcClient(cluster.address)

            @raytpu.remote
            def spin(n):
                deadline = time.monotonic() + 1.5
                acc = 0
                while time.monotonic() < deadline:
                    acc += sum(i * i for i in range(500))
                return n

            raytpu.get([spin.remote(i) for i in range(4)], timeout=60)
            # Wait until frames from 2 nodes' procs have shipped.
            assert _poll(lambda: len({
                p.split(":", 1)[1][:12]
                for p in (r["proc"]
                          for r in head.call("profile_stats")["procs"])
                if ":" in p}) >= 2, timeout=90)
            # SIGKILL one node mid-flight.
            cluster.kill_node(h1)

            def _tombstoned():
                st = head.call("profile_stats")["store"]
                return st["dead_procs"] or None

            dead = _poll(_tombstoned, timeout=90)
            assert dead, "dead node never tombstoned in ProfileStore"
            stats = head.call("profile_stats")
            store, rows = stats["store"], stats["procs"]
            # No ring survives for any proc rooted at the dead node.
            for hex12 in store["dead_procs"]:
                for r in rows:
                    assert not r["proc"].startswith(f"node:{hex12}")
                    assert not r["proc"].startswith(f"worker:{hex12}.")
                    assert not r["proc"].startswith(f"driver:{hex12}")
            # Accounting reconciles: live frames equal the per-proc sum,
            # and applied covers everything still held plus evictions.
            assert store["frames"] == sum(r["frames"] for r in rows)
            assert store["frames_applied"] >= store["frames"]
            # The cluster still answers merged queries from survivors.
            res = head.call("profile_query", "merged", 600.0)
            assert all(not p.startswith(f"node:{dead[0]}")
                       for p in res["procs"])
        finally:
            if head is not None:
                head.close()
            raytpu.shutdown()
            cluster.shutdown()
            failpoints.clear()
