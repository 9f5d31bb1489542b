"""raytpulint: the static-analysis framework (raytpu/analysis/).

Covers the PR's contracts:

- every rule has a planted-violation self-test (the rule bites) and a
  clean fixture (the rule does not cry wolf);
- ``# raytpulint: disable=RTPxxx`` same-line suppressions silence a
  finding; ``disable=all`` silences any rule;
- the baseline round-trips through JSON and its fingerprints survive
  unrelated edits (no line numbers in the fingerprint);
- ``--json`` output follows the documented schema;
- the whole-tree run is the tier-1 gate: zero unsuppressed findings
  over ``raytpu/``, each file parsed exactly once, well under 5 s.
"""

import io
import json
import pathlib
import textwrap

import pytest

from raytpu.analysis import cli as lint_cli
from raytpu.analysis.core import (
    Finding,
    all_rules,
    load_baseline,
    run_lint,
    run_rule_on_source,
    save_baseline,
)

REPO = pathlib.Path(__file__).resolve().parent.parent

MIGRATED = {"RTP001", "RTP002", "RTP003", "RTP004"}


def _rule(rid):
    (r,) = all_rules(select=[rid])
    return r


def _src(s):
    return textwrap.dedent(s).lstrip("\n")


# -- registry ----------------------------------------------------------------


class TestRegistry:
    def test_catalogue_shape(self):
        rules = all_rules()
        ids = [r.id for r in rules]
        assert ids == sorted(ids)
        assert len(set(ids)) == len(ids)
        assert MIGRATED <= set(ids)
        assert len(set(ids) - MIGRATED) >= 4  # the new invariants
        for r in rules:
            assert r.id.startswith("RTP") and len(r.id) == 6
            assert r.name and r.invariant and r.rationale
            assert r.scope

    def test_unknown_rule_id_raises(self):
        with pytest.raises(ValueError, match="RTP999"):
            all_rules(select=["RTP999"])

    def test_fresh_instances_per_run(self):
        # Whole-tree rules accumulate state; a second run must not see
        # the first run's accumulation.
        a, b = _rule("RTP003"), _rule("RTP003")
        assert a is not b


# -- per-rule planted violation + clean fixture ------------------------------


class TestTimingLiterals:  # RTP001
    def test_planted(self):
        findings = run_rule_on_source(_rule("RTP001"), _src("""
            import time
            def f(c):
                time.sleep(0.5)
                c.call('x', timeout=5.0)
        """))
        assert len(findings) == 2
        assert all(f.rule == "RTP001" for f in findings)

    def test_clean(self):
        assert run_rule_on_source(_rule("RTP001"), _src("""
            import time
            from raytpu.cluster import constants as tuning
            def f(c):
                time.sleep(tuning.PENDING_POLL_PERIOD_S)
                c.call('x', timeout=tuning.CONTROL_CALL_TIMEOUT_S)
        """)) == []

    def test_registry_file_is_exempt(self):
        assert run_rule_on_source(
            _rule("RTP001"), "import time\ntime.sleep(1.0)\n",
            rel="raytpu/cluster/constants.py") == []


class TestServerSpan:  # RTP002
    def test_planted(self):
        findings = run_rule_on_source(_rule("RTP002"), _src("""
            async def _dispatch(self, peer, frame):
                handler = self._handlers.get(frame.get('m'))
                result = handler(peer)
        """))
        assert len(findings) == 1

    def test_clean(self):
        assert run_rule_on_source(_rule("RTP002"), _src("""
            async def _dispatch(self, peer, frame):
                handler = self._handlers.get(frame.get('m'))
                with tracing.span('rpc.server.x'):
                    result = handler(peer)
        """)) == []


class TestTransitionCoverage:  # RTP003 (whole-tree)
    def test_planted(self):
        from raytpu.util.task_events import TaskTransition

        findings = run_rule_on_source(_rule("RTP003"), _src("""
            from raytpu.util import task_events
            def f():
                task_events.emit('task', 't',
                    task_events.TaskTransition.SUBMITTED)
        """), whole_tree=True)
        missing = {f.message.split()[0] for f in findings}
        assert f"TaskTransition.{TaskTransition.ALL[0]}" not in missing or \
            TaskTransition.ALL[0] != "SUBMITTED"
        assert len(findings) == len(TaskTransition.ALL) - 1

    def test_clean(self):
        from raytpu.util.task_events import TaskTransition

        src = "\n".join(f"x{i} = TaskTransition.{m}"
                        for i, m in enumerate(TaskTransition.ALL))
        assert run_rule_on_source(_rule("RTP003"), src,
                                  whole_tree=True) == []


class TestJitInBuilders:  # RTP004
    def test_planted(self):
        findings = run_rule_on_source(_rule("RTP004"), _src("""
            import jax
            def step(self):
                fn = jax.jit(lambda x: x)
            def _build_decode_fn(self):
                return jax.jit(lambda x: x)
            def _build_loopy(self):
                for _ in range(2):
                    jax.jit(lambda x: x)
        """), rel="raytpu/inference/_planted.py")
        assert len(findings) == 2  # step() and the in-loop builder call

    def test_clean(self):
        assert run_rule_on_source(_rule("RTP004"), _src("""
            import jax
            def _build_decode_fn(self):
                return jax.jit(lambda x: x)
            def step(self):
                return self._decode_fn(1)
        """), rel="raytpu/inference/_planted.py") == []


class TestWirePurity:  # RTP005
    def test_planted_non_primitive_metadata(self):
        findings = run_rule_on_source(_rule("RTP005"), _src("""
            def send(self, make_method, rid):
                frame = {"m": make_method(), "i": rid}
        """))
        assert len(findings) == 1
        assert "non-primitive" in findings[0].message

    def test_planted_unregistered_key(self):
        findings = run_rule_on_source(_rule("RTP005"), _src("""
            def send(self, rid):
                frame = {"m": "call", "i": rid, "q": 2}
                frame["zz"] = 1
        """))
        assert len(findings) == 2
        assert all("unregistered frame field" in f.message
                   for f in findings)

    def test_clean(self):
        # "a" is the payload slot: arbitrary values are allowed there
        # (the codec handles them); metadata must stay primitive.
        assert run_rule_on_source(_rule("RTP005"), _src("""
            def send(self, method, rid, args, tc, dl):
                frame = {"m": method, "i": rid, "a": [args, {}],
                         "tc": tc.to_wire(), "d": float(dl)}
        """)) == []

    def test_all_runtime_keys_are_registered(self):
        from raytpu.cluster import wire

        assert set(wire.FRAME_FIELDS) >= {"m", "a", "i", "d", "tc",
                                          "r", "e", "p"}


class TestContextvarCrossing:  # RTP006
    REL = "raytpu/cluster/node.py"

    def test_planted(self):
        findings = run_rule_on_source(_rule("RTP006"), _src("""
            def kick(self, loop, pool, work):
                loop.run_in_executor(None, work)
                pool.submit(work)
        """), rel=self.REL)
        assert len(findings) == 2

    def test_clean_wrapped_callable(self):
        assert run_rule_on_source(_rule("RTP006"), _src("""
            def kick(self, loop, pool, work):
                tc = tracing.current_trace()
                loop.run_in_executor(None, tracing.run_with_trace,
                                     tc, "hop", work)
                pool.submit(tracing.run_with_trace, tc, "hop", work)
        """), rel=self.REL) == []

    def test_clean_target_reanchors(self):
        # The submitted function itself re-anchors via the stash.
        assert run_rule_on_source(_rule("RTP006"), _src("""
            def _drain(self):
                tc = _pop_task_trace(self)
            def kick(self, pool):
                pool.submit(self._drain)
        """), rel=self.REL) == []

    def test_out_of_scope_file_ignored(self):
        assert run_rule_on_source(
            _rule("RTP006"),
            "def kick(self, pool, work):\n    pool.submit(work)\n",
            rel="raytpu/cluster/transfer.py") == []


class TestBlockingInAsync:  # RTP007
    def test_planted(self):
        findings = run_rule_on_source(_rule("RTP007"), _src("""
            import time, subprocess
            async def handler(self, sock):
                time.sleep(0.1)
                subprocess.run(["ls"])
                sock.recv(4096)
        """))
        assert len(findings) == 3

    def test_clean_nested_sync_def_is_executor_bound(self):
        assert run_rule_on_source(_rule("RTP007"), _src("""
            import time, asyncio
            async def handler(self, loop):
                def blocking():
                    time.sleep(0.1)  # runs on the executor: fine
                await loop.run_in_executor(None, blocking)
                await asyncio.sleep(0.1)
        """)) == []

    def test_sync_code_not_flagged(self):
        assert run_rule_on_source(
            _rule("RTP007"),
            "import time\ndef f():\n    time.sleep(1)\n") == []


class TestEnvRegistry:  # RTP008
    def test_planted_literal_and_alias(self):
        findings = run_rule_on_source(_rule("RTP008"), _src("""
            import os
            _K = "RAYTPU_BOGUS_KNOB_B"
            def f():
                a = os.environ.get("RAYTPU_BOGUS_KNOB_A")
                b = os.getenv(_K)
                if "RAYTPU_BOGUS_KNOB_C" in os.environ:
                    pass
        """))
        assert len(findings) == 3

    def test_planted_dynamic_name(self):
        findings = run_rule_on_source(_rule("RTP008"), _src("""
            import os
            def f(name):
                return os.environ.get(f"RAYTPU_{name}")
        """))
        assert len(findings) == 1
        assert "dynamically-built" in findings[0].message

    def test_clean_declared_names(self):
        assert run_rule_on_source(_rule("RTP008"), _src("""
            import os
            def f():
                a = os.environ.get("RAYTPU_TRACING")
                b = os.getenv("RAYTPU_FAILPOINTS")
                c = os.environ.get("NOT_OURS")  # other namespaces: fine
        """)) == []

    def test_registry_parse_matches_runtime_registry(self):
        from raytpu.analysis.rules.env_registry import declared_env_vars
        from raytpu.core.config import declared_env

        statically = declared_env_vars()
        assert set(declared_env()) <= statically
        # constants.py knobs are in there too
        assert "RAYTPU_CONTROL_CALL_TIMEOUT_S" in statically


class TestSeamSwallow:  # RTP009
    def test_planted_swallowed_rpc(self):
        findings = run_rule_on_source(_rule("RTP009"), _src("""
            def f(self, c):
                try:
                    c.call("x")
                except Exception:
                    pass
        """))
        assert len(findings) == 1
        assert "swallowed" in findings[0].message

    def test_planted_bare_except(self):
        findings = run_rule_on_source(_rule("RTP009"), _src("""
            def f(self):
                try:
                    local_work()
                except:
                    pass
        """))
        assert len(findings) == 1
        assert "bare except" in findings[0].message

    def test_clean_recorded_swallow(self):
        assert run_rule_on_source(_rule("RTP009"), _src("""
            from raytpu.util import errors
            def f(self, c):
                try:
                    c.call("x")
                except Exception as e:
                    errors.swallow("test.seam", e)
        """)) == []

    def test_clean_narrow_handler(self):
        assert run_rule_on_source(_rule("RTP009"), _src("""
            def f(self, c):
                try:
                    c.call("x")
                except ConnectionError:
                    pass
        """)) == []


class TestStepLoopBlocking:  # RTP010
    def test_planted_engine_module_scanned_whole(self):
        findings = run_rule_on_source(_rule("RTP010"), _src("""
            import raytpu, time

            def _run_decode(self, seqs):
                raytpu.get(self.remote_thing.remote())
                time.sleep(0.1)
        """), rel="raytpu/inference/engine.py")
        assert len(findings) == 2
        assert "raytpu.get()" in findings[0].message
        assert "time.sleep()" in findings[1].message

    def test_planted_serving_only_inside_step_loop(self):
        src = _src("""
            import raytpu

            def _step_loop(self):
                raytpu.get(self.handle.remote())

            def generate(self, prompt):
                raytpu.get(self.handle.remote())  # consumer thread: fine
        """)
        findings = run_rule_on_source(_rule("RTP010"), src,
                                      rel="raytpu/inference/serving.py")
        assert len(findings) == 1
        assert findings[0].line == 4  # inside _step_loop only

    def test_clean_condition_wait_is_sanctioned(self):
        assert run_rule_on_source(_rule("RTP010"), _src("""
            def _step_loop(self):
                with self._cv:
                    self._cv.wait(timeout=0.5)
                    outs = self._engine.step()
        """), rel="raytpu/inference/serving.py") == []

    def test_out_of_scope_modules_ignored(self):
        assert run_rule_on_source(_rule("RTP010"), _src("""
            import time

            def anything(self):
                time.sleep(1.0)
        """), rel="raytpu/serve/_private/router.py") == []


class TestCacheGather:  # RTP011
    def test_planted_gather_in_models(self):
        findings = run_rule_on_source(_rule("RTP011"), _src("""
            def decode_step(self, x, k_pages, v_pages, block_tables):
                ks = k_pages[block_tables].reshape(4, -1, 2, 8)
                vs = self.v_pages[idx]
        """), rel="raytpu/models/llama.py")
        assert len(findings) == 2
        assert "k_pages[...]" in findings[0].message
        assert "paged_attention" in findings[0].message

    def test_clean_literal_reads_and_reference_exempt(self):
        assert run_rule_on_source(_rule("RTP011"), _src("""
            def decode_step(self, k_pages, block_tables):
                scratch = k_pages[0]
                head = k_pages[1:3]
                n = k_pages.shape[1]
                tile = k_pages[0, :, 1]

            def _decode_reference(self, k_pages, block_tables):
                ks = k_pages[block_tables]  # sanctioned numerics oracle
        """), rel="raytpu/inference/engine.py") == []

    def test_out_of_scope_ops_layer_ignored(self):
        # The ops layer HOSTS the sanctioned gather; the rule must not
        # reach it.
        assert run_rule_on_source(_rule("RTP011"), _src("""
            def gather_kv_pages(pages, block_tables):
                return pages[block_tables]
        """), rel="raytpu/ops/paged_attention.py") == []


class TestRpcInLoop:  # RTP012
    def test_planted_per_item_call_and_notify(self):
        findings = run_rule_on_source(_rule("RTP012"), _src("""
            def ship(self, specs):
                for spec in specs:
                    self._peer(addr).call("submit_task", blob(spec))
                for loc in locs:
                    self._peer(loc).notify("task_done", spec.task_id)
        """), rel="raytpu/cluster/client.py")
        assert len(findings) == 2
        assert ".call()" in findings[0].message
        assert "submit_batch" in findings[0].message
        assert ".notify()" in findings[1].message

    def test_sanction_on_call_line_and_loop_header(self):
        assert run_rule_on_source(_rule("RTP012"), _src("""
            def teardown(self, nodes):
                for n in nodes:  # rpc-loop-ok: teardown fan-out
                    self._client(n).call("drain_node")
                for n in nodes:
                    self._client(n).call("stop")  # rpc-loop-ok: cold path
        """), rel="raytpu/cluster/head.py") == []

    def test_iterator_call_and_while_retry_not_flagged(self):
        # One list_nodes RPC feeding the loop is not per-item fan-out,
        # and while loops retry ONE call — both are out of scope.
        assert run_rule_on_source(_rule("RTP012"), _src("""
            def scan(self):
                for n in self._head.call("list_nodes"):
                    use(n)
                while not done:
                    done = self._head.call("ping")
        """), rel="raytpu/cluster/node.py") == []

    def test_nested_callback_def_not_flagged(self):
        # A def inside the loop runs later (callback), not per item.
        assert run_rule_on_source(_rule("RTP012"), _src("""
            def subscribe_all(self, topics):
                for t in topics:
                    def _cb(data):
                        self._head.call("ack", t)
                    self._subs[t] = _cb
        """), rel="raytpu/cluster/client.py") == []

    def test_out_of_scope_module_ignored(self):
        assert run_rule_on_source(_rule("RTP012"), _src("""
            def fan(self, peers):
                for p in peers:
                    p.call("ping")
        """), rel="raytpu/cluster/relay.py") == []


class TestSchedulerPurity:  # RTP013
    def test_planted_rpc_in_schedule_locked(self):
        # _schedule_locked's whole body is the critical section (its
        # contract is "caller holds self._lock").
        findings = run_rule_on_source(_rule("RTP013"), _src("""
            def _schedule_locked(self, resources, arg_oids=None):
                entry = self._pick(resources)
                self._node_client(entry.node_id).notify("push_request", {})
                return entry.node_id
        """), rel="raytpu/cluster/head.py")
        assert len(findings) == 1
        assert ".notify()" in findings[0].message
        assert "deferred" in findings[0].message

    def test_planted_io_under_lock_in_submit_batch(self):
        findings = run_rule_on_source(_rule("RTP013"), _src("""
            def _submit_batch(self, peer, blob):
                specs = wire.loads(blob)
                with self._lock:
                    for spec in specs:
                        peer.push("push_requests", {"oid": spec.task_id})
                        open("/tmp/sched.log", "a")
                return []
        """), rel="raytpu/cluster/head.py")
        assert len(findings) == 2
        assert ".push()" in findings[0].message
        assert "open()" in findings[1].message

    def test_clean_deferred_after_lock_release(self):
        # The shipped pattern: pure compute under the lock, side effects
        # queued on `deferred` and fired after release.
        assert run_rule_on_source(_rule("RTP013"), _src("""
            def _schedule_locked(self, resources, deferred=None):
                best = sorted(self._nodes.values())[0]
                if deferred is not None:
                    deferred.append((best.node_id, "oid", best.address))
                return best.node_id

            def _schedule_impl(self, peer, resources):
                deferred = []
                with self._lock:
                    node_id = self._schedule_locked(resources, deferred)
                for nid, oh, addr in deferred:
                    self._node_client(nid, addr).notify("push_request", {})
                return node_id
        """), rel="raytpu/cluster/head.py") == []

    def test_out_of_scope_module_ignored(self):
        # Only the head hosts the placement lock; other modules may hold
        # their own _lock around RPCs.
        assert run_rule_on_source(_rule("RTP013"), _src("""
            def _submit_batch(self, peer, blob):
                with self._lock:
                    self._head.call("submit_batch", blob)
        """), rel="raytpu/cluster/client.py") == []

    def test_real_tree_is_clean(self):
        res = run_lint(select=["RTP013"], use_baseline=False)
        assert res.findings == []


class TestBlobMaterialization:  # RTP014
    def test_planted_to_bytes(self):
        findings = run_rule_on_source(_rule("RTP014"), _src("""
            def _h_fetch_object(self, peer, oid_hex):
                sv = self.store.try_get(oid_hex)
                return sv.to_bytes()
        """), rel="raytpu/cluster/transfer.py")
        assert len(findings) == 1
        assert ".to_bytes()" in findings[0].message

    def test_planted_bytes_join_and_dumps(self):
        findings = run_rule_on_source(_rule("RTP014"), _src("""
            import pickle

            def assemble(parts, value):
                blob = b"".join(parts)
                alt = bytes().join(parts)
                payload = pickle.dumps(value)
                return blob, alt, payload
        """), rel="raytpu/runtime/object_store.py")
        assert len(findings) == 3
        assert "join" in findings[0].message
        assert "join" in findings[1].message
        assert "pickle.dumps" in findings[2].message

    def test_wire_framing_to_bytes_not_flagged(self):
        # int.to_bytes(4, "little") IS the segment framing, not a flatten.
        assert run_rule_on_source(_rule("RTP014"), _src("""
            def frame(header):
                return len(header).to_bytes(4, "little")
        """), rel="raytpu/cluster/transfer.py") == []

    def test_sanctioned_line_passes(self):
        assert run_rule_on_source(_rule("RTP014"), _src("""
            def push_small(client, oid_hex, sv):
                client.call("put_object", oid_hex, sv.to_bytes())  # blob-ok: small object, single wire frame
        """), rel="raytpu/cluster/transfer.py") == []

    def test_out_of_scope_module_ignored(self):
        # serialization.py legitimately flattens (to_bytes is defined
        # there); only the transfer/store/node paths are policed.
        assert run_rule_on_source(_rule("RTP014"), _src("""
            def to_wire(sv):
                return sv.to_bytes()
        """), rel="raytpu/runtime/serialization.py") == []

    def test_real_tree_is_clean(self):
        res = run_lint(select=["RTP014"], use_baseline=False)
        assert res.findings == []


class TestMetricRegistry:  # RTP015
    def test_planted_undeclared_name(self):
        findings = run_rule_on_source(_rule("RTP015"), _src("""
            from raytpu.util.metrics import Counter

            c = Counter("raytpu_bogus_total", "not in the registry")
        """))
        assert len(findings) == 1
        assert "raytpu_bogus_total" in findings[0].message
        assert "DECLARED_METRICS" in findings[0].message

    def test_planted_attribute_form_with_alias(self):
        findings = run_rule_on_source(_rule("RTP015"), _src("""
            from raytpu.util import metrics as m

            g = m.Gauge("raytpu_nope", "undeclared")
        """))
        assert len(findings) == 1
        assert "raytpu_nope" in findings[0].message

    def test_planted_dynamic_name(self):
        findings = run_rule_on_source(_rule("RTP015"), _src("""
            from raytpu.util.metrics import Histogram

            def make(suffix):
                return Histogram(f"raytpu_{suffix}_seconds", "dyn")
        """))
        assert len(findings) == 1
        assert "dynamically-built" in findings[0].message

    def test_declared_name_clean(self):
        assert run_rule_on_source(_rule("RTP015"), _src("""
            from raytpu.util import metrics

            done = metrics.Counter("raytpu_tasks_done_total", "ok")
        """)) == []

    def test_collections_counter_not_flagged(self):
        # Only constructors traceably bound to raytpu.util.metrics count.
        assert run_rule_on_source(_rule("RTP015"), _src("""
            from collections import Counter

            c = Counter()
            c["raytpu_whatever_total"] += 1
        """)) == []

    def test_registry_file_is_exempt(self):
        assert run_rule_on_source(_rule("RTP015"), _src("""
            from raytpu.util.metrics import Counter

            c = Counter("raytpu_self_total", "the registry defines these")
        """), rel="raytpu/util/metrics.py") == []

    def test_real_tree_is_clean(self):
        res = run_lint(select=["RTP015"], use_baseline=False)
        assert res.findings == []


class TestSeamSwallowTrainScope:  # RTP009, raytpu/train/ extension
    def test_planted_gang_teardown_swallow(self):
        findings = run_rule_on_source(_rule("RTP009"), _src("""
            def teardown(self, workers):
                for w in workers:
                    try:
                        raytpu.kill(w)
                    except Exception:
                        pass
        """), rel="raytpu/train/trainer.py")
        assert len(findings) == 1
        assert "swallowed" in findings[0].message

    def test_clean_recorded_gang_teardown(self):
        assert run_rule_on_source(_rule("RTP009"), _src("""
            from raytpu.util import errors

            def teardown(self, workers):
                for w in workers:
                    try:
                        raytpu.kill(w)
                    except Exception as e:
                        errors.swallow("train.gang_teardown", e)
        """), rel="raytpu/train/trainer.py") == []

    def test_out_of_scope_module_ignored(self):
        # Same planted source outside cluster/ and train/: no finding.
        assert run_rule_on_source(_rule("RTP009"), _src("""
            def f(self, c):
                try:
                    c.call("x")
                except Exception:
                    pass
        """), rel="raytpu/util/whatever.py") == []


class TestPersistCoverage:  # RTP016
    def test_planted_unpaired_mutation(self):
        findings = run_rule_on_source(_rule("RTP016"), _src("""
            class Head:
                def _register_actor(self, aid, info):
                    with self._lock:
                        self._actors[aid] = info
        """), rel="raytpu/cluster/head.py")
        assert len(findings) == 1
        assert "_persist_actor" in findings[0].message

    def test_planted_pop_without_persist(self):
        findings = run_rule_on_source(_rule("RTP016"), _src("""
            class Head:
                def _forget(self, tid):
                    self._pending_specs.pop(tid, None)
        """), rel="raytpu/cluster/head.py")
        assert len(findings) == 1
        assert "_persist_pending_task" in findings[0].message

    def test_clean_paired_mutation(self):
        assert run_rule_on_source(_rule("RTP016"), _src("""
            class Head:
                def _kv_put(self, key, value):
                    with self._lock:
                        self._kv[key] = value
                    self._persist_kv(key, value)
        """), rel="raytpu/cluster/head.py") == []

    def test_clean_deferred_persist_after_lock(self):
        # RTP013 pushes the store write past the lock release; the
        # pairing only needs to land in the same function.
        assert run_rule_on_source(_rule("RTP016"), _src("""
            class Head:
                def _submit(self, specs):
                    persist = []
                    with self._lock:
                        for tid, blob in specs:
                            self._pending_specs[tid] = blob
                            persist.append(tid)
                    for tid in persist:
                        self._persist_pending_task(tid)
        """), rel="raytpu/cluster/head.py") == []

    def test_exempt_reload_and_snapshot(self):
        assert run_rule_on_source(_rule("RTP016"), _src("""
            class Head:
                def _reload(self):
                    for k, v in self._store.load_all("kv"):
                        self._kv[k] = v

                def _snapshot(self):
                    self._actors["tmp"] = {}
        """), rel="raytpu/cluster/head.py") == []

    def test_other_cluster_modules_out_of_scope(self):
        assert run_rule_on_source(_rule("RTP016"), _src("""
            class Node:
                def f(self):
                    self._actors["x"] = 1
        """), rel="raytpu/cluster/node.py") == []

    def test_real_tree_is_clean(self):
        res = run_lint(select=["RTP016"], use_baseline=False)
        assert res.findings == []


class TestWalCoverage:  # RTP017
    def test_planted_unshipped_table(self):
        findings = run_rule_on_source(_rule("RTP017"), _src("""
            WAL_SHIP_TABLES = ("kv", "meta")

            class Head:
                def _persist_actor(self, aid, blob):
                    self._store.put("actors", aid, blob)
        """), rel="raytpu/cluster/head.py")
        assert len(findings) == 1
        assert "'actors'" in findings[0].message
        assert "WAL_SHIP_TABLES" in findings[0].message

    def test_planted_unshipped_snapshot(self):
        findings = run_rule_on_source(_rule("RTP017"), _src("""
            WAL_SHIP_TABLES = ("kv",)

            class Head:
                def _snapshot(self):
                    self._store.snapshot_table("objects", {})
        """), rel="raytpu/cluster/head.py")
        assert len(findings) == 1
        assert "'objects'" in findings[0].message

    def test_missing_ship_tuple_is_a_finding(self):
        findings = run_rule_on_source(_rule("RTP017"), _src("""
            class Head:
                def _kv_put(self, key, value):
                    self._store.put("kv", key, value)
        """), rel="raytpu/cluster/head.py")
        assert len(findings) == 1
        assert "source of truth" in findings[0].message

    def test_clean_shipped_tables(self):
        assert run_rule_on_source(_rule("RTP017"), _src("""
            WAL_SHIP_TABLES = ("kv", "actors")

            class Head:
                def _kv_put(self, key, value):
                    self._store.put("kv", key, value)

                def _drop_actor(self, aid):
                    self._store.delete("actors", aid)
        """), rel="raytpu/cluster/head.py") == []

    def test_non_literal_table_arg_skipped(self):
        assert run_rule_on_source(_rule("RTP017"), _src("""
            WAL_SHIP_TABLES = ("kv",)

            class Head:
                def _generic(self, table, key, value):
                    self._store.put(table, key, value)
        """), rel="raytpu/cluster/head.py") == []

    def test_other_modules_out_of_scope(self):
        # The standby's follower-local cursor table is deliberately not
        # shipped; the rule only audits head.py.
        assert run_rule_on_source(_rule("RTP017"), _src("""
            class StandbyHead:
                def _persist_local(self):
                    self._store.put("standby", "state", b"{}")
        """), rel="raytpu/cluster/standby.py") == []

    def test_real_tree_is_clean(self):
        res = run_lint(select=["RTP017"], use_baseline=False)
        assert res.findings == []


class TestTenantStamping:  # RTP018
    def test_planted_unstamped_spec(self):
        findings = run_rule_on_source(_rule("RTP018"), _src("""
            def submit(self, fn_ref, args):
                spec = TaskSpec(
                    task_id=TaskID.from_random(),
                    function_ref=fn_ref,
                    args=args,
                )
                return spec
        """), rel="raytpu/runtime/remote_function.py")
        assert len(findings) == 1
        assert "tenant=" in findings[0].message

    def test_clean_explicit_tenant(self):
        assert run_rule_on_source(_rule("RTP018"), _src("""
            def submit(self, fn_ref, args):
                return TaskSpec(
                    task_id=TaskID.from_random(),
                    function_ref=fn_ref,
                    tenant=tenancy.current_tenant(),
                )
        """), rel="raytpu/runtime/remote_function.py") == []

    def test_inline_suppression_with_reason(self):
        assert run_rule_on_source(_rule("RTP018"), _src("""
            def rebuild(self, fields):
                spec = TaskSpec(  # raytpulint: disable=RTP018 tenant rides the frame
                    task_id=fields['tid'],
                )
                return spec
        """), rel="raytpu/cluster/node.py") == []

    def test_double_star_forward_is_clean(self):
        # Decode/clone paths forward an already-stamped spec; the
        # mapping is opaque statically and must not false-positive.
        assert run_rule_on_source(_rule("RTP018"), _src("""
            def clone(self, spec):
                return TaskSpec(**spec.as_dict())
        """), rel="raytpu/runtime/remote_function.py") == []

    def test_definition_module_exempt(self):
        assert run_rule_on_source(_rule("RTP018"), _src("""
            def _decode(fields):
                return TaskSpec(fields[0], fields[1])
        """), rel="raytpu/runtime/task_spec.py") == []

    def test_real_tree_is_clean(self):
        res = run_lint(select=["RTP018"], use_baseline=False)
        assert res.findings == []


class TestProfileSitePurity:  # RTP019
    def test_planted_unguarded_emission(self):
        findings = run_rule_on_source(_rule("RTP019"), _src("""
            def flush(self):
                frames, dropped = profiler.prof_drain()
                self.node.notify("report_profile", frames, dropped)
        """), rel="raytpu/cluster/x.py")
        assert len(findings) == 1
        assert "prof_drain" in findings[0].message

    def test_clean_guarded_emission(self):
        assert run_rule_on_source(_rule("RTP019"), _src("""
            def flush(self):
                if profiler.profiling_enabled():
                    frames, dropped = profiler.prof_drain()
                    self.node.notify("report_profile", frames, dropped)
        """), rel="raytpu/cluster/x.py") == []

    def test_clean_anded_guard_and_nested_if(self):
        assert run_rule_on_source(_rule("RTP019"), _src("""
            def dispatch(self, marks, method):
                if marks is not None and profiling_enabled():
                    if method != "ping":
                        _observe_rpc_stages(method, marks)
        """), rel="raytpu/cluster/x.py") == []

    def test_early_return_style_is_flagged(self):
        # `if not profiling_enabled(): return` leaves the emission
        # outside the guard's body — the if-wrapped form is mandated.
        findings = run_rule_on_source(_rule("RTP019"), _src("""
            def flush(self):
                if not profiling_enabled():
                    return
                prof_snapshot()
        """), rel="raytpu/cluster/x.py")
        assert len(findings) == 1
        assert "prof_snapshot" in findings[0].message

    def test_double_flag_check_is_flagged(self):
        findings = run_rule_on_source(_rule("RTP019"), _src("""
            def flush(self):
                if profiling_enabled() and profiling_enabled():
                    prof_snapshot()
        """), rel="raytpu/cluster/x.py")
        assert len(findings) == 1
        assert "2 times" in findings[0].message

    def test_else_branch_is_not_guarded(self):
        findings = run_rule_on_source(_rule("RTP019"), _src("""
            def flush(self):
                if profiling_enabled():
                    prof_snapshot()
                else:
                    prof_drain()
        """), rel="raytpu/cluster/x.py")
        assert len(findings) == 1
        assert "prof_drain" in findings[0].message

    def test_loss_accounting_calls_need_no_guard(self):
        # requeue/discard/ingest must run even when the local flag is
        # off (a relay never eats another process's frames).
        assert run_rule_on_source(_rule("RTP019"), _src("""
            def on_ship_failed(self, frames, dropped):
                profiler.prof_requeue(frames, dropped)
                profiler.prof_discard([], 0)
                profiler.prof_ingest(frames, dropped)
        """), rel="raytpu/cluster/x.py") == []

    def test_real_tree_is_clean(self):
        res = run_lint(select=["RTP019"], use_baseline=False)
        assert res.findings == []


class TestKVShipping:  # RTP020
    def test_planted_tobytes(self):
        findings = run_rule_on_source(_rule("RTP020"), _src("""
            def read(self, hid, offset, length):
                page = self.engine.cache.k[0][3]
                return page.tobytes()
        """), rel="raytpu/inference/disagg.py")
        assert len(findings) == 1
        assert ".tobytes()" in findings[0].message

    def test_planted_whole_pool_gather(self):
        findings = run_rule_on_source(_rule("RTP020"), _src("""
            import numpy as np

            def snapshot(cache):
                whole = np.asarray(cache.k)
                layer = np.ascontiguousarray(cache.v[0])
                return whole, layer
        """), rel="raytpu/inference/disagg.py")
        assert len(findings) == 2
        assert all("whole-pool" in f.message for f in findings)

    def test_planted_join_and_dumps(self):
        findings = run_rule_on_source(_rule("RTP020"), _src("""
            import pickle

            def assemble(chunks, pool):
                blob = b"".join(chunks)
                payload = pickle.dumps(pool)
                return blob, payload
        """), rel="raytpu/serve/_private/prefix_router.py")
        assert len(findings) == 2
        assert "join" in findings[0].message
        assert "pickle.dumps" in findings[1].message

    def test_page_granular_read_not_flagged(self):
        # Two subscripts deep == one page, [page_size, kv_heads *
        # head_dim]: the sanctioned streaming grain. disagg._segment_view
        # reads it from the export's own copy of its pinned pages (a step
        # consumes the pool arrays), which the rule watches like a pool.
        assert run_rule_on_source(_rule("RTP020"), _src("""
            import numpy as np

            def segment(cache, layer, page):
                return np.ascontiguousarray(
                    np.asarray(cache.k[layer][page])).view(np.uint8)

            def segment_of_export(ex, kind, layer, pidx):
                held = ex.held_k if kind == 0 else ex.held_v
                return np.ascontiguousarray(
                    np.asarray(held[layer][pidx])).view(np.uint8)
        """), rel="raytpu/inference/disagg.py") == []

    def test_planted_gather_of_an_exports_held_layer(self):
        findings = run_rule_on_source(_rule("RTP020"), _src("""
            import numpy as np

            def snapshot(ex):
                return np.asarray(ex.held_k[0]), np.asarray(ex.held_v)
        """), rel="raytpu/inference/disagg.py")
        assert len(findings) == 2
        assert all("whole-pool" in f.message for f in findings)

    def test_wire_framing_to_bytes_not_flagged(self):
        assert run_rule_on_source(_rule("RTP020"), _src("""
            def frame(n):
                return int(n).to_bytes(4, "little")
        """), rel="raytpu/inference/disagg.py") == []

    def test_sanctioned_line_passes(self):
        assert run_rule_on_source(_rule("RTP020"), _src("""
            def debug_dump(page):
                return page.tobytes()  # kv-ship-ok: offline debug tool, one page
        """), rel="raytpu/inference/disagg.py") == []

    def test_out_of_scope_module_ignored(self):
        assert run_rule_on_source(_rule("RTP020"), _src("""
            def flatten(arr):
                return arr.tobytes()
        """), rel="raytpu/runtime/serialization.py") == []

    def test_real_tree_is_clean(self):
        res = run_lint(select=["RTP020"], use_baseline=False)
        assert res.findings == []


# -- suppressions ------------------------------------------------------------


class TestSuppressions:
    def test_same_line_disable_silences_one_rule(self):
        src = ("import time\n"
               "def f():\n"
               "    time.sleep(0.5)  # raytpulint: disable=RTP001\n")
        assert run_rule_on_source(_rule("RTP001"), src) == []

    def test_disable_all(self):
        src = ("import time\n"
               "def f():\n"
               "    time.sleep(0.5)  # raytpulint: disable=all\n")
        assert run_rule_on_source(_rule("RTP001"), src) == []

    def test_wrong_rule_id_does_not_silence(self):
        src = ("import time\n"
               "def f():\n"
               "    time.sleep(0.5)  # raytpulint: disable=RTP002\n")
        assert len(run_rule_on_source(_rule("RTP001"), src)) == 1

    def test_suppressed_findings_are_counted_not_dropped(self):
        # Whole-tree scan: the two sanctioned RTP006 exemptions (proxy
        # notify relay, worker _offload) surface as suppressed, so a
        # grep for mass-suppression regressions stays possible.
        result = run_lint(select=["RTP006"], use_baseline=False)
        assert len(result.suppressed) == 2
        assert {f.path for f in result.suppressed} == {
            "raytpu/cluster/driver_proxy.py",
            "raytpu/cluster/worker_proc.py"}


# -- baseline ----------------------------------------------------------------


class TestBaseline:
    def test_round_trip(self, tmp_path):
        f1 = Finding("RTP001", "raytpu/cluster/x.py", 10, 4, "msg one")
        f2 = Finding("RTP009", "raytpu/cluster/y.py", 20, 0, "msg two")
        path = tmp_path / "baseline.json"
        save_baseline([f1, f2, f1], path)
        data = json.loads(path.read_text())
        assert data["version"] == 1
        assert len(data["fingerprints"]) == 2  # deduped
        assert load_baseline(path) == {f1.fingerprint, f2.fingerprint}

    def test_fingerprint_survives_line_moves(self):
        a = Finding("RTP001", "raytpu/cluster/x.py", 10, 4, "msg")
        b = Finding("RTP001", "raytpu/cluster/x.py", 99, 0, "msg")
        assert a.fingerprint == b.fingerprint

    def test_missing_baseline_is_empty(self, tmp_path):
        assert load_baseline(tmp_path / "nope.json") == set()

    def test_baselined_finding_is_partitioned_out(self, tmp_path):
        # Plant a real violating file inside the package, baseline it,
        # and verify the finding moves to the baselined bucket — then
        # shift its line and verify the fingerprint still matches.
        planted = REPO / "raytpu" / "cluster" / "_lint_baseline_probe.py"
        base = tmp_path / "baseline.json"
        body = "import time\n\n\ndef probe():\n    time.sleep(0.5)\n"
        try:
            planted.write_text(body)
            r = run_lint(select=["RTP001"], use_baseline=False)
            mine = [f for f in r.findings
                    if f.path.endswith("_lint_baseline_probe.py")]
            assert len(mine) == 1
            save_baseline(mine, base)
            r2 = run_lint(select=["RTP001"], baseline_path=base)
            assert r2.ok
            assert [f.path for f in r2.baselined] == [mine[0].path]
            # unrelated edit shifts the line: fingerprint still matches
            planted.write_text("# shifted\n" + body)
            r3 = run_lint(select=["RTP001"], baseline_path=base)
            assert r3.ok and len(r3.baselined) == 1
        finally:
            planted.unlink(missing_ok=True)

    def test_checked_in_baseline_is_empty(self):
        # The acceptance bar: a clean tree, not a grandfathered one.
        from raytpu.analysis.core import default_baseline_path

        assert load_baseline(default_baseline_path()) == set()


# -- CLI ---------------------------------------------------------------------


class TestCli:
    def _run(self, argv):
        out = io.StringIO()
        import argparse

        parser = argparse.ArgumentParser()
        lint_cli.add_arguments(parser)
        code = lint_cli.run(parser.parse_args(argv), out=out)
        return code, out.getvalue()

    def test_json_schema(self):
        code, text = self._run(["--json", str(REPO / "raytpu")])
        data = json.loads(text)
        assert code == 0 and data["ok"] is True
        assert data["version"] == 1
        assert data["findings"] == [] and data["errors"] == []
        stats = data["stats"]
        assert set(stats) == {"files_scanned", "parse_count",
                              "suppressed", "baselined", "elapsed_s"}
        assert stats["parse_count"] == stats["files_scanned"] > 100

    def test_json_finding_shape(self, tmp_path):
        planted = REPO / "raytpu" / "cluster" / "_lint_json_probe.py"
        try:
            planted.write_text(
                "import time\n\n\ndef probe():\n    time.sleep(0.5)\n")
            code, text = self._run(
                ["--json", "--select", "RTP001", str(planted)])
            data = json.loads(text)
            assert code == 1 and data["ok"] is False
            (f,) = data["findings"]
            assert set(f) == {"rule", "path", "line", "col", "message"}
            assert f["rule"] == "RTP001" and f["line"] == 5
        finally:
            planted.unlink(missing_ok=True)

    def test_list_rules(self):
        code, text = self._run(["--list-rules"])
        assert code == 0
        for rid in sorted(MIGRATED) + ["RTP005", "RTP009"]:
            assert rid in text

    def test_unknown_select_is_usage_error(self):
        code, _ = self._run(["--select", "RTP999"])
        assert code == 2

    def test_module_entrypoint_and_cli_subcommand_agree(self):
        import raytpu.analysis.__main__  # noqa: F401  (import side check)
        from raytpu.scripts.cli import build_parser

        args = build_parser().parse_args(["lint", "--list-rules"])
        assert args.fn(args) == 0


# -- whole-tree gate (tier-1) ------------------------------------------------


class TestWholeTree:
    def test_tree_is_clean_parse_once_and_fast(self):
        result = run_lint()
        assert result.errors == []
        assert result.findings == [], (
            "raytpulint found unsuppressed violations:\n  "
            + "\n  ".join(str(f) for f in result.findings))
        assert result.files_scanned > 100
        assert result.parse_count == result.files_scanned
        assert result.elapsed_s < 5.0
