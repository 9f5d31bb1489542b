"""Resilience layer: retry policies, circuit breakers, deadlines.

Every timing behavior here is pinned deterministically — seeded jitter
makes the backoff schedule exact, injected clocks make breaker cooldowns
instant, and failpoints (PR 1) make transport faults repeatable. Chaos
sections assert on failpoint hit counters instead of sleeping and hoping.

Reference analogues: gRPC retry/deadline semantics (deadlines shrink
monotonically across hops; DEADLINE_EXCEEDED fails locally), Hystrix /
resilience4j breaker lifecycle (closed → open → half-open → closed).
"""

import threading
import time

import pytest

from raytpu.cluster import constants as tuning
from raytpu.cluster import wire
from raytpu.cluster.protocol import ConnectionLost, RpcClient, RpcServer
from raytpu.util import failpoints
from raytpu.util.errors import (
    CircuitOpenError,
    DeadlineExceeded,
    FatalError,
    NodeVanishedError,
    PlacementInfeasibleError,
    RetryableError,
    RpcTimeoutError,
    is_retryable,
)
from raytpu.util.resilience import (
    CLOSED,
    HALF_OPEN,
    OPEN,
    CircuitBreaker,
    Deadline,
    RetryPolicy,
    breaker_for,
    current_deadline,
    reset_breakers,
)


@pytest.fixture(autouse=True)
def _clean_state():
    """Breakers are process-global (per-peer registry) and failpoints are
    process-global: both reset per test."""
    reset_breakers()
    yield
    reset_breakers()
    failpoints.clear()


class _FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture
def echo_server():
    srv = RpcServer()
    srv.register("echo", lambda peer, x: x)
    srv.register("remaining", lambda peer: (
        current_deadline().remaining()
        if current_deadline() is not None else None))
    addr = srv.start()
    client = RpcClient(addr)
    yield srv, addr, client
    client.close()
    srv.stop()


# -- error taxonomy (satellite: typed retry signals) -------------------------


class TestErrorTaxonomy:
    def test_classification_table(self):
        assert is_retryable(NodeVanishedError("ab12"))
        assert is_retryable(PlacementInfeasibleError("no fit"))
        assert is_retryable(RpcTimeoutError("m", "peer"))
        assert is_retryable(ConnectionError("x"))
        assert is_retryable(TimeoutError("x"))
        assert is_retryable(OSError("x"))
        assert is_retryable(ConnectionLost("x"))  # structural match
        assert not is_retryable(CircuitOpenError("peer"))
        assert not is_retryable(ValueError("x"))
        assert not is_retryable(KeyError("x"))

    def test_deadline_exceeded_is_fatal_despite_timeouterror_base(self):
        # DeadlineExceeded subclasses TimeoutError (so except TimeoutError
        # consumers still catch it) but must never be retried: the budget
        # is the same on every attempt.
        e = DeadlineExceeded("op", budget_s=1.0)
        assert isinstance(e, TimeoutError)
        assert isinstance(e, FatalError)
        assert not is_retryable(e)

    def test_node_vanished_attrs(self):
        e = NodeVanishedError("ab12cd", detail="raced with death sweep")
        assert e.node_id_hex == "ab12cd"
        assert isinstance(e, RetryableError)
        assert "ab12cd" in str(e)

    def test_typed_errors_cross_the_wire(self):
        # The raytpu module prefix is on the wire allowlist: a typed error
        # raised in a remote handler arrives as the same *type* at the
        # caller, so retry classification survives the hop.
        for exc in (PlacementInfeasibleError("pg does not fit"),
                    NodeVanishedError("ab12"),
                    DeadlineExceeded("op", budget_s=0.5),
                    CircuitOpenError("host:1", open_for_s=1.0)):
            back = wire.loads(wire.dumps({"e": exc}))["e"]
            assert type(back) is type(exc)
            assert is_retryable(back) == is_retryable(exc)


# -- deadlines ---------------------------------------------------------------


class TestDeadline:
    def test_remaining_and_expiry(self):
        clk = _FakeClock()
        d = Deadline.after(2.0, clock=clk)
        assert d.remaining() == pytest.approx(2.0)
        assert not d.expired
        clk.advance(2.5)
        assert d.remaining() == pytest.approx(-0.5)
        assert d.expired
        with pytest.raises(DeadlineExceeded) as ei:
            d.check("test op")
        assert ei.value.overrun_s == pytest.approx(0.5)
        assert "test op" in str(ei.value)

    def test_bound_shrinks_timeouts(self):
        clk = _FakeClock()
        d = Deadline.after(1.0, clock=clk)
        # None (wait forever) becomes the remaining budget,
        assert d.bound(None) == pytest.approx(1.0)
        # larger timeouts shrink to it,
        assert d.bound(30.0) == pytest.approx(1.0)
        # smaller timeouts pass through,
        assert d.bound(0.25) == pytest.approx(0.25)
        # and a spent budget floors at zero, never negative.
        clk.advance(5.0)
        assert d.bound(None) == 0.0

    def test_wire_roundtrip_is_relative(self):
        # Peer clocks are not synchronized: only *remaining seconds*
        # cross the wire, and the receiver re-anchors on its own clock.
        d = Deadline.after(3.0)
        d2 = Deadline.from_wire(d.to_wire())
        assert d2.remaining() == pytest.approx(3.0, abs=0.1)


# -- retry policy ------------------------------------------------------------


class TestRetryPolicy:
    def test_seeded_jitter_is_deterministic(self):
        a = RetryPolicy(max_attempts=5, base_delay_s=0.1, max_delay_s=10.0,
                        seed=42)
        b = RetryPolicy(max_attempts=5, base_delay_s=0.1, max_delay_s=10.0,
                        seed=42)
        c = RetryPolicy(max_attempts=5, base_delay_s=0.1, max_delay_s=10.0,
                        seed=7)
        assert a.delays() == b.delays()
        assert a.delays() != c.delays()
        # Exponential shape under the jitter envelope: delay k is within
        # [base*2^k, base*2^k * 1.5] (jitter=0.5) until the cap.
        for k, delay in enumerate(a.delays()):
            lo = 0.1 * (2 ** k)
            assert lo <= delay <= lo * 1.5

    def test_run_sleeps_exactly_the_published_schedule(self):
        slept = []
        policy = RetryPolicy(max_attempts=4, base_delay_s=0.05, seed=3,
                             sleep=slept.append)
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 4:
                raise ConnectionError("transient")
            return "ok"

        assert policy.run(flaky) == "ok"
        assert len(calls) == 4
        assert slept == policy.delays()

    def test_non_retryable_raises_immediately(self):
        slept = []
        policy = RetryPolicy(max_attempts=5, seed=0, sleep=slept.append)
        calls = []

        def fatal():
            calls.append(1)
            raise ValueError("wrong, not transient")

        with pytest.raises(ValueError):
            policy.run(fatal)
        assert len(calls) == 1
        assert slept == []

    def test_final_attempt_error_propagates(self):
        policy = RetryPolicy(max_attempts=3, base_delay_s=0.0, seed=0,
                             sleep=lambda _s: None)
        with pytest.raises(ConnectionError):
            policy.run(lambda: (_ for _ in ()).throw(ConnectionError("x")))

    def test_deadline_bounds_the_whole_loop(self):
        # A backoff that would sleep past the deadline re-raises instead
        # of burning budget asleep.
        clk = _FakeClock()
        slept = []
        policy = RetryPolicy(max_attempts=10, base_delay_s=5.0, seed=0,
                             sleep=slept.append)
        d = Deadline.after(1.0, clock=clk)
        with pytest.raises(ConnectionError):
            policy.run(lambda: (_ for _ in ()).throw(ConnectionError("x")),
                       deadline=d)
        assert slept == []  # first delay (>=5s) already exceeds budget

    def test_expired_deadline_fails_before_first_attempt(self):
        clk = _FakeClock()
        d = Deadline.after(1.0, clock=clk)
        clk.advance(2.0)
        calls = []
        with pytest.raises(DeadlineExceeded):
            RetryPolicy(seed=0).run(lambda: calls.append(1), deadline=d)
        assert calls == []


# -- circuit breaker ---------------------------------------------------------


class TestCircuitBreaker:
    def test_lifecycle_closed_open_half_open_closed(self):
        clk = _FakeClock()
        br = CircuitBreaker(peer="n1:1", failure_threshold=3,
                            reset_timeout_s=10.0, clock=clk)
        assert br.state == CLOSED
        for _ in range(3):
            br.allow()
            br.record_failure()
        assert br.state == OPEN
        with pytest.raises(CircuitOpenError) as ei:
            br.allow()
        assert ei.value.peer == "n1:1"
        assert ei.value.open_for_s == pytest.approx(10.0)
        # Cooldown elapses: one probe is allowed (half-open)...
        clk.advance(10.0)
        assert br.state == HALF_OPEN
        br.allow()
        # ...but only one — concurrent callers stay rejected.
        with pytest.raises(CircuitOpenError):
            br.allow()
        # Probe succeeds: closed, failure count reset.
        br.record_success()
        assert br.state == CLOSED
        br.allow()
        br.record_failure()
        assert br.state == CLOSED  # 1 < threshold after reset

    def test_failed_probe_reopens_with_fresh_cooldown(self):
        clk = _FakeClock()
        br = CircuitBreaker(peer="n1:1", failure_threshold=1,
                            reset_timeout_s=10.0, clock=clk)
        br.record_failure()
        assert br.state == OPEN
        clk.advance(10.0)
        br.allow()  # half-open probe
        br.record_failure()
        assert br.state == OPEN
        clk.advance(5.0)  # half a cooldown: still open
        with pytest.raises(CircuitOpenError):
            br.allow()
        clk.advance(5.0)
        assert br.state == HALF_OPEN

    def test_success_is_any_reply_even_application_errors(self, echo_server):
        # A handler that raises still *answered*: the wire works, so the
        # breaker must not trip on application errors.
        srv, addr, client = echo_server
        srv.register("boom", lambda peer: (_ for _ in ()).throw(
            RuntimeError("app bug")))
        br = CircuitBreaker(peer=addr, failure_threshold=1)
        for _ in range(3):
            with pytest.raises(Exception):
                client.call("boom", breaker=br,
                            timeout=tuning.CONTROL_CALL_TIMEOUT_S)
        assert br.state == CLOSED

    def test_registry_is_shared_per_peer(self):
        a = breaker_for("host:1", failure_threshold=2)
        b = breaker_for("host:1")
        assert a is b
        assert breaker_for("host:2") is not a


# -- rpc integration ---------------------------------------------------------


class TestRpcResilience:
    def test_call_retries_transient_send_failures(self, echo_server):
        # wire.send.pre raises without closing the client, modeling a
        # transient send fault on a healthy connection: the policy's
        # attempts happen on the SAME socket and the call still lands.
        _, _, client = echo_server
        failpoints.cfg("wire.send.pre", "2*raise(ConnectionError)->off")
        slept = []
        policy = RetryPolicy(max_attempts=3, base_delay_s=0.001, seed=1,
                             sleep=slept.append)
        assert client.call("echo", 42, policy=policy,
                           timeout=tuning.CONTROL_CALL_TIMEOUT_S) == 42
        assert failpoints.stat("wire.send.pre")["fires"] == 2
        assert slept == policy.delays()[:2]
        failpoints.clear()

    def test_timeout_error_names_the_slow_hop(self, echo_server):
        _, addr, client = echo_server
        # Swallow exactly one request server-side: the caller times out.
        failpoints.cfg("rpc.dispatch.pre", "1*drop->off")
        with pytest.raises(RpcTimeoutError) as ei:
            client.call("echo", 1, timeout=0.2)
        e = ei.value
        assert e.method == "echo"
        assert e.peer == addr
        assert e.timeout_s == pytest.approx(0.2)
        assert e.elapsed_s >= 0.2
        assert "echo" in str(e) and addr in str(e)
        assert is_retryable(e)
        failpoints.clear()

    def test_expired_deadline_never_touches_the_socket(self, echo_server):
        # Acceptance: DeadlineExceeded raised before the socket is
        # touched — hit counter on the send failpoint stays at zero.
        _, _, client = echo_server
        clk = _FakeClock()
        d = Deadline.after(1.0, clock=clk)
        clk.advance(2.0)
        failpoints.cfg("wire.send.pre", "off")  # armed only to count hits
        with pytest.raises(DeadlineExceeded):
            client.call("echo", 1, deadline=d)
        assert failpoints.stat("wire.send.pre")["hits"] == 0
        failpoints.clear()

    def test_server_sees_shrunken_budget(self, echo_server):
        _, _, client = echo_server
        rem = client.call("remaining", deadline=Deadline.after(5.0))
        assert rem is not None
        assert 0.0 < rem < 5.0

    def test_no_deadline_means_no_server_side_deadline(self, echo_server):
        _, _, client = echo_server
        assert client.call("remaining",
                           timeout=tuning.CONTROL_CALL_TIMEOUT_S) is None

    def test_deadline_shrinks_across_two_hops(self):
        # client → "head" → "node": the node's handler must see strictly
        # less budget than the head's, which sees strictly less than the
        # client granted. The head-side hop passes no explicit deadline:
        # the ambient handler deadline (contextvar) propagates it.
        node = RpcServer()
        node.register("remaining",
                      lambda peer: current_deadline().remaining())
        node_addr = node.start()
        node_client = RpcClient(node_addr)

        head = RpcServer()

        def h_fanout(peer):
            mine = current_deadline().remaining()
            theirs = node_client.call(
                "remaining", timeout=tuning.CONTROL_CALL_TIMEOUT_S)
            return [mine, theirs]

        head.register("fanout", h_fanout)
        head_addr = head.start()
        head_client = RpcClient(head_addr)
        try:
            granted = 5.0
            head_rem, node_rem = head_client.call(
                "fanout", deadline=Deadline.after(granted))
            assert 0.0 < node_rem < head_rem < granted
        finally:
            head_client.close()
            node_client.close()
            head.stop()
            node.stop()


# -- chaos: storm control and recovery ---------------------------------------


@pytest.mark.chaos
class TestBreakerChaos:
    def test_no_retry_storm_against_dead_peer(self, echo_server):
        # N concurrent callers, each making several attempts against a
        # peer whose sends all fail. Without a breaker: N*attempts socket
        # burns. With the shared breaker: at most N in-flight calls plus
        # the threshold's worth of re-entries ever reach the wire.
        _, addr, client = echo_server
        n_threads, attempts, threshold = 6, 5, 3
        failpoints.cfg("wire.send.pre", "raise(ConnectionError)")
        br = CircuitBreaker(peer=addr, failure_threshold=threshold)
        rejected = []

        def caller():
            for _ in range(attempts):
                try:
                    client.call("echo", 1, breaker=br,
                                timeout=tuning.CONTROL_CALL_TIMEOUT_S)
                except CircuitOpenError:
                    rejected.append(1)
                except Exception:
                    pass

        threads = [threading.Thread(target=caller)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        hits = failpoints.stat("wire.send.pre")["hits"]
        failpoints.clear()
        assert br.state == OPEN
        # O(N) probes, never O(N * attempts).
        assert hits <= n_threads + threshold
        assert hits < n_threads * attempts
        assert len(rejected) >= n_threads * attempts - (
            n_threads + threshold)

    def test_breaker_recovers_after_peer_heals(self, echo_server):
        # Fault clears after 3 fires (the peer "heals"); the breaker must
        # come back via a half-open probe, not stay latched open.
        _, addr, client = echo_server
        clk = _FakeClock()
        br = CircuitBreaker(peer=addr, failure_threshold=3,
                            reset_timeout_s=10.0, clock=clk)
        failpoints.cfg("wire.send.pre", "3*raise(ConnectionError)->off")
        for _ in range(3):
            with pytest.raises(ConnectionError):
                client.call("echo", 1, breaker=br,
                            timeout=tuning.CONTROL_CALL_TIMEOUT_S)
        assert br.state == OPEN
        with pytest.raises(CircuitOpenError):
            client.call("echo", 1, breaker=br,
                        timeout=tuning.CONTROL_CALL_TIMEOUT_S)
        clk.advance(10.0)  # cooldown elapses -> half-open probe allowed
        assert client.call("echo", 7, breaker=br,
                           timeout=tuning.CONTROL_CALL_TIMEOUT_S) == 7
        assert br.state == CLOSED
        failpoints.clear()


# -- relay deadline forwarding (satellite d) ---------------------------------


@pytest.fixture
def relay_stack():
    """head RpcServer ← DriverProxy ← RelayChannel, with a deliberately
    small proxy relay cap so capping bugs surface fast."""
    from raytpu.core.config import cfg as config
    from raytpu.cluster.driver_proxy import DriverProxy
    from raytpu.cluster.relay import RelayChannel
    import asyncio

    head = RpcServer()
    head.register("ping", lambda peer: "pong")
    head.register("list_nodes", lambda peer: [])
    head.register("remaining", lambda peer: (
        current_deadline().remaining()
        if current_deadline() is not None else None))

    async def h_slow(peer, seconds):
        await asyncio.sleep(float(seconds))
        return "done"

    head.register("slow", h_slow)
    head_addr = head.start()

    old_cap = float(config.proxy_relay_timeout_s)
    config.set("proxy_relay_timeout_s", 0.3)
    proxy = DriverProxy(head_addr)
    proxy_addr = proxy.start()
    chan = RelayChannel(proxy_addr)
    yield chan.client_for(head_addr)
    chan.close()
    proxy.stop()
    head.stop()
    config.set("proxy_relay_timeout_s", old_cap)


class TestRelayDeadlines:
    def test_timeout_none_is_not_capped_by_proxy_default(self, relay_stack):
        # The upstream handler takes 0.7s; the proxy's own relay cap is
        # 0.3s. An explicit timeout=None (long upload semantics) must ride
        # the frame and override the proxy cap, not be squashed by it.
        assert relay_stack.call("slow", 0.7, timeout=None) == "done"

    def test_short_caller_budget_bounds_upstream_hop(self, relay_stack):
        # The caller grants 0.25s against a 5s handler: the failure must
        # arrive on the caller's budget, not the upstream's.
        start = time.monotonic()
        with pytest.raises(Exception) as ei:
            relay_stack.call("slow", 5.0, deadline=Deadline.after(0.25))
        assert time.monotonic() - start < 2.0
        assert isinstance(ei.value, (TimeoutError, RpcTimeoutError,
                                     DeadlineExceeded, ConnectionLost))

    def test_deadline_survives_the_relay_hop(self, relay_stack):
        rem = relay_stack.call("remaining", deadline=Deadline.after(5.0))
        assert rem is not None
        assert 0.0 < rem < 5.0


# -- node notify buffering (head-unreachable degradation) --------------------


class TestHeadNotifyBuffer:
    def _stub_node(self):
        import collections
        import types

        from raytpu.cluster.node import NodeServer

        ns = types.SimpleNamespace(
            _head=None,
            _notify_buffer=collections.deque(maxlen=4),
            _notify_buffer_lock=threading.Lock(),
        )
        ns._head_notify = types.MethodType(NodeServer._head_notify, ns)
        return ns

    def test_notifies_buffer_while_head_unreachable(self):
        ns = self._stub_node()
        for i in range(3):
            ns._head_notify("task_done", f"t{i}", "node")
        assert [a[0] for m, a in ns._notify_buffer] == ["t0", "t1", "t2"]

    def test_buffer_is_bounded_oldest_dropped(self):
        ns = self._stub_node()
        for i in range(10):
            ns._head_notify("task_done", f"t{i}", "node")
        assert len(ns._notify_buffer) == 4
        assert [a[0] for m, a in ns._notify_buffer] == [
            "t6", "t7", "t8", "t9"]

    def test_live_head_bypasses_buffer(self):
        ns = self._stub_node()
        sent = []
        ns._head = types_head = type("H", (), {})()
        types_head.closed = False
        types_head.notify = lambda method, *a: sent.append((method, a))
        ns._head_notify("task_done", "t0", "node")
        assert sent == [("task_done", ("t0", "node"))]
        assert not ns._notify_buffer


# -- lint: no new hardcoded timing literals (satellite f) --------------------


class TestNoHardcodedTimeouts:
    """Thin wrapper over RTP001 (raytpu/analysis/rules/timing_literals.py)
    — the ad-hoc AST scan that lived here migrated into the lint
    framework; this keeps the invariant visible from the resilience
    suite and proves the rule still bites."""

    def test_no_numeric_sleep_or_timeout_literals(self):
        from raytpu.analysis.core import run_lint

        result = run_lint(select=["RTP001"], use_baseline=False)
        assert not result.findings, (
            "hardcoded timing literals in raytpu/cluster/ — hoist them "
            "into raytpu/cluster/constants.py (RAYTPU_* env-overridable):"
            "\n  " + "\n  ".join(str(f) for f in result.findings))

    def test_scanner_catches_a_planted_literal(self):
        from raytpu.analysis.core import run_rule_on_source
        from raytpu.analysis.rules.timing_literals import TimingLiterals

        src = ("import time\n"
               "def f(c):\n"
               "    time.sleep(0.5)\n"
               "    c.call('x', timeout=5.0)\n")
        findings = run_rule_on_source(TimingLiterals(), src)
        assert len(findings) == 2


# -- env-overridable constants (satellite c) ---------------------------------


class TestTuningConstants:
    def test_env_override(self, monkeypatch):
        import importlib

        monkeypatch.setenv("RAYTPU_CONTROL_CALL_TIMEOUT_S", "9.5")
        monkeypatch.setenv("RAYTPU_HEAD_NOTIFY_BUFFER_MAX", "7")
        mod = importlib.reload(tuning)
        try:
            assert mod.CONTROL_CALL_TIMEOUT_S == 9.5
            assert mod.HEAD_NOTIFY_BUFFER_MAX == 7
        finally:
            monkeypatch.undo()
            importlib.reload(tuning)

    def test_defaults_are_sane(self):
        # Poll periods must be much shorter than the budgets they poll
        # under, or the last poll blows through the deadline.
        assert tuning.PENDING_POLL_PERIOD_S < tuning.ACTOR_RESOLVE_TIMEOUT_S
        assert tuning.PG_POLL_PERIOD_S < tuning.PG_CREATE_TIMEOUT_S
        assert tuning.OBJECT_POLL_MIN_S <= tuning.OBJECT_POLL_MAX_S
        assert tuning.RECONNECT_BASE_DELAY_S <= tuning.RECONNECT_MAX_DELAY_S


class TestHeadStall:
    def test_a_frozen_head_does_not_blame_its_nodes(self, monkeypatch):
        """While the head process (or the whole host) stands still it
        receives no heartbeat. When it wakes, that silence says nothing
        about any node. A v5e host froze for 16 s while four TPU
        runtimes started at once, and the head then declared its only
        node dead."""
        from raytpu.cluster.cluster_utils import Cluster

        monkeypatch.setenv("RAYTPU_HEARTBEAT_TIMEOUT_S", "1.0")
        monkeypatch.setenv("RAYTPU_HEARTBEAT_PERIOD_S", "0.2")
        monkeypatch.setenv("RAYTPU_HEALTH_CHECK_PERIOD_S", "0.2")
        cluster = Cluster(num_nodes=1)
        try:
            cluster.wait_for_nodes()
            cluster.pause_head()
            time.sleep(2.5)  # well past the heartbeat timeout
            cluster.resume_head()
            client = RpcClient(cluster.address)
            try:
                deadline = time.monotonic() + 3.0
                while time.monotonic() < deadline:
                    nodes = [n for n in client.call("list_nodes")
                             if n["labels"].get("role") != "driver"]
                    assert [n["alive"] for n in nodes] == [True]
                    time.sleep(0.2)
            finally:
                client.close()
        finally:
            cluster.shutdown()
