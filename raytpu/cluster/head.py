"""Cluster head: the control plane (GCS equivalent).

Reference analogue: ``src/ray/gcs/gcs_server/`` — ``GcsNodeManager`` (node
table + death broadcast), ``GcsActorManager`` (actor directory, named
actors), ``GcsKvManager`` (KV), ``GcsHealthCheckManager`` (heartbeat
timeout), ``GcsPlacementGroupManager``, plus the cluster-level half of the
two-level scheduler (``ClusterTaskManager``/hybrid policy,
``src/ray/raylet/scheduling/policy/hybrid_scheduling_policy.h:50``).

One process per cluster. Tables are in-memory dicts (the reference's
default ``InMemoryStoreClient``); everything is reconstructible from node
re-registration, matching the reference's GCS-restart story.

Actor restarts are head-driven (reference: the ``GcsActorManager``
restart state machine, ``gcs_actor_manager.h:88``): when a restartable
actor's worker or node dies, the head marks it RESTARTING, re-schedules
the stored creation spec onto a live node, and publishes
``restarting``/``restarted`` so drivers hold submissions instead of
failing them; DEAD is only published when restarts are exhausted or the
kill was explicit (``no_restart``).

TPU-first twist: a node registers with its slice topology; the scheduler
packs TPU bundles onto whole hosts of one slice (contiguous ICI) before
spreading — the topology is a scheduling dimension, not an env var.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Dict, List, Optional, Set, Tuple

from raytpu.cluster import constants as tuning
from raytpu.cluster import wire
from raytpu.cluster.protocol import (
    HeadRedirect,
    Peer,
    RpcClient,
    RpcError,
    RpcServer,
)
from raytpu.util import failpoints
from raytpu.util import metrics
from raytpu.util import profiler
from raytpu.util import task_events
from raytpu.util import tenancy
from raytpu.util import tracing
from raytpu.util import tsdb
from raytpu.util import errors
from raytpu.util.errors import PlacementInfeasibleError, TenantThrottled
from raytpu.util.failpoints import DROP, failpoint
from raytpu.util.profstore import ProfileStore
from raytpu.util.resilience import breaker_for

# Env-overridable so chaos tests (and small dev clusters) can tighten the
# failure-detection window without patching module state in subprocesses.
HEARTBEAT_TIMEOUT_S = float(os.environ.get(
    "RAYTPU_HEARTBEAT_TIMEOUT_S", "5.0"))
CHECK_PERIOD_S = float(os.environ.get(
    "RAYTPU_HEALTH_CHECK_PERIOD_S", "1.0"))


class GcsStore:
    """Durable table storage behind the head (reference:
    ``src/ray/gcs/gcs_server/gcs_table_storage.cc`` over a StoreClient;
    our store client is sqlite — single head process, WAL mode).

    Persisted tables — write-after-mutation: ``kv`` (incl. actor
    creation specs), ``actors`` (directory + restart counters), ``pgs``,
    ``named`` (named-actor index), ``pending_tasks`` (queued-infeasible
    TaskSpec blobs, so a bounce re-schedules instead of orphaning).
    Write-behind snapshots (health-loop cadence + shutdown): ``objects``
    (location/size directory), ``borrows``, ``task_events`` (flight
    recorder tail). Node entries are ephemeral by design — nodes
    re-register when the head comes back, exactly the reference's
    GCS-restart story (``in_memory_store_client.h:31`` + node
    re-registration, SURVEY A3).
    """

    def __init__(self, path: str):
        import sqlite3

        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._conn.execute("PRAGMA journal_mode=WAL")
        self._conn.execute(
            "CREATE TABLE IF NOT EXISTS tables ("
            "tbl TEXT, key TEXT, value BLOB, PRIMARY KEY (tbl, key))")
        self._conn.commit()
        self._lock = threading.Lock()
        # WAL shipping: per-table monotonic seq + a bounded in-memory
        # journal of recent mutations. A follower polls ship() with its
        # per-table cursors; entries past the journal horizon degrade to
        # a full-table resync. Entry shape: (seq, op, key, value) with
        # op in {"put", "del", "snap"} ("snap" carries the whole mapping
        # in ``value`` — only the tiny single-key write-behind tables
        # use it).
        self._seqs: Dict[str, int] = {}
        self._journal: Dict[str, deque] = {}
        # Tables already on disk start at seq 1 (a "disk baseline" the
        # empty journal can never cover) so a follower at cursor 0 gets
        # a full resync instead of being told it is caught up.
        for (t,) in self._conn.execute(
                "SELECT DISTINCT tbl FROM tables").fetchall():
            self._seqs[t] = 1
        # A fenced (superseded) head freezes its store: every mutation
        # becomes a no-op so a resumed stale incumbent cannot diverge
        # its table file from the elected head's.
        self._frozen = False

    def _journal_append(self, table: str, op: str, key: str,
                        value: Any) -> None:
        # Caller holds self._lock.
        seq = self._seqs.get(table, 0) + 1
        self._seqs[table] = seq
        j = self._journal.get(table)
        if j is None:
            j = self._journal[table] = deque(maxlen=tuning.WAL_JOURNAL_MAX)
        j.append((seq, op, key, value))

    def freeze(self) -> None:
        """Fence this store: all subsequent mutations are silently
        dropped. Used when the head loses its lease — reads stay live
        (diagnostics), writes must not race the elected successor."""
        with self._lock:
            self._frozen = True

    def put(self, table: str, key: str, value: bytes) -> None:
        with self._lock:
            if self._frozen:
                return
            self._conn.execute(
                "INSERT OR REPLACE INTO tables (tbl, key, value) "
                "VALUES (?, ?, ?)", (table, key, value))
            self._conn.commit()
            self._journal_append(table, "put", key, value)

    def delete(self, table: str, key: str) -> None:
        with self._lock:
            if self._frozen:
                return
            self._conn.execute(
                "DELETE FROM tables WHERE tbl = ? AND key = ?", (table, key))
            self._conn.commit()
            self._journal_append(table, "del", key, None)

    def load_all(self, table: str) -> Dict[str, bytes]:
        with self._lock:
            rows = self._conn.execute(
                "SELECT key, value FROM tables WHERE tbl = ?",
                (table,)).fetchall()
        return {k: v for k, v in rows}

    def snapshot_table(self, table: str, mapping: Dict[str, bytes]) -> None:
        """Replace every row of ``table`` in one transaction. The
        write-behind tables (object directory, borrows, event tail) are
        too hot for per-mutation rows; a periodic whole-table snapshot
        is their durability contract, and the single transaction means a
        crash mid-snapshot leaves the previous snapshot intact."""
        with self._lock:
            if self._frozen:
                return
            self._conn.execute("BEGIN")
            self._conn.execute(
                "DELETE FROM tables WHERE tbl = ?", (table,))
            self._conn.executemany(
                "INSERT OR REPLACE INTO tables (tbl, key, value) "
                "VALUES (?, ?, ?)",
                [(table, k, v) for k, v in mapping.items()])
            self._conn.commit()
            self._journal_append(table, "snap", "", dict(mapping))

    def ship(self, cursors: Dict[str, int],
             tables: Tuple[str, ...]) -> Dict[str, Any]:
        """One WAL-ship round: for each table, either the journal
        entries past the follower's cursor (``{"seq", "entries"}``) or —
        when the cursor fell behind the bounded journal's horizon (or
        the follower is brand new) — a full-table resync
        (``{"seq", "full"}``)."""
        out: Dict[str, Any] = {}
        full_needed: List[Tuple[str, int]] = []
        with self._lock:
            for table in tables:
                cur = int(cursors.get(table, 0) or 0)
                seq = self._seqs.get(table, 0)
                if cur >= seq:
                    continue  # follower is caught up on this table
                j = self._journal.get(table)
                if j and j[0][0] <= cur + 1:
                    out[table] = {
                        "seq": seq,
                        "entries": [e for e in j if e[0] > cur],
                    }
                else:
                    full_needed.append((table, seq))
        for table, seq in full_needed:
            # load_all takes the lock itself; a mutation landing between
            # the seq read and the load only makes the snapshot fresher
            # than the seq claims — the follower re-polls and converges.
            out[table] = {"seq": seq, "full": self.load_all(table)}
        return out

    def compact(self) -> None:
        """Fold the WAL back into the main database file (reload-on-start
        and shutdown both compact, so the WAL never grows unbounded
        across bounce cycles)."""
        with self._lock:
            self._conn.execute("PRAGMA wal_checkpoint(TRUNCATE)")
            self._conn.commit()

    def close(self) -> None:
        with self._lock:
            self._conn.close()


# Every GcsStore table MUST be listed here: this tuple is what the
# wal_ship stream replicates to the hot standby, and lint RTP017
# cross-checks it against the persistence call sites so a new table
# cannot be silently left out of replication. "meta" holds the
# epoch-stamped head lease and the replicated TSDB sequencing state.
WAL_SHIP_TABLES = ("kv", "actors", "pgs", "named", "pending_tasks",
                   "objects", "borrows", "task_events", "tenants", "meta")

# RPC methods a fenced (superseded) head still answers: negotiation,
# liveness probes, chaos-test plumbing, and read-only diagnostics.
# Everything else gets a HeadRedirect to the elected successor.
_FENCE_EXEMPT = frozenset({
    "rpc_caps", "ping", "head_info", "failpoint_cfg", "failpoint_clear",
    "failpoint_stat", "list_events", "trace_dump",
})


def read_addr_record(path: str) -> Optional[dict]:
    """Parse the head discovery record ``{"address", "epoch"}``; None
    when the file is absent/unreadable/corrupt (callers fall back to
    their last known address)."""
    if not path:
        return None
    import json as _json

    try:
        with open(path, "r") as f:
            rec = _json.loads(f.read())
    except (OSError, ValueError):
        return None
    if not isinstance(rec, dict) or not rec.get("address"):
        return None
    return rec


class NodeEntry:
    def __init__(self, node_id: str, address: str, resources: Dict[str, float],
                 labels: Dict[str, str]):
        self.node_id = node_id
        self.address = address          # node RPC endpoint
        self.total = dict(resources)
        self.available = dict(resources)
        self.labels = dict(labels)
        self.last_heartbeat = time.monotonic()
        self.alive = True
        self.avail_seq = 0  # last applied availability snapshot
        self.peer: Optional[Peer] = None

    def snapshot(self) -> dict:
        return {
            "node_id": self.node_id, "address": self.address,
            "resources": dict(self.total), "available": dict(self.available),
            "labels": dict(self.labels), "alive": self.alive,
        }


# Resource label values published by the LAST refresh, so series for
# resources that vanish (node death) are zeroed instead of lying
# forever. Module-global to match the collectors' lifetime: a head
# restarted in the same process shares the prometheus collectors, so it
# must also inherit the set of series needing zeroing.
_published_resources: set = set()

# Tenant tag values published by the LAST queue-gauge refresh (same
# zero-on-vanish contract as _published_resources above).
_published_tenants: set = set()


class _HeadMetrics:
    """Built-in cluster metrics on the head's Prometheus registry.

    Reference analogue: the core runtime metrics the C++ stats layer
    exports per node (``src/ray/stats/metric_defs.cc`` —
    ``ray_cluster_active_nodes``, ``ray_actors``, ``ray_tasks`` ...);
    here the head is the one process that already sees cluster state, so
    it publishes directly. Never raises: metrics must not take down the
    control plane.
    """

    def __init__(self):
        self.nodes = self.actors = self.pgs = None
        self.resources = self.available = None
        self.schedules = self.tasks_done = self.tasks_submitted = None
        self.tenant_placed = self.tenant_throttled = None
        self.tenant_preempted = self.tenant_queued = None
        try:
            from raytpu.util.metrics import Counter, Gauge

            self.nodes = Gauge("raytpu_cluster_nodes",
                               "Cluster nodes by liveness",
                               tag_keys=("state",))
            self.actors = Gauge("raytpu_actors",
                                "Registered (live) actors")
            self.pgs = Gauge("raytpu_placement_groups",
                             "Placement groups")
            self.resources = Gauge(
                "raytpu_resources_total",
                "Cluster resource capacity by name",
                tag_keys=("resource",))
            self.available = Gauge(
                "raytpu_resources_available",
                "Cluster resource availability by name",
                tag_keys=("resource",))
            self.schedules = Counter(
                "raytpu_schedule_requests_total",
                "Scheduling decisions served by the head")
            self.tasks_done = Counter(
                "raytpu_tasks_done_total",
                "Task completions reported to the head")
            self.tasks_submitted = Counter(
                "raytpu_tasks_submitted_total",
                "Task specs accepted for scheduling")
            self.tenant_placed = Counter(
                "raytpu_tenant_tasks_placed_total",
                "Placements per tenant",
                tag_keys=("tenant",))
            self.tenant_throttled = Counter(
                "raytpu_tenant_throttled_total",
                "Submissions shed by admission control per tenant",
                tag_keys=("tenant",))
            self.tenant_preempted = Counter(
                "raytpu_tenant_preempted_total",
                "Running tasks preempted per (victim) tenant",
                tag_keys=("tenant",))
            self.tenant_queued = Gauge(
                "raytpu_tenant_queued",
                "Specs queued at the head per tenant",
                tag_keys=("tenant",))
        except Exception:  # pragma: no cover — metrics are best-effort
            self.nodes = None

    def refresh(self, nodes, actors, pgs) -> None:
        if self.nodes is None:
            return
        try:
            alive = sum(1 for n in nodes if n.alive)
            self.nodes.set(alive, {"state": "alive"})
            self.nodes.set(len(nodes) - alive, {"state": "dead"})
            self.actors.set(len(actors))
            self.pgs.set(len(pgs))
            total: Dict[str, float] = {}
            avail: Dict[str, float] = {}
            for n in nodes:
                if not n.alive:
                    continue
                for k, v in n.total.items():
                    total[k] = total.get(k, 0.0) + v
                for k, v in n.available.items():
                    avail[k] = avail.get(k, 0.0) + v
            # A resource that vanished (its only node died) must read 0,
            # not its last value.
            global _published_resources
            for k in _published_resources - set(total):
                self.resources.set(0.0, {"resource": k})
                self.available.set(0.0, {"resource": k})
            _published_resources = set(total)
            for k, v in total.items():
                self.resources.set(v, {"resource": k})
            for k, v in avail.items():
                self.available.set(v, {"resource": k})
        except Exception:  # pragma: no cover
            pass

    def tick_schedule(self) -> None:
        self._inc(self.schedules)
        self._inc(self.tasks_submitted)

    def tick_task_done(self) -> None:
        self._inc(self.tasks_done)

    def tick_tenant(self, counter, tenant: str) -> None:
        if counter is not None and tenant:
            try:
                counter.inc(1, {"tenant": tenant})
            except Exception:  # pragma: no cover
                pass

    def refresh_tenant_queues(self, queued: Dict[str, int]) -> None:
        """Gauge the per-tenant head backlog. Tenants that drained must
        read 0, not their last value — the TSDB's staleness rules only
        retire a series the process stops publishing entirely."""
        if self.tenant_queued is None:
            return
        try:
            global _published_tenants
            for t in _published_tenants - set(queued):
                self.tenant_queued.set(0, {"tenant": t})
            _published_tenants = set(queued)
            for t, n in queued.items():
                self.tenant_queued.set(n, {"tenant": t})
        except Exception:  # pragma: no cover
            pass

    @staticmethod
    def _inc(counter) -> None:
        if counter is not None:
            try:
                counter.inc()
            except Exception:  # pragma: no cover
                pass


class HeadServer:
    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 storage_path: Optional[str] = None,
                 addr_file: Optional[str] = None,
                 takeover: bool = False):
        self._rpc = RpcServer(host, port)
        self._lock = threading.RLock()
        self._store: Optional[GcsStore] = (
            GcsStore(storage_path) if storage_path else None)
        # Hot-standby machinery: discovery record path, fencing state,
        # and the epoch this incarnation serves under (derived from the
        # stored lease below — every (re)start bumps it, so a standby
        # takeover and a restart-in-place both supersede the old epoch).
        self._addr_file = (addr_file if addr_file is not None
                           else tuning.HEAD_ADDR_FILE)
        self._takeover = takeover
        self._fenced = False
        self._redirect_to = ""
        self._redirect_epoch = 0
        self._epoch = 1
        self._last_renew = time.monotonic()
        # Head-dispatched placements, for failover dedup: (task_id hex,
        # attempt) recorded when the pending scheduler's submit_task RPC
        # to a node succeeds, shipped to the standby as an indexed log so
        # a new head neither re-dispatches a queued spec the incumbent
        # already launched nor re-queues a driver resubmission of one.
        self._placed: "OrderedDict[Tuple[str, int], bool]" = OrderedDict()
        self._placed_log: deque = deque(maxlen=tuning.WAL_JOURNAL_MAX)
        self._placed_idx = 0
        self._nodes: Dict[str, NodeEntry] = {}
        self._kv: Dict[str, bytes] = {}
        # actor_id(hex) -> {"node_id", "name", "namespace", "creation_blob"}
        self._actors: Dict[str, dict] = {}
        self._named: Dict[Tuple[str, str], str] = {}
        # object_id(hex) -> set of node_ids that hold it
        self._objects: Dict[str, Set[str]] = {}
        # object_id(hex) -> wire bytes, feeding the locality scorer.
        # Bounded FIFO (LOCALITY_DIR_MAX): beyond the cap the oldest
        # sizes are evicted and the scorer just loses their signal;
        # entries also drop with the locations on free / node death.
        self._object_sizes: Dict[str, int] = {}
        # Borrower protocol (reference: reference_count.h borrowers +
        # WaitForRefRemoved, SURVEY A1): oid -> {"node:worker", ...}. The
        # head is the authority so an owner's free cannot race a borrow
        # report — borrow_added rides the task-completion path
        # synchronously, BEFORE return-object locations are reported.
        self._borrows: Dict[str, Set[str]] = {}
        self._pending_free: Set[str] = set()
        # Early-release tombstones: a worker's async borrow_released can
        # beat the node's synchronous borrow_added for the same (oid,
        # borrower) in a narrow drop-during-registration race; the add
        # then cancels against the tombstone instead of recording a
        # borrow that would never be released. Values are creation times:
        # the matching add lands within one task-completion round-trip,
        # so anything older than the TTL is a release whose add will
        # never come — kept entries would otherwise leak and cancel a
        # future legitimate borrow of the same pair (ADVICE r3).
        self._early_releases: Dict[Tuple[str, str], float] = {}
        self._early_release_ttl_s = 60.0
        self._early_release_cap = 10000
        # Structured-event ring (reference: dashboard event module over
        # RAY_EVENT files); nodes forward their events here.
        self._events = deque(maxlen=2000)
        # Flight recorder (reference: GcsTaskManager storage): lifecycle
        # events batch-shipped from every process, folded into one
        # bounded, indexed store the state API queries.
        from raytpu.core.config import cfg as _cfg

        self._task_event_store = task_events.TaskEventStore(
            per_kind=_cfg.task_event_store_per_kind,
            events_per_entity=_cfg.task_event_store_events_per_entity)
        # Cluster TSDB (reference: the stats/exporter aggregation path):
        # shipped metric deltas from every process fold in here, behind
        # the metrics_query/metrics_push RPC surface.
        self._metric_store = tsdb.MetricStore(
            max_bytes=int(_cfg.metrics_store_max_bytes),
            fine_step_s=float(_cfg.metrics_fine_step_s),
            fine_slots=int(_cfg.metrics_fine_slots),
            coarse_step_s=float(_cfg.metrics_coarse_step_s),
            coarse_slots=int(_cfg.metrics_coarse_slots))
        metrics.set_shipper_identity("head")
        # Cluster profile store (the TSDB's sibling): shipped
        # collapsed-stack snapshots from every process, behind the
        # profile_query/profile_stats RPC surface.
        self._profile_store = ProfileStore(
            max_bytes=int(_cfg.profile_store_max_bytes),
            ring_slots=int(_cfg.profile_ring_slots))
        if profiler.profiling_enabled():
            profiler.start_continuous()
        # SLO alerts: threshold/duration rules over the TSDB, evaluated
        # on the health-loop cadence, fired into the ops-event ring. A
        # malformed rule string must not take the control plane down —
        # it degrades to no rules plus a loud ERROR event.
        try:
            rules = tsdb.parse_alert_rules(str(_cfg.metrics_alert_rules))
        except ValueError as e:
            from raytpu.util.events import record_event as _rec

            self._events.append(_rec(
                "ERROR", "SLO_ALERT_CONFIG",
                f"ignoring metrics_alert_rules: {e}"))
            rules = []
        self._alerts = tsdb.AlertEvaluator(
            self._metric_store, rules,
            on_fire=self._on_alert_fire, on_resolve=self._on_alert_resolve)
        self._object_waiters: Dict[str, List[Peer]] = {}
        # Push-path demand (reference: push_manager.h): object -> nodes
        # whose pull loops asked for it before any copy existed. When the
        # first copy is reported, the producer is told to stream it to
        # them. Values are registration times for pruning.
        self._object_node_demand: Dict[str, Dict[str, float]] = {}
        # placement groups: pg_id -> {"bundles": [...], "nodes": [node_id per bundle]}
        self._pgs: Dict[str, dict] = {}
        self._subscribers: Dict[str, List[Peer]] = {}  # topic -> peers
        # Unmet schedule() requests keyed by request id so client RETRIES
        # refresh one entry instead of inflating demand (the autoscaler's
        # feed; reference: GcsAutoscalerStateManager pending demand).
        self._unmet: Dict[str, Tuple[float, Dict[str, float]]] = {}
        # Explicit request_resources() hint (autoscaler sdk); replaced
        # wholesale on each call, merged into _get_demand's output.
        self._requested_resources: List[Dict[str, float]] = []
        # Queued-infeasible TaskSpecs: task_id(hex) -> single-spec wire
        # blob. The head owns these until capacity appears (the pending
        # scheduler thread pushes them to a node), and they persist so a
        # bounce re-schedules instead of orphaning a driver blocked in
        # get(). Semantics are at-least-once across a bounce: a driver
        # whose submit_batch call died mid-flight may resubmit a spec
        # the head also recovered.
        self._pending_specs: Dict[str, bytes] = {}
        # Multi-tenant scheduling state. ``_tenants`` rows ("t:<name>" in
        # the WAL-shipped "tenants" table) hold the durable knobs — quota
        # ceilings, WFQ weight, priority — plus the fair-queue virtual
        # pass, so shares don't invert across a standby takeover.
        # ``_tenant_running`` ("r:<tid>" rows) records in-flight
        # placements; usage is DERIVED from it on reload, so the hot
        # path never writes usage rows. ``_pending_meta`` mirrors
        # ``_pending_specs`` with (tenant, priority) so WFQ ordering
        # doesn't decode every blob each scan.
        self._tenants: Dict[str, dict] = {}
        self._tenant_running: Dict[str, dict] = {}
        self._tenant_usage: Dict[str, Dict[str, float]] = {}
        self._pending_meta: Dict[str, Tuple[str, int]] = {}
        # Pending (infeasible) placement groups feed the autoscaler's
        # demand export until the client's retry loop succeeds or gives
        # up; TTL-pruned in _get_demand, never persisted.
        self._pg_demand: Dict[str, Tuple[float, List[Dict[str, float]]]] = {}
        self._last_snapshot = time.monotonic()
        # Built-in runtime metrics (reference: the core metric defs the
        # per-node metrics agent exports to Prometheus, e.g.
        # ray_cluster_active_nodes / ray_actors; metric_defs.cc). Gauges
        # refresh from the health loop; counters tick on the hot paths.
        self._metrics = _HeadMetrics()
        self._metrics_port: Optional[int] = None
        self._job_counter = 0
        self._stop = threading.Event()
        h = self._rpc.register
        h("register_node", self._register_node)
        h("heartbeat", self._heartbeat)
        h("resource_update", self._resource_update)
        h("drain_node", self._drain_node)
        h("list_nodes", self._list_nodes)
        h("kv_put", self._kv_put)
        h("kv_get", self._kv_get)
        h("kv_del", self._kv_del)
        h("kv_keys", self._kv_keys)
        h("schedule", self._schedule)
        h("submit_batch", self._submit_batch)
        # Advertised through rpc_caps so a driver only pipelines against
        # a head that actually speaks the batched submit path.
        self._rpc.capabilities["submit_batch"] = True
        h("register_actor", self._register_actor)
        h("resolve_actor", self._resolve_actor)
        h("resolve_named_actor", self._resolve_named_actor)
        h("actor_dead", self._actor_dead)
        h("object_unavailable", self._object_unavailable)
        h("report_object", self._report_object)
        h("report_objects", self._h_report_objects)
        h("forget_object", self._forget_object)
        h("locate_object", self._locate_object)
        h("borrow_added", self._borrow_added)
        h("borrow_released", self._borrow_released)
        h("request_free", self._request_free)
        h("borrow_info", self._borrow_info)
        h("task_done", self._task_done)
        h("report_event", self._report_event)
        h("list_events", self._list_events)
        # Flight-recorder surface: batch ingest (notify path for drivers
        # and worker relays; heartbeats piggyback instead) + the state
        # API's list/summary/timeline queries.
        h("report_task_events", self._h_report_task_events)
        h("state_list", self._state_list)
        h("state_summary", self._state_summary)
        h("state_timeline", self._state_timeline)
        h("task_events_stats", self._task_events_stats)
        # Metrics pipeline surface: delta ingest off the notify path
        # (heartbeats piggyback instead), cluster-aggregated queries,
        # series listing, prometheus text, and alert-rule management.
        h("metrics_push", self._h_metrics_push)
        h("metrics_query", self._h_metrics_query)
        h("metrics_series", self._h_metrics_series)
        h("metrics_prometheus", self._h_metrics_prometheus)
        h("metrics_stats", self._h_metrics_stats)
        h("metrics_set_alert_rules", self._h_metrics_set_alert_rules)
        h("metrics_alerts", self._h_metrics_alerts)
        # Continuous-profiling surface: merged / diff cluster
        # flamegraphs over the profile store, and its per-proc
        # ship inventory (``raytpu top --profile``).
        h("profile_push", self._h_profile_push)
        h("profile_query", self._h_profile_query)
        h("profile_stats", self._h_profile_stats)
        # Multi-tenant surface: quota/weight/priority upserts and the
        # per-tenant usage/backlog view behind ``raytpu top --tenants``.
        h("tenant_set_quota", self._h_tenant_set_quota)
        h("tenant_info", self._h_tenant_info)
        h("tenant_list", self._h_tenant_list)
        h("create_pg", self._create_pg)
        h("remove_pg", self._remove_pg)
        h("pg_info", self._pg_info)
        h("subscribe", self._subscribe)
        h("publish_logs", self._publish_logs)
        h("get_demand", self._get_demand)
        h("resource_demands", self._resource_demands)
        h("request_resources", self._request_resources)
        h("next_job_id", self._next_job_id)
        h("ping", lambda peer: "pong")
        # Hot-standby surface: WAL shipping poll (also the incumbent's
        # liveness proof to the follower) + epoch/fencing introspection.
        h("wal_ship", self._h_wal_ship)
        h("head_info", self._h_head_info)
        # Chaos testing: arm/inspect failpoints on this head or, with
        # scope="cluster", on every live node daemon too (reference
        # analogue: Ray's testing-only fault-injection RPCs).
        h("failpoint_cfg", self._failpoint_cfg)
        h("failpoint_clear", self._failpoint_clear)
        h("failpoint_stat", lambda peer, name: failpoints.stat(name))
        # Distributed tracing: collect every process's span ring buffer
        # (head + nodes + their workers) in one fan-out.
        h("trace_dump", self._trace_dump)
        self._rpc.on_disconnect(self._peer_gone)
        # Actor-restart machinery (reference: GcsActorManager).
        import queue as _q

        self._restart_queue: "_q.Queue" = _q.Queue()
        self._node_clients: Dict[str, Any] = {}
        if self._store is not None:
            self._reload()
            # Epoch succession: whatever lease is on disk (written by the
            # previous incarnation, or shipped over from the incumbent
            # when this store belonged to a standby) is superseded.
            self._epoch = int(self._load_lease().get("epoch", 0)) + 1
            # TSDB continuity across failover/restart: per-origin seq
            # cursors and proc-death tombstones reload so re-shipped
            # metric frames dedup instead of double-counting and dead
            # origins stay dead (satellite: TSDB on failover).
            blob = self._store.load_all("meta").get("tsdb_state")
            if blob:
                import json as _json

                try:
                    self._metric_store.restore_seq_state(_json.loads(blob))
                except Exception as e:
                    errors.swallow("head.tsdb_restore", e)
        # Env-declared quotas seed tenants the store doesn't know yet;
        # persisted rows win (an operator's set-quota RPC outlives the
        # env of whichever incarnation happened to boot first).
        self._bootstrap_tenants()
        # Epoch rides every rpc_caps reply so head clients learn it at
        # connect time and stamp subsequent frames with it.
        self._rpc.capabilities["head_epoch"] = self._epoch
        self._rpc.frame_gate = self._frame_gate

    # -- persistence -------------------------------------------------------

    def _reload(self) -> None:
        """Rebuild tables from durable storage after a head restart.
        Actors reload as 'alive' at their recorded node; if that node never
        re-registers, the health loop's death path fires normally."""
        import json as _json

        self._kv = dict(self._store.load_all("kv"))
        for aid, blob in self._store.load_all("actors").items():
            info = _json.loads(blob)
            self._actors[aid] = info
            if info.get("name"):
                self._named[(info["namespace"], info["name"])] = aid
        # Explicit named-index rows overlay the rebuild above (they are
        # the write-after-mutation ground truth; the rebuild covers rows
        # written before the "named" table existed).
        for key, blob in self._store.load_all("named").items():
            ns, _, name = key.partition("\x1f")
            self._named[(ns, name)] = blob.decode()
        for pg_id, blob in self._store.load_all("pgs").items():
            self._pgs[pg_id] = _json.loads(blob)
        # Queued-infeasible specs: the pending scheduler thread replays
        # them once nodes re-register.
        self._pending_specs = dict(self._store.load_all("pending_tasks"))
        for tid, blob in self._pending_specs.items():
            try:
                spec = wire.loads(blob)
                self._pending_meta[tid] = (
                    str(getattr(spec, "tenant", "") or ""),
                    int(getattr(spec, "priority", 0) or 0))
            except Exception:
                self._pending_meta[tid] = ("", 0)
        # Tenant rows + in-flight placement records. Usage is recomputed
        # from the running records (not persisted per-mutation), so a
        # takeover restores quota accounting without the placement hot
        # path ever writing usage rows.
        for key, blob in self._store.load_all("tenants").items():
            try:
                row = _json.loads(blob)
            except ValueError:
                continue
            if not isinstance(row, dict):
                continue
            if key.startswith("t:"):
                self._tenants[key[2:]] = row
            elif key.startswith("r:"):
                self._tenant_running[key[2:]] = row
        self._recompute_tenant_usage()
        # Object directory snapshot: locations for nodes that never
        # re-register are filtered by the alive check in _locate_object
        # and dropped by _mark_dead / the next snapshot; meanwhile a
        # driver blocked in get() across the bounce resolves immediately
        # instead of waiting out every node's re-announce.
        snap = self._store.load_all("objects").get("snapshot")
        if snap:
            d = _json.loads(snap)
            self._objects = {oh: set(nids)
                             for oh, nids in d.get("locations", {}).items()}
            self._object_sizes = {oh: int(s)
                                  for oh, s in d.get("sizes", {}).items()}
        snap = self._store.load_all("borrows").get("snapshot")
        if snap:
            d = _json.loads(snap)
            self._borrows = {oh: set(bs)
                             for oh, bs in d.get("borrows", {}).items()}
            self._pending_free = set(d.get("pending_free", ()))
        tail = self._store.load_all("task_events").get("tail")
        if tail:
            try:
                self._task_event_store.add_batch(_json.loads(tail), 0)
            except Exception as e:
                errors.swallow("head.reload_task_events", e)
        # Reload is the new baseline: fold the WAL away so bounce cycles
        # never grow it unbounded.
        try:
            self._store.compact()
        except Exception as e:
            errors.swallow("head.reload_compact", e)

    def _persist_kv(self, key: str, value: Optional[bytes]) -> None:
        if self._store is None:
            return
        if value is None:
            self._store.delete("kv", key)
        else:
            self._store.put("kv", key, value)

    def _persist_actor(self, actor_id: str) -> None:
        if self._store is None:
            return
        import json as _json

        info = self._actors.get(actor_id)
        if info is None:
            self._store.delete("actors", actor_id)
        else:
            self._store.put("actors", actor_id,
                            _json.dumps(info).encode())

    def _persist_pg(self, pg_id: str) -> None:
        if self._store is None:
            return
        import json as _json

        pg = self._pgs.get(pg_id)
        if pg is None:
            self._store.delete("pgs", pg_id)
        else:
            self._store.put("pgs", pg_id, _json.dumps(pg).encode())

    def _persist_named(self, key: Tuple[str, str]) -> None:
        if self._store is None:
            return
        aid = self._named.get(key)
        skey = f"{key[0]}\x1f{key[1]}"
        if aid is None:
            self._store.delete("named", skey)
        else:
            self._store.put("named", skey, aid.encode())

    def _persist_pending_task(self, task_id: str) -> None:
        if self._store is None:
            return
        blob = self._pending_specs.get(task_id)
        if blob is None:
            self._store.delete("pending_tasks", task_id)
        else:
            self._store.put("pending_tasks", task_id, blob)

    def _persist_tenant(self, name: str) -> None:
        if self._store is None:
            return
        import json as _json

        row = self._tenants.get(name)
        if row is None:
            self._store.delete("tenants", f"t:{name}")
        else:
            self._store.put("tenants", f"t:{name}",
                            _json.dumps(row).encode())

    def _persist_tenant_run(self, task_id: str) -> None:
        if self._store is None:
            return
        import json as _json

        rec = self._tenant_running.get(task_id)
        if rec is None:
            self._store.delete("tenants", f"r:{task_id}")
        else:
            self._store.put("tenants", f"r:{task_id}",
                            _json.dumps(rec).encode())

    # -- multi-tenant scheduling -------------------------------------------

    def _bootstrap_tenants(self) -> None:
        """Seed quota rows from ``RAYTPU_TENANT_QUOTAS`` (grammar:
        ``"a=CPU:4,TPU:8;b=CPU:2"``) for tenants the store has no row
        for. Malformed clauses are skipped loudly, not fatally — a typo
        in an env var must not keep the control plane down."""
        spec = (tuning.TENANT_QUOTAS or "").strip()
        if not spec:
            return
        for clause in spec.split(";"):
            clause = clause.strip()
            if not clause:
                continue
            name, sep, body = clause.partition("=")
            name = name.strip()
            if not sep or not name or name in self._tenants:
                continue
            quota: Dict[str, float] = {}
            ok = bool(body.strip())
            for part in body.split(","):
                part = part.strip()
                if not part:
                    continue
                res, sep2, val = part.partition(":")
                if not sep2:
                    ok = False
                    break
                try:
                    quota[res.strip()] = float(val)
                except ValueError:
                    ok = False
                    break
            if not ok:
                from raytpu.util.events import record_event as _rec

                self._events.append(_rec(
                    "ERROR", "TENANT_QUOTA_CONFIG",
                    f"ignoring malformed RAYTPU_TENANT_QUOTAS clause "
                    f"{clause!r}"))
                continue
            self._tenants[name] = {"quota": quota,
                                   "weight": tuning.TENANT_DEFAULT_WEIGHT,
                                   "priority": 0, "pass": 0.0}
            self._persist_tenant(name)

    def _tenant_row(self, name: str) -> dict:
        """Caller holds ``self._lock``. First sight of a tenant creates
        its row with default weight, no quota (unlimited), and a virtual
        pass clamped to the current minimum among active tenants — an
        idle tenant must not bank credit and then monopolize the queue."""
        row = self._tenants.get(name)
        if row is None:
            floor = min((float(r.get("pass", 0.0))
                         for r in self._tenants.values()), default=0.0)
            row = {"quota": {}, "weight": tuning.TENANT_DEFAULT_WEIGHT,
                   "priority": 0, "pass": floor}
            self._tenants[name] = row
        return row

    def _recompute_tenant_usage(self) -> None:
        """Caller holds ``self._lock`` (or runs pre-start). Rebuild the
        derived usage map from the running records."""
        usage: Dict[str, Dict[str, float]] = {}
        for rec in self._tenant_running.values():
            t = rec.get("tenant") or ""
            if not t:
                continue
            u = usage.setdefault(t, {})
            for k, v in (rec.get("resources") or {}).items():
                u[k] = u.get(k, 0.0) + float(v)
        self._tenant_usage = usage

    def _tenant_over_quota(self, name: str,
                           requested: Dict[str, float]) -> bool:
        """Caller holds ``self._lock``. True when placing ``requested``
        would push any resource past the tenant's ceiling. No quota row
        (or an empty quota) means unlimited."""
        row = self._tenants.get(name)
        quota = (row or {}).get("quota") or {}
        if not quota:
            return False
        usage = self._tenant_usage.get(name, {})
        for res, ceiling in quota.items():
            if usage.get(res, 0.0) + requested.get(res, 0.0) \
                    > float(ceiling) + 1e-9:
                return True
        return False

    def _tenant_debit(self, tid: str, tenant_ctx: dict,
                      resources: Dict[str, float], node_id: str) -> None:
        """Caller holds ``self._lock``. Record an in-flight placement and
        debit the tenant's usage (in-memory; the caller persists the
        ``r:`` row after the lock drops)."""
        name = tenant_ctx.get("tenant") or ""
        self._tenant_running[tid] = {
            "tenant": name, "resources": dict(resources),
            "node": node_id,
            "priority": int(tenant_ctx.get("priority", 0) or 0),
            "preemptible": bool(tenant_ctx.get("preemptible", True)),
        }
        u = self._tenant_usage.setdefault(name, {})
        for k, v in resources.items():
            u[k] = u.get(k, 0.0) + float(v)

    def _tenant_credit(self, tid: str) -> bool:
        """Caller holds ``self._lock``. Retire a running record and
        credit its tenant's usage back. Returns True when a record
        existed (the caller persists the deletion after the lock)."""
        rec = self._tenant_running.pop(tid, None)
        if rec is None:
            return False
        name = rec.get("tenant") or ""
        u = self._tenant_usage.get(name)
        if u is not None:
            for k, v in (rec.get("resources") or {}).items():
                u[k] = u.get(k, 0.0) - float(v)
                if u[k] <= 1e-9:
                    u.pop(k, None)
            if not u:
                self._tenant_usage.pop(name, None)
        return True

    def _tenant_queued_counts(self) -> Dict[str, int]:
        """Caller holds ``self._lock``."""
        counts: Dict[str, int] = {}
        for t, _prio in self._pending_meta.values():
            if t:
                counts[t] = counts.get(t, 0) + 1
        return counts

    def _note_queued(self, tid: str, tenant: str, priority: int) -> None:
        """Caller holds ``self._lock``. Track a queued spec's tenant and
        clamp a newly-active tenant's pass (see ``_tenant_row``)."""
        self._pending_meta[tid] = (tenant, int(priority))
        if tuning.TENANTS and tenant:
            self._tenant_row(tenant)

    def _h_tenant_set_quota(self, peer: Peer, tenant: str,
                            quota: Optional[Dict[str, float]] = None,
                            weight: Optional[float] = None,
                            priority: Optional[int] = None) -> dict:
        if not tenant or not isinstance(tenant, str):
            raise ValueError("tenant name required")
        with self._lock:
            row = self._tenant_row(tenant)
            if quota is not None:
                row["quota"] = {str(k): float(v)
                                for k, v in dict(quota).items()}
            if weight is not None:
                w = float(weight)
                if w <= 0:
                    raise ValueError("tenant weight must be > 0")
                row["weight"] = w
            if priority is not None:
                row["priority"] = int(priority)
            out = dict(row)
        self._persist_tenant(tenant)
        return out

    def _tenant_view_locked(self, name: str) -> dict:
        row = self._tenants.get(name, {})
        queued = sum(1 for t, _p in self._pending_meta.values()
                     if t == name)
        running = sum(1 for r in self._tenant_running.values()
                      if (r.get("tenant") or "") == name)
        return {"tenant": name,
                "quota": dict(row.get("quota") or {}),
                "weight": float(row.get("weight",
                                        tuning.TENANT_DEFAULT_WEIGHT)),
                "priority": int(row.get("priority", 0)),
                "pass": float(row.get("pass", 0.0)),
                "usage": dict(self._tenant_usage.get(name, {})),
                "queued": queued, "running": running}

    def _h_tenant_info(self, peer: Peer, tenant: str) -> dict:
        with self._lock:
            return self._tenant_view_locked(tenant)

    def _h_tenant_list(self, peer: Peer) -> List[dict]:
        with self._lock:
            names = set(self._tenants) | set(self._tenant_usage)
            names.update(t for t, _p in self._pending_meta.values() if t)
            return [self._tenant_view_locked(n) for n in sorted(names)]

    def _snapshot(self) -> None:
        """Write-behind durability for the derived/hot tables: the object
        location+size directory, the borrow sets, and the flight-recorder
        tail. Per-mutation rows would put sqlite on the data-plane hot
        path; a whole-table snapshot on the health-loop cadence (and at
        shutdown) bounds the loss window to one period instead."""
        if self._store is None:
            return
        import json as _json

        with self._lock:
            objects = {oh: sorted(nids)
                       for oh, nids in self._objects.items()}
            sizes = dict(self._object_sizes)
            borrows = {oh: sorted(bs) for oh, bs in self._borrows.items()}
            pending_free = sorted(self._pending_free)
        tail: List[dict] = []
        for kind in ("task", "actor", "node"):
            for ent in self._task_event_store.list(kind, limit=500,
                                                   detail=True):
                tail.extend(ent.get("events") or ())
        try:
            self._store.snapshot_table("objects", {"snapshot": _json.dumps(
                {"locations": objects, "sizes": sizes}).encode()})
            self._store.snapshot_table("borrows", {"snapshot": _json.dumps(
                {"borrows": borrows, "pending_free": pending_free}).encode()})
            self._store.snapshot_table("task_events", {
                "tail": _json.dumps(tail).encode()})
            # TSDB sequencing state (per-origin seqs + death tombstones)
            # rides the meta table — a plain put, NOT snapshot_table,
            # because meta also holds the head lease row.
            self._store.put("meta", "tsdb_state", _json.dumps(
                self._metric_store.seq_state()).encode())
            self._last_snapshot = time.monotonic()
        except Exception as e:
            errors.swallow("head.snapshot", e)

    # -- hot standby: lease, fencing, WAL shipping -------------------------

    def _load_lease(self) -> dict:
        if self._store is None:
            return {}
        blob = self._store.load_all("meta").get("head_lease")
        if not blob:
            return {}
        import json as _json

        try:
            lease = _json.loads(blob)
        except ValueError:
            return {}
        return lease if isinstance(lease, dict) else {}

    def _renew_lease(self) -> None:
        """Rewrite the epoch-stamped lease row. Every renewal first
        re-validates the discovery record and self-fences on a higher
        epoch instead of writing: checking only when a renewal gap
        betrays a stall (SIGSTOP, long GC pause) is not enough — an
        election can race the resume and rewrite the record a moment
        AFTER the one gap check passed, leaving two heads serving
        (nodes still attached here stamp the matching old epoch, so
        the frame gate alone would never fence)."""
        if self._fenced:
            return
        if failpoint("head.lease_renew") is DROP:
            return  # renewal suppressed: the follower sees a stale lease
        rec = read_addr_record(self._addr_file)
        if rec and int(rec.get("epoch", 0) or 0) > self._epoch:
            self._fence(str(rec.get("address", "")), int(rec["epoch"]))
            return
        self._last_renew = time.monotonic()
        if self._store is not None:
            import json as _json

            self._store.put("meta", "head_lease", _json.dumps({
                "epoch": self._epoch,
                "owner": self.address or "",
                "ttl": tuning.HEAD_LEASE_TTL_S,
            }).encode())

    def _lease_loop(self) -> None:
        while not self._stop.wait(tuning.HEAD_LEASE_RENEW_PERIOD_S):
            try:
                self._renew_lease()
            except Exception as e:
                errors.swallow("head.lease_renew", e)

    def _fence(self, new_addr: str, new_epoch: int) -> None:
        """This head has been superseded (epoch ``new_epoch`` observed):
        freeze the store so a resumed stale incumbent cannot diverge its
        table file, and redirect all subsequent traffic."""
        with self._lock:
            if self._fenced:
                return
            self._fenced = True
            self._redirect_to = new_addr
            self._redirect_epoch = int(new_epoch)
        if self._store is not None:
            self._store.freeze()
        from raytpu.util.events import record_event as _rec

        self._events.append(_rec(
            "WARNING", "HEAD_FENCED",
            f"superseded by head {new_addr!r} (epoch {new_epoch}); "
            "store frozen, redirecting callers",
            epoch=int(new_epoch)))

    def _frame_gate(self, peer: Peer, frame: dict):
        """Split-brain fencing, enforced on every inbound frame: a
        fenced head redirects (node/driver traffic must not land on a
        stale incumbent), and an epoch mismatch either redirects the
        stale peer or — when the PEER has seen a newer head than us —
        fences this head on the spot."""
        if self._fenced:
            if frame.get("m") in _FENCE_EXEMPT:
                return None
            return HeadRedirect(self._redirect_to, self._redirect_epoch)
        ep = frame.get("ep")
        if ep is None:
            return None
        try:
            ep = int(ep)
        except (TypeError, ValueError):
            return None
        if ep > self._epoch:
            rec = read_addr_record(self._addr_file)
            addr = str(rec.get("address", "")) if rec else ""
            self._fence(addr, ep)
            return HeadRedirect(self._redirect_to, self._redirect_epoch)
        if ep < self._epoch:
            return HeadRedirect(self.address or "", self._epoch)
        return None

    def _write_addr_file(self) -> None:
        if not self._addr_file:
            return
        import json as _json

        try:
            tmp = f"{self._addr_file}.{os.getpid()}.tmp"
            with open(tmp, "w") as f:
                f.write(_json.dumps({"address": self.address,
                                     "epoch": self._epoch}))
            os.replace(tmp, self._addr_file)
        except OSError as e:
            errors.swallow("head.addr_file", e)

    def _h_wal_ship(self, peer: Peer, cursors: Dict[str, int],
                    tasks_cursor: int = 0) -> dict:
        """One follower poll: per-table WAL deltas (or full resyncs)
        past the follower's cursors, the placed-task log past its task
        cursor, fresh TSDB sequencing state, and this head's epoch. A
        successful reply doubles as the incumbent's liveness proof, so
        the failpoint below denies it by erroring, not by lying."""
        if failpoint("wire.wal_ship") is DROP:
            raise RpcError("wal_ship dropped by failpoint")
        if self._fenced:
            raise HeadRedirect(self._redirect_to, self._redirect_epoch)
        out: Dict[str, Any] = {
            "epoch": self._epoch,
            "addr": self.address or "",
            "ttl": tuning.HEAD_LEASE_TTL_S,
            "tables": {},
        }
        if self._store is not None:
            out["tables"] = self._store.ship(dict(cursors or {}),
                                             WAL_SHIP_TABLES)
        try:
            out["tsdb"] = self._metric_store.seq_state()
        except Exception as e:
            errors.swallow("head.wal_ship_tsdb", e)
        with self._lock:
            tc = int(tasks_cursor or 0)
            oldest = (self._placed_log[0][0] if self._placed_log
                      else self._placed_idx + 1)
            if tc + 1 < oldest:
                # The bounded log evicted entries past the follower's
                # cursor (long disconnect): deltas would silently omit
                # placements and a successor could double-dispatch.
                # Ship the whole dedup map instead — insertion order is
                # index order and each insert incremented _placed_idx,
                # so true indices are the trailing len(_placed) ones.
                base = self._placed_idx - len(self._placed) + 1
                out["placed_full"] = [
                    [base + i, tid, att]
                    for i, (tid, att) in enumerate(self._placed)]
                out["placed"] = []
            else:
                out["placed"] = [list(e) for e in self._placed_log
                                 if e[0] > tc]
            out["placed_idx"] = self._placed_idx
        return out

    def _h_head_info(self, peer: Peer) -> dict:
        return {"epoch": self._epoch, "address": self.address or "",
                "fenced": self._fenced}

    def _record_placed(self, tid: str, attempt: int) -> None:
        """Record a head-dispatched placement (caller holds _lock)."""
        key = (tid, int(attempt))
        if key in self._placed:
            return
        self._placed[key] = True
        while len(self._placed) > tuning.WAL_JOURNAL_MAX:
            self._placed.popitem(last=False)
        self._placed_idx += 1
        self._placed_log.append((self._placed_idx, tid, int(attempt)))

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> str:
        addr = self._rpc.start()
        tracing.set_process_identity("head")
        try:
            from raytpu.core.config import cfg

            port = int(cfg.head_metrics_port)
            if port:
                from raytpu.util.metrics import start_metrics_server

                if start_metrics_server(port):
                    self._metrics_port = port
        except Exception:  # metrics are best-effort, never block startup
            pass
        self._checker = threading.Thread(
            target=self._health_loop, name="head-health", daemon=True
        )
        self._checker.start()
        self._restarter = threading.Thread(
            target=self._restart_loop, name="head-actor-restart", daemon=True
        )
        self._restarter.start()
        self._pending_sched = threading.Thread(
            target=self._pending_sched_loop, name="head-pending-sched",
            daemon=True)
        self._pending_sched.start()
        # Claim the lease under the new epoch and publish the discovery
        # record before any caller can observe this head, then keep
        # renewing on a dedicated thread (the health loop's cadence is a
        # failure-detection knob; lease renewal must not inherit it).
        self._renew_lease()
        self._write_addr_file()
        self._lease_thread = threading.Thread(
            target=self._lease_loop, name="head-lease", daemon=True)
        self._lease_thread.start()
        if self._takeover:
            from raytpu.util.events import record_event as _rec

            self._events.append(_rec(
                "WARNING", "HEAD_FAILOVER",
                f"standby took over as epoch {self._epoch} at {addr}",
                epoch=self._epoch))
        if self._store is not None:
            # Recover reloaded actors: re-enqueue interrupted restarts now;
            # after a node-re-registration grace period, declare actors at
            # never-returning nodes failed so their restart path fires.
            with self._lock:
                for aid, info in self._actors.items():
                    if info["state"] == "restarting":
                        self._restart_queue.put((aid, "resumed after head "
                                                      "restart"))
            threading.Thread(target=self._reap_orphaned_actors,
                             name="head-reload-reaper", daemon=True).start()
        return addr

    def _reap_orphaned_actors(self) -> None:
        """Reloaded 'alive' actors whose node never re-registers would stay
        resolvable-but-dead forever (the health loop only scans registered
        nodes). Give nodes 2x the heartbeat window to come back, then run
        the normal failure path for the rest."""
        if self._stop.wait(HEARTBEAT_TIMEOUT_S * 2):
            return
        with self._lock:
            orphaned = [
                aid for aid, info in self._actors.items()
                if info["state"] == "alive" and (
                    info["node_id"] not in self._nodes
                    or not self._nodes[info["node_id"]].alive)
            ]
        for aid in orphaned:
            self._on_actor_failure(
                aid, "node lost during head downtime", no_restart=False)

    def stop(self) -> None:
        self._stop.set()
        self._restart_queue.put(None)
        self._rpc.stop()
        if self._metrics_port is not None:
            from raytpu.util.metrics import stop_metrics_server

            stop_metrics_server(self._metrics_port)
            self._metrics_port = None
        if self._store is not None:
            # Snapshot-on-shutdown: the write-behind tables are current
            # as of this instant, and the compaction folds the WAL away
            # so the next start reloads one clean file.
            try:
                self._snapshot()
                self._store.compact()
            except Exception as e:
                errors.swallow("head.stop_snapshot", e)
            try:
                self._store.close()
            except Exception:
                pass
        for c in self._node_clients.values():
            try:
                c.close()
            except Exception:
                pass

    @property
    def address(self) -> str:
        return self._rpc.address

    # -- node table --------------------------------------------------------

    def _register_node(self, peer: Peer, node_id: str, address: str,
                       resources: Dict[str, float],
                       labels: Dict[str, str]) -> dict:
        failpoint("head.node.register")
        with self._lock:
            entry = NodeEntry(node_id, address, resources, labels)
            entry.peer = peer
            peer.meta["node_id"] = node_id
            self._nodes[node_id] = entry
            snap = [n.snapshot() for n in self._nodes.values() if n.alive]
        # A (re-)registered node sheds any metric/profile tombstone so
        # shipping resumes after a head bounce or transient partition.
        self._metric_store.revive_proc(node_id[:12])
        self._profile_store.revive_proc(node_id[:12])
        if task_events.enabled():
            task_events.emit("node", node_id,
                             task_events.TaskTransition.NODE_ADDED,
                             name=labels.get("role") or "node",
                             node_id=node_id)
        self._publish("nodes", {"event": "added", "node": entry.snapshot()})
        # Epoch: the node stamps subsequent frames with it (fencing).
        # warm: this head was a WAL-shipping standby, so it already holds
        # the object directory — the node skips the full object replay on
        # re-register and only flushes its recent/unsent deltas.
        return {"nodes": snap, "epoch": self._epoch,
                "warm": self._takeover}

    def _heartbeat(self, peer: Peer, node_id: str,
                   available: Dict[str, float], seq: int = 0,
                   events: Optional[List[dict]] = None,
                   dropped: int = 0,
                   obj_deltas: Optional[List[list]] = None,
                   mframes: Optional[List[list]] = None,
                   mdropped: int = 0,
                   pframes: Optional[List[list]] = None,
                   pdropped: int = 0) -> None:
        # drop => the head never saw this heartbeat; enough consecutive
        # drops and the health loop declares the node dead. The node
        # requeues the piggybacked event batch on call failure, so a
        # dropped heartbeat loses liveness proof but not flight records.
        if failpoint("head.heartbeat.handle") is DROP:
            return
        with self._lock:
            entry = self._nodes.get(node_id)
            if entry is not None:
                entry.last_heartbeat = time.monotonic()
                # Ordered by the node's snapshot sequence: a preempted
                # heartbeat carrying an older snapshot must not overwrite
                # a fresher streaming delta (seq 0 = legacy, always apply).
                if seq == 0 or seq >= entry.avail_seq:
                    entry.available = dict(available)
                    entry.avail_seq = max(entry.avail_seq, seq)
        if events or dropped:
            self._task_event_store.add_batch(events or [], dropped)
        if obj_deltas:
            # Location deltas a node failed to flush directly ride the
            # liveness beat, exactly like the flight-recorder batches.
            self._apply_object_deltas(peer, node_id, obj_deltas)
        if mframes or mdropped:
            # Metric delta frames (node's own + relayed worker frames)
            # ride the same beat into the TSDB.
            self._metric_store.note_upstream_drops(int(mdropped or 0))
            self._metric_store.push(mframes or [])
        if pframes or pdropped:
            # Profile snapshots (node's own + relayed worker frames)
            # ride the same beat into the profile store; drops are
            # attributed to the shipping carrier so ``raytpu top
            # --profile`` can name the lossy proc.
            self._profile_store.note_upstream_drops(
                int(pdropped or 0), proc=f"node:{node_id[:12]}")
            self._profile_store.push(pframes or [])

    def _resource_update(self, peer: Peer, node_id: str,
                         available: Dict[str, float],
                         seq: int = 0) -> None:
        """Streaming delta from the node's resource-sync loop (reference:
        RaySyncer receiver side). Also proof of life — an alloc-churning
        node must never be declared dead between heartbeats."""
        self._heartbeat(peer, node_id, available, seq)

    def _drain_node(self, peer: Peer, node_id: str,
                    force: bool = True) -> dict:
        """Graceful removal. ``force=False`` (the autoscaler's idle
        scale-down path) refuses while the node hosts live actors — a
        node that looks idle by resource math can still be somebody's
        actor home, and reclaiming it would silently burn a restart."""
        with self._lock:
            actors = sum(1 for info in self._actors.values()
                         if info["node_id"] == node_id
                         and info["state"] == "alive")
        if not force and actors:
            return {"drained": False, "actors": actors}
        self._mark_dead(node_id, reason="drained")
        return {"drained": True, "actors": actors}

    def _list_nodes(self, peer: Peer) -> List[dict]:
        with self._lock:
            return [n.snapshot() for n in self._nodes.values()]

    # -- failpoints (chaos testing) ----------------------------------------

    def _failpoint_cfg(self, peer: Peer, name: str, spec: str,
                       scope: str = "local") -> List[str]:
        """Arm a failpoint on this head; ``scope="cluster"`` fans the same
        spec out to every live node daemon so a test can inject faults on
        remote processes it never spawned. Returns the ids it reached
        ("head" + node ids)."""
        failpoints.cfg(name, spec)
        reached = ["head"]
        if scope == "cluster":
            with self._lock:
                targets = [(n.node_id, n.address)
                           for n in self._nodes.values() if n.alive]
            for node_id, address in targets:  # rpc-loop-ok: chaos/debug fan-out to every node, cold path
                try:
                    self._node_client(node_id, address).call(
                        "failpoint_cfg", name, spec,
                        timeout=tuning.CONTROL_CALL_TIMEOUT_S,
                        breaker=breaker_for(address))
                    reached.append(node_id)
                except Exception as e:
                    # a dying node is exactly what chaos runs expect
                    errors.swallow("head.failpoint_cfg", e)
        return reached

    def _failpoint_clear(self, peer: Peer,
                         scope: str = "local") -> List[str]:
        failpoints.clear()
        reached = ["head"]
        if scope == "cluster":
            with self._lock:
                targets = [(n.node_id, n.address)
                           for n in self._nodes.values() if n.alive]
            for node_id, address in targets:  # rpc-loop-ok: chaos/debug fan-out to every node, cold path
                try:
                    self._node_client(node_id, address).call(
                        "failpoint_clear",
                        timeout=tuning.CONTROL_CALL_TIMEOUT_S,
                        breaker=breaker_for(address))
                    reached.append(node_id)
                except Exception as e:
                    errors.swallow("head.failpoint_clear", e)
        return reached

    # -- tracing -----------------------------------------------------------

    def _trace_dump(self, peer: Peer, scope: str = "cluster") -> List[dict]:
        """This head's span buffer; ``scope="cluster"`` (the default) fans
        out to every live node daemon — each of which collects its pool
        workers — in the same shape as ``failpoint_cfg``. An unreachable
        node just misses the timeline."""
        dumps: List[dict] = [tracing.dump()]
        if scope == "cluster":
            with self._lock:
                targets = [(n.node_id, n.address)
                           for n in self._nodes.values() if n.alive]
            for node_id, address in targets:  # rpc-loop-ok: chaos/debug fan-out to every node, cold path
                try:
                    got = self._node_client(node_id, address).call(
                        "trace_dump",
                        timeout=tuning.CONTROL_CALL_TIMEOUT_S,
                        breaker=breaker_for(address))
                    if isinstance(got, list):
                        dumps.extend(d for d in got if isinstance(d, dict))
                except Exception as e:
                    errors.swallow("head.trace_dump", e)
        return dumps

    def _peer_gone(self, peer: Peer) -> None:
        node_id = peer.meta.get("node_id")
        if node_id:
            self._mark_dead(node_id, reason="connection lost")
        with self._lock:
            for peers in self._subscribers.values():
                if peer in peers:
                    peers.remove(peer)
            # Object waiters registered by the departed peer would leak
            # (they're only popped when the object is first reported).
            for oid in list(self._object_waiters):
                waiters = [p for p in self._object_waiters[oid]
                           if p is not peer]
                if waiters:
                    self._object_waiters[oid] = waiters
                else:
                    del self._object_waiters[oid]

    def _health_loop(self) -> None:
        last_tick = time.monotonic()
        while not self._stop.wait(CHECK_PERIOD_S):
            # This loop oversleeping means this process, or the whole
            # host, stood still: four TPU runtimes starting at once froze
            # a v5e host for 16 s. No heartbeat could be received in that
            # stretch, so it counts against no node.
            tick = time.monotonic()
            stalled = tick - last_tick - CHECK_PERIOD_S
            last_tick = tick
            if self._fenced:
                # A superseded head must not keep declaring nodes dead
                # or firing alerts — the elected head owns the cluster.
                continue
            self._ingest_local_events()
            self._ingest_local_metrics()
            self._ingest_local_profile()
            now = time.monotonic()
            dead = []
            with self._lock:
                if stalled > CHECK_PERIOD_S:
                    for entry in self._nodes.values():
                        entry.last_heartbeat += stalled
                for entry in self._nodes.values():
                    if entry.alive and \
                            now - entry.last_heartbeat > HEARTBEAT_TIMEOUT_S:
                        dead.append(entry.node_id)
                self._metrics.refresh(list(self._nodes.values()),
                                      self._actors, self._pgs)
            for node_id in dead:
                self._mark_dead(node_id, reason="heartbeat timeout")
            try:
                self._alerts.tick()
            except Exception as e:
                errors.swallow("head.alerts.tick", e)
            if self._store is not None and \
                    now - self._last_snapshot > tuning.HEAD_SNAPSHOT_PERIOD_S:
                self._snapshot()

    def _mark_dead(self, node_id: str, reason: str) -> None:
        with self._lock:
            entry = self._nodes.get(node_id)
            if entry is None or not entry.alive:
                return
            entry.alive = False
            self._node_clients.pop(node_id, None)
            affected = [
                aid for aid, info in self._actors.items()
                if info["node_id"] == node_id and info["state"] == "alive"
            ]
            # Tenant usage held by the dead node's in-flight tasks is
            # freed now — task_done will never arrive for them, and a
            # leaked debit would throttle the tenant forever.
            credited_runs = [
                tid for tid, rec in self._tenant_running.items()
                if rec.get("node") == node_id
            ]
            for tid in credited_runs:
                self._tenant_credit(tid)
            lost_objects = []
            for oid in list(self._objects):
                self._objects[oid].discard(node_id)
                if not self._objects[oid]:
                    del self._objects[oid]
                    self._object_sizes.pop(oid, None)
                    lost_objects.append(oid)
            # Free PG bundles placed on the dead node; the nulled
            # placement is durable state (a reloaded head must not
            # believe a bundle still sits on a node that died).
            for pg_id, pg in self._pgs.items():
                if node_id in pg["nodes"]:
                    pg["nodes"] = [
                        (None if n == node_id else n) for n in pg["nodes"]
                    ]
                    self._persist_pg(pg_id)
        for tid in credited_runs:
            self._persist_tenant_run(tid)
        if task_events.enabled():
            task_events.emit("node", node_id,
                             task_events.TaskTransition.NODE_DIED,
                             error=reason, node_id=node_id)
        self._publish("nodes", {"event": "removed", "node_id": node_id,
                                "reason": reason})
        # Owners of objects whose last copy just died find out now, not
        # at their next poll: lineage owners re-execute, and completed
        # actor-call returns (no lineage) fail fast instead of leaving
        # their getters blocked forever.
        for oid in lost_objects:
            self._publish("objects", {"event": "unavailable",
                                      "object_id": oid})
        from raytpu.util.events import record_event

        with self._lock:
            self._events.append(record_event(
                "ERROR", "NODE_DIED",
                f"node {node_id[:8]} removed: {reason}",
                node_id=node_id, reason=reason))
        self._drop_borrower_prefix(node_id)
        # Tombstone the dead node's metric/profile procs (daemon + its
        # workers): their series and stack rings drop and any late frame
        # is rejected, so the death can't resurrect stale series.
        self._metric_store.mark_proc_dead(node_id[:12])
        self._profile_store.mark_proc_dead(node_id[:12])
        for aid in affected:
            self._on_actor_failure(aid, f"node {node_id} {reason}",
                                   no_restart=False)

    # -- borrower protocol --------------------------------------------------

    def _prune_early_releases(self) -> None:
        """Caller holds self._lock. Expire stale tombstones and bound the
        table so unmatched releases can't grow it or cancel a much-later
        legitimate borrow of the same (oid, borrower) pair."""
        now = time.monotonic()
        dead = [k for k, t in self._early_releases.items()
                if now - t > self._early_release_ttl_s]
        for k in dead:
            del self._early_releases[k]
        while len(self._early_releases) > self._early_release_cap:
            self._early_releases.pop(next(iter(self._early_releases)))

    def _borrow_added(self, peer: Peer, oid_hexes: List[str],
                      borrower: str) -> bool:
        with self._lock:
            self._prune_early_releases()
            for oh in oid_hexes:
                if self._early_releases.pop((oh, borrower), None) is not None:
                    continue  # released before the add landed
                self._borrows.setdefault(oh, set()).add(borrower)
        return True

    def _borrow_released(self, peer: Peer, oid_hex: str,
                         borrower: str) -> None:
        free_now = False
        with self._lock:
            self._prune_early_releases()
            holders = self._borrows.get(oid_hex)
            if holders is None or borrower not in holders:
                self._early_releases[(oid_hex, borrower)] = time.monotonic()
            if holders is not None:
                holders.discard(borrower)
                if not holders:
                    del self._borrows[oid_hex]
                    free_now = oid_hex in self._pending_free
        if free_now:
            self._do_free(oid_hex)

    def _task_done(self, peer: Peer, task_id_hex: str,
                   node_id: str) -> None:
        self._metrics.tick_task_done()
        with self._lock:
            credited = self._tenant_credit(task_id_hex)
        if credited:
            self._persist_tenant_run(task_id_hex)
        self._publish("tasks", {"event": "done", "task_id": task_id_hex,
                                "node_id": node_id})

    def _report_event(self, peer: Peer, event: dict) -> None:
        event = dict(event)
        # Whitelist the severity: this field drives dashboard rendering
        # and filtering; arbitrary peer input degrades to INFO.
        if event.get("severity") not in ("DEBUG", "INFO", "WARNING",
                                         "ERROR", "FATAL"):
            event["severity"] = "INFO"
        with self._lock:
            self._events.append(event)

    def _list_events(self, peer: Peer, severity: Optional[str] = None,
                     label: Optional[str] = None,
                     limit: int = 200) -> List[dict]:
        with self._lock:
            events = list(self._events)
        if severity:
            events = [e for e in events
                      if e.get("severity") == severity.upper()]
        if label:
            events = [e for e in events if e.get("label") == label]
        if int(limit) <= 0:
            return []
        return events[-int(limit):]

    # -- flight recorder ----------------------------------------------------

    def _ingest_local_events(self) -> None:
        """Fold the head's OWN process ring into the store. Runs from the
        health loop and lazily before every state query, so head-emitted
        transitions (NODE_*/SCHEDULED/actor lifecycle) are never staler
        than one query."""
        if not task_events.ship_enabled():
            return
        batch, dropped = task_events.drain()
        if batch or dropped:
            self._task_event_store.add_batch(batch, dropped)

    def _h_report_task_events(self, peer: Peer, events: List[dict],
                              dropped: int = 0) -> None:
        """Batch ingest off the notify path (drivers flush through their
        serve-only node daemon; worker batches arrive relayed via their
        node's heartbeat instead)."""
        self._task_event_store.add_batch(events or [], dropped)

    def _state_list(self, peer: Peer, kind: str,
                    state: Optional[str] = None, node: Optional[str] = None,
                    name: Optional[str] = None, limit: int = 100,
                    detail: bool = False) -> List[dict]:
        self._ingest_local_events()
        return self._task_event_store.list(kind, state=state, node=node,
                                           name=name, limit=limit,
                                           detail=detail)

    def _state_summary(self, peer: Peer, kind: str) -> dict:
        self._ingest_local_events()
        return self._task_event_store.summary(kind)

    def _state_timeline(self, peer: Peer, entity_id: str,
                        kind: str = "task") -> Optional[dict]:
        self._ingest_local_events()
        return self._task_event_store.get(kind, entity_id)

    def _task_events_stats(self, peer: Peer) -> dict:
        self._ingest_local_events()
        return self._task_event_store.stats()

    # -- metrics pipeline ---------------------------------------------------

    def _ingest_local_metrics(self) -> None:
        """Fold the head's OWN registry deltas (cluster gauges, schedule
        counters) into the TSDB. Runs from the health loop and lazily
        before every metrics query, so head-side series are never staler
        than one query. One flag check when shipping is disabled."""
        if not metrics.enabled():
            return
        metrics.collect(min_interval_s=tuning.METRICS_SHIP_PERIOD_S)
        frames, dropped = metrics.drain()
        if dropped:
            self._metric_store.note_upstream_drops(dropped)
        if frames:
            self._metric_store.push(frames)

    def _ingest_local_profile(self) -> None:
        """Fold the head's OWN continuous-profile snapshots into the
        profile store (health loop + lazily before profile queries).
        One flag check when profiling is disabled."""
        if profiler.profiling_enabled():
            frames, dropped = profiler.prof_drain()
            if dropped:
                self._profile_store.note_upstream_drops(dropped,
                                                        proc="head")
            if frames:
                self._profile_store.push(frames)

    def _h_profile_query(self, peer: Peer, mode: str = "merged",
                         since_s: float = 600.0, until_s: float = 0.0,
                         recent_s: float = 120.0,
                         procs: Optional[List[str]] = None) -> dict:
        self._ingest_local_profile()
        if mode == "diff":
            return self._profile_store.diff(float(recent_s))
        return self._profile_store.merged(float(since_s),
                                          float(until_s), procs=procs)

    def _h_profile_stats(self, peer: Peer) -> dict:
        self._ingest_local_profile()
        return {"store": self._profile_store.stats(),
                "procs": self._profile_store.proc_rows()}

    def _h_metrics_push(self, peer: Peer, frames: List[list],
                        dropped: int = 0) -> int:
        if dropped:
            self._metric_store.note_upstream_drops(int(dropped))
        return self._metric_store.push(frames or [])

    def _h_profile_push(self, peer: Peer, frames: List[list],
                        dropped: int = 0) -> int:
        """Direct profile-frame ingest off the heartbeat path — the
        driver's final flush at shutdown (its embedded node's heartbeat
        loop is already gone by then)."""
        if dropped:
            self._profile_store.note_upstream_drops(int(dropped))
        return self._profile_store.push(frames or [])

    def _h_metrics_query(self, peer: Peer, name: str,
                         tags: Optional[Dict[str, str]] = None,
                         agg: str = "sum", since_s: float = 600.0,
                         step: Optional[float] = None) -> dict:
        self._ingest_local_metrics()
        return self._metric_store.query(name, tags=tags, agg=agg,
                                        since_s=float(since_s), step=step)

    def _h_metrics_series(self, peer: Peer,
                          prefix: Optional[str] = None) -> List[dict]:
        self._ingest_local_metrics()
        return self._metric_store.series(prefix)

    def _h_metrics_prometheus(self, peer: Peer) -> str:
        self._ingest_local_metrics()
        return self._metric_store.prometheus_text()

    def _h_metrics_stats(self, peer: Peer) -> dict:
        return self._metric_store.stats()

    def _h_metrics_set_alert_rules(self, peer: Peer,
                                   spec: str) -> List[str]:
        rules = tsdb.parse_alert_rules(spec)  # malformed -> RPC error
        self._alerts.set_rules(rules)
        return [r.name for r in rules]

    def _h_metrics_alerts(self, peer: Peer) -> dict:
        return {"rules": [r.name for r in self._alerts.rules],
                "firing": self._alerts.firing()}

    def _on_alert_fire(self, rule: "tsdb.AlertRule", value: float) -> None:
        from raytpu.util.events import record_event

        ev = record_event(
            "ERROR", "SLO_ALERT",
            f"alert firing: {rule.name} (value {value:.6g})",
            rule=rule.name, metric=rule.metric, value=float(value))
        with self._lock:
            self._events.append(ev)

    def _on_alert_resolve(self, rule: "tsdb.AlertRule",
                          value: float) -> None:
        from raytpu.util.events import record_event

        ev = record_event(
            "INFO", "SLO_ALERT_RESOLVED",
            f"alert resolved: {rule.name} (value {value:.6g})",
            rule=rule.name, metric=rule.metric, value=float(value))
        with self._lock:
            self._events.append(ev)

    def _borrow_info(self, peer: Peer) -> dict:
        with self._lock:
            return {"borrows": {k: sorted(v)
                                for k, v in self._borrows.items()},
                    "pending_free": sorted(self._pending_free)}

    def _request_free(self, peer: Peer, oid_hex: str) -> bool:
        """Owner's refcount hit zero. Frees cluster copies unless borrowers
        still hold the object — then the free is deferred until the last
        borrow_released (or borrower death). Returns True when freed now."""
        with self._lock:
            if self._borrows.get(oid_hex):
                self._pending_free.add(oid_hex)
                return False
        self._do_free(oid_hex)
        return True

    def _do_free(self, oid_hex: str) -> None:
        with self._lock:
            self._pending_free.discard(oid_hex)
            # The locations themselves are retired by each holder's "-"
            # delta after it deletes its copy; the size entry can go now
            # (bounded-memory eviction on free — a freed oid must not
            # occupy a LOCALITY_DIR_MAX slot until the deltas land).
            self._object_sizes.pop(oid_hex, None)
            holders = []
            for node_id in self._objects.get(oid_hex, set()):
                entry = self._nodes.get(node_id)
                if entry is not None and entry.alive:
                    holders.append((node_id, entry.address))
        for node_id, address in holders:  # rpc-loop-ok: owner free fans to each holder, head-gated
            try:
                self._node_client(node_id, address).notify(
                    "free_object", oid_hex)
            except Exception as e:
                errors.swallow("head.free_object", e)

    def _node_client(self, node_id: str, address: str):
        client = self._node_clients.get(node_id)
        if client is None or client.closed:
            # Per-peer breaker gates the reconnect: fan-out paths (free
            # notifies, failpoint arming, actor restarts) skip a peer
            # whose breaker is open instead of burning a TCP connect
            # timeout each — callers already tolerate per-node failure,
            # so an open breaker degrades to partial fan-out.
            breaker = breaker_for(address)
            breaker.allow()  # raises CircuitOpenError while open
            try:
                client = RpcClient(address)
            except Exception:
                breaker.record_failure()
                raise
            breaker.record_success()
            self._node_clients[node_id] = client
        return client

    def _drop_borrower_prefix(self, node_id: str) -> None:
        """A node died: every borrower on it is gone; deferred frees whose
        last borrower lived there fire now."""
        prefix = node_id + ":"
        to_free = []
        with self._lock:
            for oh in list(self._borrows):
                holders = self._borrows[oh]
                holders.difference_update(
                    {b for b in holders if b.startswith(prefix)})
                if not holders:
                    del self._borrows[oh]
                    if oh in self._pending_free:
                        to_free.append(oh)
        for oh in to_free:
            self._do_free(oh)

    # -- kv ----------------------------------------------------------------

    def _kv_put(self, peer: Peer, key: str, value: bytes,
                overwrite: bool = True) -> bool:
        with self._lock:
            if not overwrite and key in self._kv:
                return False
            self._kv[key] = value
            self._persist_kv(key, value)
            return True

    def _kv_get(self, peer: Peer, key: str) -> Optional[bytes]:
        with self._lock:
            return self._kv.get(key)

    def _kv_del(self, peer: Peer, key: str) -> bool:
        with self._lock:
            existed = self._kv.pop(key, None) is not None
            if existed:
                self._persist_kv(key, None)
            return existed

    def _kv_keys(self, peer: Peer, prefix: str = "") -> List[str]:
        with self._lock:
            return [k for k in self._kv if k.startswith(prefix)]

    # -- scheduling --------------------------------------------------------

    def _schedule(self, peer: Peer, resources: Dict[str, float],
                  node_hint: Optional[str] = None,
                  spread_threshold: float = 0.5,
                  req_id: Optional[str] = None,
                  arg_oids: Optional[List[str]] = None) -> Optional[str]:
        """Pick a node for a task/actor of this shape. Hybrid policy
        (reference: hybrid_scheduling_policy.h:50): prefer the hinted /
        most-utilized feasible node until utilization crosses the spread
        threshold, then pick the least-utilized feasible node.
        ``arg_oids`` (appended param, older clients omit it) lets the
        locality scorer steer the decision toward the feasible node
        already holding the most argument bytes."""
        if tuning.TENANTS:
            # Admission control on the per-call path mirrors the batched
            # one: a tenant whose head backlog is at its queued budget
            # gets a typed retryable shed (the client's RetryPolicy
            # honors retry_after_s) instead of deepening the overload.
            t = tenancy.current_tenant()
            if t:
                with self._lock:
                    backlog = sum(
                        1 for tt, _p in self._pending_meta.values()
                        if tt == t)
                if failpoint("head.admission") is DROP or \
                        backlog >= tuning.TENANT_MAX_QUEUED:
                    self._metrics.tick_tenant(
                        self._metrics.tenant_throttled, t)
                    raise TenantThrottled(
                        t, tuning.TENANT_RETRY_DELAY_S,
                        "tenant backlog at head queue budget")
        # The decision span links a driver's submit span to the chosen
        # node's execution span; the outcome rides as an attribute.
        with tracing.span("sched.decide") as attrs:
            node_id = self._schedule_impl(peer, resources, node_hint,
                                          spread_threshold, req_id,
                                          arg_oids, attrs)
            attrs["node"] = node_id
            # req_id IS the task id (clients key their schedule requests
            # by it), so the decision lands on the task's timeline.
            if node_id is not None and req_id and task_events.enabled():
                task_events.emit("task", req_id,
                                 task_events.TaskTransition.SCHEDULED,
                                 node_id=node_id)
            return node_id

    def _schedule_impl(self, peer: Peer, resources: Dict[str, float],
                       node_hint: Optional[str] = None,
                       spread_threshold: float = 0.5,
                       req_id: Optional[str] = None,
                       arg_oids: Optional[List[str]] = None,
                       attrs: Optional[dict] = None,
                       tenant_ctx: Optional[dict] = None) -> Optional[str]:
        self._metrics.tick_schedule()
        if tenant_ctx is None and tuning.TENANTS:
            # Bare schedule() RPC: the tenant rides the frame ("tn"),
            # re-anchored per dispatch, not the call signature.
            t = tenancy.current_tenant()
            if t:
                tenant_ctx = {"tenant": t, "priority": 0,
                              "preemptible": True}
        deferred: List[tuple] = []
        with self._lock:
            node_id = self._schedule_locked(resources, node_hint,
                                            spread_threshold, req_id,
                                            arg_oids, attrs, deferred,
                                            tenant_ctx)
        self._run_eager_pushes(deferred)
        if node_id is not None and req_id and tuning.TENANTS and \
                tenant_ctx and tenant_ctx.get("tenant"):
            self._persist_tenant_run(req_id)
            self._metrics.tick_tenant(self._metrics.tenant_placed,
                                      tenant_ctx["tenant"])
        return node_id

    def _schedule_locked(self, resources: Dict[str, float],
                         node_hint: Optional[str] = None,
                         spread_threshold: float = 0.5,
                         req_id: Optional[str] = None,
                         arg_oids: Optional[List[str]] = None,
                         attrs: Optional[dict] = None,
                         deferred: Optional[List[tuple]] = None,
                         tenant_ctx: Optional[dict] = None
                         ) -> Optional[str]:
        """One placement decision. Caller holds ``self._lock`` — the
        batched submit path places a whole burst under one acquisition.
        Pure compute by contract (lint rule RTP013): side effects the
        decision wants (eager arg pushes) are appended to ``deferred``
        for the caller to fire after the lock is released.

        ``tenant_ctx`` (``{"tenant", "priority", "preemptible"}``) arms
        the quota gate: an over-ceiling tenant's request reads as
        infeasible (queued, not failed — capacity its peers free up
        re-admits it), and a placement is debited against the tenant's
        in-flight usage. ``RAYTPU_TENANTS=0`` never reaches this branch,
        so the decision sequence is identical to the blind scheduler."""
        tenant = (tenant_ctx or {}).get("tenant") or "" \
            if tuning.TENANTS else ""
        if tenant:
            forced = failpoint("sched.quota_check") is DROP
            if forced or self._tenant_over_quota(tenant, resources):
                key = req_id or os.urandom(8).hex()
                self._unmet[key] = (time.monotonic(), dict(resources))
                if attrs is not None:
                    attrs["quota_hit"] = \
                        int(attrs.get("quota_hit") or 0) + 1
                return None
        feasible = []
        for entry in self._nodes.values():
            if not entry.alive or entry.labels.get("role") == "driver":
                continue
            if all(entry.available.get(k, 0.0) >= v - 1e-9
                   for k, v in resources.items()):
                feasible.append(entry)
        if not feasible:
            key = req_id or os.urandom(8).hex()
            self._unmet[key] = (time.monotonic(), dict(resources))
            if len(self._unmet) > 10_000:
                cutoff = time.monotonic() - 10.0
                self._unmet = {k: v for k, v in self._unmet.items()
                               if v[0] >= cutoff}
            return None
        if req_id is not None:
            self._unmet.pop(req_id, None)
        if node_hint:
            for entry in feasible:
                if entry.node_id == node_hint:
                    return entry.node_id

        # Locality: narrow the candidate pool to the feasible nodes
        # already holding the most argument bytes. Advisory only — a
        # miss (tie, unknown sizes, total under the floor) leaves the
        # pool untouched, and an infeasible holder was never in it.
        pool = feasible
        if tuning.LOCALITY and arg_oids:
            pool = self._locality_filter(feasible, arg_oids, attrs)

        def utilization(e: NodeEntry) -> float:
            fracs = [
                1.0 - e.available.get(k, 0.0) / t
                for k, t in e.total.items() if t > 0
            ]
            return max(fracs) if fracs else 0.0

        packed = sorted(pool, key=lambda e: (-utilization(e),
                                             e.node_id))
        best = packed[0]
        if utilization(best) >= spread_threshold:
            best = min(packed, key=lambda e: (utilization(e),
                                              e.node_id))
        # Optimistic debit: bursts of schedule() calls between 1s
        # heartbeats must see each other's placements or they all pack
        # onto the same node (heartbeats overwrite with ground truth).
        for k, v in resources.items():
            best.available[k] = best.available.get(k, 0.0) - v
        if deferred is not None and arg_oids and tuning.LOCALITY and \
                tuning.LOCALITY_EAGER_PUSH:
            self._queue_eager_pushes(best.node_id, arg_oids, deferred)
        if tenant and req_id:
            # In-memory debit only; the caller persists the r: row after
            # the lock drops (RTP013 keeps this region compute-only).
            self._tenant_debit(req_id, tenant_ctx, resources,
                               best.node_id)
        return best.node_id

    def _locality_filter(self, feasible: List["NodeEntry"],
                         arg_oids: List[str],
                         attrs: Optional[dict]) -> List["NodeEntry"]:
        """Caller holds ``self._lock``. Score each feasible node by the
        wire bytes of the task's arguments it already holds and return
        the top-scoring subset — pack/spread then runs inside it, so
        utilization still breaks ties among equally-local nodes. A hit
        requires the best score to clear ``LOCALITY_MIN_BYTES`` AND to
        actually discriminate (a proper subset); otherwise the full pool
        comes back and the decision matches the locality-blind policy."""
        scores: Dict[str, int] = {}
        for oh in arg_oids:
            holders = self._objects.get(oh)
            if not holders:
                continue
            size = self._object_sizes.get(oh, 0)
            if size <= 0:
                continue
            for nid in holders:
                scores[nid] = scores.get(nid, 0) + size
        top = max((scores.get(e.node_id, 0) for e in feasible), default=0)
        winners = [e for e in feasible if scores.get(e.node_id, 0) == top]
        hit = (top >= max(1, tuning.LOCALITY_MIN_BYTES)
               and len(winners) < len(feasible))
        if attrs is not None:
            # Accumulating, so one submit_batch span reads as hit count
            # + total steered bytes across the burst.
            attrs["locality_hit"] = int(attrs.get("locality_hit") or 0) + \
                (1 if hit else 0)
            attrs["locality_bytes"] = \
                int(attrs.get("locality_bytes") or 0) + (top if hit else 0)
        return winners if hit else feasible

    def _queue_eager_pushes(self, chosen: str, arg_oids: List[str],
                            deferred: List[tuple]) -> None:
        """Caller holds ``self._lock``. Locality lost (or partially lost):
        for each large argument the chosen node does not hold, pick a live
        holder and record a push directive. The caller fires them after
        releasing the lock, so the transfer overlaps the task's trip
        through submit/queue instead of serializing with execute."""
        target = self._nodes.get(chosen)
        if target is None:
            return
        for oh in arg_oids:
            if self._object_sizes.get(oh, 0) < \
                    max(1, tuning.LOCALITY_MIN_BYTES):
                continue
            holders = self._objects.get(oh)
            if not holders or chosen in holders:
                continue
            for nid in sorted(holders):
                src = self._nodes.get(nid)
                if src is not None and src.alive:
                    deferred.append((nid, oh, target.address))
                    break

    def _run_eager_pushes(self, deferred: List[tuple]) -> None:
        """Fire the push directives the scheduler queued under the lock,
        reusing the demand-push plumbing: the holder node is told to
        stream the object to the chosen node (``push_requests`` topic,
        received by ``NodeServer._on_push_request``)."""
        for nid, oh, target_addr in deferred:  # rpc-loop-ok: eager-push directives, fired after the sched lock is released
            with self._lock:
                src = self._nodes.get(nid)
                address = src.address if src is not None and src.alive \
                    else None
            if address is None:
                continue
            try:
                self._node_client(nid, address).notify(
                    "push_request", {"object_id": oh,
                                     "targets": [target_addr]})
            except Exception as e:
                errors.swallow("head.eager_push", e)

    def _submit_batch(self, peer: Peer, blob: bytes) -> List[Any]:
        """Pipelined submission fast path: N TaskSpecs decoded from one
        frame, placed FIFO in one ``sched.decide`` pass under a single
        ``_lock`` acquisition. Per spec the reply is ``{"node_id",
        "address"}`` (placed — address included so the driver skips the
        per-task ``list_nodes`` lookup), ``{"err": ...}`` (that spec
        failed; the others are unaffected), or ``{"queued": True}``
        (infeasible now — the head owns the spec, durably when storage
        is on, and its pending scheduler dispatches it when capacity
        appears; the driver stops tracking it as pending)."""
        specs = wire.loads(blob)
        placements: List[Any] = []
        deferred: List[tuple] = []
        persist: List[str] = []
        persist_runs: List[str] = []
        shed: List[str] = []
        with tracing.span("sched.decide") as attrs:
            with self._lock:
                queued_counts = self._tenant_queued_counts() \
                    if tuning.TENANTS else {}
                for spec in specs:
                    self._metrics.tick_schedule()
                    tid = spec.task_id.hex()
                    tenant = str(getattr(spec, "tenant", "") or "")
                    priority = int(getattr(spec, "priority", 0) or 0)
                    tenant_ctx = None
                    if tuning.TENANTS and tenant:
                        tenant_ctx = {
                            "tenant": tenant, "priority": priority,
                            "preemptible": bool(getattr(
                                spec, "preemptible", True)),
                        }
                    # Failover dedup: a driver resubmitting across a
                    # head failover must not double-launch a task this
                    # head (via WAL-shipped state) already owns queued
                    # or already dispatched to a node. A HIGHER attempt
                    # (node-death resubmit) supersedes the queued copy.
                    attempt = int(getattr(spec, "attempt", 0) or 0)
                    if (tid, attempt) in self._placed:
                        placements.append({"queued": True})
                        continue
                    if tid in self._pending_specs:
                        self._pending_specs[tid] = wire.dumps(spec)
                        self._note_queued(tid, tenant, priority)
                        persist.append(tid)
                        placements.append({"queued": True})
                        continue
                    if tenant_ctx is not None:
                        # Admission control: a tenant whose head backlog
                        # is already at its queued-spec budget is shed
                        # with a typed retry-after instead of growing
                        # the pending table without bound (overload
                        # protection, not fairness — the WFQ replay
                        # handles fairness among admitted work). Dedup
                        # ran first: resubmissions of specs this head
                        # already owns never read as new load.
                        forced = failpoint("head.admission") is DROP
                        if forced or queued_counts.get(tenant, 0) \
                                >= tuning.TENANT_MAX_QUEUED:
                            placements.append({
                                "throttled":
                                    tuning.TENANT_RETRY_DELAY_S,
                                "tenant": tenant})
                            shed.append(tenant)
                            continue
                    try:
                        arg_oids = [o.hex() for o in spec.arg_ref_oids()]
                        node_id = self._schedule_locked(
                            dict(spec.resources or {}), None, 0.5,
                            tid, arg_oids, attrs, deferred, tenant_ctx)
                    except Exception as e:  # noqa: BLE001 — per-spec fault
                        placements.append({"err": str(e)})
                        continue
                    if node_id is None:
                        # Queue-at-head: the spec survives a head bounce
                        # (pending_tasks table) and re-drives placement
                        # from here, not from a driver that may be
                        # blocked in get() across the bounce.
                        self._pending_specs[tid] = wire.dumps(spec)
                        self._note_queued(tid, tenant, priority)
                        if tenant:
                            queued_counts[tenant] = \
                                queued_counts.get(tenant, 0) + 1
                        persist.append(tid)
                        placements.append({"queued": True})
                        continue
                    if self._pending_specs.pop(tid, None) is not None:
                        self._pending_meta.pop(tid, None)
                        persist.append(tid)
                    if tenant_ctx is not None:
                        persist_runs.append(tid)
                    entry = self._nodes.get(node_id)
                    placements.append(
                        {"node_id": node_id,
                         "address": entry.address if entry else None})
            # Persistence runs after the placement lock (RTP013 keeps the
            # lock-held region compute-only); a crash in the gap merely
            # re-runs the driver's own retry path.
            for tid in persist:
                self._persist_pending_task(tid)
            for tid in persist_runs:
                self._persist_tenant_run(tid)
            for spec, p in zip(specs, placements):
                if isinstance(p, dict) and p.get("node_id") and \
                        getattr(spec, "tenant", ""):
                    self._metrics.tick_tenant(self._metrics.tenant_placed,
                                              spec.tenant)
            for tenant in shed:
                self._metrics.tick_tenant(self._metrics.tenant_throttled,
                                          tenant)
            self._run_eager_pushes(deferred)
            attrs["batch"] = len(placements)
            attrs["node"] = sum(1 for p in placements
                                if isinstance(p, dict) and "node_id" in p)
            if task_events.enabled():
                for spec, p in zip(specs, placements):
                    if isinstance(p, dict) and p.get("node_id"):
                        task_events.emit(
                            "task", spec.task_id.hex(),
                            task_events.TaskTransition.SCHEDULED,
                            node_id=p["node_id"])
        return placements

    def _wfq_order_locked(self) -> List[Tuple[str, bytes]]:
        """Caller holds ``self._lock``. Order the queued specs for one
        replay scan. Tenancy off (or everything untenanted): insertion
        order — byte-identical to the historical FIFO. Tenancy on:
        weighted fair queueing by stride — each tenant carries a virtual
        ``pass``; the scan interleaves tenants lowest-pass-first,
        advancing a scratch pass by 1/weight per spec taken, FIFO within
        a tenant. The COMMITTED pass only advances on successful
        dispatch (below), so a scan that places nothing reorders
        nothing. Starvation-free: every dispatch pushes the winner's
        pass up, so the minimum rotates; a newly-active tenant starts at
        the current floor (``_tenant_row``) and cannot monopolize with
        banked idle credit. Untenanted specs keep their FIFO position
        under the reserved empty-name tenant at weight 1."""
        items = list(self._pending_specs.items())
        if not tuning.TENANTS or len(items) < 2:
            return items
        by_tenant: Dict[str, List[Tuple[str, bytes]]] = {}
        for tid, blob in items:
            t, _prio = self._pending_meta.get(tid, ("", 0))
            by_tenant.setdefault(t, []).append((tid, blob))
        if len(by_tenant) < 2:
            return items
        scratch: Dict[str, float] = {}
        stride: Dict[str, float] = {}
        for t in by_tenant:
            row = self._tenants.get(t) or {}
            scratch[t] = float(row.get("pass", 0.0))
            stride[t] = 1.0 / max(
                float(row.get("weight", tuning.TENANT_DEFAULT_WEIGHT)),
                1e-6)
        ordered: List[Tuple[str, bytes]] = []
        queues = {t: deque(q) for t, q in by_tenant.items()}
        while queues:
            t = min(queues, key=lambda n: (scratch[n], n))
            ordered.append(queues[t].popleft())
            scratch[t] += stride[t]
            if not queues[t]:
                del queues[t]
        return ordered

    def _tenant_at_quota_locked(self, name: str) -> bool:
        """Caller holds ``self._lock``. True when the tenant has a quota
        and its usage has reached (or exceeded) the ceiling on any
        quota'd resource — it holds its full entitlement."""
        row = self._tenants.get(name)
        quota = (row or {}).get("quota") or {}
        if not quota:
            return False
        usage = self._tenant_usage.get(name, {})
        return any(usage.get(res, 0.0) >= float(ceiling) - 1e-9
                   for res, ceiling in quota.items())

    def _pick_preempt_victim_locked(
            self, tenant: str, priority: int) -> Optional[Tuple[str, dict]]:
        """Caller holds ``self._lock``. A queued spec of ``tenant`` at
        ``priority`` found no capacity: pick the lowest-priority
        preemptible running task belonging to another tenant that is at
        or over its quota, with strictly lower priority. At-quota is the
        fairness predicate — a tenant still inside its ceiling keeps
        what it placed; preemption only claws back capacity held at or
        beyond a tenant's full entitlement."""
        best: Optional[Tuple[str, dict]] = None
        for tid, rec in self._tenant_running.items():
            vt = rec.get("tenant") or ""
            if not rec.get("preemptible") or vt == tenant:
                continue
            if int(rec.get("priority", 0)) >= priority:
                continue
            if not self._tenant_at_quota_locked(vt):
                continue
            if best is None or (
                    int(rec.get("priority", 0)),
                    tid) < (int(best[1].get("priority", 0)), best[0]):
                best = (tid, rec)
        return best

    def _preempt_for(self, tid: str, spec) -> bool:
        """Issue at most one preemption on behalf of a starved queued
        spec: cancel the victim on its node (lineage re-execution
        recovers the victim's work later) and credit its usage so the
        next scan sees the freed quota. Returns True when a cancel was
        dispatched."""
        tenant, priority = self._pending_meta.get(tid, ("", 0))
        if not tenant or priority <= 0:
            return False
        with self._lock:
            victim = self._pick_preempt_victim_locked(tenant, priority)
            if victim is None:
                return False
            vtid, rec = victim
            entry = self._nodes.get(rec.get("node") or "")
            address = entry.address if entry and entry.alive else None
            # Credit now, not at task_done: the cancel's failure path
            # doesn't report done, and a double-credit is impossible
            # because the record is popped here.
            self._tenant_credit(vtid)
        self._persist_tenant_run(vtid)
        self._metrics.tick_tenant(self._metrics.tenant_preempted,
                                  rec.get("tenant") or "")
        from raytpu.util.events import record_event

        with self._lock:
            self._events.append(record_event(
                "WARNING", "TENANT_PREEMPTED",
                f"task {vtid[:8]} of tenant {rec.get('tenant')!r} "
                f"preempted for tenant {tenant!r} (priority {priority})",
                tenant=rec.get("tenant"), for_tenant=tenant))
        if address is None:
            return True  # victim's node already gone; usage freed
        try:
            self._node_client(rec["node"], address).call(
                "cancel_task", bytes.fromhex(vtid),
                timeout=tuning.CONTROL_CALL_TIMEOUT_S,
                breaker=breaker_for(address))
        except Exception as e:
            errors.swallow("head.preempt_cancel", e)
        return True

    def _pending_sched_loop(self) -> None:
        """Re-drive queued-infeasible TaskSpecs — including ones reloaded
        from durable storage after a bounce — once capacity appears. The
        head dials the chosen node itself (``submit_task``), so a queued
        task completes even if its driver spends the whole window blocked
        in get(); the result flows back through the object directory as
        usual. Failed dispatches stay queued for the next scan. With
        tenancy on the scan order is weighted-fair (``_wfq_order_locked``)
        and a starved high-priority spec may preempt (``_preempt_for``),
        capped per scan so one hot tenant cannot mass-evict a cluster."""
        while not self._stop.wait(tuning.HEAD_PENDING_SCHED_PERIOD_S):
            if self._fenced:
                continue  # the elected head owns dispatch now
            with self._lock:
                batch = self._wfq_order_locked()
            preempts_left = tuning.TENANT_PREEMPT_MAX_PER_SCAN \
                if tuning.TENANTS and tuning.TENANT_PREEMPT else 0
            pass_dirty: Set[str] = set()
            for tid, blob in batch:  # rpc-loop-ok: queued-spec replay, cold path gated on spare capacity
                if self._stop.is_set():
                    return
                try:
                    spec = wire.loads(blob)
                    # Failover dedup: the incumbent already dispatched
                    # this exact attempt (the placed log shipped with
                    # the WAL) — launching it again would double-run it.
                    with self._lock:
                        att = int(getattr(spec, "attempt", 0) or 0)
                        if (tid, att) in self._placed:
                            self._pending_specs.pop(tid, None)
                            self._pending_meta.pop(tid, None)
                            dropped_placed = True
                        else:
                            dropped_placed = False
                    if dropped_placed:
                        self._persist_pending_task(tid)
                        continue
                    tenant_ctx = None
                    if tuning.TENANTS and \
                            getattr(spec, "tenant", ""):
                        tenant_ctx = {
                            "tenant": spec.tenant,
                            "priority": int(getattr(spec, "priority", 0)
                                            or 0),
                            "preemptible": bool(getattr(
                                spec, "preemptible", True)),
                        }
                    arg_oids = [o.hex() for o in spec.arg_ref_oids()]
                    node_id = self._schedule_impl(
                        None, dict(spec.resources or {}), None, 0.5,
                        tid, arg_oids, None, tenant_ctx)
                except Exception as e:
                    errors.swallow("head.pending_sched", e)
                    continue
                if node_id is None:
                    # Still infeasible; _unmet stays fresh. A priority
                    # tenant's starved spec may claw back capacity from
                    # an over-quota lower-priority one.
                    if preempts_left > 0 and self._preempt_for(tid, spec):
                        preempts_left -= 1
                    continue
                with self._lock:
                    entry = self._nodes.get(node_id)
                    address = entry.address if entry and entry.alive \
                        else None
                if address is None:
                    self._undo_tenant_dispatch(tid, tenant_ctx)
                    continue
                try:
                    self._node_client(node_id, address).call(
                        "submit_task", blob,
                        timeout=tuning.CONTROL_CALL_TIMEOUT_S,
                        breaker=breaker_for(address))
                except Exception as e:
                    # Node refused/died: keep the spec queued; the
                    # optimistic debit is corrected by its heartbeat.
                    errors.swallow("head.pending_dispatch", e)
                    self._undo_tenant_dispatch(tid, tenant_ctx)
                    continue
                with self._lock:
                    # Record the dispatch BEFORE dropping the queued
                    # copy: if we crash in between, the successor skips
                    # the spec via the shipped placed log instead of
                    # replaying it (dedup by task id + attempt).
                    self._record_placed(tid,
                                        int(getattr(spec, "attempt", 0)
                                            or 0))
                    self._pending_specs.pop(tid, None)
                    self._pending_meta.pop(tid, None)
                    if tenant_ctx is not None:
                        # Commit the fair-queue debt only for work that
                        # actually dispatched; the scratch ordering pass
                        # is discarded every scan.
                        row = self._tenant_row(tenant_ctx["tenant"])
                        row["pass"] = float(row.get("pass", 0.0)) + \
                            1.0 / max(float(row.get(
                                "weight",
                                tuning.TENANT_DEFAULT_WEIGHT)), 1e-6)
                        pass_dirty.add(tenant_ctx["tenant"])
                self._persist_pending_task(tid)
                if task_events.enabled():
                    task_events.emit("task", tid,
                                     task_events.TaskTransition.SCHEDULED,
                                     node_id=node_id)
            for t in pass_dirty:
                self._persist_tenant(t)
            if tuning.TENANTS:
                with self._lock:
                    counts = self._tenant_queued_counts()
                self._metrics.refresh_tenant_queues(counts)

    def _undo_tenant_dispatch(self, tid: str,
                              tenant_ctx: Optional[dict]) -> None:
        """A placement decision was made (and debited) but the dispatch
        never reached a node: roll the tenant's in-flight debit back so
        the quota doesn't leak — the spec stays queued and will debit
        again when it actually goes out."""
        if tenant_ctx is None:
            return
        with self._lock:
            existed = self._tenant_credit(tid)
        if existed:
            self._persist_tenant_run(tid)

    # -- actor directory ---------------------------------------------------

    def _register_actor(self, peer: Peer, actor_id: str, node_id: str,
                        name: Optional[str], namespace: str,
                        max_restarts: int = 0,
                        resources: Optional[Dict[str, float]] = None) -> None:
        with self._lock:
            existing = self._actors.get(actor_id)
            if name:
                key = (namespace, name)
                if key in self._named and self._named[key] != actor_id:
                    raise ValueError(f"actor name {name!r} already taken")
                self._named[key] = actor_id
                self._persist_named(key)
            if existing is not None:
                # Re-registration during a restart: keep restart counters.
                existing["node_id"] = node_id
                existing["state"] = "alive"
            else:
                self._actors[actor_id] = {
                    "node_id": node_id, "name": name, "namespace": namespace,
                    "max_restarts": int(max_restarts),
                    "restarts_used": 0,
                    "resources": dict(resources or {}),
                    "state": "alive",
                }
            self._persist_actor(actor_id)
        if task_events.enabled():
            task_events.emit("actor", actor_id,
                             task_events.TaskTransition.CREATED,
                             name=name, node_id=node_id)
        self._publish("actors", {"event": "registered",
                                 "actor_id": actor_id, "node_id": node_id})

    def _resolve_actor(self, peer: Peer, actor_id: str) -> Optional[dict]:
        with self._lock:
            info = self._actors.get(actor_id)
            if info is None:
                return None
            if info["state"] == "restarting":
                return {"state": "restarting"}
            node = self._nodes.get(info["node_id"])
            if node is None or not node.alive:
                return None
            return {"node_id": info["node_id"], "address": node.address,
                    "state": "alive"}

    def _resolve_named_actor(self, peer: Peer, name: str,
                             namespace: str) -> Optional[dict]:
        with self._lock:
            actor_id = self._named.get((namespace, name))
        if actor_id is None:
            return None
        info = self._resolve_actor(peer, actor_id)
        if info is None:
            return None
        info["actor_id"] = actor_id
        return info

    def _actor_dead(self, peer: Peer, actor_id: str, reason: str,
                    no_restart: bool = True) -> None:
        self._on_actor_failure(actor_id, reason, no_restart=no_restart)

    def _on_actor_failure(self, actor_id: str, reason: str,
                          no_restart: bool) -> None:
        """Restart-or-bury decision (reference: GcsActorManager
        ``OnActorWorkerDead``/``max_restarts``)."""
        with self._lock:
            info = self._actors.get(actor_id)
            if info is None:
                return
            restartable = (not no_restart
                           and info["restarts_used"] < info["max_restarts"]
                           and f"__actor_spec__::{actor_id}" in self._kv)
            if restartable:
                info["restarts_used"] += 1
                info["state"] = "restarting"
            else:
                self._actors.pop(actor_id, None)
                if info.get("name"):
                    self._named.pop((info["namespace"], info["name"]), None)
                    self._persist_named((info["namespace"], info["name"]))
            self._persist_actor(actor_id)
        if task_events.enabled():
            task_events.emit(
                "actor", actor_id,
                task_events.TaskTransition.RESTARTING if restartable
                else task_events.TaskTransition.DEAD,
                attempt=info.get("restarts_used", 0), error=reason)
        if restartable:
            self._publish("actors", {"event": "restarting",
                                     "actor_id": actor_id, "reason": reason})
            self._restart_queue.put((actor_id, reason))
        else:
            self._publish("actors", {"event": "dead", "actor_id": actor_id,
                                     "reason": reason})

    def _restart_loop(self) -> None:
        """Re-schedule restarting actors onto live nodes and push their
        stored creation specs (the head dials the node — actors must
        restart even when no driver is attached, e.g. detached actors)."""
        from raytpu.cluster.protocol import RpcClient

        while True:
            item = self._restart_queue.get()
            if item is None or self._stop.is_set():
                return
            actor_id, reason = item
            with self._lock:
                info = self._actors.get(actor_id)
                blob = self._kv.get(f"__actor_spec__::{actor_id}")
            if info is None or info["state"] != "restarting" or blob is None:
                continue
            placed = False
            deadline = time.monotonic() + tuning.ACTOR_RESOLVE_TIMEOUT_S
            while time.monotonic() < deadline and not self._stop.is_set():
                node_id = self._schedule(None, info.get("resources", {}))
                if node_id is None:
                    time.sleep(tuning.PENDING_POLL_PERIOD_S)
                    continue
                with self._lock:
                    entry = self._nodes.get(node_id)
                    address = entry.address if entry and entry.alive else None
                if address is None:
                    time.sleep(tuning.RESTART_POLL_PERIOD_S)
                    continue
                try:
                    client = self._node_client(node_id, address)
                    client.call("create_actor", blob,
                                timeout=tuning.CREATE_ACTOR_TIMEOUT_S,
                                breaker=breaker_for(address))
                except Exception:
                    time.sleep(tuning.PENDING_POLL_PERIOD_S)
                    continue
                # The node's create_actor re-registers the actor (state
                # flips to alive there).
                if task_events.enabled():
                    task_events.emit(
                        "actor", actor_id,
                        task_events.TaskTransition.RESTARTED,
                        attempt=info.get("restarts_used", 0),
                        node_id=node_id)
                self._publish("actors", {"event": "restarted",
                                         "actor_id": actor_id,
                                         "node_id": node_id})
                placed = True
                break
            if not placed:
                with self._lock:
                    info = self._actors.pop(actor_id, None)
                    if info and info.get("name"):
                        self._named.pop(
                            (info["namespace"], info["name"]), None)
                        self._persist_named(
                            (info["namespace"], info["name"]))
                    self._persist_actor(actor_id)
                self._publish("actors", {
                    "event": "dead", "actor_id": actor_id,
                    "reason": f"restart failed after: {reason}"})

    def _object_unavailable(self, peer: Peer, object_id: str) -> None:
        """A node cannot locate an object anywhere (its last copy died):
        tell owners so lineage reconstruction can kick in (reference:
        ObjectRecoveryManager, object_recovery_manager.h:41)."""
        with self._lock:
            known = bool(self._objects.get(object_id))
        if not known:
            self._publish("objects", {"event": "unavailable",
                                      "object_id": object_id})

    # -- object directory --------------------------------------------------

    def _report_object(self, peer: Peer, object_id: str,
                       node_id: str, size_bytes: int = 0) -> None:
        with self._lock:
            first_copy = object_id not in self._objects
            self._objects.setdefault(object_id, set()).add(node_id)
            if size_bytes:
                self._record_object_size(object_id, int(size_bytes))
            waiters = self._object_waiters.pop(object_id, [])
            entry = self._nodes.get(node_id)
            address = entry.address if entry else None
            push_targets: List[str] = []
            if first_copy:
                demand = self._object_node_demand.pop(object_id, None)
                for nid in demand or ():
                    dn = self._nodes.get(nid)
                    if nid != node_id and dn is not None and dn.alive:
                        push_targets.append(dn.address)
        for w in waiters:
            w.push(f"object::{object_id}",
                   {"node_id": node_id, "address": address})
        if push_targets:
            # `peer` is the producing node's connection: tell it to
            # stream the fresh object to everyone who demanded it.
            peer.push("push_requests", {"object_id": object_id,
                                        "targets": push_targets})

    def _forget_object(self, peer: Peer, object_id: str,
                       node_id: str) -> None:
        with self._lock:
            locs = self._objects.get(object_id)
            if locs is not None:
                locs.discard(node_id)
                if not locs:
                    del self._objects[object_id]
                    self._object_sizes.pop(object_id, None)

    def _h_report_objects(self, peer: Peer, node_id: str,
                          deltas: List[list]) -> None:
        """Coalesced location deltas from one node: ``["+", oid_hex,
        size_bytes]`` adds a holder (size feeds the locality scorer),
        ``["-", oid_hex, 0]`` removes one. Replaces the per-object
        ``report_object``/``forget_object`` notify storm — one frame per
        node-side flush; a failed flush requeues and rides the next
        heartbeat (the legacy per-object handlers stay for old nodes)."""
        self._apply_object_deltas(peer, node_id, deltas)

    def _apply_object_deltas(self, peer: Peer, node_id: str,
                             deltas: List[list]) -> None:
        for d in deltas:
            try:
                op, oid_hex = d[0], d[1]
                size = int(d[2]) if len(d) > 2 and d[2] else 0
            except Exception:
                continue  # malformed delta: skip, don't poison the batch
            if op == "+":
                self._report_object(peer, oid_hex, node_id, size)
            elif op == "-":
                self._forget_object(peer, oid_hex, node_id)

    def _record_object_size(self, object_id: str, size_bytes: int) -> None:
        """Caller holds ``self._lock``. Re-inserting refreshes the FIFO
        position so live objects survive the LOCALITY_DIR_MAX eviction."""
        self._object_sizes.pop(object_id, None)
        self._object_sizes[object_id] = size_bytes
        cap = max(1, tuning.LOCALITY_DIR_MAX)
        while len(self._object_sizes) > cap:
            self._object_sizes.pop(next(iter(self._object_sizes)))

    def _locate_object(self, peer: Peer, object_id: str,
                       wait: bool = False) -> List[dict]:
        """Current locations; with wait=True and none yet, the caller gets
        a push on topic ``object::<id>`` when the first copy is reported."""
        with self._lock:
            locs = [
                {"node_id": nid, "address": self._nodes[nid].address}
                for nid in self._objects.get(object_id, ())
                if nid in self._nodes and self._nodes[nid].alive
            ]
            if not locs and wait:
                waiters = self._object_waiters.setdefault(object_id, [])
                if peer not in waiters:
                    waiters.append(peer)
                # Node peers (not drivers) also register push demand.
                nid = peer.meta.get("node_id")
                if nid:
                    now = time.monotonic()
                    self._object_node_demand.setdefault(
                        object_id, {})[nid] = now
                    if len(self._object_node_demand) > 10000:
                        # Prune demand for objects that never appeared.
                        for oid in [o for o, d in
                                    self._object_node_demand.items()
                                    if all(now - t > 300.0
                                           for t in d.values())]:
                            del self._object_node_demand[oid]
        return locs

    # -- placement groups --------------------------------------------------

    def _create_pg(self, peer: Peer, pg_id: str,
                   bundles: List[Dict[str, float]],
                   strategy: str) -> dict:
        """Reserve bundles on nodes. STRICT_PACK: all on one node;
        PACK: prefer one node, spill; SPREAD/STRICT_SPREAD: distinct nodes
        (STRICT_ fails if impossible). Reservation debits node availability
        until remove_pg (reference: GcsPlacementGroupScheduler 2-phase
        commit; single head process makes one-phase safe here).

        An infeasible attempt records the PG's bundles as autoscaler
        demand (reference: GcsAutoscalerStateManager folding pending PGs
        into the cluster resource state) — the client's create retry loop
        keeps the entry fresh until a launched node makes it fit."""
        try:
            result = self._create_pg_impl(peer, pg_id, bundles, strategy)
        except PlacementInfeasibleError:
            with self._lock:
                self._pg_demand[pg_id] = (
                    time.monotonic(),
                    [{str(k): float(v) for k, v in (b or {}).items()}
                     for b in bundles])
            raise
        with self._lock:
            self._pg_demand.pop(pg_id, None)
            stamped = f"pg:{pg_id}" in self._tenant_running
        if stamped:
            self._persist_tenant_run(f"pg:{pg_id}")
        return result

    def _create_pg_impl(self, peer: Peer, pg_id: str,
                        bundles: List[Dict[str, float]],
                        strategy: str) -> dict:
        # PG reservations count against the requesting tenant's quota —
        # an over-ceiling reservation reads as infeasible (retried by
        # the client's bounded create loop, admitted when peers release
        # capacity), exactly like a task placement would.
        tenant = tenancy.current_tenant() if tuning.TENANTS else ""
        pg_total: Dict[str, float] = {}
        for b in bundles:
            for k, v in (b or {}).items():
                pg_total[k] = pg_total.get(k, 0.0) + float(v)
        with self._lock:
            if tenant and self._tenant_over_quota(tenant, pg_total):
                raise PlacementInfeasibleError(
                    f"tenant {tenant!r} over quota for placement group "
                    f"{pg_id[:8]}")
            alive = [n for n in self._nodes.values()
                     if n.alive and n.labels.get("role") != "driver"]
            placement: List[Optional[str]] = [None] * len(bundles)

            def fits(node: NodeEntry, b: Dict[str, float], scratch) -> bool:
                avail = scratch.setdefault(
                    node.node_id, dict(node.available))
                return all(avail.get(k, 0.0) >= v - 1e-9
                           for k, v in b.items())

            def take(node: NodeEntry, b: Dict[str, float], scratch) -> None:
                avail = scratch[node.node_id]
                for k, v in b.items():
                    avail[k] = avail.get(k, 0.0) - v

            scratch: Dict[str, Dict[str, float]] = {}
            if strategy in ("STRICT_PACK", "PACK"):
                for node in sorted(alive, key=lambda n: -sum(
                        n.available.get(k, 0) for b in bundles for k in b)):
                    # Cumulative fit of ALL bundles on this one node.
                    s: Dict[str, Dict[str, float]] = {}
                    ok = True
                    for b in bundles:
                        if fits(node, b, s):
                            take(node, b, s)
                        else:
                            ok = False
                            break
                    if ok:
                        placement = [node.node_id] * len(bundles)
                        scratch = s
                        break
                if placement and placement[0] is None:
                    if strategy == "STRICT_PACK":
                        raise PlacementInfeasibleError(
                            "STRICT_PACK infeasible: no single node fits "
                            "all bundles")
                    # PACK fallback: greedy pack-then-spill.
                    scratch = {}
                    for i, b in enumerate(bundles):
                        chosen = None
                        for node in alive:
                            if fits(node, b, scratch):
                                chosen = node
                                break
                        if chosen is None:
                            raise PlacementInfeasibleError(
                                f"PACK infeasible for bundle {i}: {b}")
                        take(chosen, b, scratch)
                        placement[i] = chosen.node_id
            elif strategy in ("SPREAD", "STRICT_SPREAD"):
                scratch = {}
                used: Set[str] = set()
                for i, b in enumerate(bundles):
                    fresh = [n for n in sorted(alive, key=lambda n: n.node_id)
                             if n.node_id not in used and fits(n, b, scratch)]
                    reused = [] if strategy == "STRICT_SPREAD" else [
                        n for n in sorted(alive, key=lambda n: n.node_id)
                        if n.node_id in used and fits(n, b, scratch)
                    ]
                    chosen = (fresh or reused or [None])[0]
                    if chosen is None:
                        raise PlacementInfeasibleError(
                            f"{strategy} infeasible for bundle {i}: {b}")
                    take(chosen, b, scratch)
                    used.add(chosen.node_id)
                    placement[i] = chosen.node_id
            else:
                raise ValueError(f"unknown strategy {strategy!r}")

            # Commit: debit real availability.
            for node_id, avail in scratch.items():
                self._nodes[node_id].available = avail
            self._pgs[pg_id] = {"bundles": list(bundles),
                                "nodes": placement,
                                "strategy": strategy,
                                "tenant": tenant}
            self._persist_pg(pg_id)
            if tenant:
                # Reservations are never preemptible (tasks inside the
                # group are cancelled individually, not the group).
                self._tenant_debit(f"pg:{pg_id}",
                                   {"tenant": tenant, "priority": 0,
                                    "preemptible": False},
                                   pg_total, "")
            return {"nodes": placement}

    def _remove_pg(self, peer: Peer, pg_id: str) -> None:
        with self._lock:
            self._pg_demand.pop(pg_id, None)
            pg = self._pgs.pop(pg_id, None)
            if pg is None:
                return
            self._persist_pg(pg_id)
            for b, node_id in zip(pg["bundles"], pg["nodes"]):
                entry = self._nodes.get(node_id) if node_id else None
                if entry is not None and entry.alive:
                    for k, v in b.items():
                        entry.available[k] = entry.available.get(k, 0.0) + v
            credited = self._tenant_credit(f"pg:{pg_id}")
        if credited:
            self._persist_tenant_run(f"pg:{pg_id}")

    def _pg_info(self, peer: Peer, pg_id: str) -> Optional[dict]:
        with self._lock:
            pg = self._pgs.get(pg_id)
            return dict(pg) if pg else None

    # -- pubsub ------------------------------------------------------------

    def _subscribe(self, peer: Peer, topic: str) -> None:
        with self._lock:
            peers = self._subscribers.setdefault(topic, [])
            if peer not in peers:
                peers.append(peer)

    def _publish(self, topic: str, data: Any) -> None:
        with self._lock:
            peers = list(self._subscribers.get(topic, ()))
        for p in peers:
            if not p.closed:
                p.push(topic, data)

    def _publish_logs(self, peer: Peer, record: dict) -> None:
        """Rebroadcast a node's worker-log lines to subscribed drivers
        (reference: log monitor -> GCS pubsub -> driver)."""
        self._publish("logs", record)

    def _get_demand(self, peer: Peer, window_s: float = 10.0) -> List[dict]:
        """Aggregated unmet demand in the look-back window — unschedulable
        task shapes plus each pending (infeasible) placement group's
        bundles — plus any explicit ``request_resources`` hint: the input
        to the autoscaler's get_desired_groups (bundle -> count)."""
        cutoff = time.monotonic() - window_s
        now = time.monotonic()
        with self._lock:
            self._unmet = {k: v for k, v in self._unmet.items()
                           if v[0] >= cutoff}
            agg: Dict[tuple, int] = {}
            for _, b in self._unmet.values():
                key = tuple(sorted(b.items()))
                agg[key] = agg.get(key, 0) + 1
            # Pending PGs: every bundle of an infeasible group is demand
            # (TTL-bounded — a client that gave up stops refreshing).
            for pid in [p for p, (t, _) in self._pg_demand.items()
                        if now - t > tuning.PG_DEMAND_TTL_S]:
                del self._pg_demand[pid]
            for _, bundles in self._pg_demand.values():
                for b in bundles:
                    if not b:
                        continue
                    key = tuple(sorted(b.items()))
                    agg[key] = agg.get(key, 0) + 1
            # Floor semantics, not additive: per shape, the hint and the
            # queued demand overlap — one group satisfies both a
            # requested {TPU:8} and a queued {TPU:8} task.
            hint: Dict[tuple, int] = {}
            for b in self._requested_resources:
                key = tuple(sorted(b.items()))
                hint[key] = hint.get(key, 0) + 1
            for key, n in hint.items():
                agg[key] = max(agg.get(key, 0), n)
        return [{"bundle": dict(k), "count": n} for k, n in agg.items()]

    def _resource_demands(self, peer: Peer, window_s: float = 10.0) -> dict:
        """The autoscaler monitor's one-call feed: aggregated
        queued-infeasible demand (tasks + pending PGs + hints) plus a
        per-node busy/idle census so the monitor can tell which provider
        groups are in use and which nodes are safe drain victims
        (reference: GcsAutoscalerStateManager::GetClusterResourceState)."""
        demands = self._get_demand(peer, window_s)
        with self._lock:
            actors_by_node: Dict[str, int] = {}
            for info in self._actors.values():
                if info.get("state") == "alive":
                    actors_by_node[info["node_id"]] = \
                        actors_by_node.get(info["node_id"], 0) + 1
            nodes = []
            for n in self._nodes.values():
                busy = bool(actors_by_node.get(n.node_id)) or any(
                    n.available.get(k, 0.0) < v - 1e-9
                    for k, v in n.total.items())
                nodes.append({
                    "node_id": n.node_id, "alive": n.alive,
                    "labels": dict(n.labels), "busy": busy,
                    "actors": actors_by_node.get(n.node_id, 0),
                })
            queued = len(self._pending_specs)
        return {"demands": demands, "nodes": nodes,
                "queued_tasks": queued}

    def _request_resources(self, peer: Peer, bundles: List[dict]) -> int:
        """Explicit demand hint (reference:
        ``ray.autoscaler.sdk.request_resources``,
        ``python/ray/autoscaler/sdk.py``): the autoscaler scales up to
        hold these bundles immediately, without waiting for tasks to
        queue. Each call REPLACES the previous request (reference
        semantics); an empty list withdraws it. The hint persists until
        replaced — it sets a floor, it never blocks scale-up."""
        clean = [{str(k): float(v) for k, v in (b or {}).items()}
                 for b in (bundles or [])]
        with self._lock:
            self._requested_resources = [b for b in clean if b]
            return len(self._requested_resources)

    def _next_job_id(self, peer: Peer) -> int:
        with self._lock:
            self._job_counter += 1
            return self._job_counter


def main() -> None:  # pragma: no cover - exercised via subprocess in tests
    import argparse
    import signal
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=6379)
    ap.add_argument("--storage", default="",
                    help="durable table storage path (sqlite); empty = "
                         "in-memory only")
    ap.add_argument("--addr-file", default="",
                    help="head discovery record path; rewritten with "
                         "{address, epoch} at startup so clients/nodes "
                         "find the current head across failovers")
    args = ap.parse_args()
    head = HeadServer(args.host, args.port,
                      storage_path=args.storage or None,
                      addr_file=args.addr_file or None)
    addr = head.start()
    print(f"raytpu head listening on {addr}", flush=True)
    signal.sigwait({signal.SIGINT, signal.SIGTERM})
    head.stop()
    sys.exit(0)


if __name__ == "__main__":  # pragma: no cover
    main()
