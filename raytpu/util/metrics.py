"""User-defined metrics: Counter / Gauge / Histogram — plus the
cluster shipping pipeline.

Reference analogue: ``python/ray/util/metrics.py:137,262,187`` — the
user-facing metric API whose samples flow to Prometheus. The reference
routes through OpenCensus + a per-node metrics agent; we register directly
with ``prometheus_client`` (in-process registry) and expose the scrape
endpoint via :func:`start_metrics_server` — one fewer hop, same exposition
format. Without ``prometheus_client`` installed, metrics degrade to
in-memory counters (observable via ``.value``/tests, nothing exported).

Cluster shipping (reference: ``src/ray/stats/metric_exporter.h:36`` —
per-process collectors drained to a cluster aggregation point): every
process periodically snapshots its registry *deltas* (counter increments,
gauge last-values, histogram bucket increments) into primitive-only
frames that ride the existing liveness paths (node heartbeat,
worker→node notify) to the head's :class:`raytpu.util.tsdb.MetricStore`.
Same bounded-buffer / requeue-on-failure contract as task-event shipping
(``util/task_events.py``). ``RAYTPU_METRICS_SHIP=0`` turns the whole
pipeline off; disabled-and-idle cost at each ship site is a single flag
check (:func:`enabled`).

Tag-cardinality bound: each metric holds at most ``_MAX_SERIES``
(``RAYTPU_METRIC_MAX_SERIES``) distinct tag-sets; overflow folds into a
``{"tag": "<other>"}`` series and bumps
``raytpu_metrics_series_dropped_total`` so a tag explosion can't bloat
the shipping frames or the head store.

Every built-in metric name must be declared in the append-only
:data:`DECLARED_METRICS` table (lint rule RTP015, mirroring the
``declare_env`` registry); user code outside ``raytpu/`` may mint
ad-hoc names freely.
"""

from __future__ import annotations

import bisect
import os
import threading
import time
import weakref
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

try:
    import prometheus_client as _prom
except ImportError:  # pragma: no cover - baked into this image
    _prom = None

_DEFAULT_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5,
                    5.0, 10.0, 30.0, 60.0)
_registry_lock = threading.Lock()
_registered: Dict[str, object] = {}
_instances: "weakref.WeakSet[_Metric]" = weakref.WeakSet()

# Append-only registry of every metric name the runtime itself constructs
# (lint rule RTP015 walks Counter/Gauge/Histogram call sites under
# ``raytpu/`` and cross-checks against this table, exactly like RTP008
# does for env vars). Keep alphabetized within each section; never
# remove an entry — renames append the new name and leave the old one.
DECLARED_METRICS: Dict[str, str] = {
    # -- head / cluster state ------------------------------------------
    "raytpu_actors": "live actor count by state",
    "raytpu_cluster_nodes": "cluster node count by liveness state",
    "raytpu_placement_groups": "placement group count",
    "raytpu_resources_available": "available resource units by kind",
    "raytpu_resources_total": "total resource units by kind",
    "raytpu_schedule_requests_total": "scheduling requests handled",
    "raytpu_tasks_done_total": "tasks finished cluster-wide",
    "raytpu_tasks_submitted_total": "task specs accepted for scheduling",
    "raytpu_tenant_preempted_total": "running tasks preempted per tenant",
    "raytpu_tenant_queued": "specs queued at the head per tenant",
    "raytpu_tenant_tasks_placed_total": "placements per tenant",
    "raytpu_tenant_throttled_total": "admission-shed submissions per tenant",
    # -- inference serving ---------------------------------------------
    "raytpu_infer_decode_mfu": "model FLOPs utilization per decode step",
    "raytpu_infer_decode_tokens_per_s": "decode throughput",
    "raytpu_infer_decode_tokens_total": "decode tokens generated",
    "raytpu_infer_draft_accepted_total":
        "drafted tokens the verification kept (self-drafting)",
    "raytpu_infer_drafted_tokens_total":
        "drafted tokens a decode step verified (self-drafting)",
    "raytpu_infer_dsa_rows_scored_total":
        "cached positions an indexer scored (a layer's, sparse attention)",
    "raytpu_infer_dsa_rows_selected_total":
        "scored positions the attention then read (sparse attention)",
    "raytpu_infer_handoff_aborts_total":
        "KV handoffs aborted mid-stream (peer death, TTL sweep)",
    "raytpu_infer_handoff_bytes_total":
        "payload bytes streamed in cross-replica KV handoffs",
    "raytpu_infer_handoff_fallbacks_total":
        "disaggregated pulls that fell back to a local prefill",
    "raytpu_infer_handoff_pages_total":
        "KV pages grafted via disaggregated prefill->decode handoff",
    "raytpu_infer_kv_page_utilization": "KV page pool utilization 0..1",
    "raytpu_infer_moe_pairs_total":
        "live (token, choice) pairs routed by a router with identity experts",
    "raytpu_infer_moe_zero_pairs_total":
        "routed pairs that chose an identity expert (no product)",
    "raytpu_infer_prefill_tokens_per_s": "prefill throughput",
    "raytpu_infer_prefill_tokens_total": "prefill tokens processed",
    "raytpu_infer_prefix_evictions_total": "prefix cache evictions",
    "raytpu_infer_prefix_hit_tokens_total": "prefix cache tokens reused",
    "raytpu_infer_prefix_hits_total": "prefix cache lookup hits",
    "raytpu_infer_prefix_lookups_total": "prefix cache lookups",
    "raytpu_infer_running_requests": "requests in the running batch",
    "raytpu_infer_state_seats_in_use":
        "sequences holding a seat in the engine's state arrays",
    "raytpu_infer_step_seconds": "decode step wall time",
    "raytpu_infer_ttft_seconds": "time-to-first-token distribution",
    "raytpu_infer_waiting_requests": "requests queued for admission",
    # -- node daemon ---------------------------------------------------
    "raytpu_node_pending_tasks": "tasks queued on the node",
    "raytpu_node_pull_bytes_total": "object bytes pulled from peers",
    "raytpu_node_push_rx_bytes_total": "object bytes received via push",
    "raytpu_node_rss_bytes": "node daemon resident set size",
    "raytpu_node_running_tasks": "tasks executing on the node",
    "raytpu_node_shm_capacity_bytes": "shared-memory arena capacity",
    "raytpu_node_shm_used_bytes": "shared-memory arena bytes in use",
    "raytpu_node_shm_used_highwater_bytes":
        "shared-memory arena high-water mark since daemon start",
    # -- continuous profiling / performance attribution ----------------
    "raytpu_hbm_peak_bytes": "device memory high-water mark",
    "raytpu_hbm_used_bytes": "device memory in use",
    "raytpu_rpc_stage_seconds":
        "server dispatch wall time per stage (recv/decode/queue/"
        "handler/encode/send)",
    "raytpu_train_mfu": "model FLOPs utilization per train step",
    "raytpu_train_step_seconds": "train step wall time",
    # -- serve ---------------------------------------------------------
    "raytpu_serve_requests_total":
        "serve requests routed, by deployment and tenant",
    "raytpu_serve_ttft_seconds":
        "request time-to-first-token, by deployment and tenant",
    "raytpu_serve_tpot_seconds":
        "inter-token latency (time per output token)",
    "raytpu_serve_e2e_seconds":
        "request end-to-end latency, by deployment and tenant",
    "raytpu_serve_queue_seconds":
        "replica queue wait (enqueue to semaphore)",
    "raytpu_serve_tokens_delivered_total":
        "tokens streamed to consumers, by deployment and tenant",
    "raytpu_serve_tokens_wasted_total":
        "tokens whose work was discarded, by cause",
    # -- the serving process's interpreter -----------------------------
    "raytpu_host_gc_collections_total":
        "collections of the cycle collector in an engine's process, "
        "by generation",
    "raytpu_host_gc_pause_seconds_total":
        "seconds the cycle collector held an engine's process",
    # -- metrics pipeline itself ---------------------------------------
    "raytpu_metrics_series_dropped_total":
        "tag-sets folded into <other> by the cardinality cap",
    # -- worker --------------------------------------------------------
    "raytpu_worker_tasks_total": "tasks executed by the worker process",
}

# Tag-cardinality cap: distinct tag-sets per metric before folding into
# the ``<other>`` series. Module global so tests can patch it.
ENV_MAX_SERIES = "RAYTPU_METRIC_MAX_SERIES"
_MAX_SERIES = int(os.environ.get(ENV_MAX_SERIES, "") or 128)
OTHER_TAG_VALUE = "<other>"

# Reserved headroom past the cap for series carrying a REAL "tenant"
# tag value: per-tenant SLO series (quota throttles, fairness, serve
# latency) must not silently fold into ``<other>`` just because a
# free-form tag family (task names, resources) filled the table first —
# a folded tenant series reads as "tenant is fine" on every dashboard.
ENV_TENANT_RESERVED = "RAYTPU_METRIC_TENANT_RESERVED"
_TENANT_RESERVED = int(os.environ.get(ENV_TENANT_RESERVED, "") or 32)


def _sanitize(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


class _Metric:
    def __init__(self, name: str, description: str = "",
                 tag_keys: Optional[Sequence[str]] = None):
        self._name = _sanitize(name)
        self._description = description
        self._tag_keys: Tuple[str, ...] = tuple(tag_keys or ())
        self._default_tags: Dict[str, str] = {}
        self._values: Dict[Tuple, float] = {}
        self._lock = threading.Lock()
        self._ship_state: Dict[Tuple, object] = {}
        self._prom = self._make_prom() if _prom is not None else None
        with _registry_lock:
            _instances.add(self)

    def _make_prom(self):
        raise NotImplementedError

    def _signature(self) -> tuple:
        return (type(self).__name__, self._tag_keys)

    def _get_or_register(self, factory):
        with _registry_lock:
            existing = _registered.get(self._name)
            if existing is not None:
                prev_sig, collector = existing
                if prev_sig != self._signature():
                    raise ValueError(
                        f"metric {self._name!r} already registered with a "
                        f"different type/tag_keys: {prev_sig} vs "
                        f"{self._signature()}")
                return collector
            m = factory()
            _registered[self._name] = (self._signature(), m)
            return m

    def set_default_tags(self, tags: Dict[str, str]) -> "_Metric":
        unknown = set(tags) - set(self._tag_keys)
        if unknown:
            raise ValueError(f"unknown tag keys: {sorted(unknown)}")
        self._default_tags = dict(tags)
        return self

    def _tag_tuple(self, tags: Optional[Dict[str, str]]) -> Tuple:
        merged = {**self._default_tags, **(tags or {})}
        missing = set(self._tag_keys) - set(merged)
        if missing:
            raise ValueError(f"missing tag values for {sorted(missing)}")
        return tuple(merged[k] for k in self._tag_keys)

    def _fold(self, key: Tuple, table: Dict) -> Tuple[Tuple, bool]:
        """Cardinality cap (caller holds ``self._lock``): a key beyond
        ``_MAX_SERIES`` distinct tag-sets folds into the ``<other>``
        series so one runaway tag can't bloat frames or the head store.
        Keys whose "tenant" tag carries a real value get the reserved
        headroom (``_TENANT_RESERVED``) before folding — tenant series
        are the isolation story's evidence and must outlive free-form
        tag churn. Every fold still counts in
        ``raytpu_metrics_series_dropped_total`` tagged with the metric
        name, so the evicted family is named, never silent."""
        if not self._tag_keys or key in table or len(table) < _MAX_SERIES:
            return key, False
        if "tenant" in self._tag_keys and \
                len(table) < _MAX_SERIES + _TENANT_RESERVED:
            tv = key[self._tag_keys.index("tenant")]
            if tv and tv != OTHER_TAG_VALUE:
                return key, False
        return (OTHER_TAG_VALUE,) * len(self._tag_keys), True

    def _delta_rows(self) -> List[list]:
        raise NotImplementedError

    @property
    def info(self) -> dict:
        return {"name": self._name, "description": self._description,
                "tag_keys": self._tag_keys}


class Counter(_Metric):
    """Monotonic counter (reference: ``ray.util.metrics.Counter``)."""

    def _make_prom(self):
        return self._get_or_register(lambda: _prom.Counter(
            self._name, self._description or self._name,
            labelnames=self._tag_keys))

    def inc(self, value: float = 1.0,
            tags: Optional[Dict[str, str]] = None) -> None:
        if value < 0:
            raise ValueError("counters only increase")
        key = self._tag_tuple(tags)
        with self._lock:
            key, folded = self._fold(key, self._values)
            self._values[key] = self._values.get(key, 0.0) + value
        if folded:
            _note_series_drop(self._name)
        if self._prom is not None:
            (self._prom.labels(*key) if key else self._prom).inc(value)

    @property
    def value(self) -> float:
        with self._lock:
            return sum(self._values.values())

    def _delta_rows(self) -> List[list]:
        rows: List[list] = []
        with self._lock:
            for key, val in self._values.items():
                inc = val - self._ship_state.get(key, 0.0)
                if inc > 0:
                    rows.append(["c", self._name, list(self._tag_keys),
                                 list(key), inc])
                    self._ship_state[key] = val
        return rows


class Gauge(_Metric):
    """Point-in-time value (reference: ``ray.util.metrics.Gauge``)."""

    def _make_prom(self):
        return self._get_or_register(lambda: _prom.Gauge(
            self._name, self._description or self._name,
            labelnames=self._tag_keys))

    def set(self, value: float,
            tags: Optional[Dict[str, str]] = None) -> None:
        key = self._tag_tuple(tags)
        with self._lock:
            key, folded = self._fold(key, self._values)
            self._values[key] = value
        if folded:
            _note_series_drop(self._name)
        if self._prom is not None:
            (self._prom.labels(*key) if key else self._prom).set(value)

    @property
    def value(self) -> float:
        """The untagged value when one was set; otherwise the most
        recently introduced tag set's value (legacy behavior, only
        deterministic for single-tag-set gauges)."""
        with self._lock:
            if () in self._values:
                return self._values[()]
            vals = list(self._values.values())
            return vals[-1] if vals else 0.0

    @property
    def values(self) -> Dict[Tuple, float]:
        """Per-tag-tuple snapshot (keys ordered by ``tag_keys``)."""
        with self._lock:
            return dict(self._values)

    def _delta_rows(self) -> List[list]:
        # Gauges ship every live tag-set each interval (not just on
        # change) so steady values still produce points — a flat-lined
        # KV-utilization gauge must not read as a vanished series.
        with self._lock:
            return [["g", self._name, list(self._tag_keys), list(key), val]
                    for key, val in self._values.items()]


class Histogram(_Metric):
    """Bucketed distribution (reference: ``ray.util.metrics.Histogram``)."""

    def __init__(self, name: str, description: str = "",
                 boundaries: Optional[Sequence[float]] = None,
                 tag_keys: Optional[Sequence[str]] = None):
        self._boundaries = tuple(boundaries or _DEFAULT_BUCKETS)
        super().__init__(name, description, tag_keys)
        self._observations: List[float] = []
        self._by_key: Dict[Tuple, List[float]] = {}

    def _signature(self) -> tuple:
        return (type(self).__name__, self._tag_keys, self._boundaries)

    def _make_prom(self):
        return self._get_or_register(lambda: _prom.Histogram(
            self._name, self._description or self._name,
            labelnames=self._tag_keys, buckets=self._boundaries))

    def observe(self, value: float,
                tags: Optional[Dict[str, str]] = None) -> None:
        key = self._tag_tuple(tags)
        with self._lock:
            key, folded = self._fold(key, self._by_key)
            self._observations.append(value)
            self._by_key.setdefault(key, []).append(value)
        if folded:
            _note_series_drop(self._name)
        if self._prom is not None:
            (self._prom.labels(*key) if key else self._prom).observe(value)

    @property
    def observations(self) -> List[float]:
        """All observations in arrival order (tag-blind, backward
        compatible); per-tag series live in :attr:`observations_by_tag`."""
        with self._lock:
            return list(self._observations)

    @property
    def observations_by_tag(self) -> Dict[Tuple, List[float]]:
        """Observations keyed by tag tuple (ordered by ``tag_keys``)."""
        with self._lock:
            return {k: list(v) for k, v in self._by_key.items()}

    def _delta_rows(self) -> List[list]:
        rows: List[list] = []
        with self._lock:
            for key, obs in self._by_key.items():
                idx = self._ship_state.get(key, 0)
                new = obs[idx:]
                if not new:
                    continue
                counts = [0] * (len(self._boundaries) + 1)
                for v in new:
                    counts[bisect.bisect_left(self._boundaries, v)] += 1
                rows.append(["h", self._name, list(self._tag_keys),
                             list(key), list(self._boundaries), counts,
                             float(sum(new)), len(new)])
                self._ship_state[key] = len(obs)
        return rows


# The fold counter is created lazily (the class must exist first) and
# never reports on itself: its own key space is bounded by the set of
# metric names, but self-reporting could recurse through ``inc``.
_series_dropped: Optional[Counter] = None
_series_dropped_lock = threading.Lock()


def _note_series_drop(metric_name: str) -> None:
    global _series_dropped
    if metric_name == "raytpu_metrics_series_dropped_total":
        return
    with _series_dropped_lock:
        if _series_dropped is None:
            _series_dropped = Counter(
                "raytpu_metrics_series_dropped_total",
                "tag-sets folded into <other> by the cardinality cap",
                tag_keys=("metric",))
    try:
        _series_dropped.inc(tags={"metric": metric_name})
    except Exception:  # pragma: no cover - never break the caller
        pass


# ---------------------------------------------------------------------------
# Cluster shipping: registry deltas -> primitive frames -> head TSDB.
#
# Frame shape (strict-wire primitives only):
#   [proc_id, seq, ts, rows]
# with rows one of
#   ["c", name, [tag_keys], [tag_vals], increment]
#   ["g", name, [tag_keys], [tag_vals], last_value]
#   ["h", name, [tag_keys], [tag_vals], [boundaries], [bucket_incs],
#    sum_inc, count_inc]
# ``seq`` is per-origin monotonic; the head drops seq <= last-applied so
# a requeued-and-reshipped frame merges idempotently.
# ---------------------------------------------------------------------------

ENV_SHIP = "RAYTPU_METRICS_SHIP"
ENV_BUFFER_MAX = "RAYTPU_METRICS_BUFFER_MAX"

_BUFFER_MAX = int(os.environ.get(ENV_BUFFER_MAX, "") or 256)
_ship_enabled = os.environ.get(ENV_SHIP, "") not in ("0", "false", "False")
_ship_lock = threading.Lock()
_frames: Deque[list] = deque()
_frames_dropped_total = 0
_frames_dropped_shipped = 0  # watermark: drops already reported downstream
_ship_seq = 0
_last_collect = [0.0]
_proc_id = [""]


def enabled() -> bool:
    """THE flag check: every ship site guards with exactly this call, so
    ``RAYTPU_METRICS_SHIP=0`` costs one boolean read per tick."""
    return _ship_enabled


def enable_metrics_ship(env: bool = False) -> None:
    global _ship_enabled
    _ship_enabled = True
    if env:
        os.environ[ENV_SHIP] = "1"


def disable_metrics_ship(env: bool = False) -> None:
    """Default is ON, so (unlike task events) disabling for children
    must *set* the env var to ``0`` rather than unset it."""
    global _ship_enabled
    _ship_enabled = False
    if env:
        os.environ[ENV_SHIP] = "0"


def set_shipper_identity(proc_id: str) -> None:
    """Stamp outgoing frames with this process's stable identity
    (``head`` / ``node:<hex12>`` / ``driver:<hex12>`` /
    ``worker:<nodehex12>.<workerhex12>``). The head tombstones dead
    procs by this id, so the convention is load-bearing."""
    _proc_id[0] = str(proc_id)


def shipper_identity() -> str:
    return _proc_id[0] or f"pid:{os.getpid()}"


def collect(min_interval_s: float = 0.0, force: bool = False,
            now: Optional[float] = None) -> bool:
    """Snapshot registry deltas into one pending frame. Rate-limited by
    ``min_interval_s`` so a fast heartbeat loop can call it every beat.
    Returns True iff a frame was produced."""
    if not _ship_enabled:
        return False
    if now is None:
        now = time.time()
    with _ship_lock:
        if not force and min_interval_s > 0 and \
                now - _last_collect[0] < min_interval_s:
            return False
        _last_collect[0] = now
    with _registry_lock:
        insts = list(_instances)
    rows: List[list] = []
    for m in insts:
        try:
            rows.extend(m._delta_rows())
        except Exception:  # pragma: no cover - one bad metric != no ship
            pass
    if not rows:
        return False
    global _ship_seq, _frames_dropped_total
    with _ship_lock:
        _ship_seq += 1
        frame = [shipper_identity(), _ship_seq, now, rows]
        if len(_frames) >= _BUFFER_MAX:
            _frames.popleft()
            _frames_dropped_total += 1
        _frames.append(frame)
    return True


def drain() -> Tuple[List[list], int]:
    """Take everything pending plus the not-yet-reported drop delta.
    On ship failure hand both back via :func:`requeue` — the watermark
    arithmetic keeps drop counts exact across retries."""
    global _frames_dropped_shipped
    with _ship_lock:
        frames = list(_frames)
        _frames.clear()
        dropped_delta = _frames_dropped_total - _frames_dropped_shipped
        _frames_dropped_shipped = _frames_dropped_total
    return frames, dropped_delta


def requeue(frames: List[list], dropped: int = 0) -> None:
    """Put a failed ship back at the FRONT of the buffer (oldest-first
    order preserved); overflow drops the oldest of the requeued batch."""
    if not frames and not dropped:
        return
    global _frames_dropped_total, _frames_dropped_shipped
    with _ship_lock:
        _frames_dropped_shipped -= dropped
        space = _BUFFER_MAX - len(_frames)
        if len(frames) > space:
            lost = len(frames) - max(space, 0)
            frames = frames[lost:]
            _frames_dropped_total += lost
        _frames.extendleft(reversed(frames))


def ingest(frames: List[list], dropped: int = 0) -> None:
    """Relay path: a node daemon absorbs a worker's drained frames into
    its own buffer; they ride the next heartbeat to the head."""
    global _frames_dropped_total
    with _ship_lock:
        _frames_dropped_total += int(dropped or 0)
        for f in frames or ():
            if len(_frames) >= _BUFFER_MAX:
                _frames.popleft()
                _frames_dropped_total += 1
            _frames.append(f)


def pending_frames() -> int:
    with _ship_lock:
        return len(_frames)


def reset_shipping() -> None:
    """Test isolation: clear the buffer, counters, and every metric's
    per-instance ship watermarks (so totals re-ship as fresh deltas)."""
    global _frames_dropped_total, _frames_dropped_shipped, _ship_seq
    with _ship_lock:
        _frames.clear()
        _frames_dropped_total = 0
        _frames_dropped_shipped = 0
        _ship_seq = 0
        _last_collect[0] = 0.0
    with _registry_lock:
        insts = list(_instances)
    for m in insts:
        with m._lock:
            m._ship_state.clear()


_servers: Dict[int, tuple] = {}  # port -> (wsgi_server, thread)
_server_lock = threading.Lock()


def start_metrics_server(port: int = 8090) -> bool:
    """Expose the Prometheus scrape endpoint (reference: per-node metrics
    agent → Prometheus exposition). Idempotent per port; a second caller
    asking for a DIFFERENT port gets its own endpoint (a restarted head
    with a new config must not silently reuse the dead one's port)."""
    if _prom is None:
        return False
    with _server_lock:
        if port in _servers:
            return True
        _servers[port] = _prom.start_http_server(port)
        return True


def stop_metrics_server(port: int) -> None:
    """Shut down the scrape endpoint on ``port`` (no-op if not running)."""
    with _server_lock:
        entry = _servers.pop(port, None)
    if entry is None:
        return
    server, thread = entry
    try:
        server.shutdown()
        # shutdown() only stops the serve loop; the listening socket
        # stays bound until closed — a restart on the same port would
        # otherwise race GC for EADDRINUSE.
        server.server_close()
        thread.join(timeout=5)
    except Exception:  # pragma: no cover
        pass
