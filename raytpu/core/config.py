"""Runtime config registry.

Reference analogue: ``src/ray/common/ray_config_def.h`` — 219 compile-time
declared knobs, each overridable from the environment (``RAY_<name>``) and
serialized to every process at startup. Same shape here: declared once,
typed, env-overridable via ``RAYTPU_<name>``, snapshot-serializable so a
head process can ship its view to workers.
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict

_REGISTRY: Dict[str, "_ConfigEntry"] = {}


class _ConfigEntry:
    __slots__ = ("name", "default", "parser", "value")

    def __init__(self, name: str, default: Any, parser: Callable[[str], Any]):
        self.name = name
        self.default = default
        self.parser = parser
        env = os.environ.get(f"RAYTPU_{name}")
        self.value = parser(env) if env is not None else default


def _parse_bool(s: str) -> bool:
    return s.strip().lower() in ("1", "true", "yes", "on")


def declare(name: str, default: Any) -> None:
    if name in _REGISTRY:
        raise ValueError(f"config {name} declared twice")
    if isinstance(default, bool):
        parser: Callable[[str], Any] = _parse_bool
    elif isinstance(default, int):
        parser = int
    elif isinstance(default, float):
        parser = float
    else:
        parser = str
    _REGISTRY[name] = _ConfigEntry(name, default, parser)


class _Config:
    """Attribute access to declared knobs: ``cfg.scheduler_spread_threshold``."""

    def __getattr__(self, name: str) -> Any:
        try:
            return _REGISTRY[name].value
        except KeyError:
            raise AttributeError(f"unknown config knob {name!r}") from None

    def set(self, name: str, value: Any) -> None:
        if name not in _REGISTRY:
            raise KeyError(f"unknown config knob {name!r}")
        _REGISTRY[name].value = value

    def snapshot(self) -> str:
        """Serialize current values (to ship to spawned worker processes)."""
        return json.dumps({k: e.value for k, e in _REGISTRY.items()})

    def load_snapshot(self, blob: str) -> None:
        for k, v in json.loads(blob).items():
            if k in _REGISTRY:
                _REGISTRY[k].value = v

    def items(self):
        return {k: e.value for k, e in _REGISTRY.items()}.items()


cfg = _Config()

# --- Environment-variable registry -------------------------------------------
#
# Some RAYTPU_* variables are read directly (process-boot flags, opt-in
# debug hooks) rather than through a ``declare``d knob — usually because
# they must be readable before config snapshots exist, or because the
# reading module must stay import-light. They are still declared here so
# every environment knob is discoverable in one place; the RTP008 lint
# rule enforces that no RAYTPU_* read escapes the registries.

_ENV_REGISTRY: Dict[str, str] = {}


def declare_env(name: str, doc: str) -> None:
    """Register a RAYTPU_* variable that is read via ``os.environ``
    directly (not through ``declare``)."""
    if not name.startswith("RAYTPU_"):
        raise ValueError(f"env var {name!r} must start with RAYTPU_")
    if name in _ENV_REGISTRY:
        raise ValueError(f"env var {name} declared twice")
    _ENV_REGISTRY[name] = doc


def declared_env() -> Dict[str, str]:
    """All directly-read env vars with their one-line docs."""
    return dict(_ENV_REGISTRY)


# Tracing (util/tracing.py): read at import so tracing works before any
# cluster config exists.
declare_env("RAYTPU_TRACING", "enable distributed tracing spans (bool)")
declare_env("RAYTPU_TRACE_SAMPLE", "trace sampling rate in [0,1]")
declare_env("RAYTPU_TRACE_BUFFER", "per-process span ring-buffer size")

# Task-event flight recorder (util/task_events.py).
declare_env("RAYTPU_TASK_EVENTS", "enable the task-event flight recorder (bool)")
declare_env("RAYTPU_TASK_EVENTS_RING", "per-process task-event ring size")
declare_env("RAYTPU_REQUEST_EVENTS",
            "enable serving-plane request lifecycle events (bool)")

# Fault injection (util/failpoints.py): armed via env so child worker
# processes inherit the failure plan without any RPC.
declare_env("RAYTPU_FAILPOINTS", "failpoint spec armed for this process tree")
declare_env("RAYTPU_FAILPOINTS_SEED", "deterministic seed for probabilistic failpoints")

# Resilience defaults (util/resilience.py): read before config snapshots
# arrive so retry/breaker policies cover the bootstrap RPCs too.
declare_env("RAYTPU_RETRY_MAX_ATTEMPTS", "default retry attempt cap")
declare_env("RAYTPU_RETRY_BASE_DELAY_S", "retry backoff base delay (s)")
declare_env("RAYTPU_RETRY_MAX_DELAY_S", "retry backoff delay ceiling (s)")
declare_env("RAYTPU_BREAKER_FAILURE_THRESHOLD", "circuit-breaker trip threshold")
declare_env("RAYTPU_BREAKER_RESET_TIMEOUT_S", "circuit-breaker half-open delay (s)")

# Usage stats (util/usage_stats.py).
declare_env("RAYTPU_USAGE_STATS_ENABLED", "opt-in anonymous usage stats (bool)")
declare_env("RAYTPU_USAGE_STATS_PATH", "override usage-stats spool path")

# Tenancy (util/tenancy.py, cluster/constants.py): the identity is read
# at import (before any config snapshot) so worker subprocesses inherit
# their driver's tenant; the scheduler knobs are cluster constants.
declare_env("RAYTPU_TENANT", "default tenant identity for this process tree")
declare_env("RAYTPU_TENANTS",
            "master switch: tenant-aware scheduling (quotas/WFQ/preemption)")
declare_env("RAYTPU_TENANT_DEFAULT_WEIGHT", "fair-queue weight for unknown tenants")
declare_env("RAYTPU_TENANT_QUOTAS",
            "static quota bootstrap: 'a=CPU:4,TPU:8;b=CPU:2'")
declare_env("RAYTPU_TENANT_MAX_QUEUED",
            "queued-spec depth per tenant before admission sheds")
declare_env("RAYTPU_TENANT_RETRY_DELAY_S", "retry_after hint on TenantThrottled")
declare_env("RAYTPU_TENANT_PREEMPT", "enable priority preemption (bool)")
declare_env("RAYTPU_TENANT_PREEMPT_MAX_PER_SCAN",
            "preemptions per pending-queue scan")
declare_env("RAYTPU_METRIC_TENANT_RESERVED",
            "reserved series headroom for tenant-tagged metrics")

# Head / node boot flags (cluster/head.py, cluster/node.py,
# cluster/topology.py): consumed during process bring-up, before the
# head's config snapshot has been shipped.
declare_env("RAYTPU_HEARTBEAT_TIMEOUT_S", "head marks a node dead after this silence")
declare_env("RAYTPU_HEARTBEAT_PERIOD_S", "node heartbeat send period (s)")
declare_env("RAYTPU_HEALTH_CHECK_PERIOD_S", "head health-check sweep period (s)")
declare_env("RAYTPU_HOST_IP", "advertised address override for this host")
declare_env("RAYTPU_NUM_TPUS", "TPU chip count override for topology detection")
declare_env("RAYTPU_VISIBLE_CHIPS",
            "chips this worker leased (set by the node at spawn; empty = none)")

# Control-plane fast path (cluster/constants.py, cluster/protocol.py,
# cluster/client.py): wire-frame coalescing + pipelined task submission.
declare_env("RAYTPU_RPC_BATCH",
            "enable batched wire frames + pipelined submission (bool)")
declare_env("RAYTPU_RPC_BATCH_MAX_FRAMES", "coalesced sub-frames per flush cap")
declare_env("RAYTPU_RPC_BATCH_MAX_BYTES", "coalesced payload bytes per flush cap")
declare_env("RAYTPU_RPC_BATCH_MAX_WAIT_S",
            "extra straggler wait per non-empty flush (s; 0 = group-commit)")
declare_env("RAYTPU_SUBMIT_WINDOW", "pipelined submission in-flight window")
declare_env("RAYTPU_SUBMIT_BATCH_MAX", "max TaskSpecs per submit_batch RPC")

# Locality-aware scheduling (cluster/constants.py, cluster/head.py,
# cluster/node.py): the head's size-aware object directory steers
# placements toward the node already holding a task's argument bytes.
declare_env("RAYTPU_LOCALITY",
            "prefer the node holding the most argument bytes (bool)")
declare_env("RAYTPU_LOCALITY_MIN_BYTES",
            "local-bytes floor below which locality never steers a placement")
declare_env("RAYTPU_LOCALITY_DIR_MAX",
            "head-side oid->size map bound (oldest sizes evicted beyond it)")
declare_env("RAYTPU_LOCALITY_EAGER_PUSH",
            "push large args to a remote placement at schedule time (bool)")
declare_env("RAYTPU_OBJ_REPORT_BUFFER_MAX",
            "node-side buffered object-location deltas cap")

# Elastic cluster (cluster/constants.py, cluster/head.py,
# cluster/client.py, train/trainer.py): durable head failover cadence,
# driver reconnect budget, autoscaler demand TTLs, elastic-gang timing.
declare_env("RAYTPU_HEAD_SNAPSHOT_PERIOD_S",
            "head write-behind snapshot cadence for derived tables (s)")
declare_env("RAYTPU_HEAD_PENDING_SCHED_PERIOD_S",
            "head queued-TaskSpec re-schedule scan period (s)")
declare_env("RAYTPU_HEAD_RECONNECT_TIMEOUT_S",
            "driver budget to re-dial a bounced head (s)")
declare_env("RAYTPU_PG_DEMAND_TTL_S",
            "pending placement group feeds autoscaler demand this long (s)")
declare_env("RAYTPU_ELASTIC_PROBE_TIMEOUT_S",
            "elastic fit() capacity-probe budget after a gang failure (s)")
declare_env("RAYTPU_ELASTIC_PROBE_PERIOD_S",
            "elastic capacity-probe poll period (s)")
declare_env("RAYTPU_ELASTIC_UPSCALE_CHECK_PERIOD_S",
            "running gang's replacement-capacity check period (s)")

# Zero-copy data plane (runtime/serialization.py, runtime/object_store.py,
# cluster/transfer.py): serialize-into-shm puts, pinned shared-memory
# views on get, streaming receives into final storage.
declare_env("RAYTPU_ZEROCOPY",
            "zero-copy data plane: pinned shm views + serialize-into-place "
            "(bool, default on; off is byte-identical to the legacy layout)")

# Runtime environments (runtime_env/container.py, runtime_env/pip_env.py).
declare_env("RAYTPU_CONTAINER_ENGINE", "container engine binary (docker/podman)")
declare_env("RAYTPU_ALLOW_PIP", "allow pip-install runtime envs (bool)")

# Workflows (workflow/storage.py).
declare_env("RAYTPU_WORKFLOW_ROOT", "workflow checkpoint storage root")

# Metrics pipeline (util/metrics.py): read at import so the registry and
# shipping buffer are bounded before any cluster config exists.
declare_env("RAYTPU_METRICS_SHIP",
            "ship metric deltas to the head TSDB (bool, default on)")
declare_env("RAYTPU_METRIC_MAX_SERIES",
            "distinct tag-sets per metric before folding into <other>")
declare_env("RAYTPU_METRICS_BUFFER_MAX",
            "per-process pending metric-frame buffer cap")

# Continuous profiling (util/profiler.py): read at import so the
# duty-cycled sampler is configured before any cluster config exists.
declare_env("RAYTPU_PROFILE_CONTINUOUS",
            "always-on duty-cycled sampling profiler (bool, default off)")
declare_env("RAYTPU_PROFILE_PERIOD_S",
            "seconds between continuous-profiler sampling bursts")
declare_env("RAYTPU_PROFILE_WINDOW_S",
            "duration of one continuous-profiler sampling burst")
declare_env("RAYTPU_PROFILE_HZ", "continuous-profiler sampling rate")
declare_env("RAYTPU_PROFILE_BUFFER_MAX",
            "per-process pending profile-frame buffer cap")
declare_env("RAYTPU_PROFILE_STACKS_MAX",
            "hottest stacks kept per profile snapshot before (other)")

# Disaggregated serving plane (serve router + inference/disagg.py).
declare_env("RAYTPU_SERVE_PROBE_TIMEOUT_S",
            "serve router queue-length/prefix-summary probe budget")
declare_env("RAYTPU_PREFIX_ROUTING",
            "prefix-cache-aware replica routing (bool, default off)")
declare_env("RAYTPU_PREFIX_SUMMARY_TTL_S",
            "router-side cache TTL for replica prefix summaries")
declare_env("RAYTPU_PREFIX_SUMMARY_MAX",
            "max page-chain digests per replica prefix summary")
declare_env("RAYTPU_KV_STREAM_CHUNK_BYTES",
            "chunk size for cross-replica KV-page streaming")
declare_env("RAYTPU_KV_HANDOFF_TTL_S",
            "orphaned KV-export pin TTL on the prefill replica")

# --- Declared knobs (reference: ray_config_def.h) ----------------------------

# Scheduling. Hybrid policy packs nodes until utilization crosses this
# threshold, then spreads by score (reference: ray_config_def.h:186
# ``scheduler_spread_threshold`` = 0.5).
declare("scheduler_spread_threshold", 0.5)
declare("scheduler_top_k_fraction", 0.2)
declare("max_pending_lease_requests_per_scheduling_category", 10)

# Objects. Results larger than this go to the shared-memory store instead of
# being returned inline (reference: ray_config_def.h:206
# ``max_direct_call_object_size`` = 100 KiB).
declare("max_direct_call_object_size", 100 * 1024)
declare("object_store_memory_bytes", 2 * 1024 * 1024 * 1024)
declare("object_store_fallback_directory", "")
declare("object_spilling_threshold", 0.8)
# Node-to-node transfer chunking (reference: chunked pull/push,
# object_manager.cc with chunk_size from ray_config_def.h).
# Byte budget for one streaming Dataset execution's in-flight blocks
# (reference: ResourceManager object-store budgets). 0 = auto: 25% of
# object_store_memory_bytes.
declare("data_memory_budget_bytes", 0)
declare("object_transfer_chunk_bytes", 4 * 1024 * 1024)
declare("object_transfer_max_concurrency", 8)
# Push-based transfer (reference: push_manager.h bounded-in-flight
# pushes): a producer streams a demanded object to the requesting node
# the moment it exists, skipping the pull round-trips.
declare("object_transfer_push_enabled", True)
# Incomplete inbound push buffers (producer died mid-push) are dropped
# after this long.
declare("object_push_rx_ttl_s", 60.0)
# 0 = monitor whole-system memory fraction (memory_usage_threshold);
# >0 = hard byte budget for the node's process tree (tests, cgroups).
declare("memory_limit_bytes", 0)

# Worker pool.
declare("num_workers_soft_limit", 8)
declare("worker_processes", True)
declare("worker_register_timeout_seconds", 60.0)
declare("idle_worker_killing_time_threshold_ms", 1000 * 60 * 5)
declare("prestart_workers", True)

# Health / fault tolerance (reference: gcs_health_check_manager.cc).
declare("health_check_period_ms", 1000)
declare("health_check_timeout_ms", 10000)
declare("health_check_failure_threshold", 5)
declare("task_max_retries", 3)
declare("actor_max_restarts", 0)
declare("lineage_pinning_enabled", True)
declare("max_lineage_bytes", 1024 * 1024 * 1024)

# RPC.
declare("rpc_connect_timeout_s", 10.0)
declare("rpc_call_timeout_s", 120.0)
declare("pubsub_batch_ms", 10)
# Upper bound on one relayed driver-proxy RPC; a hung upstream node fails
# the one relayed call instead of wedging the proxy (see driver_proxy.py).
declare("proxy_relay_timeout_s", 120.0)

# Metrics / events.
declare("metrics_report_interval_ms", 2500)
declare("task_events_buffer_size", 100000)
declare("enable_timeline", True)
# Head-side flight-recorder store: max entities kept per kind
# (task/actor/object/node) before FIFO eviction, and max events folded
# per entity (reference: RAY_task_events_max_num_task_in_gcs).
declare("task_event_store_per_kind", 4096)
declare("task_event_store_events_per_entity", 256)
# Log infrastructure (reference: per-process log files under the session
# dir + the log monitor streaming worker output to drivers).
declare("session_dir", "")  # empty = /tmp/raytpu/session_<node pid>
declare("log_to_driver", True)

# TPU / mesh.
declare("tpu_visible_chips_env", "TPU_VISIBLE_CHIPS")
declare("mesh_dcn_axis", "dcn")
declare("default_remote_chips", 0)

# TorchTrainer compat: gloo process-group op timeout — it bounds every
# collective for the life of training (reference train default: 30 min).
declare("torch_pg_timeout_s", 1800.0)

# Memory monitor (reference: memory_monitor.h:52).
declare("memory_usage_threshold", 0.95)
declare("memory_monitor_refresh_ms", 250)

# Prometheus scrape endpoint on the head (reference: per-node metrics
# agent port, metrics_agent.py). 0 = disabled; scrape config for it via
# `raytpu metrics export-config`.
declare("head_metrics_port", 0)

# Head TSDB (util/tsdb.py): bounded cluster time-series store fed by
# shipped metric deltas. Fine ring 120 x 5 s = 10 min sharp history,
# coarse ring 120 x 30 s = 1 h downsampled, all under a hard byte cap.
declare("metrics_store_max_bytes", 8 * 1024 * 1024)
declare("metrics_fine_step_s", 5.0)
declare("metrics_fine_slots", 120)
declare("metrics_coarse_step_s", 30.0)
declare("metrics_coarse_slots", 120)
# SLO alert rules evaluated on the head over the TSDB, ';'-separated,
# e.g. "raytpu_infer_ttft_seconds:p95 > 2.0 for 30s" or with tag
# selectors "raytpu_tenant_queued{tenant=a} > 100 for 30s". Fires into
# the ops-event log (state.list_events / post-mortem dumps).
declare("metrics_alert_rules", "")

# Head-side cluster profile store (util/profstore.py): per-proc rings of
# shipped collapsed-stack snapshots under one byte cap, FIFO-evicted
# like the TSDB.
declare("profile_store_max_bytes", 4 * 1024 * 1024)
declare("profile_ring_slots", 120)
