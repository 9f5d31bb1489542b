"""Serve controller: declarative app state reconciled onto replica actors.

Reference analogue: ``python/ray/serve/_private/controller.py`` —
``ServeController`` (``:84``, ``deploy_application`` ``:699``) and
``python/ray/serve/_private/deployment_state.py`` — ``DeploymentState``
(``:1202``), ``DeploymentStateManager`` (``:2392``). The controller is a
detached async actor. Each reconcile tick: diff target vs running replicas,
start/stop replica actors, run health checks, feed queue metrics to the
autoscaler, and publish routing tables through the long-poll host.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Any, Dict, List, Optional

import cloudpickle

from raytpu.serve._private.autoscaling_policy import (AutoscalingPolicyManager,
                                                      EnginePressure)
from raytpu.serve._private.long_poll import LongPollHost
from raytpu.serve.config import DeploymentConfig, ReplicaConfig

logger = logging.getLogger("raytpu.serve")

CONTROLLER_NAME = "SERVE_CONTROLLER"
RECONCILE_PERIOD_S = 0.1


class ReplicaWrapper:
    """Controller-side record of one replica actor (reference:
    ``ActorReplicaWrapper``, deployment_state.py:219)."""

    def __init__(self, replica_id: str, handle, config: ReplicaConfig):
        self.replica_id = replica_id
        self.handle = handle
        self.config = config
        self.healthy = True
        # False until the replica's first health reply, i.e. until its
        # constructor has finished. A starting replica gets no traffic
        # and is held to no health-check deadline: loading a model and
        # compiling for it take as long as they take.
        self.ready = False
        self.last_health_check = time.monotonic()
        self.draining = False
        # Latest prefix-cache advertisement piggybacked on this
        # replica's health reply (None until it advertises one).
        self.prefix_summary = None

    @property
    def serving(self) -> bool:
        """Constructed and not known dead: counts as RUNNING, and is
        published to the routers."""
        return self.healthy and self.ready


class DeploymentState:
    """Target state + running replicas for one deployment."""

    def __init__(self, app_name: str, name: str, replica_config: ReplicaConfig):
        self.app_name = app_name
        self.name = name
        self.replica_config = replica_config
        self.target_num_replicas = self._initial_target()
        self.replicas: Dict[str, ReplicaWrapper] = {}
        self._counter = 0
        self.deleting = False
        # Last prefix-summary snapshot pushed to long-poll subscribers
        # (change-only publication; None = never published).
        self.last_prefix_snapshot = None
        cfg = replica_config.deployment_config.autoscaling_config
        self.autoscaler = AutoscalingPolicyManager(cfg) if cfg else None

    def _initial_target(self) -> int:
        dc = self.replica_config.deployment_config
        if dc.autoscaling_config:
            ac = dc.autoscaling_config
            return ac.initial_replicas if ac.initial_replicas is not None \
                else ac.min_replicas
        return dc.num_replicas

    @property
    def full_name(self) -> str:
        return f"{self.app_name}#{self.name}"

    def next_replica_id(self) -> str:
        self._counter += 1
        return f"{self.full_name}#{self._counter}"


class ServeController(LongPollHost):
    """Async detached actor. All methods run on its event loop."""

    def __init__(self):
        LongPollHost.__init__(self)
        # app_name -> {deployment_name -> DeploymentState}
        self._apps: Dict[str, Dict[str, DeploymentState]] = {}
        self._app_meta: Dict[str, dict] = {}  # route_prefix, ingress name
        self._loop_task: Optional[asyncio.Task] = None
        self._shutdown = False
        # full_name -> [(ts, n)] requests reported waiting by handles with
        # no replicas to route to (the scale-from-zero signal; reference:
        # handles report queued metrics to the controller for autoscaling).
        self._pending_demand: Dict[str, list] = {}
        # In-flight replica start-up waits and stop tasks (concurrent
        # drains; the reconcile loop must not stall behind a constructor
        # or graceful_shutdown_timeout_s). Held so they are not collected.
        self._background_tasks: set = set()

    def _ensure_loop(self):
        if self._loop_task is None or self._loop_task.done():
            self._loop_task = asyncio.ensure_future(self._reconcile_loop())

    # -- API used by serve.run / handles ----------------------------------

    async def deploy_application(
        self,
        app_name: str,
        route_prefix: Optional[str],
        ingress_deployment: str,
        deployments_blob: bytes,
    ) -> None:
        """deployments_blob: cloudpickle'd list[ReplicaConfig]."""
        self._ensure_loop()
        configs: List[ReplicaConfig] = cloudpickle.loads(deployments_blob)
        states = self._apps.setdefault(app_name, {})
        new_names = set()
        for rc in configs:
            new_names.add(rc.deployment_name)
            existing = states.get(rc.deployment_name)
            if existing is None:
                states[rc.deployment_name] = DeploymentState(
                    app_name, rc.deployment_name, rc
                )
            else:
                existing.deleting = False  # re-added after a removal
                await self._update_deployment(existing, rc)
        # Deployments removed from the app: drain to 0, reconcile drops the
        # state once the last replica is gone (``deleting`` flag).
        for name in list(states):
            if name not in new_names:
                states[name].deleting = True
                states[name].target_num_replicas = 0
        self._app_meta[app_name] = {
            "route_prefix": route_prefix,
            "ingress": ingress_deployment,
        }
        self.notify_changed("route_table", self._route_table())
        await self._reconcile_once()

    async def _update_deployment(self, state: DeploymentState, rc: ReplicaConfig):
        old_dc = state.replica_config.deployment_config
        new_dc = rc.deployment_config
        code_changed = (
            rc.serialized_callable != state.replica_config.serialized_callable
            or rc.init_args != state.replica_config.init_args
            or rc.init_kwargs != state.replica_config.init_kwargs
        )
        state.replica_config = rc
        if new_dc.autoscaling_config and state.autoscaler is None:
            state.autoscaler = AutoscalingPolicyManager(new_dc.autoscaling_config)
        elif not new_dc.autoscaling_config:
            state.autoscaler = None
        if state.autoscaler is None:
            state.target_num_replicas = new_dc.num_replicas
        if code_changed:
            # Rolling replace: stop everything, reconcile restarts fresh.
            for rep in list(state.replicas.values()):
                self._stop_replica_background(state, rep)
        elif new_dc.user_config != old_dc.user_config and \
                new_dc.user_config is not None:
            for rep in state.replicas.values():
                try:
                    await rep.handle.reconfigure.remote(new_dc.user_config)
                except Exception:
                    rep.healthy = False

    async def delete_application(self, app_name: str) -> None:
        states = self._apps.get(app_name)
        if states is None:
            return
        stops = [
            self._stop_replica_background(state, rep)
            for state in states.values()
            for rep in list(state.replicas.values())
        ]
        if stops:
            await asyncio.gather(*stops, return_exceptions=True)
        del self._apps[app_name]
        self._app_meta.pop(app_name, None)
        self.notify_changed("route_table", self._route_table())
        for state in states.values():
            self.notify_changed(f"replicas::{state.full_name}", [])

    async def get_deployment_targets(self, app_name: str) -> List[str]:
        return sorted(self._apps.get(app_name, {}))

    async def status(self) -> Dict[str, Any]:
        out = {}
        for app, states in self._apps.items():
            deps = {}
            for name, st in states.items():
                healthy = sum(1 for r in st.replicas.values() if r.serving)
                if healthy >= st.target_num_replicas:
                    status = "RUNNING"
                elif st.replicas:
                    status = "UPDATING"
                else:
                    status = "DEPLOYING" if st.target_num_replicas else "RUNNING"
                deps[name] = {
                    "status": status,
                    "target_replicas": st.target_num_replicas,
                    "running_replicas": len(st.replicas),
                    "healthy_replicas": healthy,
                }
            out[app] = {
                "route_prefix": self._app_meta.get(app, {}).get("route_prefix"),
                "ingress": self._app_meta.get(app, {}).get("ingress"),
                "deployments": deps,
            }
        return out

    async def graceful_shutdown(self) -> None:
        self._shutdown = True
        if self._loop_task is not None:
            self._loop_task.cancel()
            self._loop_task = None
        for app in list(self._apps):
            await self.delete_application(app)

    # -- reconcile loop ----------------------------------------------------

    async def _reconcile_loop(self):
        while not self._shutdown:
            try:
                await self._reconcile_once()
            except Exception:
                logger.exception("serve controller reconcile failed")
            await asyncio.sleep(RECONCILE_PERIOD_S)

    async def _reconcile_once(self):
        for app_name, states in list(self._apps.items()):
            for name, state in list(states.items()):
                if not state.deleting:
                    await self._autoscale(state)
                await self._reconcile_deployment(state)
                await self._health_check(state)
                if state.deleting and not state.replicas:
                    states.pop(name, None)

    async def _reconcile_deployment(self, state: DeploymentState):
        # Remove dead/unhealthy replicas first so they get replaced.
        for rep in [r for r in state.replicas.values() if not r.healthy]:
            self._stop_replica_background(state, rep)
        delta = state.target_num_replicas - len(state.replicas)
        if delta > 0:
            for _ in range(delta):
                self._start_replica(state)
            self._publish_replicas(state)
        elif delta < 0:
            doomed = list(state.replicas.values())[delta:]
            for rep in doomed:
                self._stop_replica_background(state, rep)

    def _stop_replica_background(self, state: DeploymentState,
                                 rep: ReplicaWrapper) -> asyncio.Task:
        """Unpublish immediately; drain+kill concurrently so one slow drain
        (up to graceful_shutdown_timeout_s) can't freeze the reconcile loop
        for every other deployment."""
        state.replicas.pop(rep.replica_id, None)
        self._publish_replicas(state)
        task = asyncio.ensure_future(self._drain_and_kill(rep))
        self._background_tasks.add(task)
        task.add_done_callback(self._background_tasks.discard)
        return task

    def _start_replica(self, state: DeploymentState):
        import raytpu
        from raytpu.serve._private.replica import Replica

        rid = state.next_replica_id()
        opts = dict(state.replica_config.deployment_config.ray_actor_options)
        opts.setdefault("max_concurrency", 10_000)
        handle = raytpu.remote(Replica).options(**opts).remote(
            rid, cloudpickle.dumps(state.replica_config)
        )
        rep = ReplicaWrapper(rid, handle, state.replica_config)
        state.replicas[rid] = rep
        task = asyncio.ensure_future(self._await_ready(state, rep))
        self._background_tasks.add(task)
        task.add_done_callback(self._background_tasks.discard)

    async def _await_ready(self, state: DeploymentState,
                           rep: ReplicaWrapper):
        """Wait, without a deadline, for a new replica's first health
        reply; then publish it to the routers. A constructor that raises
        (the actor dies) marks it unhealthy, and reconcile replaces it."""
        try:
            reply = await _await_ref(rep.handle.check_health.remote())
        except Exception:
            rep.healthy = False
            return
        rep.ready = True
        rep.last_health_check = time.monotonic()
        if isinstance(reply, dict):
            rep.prefix_summary = reply.get("prefix_summary")
        if state.replicas.get(rep.replica_id) is rep:
            self._publish_replicas(state)

    async def _drain_and_kill(self, rep: ReplicaWrapper):
        import raytpu

        dc = rep.config.deployment_config
        try:
            await asyncio.wait_for(
                _await_ref(rep.handle.prepare_for_shutdown.remote(
                    dc.graceful_shutdown_wait_loop_s,
                    dc.graceful_shutdown_timeout_s,
                )),
                timeout=dc.graceful_shutdown_timeout_s + 1.0,
            )
        except Exception:
            pass
        try:
            raytpu.kill(rep.handle)
        except Exception:
            pass

    async def _health_check(self, state: DeploymentState):
        now = time.monotonic()
        period = state.replica_config.deployment_config.health_check_period_s
        for rep in list(state.replicas.values()):
            if not rep.ready or now - rep.last_health_check < period:
                continue
            rep.last_health_check = now
            try:
                reply = await asyncio.wait_for(
                    _await_ref(rep.handle.check_health.remote()),
                    timeout=state.replica_config.deployment_config
                    .health_check_timeout_s,
                )
            except Exception:
                rep.healthy = False
                continue
            # Modern replicas piggyback their prefix-cache summary on
            # the health reply; legacy replicas return a bare bool.
            if isinstance(reply, dict):
                rep.prefix_summary = reply.get("prefix_summary")
        self._publish_prefix_summaries(state)

    def _publish_prefix_summaries(self, state: DeploymentState):
        """Change-only broadcast of the deployment's per-replica
        prefix-cache summaries to ``prefix::<full_name>`` long-poll
        subscribers. Unhealthy replicas and replicas that never
        advertised are excluded — routers unicast-probe those instead
        of trusting missing evidence. Steady state (no cache drift)
        publishes nothing, so idle clusters wake zero routers."""
        snap = {
            r.replica_id: r.prefix_summary
            for r in state.replicas.values()
            if r.healthy and r.prefix_summary is not None
        }
        if snap == state.last_prefix_snapshot:
            return
        state.last_prefix_snapshot = {
            rid: dict(s) if isinstance(s, dict) else s
            for rid, s in snap.items()}
        self.notify_changed(f"prefix::{state.full_name}", snap)

    async def record_handle_demand(self, full_name: str, n: float = 1.0):
        self._pending_demand.setdefault(full_name, []).append(
            (time.monotonic(), n))

    def _demand_level(self, full_name: str) -> float:
        """Requests reported waiting by handles within the last 2s. A level
        (not a counter): each waiting request re-reports ~1/s, so summing a
        2s window survives reconcile ticks that land between reports —
        required for upscale hysteresis to ever elapse at zero replicas."""
        entries = self._pending_demand.get(full_name)
        if not entries:
            return 0.0
        cutoff = time.monotonic() - 2.0
        fresh = [(t, n) for (t, n) in entries if t >= cutoff]
        if not fresh:
            self._pending_demand.pop(full_name, None)
            return 0.0
        self._pending_demand[full_name] = fresh
        # Each waiting request contributes ~2 reports per window; halve,
        # but any fresh report counts as at least one waiting request.
        return max(sum(n for _, n in fresh) / 2.0, 1.0)

    def _tsdb_engine_pressure(self):
        """Cluster-aggregated engine pressure from the head TSDB — one
        query through this worker's daemon replaces the O(replicas)
        ``get_metrics`` fan-out. Engine series are untagged, so this is
        cluster-wide pressure; with one engine deployment per cluster
        (the common shape) it equals the per-deployment view. Returns
        ``(EnginePressure, running)`` or ``(None, 0.0)`` when the TSDB
        has no fresh infer series (shipping off, local mode, engines not
        exporting) — callers then fall back to polling replicas."""
        from raytpu.runtime import api as rt_api
        from raytpu.util import metrics

        if not metrics.enabled():
            return None, 0.0
        host = getattr(rt_api._backend, "_host", None)
        if host is None:
            return None, 0.0

        def latest(name: str, agg: str):
            try:
                res = host.node.call("metrics_query", name, None, agg,
                                     30.0, None, timeout=2.0)
            except Exception:
                return None
            if not res or not res.get("series_matched"):
                return None
            pts = [p for p in res.get("points") or [] if p[1] is not None]
            return pts[-1][1] if pts else None

        waiting = latest("raytpu_infer_waiting_requests", "sum")
        if waiting is None:
            return None, 0.0
        return EnginePressure(
            waiting_requests=waiting,
            kv_utilization=latest(
                "raytpu_infer_kv_page_utilization", "max") or 0.0,
            ttft_p95_s=latest("raytpu_infer_ttft_seconds", "p95") or 0.0,
        ), latest("raytpu_infer_running_requests", "sum") or 0.0

    async def _autoscale(self, state: DeploymentState):
        if state.autoscaler is None:
            return
        total = self._demand_level(state.full_name)
        # Engine pressure aggregates: queue depths SUM (total unmet
        # demand), occupancy and latency take the WORST replica (one
        # saturated engine is a problem even if its peers are idle).
        # Preferred source is the head TSDB (already cluster-merged, one
        # query); the per-replica fan-out below is the fallback.
        try:
            pressure, running = await asyncio.get_event_loop() \
                .run_in_executor(None, self._tsdb_engine_pressure)
        except Exception:
            pressure, running = None, 0.0
        if pressure is not None:
            total += running
            decision = state.autoscaler.get_decision_num_replicas(
                total, state.target_num_replicas, engine_pressure=pressure
            )
            if decision is not None and decision != state.target_num_replicas:
                logger.info(
                    "autoscaling %s: %d -> %d (load=%.1f, tsdb)",
                    state.full_name, state.target_num_replicas, decision,
                    total,
                )
                state.target_num_replicas = decision
            return
        waiting = kv_util = ttft = 0.0
        saw_pressure = False
        for rep in [r for r in state.replicas.values() if r.ready]:
            try:
                m = await asyncio.wait_for(
                    _await_ref(rep.handle.get_metrics.remote()), timeout=2.0
                )
                total += m["avg_ongoing"]
                if "engine_waiting_requests" in m:
                    saw_pressure = True
                    waiting += m["engine_waiting_requests"]
                    kv_util = max(kv_util,
                                  m.get("engine_kv_utilization", 0.0))
                    ttft = max(ttft, m.get("engine_ttft_p95_s", 0.0))
            except Exception:
                pass
        pressure = None
        if saw_pressure:
            pressure = EnginePressure(waiting_requests=waiting,
                                      kv_utilization=kv_util,
                                      ttft_p95_s=ttft)
        decision = state.autoscaler.get_decision_num_replicas(
            total, state.target_num_replicas, engine_pressure=pressure
        )
        if decision is not None and decision != state.target_num_replicas:
            logger.info(
                "autoscaling %s: %d -> %d (load=%.1f)",
                state.full_name, state.target_num_replicas, decision, total,
            )
            state.target_num_replicas = decision

    # -- routing state published to handles/proxies ------------------------

    def _publish_replicas(self, state: DeploymentState):
        snapshot = {
            "replicas": [
                (r.replica_id, r.handle)
                for r in state.replicas.values() if r.serving
            ],
            # Routers size their saturation threshold from the deployment's
            # actual config, not the handle-constructor default.
            "max_ongoing": state.replica_config.deployment_config
            .max_ongoing_requests,
            # Disaggregation topology: None / "prefill" / "decode".
            "role": state.replica_config.deployment_config.role,
        }
        self.notify_changed(f"replicas::{state.full_name}", snapshot)

    def _route_table(self) -> Dict[str, tuple]:
        table = {}
        for app, meta in self._app_meta.items():
            if meta.get("route_prefix"):
                table[meta["route_prefix"]] = (app, meta["ingress"])
        return table

    async def get_route_table(self) -> Dict[str, tuple]:
        return self._route_table()

    async def get_running_replicas(self, full_name: str) -> list:
        for states in self._apps.values():
            for state in states.values():
                if state.full_name == full_name:
                    return [
                        (r.replica_id, r.handle)
                        for r in state.replicas.values() if r.serving
                    ]
        return []


async def _await_ref(ref):
    from raytpu.runtime.api import _async_get

    return await _async_get(ref)


def get_or_create_controller():
    """Find the named controller actor or start it (detached)."""
    import raytpu

    try:
        return raytpu.get_actor(CONTROLLER_NAME)
    except Exception:
        pass
    return raytpu.remote(ServeController).options(
        name=CONTROLLER_NAME, lifetime="detached", max_concurrency=10_000
    ).remote()
