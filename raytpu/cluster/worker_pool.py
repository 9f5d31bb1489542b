"""Node-daemon worker pool: leases, reuse, chip isolation, crash reaping.

Reference analogue: ``src/ray/raylet/worker_pool.cc`` (1652 LoC) — idle
workers cached per (job, runtime-env) and popped per lease
(``worker_pool.h:343,354,417``); plus the TPU accelerator manager's
per-process chip isolation (``python/ray/_private/accelerators/tpu.py:
30-49``), which here happens at spawn: a worker bound to chips gets
``TPU_VISIBLE_CHIPS`` et al. in its environment and keeps that binding for
life (chip visibility can't change after the TPU runtime initializes).

Pool key: ``(job_id, runtime-env-hash, chips-tuple)``. A lease pops a
matching idle worker or spawns one; crashed workers are reaped by a
monitor thread which fails their in-flight work with
:class:`WorkerCrashedError` (the daemon survives — that is the point).
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

from raytpu.cluster import constants as tuning
from raytpu.cluster.protocol import RpcClient
from raytpu.core.config import cfg
from raytpu.util import compile_cache, errors
from raytpu.util import tracing
from raytpu.util.failpoints import DROP, failpoint
from raytpu.util.events import record_event
from raytpu.core.errors import WorkerCrashedError
from raytpu.core.ids import JobID, WorkerID


def runtime_env_hash(runtime_env: Optional[dict]) -> str:
    if not runtime_env:
        return ""
    try:
        return hashlib.sha1(
            json.dumps(runtime_env, sort_keys=True, default=str).encode()
        ).hexdigest()[:12]
    except Exception:
        return "unhashable"


# Chip bounds of a worker holding part of a four-chip (2x2) host; a
# worker holding the whole host keeps the host's own.
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1"}
# libtpu's default mesh-controller port; every process on a host needs
# its own.
_MESH_CONTROLLER_PORT = 8476


def chip_env(chips: Tuple[int, ...]) -> Dict[str, str]:
    """Per-worker TPU visibility env (reference ``tpu.py:30-49``).

    A chip belongs to one process at a time, and a process that imports
    JAX takes every chip it can see. So a worker that leased chips sees
    exactly those, and one that leased none is pinned to the CPU — it
    could otherwise take a chip from the worker that leased it.
    """
    if not chips:
        return {"RAYTPU_VISIBLE_CHIPS": "", "JAX_PLATFORMS": "cpu"}
    ids = ",".join(str(c) for c in chips)
    env = {
        "RAYTPU_VISIBLE_CHIPS": ids,
        "TPU_VISIBLE_CHIPS": ids,
        "TPU_VISIBLE_DEVICES": ids,
    }
    bounds = _CHIP_BOUNDS.get(len(chips))
    if bounds is not None:
        port = _MESH_CONTROLLER_PORT + min(chips)
        env.update({
            # Both spellings: a TPU VM's own environment sets the
            # *_HOST_* ones for the whole host.
            "TPU_CHIPS_PER_PROCESS_BOUNDS": bounds,
            "TPU_CHIPS_PER_HOST_BOUNDS": bounds,
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_HOST_BOUNDS": "1,1,1",
            "TPU_MESH_CONTROLLER_ADDRESS": f"localhost:{port}",
            "TPU_MESH_CONTROLLER_PORT": str(port),
        })
    return env


class WorkerHandle:
    def __init__(self, worker_id: WorkerID, key: tuple,
                 chips: Tuple[int, ...],
                 proc: Optional[subprocess.Popen] = None):
        self.worker_id = worker_id
        self.key = key
        self.chips = chips
        self.proc = proc  # None until _spawn (reserved slot)
        self.client: Optional[RpcClient] = None
        self.address: Optional[str] = None
        self.pid: Optional[int] = None
        self.ready = threading.Event()
        self.dead = False
        self.dedicated = False  # actor-bound: never returned to the pool
        self.kill_reason: Optional[str] = None  # set by pool.kill()
        # True while the worker's task sits in raytpu.get (blocked-worker
        # protocol): excluded from the pool soft cap so nested tasks can
        # always obtain a worker (reference: raylets exceed the soft limit
        # for blocked workers).
        self.blocked = False
        self.last_used = time.monotonic()
        self.on_death: Optional[Callable[[str], None]] = None  # actor hook
        # Container spec from the runtime env (the lease key pins the
        # image via the renv hash): _spawn wraps the worker command.
        self.container = None

    def crash(self, reason: str) -> None:
        self.dead = True
        if self.on_death is not None:
            try:
                self.on_death(reason)
            except Exception:
                pass
        if self.client is not None:
            self.client.close()


class WorkerPool:
    def __init__(self, node_address: str, shm_name: Optional[str],
                 node_id_hex: str, base_env: Optional[Dict[str, str]] = None,
                 soft_limit: Optional[int] = None,
                 log_dir: Optional[str] = None):
        self.node_address = node_address
        self.shm_name = shm_name or ""
        self.node_id_hex = node_id_hex
        self.log_dir = log_dir
        self.base_env = dict(base_env or {})
        # The cap must at least cover the CPU ledger, or tasks the
        # scheduler admitted would starve waiting for workers.
        self.soft_limit = max(int(cfg.num_workers_soft_limit) or 8,
                              int(soft_limit or 0))
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._workers: Dict[str, WorkerHandle] = {}  # worker_id hex -> handle
        self._idle: Dict[tuple, List[WorkerHandle]] = {}
        self._stopped = False
        self.on_worker_gone = None  # cb(worker_id_hex); set by NodeServer
        self._monitor = threading.Thread(
            target=self._monitor_loop, name="worker-pool-monitor", daemon=True)
        self._monitor.start()

    # -- registration (called from the node RPC handler) -------------------

    def on_register(self, worker_id_hex: str, address: str, pid: int) -> None:
        # drop => the registration is lost; the lease waiting on ready
        # times out exactly like a worker that wedged during startup.
        if failpoint("worker.register.pre") is DROP:
            return
        with self._lock:
            h = self._workers.get(worker_id_hex)
        if h is None:
            return
        h.address = address
        h.pid = pid
        try:
            h.client = RpcClient(address)
        except Exception:
            h.crash("worker RPC connect failed")
            return
        h.ready.set()

    # -- leasing -----------------------------------------------------------

    def lease(self, job_id: JobID, renv: Optional[dict],
              chips: Tuple[int, ...], *, dedicated: bool = False,
              timeout: Optional[float] = None) -> WorkerHandle:
        """Pop an idle matching worker or spawn one. Blocks on the soft
        process cap (reference: ``num_workers_soft_limit``)."""
        # The lease span separates "waiting for a worker" (cap waits,
        # cold spawns) from the task's own execution in a timeline.
        with tracing.span("worker.lease") as attrs:
            h = self._lease_impl(job_id, renv, chips, dedicated=dedicated,
                                 timeout=timeout)
            attrs["worker"] = h.worker_id.hex()[:12]
            return h

    def _lease_impl(self, job_id: JobID, renv: Optional[dict],
                    chips: Tuple[int, ...], *, dedicated: bool = False,
                    timeout: Optional[float] = None) -> WorkerHandle:
        failpoint("worker.lease.pre")
        key = (job_id.hex(), runtime_env_hash(renv), tuple(chips))
        if timeout is None:
            # Never wedge the dispatcher forever.
            timeout = tuning.WORKER_LEASE_TIMEOUT_S
        deadline = time.monotonic() + timeout
        with self._lock:
            while True:
                if self._stopped:
                    raise WorkerCrashedError("pool stopped")
                idles = self._idle.get(key)
                while idles:
                    h = idles.pop()
                    if (not h.dead and h.proc is not None
                            and h.proc.poll() is None):
                        h.dedicated = dedicated
                        h.last_used = time.monotonic()
                        return h
                # Dedicated (actor) workers are bounded by the resource
                # ledger, not the pool cap, and blocked workers (sitting
                # in raytpu.get) are excluded so nested tasks can always
                # obtain a worker (reference: the soft limit only governs
                # idle/task workers; raylets exceed it for blocked ones).
                limit = self.soft_limit
                live = sum(1 for w in self._workers.values()
                           if not w.dead and not w.dedicated
                           and not w.blocked)
                if live >= limit:
                    # Over the cap: evict idle workers of other keys (e.g.
                    # finished jobs) to make room — LRU first. terminate()
                    # only sends a signal, so it is safe under the lock.
                    all_idle = sorted(
                        (h for hs in self._idle.values() for h in hs),
                        key=lambda h: h.last_used)
                    for victim in all_idle[:max(1, live - limit + 1)]:
                        self._drop_locked(victim)
                        victim.dead = True
                        try:
                            if victim.proc is not None:
                                victim.proc.terminate()
                        except Exception:
                            pass
                        live -= 1
                if live < limit or dedicated:
                    h = self._reserve_locked(key, chips)
                    h.dedicated = dedicated
                    if renv:
                        h.container = renv.get("container")
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise WorkerCrashedError(
                        "worker lease timed out at pool cap")
                self._cv.wait(timeout=min(remaining, 0.1))
        # Popen outside the lock: spawns overlap and never stall
        # lease/release/on_register traffic.
        try:
            self._spawn(h)
        except Exception as e:
            # e.g. container engine missing: fail the lease cleanly and
            # free the reserved slot instead of wedging on ready.wait.
            h.crash(f"worker spawn failed: {e}")
            with self._lock:
                self._workers.pop(h.worker_id.hex(), None)
            raise WorkerCrashedError(f"worker spawn failed: {e}") from e
        if not h.ready.wait(timeout=float(cfg.worker_register_timeout_seconds)):
            h.crash("worker failed to register in time")
            try:
                if h.proc is not None:
                    h.proc.terminate()  # never leak an orphan holding chips
            except Exception:
                pass
            with self._lock:
                self._workers.pop(h.worker_id.hex(), None)
            raise WorkerCrashedError("worker failed to start")
        if h.dead:
            raise WorkerCrashedError("worker died during startup")
        return h

    def release(self, h: WorkerHandle) -> None:
        """Return a leased worker to the idle cache (or drop it if dead)."""
        with self._lock:
            if (h.dead or h.dedicated or self._stopped
                    or h.client is None or h.client.closed
                    or h.proc is None or h.proc.poll() is not None):
                self._drop_locked(h)
            else:
                h.last_used = time.monotonic()
                self._idle.setdefault(h.key, []).append(h)
            self._cv.notify_all()

    def kill(self, h: WorkerHandle, reason: str = "killed",
             failure: bool = False) -> None:
        h.kill_reason = reason  # surfaced in the task's failure message
        # Already-dead workers were reported by the reaper (WORKER_CRASHED)
        # — a cleanup kill must not double-log the incident. Routine kills
        # (raytpu.kill, idle reaping) stay INFO; callers mark failures.
        if not h.dead:
            record_event("ERROR" if failure else "INFO", "WORKER_KILLED",
                         f"worker {h.worker_id.hex()[:8]} killed: {reason}",
                         worker_id=h.worker_id.hex(), reason=reason)
        try:
            if h.client is not None and not h.client.closed:
                h.client.call("kill", reason,
                              timeout=tuning.WORKER_KILL_TIMEOUT_S)
        except Exception as e:
            errors.swallow("pool.kill_rpc", e)
        try:
            if h.proc is not None:
                h.proc.terminate()
        except Exception:
            pass
        with self._lock:
            self._drop_locked(h)
            self._cv.notify_all()

    # -- internals ---------------------------------------------------------

    def _reserve_locked(self, key: tuple,
                        chips: Tuple[int, ...]) -> WorkerHandle:
        worker_id = WorkerID.from_random()
        h = WorkerHandle(worker_id, key, chips, proc=None)
        self._workers[worker_id.hex()] = h
        return h

    def _spawn(self, h: WorkerHandle) -> None:
        if h.proc is not None:
            return  # popped from idle, already running
        failpoint("worker.spawn.pre")
        # os.environ carries RAYTPU_FAILPOINTS, so failpoints armed with
        # env=True (or inherited by this daemon) reach the worker too.
        env = dict(os.environ)
        env.update(self.base_env)
        if h.chips:  # the workers that compile for a chip
            env.update(compile_cache.spawn_env())
        env.update(chip_env(h.chips))
        # The host this node is reachable at — gang rendezvous publishes
        # coordinator addresses on it (a worker cannot otherwise know its
        # externally visible IP).
        env.setdefault("RAYTPU_HOST_IP",
                       self.node_address.rsplit(":", 1)[0])
        cmd = [
            sys.executable, "-m", "raytpu.cluster.worker_proc",
            "--node", self.node_address,
            "--shm", self.shm_name,
            "--worker-id", h.worker_id.hex(),
            "--job", h.key[0],
            "--node-id", self.node_id_hex,
        ]
        # Container wrap BEFORE any fd is opened: a failed wrap (e.g. no
        # engine on the node) must not leak log file handles.
        if h.container is not None:
            from raytpu.runtime_env.container import wrap_worker_command

            cmd, env = wrap_worker_command(cmd, env, h.container)
        # Per-process log files (reference: worker-<id>-<pid>.out/.err
        # under the session dir); the node's log monitor tails .out/.err
        # and streams new lines to drivers.
        stdout = stderr = None
        if self.log_dir:
            wid = h.worker_id.hex()[:12]
            stdout = open(os.path.join(
                self.log_dir, f"worker-{wid}.out"), "ab", buffering=0)
            stderr = open(os.path.join(
                self.log_dir, f"worker-{wid}.err"), "ab", buffering=0)
        try:
            h.proc = subprocess.Popen(cmd, env=env,
                                      start_new_session=True,
                                      stdout=stdout, stderr=stderr)
        finally:
            if stdout is not None:
                stdout.close()
                stderr.close()

    def _drop_locked(self, h: WorkerHandle) -> None:
        self._workers.pop(h.worker_id.hex(), None)
        idles = self._idle.get(h.key)
        if idles and h in idles:
            idles.remove(h)
        # Borrow cleanup etc. — the callback must be cheap (it spawns its
        # own thread for any RPC work; we hold the pool lock here).
        if self.on_worker_gone is not None:
            try:
                self.on_worker_gone(h.worker_id.hex())
            except Exception:
                pass

    def _monitor_loop(self) -> None:
        while not self._stopped:
            time.sleep(tuning.MONITOR_POLL_PERIOD_S)
            dead: List[WorkerHandle] = []
            idle_kill: List[WorkerHandle] = []
            now = time.monotonic()
            idle_ttl = float(cfg.idle_worker_killing_time_threshold_ms) / 1e3
            with self._lock:
                for h in list(self._workers.values()):
                    if h.dead or h.proc is None:
                        continue
                    if h.proc.poll() is not None:
                        dead.append(h)
                        self._drop_locked(h)
                    elif (not h.dedicated and h.ready.is_set()
                          and now - h.last_used > idle_ttl
                          and any(h is w for w in
                                  self._idle.get(h.key, ()))):
                        idle_kill.append(h)
                if dead or idle_kill:
                    self._cv.notify_all()
            for h in dead:
                record_event("ERROR", "WORKER_CRASHED",
                             f"worker {h.worker_id.hex()[:8]} exited with "
                             f"code {h.proc.returncode}",
                             worker_id=h.worker_id.hex(),
                             exit_code=h.proc.returncode)
                h.crash(f"worker process exited with code "
                        f"{h.proc.returncode}")
            for h in idle_kill:
                self.kill(h, "idle timeout")

    def stats(self) -> dict:
        with self._lock:
            return {
                "workers": len(self._workers),
                "idle": sum(len(v) for v in self._idle.values()),
            }

    def shutdown(self) -> None:
        self._stopped = True
        with self._lock:
            workers = list(self._workers.values())
            self._workers.clear()
            self._idle.clear()
        for h in workers:
            try:
                if h.proc is not None:
                    h.proc.terminate()
            except Exception:
                pass
        for h in workers:
            if h.proc is None:
                continue
            try:
                h.proc.wait(timeout=tuning.WORKER_KILL_TIMEOUT_S)
            except Exception:
                try:
                    h.proc.kill()
                except Exception as e:
                    errors.swallow("worker_pool.kill_escalation", e)
