"""JaxTrainer — the distributed-training orchestrator.

Reference analogue (SURVEY.md §3.4 call stack): ``BaseTrainer.fit``
(``python/ray/train/base_trainer.py:567``) → ``DataParallelTrainer``
(``data_parallel_trainer.py:22``) → ``BackendExecutor`` (PG creation at
``_internal/backend_executor.py:197``) → ``WorkerGroup``
(``_internal/worker_group.py:102``) → per-worker ``_TrainSession``.

TPU-first redesign: the worker group is a *gang* — one worker actor per
host, each owning a contiguous-ICI bundle of chips; rendezvous runs
``jax.distributed.initialize`` with the coordinator published through the
control plane (reference pattern: NCCLUniqueIDStore named actor, SURVEY.md
A5); the training loop itself is single-program SPMD over the global mesh,
so there is no gradient-bucket machinery to orchestrate — XLA owns the
collectives. Elastic recovery is gang-shaped too (FailureConfig →
checkpoint + gang restart, not per-task retry).
"""

from __future__ import annotations

import os
import tempfile
import time
from typing import Any, Callable, Dict, Optional

import raytpu
from raytpu.cluster import constants as tuning
from raytpu.train import session as session_mod
from raytpu.train.checkpoint import Checkpoint, CheckpointManager
from raytpu.train.config import (
    CheckpointConfig,
    FailureConfig,
    Result,
    RunConfig,
    ScalingConfig,
)
from raytpu.util import compile_cache, errors


@raytpu.remote(num_cpus=0)
class _RendezvousStore:
    """Named actor publishing the gang coordinator address through the
    control plane — the analogue of the reference's NCCLUniqueIDStore
    named actor (SURVEY.md A5; ``util/collective/.../NCCLUniqueIDStore``).
    Keyed by gang attempt so a restarted gang never reads a dead
    incarnation's address."""

    def __init__(self):
        self._addrs: Dict[int, str] = {}

    def set_addr(self, attempt: int, addr: str) -> bool:
        self._addrs[attempt] = addr
        return True

    def get_addr(self, attempt: int) -> Optional[str]:
        return self._addrs.get(attempt)


@raytpu.remote(num_cpus=0)
class TrainWorker:
    """One gang member: hosts the user loop in a thread + a session."""

    def __init__(self, rank: int, world_size: int, context_kwargs: dict):
        self.rank = rank
        self.world_size = world_size
        self.context = session_mod.TrainContext(
            rank=rank, world_size=world_size, local_rank=rank,
            **context_kwargs)
        self.session = None
        self.thread = None
        self.error = None
        self.done = False

    def setup_distributed(self, coordinator: Optional[str],
                          num_processes: int, process_id: int,
                          rdzv_name: Optional[str] = None,
                          attempt: int = 0, backend: str = "jax"):
        """Multi-host rendezvous (reference analogue:
        ``_setup_torch_process_group``, ``torch/config.py:65``).

        ``coordinator="auto"``: rank 0 binds a free port on its host and
        publishes ``host:port`` through the :class:`_RendezvousStore`
        named actor; other ranks poll it. Then every rank runs
        ``jax.distributed.initialize`` so the gang forms one global JAX
        runtime (the mesh spans all hosts' devices).
        """
        if coordinator is None or num_processes <= 1:
            if backend == "torch":
                if num_processes > 1:
                    # JAX in-process workers share one runtime, so a None
                    # coordinator is fine there — torch has no shared
                    # runtime: an uninitialized process group would train
                    # N diverging replicas with zero gradient sync.
                    raise ValueError(
                        "TorchTrainer with num_workers > 1 requires "
                        "ScalingConfig(coordinator_address='auto' or "
                        "'host:port') to form the gloo process group")
                self._init_torch_pg("127.0.0.1:0", 1, 0)
            return True
        if coordinator == "auto":
            store = raytpu.get_actor(rdzv_name)
            if process_id == 0:
                import socket

                host = os.environ.get("RAYTPU_HOST_IP", "127.0.0.1")
                s = socket.socket()
                s.bind((host, 0))
                port = s.getsockname()[1]
                s.close()
                coordinator = f"{host}:{port}"
                raytpu.get(store.set_addr.remote(attempt, coordinator))
            else:
                deadline = time.monotonic() + 60.0
                while True:
                    coordinator = raytpu.get(
                        store.get_addr.remote(attempt))
                    if coordinator:
                        break
                    if time.monotonic() >= deadline:
                        raise TimeoutError(
                            "rendezvous: coordinator address never "
                            "published")
                    time.sleep(0.1)
        if backend == "torch":
            self._init_torch_pg(coordinator, num_processes, process_id)
            return True
        import jax

        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )
        return True

    @staticmethod
    def _init_torch_pg(coordinator: str, num_processes: int,
                       process_id: int) -> None:
        """Migration-compat gang (reference: _setup_torch_process_group,
        torch/config.py:65): gloo over the same rendezvous plumbing. The
        timeout bounds EVERY collective for the life of training, so it
        defaults to the reference's 1800s (``torch_pg_timeout_s``), not
        a rendezvous-scale value."""
        import datetime

        import torch.distributed as dist

        from raytpu.core.config import cfg

        if dist.is_initialized():
            return
        if coordinator.endswith(":0"):  # world-size-1 local group
            import socket

            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            coordinator = f"127.0.0.1:{s.getsockname()[1]}"
            s.close()
        dist.init_process_group(
            "gloo", init_method=f"tcp://{coordinator}",
            rank=process_id, world_size=num_processes,
            timeout=datetime.timedelta(
                seconds=float(cfg.torch_pg_timeout_s)))

    def start(self, train_fn_blob: bytes, config: dict, dataset_shards=None,
              resume_path=None):
        import threading

        import cloudpickle

        compile_cache.enable()
        train_fn = cloudpickle.loads(train_fn_blob)
        self.session = session_mod._Session(self.context, dataset_shards)
        if resume_path:
            self.session.latest_checkpoint = Checkpoint(resume_path)

        def run():
            session_mod._set_session(self.session)
            try:
                train_fn(config)
            except BaseException as e:  # noqa: BLE001
                self.error = e
            finally:
                self.done = True
                session_mod._set_session(None)
                self.session.wake()  # unblock any in-flight long-poll

        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()
        return True

    def poll(self, max_wait: float = 0.0):
        """Returns ([(metrics, ckpt_path_or_None), ...], done, error_repr).

        ``max_wait > 0`` long-polls: blocks until a report lands, the
        loop finishes, or the timeout passes — the trainer drives this
        at ~0.5s instead of a tight 50ms spin (which measurably stole
        cycles from the train loop on small hosts and multiplied RPCs
        on clusters).

        `done` is read BEFORE draining: if the loop finishes between the
        drain and the flag read, the final reports are still picked up on
        the trainer's next (guaranteed, because done was False) poll."""
        if max_wait > 0 and self.session and not self.done \
                and self.error is None:
            self.session.wait_for_news(max_wait)
        done = self.done
        pairs = self.session.drain() if self.session else []
        out = [(m, (c.path if c is not None else None)) for m, c in pairs]
        err = None
        if self.error is not None:
            import traceback

            err = "".join(traceback.format_exception(
                type(self.error), self.error, self.error.__traceback__))
        return out, done, err



class BaseTrainer:
    def __init__(self, *, scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.resume_from_checkpoint = resume_from_checkpoint

    def fit(self) -> Result:
        raise NotImplementedError


class JaxTrainer(BaseTrainer):
    """Data-parallel (and beyond — the mesh decides) JAX trainer.

    train_loop_per_worker(config) runs on every gang member; inside it use
    ``raytpu.train.report`` / ``get_context`` / ``get_dataset_shard`` and
    the mesh helpers in :mod:`raytpu.parallel`.
    """

    # Which process-group flavor setup_distributed forms for the gang.
    distributed_backend = "jax"

    def __init__(self, train_loop_per_worker: Callable[[dict], None], *,
                 train_loop_config: Optional[dict] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        super().__init__(scaling_config=scaling_config,
                         run_config=run_config,
                         resume_from_checkpoint=resume_from_checkpoint)
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.datasets = datasets or {}

    def fit(self) -> Result:
        import cloudpickle

        sc = self.scaling_config
        rc = self.run_config
        name = rc.name or f"raytpu-train-{int(time.time())}"
        storage = rc.storage_path or os.path.join(
            tempfile.gettempdir(), "raytpu_results")
        run_dir = os.path.join(storage, name)
        os.makedirs(run_dir, exist_ok=True)
        manager = CheckpointManager(
            os.path.join(run_dir, "checkpoints"),
            num_to_keep=rc.checkpoint_config.num_to_keep,
            score_attribute=rc.checkpoint_config.checkpoint_score_attribute,
            score_order=rc.checkpoint_config.checkpoint_score_order,
        )

        rdzv = None
        rdzv_name = None
        if sc.coordinator_address == "auto" and sc.num_workers > 1:
            rdzv_name = f"rdzv::{name}"
            # Restartable: the store must survive node loss — losing it
            # would burn every gang-retry attempt on rendezvous failures.
            # A restarted (empty) incarnation is fine: each attempt
            # publishes under its own key.
            rdzv = _RendezvousStore.options(
                name=rdzv_name, max_restarts=100).remote()

        attempts = rc.failure_config.max_failures + 1
        elastic = bool(sc.elastic and sc.min_workers
                       and sc.min_workers < sc.num_workers)
        floor = max(1, min(sc.min_workers or sc.num_workers,
                           sc.num_workers))
        fn_blob = cloudpickle.dumps(self.train_loop_per_worker)
        last_error = None
        failures = 0
        world = sc.num_workers
        history: list = []
        try:
            incarnation = 0  # rendezvous key: unique per gang formed
            while True:
                result = self._run_gang(sc, name, run_dir, manager,
                                        fn_blob, rdzv_name=rdzv_name,
                                        attempt=incarnation,
                                        world_size=world,
                                        target_world=(sc.num_workers
                                                      if elastic else None))
                incarnation += 1
                # Continuous history across gang incarnations: a resumed
                # run is ONE experiment, not N.
                history.extend(result.metrics_history)
                if isinstance(result.error, _GangRescale):
                    # Capacity returned mid-run; the gang parked at a
                    # checkpoint boundary. Re-form at full strength —
                    # this is progress, not a failure: no budget burned.
                    world = result.error.world
                    self.resume_from_checkpoint = manager.latest()
                    continue
                if result.error is None:
                    return Result(
                        metrics=history[-1] if history else {},
                        metrics_history=history,
                        checkpoint=result.checkpoint,
                        path=run_dir, error=None)
                last_error = result.error
                failures += 1
                if failures >= attempts:
                    break
                # Gang restart from the latest checkpoint (SURVEY.md §7
                # hard part (d): elastic recovery = checkpoint + gang
                # restart).
                self.resume_from_checkpoint = manager.latest()
                if elastic:
                    # Probe live capacity: the biggest feasible world
                    # size in [floor, num_workers]. Training resumes
                    # degraded rather than burning the whole failure
                    # budget waiting for a full-strength cluster.
                    world = _probe_world_size(sc, floor,
                                              sc.num_workers) or world
            return Result(metrics=history[-1] if history else {},
                          metrics_history=history, checkpoint=None,
                          path=run_dir, error=last_error)
        finally:
            if rdzv is not None:
                try:
                    raytpu.kill(rdzv)
                except Exception as e:
                    errors.swallow("train.gang_teardown", e)
            # Staged snapshots that were never registered (failed gangs,
            # undrained reports) are garbage once fit() returns.
            import shutil

            shutil.rmtree(os.path.join(run_dir, ".staged_ckpts"),
                          ignore_errors=True)

    # -- internals ------------------------------------------------------------

    def _run_gang(self, sc: ScalingConfig, name: str, run_dir: str,
                  manager: CheckpointManager, fn_blob: bytes,
                  rdzv_name: Optional[str] = None,
                  attempt: int = 0,
                  world_size: Optional[int] = None,
                  target_world: Optional[int] = None) -> Result:
        from raytpu.core.errors import TaskError

        n = world_size or sc.num_workers
        pg = None
        workers = []
        history = []
        last_ckpt = None
        # Scale-back-up bookkeeping (elastic gang below full strength):
        # capacity is probed at most once per check period, and only a
        # checkpoint boundary may trigger the rescale — re-forming the
        # gang anywhere else would lose progress since the last save.
        next_upscale_check = time.monotonic() \
            + tuning.ELASTIC_UPSCALE_CHECK_PERIOD_S
        try:
            bundles = sc.bundle_specs(n)
            pg = raytpu.placement_group(bundles,
                                        strategy=sc.placement_strategy)
            shards = _split_datasets(self.datasets, n)
            for rank in range(n):
                ctx_kwargs = {
                    "experiment_name": name,
                    "storage_path": run_dir,
                    "chip_coords": pg.chip_coords(rank) if sc.use_tpu else None,
                }
                w = TrainWorker.options(
                    placement_group=pg,
                    placement_group_bundle_index=rank,
                ).remote(rank, n, ctx_kwargs)
                workers.append(w)
            # Gang rendezvous: jax.distributed.initialize runs only when a
            # coordinator address is configured (multi-host cluster mode);
            # in-process workers share one JAX runtime and must skip it.
            raytpu.get([
                w.setup_distributed.remote(
                    sc.coordinator_address, n, i,
                    rdzv_name, attempt, self.distributed_backend)
                for i, w in enumerate(workers)])
            resume = (self.resume_from_checkpoint.path
                      if self.resume_from_checkpoint is not None else None)
            raytpu.get([
                w.start.remote(fn_blob, self.train_loop_config,
                               shards[i], resume)
                for i, w in enumerate(workers)])

            error = None
            while True:
                # Long-poll rank 0 (it drives metrics/checkpoints); other
                # ranks answer instantly. No driver-side spin: the worker
                # wakes us on report/finish (see TrainWorker.poll).
                polls = raytpu.get(
                    [w.poll.remote(0.5 if i == 0 else 0.0)
                     for i, w in enumerate(workers)])
                ckpt_this_round = False
                for metrics, ckpt_path in polls[0][0]:  # rank 0 drives
                    history.append(metrics)
                    if ckpt_path:
                        last_ckpt = manager.register(
                            Checkpoint(ckpt_path), metrics)
                        ckpt_this_round = True
                if ckpt_this_round and target_world and n < target_world \
                        and time.monotonic() >= next_upscale_check:
                    # Checkpoint boundary while degraded: if replacement
                    # capacity can hold the FULL gang's extra bundles,
                    # park here and let fit() re-form at full strength,
                    # resuming from the checkpoint just registered.
                    next_upscale_check = time.monotonic() \
                        + tuning.ELASTIC_UPSCALE_CHECK_PERIOD_S
                    if _world_feasible(sc, target_world, held=n):
                        return Result(
                            metrics=history[-1] if history else {},
                            metrics_history=history,
                            checkpoint=last_ckpt or manager.latest(),
                            path=run_dir,
                            error=_GangRescale(target_world),
                        )
                errs = [p[2] for p in polls if p[2]]
                if errs:
                    error = TaskError("train_loop_per_worker", errs[0])
                    break
                if all(p[1] for p in polls):
                    break
                # Pace every round: a loop reporting hundreds of times a
                # second must not drive a poll round per report — drains
                # batch. Idle gangs park in the long-poll either way.
                time.sleep(0.05)
            return Result(
                metrics=history[-1] if history else {},
                metrics_history=history,
                checkpoint=last_ckpt or manager.latest(),
                path=run_dir,
                error=error,
            )
        except Exception as e:
            # Gang-shaped failure: a member (or its node/PG) died. Surface
            # it as a failed Result so fit()'s FailureConfig loop restarts
            # the whole gang from the latest checkpoint (SURVEY §7 hard
            # part (d)) instead of crashing the driver.
            return Result(
                metrics=history[-1] if history else {},
                metrics_history=history,
                checkpoint=last_ckpt or manager.latest(),
                path=run_dir,
                error=e if isinstance(e, TaskError) else TaskError(
                    "train_gang", f"gang failure: {type(e).__name__}: {e}"),
            )
        finally:
            for w in workers:
                try:
                    raytpu.kill(w)
                except Exception as e:
                    errors.swallow("train.gang_teardown", e)
            if pg is not None:
                try:
                    raytpu.remove_placement_group(pg)
                except Exception as e:
                    errors.swallow("train.gang_teardown", e)


class _GangRescale(Exception):
    """Internal fit() control flow, never user-visible: an elastic gang
    running below full strength found capacity for ``world`` workers and
    parked at a checkpoint boundary so fit() can re-form it bigger."""

    def __init__(self, world: int):
        super().__init__(f"rescale gang to {world} workers")
        self.world = world


def _world_feasible(sc: ScalingConfig, world: int, held: int = 0) -> bool:
    """Can a ``world``-worker gang place on the live cluster right now?

    Greedy first-fit of ``sc.bundle_specs(world)`` onto each alive
    node's available resources — the driver-side mirror of the head's
    PG packer, cheap enough to poll. ``held``: bundles the CURRENT gang
    already occupies (released the moment fit() re-forms it), so an
    upscale probe only needs ``world - held`` fresh bundles. For
    STRICT_PACK the held bundles are known to sit on one node, and the
    probe requires a single node covering the full need net of them —
    slightly optimistic when another node matches, in which case the
    rescale attempt fails PG creation and the elastic loop recovers.
    """
    bundles = sc.bundle_specs(world)
    if not bundles:
        return True
    try:
        infos = raytpu.nodes()
    except Exception as e:
        errors.swallow("train.elastic_probe", e)
        return False
    # The cluster client returns reference-style capitalized keys
    # ("Alive"/"Available"/"Labels"); the local backend lowercase ones.
    avail = []
    for i in infos:
        labels = i.get("Labels") or i.get("labels") or {}
        if not i.get("Alive", i.get("alive")) \
                or labels.get("role") == "driver":
            continue
        avail.append(dict(i.get("Available") or i.get("available") or {}))
    if sc.placement_strategy == "STRICT_PACK":
        need: Dict[str, float] = {}
        for b in bundles[held:]:
            for k, v in b.items():
                need[k] = need.get(k, 0.0) + v
        return any(all(a.get(k, 0.0) >= v - 1e-9
                       for k, v in need.items()) for a in avail)
    for b in bundles[held:]:
        for a in avail:
            if all(a.get(k, 0.0) >= v - 1e-9 for k, v in b.items()):
                for k, v in b.items():
                    a[k] = a.get(k, 0.0) - v
                break
        else:
            return False
    return True


def _probe_world_size(sc: ScalingConfig, floor: int,
                      ceiling: int) -> Optional[int]:
    """Post-failure capacity probe: wait up to ELASTIC_PROBE_TIMEOUT_S
    for ANY feasible world size in ``[floor, ceiling]``, preferring the
    biggest. Returns None when nothing fits within the budget — the
    caller retries at its previous size and lets the gang failure
    surface normally."""
    deadline = time.monotonic() + tuning.ELASTIC_PROBE_TIMEOUT_S
    while True:
        for world in range(ceiling, floor - 1, -1):
            if _world_feasible(sc, world):
                return world
        if time.monotonic() >= deadline:
            return None
        time.sleep(tuning.ELASTIC_PROBE_PERIOD_S)


def _split_datasets(datasets: Dict[str, Any], n: int):
    """Per-worker dataset shards via streaming_split (reference:
    ``DataConfig.configure_ingest``, SURVEY.md A8)."""
    shards = [dict() for _ in range(n)]
    for key, ds in datasets.items():
        if hasattr(ds, "streaming_split"):
            its = ds.streaming_split(n)
            for i in range(n):
                shards[i][key] = its[i]
        else:
            for i in range(n):
                shards[i][key] = ds
    return shards


# Reference-parity alias: the reference's trainer hierarchy roots at
# DataParallelTrainer (python/ray/train/data_parallel_trainer.py);
# JaxTrainer IS our data-parallel trainer.
DataParallelTrainer = JaxTrainer
