"""Worker-process plane: crash containment, chip isolation, pool reuse.

Reference analogue: worker pool + lease protocol
(``src/ray/raylet/worker_pool.h:343,354,417``) and TPU chip isolation
(``python/ray/_private/accelerators/tpu.py:30-49``). The invariants under
test: a crashing user task kills only its worker subprocess (the node
daemon survives and retries), and two 1-chip actors see disjoint chips.
"""

import os
import time

import pytest

import raytpu
from raytpu.cluster import Cluster
from raytpu.core.errors import ActorDiedError, WorkerCrashedError


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(num_nodes=1,
                node_resources={"num_cpus": 4, "num_tpus": 2})
    c.wait_for_nodes(1)
    yield c
    c.shutdown()


@pytest.fixture
def driver(cluster):
    raytpu.shutdown()
    raytpu.init(address=f"tcp://{cluster.address}")
    yield raytpu
    raytpu.shutdown()


class TestProcessExecution:
    def test_task_runs_in_subprocess_and_reuses_worker(self, driver):
        @raytpu.remote
        def pid():
            return os.getpid()

        p1 = raytpu.get(pid.remote(), timeout=60)
        p2 = raytpu.get(pid.remote(), timeout=60)
        assert p1 != os.getpid()
        # Same (job, env, chips) key → the idle worker is reused.
        assert p1 == p2

    def test_crash_containment_daemon_survives(self, driver):
        @raytpu.remote(max_retries=0)
        def die():
            os._exit(17)

        with pytest.raises(WorkerCrashedError):
            raytpu.get(die.remote(), timeout=60)

        # The node daemon survived: new work still executes.
        @raytpu.remote
        def ok():
            return "alive"

        assert raytpu.get(ok.remote(), timeout=60) == "alive"

    def test_crash_retries_then_succeeds(self, driver, tmp_path):
        marker = str(tmp_path / "attempted")

        @raytpu.remote(max_retries=2)
        def flaky(path):
            if not os.path.exists(path):
                with open(path, "w") as f:
                    f.write("x")
                os._exit(1)
            return "second try"

        assert raytpu.get(flaky.remote(marker), timeout=120) == "second try"

    def test_nested_task_and_put_from_worker(self, driver):
        @raytpu.remote
        def inner(x):
            return x * 2

        @raytpu.remote
        def outer():
            ref = raytpu.put(21)
            return raytpu.get(inner.remote(raytpu.get(ref)), timeout=60)

        assert raytpu.get(outer.remote(), timeout=120) == 42


class TestChipIsolation:
    def test_chip_env_shows_the_tpu_only_to_its_leaseholder(self):
        from raytpu.cluster.worker_pool import chip_env

        one = chip_env((2,))
        assert one["TPU_VISIBLE_CHIPS"] == one["RAYTPU_VISIBLE_CHIPS"] == "2"
        assert one["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert one["TPU_PROCESS_BOUNDS"] == "1,1,1"
        assert "JAX_PLATFORMS" not in one
        # Processes sharing a host must not share a controller port.
        assert one["TPU_MESH_CONTROLLER_PORT"] != \
            chip_env((3,))["TPU_MESH_CONTROLLER_PORT"]
        assert chip_env((0, 1))["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
        # The whole 2x2 host: every chip visible, no bounds to get wrong.
        host = chip_env((0, 1, 2, 3))
        assert host["TPU_VISIBLE_CHIPS"] == "0,1,2,3"
        assert "TPU_CHIPS_PER_PROCESS_BOUNDS" not in host
        assert "JAX_PLATFORMS" not in host
        # No lease: held to the CPU, so it cannot take a leased chip.
        assert chip_env(()) == {"RAYTPU_VISIBLE_CHIPS": "",
                                "JAX_PLATFORMS": "cpu"}

    def test_two_actors_disjoint_chips(self, driver):
        @raytpu.remote(num_tpus=1)
        class ChipOwner:
            def chips(self):
                return os.environ.get("RAYTPU_VISIBLE_CHIPS")

            def tpu_env(self):
                return {k: v for k, v in os.environ.items()
                        if k.startswith("TPU_")}

        a = ChipOwner.remote()
        b = ChipOwner.remote()
        ca = raytpu.get(a.chips.remote(), timeout=60)
        cb = raytpu.get(b.chips.remote(), timeout=60)
        assert ca is not None and cb is not None
        assert ca != "" and cb != ""
        assert set(ca.split(",")).isdisjoint(set(cb.split(",")))
        env = raytpu.get(a.tpu_env.remote(), timeout=60)
        assert env.get("TPU_VISIBLE_CHIPS") == ca
        assert env.get("TPU_CHIPS_PER_PROCESS_BOUNDS") == "1,1,1"
        raytpu.kill(a)
        raytpu.kill(b)

    def test_tpu_task_gets_chip_env(self, driver):
        @raytpu.remote(num_tpus=1)
        def which_chips():
            return os.environ.get("RAYTPU_VISIBLE_CHIPS")

        chips = raytpu.get(which_chips.remote(), timeout=60)
        assert chips in ("0", "1")


class TestActorProcess:
    def test_actor_state_in_own_process(self, driver):
        @raytpu.remote
        class Counter:
            def __init__(self):
                self.n = 0
                self.pid = os.getpid()

            def incr(self):
                self.n += 1
                return self.n

            def where(self):
                return self.pid

        c = Counter.remote()
        assert raytpu.get(c.incr.remote(), timeout=60) == 1
        assert raytpu.get(c.incr.remote(), timeout=60) == 2
        assert raytpu.get(c.where.remote(), timeout=60) != os.getpid()
        raytpu.kill(c)

    def test_actor_crash_is_actor_death_not_node_death(self, driver):
        @raytpu.remote
        class Bomb:
            def boom(self):
                os._exit(3)

            def ping(self):
                return "pong"

        b = Bomb.remote()
        assert raytpu.get(b.ping.remote(), timeout=60) == "pong"
        with pytest.raises((ActorDiedError, WorkerCrashedError)):
            raytpu.get(b.boom.remote(), timeout=60)
        # Subsequent calls observe the death promptly.
        with pytest.raises((ActorDiedError, WorkerCrashedError)):
            raytpu.get(b.ping.remote(), timeout=60)

        # And the node itself is fine.
        @raytpu.remote
        def ok():
            return 1

        assert raytpu.get(ok.remote(), timeout=60) == 1

    def test_async_actor_in_process(self, driver):
        @raytpu.remote(max_concurrency=4)
        class Async:
            async def work(self, x):
                import asyncio

                await asyncio.sleep(0.05)
                return x + 1

        a = Async.remote()
        refs = [a.work.remote(i) for i in range(4)]
        assert sorted(raytpu.get(refs, timeout=60)) == [1, 2, 3, 4]
        raytpu.kill(a)
