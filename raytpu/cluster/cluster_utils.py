"""Single-host multi-process cluster harness for tests and local use.

Reference analogue: ``Cluster`` (``python/ray/cluster_utils.py:135``) — the
reference's primary multi-node-without-a-cluster mechanism (SURVEY.md §4
item 2): real head + node processes on one machine. ``kill_node`` is the
chaos hook (reference: ``NodeKillerActor``,
``python/ray/_private/test_utils.py:1497``).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List, Optional

from raytpu.cluster.protocol import RpcClient


def _await_banner(proc: subprocess.Popen, marker: str, what: str,
                  max_lines: int = 50) -> str:
    """Read lines until the startup banner appears, skipping interpreter
    noise (warnings etc.); raise with everything seen if the process dies
    or never prints it."""
    seen = []
    for _ in range(max_lines):
        line = proc.stdout.readline()
        if not line:
            break
        seen.append(line)
        if marker in line:
            return line
    raise RuntimeError(
        f"{what} failed to start (rc={proc.poll()}):\n{''.join(seen)}")


class ClusterNodeHandle:
    def __init__(self, proc: subprocess.Popen, node_id: Optional[str] = None):
        self.proc = proc
        self.node_id = node_id

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None


class Cluster:
    """Launches a head process + node processes; drivers connect with
    ``raytpu.init(address=cluster.address)``."""

    def __init__(self, num_nodes: int = 0,
                 node_resources: Optional[Dict] = None,
                 host: str = "127.0.0.1",
                 head_storage: Optional[str] = None,
                 addr_file: Optional[str] = None):
        env = dict(os.environ)
        # Child processes must import raytpu from the same tree as us even
        # when it isn't pip-installed.
        import raytpu

        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(raytpu.__file__)))
        env["PYTHONPATH"] = pkg_root + os.pathsep + env.get("PYTHONPATH", "")
        self._head_addr_file = addr_file
        if addr_file:
            # Every child (nodes, workers) inherits the discovery record
            # path, so redirect-on-failover works without per-process
            # configuration.
            env["RAYTPU_HEAD_ADDR_FILE"] = addr_file
        self._env = env
        self._host = host
        self._head_storage = head_storage
        self.standby_proc: Optional[subprocess.Popen] = None
        self.head_proc = self._spawn_head(port=0)
        line = _await_banner(self.head_proc, "listening on", "head")
        self.address = line.strip().rsplit(" ", 1)[-1]
        self.nodes: List[ClusterNodeHandle] = []
        for _ in range(num_nodes):
            self.add_node(**(node_resources or {"num_cpus": 2}))

    def _spawn_head(self, port: int) -> subprocess.Popen:
        cmd = [sys.executable, "-m", "raytpu.cluster.head",
               "--host", self._host, "--port", str(port)]
        if self._head_storage:
            cmd += ["--storage", self._head_storage]
        if self._head_addr_file:
            cmd += ["--addr-file", self._head_addr_file]
        return subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=self._env,
        )

    def kill_head(self) -> None:
        """Chaos hook: SIGKILL the head process (control-plane loss)."""
        self.head_proc.kill()
        self.head_proc.wait(timeout=10)

    def pause_head(self) -> None:
        """Chaos hook: SIGSTOP the head — alive but silent past any
        lease TTL (the split-brain half of a failover test)."""
        self.head_proc.send_signal(signal.SIGSTOP)

    def resume_head(self) -> None:
        """Resume a SIGSTOP'd head; it must discover it was superseded
        and self-fence rather than keep acting as the head."""
        self.head_proc.send_signal(signal.SIGCONT)

    def add_standby(self, storage: Optional[str] = None) -> None:
        """Spawn a hot-standby head following the current head. Requires
        ``head_storage`` (the standby tails the head's WAL into its own
        replica store) and ``addr_file`` (how clients find it after
        takeover)."""
        if not self._head_storage:
            raise RuntimeError("standby requires head_storage")
        self._standby_storage = storage or f"{self._head_storage}.standby"
        self.standby_proc = self._spawn_standby()

    def _spawn_standby(self) -> subprocess.Popen:
        cmd = [sys.executable, "-m", "raytpu.cluster.standby",
               "--head", self.address,
               "--storage", self._standby_storage,
               "--host", self._host, "--port", "0"]
        if self._head_addr_file:
            cmd += ["--addr-file", self._head_addr_file]
        proc = subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=self._env,
        )
        _await_banner(proc, "standby following", "standby")
        return proc

    def kill_standby(self) -> None:
        """Chaos hook: SIGKILL the follower mid-tail."""
        self.standby_proc.kill()
        self.standby_proc.wait(timeout=10)

    def restart_standby(self) -> None:
        """Respawn the follower on its existing replica store — it must
        resume WAL tailing from its persisted cursor."""
        if self.standby_proc is not None and self.standby_proc.poll() is None:
            self.kill_standby()
        self.standby_proc = self._spawn_standby()

    def await_takeover(self, timeout: float = 30.0) -> str:
        """Block until the standby takes over (it bound the serving
        socket and rewrote the discovery record); updates
        ``self.address``. Prefers polling the addr file — the standby's
        stdout goes silent while the incumbent is merely paused, and a
        blocking readline there would ignore ``timeout``."""
        deadline = time.monotonic() + timeout
        if self._head_addr_file:
            while time.monotonic() < deadline:
                if self.standby_proc.poll() is not None:
                    raise RuntimeError("standby died before takeover")
                try:
                    with open(self._head_addr_file) as f:
                        rec = json.load(f)
                except (OSError, ValueError):
                    rec = None
                if rec and rec.get("address") and \
                        rec["address"] != self.address:
                    self.address = str(rec["address"])
                    return self.address
                time.sleep(0.05)
            raise RuntimeError(
                f"standby did not take over within {timeout:g}s "
                f"(discovery record unchanged)")
        seen: List[str] = []
        while time.monotonic() < deadline:
            if self.standby_proc.poll() is not None:
                raise RuntimeError(
                    "standby died before takeover:\n" + "".join(seen))
            line = self.standby_proc.stdout.readline()
            if not line:
                time.sleep(0.05)
                continue
            seen.append(line)
            if "listening on" in line:
                self.address = line.strip().rsplit(" ", 1)[-1]
                return self.address
        raise RuntimeError(
            f"standby did not take over within {timeout:g}s:\n"
            + "".join(seen))

    def restart_head(self) -> None:
        """Restart the head at the SAME address; requires head_storage for
        tables to survive (reference: GCS restart with persistent store)."""
        if self.head_proc.poll() is None:
            self.kill_head()
        port = int(self.address.rsplit(":", 1)[-1])
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            self.head_proc = self._spawn_head(port=port)
            try:
                _await_banner(self.head_proc, "listening on", "head")
                return
            except RuntimeError:
                # Port may linger in TIME_WAIT briefly after the kill.
                time.sleep(0.5)
        raise RuntimeError("head failed to restart on its old port")

    def add_node(self, num_cpus: float = 2, num_tpus: int = 0,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None
                 ) -> ClusterNodeHandle:
        proc = subprocess.Popen(
            [sys.executable, "-m", "raytpu.cluster.node",
             "--head", self.address,
             "--num-cpus", str(num_cpus),
             "--num-tpus", str(num_tpus),
             "--resources", json.dumps(resources or {}),
             "--labels", json.dumps(labels or {}),
             "--host", self._host],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=self._env,
        )
        line = _await_banner(proc, "raytpu node", "node")
        node_id = line.split()[2]
        handle = ClusterNodeHandle(proc, node_id)
        self.nodes.append(handle)
        return handle

    def wait_for_nodes(self, count: Optional[int] = None,
                       timeout: float = 15.0) -> None:
        want = count if count is not None else len(self.nodes)
        client = RpcClient(self.address)
        try:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                alive = [
                    n for n in client.call("list_nodes")
                    if n["alive"] and n["labels"].get("role") != "driver"
                ]
                if len(alive) >= want:
                    return
                time.sleep(0.1)
            raise TimeoutError(
                f"only {len(alive)} of {want} nodes registered")
        finally:
            client.close()

    def kill_node(self, handle: ClusterNodeHandle,
                  graceful: bool = False) -> None:
        """Chaos hook: SIGKILL (default) simulates a host loss; the head
        detects it via heartbeat timeout (reference: GcsHealthCheckManager)."""
        if graceful:
            handle.proc.send_signal(signal.SIGTERM)
        else:
            handle.proc.kill()
        handle.proc.wait(timeout=10)

    def shutdown(self) -> None:
        for n in self.nodes:
            if n.alive:
                n.proc.send_signal(signal.SIGTERM)
        for n in self.nodes:
            try:
                n.proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                n.proc.kill()
        if self.standby_proc is not None and self.standby_proc.poll() is None:
            self.standby_proc.send_signal(signal.SIGTERM)
            try:
                self.standby_proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.standby_proc.kill()
        if self.head_proc.poll() is None:
            # A SIGSTOP'd head cannot handle SIGTERM; wake it first.
            try:
                self.head_proc.send_signal(signal.SIGCONT)
            except Exception:
                pass
            self.head_proc.send_signal(signal.SIGTERM)
            try:
                self.head_proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                self.head_proc.kill()
