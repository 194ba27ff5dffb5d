"""In-process multi-node cluster for tests.

Reference analog: python/ray/cluster_utils.py:135 `Cluster` — `add_node`
spawns a full raylet (+ its own object store) per simulated node on one
machine, each with its own resource dict; `remove_node` kills it to exercise
fault-tolerance paths. This is the main multi-node-without-a-cluster trick
(SURVEY §4.2).
"""

from __future__ import annotations

import subprocess
import time
from typing import Dict, List, Optional

from ray_tpu.runtime import node as node_mod


class ClusterNode:
    def __init__(self, proc: subprocess.Popen, info: dict,
                 resources: Dict[str, float],
                 labels: Optional[Dict[str, str]] = None):
        self.proc = proc
        self.node_id = bytes.fromhex(info["node_id"])
        self.address = tuple(info["address"])
        self.store_path = info["store_path"]
        self.resources = resources
        self.labels: Dict[str, str] = dict(labels or {})


class Cluster:
    """Start a GCS and add/remove simulated nodes.

    Usage:
        cluster = Cluster()
        cluster.add_node(num_cpus=4)              # becomes the head node
        cluster.add_node(num_cpus=2, resources={"TPU": 4})
        ray_tpu.init(address=cluster.address)
    """

    def __init__(self):
        self.session_dir = node_mod.new_session_dir()
        self.gcs_proc, self.gcs_address = node_mod.start_gcs(self.session_dir)
        self.nodes: List[ClusterNode] = []

    @property
    def address(self) -> str:
        return f"{self.gcs_address[0]}:{self.gcs_address[1]}"

    def kill_gcs(self):
        """SIGKILL the GCS process (FT testing)."""
        self.gcs_proc.kill()
        self.gcs_proc.wait(timeout=10)

    def restart_gcs(self):
        """Restart the GCS on the SAME port with its durable sqlite state;
        raylets/workers reconnect and resume (redis-backed GCS restart
        analog)."""
        if self.gcs_proc.poll() is None:
            self.kill_gcs()
        self.gcs_proc, self.gcs_address = node_mod.start_gcs(
            self.session_dir, port=self.gcs_address[1])

    def add_node(self, *, num_cpus: float = 1.0, num_tpus: float = 0.0,
                 resources: Optional[Dict[str, float]] = None,
                 labels: Optional[Dict[str, str]] = None,
                 object_store_memory: int = 512 << 20,
                 env: Optional[Dict[str, str]] = None) -> ClusterNode:
        res: Dict[str, float] = {"CPU": float(num_cpus)}
        if num_tpus:
            res["TPU"] = float(num_tpus)
        res.update({k: float(v) for k, v in (resources or {}).items()})
        is_head = not self.nodes
        import sys
        worker_env = {"PYTHONPATH": ":".join(p for p in sys.path if p)}
        worker_env.update(env or {})
        proc, info = node_mod.start_raylet(
            self.session_dir, self.gcs_address, res, labels or {},
            object_store_memory, is_head=is_head, worker_env=worker_env,
            name=f"raylet{len(self.nodes)}")
        node = ClusterNode(proc, info, res, labels)
        self.nodes.append(node)
        return node

    def remove_node(self, node: ClusterNode, force: bool = True):
        """Kill a node (raylet + its workers) to simulate node failure;
        force=False lets the raylet reap its workers. Either way its arena
        is gone when this returns (node.stop_raylet). Removing a node twice
        is a no-op: a killer's timer thread and `shutdown` may both get to
        the same node."""
        try:
            node_mod.stop_raylet(node.proc, node.store_path, force=force)
        except Exception:
            pass
        try:
            self.nodes.remove(node)
        except ValueError:
            pass

    def wait_for_nodes(self, count: Optional[int] = None, timeout: float = 30):
        """Block until GCS sees `count` (default: all added) live nodes."""
        import ray_tpu
        want = count if count is not None else len(self.nodes)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            alive = [n for n in ray_tpu.nodes() if n["alive"]]
            if len(alive) >= want:
                return
            time.sleep(0.1)
        raise TimeoutError(f"only {len(alive)} of {want} nodes alive")

    def shutdown(self):
        for node in list(self.nodes):
            self.remove_node(node, force=False)  # let raylets reap their workers
        try:
            self.gcs_proc.terminate()
            self.gcs_proc.wait(timeout=5)
        except Exception:
            try:
                self.gcs_proc.kill()
            except Exception:
                pass
