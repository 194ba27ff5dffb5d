"""Ray-on-Spark: bootstrap a ray_tpu cluster on a Spark cluster.

Reference analog: python/ray/util/spark/cluster_init.py
(setup_ray_cluster / shutdown_ray_cluster / MAX_NUM_WORKER_NODES). Shape
matches the reference's design:

  * the HEAD (GCS + a 0-CPU raylet) runs next to the Spark driver — no
    tasks schedule onto the driver host by default;
  * each ray_tpu WORKER node is pinned to one Spark executor by a
    long-running BARRIER job (barrier so Spark co-schedules every worker
    and tears them down together), launched from a background thread;
  * worker nodes self-terminate when the head's GCS becomes unreachable,
    so a driver-side shutdown (or driver death) reaps the whole cluster
    even if Spark's task-cancel signal is lost.

pyspark is NOT required to import this module: `setup_ray_cluster`
accepts any object with the SparkSession surface it uses
(sparkContext.parallelize(...).barrier().mapPartitions(...).collect(),
setJobGroup/cancelJobGroup, defaultParallelism) — the tests drive it
with an in-process fake the same way the KubeRay provider is driven by
FakeKubeApi; a real SparkSession works unchanged.
"""

from __future__ import annotations

import logging
import os
import threading
import time
import uuid
from typing import Dict, Optional, Tuple

logger = logging.getLogger(__name__)

# Sentinel: size the cluster to the Spark cluster's default parallelism
# (reference: ray.util.spark.MAX_NUM_WORKER_NODES).
MAX_NUM_WORKER_NODES = -1

_active_cluster: Optional["RayClusterOnSpark"] = None


def _run_worker_node(gcs_address: str, resources: Dict[str, float],
                     object_store_memory: int, auth_token_hex: str,
                     poll_interval_s: float = 2.0) -> str:
    """Runs ON A SPARK EXECUTOR (inside the barrier task): start one
    ray_tpu worker node attached to `gcs_address` and babysit it until
    the head disappears. Returns the node id hex on exit.

    The babysit loop is the cleanup guarantee: Spark task-kill runs the
    finally (normal cancel), and if the executor is lost abruptly the
    next GCS health sweep marks the node dead — while a lost HEAD makes
    this loop kill its raylet, so no orphan raylets outlive the cluster
    (reference: start_ray_node's parent-death watch, cluster_init.py)."""
    import socket
    import tempfile

    from ray_tpu.runtime import node as node_mod

    if auth_token_hex:
        os.environ["RAY_TPU_AUTH_TOKEN"] = auth_token_hex
    host, port = gcs_address.rsplit(":", 1)
    session_dir = tempfile.mkdtemp(prefix="ray_tpu_spark_worker_")
    os.makedirs(os.path.join(session_dir, "logs"), exist_ok=True)
    import sys

    worker_env = {"PYTHONPATH": ":".join(p for p in sys.path if p)}
    if auth_token_hex:
        worker_env["RAY_TPU_AUTH_TOKEN"] = auth_token_hex
    proc, info = node_mod.start_raylet(
        session_dir, (host, int(port)), dict(resources), {},
        object_store_memory, is_head=False, worker_env=worker_env,
        name=f"spark-worker-{uuid.uuid4().hex[:6]}")
    try:
        while proc.poll() is None:
            time.sleep(poll_interval_s)
            try:
                with socket.create_connection((host, int(port)),
                                              timeout=5):
                    pass
            except OSError:
                # Head gone: the cluster is over; don't orphan the raylet.
                break
    finally:
        node_mod.stop_raylet(proc, info["store_path"])
    return info["node_id"]


class RayClusterOnSpark:
    """Handle for a ray_tpu cluster running on Spark executors."""

    def __init__(self, spark, address: str, session_dir: str, gcs_proc,
                 head_proc, head_store_path: str, job_group: str,
                 job_thread: threading.Thread, num_workers: int):
        self.spark = spark
        self.address = address
        self.session_dir = session_dir
        self._gcs_proc = gcs_proc
        self._head_proc = head_proc
        self._head_store_path = head_store_path
        self._job_group = job_group
        self._job_thread = job_thread
        self.num_workers = num_workers
        self._down = False

    def shutdown(self):
        global _active_cluster
        if self._down:
            return
        self._down = True
        try:
            self.spark.sparkContext.cancelJobGroup(self._job_group)
        except Exception:
            logger.warning("cancelJobGroup failed", exc_info=True)
        # Killing the head makes every worker's babysit loop exit even if
        # the Spark cancel never reaches an executor.
        from ray_tpu.runtime import node as node_mod

        try:
            node_mod.stop_raylet(self._head_proc, self._head_store_path)
        except Exception:
            logger.warning("stopping the head raylet failed", exc_info=True)
        try:
            self._gcs_proc.terminate()
            self._gcs_proc.wait(timeout=10)
        except Exception:
            self._gcs_proc.kill()
        self._job_thread.join(timeout=30)
        if _active_cluster is self:
            _active_cluster = None


def setup_ray_cluster(
        *, spark, max_worker_nodes: int,
        num_cpus_worker_node: int = 1,
        num_tpus_worker_node: int = 0,
        resources_worker_node: Optional[Dict[str, float]] = None,
        object_store_memory_worker_node: int = 256 << 20,
        head_resources: Optional[Dict[str, float]] = None,
        timeout_s: float = 120.0,
) -> Tuple[str, RayClusterOnSpark]:
    """Start a ray_tpu cluster across a Spark cluster's executors.

    Returns (address, handle); connect with
    ``ray_tpu.init(address=address)``, tear down with
    ``shutdown_ray_cluster()`` (or ``handle.shutdown()``).
    """
    global _active_cluster
    if _active_cluster is not None:
        raise RuntimeError(
            "a ray_tpu cluster is already running on this Spark session; "
            "call shutdown_ray_cluster() first")
    from ray_tpu.runtime import node as node_mod
    from ray_tpu.runtime.rpc import get_session_token

    sc = spark.sparkContext
    n = max_worker_nodes
    if n == MAX_NUM_WORKER_NODES:
        n = int(getattr(sc, "defaultParallelism", 2))
    if n <= 0:
        raise ValueError(f"max_worker_nodes must be positive or "
                         f"MAX_NUM_WORKER_NODES, got {max_worker_nodes}")

    session_dir = node_mod.new_session_dir()
    gcs_proc, gcs_address = node_mod.start_gcs(session_dir)
    try:
        # 0-CPU head: keeps GCS-adjacent services local while scheduling
        # no work onto the Spark driver host (reference default).
        import sys

        head_env = {"PYTHONPATH": ":".join(p for p in sys.path if p)}
        head_proc, head_info = node_mod.start_raylet(
            session_dir, gcs_address, dict(head_resources or {"CPU": 0.0}),
            {"spark-role": "head"}, 128 << 20, is_head=True,
            worker_env=head_env, name="spark-head")
    except Exception:
        # Don't orphan the GCS (it would squat its port for the next
        # setup attempt on this host).
        gcs_proc.terminate()
        raise
    address = f"{gcs_address[0]}:{gcs_address[1]}"
    token = get_session_token()
    token_hex = token.hex() if token else ""

    res: Dict[str, float] = {"CPU": float(num_cpus_worker_node)}
    if num_tpus_worker_node:
        res["TPU"] = float(num_tpus_worker_node)
    res.update({k: float(v)
                for k, v in (resources_worker_node or {}).items()})

    job_group = f"ray-tpu-on-spark-{uuid.uuid4().hex[:8]}"

    def _barrier_job():
        try:
            sc.setJobGroup(job_group,
                           "ray_tpu worker nodes (long-running)")
            (sc.parallelize(range(n), n)
             .barrier()
             .mapPartitions(lambda _it: [_run_worker_node(
                 address, res, object_store_memory_worker_node,
                 token_hex)])
             .collect())
        except Exception:
            logger.info("ray-on-spark barrier job ended", exc_info=True)

    job_thread = threading.Thread(target=_barrier_job, daemon=True,
                                  name=job_group)
    job_thread.start()

    handle = RayClusterOnSpark(spark, address, session_dir, gcs_proc,
                               head_proc, head_info["store_path"], job_group,
                               job_thread, n)
    # Wait for all n workers to register with the GCS.
    deadline = time.monotonic() + timeout_s
    while True:
        alive = _alive_worker_count(session_dir, gcs_address)
        if alive >= n:
            break
        if time.monotonic() > deadline:
            handle.shutdown()
            raise TimeoutError(
                f"only {alive}/{n} ray_tpu worker nodes registered within "
                f"{timeout_s}s")
        time.sleep(0.5)
    _active_cluster = handle
    return address, handle


def _alive_worker_count(session_dir: str, gcs_address) -> int:
    """Count alive non-head nodes via a short-lived GCS client."""
    import asyncio

    from ray_tpu.runtime.rpc import RpcClient

    async def _count():
        client = RpcClient(*gcs_address)
        await client.connect(timeout=10)
        try:
            nodes = await client.call("get_nodes")
        finally:
            await client.close()
        return sum(1 for nd in nodes
                   if nd.get("alive") and not nd.get("is_head"))

    try:
        return asyncio.run(_count())
    except Exception:
        return 0


def shutdown_ray_cluster():
    """Tear down the cluster started by setup_ray_cluster."""
    global _active_cluster
    if _active_cluster is None:
        raise RuntimeError("no ray_tpu cluster is running on Spark")
    _active_cluster.shutdown()
