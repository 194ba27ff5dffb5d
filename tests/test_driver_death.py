"""A SIGKILLed driver must not leak its auto-started cluster.

Reference behavior: a ray.init()-owned local cluster dies with the driver.
Ours: init() registers the driver connection as the cluster owner; the GCS
tears everything down when that connection drops without a graceful
shutdown (after a reconnect grace period).

"Its cluster" is what the driver's SESSION started (`node.session_pids`, and
the raylet's children, its workers): this host runs other clusters while the
test runs, the other xdist workers' among them, and they are none of its
business. The neighbour below is one of them, started here to be sure of it.
"""

import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.runtime import node as node_mod

DRIVER = """
import os, sys, time
import ray_tpu

ray_tpu.init(num_cpus=1)
print("READY", ray_tpu.get_runtime_context().session_dir,
      ray_tpu.nodes()[0]["object_store_path"], flush=True)
time.sleep(120)   # killed long before this expires
"""


def _alive(pids):
    alive = []
    for pid in pids:
        try:
            os.kill(pid, 0)
            alive.append(pid)
        except OSError:
            pass
    return alive


@ray_tpu.remote
def _answer():
    return os.getpid()


@pytest.fixture(scope="module")
def killed_driver(tmp_path_factory):
    """One run of the scenario: a neighbour cluster that answers, a driver
    with a cluster of its own, the driver SIGKILLed, and 30 s for its
    cluster to go. Yields what was left of it (processes, the arena's path)
    and the neighbour."""
    neighbour = Cluster()
    proc = None
    pids = []
    try:
        neighbour.add_node(num_cpus=1, object_store_memory=64 << 20)
        ray_tpu.init(address=neighbour.address)
        assert ray_tpu.get(_answer.remote(), timeout=60) > 0

        script = tmp_path_factory.mktemp("driver") / "driver.py"
        script.write_text(DRIVER)
        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + ":" + env.get("PYTHONPATH", "")
        proc = subprocess.Popen([sys.executable, str(script)],
                                stdout=subprocess.PIPE, text=True, env=env)
        ready, session_dir, store_path = proc.stdout.readline().split()
        assert ready == "READY", ready
        assert os.path.exists(store_path)

        started = dict(node_mod.session_pids(session_dir))
        assert set(started) == {"gcs", "raylet"}, started
        pids = list(started.values()) + node_mod.child_pids(started["raylet"])
        assert _alive(pids) == pids

        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=10)

        # Grace period (10 s) + teardown: everything must exit.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and _alive(pids):
            time.sleep(0.5)
        yield _alive(pids), store_path, neighbour
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
        # Never leak into other tests even on failure: the pids read from
        # the driver's own session, and nothing else.
        for pid in _alive(pids):
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
        ray_tpu.shutdown()
        neighbour.shutdown()


def test_sigkilled_driver_tears_down_cluster(killed_driver):
    leaked, store_path, _ = killed_driver
    assert not leaked, f"cluster processes leaked after driver death: {leaked}"
    assert not os.path.exists(store_path)


def test_a_neighbour_cluster_outlives_the_killed_drivers(killed_driver):
    _, _, neighbour = killed_driver
    mine = [neighbour.gcs_proc.pid] + [n.proc.pid for n in neighbour.nodes]
    assert _alive(mine) == mine
    assert ray_tpu.get(_answer.remote(), timeout=60) > 0
