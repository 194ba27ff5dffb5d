"""Fault-tolerance tests: lineage reconstruction and OOM worker killing.

Reference test model: python/ray/tests/test_reconstruction*.py (kill the node
holding an object, get() re-executes lineage) and test_memory_pressure.py
(memory monitor kills retriable workers).
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.mark.slow  # >60s measured: full-tier only
def test_lineage_reconstruction_after_node_loss():
    c = Cluster()
    c.add_node(num_cpus=1, resources={"head": 1})
    doomed = c.add_node(num_cpus=1, resources={"other": 1})
    ray_tpu.init(address=c.address)
    try:
        c.wait_for_nodes(2)

        @ray_tpu.remote(num_cpus=0, resources={"other": 1})
        def produce():
            return np.arange(300_000, dtype=np.float64)  # plasma-sized

        ref = produce.remote()
        # Force completion so the object exists only on the doomed node.
        ray_tpu.wait([ref], num_returns=1, timeout=120)
        c.remove_node(doomed, force=True)
        # Replacement capacity for the re-executed task.
        c.add_node(num_cpus=1, resources={"other": 1})
        c.wait_for_nodes(2)
        out = ray_tpu.get(ref, timeout=180)
        assert out.shape == (300_000,) and out[7] == 7.0
    finally:
        ray_tpu.shutdown()
        c.shutdown()


@pytest.mark.slow  # >60s measured: full-tier only
def test_recursive_reconstruction_of_lost_dependency():
    """Kill the node holding BOTH a task's result and its argument: get()
    re-executes the consumer, whose lost arg is itself reconstructed
    recursively (object_recovery_manager recursion, an earlier review, weak #11)."""
    c = Cluster()
    c.add_node(num_cpus=1, resources={"head": 1})
    doomed = c.add_node(num_cpus=1, resources={"other": 1})
    ray_tpu.init(address=c.address)
    try:
        c.wait_for_nodes(2)

        @ray_tpu.remote(num_cpus=0, resources={"other": 1})
        def produce():
            return np.arange(300_000, dtype=np.float64)

        @ray_tpu.remote(num_cpus=0, resources={"other": 1})
        def consume(x):
            return x * 2.0

        a = produce.remote()
        b = consume.remote(a)
        ray_tpu.wait([b], num_returns=1, timeout=120)
        c.remove_node(doomed, force=True)
        c.add_node(num_cpus=1, resources={"other": 1})
        c.wait_for_nodes(2)
        out = ray_tpu.get(b, timeout=180)
        assert out.shape == (300_000,) and out[7] == 14.0
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def test_oom_killer_retries_task(fake_memory_pressure):
    mem_file, marker = fake_memory_pressure
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(max_retries=2)
        def pressure(mem_file, marker):
            if not os.path.exists(marker):
                # First attempt: raise reported memory over the threshold and
                # hang — the raylet's monitor must kill this worker (and the
                # fixture's watcher then reports the memory freed).
                with open(mem_file, "w") as f:
                    f.write("0.99")
                with open(marker, "w") as f:
                    f.write(str(os.getpid()))
                time.sleep(120)
                return "not killed"
            return "survived retry"

        assert ray_tpu.get(pressure.remote(mem_file, marker),
                           timeout=120) == "survived retry"
    finally:
        ray_tpu.shutdown()


def test_memory_usage_fraction_reads_proc():
    from ray_tpu.runtime.memory_monitor import node_memory_usage_fraction

    frac = node_memory_usage_fraction()
    assert frac is not None and 0.0 < frac < 1.0


@pytest.mark.slow  # >60s measured: full-tier only
def test_noop_cancel_does_not_poison_reconstruction():
    """cancel() on a finished task is a no-op and must leave NO trace:
    lineage reconstruction of that task's lost object must still work
    (a suppressed re-execution here would surface as ObjectLostError)."""
    c = Cluster()
    c.add_node(num_cpus=1, resources={"head": 1})
    doomed = c.add_node(num_cpus=1, resources={"other": 1})
    ray_tpu.init(address=c.address)
    try:
        c.wait_for_nodes(2)

        @ray_tpu.remote(num_cpus=0, resources={"other": 1})
        def produce():
            return np.arange(300_000, dtype=np.float64)  # plasma-sized

        ref = produce.remote()
        ray_tpu.wait([ref], num_returns=1, timeout=120)
        assert ray_tpu.cancel(ref) is False  # finished: documented no-op
        c.remove_node(doomed, force=True)
        c.add_node(num_cpus=1, resources={"other": 1})
        c.wait_for_nodes(2)
        out = ray_tpu.get(ref, timeout=180)
        assert out.shape == (300_000,) and out[7] == 7.0
    finally:
        ray_tpu.shutdown()
        c.shutdown()
