"""Multi-node scheduling + placement group tests.

Reference test model: python/ray/tests/test_placement_group*.py and
test_multi_node*.py over cluster_utils.Cluster.
"""

import time

import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu.util import (
    PACK, STRICT_PACK, STRICT_SPREAD, placement_group, placement_group_table,
    remove_placement_group)
from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy


@pytest.fixture(scope="module")
def cluster():
    c = Cluster()
    c.add_node(num_cpus=2, resources={"head": 1})
    c.add_node(num_cpus=2, resources={"TPU": 4}, labels={"tpu-slice": "v5e-4-test"})
    c.add_node(num_cpus=2, resources={"TPU": 4})
    ray_tpu.init(address=c.address)
    c.wait_for_nodes(3)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


@ray_tpu.remote
class NodeProbe:
    def node(self):
        import os
        return os.environ["RAY_TPU_NODE_ID"]


def test_cluster_sees_all_resources(cluster):
    total = ray_tpu.cluster_resources()
    assert total["CPU"] == 6.0
    assert total["TPU"] == 8.0


def _release_actor(handle):
    """Kill an actor and wait until its resources are visible as free again
    (availability propagates via raylet heartbeats)."""
    ray_tpu.kill(handle)
    time.sleep(0.5)


def test_actor_scheduled_by_custom_resource(cluster):
    a = NodeProbe.options(resources={"head": 1}).remote()
    node = ray_tpu.get(a.node.remote(), timeout=60)
    head = next(n for n in ray_tpu.nodes() if n["resources"].get("head"))
    assert bytes.fromhex(node) == head["node_id"]
    _release_actor(a)


def test_tpu_actor_lands_on_tpu_node(cluster):
    a = NodeProbe.options(num_tpus=1).remote()
    node = ray_tpu.get(a.node.remote(), timeout=60)
    tpu_nodes = {n["node_id"] for n in ray_tpu.nodes() if n["resources"].get("TPU")}
    assert bytes.fromhex(node) in tpu_nodes
    _release_actor(a)
    # Wait for the TPU to be released and the heartbeat to propagate it.
    deadline = time.time() + 30
    while time.time() < deadline:
        if ray_tpu.available_resources().get("TPU", 0) >= 8:
            return
        time.sleep(0.3)
    raise AssertionError("TPU resource not released after actor kill")


def test_strict_pack_prefers_tpu_slice(cluster):
    pg = placement_group([{"TPU": 2}, {"TPU": 2}], strategy=STRICT_PACK)
    assert pg.wait(30)
    info = pg.table()
    locs = set(info["locations"])
    assert len(locs) == 1  # one node holds all bundles
    slice_node = next(n for n in ray_tpu.nodes()
                      if n["labels"].get("tpu-slice") == "v5e-4-test")
    assert locs == {slice_node["node_id"]}
    remove_placement_group(pg)


def test_strict_spread(cluster):
    pg = placement_group([{"CPU": 1}] * 3, strategy=STRICT_SPREAD)
    assert pg.wait(30)
    assert len(set(pg.table()["locations"])) == 3
    remove_placement_group(pg)


def test_infeasible_pg_rejected(cluster):
    with pytest.raises(ray_tpu.RayTpuError):
        placement_group([{"TPU": 100}], strategy=STRICT_PACK)


def test_actor_in_placement_group(cluster):
    pg = placement_group([{"CPU": 1, "TPU": 1}], strategy=PACK)
    assert pg.wait(30)
    a = NodeProbe.options(
        num_tpus=1,
        scheduling_strategy=PlacementGroupSchedulingStrategy(pg, 0)).remote()
    node = ray_tpu.get(a.node.remote(), timeout=60)
    assert bytes.fromhex(node) == pg.table()["locations"][0]
    remove_placement_group(pg)


def test_pg_resources_released_on_remove(cluster):
    # Settle: wait until releases from earlier tests have propagated so
    # `before` reflects the true free count, not a stale heartbeat.
    total = ray_tpu.cluster_resources().get("TPU", 0)
    deadline = time.time() + 30
    while (ray_tpu.available_resources().get("TPU", 0) < total
           and time.time() < deadline):
        time.sleep(0.3)
    before = ray_tpu.available_resources().get("TPU", 0)
    pg = placement_group([{"TPU": 2}], strategy=PACK)
    assert pg.wait(30)
    time.sleep(2.5)  # heartbeat propagation
    during = ray_tpu.available_resources().get("TPU", 0)
    assert during <= before - 2
    remove_placement_group(pg)
    deadline = time.time() + 15
    while time.time() < deadline:
        if ray_tpu.available_resources().get("TPU", 0) >= before:
            break
        time.sleep(0.3)
    assert ray_tpu.available_resources().get("TPU", 0) >= before


def test_tasks_run_on_remote_nodes(cluster):
    @ray_tpu.remote(num_cpus=0, resources={"TPU": 1})
    def where():
        import os
        return os.environ["RAY_TPU_NODE_ID"]

    # Driver's local raylet has no TPU: lease must spill to a TPU node.
    node = ray_tpu.get(where.remote(), timeout=60)
    tpu_nodes = {n["node_id"].hex() for n in ray_tpu.nodes() if n["resources"].get("TPU")}
    assert node in tpu_nodes


def test_node_death_restarts_actor_elsewhere(cluster):
    extra = cluster.add_node(num_cpus=1, resources={"victim": 1})
    cluster.wait_for_nodes(4)
    a = NodeProbe.options(resources={"victim": 0.5}, max_restarts=1).remote()
    first = ray_tpu.get(a.node.remote(), timeout=60)
    assert bytes.fromhex(first) == extra.node_id
    cluster.remove_node(extra, force=True)
    # GCS notices the dead node and tries restart; no node has "victim" left,
    # so the actor must end up DEAD (restart exhausted), not hang.
    deadline = time.time() + 60
    while time.time() < deadline:
        try:
            ray_tpu.get(a.node.remote(), timeout=10)
        except ray_tpu.ActorError:
            break
        except ray_tpu.GetTimeoutError:
            pass
        time.sleep(0.5)
    else:
        pytest.fail("actor on dead node neither restarted nor died")


def test_no_arena_outlives_its_raylet():
    """A killed raylet cannot unlink its arena, so whoever ends it does
    (node.stop_raylet): a force-removed node's segment is gone at once, a
    shut-down cluster leaves none, and the session's record names exactly
    the processes the session started."""
    import os

    from ray_tpu.runtime import node as node_mod

    c = Cluster()
    try:
        killed = c.add_node(num_cpus=1, object_store_memory=32 << 20)
        kept = c.add_node(num_cpus=1, object_store_memory=32 << 20)
        assert node_mod.session_pids(c.session_dir) == [
            ("gcs", c.gcs_proc.pid), ("raylet0", killed.proc.pid),
            ("raylet1", kept.proc.pid)]
        assert os.path.exists(killed.store_path)
        c.remove_node(killed, force=True)
        assert killed.proc.poll() is not None
        assert not os.path.exists(killed.store_path)
        assert os.path.exists(kept.store_path)
        # A killer's timer thread and `shutdown` may both get to one node:
        # the second removal finds nothing to do and does not raise.
        c.remove_node(killed, force=True)
        assert c.nodes == [kept]
    finally:
        c.shutdown()
    assert kept.proc.poll() is not None
    assert not os.path.exists(kept.store_path)
