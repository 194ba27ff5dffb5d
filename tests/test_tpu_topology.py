"""ICI topology placement + slice-atomic autoscaling.

Round-1 review item 9: STRICT_PACK must reserve a contiguous worker-id run
of ONE multi-host slice (never fragment across slices), and the autoscaler
must scale by whole slices. Reference analogs: detection design at
python/ray/_private/accelerators/tpu.py:70-116, bundle strategies
src/ray/protobuf/common.proto:978-985; the placement logic itself has no
reference implementation (SURVEY §7 hard part 3).
"""

import pytest

import ray_tpu
from ray_tpu.runtime import tpu_topology as topo


def test_pod_type_parsing():
    assert topo.parse_pod_type("v5e-32") == ("v5e", 32)
    assert topo.parse_pod_type("v5p-128") == ("v5p", 128)
    assert topo.parse_pod_type("nonsense") is None
    assert topo.hosts_in_slice("v5e-32") == 8
    assert topo.hosts_in_slice("v5e-4") == 1
    assert topo.chips_per_host("v5e-32") == 4


def test_find_contiguous_hosts_prefers_smallest_slice():
    def node(slice_name, wid, nid):
        return {"node_id": nid,
                "labels": topo.slice_labels(slice_name, "v5e-16", wid)}

    nodes = ([node("big", w, f"b{w}".encode()) for w in range(8)]
             + [node("small", w, f"s{w}".encode()) for w in range(4)])
    plan = topo.find_contiguous_hosts(nodes, 4, fits=lambda i, nid: True)
    assert plan is not None
    assert [nid for _, nid in plan] == [b"s0", b"s1", b"s2", b"s3"]


def test_find_contiguous_hosts_rejects_holes():
    def node(wid):
        return {"node_id": f"n{wid}".encode(),
                "labels": topo.slice_labels("s", "v5e-32", wid)}

    # Host 2 missing: runs are [0,1] and [3,4,5] — no contiguous 4-run.
    nodes = [node(w) for w in [0, 1, 3, 4, 5]]
    assert topo.find_contiguous_hosts(nodes, 4, fits=lambda i, n: True) is None
    assert topo.find_contiguous_hosts(nodes, 3, fits=lambda i, n: True) == [
        (0, b"n3"), (1, b"n4"), (2, b"n5")]


@pytest.mark.slow  # >60s measured: full-tier only
def test_strict_pack_lands_on_one_slice():
    """4-host {TPU:4} bundles on a cluster with one intact 4-host slice, one
    2-host slice, and loose TPU nodes: placed exactly on the intact slice."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=2)  # head, no TPU
        slice_nodes = {}
        for wid in range(4):
            n = cluster.add_node(
                num_cpus=1, num_tpus=4,
                labels=topo.slice_labels("sliceA", "v5e-16", wid))
            slice_nodes[n.node_id.hex() if hasattr(n, "node_id")
                        else bytes(n.info["node_id"], "ascii")] = wid
        for wid in range(2):
            cluster.add_node(num_cpus=1, num_tpus=4,
                             labels=topo.slice_labels("sliceB", "v5e-8", wid))
        cluster.add_node(num_cpus=1, num_tpus=4)  # loose TPU host
        ray_tpu.init(address=cluster.address)

        from ray_tpu.core.placement_group import placement_group

        pg = placement_group([{"TPU": 4}] * 4, strategy="STRICT_PACK")
        assert pg.wait(timeout_seconds=60)
        table = pg.table()
        locations = table["locations"]
        assert all(loc is not None for loc in locations)
        # All four bundles on sliceA hosts (the only contiguous 4-run).
        info = {bytes.fromhex(n["node_id"]) if isinstance(n["node_id"], str)
                else n["node_id"]: n["labels"]
                for n in ray_tpu.nodes()}
        names = {info[loc].get("tpu-slice-name") for loc in locations}
        assert names == {"sliceA"}, names
        # Distinct hosts, contiguous worker ids aligned with bundle order.
        wids = [int(info[loc]["tpu-worker-id"]) for loc in locations]
        assert wids == sorted(wids) and wids == list(
            range(wids[0], wids[0] + 4))
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()


@pytest.mark.slow  # >60s measured: full-tier only
def test_strict_pack_rejects_fragmented_slices():
    """Only 2+2 hosts across two slices: a 4-bundle STRICT_PACK group must
    NOT be created (fragmenting would put DCN inside the job's ICI mesh)."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=2)
        for wid in range(2):
            cluster.add_node(num_cpus=1, num_tpus=4,
                             labels=topo.slice_labels("x", "v5e-8", wid))
        for wid in range(2):
            cluster.add_node(num_cpus=1, num_tpus=4,
                             labels=topo.slice_labels("y", "v5e-8", wid))
        ray_tpu.init(address=cluster.address)

        from ray_tpu.core.exceptions import PlacementGroupError
        from ray_tpu.core.placement_group import placement_group

        with pytest.raises(PlacementGroupError, match="infeasible"):
            placement_group([{"TPU": 4}] * 4, strategy="STRICT_PACK")
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def test_autoscaler_launches_whole_slice():
    """Demand for a 4-host TPU group launches one atomic v5e-16 slice whose
    hosts share a slice name with worker ids 0..3; idle teardown removes the
    whole slice together."""
    from ray_tpu.autoscaler.autoscaler import (Autoscaler,
                                               FakeMultiNodeProvider,
                                               InstanceType)
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=2)  # head
        ray_tpu.init(address=cluster.address)
        provider = FakeMultiNodeProvider(cluster)
        t = InstanceType.for_pod_type("v5e-16", "v5e-16", cpus_per_host=1)
        assert t.hosts == 4 and t.resources["TPU"] == 4.0
        scaler = Autoscaler(provider, [t], idle_timeout_s=1.0,
                            max_workers=8, boot_grace_s=60.0)
        r = scaler.reconcile(demand=[{"TPU": 4.0}] * 4)
        assert r["launched"] == 4  # one slice = four host instances
        # All four share one slice name, ids 0..3.
        import time

        deadline = time.time() + 30
        while time.time() < deadline:
            tpu_nodes = [n for n in ray_tpu.nodes()
                         if n["labels"].get("tpu-slice-name")]
            if len(tpu_nodes) == 4 and all(n["alive"] for n in tpu_nodes):
                break
            time.sleep(0.5)
        names = {n["labels"]["tpu-slice-name"] for n in tpu_nodes}
        assert len(names) == 1
        wids = sorted(int(n["labels"]["tpu-worker-id"]) for n in tpu_nodes)
        assert wids == [0, 1, 2, 3]
        # Booting capacity suppresses relaunch for the same demand.
        r2 = scaler.reconcile(demand=[{"TPU": 4.0}] * 4)
        assert r2["launched"] == 0
        # Idle: the whole slice terminates atomically.
        deadline = time.time() + 30
        while time.time() < deadline:
            r3 = scaler.reconcile(demand=[])
            if r3["terminated"]:
                break
            time.sleep(0.5)
        assert r3["terminated"] == 4
        assert not scaler.instances
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()
