"""GCE Cloud-TPU queued-resource provider against the recorded-API fake.

Reference analog: python/ray/autoscaler/_private/gcp/node_provider.py e2e
via recorded API; slice-granular contract per _private/accelerators/
tpu.py:23-67 (pod metadata -> worker identity/labels).
"""

import time

import pytest

import ray_tpu
from ray_tpu.autoscaler.autoscaler import Autoscaler, InstanceType
from ray_tpu.autoscaler.gce import GceTpuQueuedProvider, start_gce_fake
from ray_tpu.cluster_utils import Cluster


@pytest.fixture
def gce_fake():
    server, url, state = start_gce_fake()
    yield url, state
    server.shutdown()


def test_provider_launch_is_one_create_per_slice(gce_fake):
    url, state = gce_fake
    p = GceTpuQueuedProvider("proj", "us-central2-b", base_url=url)
    t = InstanceType.for_pod_type("v5e-16", "v5e-16", cpus_per_host=1)
    ids = p.launch_slice(t)
    assert len(ids) == 4  # 4 hosts x 4 chips
    creates = [r for r in state.requests if r["method"] == "POST"]
    assert len(creates) == 1, "whole-slice create must be ONE API call"
    body = creates[0]["body"]
    spec = body["tpu"]["nodeSpec"][0]
    assert spec["node"]["acceleratorType"] == "v5e-16"
    assert "queued_resource_id=" in creates[0]["path"]
    # All four worker ids share one queued resource.
    assert len({i.split("/")[0] for i in ids}) == 1
    assert sorted(i.split("worker-")[1] for i in ids) == ["0", "1", "2", "3"]


def test_provider_terminate_is_one_delete_per_slice(gce_fake):
    url, state = gce_fake
    p = GceTpuQueuedProvider("proj", "us-central2-b", base_url=url)
    t = InstanceType.for_pod_type("v5e-16", "v5e-16", cpus_per_host=1)
    ids = p.launch_slice(t)
    assert len(p.non_terminated()) == 4
    for iid in ids:  # reconciler terminates every sibling: still 1 DELETE
        p.terminate(iid)
    deletes = [r for r in state.requests if r["method"] == "DELETE"]
    assert len(deletes) == 1, "slice drain must be ONE delete"
    assert p.non_terminated() == []


def test_provider_rejects_per_chip_launch(gce_fake):
    url, _ = gce_fake
    p = GceTpuQueuedProvider("proj", "us-central2-b", base_url=url)
    with pytest.raises(ValueError, match="slice"):
        p.launch(InstanceType.for_pod_type("v5e-16", "v5e-16"))
    with pytest.raises(ValueError, match="TPU"):
        p.launch_slice(InstanceType("cpu", {"CPU": 4.0}))


def test_autoscaler_e2e_acquires_and_drains_v5e16(gce_fake):
    """The end-to-end case: TPU demand -> autoscaler acquires a fake v5e-16
    slice through the recorded API (nodes register with ICI labels derived
    from pod metadata), idle -> the whole slice drains atomically."""
    url, state = gce_fake
    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=2)  # head
        ray_tpu.init(address=cluster.address)
        provider = GceTpuQueuedProvider("proj", "us-central2-b",
                                        base_url=url, cluster=cluster)
        t = InstanceType.for_pod_type("v5e-16", "v5e-16", cpus_per_host=1)
        scaler = Autoscaler(provider, [t], idle_timeout_s=1.0,
                            max_workers=8, boot_grace_s=60.0)
        r = scaler.reconcile(demand=[{"TPU": 4.0}] * 4)
        assert r["launched"] == 4  # one slice = four host instances
        creates = [q for q in state.requests if q["method"] == "POST"]
        assert len(creates) == 1

        deadline = time.time() + 30
        tpu_nodes = []
        while time.time() < deadline:
            scaler.reconcile(demand=[{"TPU": 4.0}] * 4)
            tpu_nodes = [n for n in ray_tpu.nodes()
                         if n["labels"].get("tpu-slice-name")]
            if len(tpu_nodes) == 4 and all(n["alive"] for n in tpu_nodes):
                break
            time.sleep(0.5)
        assert len(tpu_nodes) == 4
        # Labels derived from the queued resource: one slice name (the
        # qr id), pod type from acceleratorType, worker ids 0..3.
        names = {n["labels"]["tpu-slice-name"] for n in tpu_nodes}
        assert len(names) == 1 and names.pop().startswith("ray-tpu-")
        assert {n["labels"]["tpu-pod-type"] for n in tpu_nodes} == {"v5e-16"}
        wids = sorted(int(n["labels"]["tpu-worker-id"]) for n in tpu_nodes)
        assert wids == [0, 1, 2, 3]
        # Booting/registered capacity suppresses relaunch.
        assert scaler.reconcile(demand=[{"TPU": 4.0}] * 4)["launched"] == 0
        assert len([q for q in state.requests
                    if q["method"] == "POST"]) == 1

        # Idle: whole slice drains atomically, as ONE api delete.
        deadline = time.time() + 30
        r3 = {}
        while time.time() < deadline:
            r3 = scaler.reconcile(demand=[])
            if r3.get("terminated"):
                break
            time.sleep(0.5)
        assert r3.get("terminated") == 4
        deletes = [q for q in state.requests if q["method"] == "DELETE"]
        assert len(deletes) == 1
        assert not scaler.instances
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()


def test_capacity_starvation_reaps_after_boot_grace(gce_fake):
    """A queued resource stuck WAITING_FOR_RESOURCES past boot grace is
    reaped (one delete) so a replacement can be requested elsewhere."""
    url, state = gce_fake
    state.deny_capacity = 1
    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=2)
        ray_tpu.init(address=cluster.address)
        provider = GceTpuQueuedProvider("proj", "us-central2-b",
                                        base_url=url, cluster=cluster)
        t = InstanceType.for_pod_type("v5e-16", "v5e-16", cpus_per_host=1)
        scaler = Autoscaler(provider, [t], idle_timeout_s=1.0,
                            max_workers=8, boot_grace_s=0.5)
        assert scaler.reconcile(demand=[{"TPU": 4.0}] * 4)["launched"] == 4
        time.sleep(0.6)
        scaler.reconcile(demand=[{"TPU": 4.0}] * 4)
        deletes = [q for q in state.requests if q["method"] == "DELETE"]
        assert len(deletes) == 1, "starved slice reaped with one delete"
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()
