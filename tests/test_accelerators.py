"""Accelerator manager registry + wire-protocol guard.

Reference analogs: python/ray/_private/accelerators/ (per-vendor managers)
and the protobuf IDL's versioned wire contract.
"""

import asyncio

import pytest

import ray_tpu
from ray_tpu.runtime import accelerators


def test_tpu_manager_uses_fake_chips(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FAKE_TPU_CHIPS", "4")
    assert accelerators.TPUAcceleratorManager.detect_count() == 4
    assert accelerators.detect_accelerators().get("TPU") == 4.0


# What hosts show, and what the node must advertise. The v5e machine of
# chip_smoke.py is the first row: one /dev/vfio/<iommu group> node and
# TPU_ACCELERATOR_TYPE set. vfio alone is not a TPU (GPU passthrough binds
# vfio-pci too): without the variable a group counts only when it holds a
# Google PCI device.
@pytest.mark.parametrize("dev_nodes, pod_type, vendors, chips", [
    (["/dev/vfio/3", "/dev/vfio/vfio"], "v5litepod-4", {}, 1),
    (["/dev/vfio/0", "/dev/vfio/1", "/dev/vfio/vfio"], None,
     {"0": "0x1ae0", "1": "0x1ae0"}, 2),
    (["/dev/vfio/7", "/dev/vfio/vfio"], None, {"7": "0x10de"}, 0),
    (["/dev/accel0", "/dev/accel1", "/dev/accel2", "/dev/accel3"], None,
     {}, 4),
    ([], "v5litepod-4", {}, 0),
])
def test_tpu_chip_detection(monkeypatch, dev_nodes, pod_type, vendors, chips):
    import fnmatch

    from ray_tpu.runtime import resources

    for var in ("RAY_TPU_FAKE_TPU_CHIPS", "TPU_VISIBLE_CHIPS", "TPU_POD_TYPE",
                "TPU_ACCELERATOR_TYPE"):
        monkeypatch.delenv(var, raising=False)
    if pod_type:
        monkeypatch.setenv("TPU_ACCELERATOR_TYPE", pod_type)
    monkeypatch.setattr(
        resources.glob, "glob",
        lambda pattern: [n for n in dev_nodes if fnmatch.fnmatch(n, pattern)])
    monkeypatch.setattr(
        resources, "_group_vendors",
        lambda group: {vendors[group]} if group in vendors else set())
    assert resources.detect_tpu_chips() == chips
    assert resources.node_resources(num_cpus=1).get("TPU", 0.0) == chips


def test_gpu_manager_detection(monkeypatch):
    monkeypatch.setenv("RAY_TPU_FAKE_GPUS", "2")
    assert accelerators.NvidiaGPUAcceleratorManager.detect_count() == 2
    env = accelerators.NvidiaGPUAcceleratorManager.visibility_env((0, 1))
    assert env == {"CUDA_VISIBLE_DEVICES": "0,1"}


def test_gpu_resource_flows_into_node_resources(monkeypatch):
    from ray_tpu.runtime.resources import node_resources

    monkeypatch.setenv("RAY_TPU_FAKE_GPUS", "3")
    monkeypatch.setenv("RAY_TPU_FAKE_TPU_CHIPS", "0")
    res = node_resources(num_cpus=2)
    assert res["GPU"] == 3.0 and res["CPU"] == 2.0


def test_custom_manager_registration():
    class NPUManager(accelerators.AcceleratorManager):
        resource_name = "NPU"

        @staticmethod
        def detect_count():
            return 1

    accelerators.register(NPUManager)
    try:
        assert accelerators.detect_accelerators().get("NPU") == 1.0
    finally:
        accelerators._MANAGERS.remove(NPUManager)


def test_wire_protocol_rejects_foreign_bytes():
    """A non-ray_tpu client (wrong magic) is dropped before any pickle
    runs; a version-skewed peer gets a versioned error. Runs with auth OFF
    (a prior test's cluster may have left a session token in the process);
    the authed handshake path is covered by test_wire_auth.py."""
    from ray_tpu.runtime import rpc
    from ray_tpu.runtime.rpc import (
        _MAGIC, _frame, _read_frame, ProtocolMismatch, RpcServer)

    rpc.set_session_token(None)

    def _restore():
        rpc._token_loaded = False  # later tests reload from env

    async def run():
        server = RpcServer("127.0.0.1", 0)

        async def handle_ping(conn):
            return {"ok": True}

        server.register("ping", handle_ping)
        await server.start()
        host, port = server.address

        # Garbage magic: the server answers one version-bearing frame (so a
        # skewed ray_tpu peer can self-diagnose) and drops the connection.
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"GET / HTTP/1.1\r\nHost: x\r\n\r\n")
        await writer.drain()
        data = await asyncio.wait_for(reader.read(4096), timeout=10)
        assert data[:4] == _MAGIC
        tail = await asyncio.wait_for(reader.read(64), timeout=10)
        assert tail == b""  # then: closed
        writer.close()

        # Direct decode check: version-skewed frame diagnoses the versions.
        frame = _frame((0, 1, "ping", {}))
        skewed = b"RTP\x63" + frame[4:]
        r = asyncio.StreamReader()
        r.feed_data(skewed)
        r.feed_eof()
        with pytest.raises(ProtocolMismatch, match="v99"):
            await _read_frame(r)

        # Well-formed frame round-trips.
        r = asyncio.StreamReader()
        r.feed_data(frame)
        r.feed_eof()
        assert await _read_frame(r) == (0, 1, "ping", {})
        assert frame[:4] == _MAGIC
        await server.close()

    try:
        asyncio.run(run())
    finally:
        _restore()
