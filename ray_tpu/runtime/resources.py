"""Node resource detection, TPU-first.

Reference analog: python/ray/_private/accelerators/tpu.py:70
TPUAcceleratorManager (chip detection via /dev/accel* | /dev/vfio/*, pod-type
metadata, TPU_VISIBLE_CHIPS isolation) generalized into this framework's
first-class resource model: a node advertises {"CPU", "memory", "TPU", ...}
plus labels ("tpu-pod-type", "tpu-slice", "tpu-worker-id") that the
scheduler/placement-group code uses for ICI-contiguous placement.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Optional, Tuple


_GOOGLE_PCI_VENDOR = "0x1ae0"


def detect_tpu_chips() -> int:
    """Count local TPU chips. Test/override hook: RAY_TPU_FAKE_TPU_CHIPS."""
    fake = os.environ.get("RAY_TPU_FAKE_TPU_CHIPS")
    if fake:
        return int(fake)
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return len([c for c in visible.split(",") if c.strip() != ""])
    accel = glob.glob("/dev/accel*")
    if accel:
        return len(accel)
    # A v5e host exposes its chips as /dev/vfio/<iommu group>. vfio nodes are
    # NOT TPU-specific (GPU passthrough binds vfio-pci too): count a group
    # when the environment says this host is part of a TPU pod/slice, or
    # when the group holds a Google PCI device.
    groups = [os.path.basename(p) for p in glob.glob("/dev/vfio/[0-9]*")]
    if detect_tpu_pod_type():
        return len(groups)
    return sum(1 for g in groups if _GOOGLE_PCI_VENDOR in _group_vendors(g))


def _group_vendors(group: str) -> set:
    vendors = set()
    for path in glob.glob(f"/sys/kernel/iommu_groups/{group}/devices/*/vendor"):
        try:
            with open(path) as f:
                vendors.add(f.read().strip())
        except OSError:
            pass
    return vendors


def detect_tpu_pod_type() -> Optional[str]:
    """Pod/slice type, e.g. "v5e-8". From env (GCE metadata requires egress;
    deployments set TPU_POD_TYPE / TPU_ACCELERATOR_TYPE)."""
    return os.environ.get("TPU_POD_TYPE") or os.environ.get("TPU_ACCELERATOR_TYPE")


def tpu_slice_labels() -> Dict[str, str]:
    labels: Dict[str, str] = {}
    pod = detect_tpu_pod_type()
    if pod:
        labels["tpu-pod-type"] = pod
        worker_id = os.environ.get("TPU_WORKER_ID", "0")
        labels["tpu-worker-id"] = worker_id
        # A host that owns all chips of a single-host slice advertises the
        # slice as intact: STRICT_PACK bundles prefer such nodes so a
        # bundle-per-chip group gets contiguous ICI.
        labels["tpu-slice"] = f"{pod}-{os.environ.get('TPU_NAME', 'local')}-{worker_id}"
    return labels


def node_resources(num_cpus: Optional[float] = None,
                   num_tpus: Optional[float] = None,
                   memory: Optional[int] = None,
                   resources: Optional[Dict[str, float]] = None) -> Dict[str, float]:
    out: Dict[str, float] = {}
    if num_cpus is None:
        num_cpus = float(os.cpu_count() or 1)
    out["CPU"] = float(num_cpus)
    if num_tpus is None:
        num_tpus = float(detect_tpu_chips())
    if num_tpus:
        out["TPU"] = float(num_tpus)
        pod = detect_tpu_pod_type()
        if pod:
            # Headline resource for slice-head scheduling, mirroring the
            # reference's "TPU-{pod_type}-head" custom resource.
            out[f"TPU-{pod}-head"] = 1.0
    if memory is None:
        try:
            with open("/proc/meminfo") as f:
                for line in f:
                    if line.startswith("MemTotal:"):
                        memory = int(line.split()[1]) * 1024
                        break
        except OSError:
            memory = 0
    if memory:
        out["memory"] = float(memory)
    # Non-TPU accelerator families via the manager registry (GPU, plugins):
    # TPU stays first-class above; others contribute when present.
    from ray_tpu.runtime import accelerators as accel_mod

    for name, n in accel_mod.detect_accelerators().items():
        if name != "TPU" and name not in out:
            out[name] = n
    for k, v in (resources or {}).items():
        out[k] = float(v)
    return out


def visible_chip_env(chip_ids: Tuple[int, ...]) -> Dict[str, str]:
    """Env vars that confine a worker to specific chips (TPU_VISIBLE_CHIPS
    isolation, reference tpu.py set_current_process_visible_accelerator_ids).

    NOT wired in yet: the raylet hands every pool worker its whole
    environment, and a lease of N TPUs only counts — nothing confines the
    worker to N chips. On a one-chip host that is enough as long as exactly
    one worker touches JAX (chip_smoke.py's cluster phase shows it); several
    TPU workers on one multi-chip host need this applied at worker start
    (ROADMAP S7)."""
    ids = ",".join(str(c) for c in chip_ids)
    return {
        "TPU_VISIBLE_CHIPS": ids,
        "TPU_CHIPS_PER_PROCESS_BOUNDS": f"1,{len(chip_ids)},1",
    }
