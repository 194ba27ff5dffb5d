"""Train backends: how a worker group becomes a distributed compute group.

Reference analog: train/torch/config.py:36,153 (_TorchBackend wiring
init_process_group over NCCL) and backend_executor's rank/env plumbing
(:278-456). TPU-native:

  * JaxBackend — multi-host jax.distributed bootstrap (coordinator address
    rendezvoused through the GCS KV). After on_start, `jax.devices()` spans
    the whole worker group and pjit/shard_map programs run collectives over
    ICI/DCN. This is the FSDP/TP/SP path.
  * CollectiveBackend — out-of-graph gradient sync via the TCP communicator
    (gloo analog). This is the CPU-testable DDP path: each worker computes
    grads locally and allreduces host arrays.
"""

from __future__ import annotations

from typing import Dict, Optional


class Backend:
    backend_name = "base"

    def on_start(self, rank: int, world_size: int, group_name: str):
        """Runs INSIDE each train worker before the user function."""

    def on_shutdown(self, rank: int, world_size: int, group_name: str):
        pass


class JaxBackend(Backend):
    """jax.distributed across the worker group (the NCCL-process-group
    replacement). Workers must each own their TPU chips. Nothing enforces
    that yet: a lease counts chips but does not set TPU_VISIBLE_CHIPS
    (runtime/resources.py visible_chip_env has no caller), so two such
    workers on one host contend for the same chips."""

    backend_name = "jax"

    def on_start(self, rank: int, world_size: int, group_name: str):
        from ray_tpu.collective.collective import _gcs_kv
        from ray_tpu.collective.jax_backend import initialize_jax_distributed

        kv_put, kv_get = _gcs_kv()
        initialize_jax_distributed(rank, world_size, group_name, kv_put, kv_get)


class CollectiveBackend(Backend):
    """TCP collective group for out-of-graph DDP gradient sync."""

    backend_name = "collective"

    def __init__(self):
        self.comm = None

    def on_start(self, rank: int, world_size: int, group_name: str):
        from ray_tpu.collective.collective import init_collective_group

        global _active_group
        self.comm = init_collective_group(world_size, rank, backend="tcp",
                                          group_name=group_name)
        _active_group = group_name

    def on_shutdown(self, rank: int, world_size: int, group_name: str):
        from ray_tpu.collective.collective import destroy_collective_group

        try:
            destroy_collective_group(group_name)
        except Exception:
            pass
        self.comm = None


BACKENDS = {"jax": JaxBackend, "collective": CollectiveBackend, "none": Backend}

# The collective group name of the currently-running train job in this
# worker process (set by setup_backend; used by allreduce_gradients).
_active_group: Optional[str] = None


def make_backend(name_or_backend) -> Backend:
    if isinstance(name_or_backend, Backend):
        return name_or_backend
    return BACKENDS[name_or_backend or "none"]()


def reduce_gradients(comm, grads, bucket_bytes: Optional[int] = None):
    """Bucketed overlapped mean-allreduce of a gradient pytree over `comm`.

    Reference analog: torch DDP's gradient-bucketing Reducer. Leaves are
    grouped by dtype (never concatenated across dtypes — a mixed f32/f64
    tree reduces each dtype natively instead of silently upcasting the
    whole buffer) and coalesced into flat buckets of ~`bucket_bytes`
    (cfg().ddp_bucket_bytes default). Each bucket's allreduce is launched
    asynchronously THE MOMENT the bucket fills, so the wire reduction of
    early buckets overlaps the flatten/copy work of later ones, and the
    per-group FIFO op thread pipelines the buckets back to back. Handles
    are then waited in launch order and leaves scattered back in their
    original tree positions and dtypes.
    """
    import jax
    import numpy as np

    from ray_tpu.config import cfg

    if bucket_bytes is None:
        bucket_bytes = cfg().ddp_bucket_bytes
    bucket_bytes = max(1, int(bucket_bytes))

    leaves, treedef = jax.tree.flatten(grads)
    arrs = [np.asarray(l) for l in leaves]
    out: list = [None] * len(leaves)

    # dtype -> list of (leaf index, flat view) accumulating the open bucket
    open_buckets: Dict[str, list] = {}
    open_bytes: Dict[str, int] = {}
    launched: list = []  # (Work, dtype, [(leaf idx, shape, size), ...])

    def _flush(dt: str):
        entries = open_buckets.pop(dt, None)
        open_bytes.pop(dt, None)
        if not entries:
            return
        flat = np.concatenate([v for _, v in entries]) if len(entries) > 1 \
            else np.ascontiguousarray(entries[0][1])
        meta = [(i, arrs[i].shape, arrs[i].size) for i, _ in entries]
        launched.append((comm.allreduce_async(flat, op="mean"), dt, meta))

    for i, a in enumerate(arrs):
        dt = a.dtype.str
        open_buckets.setdefault(dt, []).append((i, a.ravel()))
        open_bytes[dt] = open_bytes.get(dt, 0) + a.nbytes
        if open_bytes[dt] >= bucket_bytes:
            _flush(dt)
    for dt in list(open_buckets):
        _flush(dt)

    for work, dt, meta in launched:
        reduced = np.asarray(work.wait())
        if reduced.dtype.str != dt:  # integer mean comes back float64
            reduced = reduced.astype(np.dtype(dt))
        offset = 0
        for i, shape, size in meta:
            out[i] = reduced[offset:offset + size].reshape(shape)
            offset += size
    return jax.tree.unflatten(treedef, out)


def allreduce_gradients(grads, group_name: Optional[str] = None,
                        bucket_bytes: Optional[int] = None):
    """DDP helper: mean-allreduce a pytree of host/jax arrays over the
    worker group's collective backend (reference: the NCCL allreduce inside
    DDP's backward). Use inside train loops running the CollectiveBackend.
    Gradients are coalesced into per-dtype buckets whose ring allreduces
    launch as each bucket fills (see reduce_gradients). Inside a train
    worker the whole sync is booked to the step's "collective" phase
    (train/telemetry.py straggler attribution); outside one, the phase
    wrapper is a no-op."""
    from ray_tpu.collective.collective import get_group
    from ray_tpu.train.session import step_phase

    comm = get_group(group_name or _active_group or "default")
    with step_phase("collective"):
        return reduce_gradients(comm, grads, bucket_bytes=bucket_bytes)
