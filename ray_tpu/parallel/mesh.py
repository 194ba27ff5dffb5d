"""Device mesh construction and axis conventions.

The TPU-native parallelism substrate (SURVEY §2.4): one `jax.sharding.Mesh`
whose named axes carry every strategy the reference ships or outsources —

  axis   | strategy                          | reference analog
  -------+-----------------------------------+---------------------------------
  dp     | data parallel (pure replication)  | Train DDP (torch/config.py:153)
  fsdp   | data parallel + param sharding    | FSDP wrap (train_loop_utils.py:188)
  tp     | tensor parallel                   | vLLM Megatron TP (vllm_models.py:117)
  sp     | sequence/context parallel         | absent in reference (vLLM-internal)
  ep     | expert parallel                   | absent in reference

Pipeline parallelism is deliberately NOT a mesh axis: it is actor-to-actor
(compiled-graph style, see ray_tpu/parallel/pipeline.py), matching the
reference's substrate (compiled_dag_node.py) and the MPMD design in PAPERS.md.

Axis order is outer-to-inner by communication intensity: tp (most chatty)
innermost so it maps to the fastest ICI dimension; dp outermost so its
gradient reductions ride the slowest links. `jax.experimental.mesh_utils`
arranges physical devices so inner mesh axes land on adjacent chips.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

AXES = ("dp", "fsdp", "sp", "ep", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    dp: int = 1
    fsdp: int = 1
    sp: int = 1
    ep: int = 1
    tp: int = 1

    @property
    def num_devices(self) -> int:
        return self.dp * self.fsdp * self.sp * self.ep * self.tp

    def axis_sizes(self) -> Tuple[int, ...]:
        return (self.dp, self.fsdp, self.sp, self.ep, self.tp)

    @staticmethod
    def auto(num_devices: int, *, tp: int = 1, sp: int = 1, ep: int = 1,
             dp: Optional[int] = None) -> "MeshConfig":
        """Fill the fsdp axis with whatever tp/sp/ep/dp leave over."""
        used = tp * sp * ep * (dp or 1)
        if num_devices % used != 0:
            raise ValueError(f"{num_devices} devices not divisible by tp*sp*ep*dp={used}")
        return MeshConfig(dp=dp or 1, fsdp=num_devices // used, sp=sp, ep=ep, tp=tp)


def build_mesh(config: MeshConfig, devices: Optional[Sequence] = None):
    """Create the named Mesh. Uses mesh_utils for ICI-friendly layout when
    building over the full device set."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if config.num_devices != n:
        raise ValueError(
            f"mesh config wants {config.num_devices} devices, have {n}")
    shape = config.axis_sizes()
    if list(devices) == list(jax.devices()):
        from jax.experimental import mesh_utils

        # Raises where the shape cannot be laid onto the physical topology:
        # a plain reshape there would put chatty axes on distant chips.
        dev_array = mesh_utils.create_device_mesh(shape, devices)
    else:
        dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, AXES)


_CURRENT_MESH = None
_CURRENT_RULES = None


class use_mesh:
    """Context manager installing `mesh` (and optionally the active
    logical-axis `rules`) as ambient state. Model code uses it for explicit
    shard_map (ring attention) and activation sharding constraints
    (sharding.constrain)."""

    def __init__(self, mesh, rules=None):
        self.mesh = mesh
        self.rules = rules
        self._prev = None

    def __enter__(self):
        global _CURRENT_MESH, _CURRENT_RULES
        self._prev = (_CURRENT_MESH, _CURRENT_RULES)
        _CURRENT_MESH = self.mesh
        if self.rules is not None:
            _CURRENT_RULES = self.rules
        return self.mesh

    def __exit__(self, *exc):
        global _CURRENT_MESH, _CURRENT_RULES
        _CURRENT_MESH, _CURRENT_RULES = self._prev
        return False


def current_mesh():
    return _CURRENT_MESH


def current_rules():
    return _CURRENT_RULES


def single_device_mesh():
    import jax

    return build_mesh(MeshConfig(), devices=jax.devices()[:1])


def batch_axes() -> Tuple[str, ...]:
    """Mesh axes the global batch is sharded over."""
    return ("dp", "fsdp", "sp", "ep")


def data_parallel_size(mesh) -> int:
    return math.prod(mesh.shape[a] for a in batch_axes())
