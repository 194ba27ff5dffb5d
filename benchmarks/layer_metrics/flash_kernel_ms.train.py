"""Train step: device time of the flash-attention kernels a step. Self time
on device 0's `XLA Ops` line of the Pallas custom calls over the traced steps.
The train step's only Pallas kernels are those of `ops/attention.py` (the
train cell's note `pallas_kernels_in_step` counts them in
`compiled.as_text()`: forward, the forward again under remat, dQ and dK/dV).
They run inside shard_map, so a v5e profile of the benchmark's program calls
them `shard_map.<n>`, not `tpu_custom_call.<n>` (looked at by hand, PR 26:
`shard_map.304` and `.307` flash_fwd, `.305` flash_bwd_dkv_resident, `.306`
flash_bwd_dq_resident, by their `kernel_metadata`); see `tick_phases`."""
from tick_phases import FLASH_KERNELS, is_custom_call, ms_per_traced_step


def read(run):
    return ms_per_traced_step(run, lambda n: is_custom_call(n, FLASH_KERNELS))
