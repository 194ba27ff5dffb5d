"""Model step: device time a tick of the power-retention kernel (the
recurrent step of the tick's decode rows and the chunked form of its prompt
slice, one call a layer, ops/power_retention.py). Self time on device 0's
`XLA Ops` line of the operations whose name holds `power_retention` (the
jitted entry `power_retention_call`, which the kernel's HLO instruction is
named after) in the traced slice, over the ticks in the slice. Those events
are NOT in `paged_kernel_ms.tick`: they are not named `tpu_custom_call`. None
where the program has no such kernel (a model without retention layers, an
older program)."""
from tick_phases import self_seconds, slice_ticks

RETENTION_KERNEL = "power_retention"


def read(run):
    ticks = slice_ticks(run)
    seconds = (self_seconds(run, lambda n: RETENTION_KERNEL in n)
               if ticks else 0)
    return 1e3 * seconds / len(ticks) if seconds else None
