"""Model step: device time a tick of the selective-scan kernel (the Mamba
layers' recurrence over the tick's ragged rows, ops/ssm_scan.py). Self time on
device 0's `XLA Ops` line of the operations whose name holds `ssm_scan` (the
jitted entry `ssm_scan_call`, which the kernel's HLO instruction is named
after) in the traced slice, over the ticks in the slice. Those events are NOT
in `paged_kernel_ms.tick`: they are not named `tpu_custom_call`. None where
the program has no such kernel (a model without state-space layers, an older
program)."""
from tick_phases import self_seconds, slice_ticks

SCAN_KERNEL = "ssm_scan"


def read(run):
    ticks = slice_ticks(run)
    seconds = self_seconds(run, lambda n: SCAN_KERNEL in n) if ticks else 0
    return 1e3 * seconds / len(ticks) if seconds else None
