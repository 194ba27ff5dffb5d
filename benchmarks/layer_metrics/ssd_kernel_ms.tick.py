"""Model step: device time a tick of the Mamba-2 (SSD) kernel (the recurrent
step of the tick's decode rows and the chunked form of its prompt slice, one
call an `M` layer, ops/ssd.py). Self time on device 0's `XLA Ops` line of the
operations whose name holds `ssd_call` (the jitted entry, which the kernel's
HLO instruction is named after) in the traced slice, over the ticks in the
slice. Those events are NOT in `paged_kernel_ms.tick`, which in a cell of this
family holds the one attention layer's K/V kernel alone: they are not named
`tpu_custom_call`. NOT in it either: what XLA lays around a call (the
convolution, the rows packed for the kernel, the gated norm). None where the
program has no such kernel (a model without Mamba-2 layers, an older
program)."""
from tick_phases import self_seconds, slice_ticks

SSD_KERNEL = "ssd_call"


def read(run):
    ticks = slice_ticks(run)
    seconds = (self_seconds(run, lambda n: SSD_KERNEL in n) if ticks else 0)
    return 1e3 * seconds / len(ticks) if seconds else None
