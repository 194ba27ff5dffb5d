"""Model step: device time a tick of the paged kernel's WINDOW form (the
window layers' calls). Self time on device 0's `XLA Ops` line of the
operations whose name holds `paged_attention_window` (the jitted entry
`paged_attention_window_call`, which the kernel's HLO instruction is named
after) in the traced slice, over the ticks in the slice. Beside
`paged_kernel_ms.tick`, which counts both forms: a window layer that walked
its whole context would read about five times that; one that skips the pages
behind the window a small fraction of it. None where the program has no such
kernel (a model without window layers, an older program)."""
from tick_phases import self_seconds, slice_ticks

WINDOW_KERNEL = "paged_attention_window"


def read(run):
    ticks = slice_ticks(run)
    seconds = self_seconds(run, lambda n: WINDOW_KERNEL in n) if ticks else 0
    return 1e3 * seconds / len(ticks) if seconds else None
