"""Model step: device time a tick of the Kimi Delta Attention kernel (the
recurrent step of the tick's decode rows and the chunked form of its prompt
slice, one call a KDA layer, ops/kda.py). Self time on device 0's `XLA Ops`
line of the operations whose name holds `kda_call` (the jitted entry, which
the kernel's HLO instruction is named after) in the traced slice, over the
ticks in the slice. Those events are NOT in `paged_kernel_ms.tick`, which in
a cell of this family holds the latent kernel alone: they are not named
`tpu_custom_call`. NOT in it either: the gather and the transpose by which
`ops/kda.py`'s wrapper lays the kernel's planes a layer (XLA fusions named
`fusion.<n>` / `transpose.<n>`, 2.09 + 1.49 ms a tick in this PR's traced
run: PERF.md section 3), so time moved between wrapper and kernel moves this
number and not the tick. None where the program has no such kernel (a model
without KDA layers, an older program)."""
from tick_phases import self_seconds, slice_ticks

KDA_KERNEL = "kda_call"


def read(run):
    ticks = slice_ticks(run)
    seconds = (self_seconds(run, lambda n: KDA_KERNEL in n) if ticks else 0)
    return 1e3 * seconds / len(ticks) if seconds else None
