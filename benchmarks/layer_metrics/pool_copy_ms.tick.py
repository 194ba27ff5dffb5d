"""Model step: device time a tick of the plain copies in the step program,
which are the re-layouts of the whole K and V pool going into and coming out
of the layer scan (PERF.md section 5). Self time on device 0's `XLA Ops`
line of names `copy.<n>` (not `copy-start` / `copy-done`, not
`copy_bitcast_fusion`) in the traced slice, over the ticks in the slice."""
from tick_phases import is_pool_copy, ms_per_slice_tick


def read(run):
    return ms_per_slice_tick(run, is_pool_copy)
