"""Model step: device time of the paged-attention kernel a tick. Self time
on device 0's `XLA Ops` line of the Pallas custom calls in the traced slice
(`tpu_custom_call.<n>` in the benchmark's program, see `tick_phases`), over
the ticks whose middle falls in the slice. Every serving tick of the cells
that list this metric is a unified tick, and a unified step program holds one
Pallas kernel, `paged_attention_unified` (`_rua_kernel`), called once a layer
inside the layer scan: the one such event of a v5e trace carries
`kernel_metadata={"kernel":"paged_attention_unified"}` (looked at by hand, PR
26), so the prefix is the kernel."""
from tick_phases import PAGED_KERNELS, is_custom_call, ms_per_slice_tick


def read(run):
    return ms_per_slice_tick(run, lambda n: is_custom_call(n, PAGED_KERNELS))
