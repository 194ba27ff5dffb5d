"""Model step: device time a tick of the sparse latent attention's kernel:
`dsa_attend_call` (ops/sparse_latent.py: one softmax a query token over its
own selected rows of the latent pool, once a layer). Self time on device 0's
`XLA Ops` line of the operations whose name holds the entry's name in the
traced slice, over the ticks in the slice. NOT in it: the gather that lays a
token's selected rows side by side before the kernel (XLA's, inside the same
entry: an event named `fusion.<n>` / `gather.<n>`), which PERF.md section 5
reads from the breakdown. Not in `paged_kernel_ms.tick` either. None where
the program has no such kernel."""
from tick_phases import self_seconds, slice_ticks

ENTRY = "dsa_attend_call"


def read(run):
    ticks = slice_ticks(run)
    s = self_seconds(run, lambda n: ENTRY in n) if ticks else 0
    return 1e3 * s / len(ticks) if s else None
