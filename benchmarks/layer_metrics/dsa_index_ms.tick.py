"""Model step: device time a tick of the sparse attention's INDEXER: the
kernels of `dsa_index_call` (a query row's index heads against the paged index
keys of its context) and `dsa_select_call` (the index_topk-th largest score
by selection), ops/sparse_latent.py, once a "full" layer. Self time on device
0's `XLA Ops` line of the operations whose name holds either entry's name
(the jitted entry, which its kernel's HLO instruction is named after) in the
traced slice, over the ticks in the slice. NOT in it: what XLA runs around the
kernels inside the entries (the blocks' rows laid token-major, the compaction
of the selected positions: fusions named `fusion.<n>`), so time moved between
them and the kernels moves this number and not the tick. These events are not
in `paged_kernel_ms.tick` (not named `tpu_custom_call`). None where the
program has no such kernel."""
from tick_phases import self_seconds, slice_ticks

ENTRIES = ("dsa_index_call", "dsa_select_call")


def read(run):
    ticks = slice_ticks(run)
    s = (self_seconds(run, lambda n: any(e in n for e in ENTRIES))
         if ticks else 0)
    return 1e3 * s / len(ticks) if s else None
