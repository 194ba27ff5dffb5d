"""Model step: device time a tick of the two KERNELS of block-sparse
attention's first stage, and of them ONLY (about half of what the stage costs:
see "NOT in it" below), once a sparse layer: `block_select_call`'s (a query
token's heads against the page means of its context, the softmax over the
whole kernels and the sum over a kv head's heads, ops/block_sparse.py) and
`dsa_select_call`'s (the topk-th largest block score by selection,
ops/sparse_latent.py's, which this stage reuses). Self time on device 0's `XLA Ops` line of the operations
whose name holds either entry's name (the jitted entry, which its kernel's
HLO instruction is named after) in the traced slice, over the ticks in the
slice. NOT in it: what XLA runs around the kernels inside the entries (the
gather of a sequence's page means in table order, the max-pool to blocks, the
compaction of the kept blocks: fusions named `fusion.<n>`, 2.6% of device
time in the cell against these kernels' 2.5%, PERF.md section 5), so this
number UNDERSTATES the stage it is named after, and time moved between them
and the kernels moves this number and not the tick. None where the program
has no such kernel."""
from tick_phases import self_seconds, slice_ticks

ENTRIES = ("block_select", "dsa_select_call")


def read(run):
    ticks = slice_ticks(run)
    s = (self_seconds(run, lambda n: any(e in n for e in ENTRIES))
         if ticks else 0)
    return 1e3 * s / len(ticks) if s else None
