"""Model step: device time a tick of block-sparse attention's SECOND STAGE,
once a sparse layer: the row kernel under a table a (row, kv head) for the
sequences of one row (`block_attend_call`) and under a (token, kv head,
block) mask for the sequences of more (`block_attend_rows_call`),
ops/block_sparse.py. Self time on device 0's `XLA Ops` line of the operations
whose name holds `block_attend` (the jitted entries, which their kernels' HLO
instructions are named after) in the traced slice, over the ticks in the
slice. NOT in it: the tables and masks XLA lays for them (`fusion.<n>`), and
the dense row kernel's events, which are named `paged_attention_` and walk
nothing in a cell whose every context is past `dense_len`. None where the
program has no such kernel."""
from tick_phases import self_seconds, slice_ticks

ENTRY = "block_attend"


def read(run):
    ticks = slice_ticks(run)
    s = self_seconds(run, lambda n: ENTRY in n) if ticks else 0
    return 1e3 * s / len(ticks) if s else None
