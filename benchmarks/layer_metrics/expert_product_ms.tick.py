"""Expert layer: device time a tick of the held experts' grouped products
(`models/expert_share.py::held_expert_ffn`: one product a projection a routed
layer), WHATEVER implements them. Self time on device 0's `XLA Ops` line of
the operations whose name holds `ragged-dot` (XLA's own grouped kernel, what
`jax.lax.ragged_dot` lowers to on a v5e: `ragged-dot-none.<n>` and the small
`ragged-dot-metadata.<n>` before it) or `grouped_dot` (the Pallas kernel of
`ops/grouped_dot.py`, whose events read `grouped_dot_call.<n>` after its
jitted entry; its tag is `grouped_dot`; since PR 53 every routed
configuration's products on a TPU) in the traced slice, over the ticks in the
slice. Neither
is in `paged_kernel_ms.tick`: neither is named `tpu_custom_call*` nor holds
`paged_attention_`. NOT in it: the sort and the gather of the pairs, the
gate, the un-sort, and the plan XLA computes around the Pallas kernel
(`visit_plan`: small fusions with names of their own). None where the program
has no such product (a model without routed experts) or without a trace."""
from tick_phases import self_seconds, slice_ticks

EXPERT_PRODUCTS = ("ragged-dot", "grouped_dot")


def read(run):
    ticks = slice_ticks(run)
    seconds = (self_seconds(run, lambda n: any(p in n for p in EXPERT_PRODUCTS))
               if ticks else 0)
    return 1e3 * seconds / len(ticks) if seconds else None
