"""Model step: device time a tick of everything XLA itself compiles: self
time on device 0's `XLA Ops` line of every operation that is no Pallas call
(`paged_kernel_ms.tick`'s names), no expert product (`expert_product_ms.tick`'s
EXPERT_PRODUCTS) and no pool copy (`pool_copy_ms.tick`'s), in the traced
slice, over the ticks in the slice. Where a block's mixers have no kernel of
their own (models/lfm2_moe.py: the gated short convolution is `jax.numpy`)
this is where they run, beside the routers, the pairs' sort and un-sort, the
dense products, the norms, the head and the sampler: the number a change to
`ops/ssm_scan.ragged_conv` or to `held_expert_ffn`'s sort moves. None
without a trace."""
from harness import load_module
from tick_phases import (PAGED_KERNELS, is_custom_call, is_pool_copy,
                         ms_per_slice_tick)

EXPERT_PRODUCTS = load_module("layer_metrics",
                              "expert_product_ms.tick").EXPERT_PRODUCTS


def read(run):
    return ms_per_slice_tick(run, lambda n: not (
        is_custom_call(n, PAGED_KERNELS) or is_pool_copy(n)
        or any(p in n for p in EXPERT_PRODUCTS)))
