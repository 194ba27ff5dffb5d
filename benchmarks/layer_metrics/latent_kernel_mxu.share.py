"""Model step: the paged latent-attention kernel's share of the chip's bf16
peak, counting useful operations only. `attn_pairs` of the ticks in the
traced slice (query-context pairs the tick's attention covered, causal,
summed by `_mixed_tick`) times the family's `attention_flops_per_pair` (the
equations' own count over all layers), over the paged kernels' seconds in the
slice (see `paged_kernel_ms.tick`), over the chip's peak from peaks.json. The
absorbed form executes several times this count, so the share is a floor on
what the kernel keeps the MXU busy with; beside it `paged_kernel_hbm.share`
is the same kernel's bytes. None where the program keeps no `attn_pairs` or
the family counts no operations a pair."""
from harness import load_module
from tick_phases import (PAGED_KERNELS, is_custom_call, self_seconds,
                         slice_ticks)


def read(run):
    ticks = [t for t in slice_ticks(run) if "attn_pairs" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "attention_flops_per_pair"):
        return None
    seconds = self_seconds(run, lambda n: is_custom_call(n, PAGED_KERNELS))
    if not seconds:
        return None
    flops = (sum(t["attn_pairs"] for t in ticks)
             * family.attention_flops_per_pair(run.config["sizes"]))
    return 100.0 * flops / seconds / run.peaks["bf16_flops_per_s"]
