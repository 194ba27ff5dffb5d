"""Model step: the sparse attention kernel's share of the chip's bf16 peak,
counting useful operations only: `dsa_pairs` of the ticks in the traced slice
(the query-context pairs a latent layer must cover: min(position + 1,
index_topk) a query token, the block's `tick_counts`; `attn_pairs` is the
dense count) times the family's `attention_flops_per_pair` (the equations' own
count over all layers), over the seconds of `dsa_attend_call`'s events in the
slice, over the chip's peak from peaks.json. The absorbed form executes
several times this count, so the share is a floor on what the kernel keeps
the MXU busy with. None where the program keeps no such count or has no such
kernel."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

ENTRY = "dsa_attend_call"


def read(run):
    ticks = [t for t in slice_ticks(run) if "dsa_pairs" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "attention_flops_per_pair"):
        return None
    seconds = self_seconds(run, lambda n: ENTRY in n)
    if not seconds:
        return None
    flops = (sum(t["dsa_pairs"] for t in ticks)
             * family.attention_flops_per_pair(run.config["sizes"]))
    return 100.0 * flops / seconds / run.peaks["bf16_flops_per_s"]
