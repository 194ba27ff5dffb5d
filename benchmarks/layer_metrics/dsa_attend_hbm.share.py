"""Model step: the sparse attention kernel's share of the chip's memory
bandwidth, counting the bytes no form of it can avoid: `dsa_attend_rows` of
the ticks in the traced slice (the latent rows one layer must read at least
once a ROW of the tick: min(context, index_topk) a row, whatever the kernel
does; the block's `tick_counts`) times the family's `cache_bytes_per_token`
(a token's rows over all layers, padding not counted), over the seconds of
`dsa_attend_call`'s events in the slice, over the chip's peak. No correct
kernel reads less than one token's set a row, so none passes 100%; this PR's
kernel reads a set a TOKEN (a slice's tokens each their own), and its rows
padded to 640 lanes. None where the program keeps no such count or has no such
kernel."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

ENTRY = "dsa_attend_call"


def read(run):
    ticks = [t for t in slice_ticks(run) if "dsa_attend_rows" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "cache_bytes_per_token"):
        return None
    seconds = self_seconds(run, lambda n: ENTRY in n)
    if not seconds:
        return None
    moved = (sum(t["dsa_attend_rows"] for t in ticks)
             * family.cache_bytes_per_token(run.config["sizes"]))
    return 100.0 * moved / seconds / run.peaks["hbm_bytes_per_s"]
