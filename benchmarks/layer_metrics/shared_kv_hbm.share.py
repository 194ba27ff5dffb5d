"""Model step: the share of the chip's memory bandwidth that the paged kernel
reaches on a pool with more readers than writers (one K/V layer that a
cross-decoder's layers read), counting useful bytes only. Every row of a tick
walks the pool once in the layer that writes it (`kv_tokens`), and the rows
that enter the cross-decoder walk it once more a cross layer
(`cross_kv_tokens`, counted once and not once a layer, times the family's
`cross_layers`); times the family's `cache_bytes_per_token` (what a token
holds there), over the seconds of the operations named `paged_attention_` and
not `paged_attention_window` in the traced slice, over the chip's peak from
peaks.json. Pages are read whole and queries, outputs and tables are left out:
a floor, and it cannot pass 100%. (`paged_kernel_hbm.share` multiplies
`kv_tokens` by the bytes a token holds and would read an eighth of this.) None
where the program keeps no such count or the family has no cross layers."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

PAGED, WINDOW = "paged_attention_", "paged_attention_window"


def read(run):
    ticks = [t for t in slice_ticks(run) if "cross_kv_tokens" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "cross_layers"):
        return None
    seconds = self_seconds(run, lambda n: PAGED in n and WINDOW not in n)
    if not seconds:
        return None
    sizes = run.config["sizes"]
    tokens = sum(t["kv_tokens"] + family.cross_layers(sizes)
                 * t["cross_kv_tokens"] for t in ticks)
    return (100.0 * tokens * family.cache_bytes_per_token(sizes) / seconds
            / run.peaks["hbm_bytes_per_s"])
