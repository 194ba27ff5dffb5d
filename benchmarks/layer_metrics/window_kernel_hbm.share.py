"""Model step: the window form's share of the chip's memory bandwidth,
counting useful bytes only. `window_kv_tokens` of the ticks in the traced
slice (the tokens inside the rows' windows, counted once and not once a
layer, summed by `_mixed_tick`) times the family's
`window_cache_bytes_per_token` (K and V of every window layer), over the
window kernels' seconds in the slice (see `window_kernel_ms.tick`), over the
chip's peak from peaks.json. Pages are read whole and queries, outputs,
tables and a K row's padding are left out, so this is a floor, and it cannot
pass 100%. A walk of a few pages is bound by its steps, not by bytes: a small
share is expected. None where the program keeps no such count or the family
has no window layers."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

WINDOW_KERNEL = "paged_attention_window"


def read(run):
    ticks = [t for t in slice_ticks(run) if "window_kv_tokens" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "window_cache_bytes_per_token"):
        return None
    seconds = self_seconds(run, lambda n: WINDOW_KERNEL in n)
    if not seconds:
        return None
    read_bytes = (sum(t["window_kv_tokens"] for t in ticks)
                  * family.window_cache_bytes_per_token(run.config["sizes"]))
    return 100.0 * read_bytes / seconds / run.peaks["hbm_bytes_per_s"]
