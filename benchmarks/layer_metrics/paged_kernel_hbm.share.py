"""Model step: the paged kernel's share of the chip's memory bandwidth,
counting useful bytes only. `kv_tokens` of the ticks in the traced slice (the
context tokens the kernel reads, summed by `_mixed_tick`) times the bytes of
cache behind one token over all layers (the family's `cache_bytes_per_token`:
K and V for grouped-query attention), over the kernel's seconds in the
slice (see `paged_kernel_ms.tick`), over the chip's peak from peaks.json.
Pages are read whole and queries, outputs and tables are left out, so this is
a floor on the kernel's traffic: bound by bytes, not by operations."""
from harness import load_module
from tick_phases import (PAGED_KERNELS, is_custom_call, self_seconds,
                         slice_ticks)


def read(run):
    ticks = [t for t in slice_ticks(run) if "kv_tokens" in t]
    if not ticks:
        return None
    seconds = self_seconds(run, lambda n: is_custom_call(n, PAGED_KERNELS))
    if not seconds:
        return None
    family = load_module("families", run.config["family"])
    read_bytes = (sum(t["kv_tokens"] for t in ticks)
                  * family.cache_bytes_per_token(run.config["sizes"]))
    return 100.0 * read_bytes / seconds / run.peaks["hbm_bytes_per_s"]
