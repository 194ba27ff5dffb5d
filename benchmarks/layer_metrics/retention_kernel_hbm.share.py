"""Model step: the power-retention kernel's share of the chip's memory
bandwidth, counting the bytes no form of the layer can avoid. The family's
`retention_bytes(sizes, retention_rows, retention_seqs)` (a sequence's S and
z read once a layer, a row's q, k, v and gates in and its o out; the
write-back is not counted) summed over the ticks in the traced slice
(`retention_rows`: rows the retention calls carried, `retention_seqs`: slots
they read and wrote, both counted by `_mixed_tick`), over the kernel's seconds
in the slice (see `retention_kernel_ms.tick`), over the chip's peak from
peaks.json. A floor, and it cannot pass 100%: a form that reads and rewrites
the state every step reads at most about half. None where the program keeps
no such count or the family has no retention layers."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

RETENTION_KERNEL = "power_retention"


def read(run):
    ticks = [t for t in slice_ticks(run) if "retention_rows" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "retention_bytes"):
        return None
    seconds = self_seconds(run, lambda n: RETENTION_KERNEL in n)
    if not seconds:
        return None
    sizes = run.config["sizes"]
    read_bytes = sum(family.retention_bytes(
        sizes, t["retention_rows"], t["retention_seqs"]) for t in ticks)
    return 100.0 * read_bytes / seconds / run.peaks["hbm_bytes_per_s"]
