"""Model step: the selective-scan kernel's share of the chip's memory
bandwidth, counting the bytes the recurrence needs whatever implements it.
The family's `ssm_bytes(sizes, ssm_rows, ssm_seqs)` (a row's x and dt in and
y out and its B and C, a sequence's scan state in and out, every Mamba layer)
summed over the ticks in the traced slice (`ssm_rows`: rows through the scan,
`ssm_seqs`: slots read and written, both counted by `_mixed_tick`), over the
scan kernels' seconds in the slice (see `ssm_kernel_ms.tick`), over the chip's
peak from peaks.json. A floor, and it cannot pass 100%. A walk of one row a
sequence is bound by its steps and its DMAs' latency, not by bytes: a small
share is expected. None where the program keeps no such count or the family
has no state-space layers."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

SCAN_KERNEL = "ssm_scan"


def read(run):
    ticks = [t for t in slice_ticks(run) if "ssm_rows" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "ssm_bytes"):
        return None
    seconds = self_seconds(run, lambda n: SCAN_KERNEL in n)
    if not seconds:
        return None
    sizes = run.config["sizes"]
    read_bytes = sum(family.ssm_bytes(sizes, t["ssm_rows"], t["ssm_seqs"])
                     for t in ticks)
    return 100.0 * read_bytes / seconds / run.peaks["hbm_bytes_per_s"]
