"""Model step: the Mamba-2 (SSD) kernel's share of the chip's memory
bandwidth, counting the bytes no form of the layer can avoid. The family's
`ssd_bytes(sizes, ssd_rows, ssd_seqs)` (a sequence's S in ONCE a layer, a
row's x, B, C and dt in and its y out; the state's write-back is not counted,
so a kernel that reads and rewrites S a step reads at most about half; the
convolution's tails move outside the kernel and are not counted) summed over
the ticks in the traced slice (`ssd_rows`: rows the SSD calls carried,
`ssd_seqs`: slots they read and wrote, both counted by `_mixed_tick`), over
the kernel's seconds in the slice (see `ssd_kernel_ms.tick`: the kernel's
events alone), over the chip's peak from peaks.json: the kernel's share of its
roofline, a floor whatever implements it, which cannot pass 100%. None where
the program keeps no such count or the family has no Mamba-2 layers."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

SSD_KERNEL = "ssd_call"


def read(run):
    ticks = [t for t in slice_ticks(run) if "ssd_rows" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "ssd_bytes"):
        return None
    seconds = self_seconds(run, lambda n: SSD_KERNEL in n)
    if not seconds:
        return None
    sizes = run.config["sizes"]
    moved = sum(family.ssd_bytes(sizes, t["ssd_rows"], t["ssd_seqs"])
                for t in ticks)
    return 100.0 * moved / seconds / run.peaks["hbm_bytes_per_s"]
