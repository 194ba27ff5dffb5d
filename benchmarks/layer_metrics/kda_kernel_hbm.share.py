"""Model step: the Kimi Delta Attention kernel's share of the chip's memory
bandwidth, counting the bytes no form of the layer can avoid. The family's
`kda_bytes(sizes, kda_rows, kda_seqs)` (a sequence's S READ once a layer, a
row's q, k, v, gates and beta in and its o out; the state's write-back is not
counted, so a kernel that reads and rewrites S a step reads at most about
half; the convolution's tails move outside the kernel and are not counted)
summed over the ticks in the traced slice (`kda_rows`: rows the KDA calls
carried, `kda_seqs`: slots they read and wrote, both counted by
`_mixed_tick`), over the kernel's seconds in the slice (see
`kda_kernel_ms.tick`: the kernel's events alone, NOT the gather and transpose
by which XLA lays its planes around every call), over the chip's peak from
peaks.json: the kernel's share of its roofline. None where the program keeps
no such count or the family has no KDA layers."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

KDA_KERNEL = "kda_call"


def read(run):
    ticks = [t for t in slice_ticks(run) if "kda_rows" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "kda_bytes"):
        return None
    seconds = self_seconds(run, lambda n: KDA_KERNEL in n)
    if not seconds:
        return None
    sizes = run.config["sizes"]
    moved = sum(family.kda_bytes(sizes, t["kda_rows"], t["kda_seqs"])
                for t in ticks)
    return 100.0 * moved / seconds / run.peaks["hbm_bytes_per_s"]
