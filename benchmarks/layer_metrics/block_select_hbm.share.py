"""Model step: the first stage's share of the chip's memory bandwidth,
counting the bytes no form of it can avoid: the family's `select_bytes(sizes,
pages_scored, select_rows)` (every DISTINCT page's mean row of the step's
selecting contexts once a sparse layer, a shared document's counted once; a
selecting row's q in and its table out) summed over the ticks in the traced
slice (`pages_scored`, `select_rows`: the block's `tick_counts`, host
arithmetic), over the seconds of the first stage's kernels in the slice (see
`block_select_ms.tick`), over the chip's peak from peaks.json. A floor
whatever implements the stage, so it cannot pass 100%: this PR's form gathers
every SEQUENCE's means (a shared document's once a sequence) outside the
kernel's own events and reads them again inside. None where the program keeps
no such count or has no such kernel."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

ENTRIES = ("block_select", "dsa_select_call")


def read(run):
    ticks = [t for t in slice_ticks(run) if "pages_scored" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "select_bytes"):
        return None
    seconds = self_seconds(run, lambda n: any(e in n for e in ENTRIES))
    if not seconds:
        return None
    sizes = run.config["sizes"]
    moved = sum(family.select_bytes(sizes, t["pages_scored"],
                                    t["select_rows"]) for t in ticks)
    return 100.0 * moved / seconds / run.peaks["hbm_bytes_per_s"]
