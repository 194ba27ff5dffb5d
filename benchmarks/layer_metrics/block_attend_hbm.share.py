"""Model step: the second stage's share of the chip's memory bandwidth,
counting the bytes no form of it can avoid: the family's `attend_bytes(sizes,
select_seqs, select_rows)` (for every selecting (sequence, kv head) of a
step ONE token's kept set, `topk` blocks of that head's K and V lanes, once a
sparse layer however many of its tokens the step carries, since their union
holds at least one token's set; a selecting row's q in and o out) summed over
the ticks in the traced slice (`select_seqs`, `select_rows`: the block's
`tick_counts`), over the seconds of the second stage's kernels in the slice
(see `block_attend_ms.tick`), over the chip's peak from peaks.json. A floor
whatever implements the stage (a walk of the union, a gather, whole 256-lane
rows or a head's half), so it cannot pass 100%: this PR's decode rows read
whole rows (both kv heads' lanes: twice the count) and a slice walks its
whole context. None where the program keeps no such count or has no such
kernel."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

ENTRY = "block_attend"


def read(run):
    ticks = [t for t in slice_ticks(run) if "select_seqs" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "attend_bytes"):
        return None
    seconds = self_seconds(run, lambda n: ENTRY in n)
    if not seconds:
        return None
    sizes = run.config["sizes"]
    moved = sum(family.attend_bytes(sizes, t["select_seqs"],
                                    t["select_rows"]) for t in ticks)
    return 100.0 * moved / seconds / run.peaks["hbm_bytes_per_s"]
