"""Model step: the indexer's share of the chip's memory bandwidth, counting
the bytes no form of it can avoid: `dsa_index_rows` of the ticks in the traced
slice (the index keys one "full" layer must read at least once a row: the sum
of the rows' contexts, counted by the block's `tick_counts`) times the
family's `index_bytes_per_row` times its `index_layers`, over the seconds of
`dsa_index_ms.tick`'s events in the slice, over the chip's peak from
peaks.json. A floor: a slice's blocks each read their context again. None
where the program keeps no such count or has no such kernel."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

ENTRIES = ("dsa_index_call", "dsa_select_call")


def read(run):
    ticks = [t for t in slice_ticks(run) if "dsa_index_rows" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "index_bytes_per_row"):
        return None
    seconds = self_seconds(run, lambda n: any(e in n for e in ENTRIES))
    if not seconds:
        return None
    sizes = run.config["sizes"]
    moved = (sum(t["dsa_index_rows"] for t in ticks)
             * family.index_bytes_per_row(sizes) * family.index_layers(sizes))
    return 100.0 * moved / seconds / run.peaks["hbm_bytes_per_s"]
