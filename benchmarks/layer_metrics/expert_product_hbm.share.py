"""Expert layer: the held experts' products' share of the chip's memory
bandwidth, counting what no implementation can do without. The family's
`expert_bytes(sizes, experts_met, expert_rows)` of the ticks in the traced
slice (the three matrices of every held expert that HAD A ROW, once a layer,
`experts_met` and `expert_rows` counted on the device and fetched with the
tick's samples; the computed rows in at the hidden width and out again; the
hidden layer between the products left out), over the products' seconds in
the slice (see `expert_product_ms.tick`: XLA's `ragged-dot` or the Pallas
`grouped_dot`, whichever runs), over the chip's peak from peaks.json. A floor
on the products' traffic whatever implements them, so it cannot pass 100%;
the experts of Trinity-Large-Preview (3072 x 3072 a matrix) are the largest
the weight stream of `ops/grouped_dot.py` meets. None where the program keeps
no `experts_met`, the family has no `expert_bytes`, or without a trace."""
from harness import load_module
from tick_phases import self_seconds, slice_ticks

EXPERT_PRODUCTS = load_module("layer_metrics",
                              "expert_product_ms.tick").EXPERT_PRODUCTS


def read(run):
    ticks = [t for t in slice_ticks(run) if "experts_met" in t]
    family = load_module("families", run.config["family"])
    if not ticks or not hasattr(family, "expert_bytes"):
        return None
    seconds = self_seconds(run,
                           lambda n: any(p in n for p in EXPERT_PRODUCTS))
    if not seconds:
        return None
    sizes = run.config["sizes"]
    read_bytes = sum(family.expert_bytes(sizes, t["experts_met"],
                                         t["expert_rows"]) for t in ticks)
    return 100.0 * read_bytes / seconds / run.peaks["hbm_bytes_per_s"]
