"""Model step: rows a tick that pass a gated short convolution, summed over
the conv layers: a tick record's `conv_rows` (the rows the state group's
layers carried, under the block's own name: models/lfm2_moe.py,
`Block.state_fields`) times the configuration's `conv` layers, mean over the
window's ticks that carried any. Each such row reads and rewrites its
sequence's two-row tail in a slot (57 KB a slot at seven layers). None where
the program keeps no such count (another family, an older program)."""


def read(run):
    layers = sum(1 for k in run.config["sizes"].get("layer_types", ())
                 if k == "conv")
    xs = [t["conv_rows"] for t in run.window_ticks() if t.get("conv_rows")]
    return layers * sum(xs) / len(xs) if xs and layers else None
