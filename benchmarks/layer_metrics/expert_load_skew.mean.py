"""Expert layer: how unevenly a tick's rows fall on the held experts: the
busiest held expert's rows (`expert_rows_max`, a layer's largest group,
summed over the routed layers) times the experts held (the configuration's
`n_routed_experts`) over the rows computed (`expert_rows`); 1.0 = even. Mean
over the window's ticks that computed any. The grouped products walk every
held expert's weights, so a skewed tick costs no more bytes, only rows."""


def read(run):
    held = run.config["sizes"].get("n_routed_experts")
    xs = [t["expert_rows_max"] * held / t["expert_rows"]
          for t in run.window_ticks()
          if t.get("expert_rows") and "expert_rows_max" in t]
    return sum(xs) / len(xs) if xs and held else None
