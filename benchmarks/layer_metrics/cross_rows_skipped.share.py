"""Engine tick: the share of a tick's rows that the narrowing keeps out of
the cross-decoder. 1 - `cross_rows` (rows that entered it: one a sequence)
over `used` (every real row of the tick, through the self-decoder), both
counted by `_mixed_tick`; mean over the window's ticks, in percent. A prompt
slice of n tokens skips n - 1; a decode row skips none, so a tick of decode
rows alone reads 0. None where the program keeps no such count (a model whose
rows all pass every layer, an older program)."""


def read(run):
    xs = [100.0 * (1.0 - t["cross_rows"] / t["used"])
          for t in run.window_ticks()
          if "cross_rows" in t and t.get("used")]
    return sum(xs) / len(xs) if xs else None
