"""Engine tick: the share of the window's ticks that were dispatched with
another step in flight (`lookahead` of the flight record, PR 34), in percent.
100 where the device always has its next launch queued; the rest are a busy
period's first tick and the call that lands its last step (`settled: idle`),
or ticks that had to settle first (a draft, a host-sampled tick, page
pressure). None where the program keeps no such field (older than PR 34)."""


def read(run):
    xs = [bool(t["lookahead"]) for t in run.window_ticks()
          if "lookahead" in t]
    return 100.0 * sum(xs) / len(xs) if xs else None
