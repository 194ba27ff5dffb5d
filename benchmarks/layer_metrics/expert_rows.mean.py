"""Expert layer: token-expert pairs the experts this program HOLDS computed
in a tick, summed over the routed layers (`expert_rows`, counted on the
device and fetched with the tick's samples), mean over the window's ticks.
Of a tick's `routed_rows` picks these are the ones that hit a held expert;
the rest belong to absent chips. None where the program keeps no such
count (a dense model, an older program)."""


def read(run):
    xs = [t["expert_rows"] for t in run.window_ticks() if "expert_rows" in t]
    return sum(xs) / len(xs) if xs else None
