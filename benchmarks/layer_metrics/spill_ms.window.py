"""Engine tick: the engine thread's time in the eviction-spill path over the
whole window, the sum of `spill_ms` of the flight record (PR 30; it lies
inside `admit_ms` / `compose_ms`): the tier's reservation, the one gather's
dispatch, a wait for the oldest staging result. 0 in a cell whose pool never
fills. None where the program keeps no such field (older than PR 30)."""


def read(run):
    xs = [t["spill_ms"] for t in run.window_ticks() if "spill_ms" in t]
    return float(sum(xs)) if xs else None
