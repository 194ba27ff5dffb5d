"""Engine tick: what a window loses to its LONG ticks, the part of a run
that no median sees. Over the window's ticks, the sum of each long tick's
excess period: a tick's period is `since_prev_ms + admit_ms + dur_ms` of the
flight record (without `since_prev_ms` where `settled` is `idle`), long where
it exceeds twice the window's median period by 20 ms or more, its excess what
it has over that median (`time_account.py`; the engine's own rule, PR 37). 0 in
a quiet run; a pause of the machine adds ~110 ms, a full pass of the cycle
collector 120-160. `samples` are the excesses, for the run's notes."""
from time_account import long_excesses


def samples(run):
    return long_excesses(run.window_ticks()) or []


def read(run):
    xs = long_excesses(run.window_ticks())
    return None if xs is None else float(sum(xs))
