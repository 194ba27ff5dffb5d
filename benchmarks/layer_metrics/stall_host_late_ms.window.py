"""Engine tick: milliseconds of the window's long ticks put down to a late
host (cause `host_late` of the time account, PR 37: the wait was long, a step
was queued behind the awaited one and the next wait found its result there:
the machine's pause, or a completion that reached Python late). None where the
program keeps no account."""
from time_account import stalls_ms


def read(run):
    return stalls_ms(run, lambda cause: cause == "host_late")
