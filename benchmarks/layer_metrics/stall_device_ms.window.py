"""Engine tick: milliseconds of the window's long ticks put down to the
device's side (cause `device` of the time account, PR 37: the wait was long
and the step queued behind the awaited one was not done either). None where
the program keeps no account."""
from time_account import stalls_ms


def read(run):
    return stalls_ms(run, lambda cause: cause == "device")
