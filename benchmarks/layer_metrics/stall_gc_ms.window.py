"""Engine tick: milliseconds of the window's long ticks that the engine put
down to a pass of the cycle collector (cause `gc` of the time account, PR 37:
`time_account.stalls_ms`). None where the program keeps no account."""
from time_account import stalls_ms


def read(run):
    return stalls_ms(run, lambda cause: cause == "gc")
