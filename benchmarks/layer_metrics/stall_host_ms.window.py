"""Engine tick: milliseconds of the window's long ticks that lay in the
host's own phases (causes `host_work:<phase>` and `host_blocked:<phase>` of
the time account, PR 37: the engine thread worked, or held no CPU). None
where the program keeps no account."""
from time_account import stalls_ms


def read(run):
    return stalls_ms(run, lambda cause: cause.startswith("host_work:")
                     or cause.startswith("host_blocked:"))
