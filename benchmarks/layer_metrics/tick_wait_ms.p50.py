"""Model step: `wait_ms` of the flight record, median over the window's
ticks: the host blocked in the two `np.asarray` on the step program's
results, which is the device's time for the tick less what the host's
dispatch overlapped."""
from tick_phases import window_median


def read(run):
    return window_median(run, "wait_ms")
