"""Serve deployment: `since_prev_ms` of the flight record, median over the
window's ticks: from the end of one recorded tick to the next tick's
`_admit()`, which is `LLMServer._engine_loop` between two `step()` calls
(stream puts, gauges, the lock handed to submitters, an idle sleep)."""
from tick_phases import window_median


def read(run):
    return window_median(run, "since_prev_ms")
