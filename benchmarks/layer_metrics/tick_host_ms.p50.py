"""Engine tick: host time of one unified tick that is not spent blocked on
the device, median over the window's ticks: `admit_ms + compose_ms +
dispatch_ms + commit_ms` of the flight record (the phase clocks
`LLMEngine.step()` and `_mixed_tick()` keep since PR 26). What a leaner
composer, sampler set-up or commit loop would shorten."""
from tick_phases import window_median


def read(run):
    return window_median(run, "admit_ms", "compose_ms", "dispatch_ms",
                         "commit_ms")
