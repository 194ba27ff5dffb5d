"""Device: the part of `device_idle.serve` that no host work can shorten.
Seconds of device 0's idle gaps that lie inside the `wait` phase of a tick
(the host already blocked on the device: launch latency and bubbles inside
the step program), over the traced slice. `device_idle.serve` minus this is
idle while the host composes, dispatches, commits or runs the server loop."""
from tick_phases import idle_inside, slice_on_host_clock, wait_intervals


def read(run):
    waits = wait_intervals(run.ticks)
    if slice_on_host_clock(run) is None or not waits:
        return None
    return 100.0 * idle_inside(run, waits) / run.trace["window_s"]
