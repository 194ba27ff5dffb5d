"""Engine tick: host time of one `step()` (compose, dispatch, harvest),
median over the ticks that started inside the window, from the flight
recorder."""
from harness import percentile


def read(run):
    xs = [t["dur_ms"] for t in run.window_ticks() if "dur_ms" in t]
    return percentile(xs, 50) if xs else None
