"""Gap between consecutive token events of one stream, 95th percentile, ms,
over every gap of every request whose later event fell inside the window."""
from harness import percentile


def samples(run):
    return [(b - a) * 1e3 for r in run.requests
            for a, b in zip(r.token_times, r.token_times[1:])
            if run.in_window(b)]


def read(run):
    xs = samples(run)
    return percentile(xs, 95) if xs else None
