"""Time to first token, 95th percentile, ms: from the moment a request was
due to its first token event from `completions_stream`, over every request
due inside the window. A request with no first token has no sample here; it
is counted as failed, and a failed request makes the run incorrect."""
from harness import percentile


def samples(run):
    return [(r.token_times[0] - r.due) * 1e3
            for r in run.window_requests() if r.token_times]


def read(run):
    xs = samples(run)
    return percentile(xs, 95) if xs else None
