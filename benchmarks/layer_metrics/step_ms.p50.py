"""Train step: time between the completions of consecutive steps (host clock,
each ended by waiting for the step's loss), median over the window."""
from harness import percentile


def read(run):
    done = [s["t_done"] for s in run.steps]
    xs = [(b - a) * 1e3 for a, b in zip(done, done[1:])]
    return percentile(xs, 50) if xs else None
