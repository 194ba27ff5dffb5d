"""Engine tick, in a saturated closed loop: median time from a request's
sending to its first token, over the requests sent in the window. There the
prompt waits for its share of the tick's token budget, one slice a tick, while
its client's row decodes nothing: the tail of this wait swings with the order
of the prompts, so the median is kept, and as a per-layer metric."""
from harness import load_module, percentile


def read(run):
    xs = load_module("layer_metrics", "first_token_ms.p95").samples(run)
    return percentile(xs, 50) if xs else None
