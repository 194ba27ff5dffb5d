"""Engine tick: the median gap between consecutive token events of one
stream, over the same samples as the end-to-end `itl_ms.p95`. The tail sits on
the ticks that carry prefill rows or eviction spills and spreads by 2-4%
between runs; the median is a decode-only tick and repeats within 1% (PERF.md
section 6, PR 28), so a change to the tick shows here first."""
from harness import load_module, percentile


def read(run):
    xs = load_module("e2e_metrics", "itl_ms.p95").samples(run)
    return percentile(xs, 50) if xs else None
