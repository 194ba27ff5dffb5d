"""Engine admission, in the open loop: time to first token, 95th percentile,
ms: from the moment a request was due to its first token event from
`completions_stream`, over every request due inside the window. A request with
no first token has no sample here; it is counted as failed, and a failed
request makes the run incorrect. Until PR 28 this was the end-to-end metric
`ttft_ms.p95`; on ~39 requests a window it is nearly the second largest
sample, and its runs spread by more than half of the widest bound (PERF.md
section 6), so it is read per layer, under this name, with no bound."""
from harness import percentile


def samples(run):
    return [(r.token_times[0] - r.due) * 1e3
            for r in run.window_requests() if r.token_times]


def read(run):
    xs = samples(run)
    return percentile(xs, 95) if xs else None
