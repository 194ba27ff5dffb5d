"""Engine admission: admit to first token, median over the `llm:prefill`
spans of the finished requests that were due in the window. Beside
`queue_ms.p95` it splits the first token's wait into admission and the
slices and ticks the prompt then needed."""
from harness import percentile
from tick_phases import prefill_span_values


def read(run):
    xs = prefill_span_values(run)
    return percentile(xs, 50) if xs else None
