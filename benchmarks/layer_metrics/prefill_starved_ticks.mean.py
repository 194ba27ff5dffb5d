"""Engine admission: ticks a prompt sat admitted in `engine.prefilling`
without a slice of the token budget, mean over the `llm:prefill` spans
(argument `starved_ticks`, counted by `_mixed_tick` since PR 26) of the
finished requests that were due in the window. A row held so decodes
nothing: in a saturated closed loop this is tokens/s."""
from tick_phases import prefill_span_values


def read(run):
    xs = prefill_span_values(run, "starved_ticks")
    return sum(xs) / len(xs) if xs else None
