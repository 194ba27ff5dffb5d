"""Engine admission: submit to admit, 95th percentile, ms, over the finished
requests due in the window. The engine records an `llm:queue` span only where
a request waited; one admitted at once counts as zero."""
from harness import percentile


def read(run):
    queued = {s["args"]["request_id"]: s["dur"] / 1e3
              for s in run.window_spans("llm:queue")}
    finished = [s["args"]["request_id"]
                for s in run.window_spans("llm:decode")]
    if not finished:
        return None
    return percentile([queued.get(rid, 0.0) for rid in finished], 95)
