"""Replica start-up: seconds of Python tracing and of lowering to MLIR
(Mosaic's lowering of every Pallas call is in the latter) over the whole of
`build_engine`, `trace_s + lower_s` of the `llm:startup` span (the compile
ledger's own seconds, `ray_tpu/util/tracing.py`). Paid warm and cold alike:
what a new kernel or one more bucket adds to EVERY start. None where the
program writes no such span (older than PR 55): no `per_layer` entry yet
(`startup_account.py`)."""
from startup_account import one, startup_arg


def read(run):
    return startup_arg(run, "trace_s", "lower_s")


def samples(run):
    return one(read(run))
