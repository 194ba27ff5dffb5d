"""Replica start-up: seconds of `serving.build_engine`, the `llm:startup`
span's extent: parameters, placement, warm-up and `other_s` (imports, the
engine's construction). None where the program writes no such span (older
than PR 55), which is why this file has no `per_layer` entry yet
(`startup_account.py`)."""
from startup_account import one, startup_span


def read(run):
    span = startup_span(run)
    return None if span is None else span["dur"] / 1e6


def samples(run):
    return one(read(run))
