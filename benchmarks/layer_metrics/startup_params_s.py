"""Replica start-up: seconds of the parameters' draw (`init_params`, eager:
one small program a primitive and shape, most of which compile at every
start) or the checkpoint's load, the `llm:startup:params` span's extent, to
the call's return. None where the program writes no such span (older than PR
55): no `per_layer` entry yet (`startup_account.py`)."""
from startup_account import children, one


def read(run):
    spans = children(run, "llm:startup:params")
    return spans[0]["dur"] / 1e6 if spans else None


def samples(run):
    return one(read(run))
