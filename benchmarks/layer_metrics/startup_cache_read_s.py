"""Replica start-up: seconds spent reading executables out of JAX's
persistent compilation cache over the whole of `build_engine`,
`cache_read_s` of the `llm:startup` span. 0 on a cold start. None where the
program writes no such span (older than PR 55): no `per_layer` entry yet
(`startup_account.py`)."""
from startup_account import one, startup_arg


def read(run):
    return startup_arg(run, "cache_read_s")


def samples(run):
    return one(read(run))
