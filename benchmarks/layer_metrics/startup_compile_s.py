"""Replica start-up: seconds of the backend's compile (less the persistent
cache's reads) over the whole of `build_engine`, the parameter draw's
programs included: `compile_s` of the `llm:startup` span. What a cold start
pays, and what a warm one compiles AGAIN (JAX's cache keeps no program that
compiled in under a second, so an eager draw's tens of small ones compile at
every start). None where the program writes no such span (older than PR 55):
no `per_layer` entry yet (`startup_account.py`)."""
from startup_account import one, startup_arg


def read(run):
    return startup_arg(run, "compile_s")


def samples(run):
    return one(read(run))
