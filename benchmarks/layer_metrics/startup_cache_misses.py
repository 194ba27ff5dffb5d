"""Replica start-up: programs compiled and WRITTEN to the persistent
compilation cache over the whole of `build_engine` (`cache_misses` of the
`llm:startup` span; unit `programs`): the number that says whether a "warm"
run was warm. A cold start misses every program the cache keeps; a warm one
none. None where the program writes no such span (older than PR 55): no
`per_layer` entry yet (`startup_account.py`)."""
from startup_account import one, startup_arg


def read(run):
    return startup_arg(run, "cache_misses")


def samples(run):
    return one(read(run))
