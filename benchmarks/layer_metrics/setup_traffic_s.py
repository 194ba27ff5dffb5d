"""Replica start-up: the part of `setup_s` in which the engine SERVES: from
the first flight record's `t` (the first tick of the check's first request)
to the window's start `run.t0`. In it: the repeat check's three requests, the
traffic's shared prefixes served once each (GLM's four documents: 1,024
prefill slices), and `warm_s` seconds of the load before the window. What
lies before it (imports, `build_engine`, the logits check, which calls
`runner.step` with no tick) is `setup_s` less this. None where the run has
no flight record."""
from startup_account import first_tick, one


def read(run):
    tick = first_tick(run)
    return run.t0 - tick if tick is not None and run.t0 else None


def samples(run):
    return one(read(run))
