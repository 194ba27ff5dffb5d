"""Replica start-up: seconds of `engine.warmup` (`engine.stats()["warmup_s"]`,
kept since PR 25's parent): every program of the tick's token ladder, the
spill gather's sizes and a state group's snapshot copy, compiled and
enqueued one after another, and ONE wait for the device at the end. On a warm
start a program costs its trace, its lowering (Mosaic's, of every Pallas
call) and the persistent cache's read; on a cold one XLA's compile besides
(`startup_trace_lower_s`, `startup_compile_s`, `startup_cache_read_s` split
the whole start so where the program writes the spans, PR 55). None where the
statistic is absent."""
from startup_account import one


def read(run):
    return run.stats_after.get("warmup_s")


def samples(run):
    return one(read(run))
