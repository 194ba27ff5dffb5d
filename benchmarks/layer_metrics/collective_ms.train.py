"""Mesh / collectives: summed durations, per traced step, of the first
chip's trace events whose HLO name starts all-gather, all-reduce,
reduce-scatter, collective-permute or all-to-all (an asynchronous one counted
from its start to its done, so time overlapped with compute is included)."""


def read(run):
    if not run.trace or not run.trace.get("steps_traced"):
        return None
    return 1e3 * run.trace["device0_collective_s"] / run.trace["steps_traced"]
