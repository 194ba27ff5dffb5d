"""Mesh / collectives: what chip 0's core spent in or waiting on
collectives a step. Self time on device 0's `XLA Ops` line of the names
`trace_reduce.is_collective` takes, `-start` and `-done` included, and of the
`async-collective-start/-done` fusions, over the traced steps.
`collective_ms.train` counts an asynchronous collective from its start to its
done, compute that overlaps it included; here a `-start` is the time to issue
it and a `-done` the time the core waited for it, so the reading lies between
0 and `collective_ms.train`.

One fsdp=2 x tp=2 trace looked at by hand (v5e, PR 26): the tp all-reduces are
SYNCHRONOUS, under their own names (`all-reduce.98` `.99` `.100` `.103` `.104`,
each 1.62 ms once a layer a step, and a few once a step), nothing else runs on
the core meanwhile, and they are 89% of this reading; the fsdp all-gathers of
the layer scan became asynchronous `collective-permute-start/-done` pairs
(fsdp=2: one permute and a concatenate), 5.8-6.4 ms each on the async line and
a `-done` of 5 ns on the ops line: fully hidden; the rest of the gathers and
the reduce-scatters are `async-collective-start/-done.<n>` fusions (7 ms a
step in all, names `is_collective` does not take, so added here) and
`all-reduce-scatter` fusions called `fusion.<n>` (not told from compute by
name: left out)."""
import trace_reduce
from tick_phases import ms_per_traced_step


def read(run):
    return ms_per_traced_step(
        run, lambda n: trace_reduce.is_collective(n)
        or n.startswith("async-collective-"))
