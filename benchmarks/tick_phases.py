"""What the readers of the tick's phases share (PR 26): which of the flight
recorder's ticks fall in the traced slice, device 0's idle time inside given
host intervals, and device time by a predicate on the operation's name. (The
bytes of cache behind one context token are the family's: PR 28.)

The program keeps the clock: since PR 26 a unified tick's flight record holds
`admit_ms`, `since_prev_ms`, `compose_ms`, `dispatch_ms`, `wait_ms`,
`commit_ms` (host clock, `time.time()`) and the counters `kv_tokens`,
`prefill_tokens`, `starved`. The reduced trace keeps no host events, so the
readers join ticks to the device's lines through `host_minus_trace_clock_s`
(the harness's CLOCK_MARK). A program older than PR 26 has none of those
fields: every function here then finds nothing and the reader returns None.
`run.py` calls a run incorrect when a LISTED reader returns None, and a check
runs these files over a PR's parent too, so `BENCHMARK.json` lists a reader
only once the parent's program keeps what it reads (PERF.md section 7).
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

from harness import percentile

# How a v5e profile names a Pallas kernel's event (looked at by hand, PR 26).
# Under the benchmark's JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS=0 the HLO
# instruction, which is all `trace_reduce.op_name` keeps, is
# `tpu_custom_call.<n>`, or `shard_map.<n>` for a kernel called inside
# shard_map (the train step's flash kernels), numbered per program; the
# kernel's own name (`ops.kernel_tag`, PR 26) is further along in the event's
# text, `frontend_attributes={kernel_metadata={"kernel":"<name>"}}`. Without
# that setting the instruction is `paged_attention_unified.<n>`,
# `jvp_flash_fwd_.<n>`, `transpose_jvp_flash_bwd_dq__.<n>`. So a reader takes
# both prefixes, and any name that holds one of its kernels'. In a program
# compiled the benchmark's way nothing else has those prefixes
# (tests/test_tpu_compile.py holds the train step to that).
KERNEL_PREFIXES = ("tpu_custom_call", "shard_map.")
PAGED_KERNELS = ("paged_attention_",)
FLASH_KERNELS = ("flash_fwd", "flash_bwd")
POOL_COPY = re.compile(r"copy(\.\d+)?")


def is_custom_call(name: str, kernels: Tuple[str, ...]) -> bool:
    return name.startswith(KERNEL_PREFIXES) or any(k in name for k in kernels)


def is_pool_copy(name: str) -> bool:
    """A plain `copy.<n>`: not `copy-start` / `copy-done` (asynchronous
    copies) and not a `copy_bitcast_fusion`."""
    return POOL_COPY.fullmatch(name) is not None


def slice_on_host_clock(run) -> Optional[Tuple[float, float]]:
    """The traced slice (first device operation's start to the last one's
    end) as host times, or None without a trace or a clock mark."""
    trace = run.trace
    if not trace or trace.get("host_minus_trace_clock_s") is None:
        return None
    start = trace["window_start_s"] + trace["host_minus_trace_clock_s"]
    return start, start + trace["window_s"]


def slice_ticks(run) -> List[Dict]:
    """The ticks whose middle falls inside the traced slice."""
    bounds = slice_on_host_clock(run)
    if bounds is None:
        return []
    return [t for t in run.ticks if "t" in t and "dur_ms" in t
            and bounds[0] <= t["t"] + t["dur_ms"] / 2e3 < bounds[1]]


def wait_intervals(ticks: Iterable[Dict]) -> List[Tuple[float, float]]:
    """[start, end) on the host's clock of each tick's `wait` phase: the
    host blocked on the device's results."""
    out = []
    for t in ticks:
        if all(k in t for k in ("compose_ms", "dispatch_ms", "wait_ms")):
            start = t["t"] + (t["compose_ms"] + t["dispatch_ms"]) / 1e3
            out.append((start, start + t["wait_ms"] / 1e3))
    return out


def idle_inside(run, host_intervals: List[Tuple[float, float]]) -> float:
    """Seconds of device 0's idle gaps that lie inside the given host
    intervals (which must not overlap each other)."""
    offset = run.trace["host_minus_trace_clock_s"]
    intervals = sorted((s - offset, e - offset) for s, e in host_intervals)
    total, first = 0.0, 0
    for a, b in sorted(run.trace["device0_gaps"]):
        while first < len(intervals) and intervals[first][1] <= a:
            first += 1
        for s, e in intervals[first:]:
            if s >= b:
                break
            total += min(b, e) - max(a, s)
    return total


def self_seconds(run, wanted: Callable[[str], bool]) -> float:
    """Self time on device 0's `XLA Ops` line of the names `wanted` takes."""
    return sum(s for name, s in run.trace["device0_self_s_by_name"].items()
               if wanted(name))


def ms_per_slice_tick(run, wanted: Callable[[str], bool]) -> Optional[float]:
    """Device time of the wanted operations over the ticks in the slice."""
    ticks = slice_ticks(run)
    return 1e3 * self_seconds(run, wanted) / len(ticks) if ticks else None


def ms_per_traced_step(run, wanted: Callable[[str], bool]) -> Optional[float]:
    if not run.trace or not run.trace.get("steps_traced"):
        return None
    return 1e3 * self_seconds(run, wanted) / run.trace["steps_traced"]


def window_median(run, *fields: str) -> Optional[float]:
    """Median over the window's ticks of the sum of `fields`; ticks that
    lack one (another kind of tick, an older program) are left out."""
    xs = [sum(t[f] for f in fields) for t in run.window_ticks()
          if all(f in t for f in fields)]
    return percentile(xs, 50) if xs else None


def prefill_span_values(run, arg: Optional[str] = None) -> List[float]:
    """Of the `llm:prefill` spans of the requests due in the window: the
    duration in ms, or the argument `arg` where the program records it."""
    spans = run.window_spans("llm:prefill")
    if arg is None:
        return [s["dur"] / 1e3 for s in spans]
    return [s["args"][arg] for s in spans if arg in s["args"]]
