"""From a profiler trace (`.xplane.pb`) to three things, and no more: the
busy union of each device plane, event time grouped by name, and the gaps.

What a TPU trace holds (looked at by hand, PR 25): one plane per chip called
`/device:TPU:<n>`, whose line `XLA Ops` has one event per executed HLO
operation, named by its HLO text (`%fusion.3 = bf16[...] fusion(...)`), with
control flow nested (a `%while` event encloses its body's events), and whose
line `Async XLA Ops` has one event per asynchronous operation from its start
to its done (copies, and across chips the collectives). Times are nanoseconds
from the start of the profiling session, on the host planes too; a
`TraceAnnotation` called CLOCK_MARK, written by the harness with the host's
`time.time()` beside it, ties that clock to the one spans and ticks use.

Read with `jax.profiler.ProfileData` and nothing else.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

CLOCK_MARK = "bench_clock_mark"
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all")

Event = Tuple[float, float, str]      # start s, duration s, name


def op_name(text: str) -> str:
    """`%fusion.3 = bf16[8]{0} fusion(...)` -> `fusion.3`."""
    return text.split(" = ", 1)[0].lstrip("%")[:96]


def busy_union(events: List[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals in which some event ran."""
    merged: List[List[float]] = []
    for start, dur, _ in sorted(events):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return [(a, b) for a, b in merged]


def gaps(busy: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The idle intervals between consecutive busy intervals."""
    return [(a_end, b_start) for (_, a_end), (b_start, _)
            in zip(busy, busy[1:]) if b_start > a_end]


def self_time_by_name(events: List[Event]) -> Dict[str, float]:
    """Seconds by name on one line, an enclosing event (a `while`, a `call`)
    counting only the time its nested events do not cover."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    self_s = [e[1] for e in events]
    stack: List[Tuple[float, int]] = []        # (end, index)
    for i in order:
        start, dur, _ = events[i]
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:       # the part of it inside the enclosing event
            self_s[stack[-1][1]] -= min(start + dur, stack[-1][0]) - start
        stack.append((start + dur, i))
    out: Dict[str, float] = defaultdict(float)
    for (_, _, name), s in zip(events, self_s):
        out[name] += max(s, 0.0)
    return dict(out)


def is_collective(name: str) -> bool:
    return name.startswith(COLLECTIVES)


def _line_events(plane, line_name: str) -> List[Event]:
    return [(e.start_ns * 1e-9, e.duration_ns * 1e-9, op_name(e.name))
            for line in plane.lines if line.name == line_name
            for e in line.events]


def reduce_file(path: str,
                mark_host_time: Optional[float] = None) -> Optional[Dict]:
    """The reduction of one trace file, or None if no device operation is in
    it. `window` is from the first device operation's start to the last one's
    end over all chips; `busy_s` is the busy union inside it, averaged over
    the chips; device 0's gaps and names are kept for the breakdown."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    asyncs: Dict[int, List[Event]] = {}
    mark_trace_time = None
    for plane in data.planes:
        m = DEVICE_PLANE.fullmatch(plane.name)
        if m:
            ops[int(m.group(1))] = _line_events(plane, OPS_LINE)
            asyncs[int(m.group(1))] = _line_events(plane, ASYNC_LINE)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == CLOCK_MARK:
                        mark_trace_time = e.start_ns * 1e-9
    ops = {d: ev for d, ev in ops.items() if ev}
    if not ops:
        return None
    start = min(e[0] for ev in ops.values() for e in ev)
    end = max(e[0] + e[1] for ev in ops.values() for e in ev)
    busy = {d: busy_union(ev) for d, ev in ops.items()}
    busy_s = {d: sum(b - a for a, b in iv) for d, iv in busy.items()}
    first = min(ops)
    # Edge gaps count too: a chip that starts late or ends early was idle.
    edge = [(start, busy[first][0][0]), (busy[first][-1][1], end)]
    offset = (mark_host_time - mark_trace_time
              if mark_host_time is not None and mark_trace_time is not None
              else None)
    # Synchronous collectives sit on the ops line under their own name; an
    # asynchronous one is one event on the async line from start to done.
    collective = [e for e in ops[first] if is_collective(e[2])
                  and not e[2].split(".")[0].endswith(("-start", "-done"))]
    collective += [e for e in asyncs.get(first, []) if is_collective(e[2])]
    return {
        "chips_traced": len(ops),
        "window_s": end - start, "window_start_s": start,
        "busy_s": sum(busy_s.values()) / len(busy_s),
        "busy_s_by_device": busy_s,
        "device0": first,
        "device0_gaps": [g for g in gaps(busy[first]) + edge if g[1] > g[0]],
        "device0_self_s_by_name": self_time_by_name(ops[first]),
        "device0_collective_s": sum(e[1] for e in collective),
        "device0_collective_events": len(collective),
        "host_minus_trace_clock_s": offset,
    }


def reduce_dir(trace_dir: str,
               mark_host_time: Optional[float] = None) -> Optional[Dict]:
    """The newest `.xplane.pb` under a `jax.profiler.start_trace` directory."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return reduce_file(files[-1], mark_host_time) if files else None


def top(named_seconds: Dict[str, float], n: int) -> List[List]:
    return [[k, v] for k, v in sorted(named_seconds.items(),
                                      key=lambda kv: -kv[1])[:n]]


def label_gaps(reduced: Dict, host_intervals: List[Tuple[float, float, str]],
               n: int) -> List[List]:
    """Device 0's idle seconds grouped by what the host was doing: each gap
    goes to the host interval (start, end, label; host clock) that covers its
    middle, else to "outside"; the n labels with most idle time."""
    offset = reduced.get("host_minus_trace_clock_s")
    by_label: Dict[str, float] = defaultdict(float)
    intervals = sorted(host_intervals)
    for a, b in reduced["device0_gaps"]:
        label = "unlabelled"
        if offset is not None:
            mid = (a + b) / 2.0 + offset
            label = next((lab for s, e, lab in intervals if s <= mid < e),
                         "outside")
        by_label[label] += b - a
    return top(by_label, n)
