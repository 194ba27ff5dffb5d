"""What the readers of the engine's time account share (PR 37): a tick's
period, which periods of a window are long, and the growth of the account's
stalls over the window.

The flight record keeps `since_prev_ms`, `admit_ms` and `dur_ms` since PR 26
and `settled` since PR 34, so `tick_tail_ms.window` reads a parent's run as it
reads this program's. `engine.stats()["time"]` is PR 37's: a run of an older
program has no such key, `stalls_ms` then returns None, and the four readers
of it have no entry in `BENCHMARK.json` until a parent feeds them (`run.py`
calls a run incorrect when a LISTED reader returns None).

The rule is the engine's (`ray_tpu/llm/engine.py`, `long_tick_excess`), with
the WINDOW's own median where the engine has the last 128 periods': a period
is long where it exceeds twice the median by 20 ms or more, and its excess is
what it has over the median.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

from harness import percentile

STALL_FACTOR = 2.0
STALL_FLOOR_MS = 20.0


def period_ms(t: Dict) -> Optional[float]:
    """The five phases of a `step()` call and the loop before it, ms; the
    first step after an idle engine (`settled: idle`) and the call that only
    lands the last one are counted without `since_prev_ms`, which may be an
    idle engine's wait for a request. None for a tick without the fields
    (another kind of record, a program older than PR 26)."""
    if not all(k in t for k in ("since_prev_ms", "admit_ms", "dur_ms")):
        return None
    loop = 0.0 if t.get("settled") == "idle" else t["since_prev_ms"]
    return loop + t["admit_ms"] + t["dur_ms"]


def long_excesses(ticks: List[Dict]) -> Optional[List[float]]:
    """The excess over the median period of each long tick of `ticks` (an
    empty list where none is long), or None where no tick has a period."""
    periods = [p for p in map(period_ms, ticks) if p is not None]
    if not periods:
        return None
    median = percentile(periods, 50)
    return [p - median for p in periods
            if p - STALL_FACTOR * median >= STALL_FLOOR_MS]


def stalls_ms(run, wanted: Callable[[str], bool]) -> Optional[float]:
    """Milliseconds the engine put down to the causes `wanted` takes, inside
    the window: `stats()["time"]["stalls"]` at its end less at its start."""
    if "time" not in run.stats_after or "time" not in run.stats_before:
        return None
    before = run.stats_before["time"]["stalls"]
    total = 0.0
    for cause, entry in run.stats_after["time"]["stalls"].items():
        if wanted(cause):
            total += entry["seconds"] - before.get(cause, {}).get(
                "seconds", 0.0)
    return 1e3 * total
