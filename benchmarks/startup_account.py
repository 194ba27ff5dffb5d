"""What the readers of a replica's start-up account share (PR 55): the
`llm:startup` span of a run, a reading as `samples`, and the table of where
`setup_s` goes.

`serving.build_engine` writes a span `llm:startup` over its call whose
arguments are the account (`total_s`, `params_s`, `place_s`, `warmup_s`,
`other_s`, the compile ledger's `trace_s` / `lower_s` / `compile_s` /
`cache_read_s` over the call, `programs`, `compiles`, `cache_hits`,
`cache_misses`, `device_tail_s`), and under it (by `parent_span_id`)
`llm:startup:params`, `llm:startup:place` and `llm:startup:warmup`, whose
arguments hold the ledger's four stages over the warm-up and its
`device_tail_s`. `serve_cell.py` keeps every `llm:*` span of the ring in `run.spans` (`ts` and `dur` in microseconds, the host's
clock).

A run of a program older than PR 55 has no such span: every function here
then returns None, the readers with it, and the six readers of the spans have
no entry in `BENCHMARK.json` until a parent feeds them (`run.py` calls a run
incorrect when a LISTED reader returns None). The two that read what the
parent keeps (`stats()["warmup_s"]`, a flight record's `t`) are listed. Each
reader's `samples(run)` is its one reading, so every run's log holds it
(`run.py` notes the `samples` of every file in the folder, listed or not,
traced or not).
"""

from __future__ import annotations

from typing import Dict, List, Optional

STAGES = ("trace_s", "lower_s", "compile_s", "cache_read_s")


def startup_span(run) -> Optional[Dict]:
    """The run's `llm:startup` span (the first, where a process built more
    than one engine), or None."""
    spans = [s for s in run.spans if s["name"] == "llm:startup"]
    return min(spans, key=lambda s: s["ts"]) if spans else None


def startup_arg(run, *keys: str) -> Optional[float]:
    """The sum of arguments `keys` of the run's `llm:startup` span, or None
    where the run has no such span or the span lacks one of them."""
    span = startup_span(run)
    if span is None or any(key not in span["args"] for key in keys):
        return None
    return sum(span["args"][key] for key in keys)


def children(run, name: str) -> List[Dict]:
    """The spans called `name` directly under the run's `llm:startup`."""
    top = startup_span(run)
    if top is None:
        return []
    return [s for s in run.spans if s["name"] == name
            and s["args"].get("parent_span_id") == top["args"]["span_id"]]


def first_tick(run) -> Optional[float]:
    """The `t` of the run's first flight record: the engine's first tick."""
    ts = [t["t"] for t in run.ticks if "t" in t]
    return min(ts) if ts else None


def one(value) -> List[float]:
    """A reading as `samples`: `[value]`, or `[]` where there is none."""
    return [] if value is None else [float(value)]


def table(run) -> Optional[Dict[str, float]]:
    """Where `setup_s` goes: consecutive intervals of the host's clock from
    the process's start to the window's, in seconds, in order. `before` (the
    interpreter, imports, the configuration: up to `build_engine`), `params`,
    `place`, then the warm-up split by the ledger's stages over it
    (`warmup_trace_lower`, `warmup_compile`, `warmup_cache_read`: the
    `llm:startup:warmup` span's arguments; zeros where warm-up was off),
    `warmup_dispatch` (what the stages and the tail leave of it) and
    `device_tail` (its one closing wait), `other` (the rest of
    `build_engine`), `checks` (from ready to the first flight record: the
    server's construction and the logits check, which calls `runner.step`
    with no tick) and `traffic` (`setup_traffic_s`). `sum` is their sum and
    `setup_s` what the harness reports. None without the span or a tick."""
    top, tick = startup_span(run), first_tick(run)
    if top is None or tick is None:
        return None
    a = top["args"]
    ts, end = top["ts"] / 1e6, (top["ts"] + top["dur"]) / 1e6
    warm = children(run, "llm:startup:warmup")
    stage = {key: sum(w["args"][key] for w in warm) for key in STAGES}
    parts = {
        "before": ts - run.t_process_start,
        "params": a["params_s"], "place": a["place_s"],
        "warmup_trace_lower": stage["trace_s"] + stage["lower_s"],
        "warmup_compile": stage["compile_s"],
        "warmup_cache_read": stage["cache_read_s"],
        "warmup_dispatch": (a["warmup_s"] - sum(stage.values())
                            - a["device_tail_s"]),
        "device_tail": a["device_tail_s"],
        "other": top["dur"] / 1e6 - a["params_s"] - a["place_s"]
        - a["warmup_s"],
        "checks": tick - end,
        "traffic": run.t0 - tick,
    }
    parts["sum"] = sum(parts.values())
    parts["setup_s"] = run.t0 - run.t_process_start
    return parts
