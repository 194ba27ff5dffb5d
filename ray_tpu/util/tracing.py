"""Task tracing: spans around submit/execute, optional OpenTelemetry export.

Reference analog: python/ray/util/tracing/tracing_helper.py (lazy otel import
:36-57; @_tracing_task_invocation wrapping RemoteFunction._remote at
remote_function.py:302). The TPU build records spans into an in-process ring
buffer always (cheap), and mirrors them to OpenTelemetry when the user has
opentelemetry-sdk installed and tracing enabled; ``ray_tpu.scripts timeline``
dumps the ring as a chrome://tracing JSON file.
"""

from __future__ import annotations

import collections
import gc
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Optional

_MAX_SPANS = int(os.environ.get("RAY_TPU_TRACE_BUFFER", "10000"))
_spans = collections.deque(maxlen=_MAX_SPANS)
_lock = threading.Lock()
_enabled = os.environ.get("RAY_TPU_TRACING", "1") != "0"

_otel_tracer = None

# -- cross-process trace context ---------------------------------------------
# W3C-traceparent-shaped propagation (tracing_helper.py:_inject_tracing
# analog, minus the otel hard dependency): every span mints an 8-byte span
# id and joins the thread's current trace (minting a 16-byte trace id at
# the root). submit_task copies the caller's (trace_id, span_id) into the
# TaskSpec wire envelope (TaskSpecMsg fields 17/18); the executing worker
# adopts them via trace_context() so the execute span — and any spans the
# task body opens, including nested submits — carry the same trace id and
# parent-link back to the driver-side submit span. Stitching is by id, not
# wall clock, so it survives process boundaries and clock skew.
_ctx = threading.local()


def request_trace_id(request_id: str) -> bytes:
    """Deterministic 16-byte trace id for one LLM serving request.

    Derived from crc32(request_id) — the same function the engine seeds
    sampling from — so EVERY process that handles the request (router,
    prefill replica, decode replica, migration target, the CLI after the
    fact) computes the identical trace id from the rid alone. Stitching a
    request's spans across failover replays and live migration therefore
    needs no side channel: the rid is the trace identity; the disagg wire
    only carries parent-span linkage."""
    import zlib

    rid = request_id.encode()
    return b"".join(
        zlib.crc32(rid + bytes([i])).to_bytes(4, "big") for i in range(4))


def current_trace_id() -> Optional[bytes]:
    return getattr(_ctx, "trace_id", None)


def current_span_id() -> Optional[bytes]:
    return getattr(_ctx, "span_id", None)


@contextmanager
def trace_context(trace_id: Optional[bytes],
                  parent_span_id: Optional[bytes]):
    """Adopt a propagated (trace_id, parent_span_id) pair — the executor
    side of the TaskSpec trace fields. Spans opened inside parent to the
    propagated span id; the previous thread context is restored on exit."""
    prev = (getattr(_ctx, "trace_id", None), getattr(_ctx, "span_id", None))
    _ctx.trace_id = trace_id
    _ctx.span_id = parent_span_id
    try:
        yield
    finally:
        _ctx.trace_id, _ctx.span_id = prev


def _get_otel():
    """Lazy optional OpenTelemetry tracer (absent in the base image)."""
    global _otel_tracer
    if _otel_tracer is None:
        try:
            from opentelemetry import trace  # type: ignore
            _otel_tracer = trace.get_tracer("ray_tpu")
        except Exception:
            _otel_tracer = False
    return _otel_tracer or None


def enabled() -> bool:
    return _enabled


def set_enabled(value: bool):
    """Off: no span is written and the compile ledger's listeners are taken
    off `jax.monitoring` (the next `watch_compiles()` after on puts them
    back)."""
    global _enabled
    _enabled = value
    if not value:
        _unwatch_compiles()


@contextmanager
def span(name: str, kind: str, **attrs):
    """Record one span; nests naturally via wall-clock containment. Yields
    the span's arguments: the body may add what is known only at its end."""
    if not _enabled:
        yield attrs
        return
    otel = _get_otel()
    ctx = otel.start_as_current_span(name) if otel else None
    if ctx is not None:
        ctx.__enter__()
    trace_id = getattr(_ctx, "trace_id", None) or os.urandom(16)
    parent = getattr(_ctx, "span_id", None)
    span_id = os.urandom(8)
    prev = (getattr(_ctx, "trace_id", None), getattr(_ctx, "span_id", None))
    _ctx.trace_id, _ctx.span_id = trace_id, span_id
    start = time.time()
    try:
        yield attrs
    finally:
        _ctx.trace_id, _ctx.span_id = prev
        end = time.time()
        ids = {"trace_id": trace_id.hex(), "span_id": span_id.hex()}
        if parent is not None:
            ids["parent_span_id"] = parent.hex()
        with _lock:
            _spans.append({"name": name, "cat": kind, "ts": start * 1e6,
                           "dur": (end - start) * 1e6, "ph": "X",
                           "pid": os.getpid(),
                           "tid": threading.get_ident() % 100000,
                           "args": {**ids, **attrs}})
        if ctx is not None:
            ctx.__exit__(None, None, None)


def record_span(name: str, kind: str, start: float, end: float, **attrs):
    """Append a span retroactively from measured wall-clock bounds.

    For code that times phases itself (e.g. Train closes a step record at
    `session.report()` — the step's extent is only known after the fact).
    The span joins the thread's current trace context exactly like
    `span()` would."""
    if not _enabled:
        return
    trace_id = getattr(_ctx, "trace_id", None) or os.urandom(16)
    parent = getattr(_ctx, "span_id", None)
    ids = {"trace_id": trace_id.hex(), "span_id": os.urandom(8).hex()}
    if parent is not None:
        ids["parent_span_id"] = parent.hex()
    with _lock:
        _spans.append({"name": name, "cat": kind, "ts": start * 1e6,
                       "dur": max(0.0, end - start) * 1e6, "ph": "X",
                       "pid": os.getpid(),
                       "tid": threading.get_ident() % 100000,
                       "args": {**ids, **attrs}})


class PhaseClock:
    """The host's clock for consecutive phases of one piece of hot-loop work
    (an engine tick), and the same phases as `jax.profiler.TraceAnnotation`s.

    `with PhaseClock("llm:tick") as clock:` holds an annotation `llm:tick`
    for the block; `clock.mark("compose")` ends the phase before it, enters
    `llm:tick:compose` and returns `time.time()` for the caller's record (the
    flight recorder), so the record and the profiler's host plane cut the
    work at the same instants. Outside a profiling session an annotation
    is a no-op in C++; inside one it lands on the host plane of the trace
    that holds the device's lines, on the same clock. Not a second ring:
    nothing is stored here."""

    __slots__ = ("_name", "_annotation", "_outer", "_phase", "phase_start")

    def __init__(self, name: str):
        from jax.profiler import TraceAnnotation

        self._name = name
        self._annotation = TraceAnnotation
        self._outer = self._phase = None
        self.phase_start = 0.0      # what the latest mark() returned

    def __enter__(self) -> "PhaseClock":
        self._outer = self._annotation(self._name)
        self._outer.__enter__()
        return self

    def mark(self, phase: Optional[str]) -> float:
        """End the current phase, begin `phase` (None: none), return now."""
        if self._phase is not None:
            self._phase.__exit__(None, None, None)
            self._phase = None
        if phase is not None:
            self._phase = self._annotation(f"{self._name}:{phase}")
            self._phase.__enter__()
        self.phase_start = time.time()
        return self.phase_start

    def __exit__(self, *exc) -> None:
        self.mark(None)
        self._outer.__exit__(*exc)


# -- the collector's clock ----------------------------------------------------
# Full passes of the cycle collector hold the GIL for 100 ms and more in a
# process with millions of tracked objects, on WHICHEVER thread allocates
# the object that starts one: a thread that waits for the device beside it
# cannot return until the pass ends. One `gc.callbacks` entry a process keeps
# the passes of 1 ms or more (host clock, `time.time()`), so whoever holds an
# interval can ask how much of it the collector took (the engine tick's
# `gc_ms`, llm/engine.py). Passes never overlap (a collection is not
# re-entrant and runs with the GIL held), so one start time is enough.
_GC_MIN_S = 1e-3
_gc_passes: collections.deque = collections.deque(maxlen=256)
_gc_started: Optional[float] = None


def _on_collection(phase: str, info: dict) -> None:
    global _gc_started
    if phase == "start":
        _gc_started = time.time()
    elif _gc_started is not None:
        now = time.time()
        if now - _gc_started >= _GC_MIN_S:
            _gc_passes.append((_gc_started, now, info.get("generation")))
        _gc_started = None


def watch_collector() -> None:
    """Install the collector's clock, once a process (first use)."""
    if _on_collection not in gc.callbacks:
        gc.callbacks.append(_on_collection)


def collector_passes() -> list:
    """`(t_start, t_stop, generation)` of the kept passes, oldest first."""
    return list(_gc_passes)


def collector_seconds(a: float, b: float) -> float:
    """Seconds of kept collector passes that overlap the host interval
    [a, b]. A copy is taken in one call (a pass on another thread may append
    meanwhile); the common case, no pass since `a`, copies nothing."""
    if not _gc_passes or _gc_passes[-1][1] <= a:
        return 0.0
    total = 0.0
    for start, stop, _ in reversed(list(_gc_passes)):
        if stop <= a:
            break
        total += max(0.0, min(stop, b) - max(start, a))
    return total


# -- the compile ledger -------------------------------------------------------
# Where a program's way to the device goes: JAX reports each stage of it to
# `jax.monitoring` (a start as a scalar, the end as a time span, both stamped
# with `time.time()`, the spans' clock), and one set of listeners a process
# keeps seconds and counts by stage: `trace` (Python to a jaxpr), `lower`
# (jaxpr to MLIR: Mosaic's lowering of every Pallas call is in here),
# `compile` (the backend's compile, less the persistent cache's read where
# it hit), `cache_read` (that read), the backend's `compiles`, and the
# persistent cache's hits and misses (JAX counts as a miss a program it WRITES
# to the cache: one that compiled in under the cache's minimum compile time is
# neither, and compiles again at every start). Stages
# nest (a jitted function traced inside another's trace, a helper traced
# inside a lowering): a stage's OWN seconds are its extent less what nests
# inside it, so the stages of an interval add up to no more than the interval.
# Totals only: whoever brackets a piece of work reads them before and after
# (`compile_since`). A callback is a few additions under a lock of its own;
# nothing runs where nothing compiles.
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
COMPILE_STAGE_KEYS = ("trace_s", "lower_s", "compile_s", "cache_read_s")
_compile_totals = dict.fromkeys(COMPILE_STAGE_KEYS, 0.0)
_compile_totals.update(compiles=0, cache_hits=0, cache_misses=0)
_compile_lock = threading.Lock()
_compile_open = threading.local()   # .frames: the stages this thread is in
_compile_watched = False


def _open_frames() -> list:
    frames = getattr(_compile_open, "frames", None)
    if frames is None:
        frames = _compile_open.frames = []
    return frames


def _on_stage_start(event: str, start: float, **_) -> None:
    if event in _COMPILE_STAGES:    # [start, nested s, cache read s]
        _open_frames().append([start, 0.0, 0.0])


def _on_cache_event(event: str, **_) -> None:
    key = _CACHE_EVENTS.get(event)
    if key is None:
        return
    with _compile_lock:
        _compile_totals[key] += 1


def _on_cache_read(event: str, seconds: float, **_) -> None:
    if event == _CACHE_READ:
        frames = _open_frames()
        if frames:
            frames[-1][2] += seconds


def _on_stage_end(event: str, start: float, end: float, **_) -> None:
    stage = _COMPILE_STAGES.get(event)
    if stage is None:
        return
    frames = _open_frames()
    nested, read = 0.0, 0.0
    while frames:       # frames above its own: stages an error left open
        frame = frames.pop()
        if frame[0] == start:
            _, nested, read = frame
            break
    if frames:
        frames[-1][1] += end - start
    own = max(0.0, end - start - nested - read)
    with _compile_lock:
        _compile_totals[stage + "_s"] += own
        _compile_totals["cache_read_s"] += read
        if stage == "compile":
            _compile_totals["compiles"] += 1


def watch_compiles() -> bool:
    """Install the compile ledger, once a process (first use); False, and
    nothing registered, where tracing is off."""
    global _compile_watched
    if _enabled and not _compile_watched:
        from jax import monitoring

        monitoring.register_scalar_listener(_on_stage_start)
        monitoring.register_event_listener(_on_cache_event)
        monitoring.register_event_duration_secs_listener(_on_cache_read)
        monitoring.register_event_time_span_listener(_on_stage_end)
        _compile_watched = True
    return _compile_watched


def _unwatch_compiles() -> None:
    global _compile_watched
    if _compile_watched:
        from jax import monitoring

        monitoring.unregister_scalar_listener(_on_stage_start)
        monitoring.unregister_event_listener(_on_cache_event)
        monitoring.unregister_event_duration_listener(_on_cache_read)
        monitoring.unregister_event_time_span_listener(_on_stage_end)
        _compile_watched = False


def compile_totals() -> dict:
    """The ledger's running totals since the process began: own seconds by
    stage (`COMPILE_STAGE_KEYS`), `compiles` (the backend's, read from the
    cache or not), `cache_hits`, `cache_misses`. Read before and after a
    piece of work (`compile_since`): exact, short stages included."""
    with _compile_lock:
        return dict(_compile_totals)


def compile_since(before: dict) -> dict:
    """What the ledger gained since `before = compile_totals()`."""
    now = compile_totals()
    return {key: now[key] - before[key] for key in now}


def stage_args(gained: dict) -> dict:
    """A span's arguments from `gained = compile_since(...)`: the four
    stages' seconds, the backend's `compiles`, and `cache_hit`: the
    persistent cache gave a program and none was written to it."""
    args = {key: round(gained[key], 4) for key in COMPILE_STAGE_KEYS}
    args["compiles"] = gained["compiles"]
    args["cache_hit"] = (gained["cache_hits"] > 0
                         and not gained["cache_misses"])
    return args


def get_spans() -> list:
    with _lock:
        return list(_spans)


def dump_chrome_trace(path: str):
    """Write the span ring in chrome://tracing 'traceEvents' format
    (the `ray timeline` CLI analog)."""
    with open(path, "w") as f:
        json.dump({"traceEvents": get_spans()}, f)


def merge_spans(groups) -> list:
    """Merge per-process span rings into one chrome traceEvents list.

    `groups` is an iterable of (label, spans) — one entry per process, as
    returned by the cluster `dump_spans` fan-out. os.getpid() collides
    across hosts, so every (label, original pid) pair is remapped to a
    unique lane and announced with a process_name metadata event; the
    trace/span ids in each span's `args` are untouched — they are what
    stitches submit -> execute -> nested submit across lanes."""
    events, lanes = [], {}
    for label, spans in groups:
        for s in spans:
            key = (label, s.get("pid"))
            lane = lanes.get(key)
            if lane is None:
                lane = lanes[key] = len(lanes) + 1
                events.append({"ph": "M", "name": "process_name",
                               "pid": lane,
                               "args": {"name": f"{label} (pid {s.get('pid')})"}})
            ev = dict(s)
            ev["pid"] = lane
            events.append(ev)
    return events
