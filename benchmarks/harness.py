"""What every cell shares: the manifest, finding files by name, the process
environment, the device check, the run record and the result line.

Nothing here knows a cell, a configuration, a traffic mix or a metric by name.
`BENCHMARK.json` names them; their files are found by that name:

    configs/<config>.json        sizes as run, source, reduced, assumed
    families/<family>.py         adapter from a configuration file to the
                                 program's model, and its plain reference
    traffic/<traffic>.json       parameters of one traffic mix (`kind` + ...)
    e2e_metrics/<metric>.py      read(run) -> number | None
    layer_metrics/<metric>.py    read(run) -> number | None
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import statistics
import time
from typing import Any, Dict, List, Optional

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# Rings the program keeps for its own use, sized here (from outside) so that
# a whole run fits: one flight record a tick, three lifecycle spans a request.
ENGINE_RINGS = {"RAY_TPU_LLM_FLIGHT_RECORDS": "200000",
                "RAY_TPU_TRACE_BUFFER": "200000"}


def load_json(*parts: str) -> Dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_manifest() -> Dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: Dict, name: str) -> Dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json; "
                     f"have {[c['name'] for c in manifest['workloads']]}")


def metrics_of(manifest: Dict, section: str, cell_name: str) -> List[Dict]:
    """The metrics of `section` ("end_to_end" | "per_layer") that this cell
    reports: those with no `workloads` key, or with the cell in it."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_module(directory: str, name: str):
    """Import benchmarks/<directory>/<name>.py by path (names hold dots)."""
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def set_environment() -> str:
    """Before JAX or the program is imported. Returns the compile cache
    directory: the one given from outside, else a fixed path in the checkout
    (the path is part of the cache key, so it never moves)."""
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                  os.path.join(ROOT, ".jax_cache"))
    # A Pallas program's cache key holds its source locations; without this
    # it holds the whole Python call stack and no two entry points share one.
    os.environ.setdefault("JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS", "0")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for key, value in ENGINE_RINGS.items():
        os.environ[key] = value
    return cache


def require_tpu(chips: int) -> Dict:
    """The device as JAX reports it; no TPU, or fewer chips than the cell
    asks for, ends the run with no result line."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"benchmark: JAX found no accelerator: {e}")
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"benchmark: need {chips} TPU chip(s), JAX found "
                         f"{len(devs)} x {devs[0].platform}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps no
    such count, as the CPU does in a rehearsal)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def load_peaks(device_kind: str) -> Dict:
    table = load_json("peaks.json")["peaks"]
    if device_kind not in table:
        raise SystemExit(f"benchmark: no peaks known for device kind "
                         f"{device_kind!r}; peaks.json has {sorted(table)}")
    return table[device_kind]


def seed32(seed: int, salt: int = 0) -> int:
    """`--seed` may pass 2**31; fold it (with a salt for independent streams)
    into what a 32-bit key takes."""
    return (seed * 2654435761 + salt * 40503 + 12345) % (2**31 - 1)


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between order
    statistics (numpy's default), in plain Python; raises on no samples."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def iqr_share(values) -> float:
    """Distance between first and third quartile over the median, as the
    driver reads a spread (`statistics.quantiles(values, n=4)`)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


@dataclasses.dataclass
class Request:
    """One request of a serving cell, as the client saw it. Times are
    `time.time()` seconds, the clock the engine's spans and ticks use."""
    id: str
    prompt_len: int
    max_tokens: int
    due: float                       # when the traffic wanted it sent
    sent: float = 0.0                # when the client thread sent it
    token_times: List[float] = dataclasses.field(default_factory=list)
    done: Optional[float] = None     # final event
    n_tokens: int = 0                # ids in the final event
    error: Optional[str] = None


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read. A reader returns None where what
    it reads is absent, and the harness leaves that metric out."""
    kind: str                        # the traffic file's `kind`
    config: Dict                     # the configuration file
    traffic: Dict                    # the traffic file
    chips: int
    device: Dict
    peaks: Dict
    t_process_start: float
    t0: float = 0.0                  # window start
    t1: float = 0.0                  # window end
    # serving
    requests: List[Request] = dataclasses.field(default_factory=list)
    stats_before: Dict = dataclasses.field(default_factory=dict)
    stats_after: Dict = dataclasses.field(default_factory=dict)
    ticks: List[Dict] = dataclasses.field(default_factory=list)
    spans: List[Dict] = dataclasses.field(default_factory=list)
    late_ms: List[float] = dataclasses.field(default_factory=list)
    # training
    steps: List[Dict] = dataclasses.field(default_factory=list)
    traced_steps: List[Dict] = dataclasses.field(default_factory=list)
    tokens_per_step: int = 0
    flops_per_token: float = 0.0
    # traced run: trace_reduce.reduce()'s result over the traced sub-window
    trace: Optional[Dict] = None
    checks: Dict = dataclasses.field(default_factory=dict)
    problems: List[str] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.t0 <= t <= self.t1

    def window_requests(self) -> List[Request]:
        """Requests that were due inside the window: the attempted ones."""
        return [r for r in self.requests if self.in_window(r.due)]

    def window_ticks(self) -> List[Dict]:
        return [t for t in self.ticks if self.in_window(t.get("t"))]

    def window_spans(self, name: str) -> List[Dict]:
        """Lifecycle spans called `name` of the requests due in the window,
        keyed by nothing: a list (span `ts` and `dur` are microseconds)."""
        ids = {r.id for r in self.window_requests()}
        return [s for s in self.spans if s["name"] == name
                and s["args"].get("request_id") in ids]


def new_run(ctx, **fields) -> Run:
    """The run record of a cell, from what run.py gave its runner."""
    return Run(kind=ctx.traffic["kind"], config=ctx.config,
               traffic=ctx.traffic, chips=ctx.chips, device=ctx.device,
               peaks=ctx.peaks, t_process_start=ctx.t_process_start, **fields)


def read_metrics(run: Run, directory: str, wanted: List[Dict]) -> Dict:
    """Each wanted metric through its own reader; absent readings left out."""
    out = {}
    for m in wanted:
        value = load_module(directory, m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def traced(trace_dir: str, body) -> Optional[Dict]:
    """Run `body()` under the profiler and reduce the trace. A marker with
    the host's clock beside it ties the trace's clock to spans and ticks."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        mark = time.time()
        with jax.profiler.TraceAnnotation(trace_reduce.CLOCK_MARK):
            pass
        body()
    finally:
        jax.profiler.stop_trace()
    reduced = trace_reduce.reduce_dir(trace_dir, mark_host_time=mark)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return reduced


def note(**fields: Any) -> None:
    """An earlier line of the run's output: worth keeping, not the result."""
    print(json.dumps({"t": round(time.time(), 3), **fields}, default=str),
          flush=True)
