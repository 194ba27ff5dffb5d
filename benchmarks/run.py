"""Run one cell of the benchmark once, on the TPU this machine holds:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run is a new process. It fails, with no result line, where JAX finds no TPU
or fewer chips than the cell asks for: there is no CPU mode under this
command (`rehearse.py` is the CPU rehearsal, and prints no result line
either). It builds the system from `--seed`, checks it against the plain
reference, warms up, measures for `--seconds`, and prints as its LAST line one
JSON object: `correct`, `attempted`, `failed`, `metrics`, `device`, and in a
traced run `breakdown`. With `--trace 0` the metrics are the cell's end-to-end
metrics, with `--trace 1` its per-layer metrics. Earlier lines are notes.

The cell, its configuration, its traffic and its metrics are found by name
from `BENCHMARK.json`; see `harness.py` and `README.md`.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse     # noqa: E402
import dataclasses  # noqa: E402
import importlib    # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402
from typing import Dict, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness          # noqa: E402
import trace_reduce     # noqa: E402


@dataclasses.dataclass
class Context:
    """What a cell's runner is given."""
    workload: str
    seed: int
    seconds: float
    trace: bool
    chips: int
    config: Dict
    traffic: Dict
    device: Dict
    peaks: Dict
    out_dir: str
    t_process_start: float
    require_kernels: bool = True    # False only in the CPU rehearsal


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="override one traffic parameter (a rate sweep by "
                         "hand; never used by a check)")
    return ap.parse_args(argv)


def execute(args, *, rehearsal: Optional[Dict] = None) -> Dict:
    """One run; returns the result object. `rehearsal` (from rehearse.py)
    replaces sizes and skips the TPU requirement; it is never a result."""
    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        # never fall back to some installed copy of the program
        raise SystemExit("benchmark: no ray_tpu/ in this checkout: nothing "
                         "to measure")
    manifest = harness.load_manifest()
    cell = harness.find_cell(manifest, args.workload)
    config = harness.load_json("configs", cell["config"] + ".json")
    traffic = harness.load_json("traffic", cell["traffic"] + ".json")
    for item in args.set:
        key, value = item.split("=", 1)
        traffic[key] = json.loads(value)
    cache_dir = harness.set_environment()
    if rehearsal is not None:    # after the environment: it loads the family
        rehearsal["shrink"](config, traffic)

    import jax

    if rehearsal is None:
        device = harness.require_tpu(cell["chips"])
    else:
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": cell["chips"]}
    peaks = (harness.load_peaks(device["kind"]) if rehearsal is None
             else {"bf16_flops_per_s": float("nan")})
    out_dir = os.path.join(ROOT, ".bench_out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    harness.note(phase="start", workload=cell["name"], seed=args.seed,
                 seconds=args.seconds, trace=args.trace, device=device,
                 compile_cache=cache_dir,
                 cache_entries=len(os.listdir(cache_dir))
                 if os.path.isdir(cache_dir) else 0)

    ctx = Context(workload=cell["name"], seed=args.seed,
                  seconds=args.seconds, trace=bool(args.trace),
                  chips=cell["chips"], config=config, traffic=traffic,
                  device=device, peaks=peaks, out_dir=out_dir,
                  t_process_start=T_PROCESS_START,
                  require_kernels=rehearsal is None)
    runner = importlib.import_module(traffic["runner"])
    run = runner.run_cell(ctx)

    section, directory = (("per_layer", "layer_metrics") if args.trace
                          else ("end_to_end", "e2e_metrics"))
    wanted = harness.metrics_of(manifest, section, cell["name"])
    metrics = harness.read_metrics(run, directory, wanted)
    missing = [m["name"] for m in wanted if m["name"] not in metrics]
    if missing:
        run.problems.append(f"metrics with nothing to read: {missing}")
    attempted, failed = runner.attempted_failed(run)
    if failed:
        run.problems.append(f"{failed} of {attempted} failed")
    if not attempted:
        run.problems.append("nothing was attempted inside the window")
    for folder in ("e2e_metrics", "layer_metrics"):
        for name in sorted(os.listdir(os.path.join(HERE, folder))):
            if not name.endswith(".py"):
                continue
            module = harness.load_module(folder, name[:-3])
            xs = module.samples(run) if hasattr(module, "samples") else None
            if xs:
                harness.note(metric=name[:-3], samples=len(xs),
                             p50=harness.percentile(xs, 50),
                             p95=harness.percentile(xs, 95), max=max(xs))
    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes(
        jax.devices()[:cell["chips"]]))
    result = {"correct": not run.problems, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace_reduce.top(
                run.trace["device0_self_s_by_name"], 10),
            "idle_gaps": trace_reduce.label_gaps(
                run.trace, runner.host_intervals(run), 5)}
    elif args.trace:
        run.problems.append("the traced slice holds no device operation")
        result["correct"] = False
    if run.problems:
        harness.note(problems=run.problems)
    # Each number compared beside its limit, as the last lines on standard
    # error too: what the driver's record keeps of a run that is not correct.
    for name, check in run.checks.items():
        print(json.dumps({"check": name, **check}, default=str),
              file=sys.stderr)
    print(json.dumps({"correct": result["correct"], "attempted": attempted,
                      "failed": failed, "problems": run.problems}),
          file=sys.stderr, flush=True)
    return result


def main() -> None:
    args = parse_args()
    result = execute(args)
    print(json.dumps(result), flush=True)
    sys.stderr.flush()
    # The engine's loop and the clients' streams are daemon threads, some
    # blocked on queues for good; leave without running their finalizers.
    os._exit(0)


if __name__ == "__main__":
    main()
