"""Self-test of the reader PR 53 added, `expert_product_ms.tick`: the held
experts' grouped products by either implementation's event names, on a
made-up run whose value is worked out by hand.

    python -m pytest benchmarks/tests -q
"""

import json
import os

import pytest

import harness

NAME = "expert_product_ms.tick"
ROUTED = ["deepseekv2-docqa-closed32", "mimov2flash-longdoc-closed32",
          "kimilinear-longout-closed64", "glm52-longdoc-closed32",
          "nemotron3super-longout-closed64"]


def _read(run, name=NAME):
    return harness.load_module("layer_metrics", name).read(run)


def _run(ops):
    run = harness.Run(
        kind="closed", config={"sizes": {}, "family": "nemotron_h"},
        traffic={}, chips=1, device={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = [{"t": 1009.9 + 0.05 * i, "dur_ms": 45.0, "kind": "mixed",
                  "kv_tokens": 64_000} for i in range(4)]
    run.trace = {"window_start_s": 9.94, "window_s": 0.1, "busy_s": 0.09,
                 "host_minus_trace_clock_s": 1000.0, "device0_gaps": [],
                 "device0_self_s_by_name": ops}
    return run


OTHERS = {"ssd_call.7": 0.010, "tpu_custom_call.3": 0.0004,
          "paged_attention_kv_rows_call.2": 0.0002, "fusion.1": 0.03,
          "copy.3": 0.001}


@pytest.mark.parametrize("ops,expected", [
    # ticks 1 and 2 are in the slice. XLA's products alone (the parent's
    # program, and the cells whose shapes keep it)
    ({"ragged-dot-none": 0.004, "ragged-dot-none.7": 0.006}, 5.0),
    # the kernel's events, by the jitted entry's name
    ({"grouped_dot_call.3": 0.002, "grouped_dot_call": 0.001}, 1.5),
    # a program that holds both, and the kernel under its tag's name (a
    # program compiled with the tracebacks in)
    ({"ragged-dot-none.2": 0.004, "grouped_dot_call.1": 0.001,
      "grouped_dot.4": 0.001}, 3.0),
])
def test_reader_sums_both_implementations(ops, expected):
    assert _read(_run({**OTHERS, **ops})) == pytest.approx(expected)


def test_reader_finds_nothing_without_products_or_trace():
    assert _read(_run(dict(OTHERS))) is None
    run = _run({"ragged-dot-none": 0.004})
    run.trace = None
    assert _read(run) is None


def test_the_kernels_events_are_no_paged_kernels():
    """`paged_kernel_ms.tick` (and the share over it) sums `tpu_custom_call*`
    and `paged_attention_*`: the grouped kernel's events are neither."""
    ops = {**OTHERS, "grouped_dot_call.3": 0.5, "ragged-dot-none": 0.5}
    assert _read(_run(ops), "paged_kernel_ms.tick") == pytest.approx(
        1e3 * 0.0006 / 2)


def test_manifest_lists_the_reader_for_the_five_routed_cells():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace",
        "layer": "expert layer (models/deepseek_v2.py)",
        "moves": "serve_tokens_per_s", "workloads": ROUTED}
    cells = {w["name"] for w in manifest["workloads"]}
    assert set(ROUTED) <= cells
