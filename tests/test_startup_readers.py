"""The benchmark's readers of a replica's start-up (PR 55) on hand-made runs:
the two the manifest lists since PR 55 (a run of PR 55's PARENT feeds them:
`stats()["warmup_s"]` and a flight record's `t`) and the six that read the
`llm:startup*` spans, which the parent of PR 55 does not write (None and `[]`
there, never an exception): `startup_params_s` is listed since PR 62, whose
parent writes the span, the five others are not yet; and the helper's table
of where `setup_s` goes. Every value is worked out by hand here.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
LAYER = "replica start-up (llm/serving.py build_engine)"
LISTED = ["startup_warmup_s", "setup_traffic_s"]
LISTED_SPANS = ["startup_params_s"]       # since PR 62
UNLISTED = ["startup_s", "startup_trace_lower_s", "startup_compile_s",
            "startup_cache_read_s", "startup_cache_misses"]
SPANS = sorted(LISTED_SPANS + UNLISTED)


@pytest.fixture(scope="module")
def harness():
    """`benchmarks/harness.py`, with the folder on the path while this file's
    tests run (a reader imports its helper by name as it is loaded)."""
    sys.path.insert(0, BENCH)
    try:
        import harness
        yield harness
    finally:
        sys.path.remove(BENCH)


def _span(name, ts, dur, span_id, parent=None, **args):
    ids = {"trace_id": "ab" * 16, "span_id": span_id}
    if parent is not None:
        ids["parent_span_id"] = parent
    return {"name": name, "cat": "llm", "ts": ts * 1e6, "dur": dur * 1e6,
            "ph": "X", "pid": 1, "tid": 1, "args": {**ids, **args}}


def _program(ts, dur, span_id, parent="s0", **parts):
    """A warmed program: the `llm:step_compile` span its dispatch wrote."""
    base = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "cache_read_s": 0.0, "compiles": 1, "cache_hit": True}
    base.update(parts)
    return _span("llm:step_compile", ts, dur, span_id, parent,
                 entry_point="mixed", compile_index=1, **base)


def _startup_spans():
    """A start of 60 s at second 1010: 2 s of imports, 12 s of parameters,
    1 s of adapters, 3 of placement, 2 s of engine, then 40 s of warm-up:
    three programs of 10, 10 and 15 s (the last one missed the cache; the
    warm-up's span holds the stages' sums over all three) and a closing wait
    of 5 s. A second engine's start later in the same process,
    which no reader counts."""
    return [
        _span("llm:startup:params", 1012.0, 12.0, "s1", "s0", source="init",
              bytes=14e9, trace_s=0.5, lower_s=1.0, compile_s=4.0,
              cache_read_s=0.0, compiles=30, cache_hit=False),
        _span("llm:startup:place", 1025.0, 3.0, "s2", "s0", param_bytes=14e9,
              cache_bytes=1e9, pages=2048, slots=0),
        _program(1030.0, 10.0, "s3", trace_s=2.0, lower_s=1.5,
                 cache_read_s=1.25, compile_s=0.25),
        _program(1040.0, 10.0, "s4", trace_s=2.5, lower_s=2.0,
                 cache_read_s=1.75, compile_s=0.25),
        _program(1050.0, 15.0, "s5", trace_s=1.0, lower_s=2.5, compile_s=11.0,
                 cache_hit=False),
        _span("llm:startup:warmup", 1030.0, 40.0, "s6", "s0", programs=3,
              full=False, device_tail_s=5.0, trace_s=5.5, lower_s=6.0,
              compile_s=11.5, cache_read_s=3.0, compiles=3, cache_hit=False),
        _span("llm:startup", 1010.0, 60.0, "s0", replica="1-abc",
              model="LlamaConfig", total_s=60.0, params_s=12.0, place_s=3.0,
              warmup_s=40.0, other_s=5.0, trace_s=6.0, lower_s=7.0,
              compile_s=15.5, cache_read_s=3.0, programs=3, compiles=33,
              cache_hits=2, cache_misses=7, device_tail_s=5.0),
        # a second engine, later: not this run's replica
        _span("llm:startup", 1200.0, 9.0, "t0", replica="1-def",
              trace_s=4.0, lower_s=0.0, compile_s=1.0, cache_read_s=0.0,
              cache_misses=1),
        _span("llm:startup:warmup", 1201.0, 8.0, "t1", "t0", programs=1,
              full=False, device_tail_s=0.0, trace_s=4.0, lower_s=0.0,
              compile_s=0.0, cache_read_s=0.0, compiles=1, cache_hit=True),
        # a request's span: not a start-up's
        _span("llm:decode", 1100.0, 2.0, "r0", request_id="x"),
    ]


def _run(harness, spans=(), ticks=None, stats_after=None, t0=1090.0):
    run = harness.Run(kind="closed", config={}, traffic={}, chips=1,
                      device={}, peaks={}, t_process_start=1000.0,
                      t0=t0, t1=t0 + 40.0)
    run.spans = list(spans)
    run.ticks = ([{"t": 1078.5, "kind": "mixed", "dur_ms": 20.0},
                  {"t": 1075.25, "kind": "mixed", "dur_ms": 20.0},
                  {"kind": "migration_pause"},
                  {"t": 1095.0, "kind": "mixed", "dur_ms": 20.0}]
                 if ticks is None else ticks)
    run.stats_after = ({"running": 28, "warmup_s": 40.125,
                        "warmup_shapes": 9}
                       if stats_after is None else stats_after)
    return run


@pytest.mark.parametrize("name,expected", [
    ("startup_warmup_s", 40.125),
    ("setup_traffic_s", 1090.0 - 1075.25),
])
def test_listed_reader_on_a_parent_shaped_run(harness, name, expected):
    """No `llm:startup` span, no `stats()["startup"]`: what PR 53's program
    hands a run."""
    module = harness.load_module("layer_metrics", name)
    run = _run(harness)
    assert module.read(run) == pytest.approx(expected)
    assert module.samples(run) == pytest.approx([expected])
    assert module.read(_run(harness, _startup_spans())) == pytest.approx(
        expected)


@pytest.mark.parametrize("name", LISTED)
def test_listed_reader_finds_nothing_without_its_source(harness, name):
    module = harness.load_module("layer_metrics", name)
    bare = _run(harness, ticks=[{"kind": "migration_pause"}], stats_after={})
    assert module.read(bare) is None and module.samples(bare) == []
    unmeasured = _run(harness, ticks=[], stats_after={}, t0=0.0)
    assert module.read(unmeasured) is None


@pytest.mark.parametrize("name,expected", [
    ("startup_s", 60.0),
    ("startup_params_s", 12.0),
    ("startup_trace_lower_s", 6.0 + 7.0),       # the draw's included
    ("startup_compile_s", 15.5),
    ("startup_cache_read_s", 3.0),
    ("startup_cache_misses", 7),
])
def test_span_reader_on_a_change_shaped_run(harness, name, expected):
    module = harness.load_module("layer_metrics", name)
    run = _run(harness, _startup_spans())
    assert module.read(run) == pytest.approx(expected)
    assert module.samples(run) == pytest.approx([expected])
    assert all(isinstance(x, float) for x in module.samples(run))


@pytest.mark.parametrize("name", SPANS)
def test_span_reader_on_a_run_without_the_spans_returns_none(harness, name):
    module = harness.load_module("layer_metrics", name)
    for run in (_run(harness),
                _run(harness, [_span("llm:decode", 1100.0, 2.0, "r0")]),
                _run(harness, [_span("llm:startup", 1010.0, 60.0, "s0")]
                     if name not in ("startup_s",) else []),
                _run(harness, ticks=[], stats_after={})):
        assert module.read(run) is None
        assert module.samples(run) == []


def test_the_table_is_consecutive_and_adds_up_to_setup_s(harness):
    sys.path.insert(0, BENCH)
    try:
        import startup_account
    finally:
        sys.path.remove(BENCH)
    table = startup_account.table(_run(harness, _startup_spans()))
    assert table == pytest.approx({
        "before": 10.0, "params": 12.0, "place": 3.0,
        "warmup_trace_lower": 5.5 + 6.0,
        "warmup_compile": 11.5, "warmup_cache_read": 3.0,
        "warmup_dispatch": 40.0 - 11.5 - 11.5 - 3.0 - 5.0,
        "device_tail": 5.0, "other": 5.0, "checks": 1075.25 - 1070.0,
        "traffic": 1090.0 - 1075.25, "sum": 90.0, "setup_s": 90.0})
    assert startup_account.table(_run(harness)) is None
    assert startup_account.table(
        _run(harness, _startup_spans(), ticks=[])) is None


def test_the_manifest_lists_what_a_parent_feeds_and_not_the_five(harness):
    manifest = harness.load_manifest()
    listed = {p["name"]: p for p in manifest["per_layer"]}
    assert set(LISTED) <= set(listed) and not set(UNLISTED) & set(listed)
    # appended together, in order (later PRs append behind them)
    names = [p["name"] for p in manifest["per_layer"]]
    assert names[names.index(LISTED[0]):][:2] == LISTED
    serving = [w["name"] for w in manifest["workloads"]
               if harness.load_json("traffic", w["traffic"] + ".json")[
                   "runner"] == "serve_cell"]
    assert len(serving) >= 9
    reports = {m["name"]: m.get("workloads") for m in manifest["end_to_end"]}
    assert reports["setup_s"] is None       # every cell reports it
    for name in LISTED:
        # (every serving cell since: a cell joins the list when it arrives)
        assert listed[name] == {
            "name": name, "unit": "s", "better": "lower",
            "source": "program_counter", "layer": LAYER, "moves": "setup_s",
            "workloads": serving[:len(listed[name]["workloads"])]}
        assert len(listed[name]["workloads"]) >= 9
        assert harness.load_module("layer_metrics", name).read(
            _run(harness)) is not None
    # the draw's seconds: the span's reader, every serving cell (held by its
    # entry and not by its place, as the two above: later PRs append)
    assert listed["startup_params_s"] == {
        "name": "startup_params_s", "unit": "s", "better": "lower",
        "source": "program_span", "layer": LAYER, "moves": "setup_s",
        "workloads": serving[:len(listed["startup_params_s"]["workloads"])]}
    assert len(listed["startup_params_s"]["workloads"]) >= 11
    for name in LISTED + SPANS:
        module = harness.load_module("layer_metrics", name)
        assert module.__doc__ and callable(module.read)
        assert callable(module.samples)


def test_perf_md_has_the_layer_and_every_reader(harness):
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    assert "| " + LAYER + " |" in perf
    for name in LISTED + SPANS:
        assert "`" + name + "`" in perf, name
    # the five that wait are spelled out as entries the next PR can append
    waiting = perf.split("Readers that wait for a parent that feeds them")[1]
    block = waiting[waiting.index("```json") + 7:]
    entries = json.loads(block[:block.index("```")])
    assert [e["name"] for e in entries] == UNLISTED
    assert all(e["layer"] == LAYER and e["moves"] == "setup_s"
               for e in entries)
