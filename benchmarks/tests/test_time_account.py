"""Self-tests of PR 37's readers on hand-made runs: the three the manifest
lists (a PARENT-shaped run feeds them: flight-record fields of PR 26, PR 30
and PR 34) and the four it does not list yet (they read
`engine.stats()["time"]`, which the parent of PR 37 does not keep: None there,
never an exception). Every value is worked out by hand here.

`run.py` calls a run incorrect when a reader the manifest lists returns None,
and a check runs a PR's benchmark files over the parent's program too, so the
guard at the end holds the manifest to what a parent-shaped run can feed.

    python -m pytest benchmarks/tests -q
"""

import os

import pytest

import harness
import time_account

LISTED = ["tick_tail_ms.window", "lookahead.share", "spill_ms.window"]
UNLISTED = ["stall_gc_ms.window", "stall_host_late_ms.window",
            "stall_device_ms.window", "stall_host_ms.window"]
CLOSED = ["mistral7b-chat-closed32", "deepseekv2-docqa-closed32",
          "mimov2flash-longdoc-closed32", "phi4flash-reason-closed64"]
SERVING = CLOSED[:1] + ["mistral7b-prefix-open"] + CLOSED[1:]


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


def _tick(i, **fields):
    """A tick of a 17.4 ms engine, as the parent of PR 37 records it: 0.6 ms
    of loop, 0.3 of admission, 16.5 of the four phases."""
    tick = {"t": 1000.0 + 0.0174 * i, "kind": "mixed", "dur_ms": 16.5,
            "admit_ms": 0.3, "since_prev_ms": 0.6, "compose_ms": 2.0,
            "dispatch_ms": 1.5, "wait_ms": 12.0, "commit_ms": 1.0,
            "decode_rows": 28, "prefill_rows": 0, "lookahead": True,
            "spill_pages": 0, "spill_skipped": 0, "spill_ms": 0.0}
    tick.update(fields)
    return tick


def _run(ticks, before=None, after=None):
    run = harness.Run(kind="closed", config={}, traffic={}, chips=1,
                      device={}, peaks={}, t_process_start=0.0,
                      t0=999.0, t1=1040.0)
    run.ticks = ticks
    run.stats_before = before if before is not None else {"running": 28}
    run.stats_after = after if after is not None else {"running": 28}
    return run


def _quiet():
    """A hundred ticks, every third with two prefill rows at 1.3 x the
    period: nothing is long."""
    return [_tick(i, dur_ms=16.5 + 5.22 * (i % 3 == 0)) for i in range(100)]


def _eventful():
    """The same window with a pause of the machine (wait 122 ms), a collector
    pass inside a wait (152 ms), a spill's gather in compose (64 ms), one
    tick just under the rule, and an idle engine of 5 s before a busy
    period's first tick."""
    ticks = _quiet()
    ticks[10] = _tick(10, dur_ms=126.5, wait_ms=122.0)
    ticks[40] = _tick(40, dur_ms=156.5, wait_ms=152.0)
    ticks[55] = _tick(55, dur_ms=78.5, compose_ms=64.0, spill_ms=61.5,
                      spill_pages=128)
    ticks[70] = _tick(70, dur_ms=53.8)      # 54.7 < 2 x 17.4 + 20
    ticks[80] = _tick(80, since_prev_ms=5000.0, lookahead=False,
                      settled="idle")
    ticks[81] = _tick(81, lookahead=False, settled="pressure")
    ticks.append(_tick(5000, dur_ms=900.0))  # after the window: not counted
    return ticks


def test_a_ticks_period_and_the_long_ones():
    assert time_account.period_ms(_tick(0)) == pytest.approx(17.4)
    assert time_account.period_ms(_tick(0, since_prev_ms=5000.0,
                                        settled="idle")) == (
        pytest.approx(16.8))
    assert time_account.period_ms({"t": 1.0, "dur_ms": 3.0}) is None
    assert time_account.long_excesses([]) is None
    assert time_account.long_excesses([{"kind": "migration_pause"}]) is None
    assert time_account.long_excesses(_quiet()) == []


@pytest.mark.parametrize("name,ticks,expected", [
    ("tick_tail_ms.window", _quiet, 0.0),
    # the three long periods over the window's median of 17.4 ms:
    # (0.9 + 126.5) + (0.9 + 156.5) + (0.9 + 78.5) - 3 x 17.4
    ("tick_tail_ms.window", _eventful, 364.2 - 52.2),
    ("lookahead.share", _quiet, 100.0),
    ("lookahead.share", _eventful, 98.0),
    ("spill_ms.window", _quiet, 0.0),
    ("spill_ms.window", _eventful, 61.5),
])
def test_listed_reader_on_a_parent_shaped_run(name, ticks, expected):
    value = _read(name, _run(ticks()))
    assert value == pytest.approx(expected, abs=1e-6)
    assert isinstance(value, float)


def test_the_tails_samples_are_the_long_ticks_excesses():
    module = harness.load_module("layer_metrics", "tick_tail_ms.window")
    assert module.samples(_run(_quiet())) == []
    assert sorted(module.samples(_run(_eventful()))) == pytest.approx(
        [79.4 - 17.4, 127.4 - 17.4, 157.4 - 17.4])


@pytest.mark.parametrize("name", LISTED)
def test_listed_reader_finds_nothing_without_its_fields(name):
    """A run without ticks, or of a program older than the field: None,
    never an exception; a value wherever the field is."""
    assert _read(name, _run([])) is None
    bare = [{"t": 1000.0 + i, "dur_ms": 20.0, "kind": "decode"}
            for i in range(5)]
    assert _read(name, _run(bare)) is None


STALLS = {"gc": {"ticks": 3, "seconds": 0.412},
          "host_late": {"ticks": 5, "seconds": 0.561},
          "device": {"ticks": 1, "seconds": 2.84},
          "host_work:spill": {"ticks": 2, "seconds": 0.131},
          "host_blocked:loop": {"ticks": 1, "seconds": 0.047},
          "wait": {"ticks": 1, "seconds": 0.09}}
EARLIER = {"gc": {"ticks": 1, "seconds": 0.152},
           "host_late": {"ticks": 1, "seconds": 0.11}}


def _account(stalls):
    return {"running": 28, "time": {"wait": 30.0, "ticks": 2000,
                                    "stalls": stalls}}


@pytest.mark.parametrize("name,expected", [
    ("stall_gc_ms.window", 412.0 - 152.0),
    ("stall_host_late_ms.window", 561.0 - 110.0),
    ("stall_device_ms.window", 2840.0),
    ("stall_host_ms.window", 131.0 + 47.0),
])
def test_unlisted_reader_on_a_change_shaped_run(name, expected):
    run = _run(_eventful(), _account(EARLIER), _account(STALLS))
    assert _read(name, run) == pytest.approx(expected)
    quiet = _run(_quiet(), _account(EARLIER), _account(EARLIER))
    assert _read(name, quiet) == 0.0


@pytest.mark.parametrize("name", UNLISTED)
def test_unlisted_reader_on_a_parent_shaped_run_returns_none(name):
    assert _read(name, _run(_eventful())) is None
    assert _read(name, _run([])) is None


def test_the_manifest_lists_the_three_and_only_what_a_parent_feeds():
    """The guard of `test_tick_phases.py`, for PR 37's entries: a listed
    reader reads a value from a PARENT-shaped run (no `stats()["time"]`, no
    `gc_ms`, `cpu_ms` or `stall` in a record); the four that need the account
    are files without an entry."""
    manifest = harness.load_manifest()
    listed = {p["name"]: p for p in manifest["per_layer"]}
    assert set(LISTED) <= set(listed) and not set(UNLISTED) & set(listed)
    assert [p["name"] for p in manifest["per_layer"][-3:]] == LISTED
    assert len(manifest["per_layer"]) == 36
    for name in LISTED:
        assert _read(name, _run(_eventful())) is not None, name
    for name in LISTED + UNLISTED:
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", name + ".py")), name
    assert listed["tick_tail_ms.window"]["workloads"] == CLOSED
    assert listed["lookahead.share"]["workloads"] == SERVING
    assert listed["spill_ms.window"]["workloads"] == CLOSED[:1]


def test_new_entries_name_a_layer_and_a_metric_their_cells_report():
    manifest = harness.load_manifest()
    reports = {m["name"]: set(m.get("workloads", ()))
               for m in manifest["end_to_end"]}
    with open(os.path.join(harness.ROOT, "PERF.md")) as f:
        perf = f.read()
    for p in manifest["per_layer"][-3:]:
        assert p["layer"] == "engine tick (llm/engine.py _mixed_tick)"
        assert "| " + p["layer"] + " |" in perf
        assert p["source"] == "program_counter"
        assert set(p["workloads"]) <= reports[p["moves"]], p["name"]
        assert "`" + p["name"] + "`" in perf, p["name"]
