"""Self-tests of the PR 26 readers on a hand-made run: ticks, spans and a
reduced trace whose gaps and names are known, so every value is worked out by
hand here. Also what the readers do with a program older than PR 26 (no such
fields: None, never an exception) and without a trace.

`run.py` calls a run incorrect when a reader the manifest lists returns None,
and a check runs a PR's benchmark files over the parent's program too. So the
manifest lists a reader only once the parent keeps what it reads: the
NEEDS_PR26 ones were files without an entry until PR 28, whose parent (and
every parent since PR 26) keeps their fields.

    python -m pytest benchmarks/tests -q
"""

import pytest

import harness
import tick_phases

OFFSET = 1000.0        # host clock minus trace clock
SIZES = {"num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 16,
         "torch_dtype": "float32"}      # 2 x 2 x 2 x 16 x 4 B = 512 B a token
SERVING = ["tick_host_ms.p50", "tick_wait_ms.p50", "loop_gap_ms.p50",
           "paged_kernel_ms.tick", "paged_kernel_hbm.share",
           "pool_copy_ms.tick", "idle_in_wait.serve", "prefill_ms.p50",
           "prefill_starved_ticks.mean"]
TRAINING = ["collective_exposed_ms.train", "flash_kernel_ms.train"]
NEEDS_PR26 = {"tick_host_ms.p50", "tick_wait_ms.p50", "loop_gap_ms.p50",
              "paged_kernel_hbm.share", "idle_in_wait.serve",
              "prefill_starved_ticks.mean"}


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


def _ticks(new_fields=True):
    """Six unified ticks of 200 ms, 250 ms apart, from host time 1009.70:
    their middles are 1009.80 (before the slice), 1010.05, 1010.30, 1010.55,
    1010.80 (inside it) and 1011.05 (after it)."""
    out = []
    for i in range(6):
        tick = {"t": 1009.70 + 0.25 * i, "dur_ms": 200.0, "kind": "mixed",
                "decode_rows": 4, "prefill_rows": 1}
        if new_fields:
            tick.update(admit_ms=1.0, since_prev_ms=49.0, compose_ms=10.0,
                        dispatch_ms=5.0, wait_ms=180.0, commit_ms=5.0,
                        kv_tokens=1000 * (i + 1), prefill_tokens=8, starved=2)
        out.append(tick)
    return out


def _spans(new_args=True):
    out = []
    for rid, dur_ms, starved in (("a", 100.0, 0), ("b", 300.0, 4),
                                 ("c", 200.0, 2), ("early", 900.0, 50)):
        args = {"request_id": rid, "tokens": 24}
        if new_args:
            args.update(cached_tokens=0, slices=3, starved_ticks=starved)
        out.append({"name": "llm:prefill", "ts": 0.0, "dur": dur_ms * 1e3,
                    "args": args})
    out.append({"name": "llm:decode", "ts": 0.0, "dur": 5e6,
                "args": {"request_id": "a"}})
    return out


def _serving_run(new_fields=True, trace=True):
    run = harness.Run(kind="open", config={"sizes": SIZES, "family": "llama"},
                      traffic={},
                      chips=1, device={}, peaks={"hbm_bytes_per_s": 1e9},
                      t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = _ticks(new_fields)
    run.spans = _spans(new_fields)
    run.requests = [harness.Request(id=rid, prompt_len=24, max_tokens=4,
                                    due=due)
                    for rid, due in (("a", 1001.0), ("b", 1002.0),
                                     ("c", 1003.0), ("early", 990.0))]
    if trace:
        run.trace = {
            # the slice: trace clock 10.0 .. 11.0 = host 1010.0 .. 1011.0
            "window_start_s": 10.0, "window_s": 1.0, "busy_s": 0.93,
            "host_minus_trace_clock_s": OFFSET,
            # tick 1's wait phase is host 1009.965 .. 1010.145 = trace
            # 9.965 .. 10.145, tick 2's starts at trace 10.215:
            "device0_gaps": [(10.06, 10.07),    # inside tick 1's wait: 0.010
                             (10.14, 10.16),    # 0.005 of it inside
                             (10.16, 10.19),    # between two waits: 0
                             (10.0, 10.01)],    # an edge gap, inside: 0.010
            "device0_self_s_by_name": {
                "tpu_custom_call.3": 0.4, "paged_attention_unified.7": 0.2,
                "copy.61": 0.05, "copy.62": 0.03, "copy-start.2": 0.2,
                "copy-done.2": 0.1, "copy_bitcast_fusion.5": 0.1,
                "fusion.1": 0.15}}
    return run


def _training_run(trace=True):
    run = harness.Run(kind="train_steps", config={}, traffic={}, chips=4,
                      device={}, peaks={}, t_process_start=0.0)
    if trace:
        run.trace = {"steps_traced": 3, "device0_self_s_by_name": {
            "all-gather-start.1": 0.01, "all-gather-done.1": 0.2,
            "all-reduce.5": 0.06, "async-collective-done.7": 0.03,
            "fusion.2": 1.0, "tpu_custom_call.4": 0.1, "shard_map.305": 0.2,
            "flash_bwd_dq.2": 0.3, "copy.1": 0.5}}
    return run


@pytest.mark.parametrize("name,expected", [
    ("tick_host_ms.p50", 1.0 + 10.0 + 5.0 + 5.0),
    ("tick_wait_ms.p50", 180.0),
    ("loop_gap_ms.p50", 49.0),
    # 0.6 s of kernel over the four ticks whose middle is in the slice
    ("paged_kernel_ms.tick", 1e3 * 0.6 / 4),
    # (2000 + 3000 + 4000 + 5000) tokens x 512 B over 0.6 s over 1 GB/s
    ("paged_kernel_hbm.share", 100 * 14000 * 512 / 0.6 / 1e9),
    ("pool_copy_ms.tick", 1e3 * 0.08 / 4),
    ("idle_in_wait.serve", 100 * 0.025 / 1.0),
    ("prefill_ms.p50", 200.0),
    ("prefill_starved_ticks.mean", (0 + 4 + 2) / 3),
])
def test_serving_reader_gives_the_hand_computed_value(name, expected):
    assert _read(name, _serving_run()) == pytest.approx(expected)


@pytest.mark.parametrize("name,expected", [
    ("collective_exposed_ms.train", 1e3 * 0.3 / 3),
    ("flash_kernel_ms.train", 1e3 * 0.6 / 3),
])
def test_training_reader_gives_the_hand_computed_value(name, expected):
    assert _read(name, _training_run()) == pytest.approx(expected)


def test_idle_in_wait_is_a_part_of_device_idle():
    run = _serving_run()
    assert _read("idle_in_wait.serve", run) <= _read("device_idle.serve", run)
    assert _read("device_idle.serve", run) == pytest.approx(7.0)


@pytest.mark.parametrize("name", SERVING)
def test_reader_of_an_older_programs_run_returns_none_or_a_number(name):
    """The parent of PR 26 has no phase fields, counters or span arguments:
    a reader that needs them finds nothing; the others still read."""
    value = _read(name, _serving_run(new_fields=False))
    assert (value is None) == (name in NEEDS_PR26), value


@pytest.mark.parametrize("name", SERVING + TRAINING)
def test_reader_without_a_trace_does_not_raise(name):
    """As in the CPU rehearsal, where no device plane is traced."""
    run = (_training_run(trace=False) if name in TRAINING
           else _serving_run(trace=False))
    value = _read(name, run)
    from_records_alone = {"tick_host_ms.p50", "tick_wait_ms.p50",
                          "loop_gap_ms.p50", "prefill_ms.p50",
                          "prefill_starved_ticks.mean"}
    assert (value is not None) == (name in from_records_alone)


def test_slice_ticks_and_clock():
    run = _serving_run()
    assert tick_phases.slice_on_host_clock(run) == (1010.0, 1011.0)
    assert [round(t["t"], 2) for t in tick_phases.slice_ticks(run)] == [
        1009.95, 1010.2, 1010.45, 1010.7]
    run.trace["host_minus_trace_clock_s"] = None
    assert tick_phases.slice_ticks(run) == []
    assert _read("paged_kernel_ms.tick", run) is None
    assert _read("idle_in_wait.serve", run) is None


def test_names_the_readers_take():
    assert tick_phases.is_pool_copy("copy.61") and tick_phases.is_pool_copy("copy")
    assert not any(tick_phases.is_pool_copy(n) for n in (
        "copy-start.2", "copy-done", "copy_bitcast_fusion.5", "copy.61.remat"))
    assert tick_phases.is_custom_call("tpu_custom_call", ())
    # as a v5e trace names them without the benchmark's location setting
    for name in ("jvp_flash_fwd_.1", "transpose_jvp_flash_bwd_dkv__.1"):
        assert tick_phases.is_custom_call(name, tick_phases.FLASH_KERNELS)
    assert tick_phases.is_custom_call("paged_attention_unified.1",
                                      tick_phases.PAGED_KERNELS)
    assert not tick_phases.is_custom_call("fusion.3",
                                          tick_phases.PAGED_KERNELS)


def test_cache_bytes_per_token_are_the_familys():
    config = harness.load_json("configs", "mistral-7b-v0.3-l16.json")
    family = harness.load_module("families", config["family"])
    assert family.cache_bytes_per_token(config["sizes"]) == 64 * 1024
    assert not hasattr(tick_phases, "kv_bytes_per_token")


def test_manifest_lists_every_reader_since_the_parent_feeds_them_all():
    """Until PR 28 the NEEDS_PR26 readers had no entry; a run of PR 26's
    program or a later one feeds every one of them."""
    listed = {p["name"] for p in harness.load_manifest()["per_layer"]}
    new = set(SERVING + TRAINING)
    assert new <= listed
    for name in sorted(new - set(TRAINING)):
        assert _read(name, _serving_run()) is not None, name


def test_new_entries_name_layers_the_manifest_or_perf_md_has():
    m = harness.load_manifest()
    new = [p for p in m["per_layer"] if p["name"] in SERVING + TRAINING]
    assert len(new) == 11
    with open(harness.ROOT + "/PERF.md") as f:
        perf = f.read()
    for p in new:
        assert "| " + p["layer"] + " |" in perf, p["layer"]
        assert "workloads" in p and p["workloads"]
