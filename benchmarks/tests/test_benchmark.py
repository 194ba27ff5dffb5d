"""Self-tests of the benchmark's own arithmetic (not collected by tier-1):

    python -m pytest benchmarks/tests -q
"""

import json
import os

import pytest

import harness
import trace_reduce
import traffic

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# Recorded on a TPU v5 lite chip (PR 25): three calls of a jitted 3-step scan
# of tanh(x @ x) on a 512 x 512 bf16 matrix, host and Python tracers off.
TRACE = os.path.join(DATA, "tiny_scan_v5e.xplane.pb")


# ---- trace reduction ------------------------------------------------------

def test_busy_union_gaps_and_nesting_on_made_up_events():
    events = [(0.0, 10.0, "while"), (1.0, 2.0, "a"), (4.0, 3.0, "b"),
              (12.0, 1.0, "a"), (12.5, 1.5, "c"), (20.0, 1.0, "a")]
    busy = trace_reduce.busy_union(events)
    assert busy == [(0.0, 10.0), (12.0, 14.0), (20.0, 21.0)]
    assert trace_reduce.gaps(busy) == [(10.0, 12.0), (14.0, 20.0)]
    by_name = trace_reduce.self_time_by_name(events)
    # the while keeps only what its two nested events do not cover
    # "c" overlaps the second "a" without being nested in it: "a" loses only
    # the half second they share
    assert by_name == {"while": 5.0, "a": 3.5, "b": 3.0, "c": 1.5}


def test_op_name_and_collectives():
    assert trace_reduce.op_name(
        "%all-gather-start.3 = (bf16[8]{0}) all-gather-start(...)") \
        == "all-gather-start.3"
    assert trace_reduce.is_collective("all-reduce.7")
    assert trace_reduce.is_collective("collective-permute-start.1")
    assert not trace_reduce.is_collective("fusion.12")


def test_recorded_trace_reduces_to_what_was_run():
    r = trace_reduce.reduce_file(TRACE)
    assert r["chips_traced"] == 1 and r["device0"] == 0
    by_name = r["device0_self_s_by_name"]
    # 3 calls x 3 scan steps of the fused matmul+tanh, ~1.48 us each
    fusion = by_name["convolution_tanh_fusion.2"]
    assert 9 * 1.3e-6 < fusion < 9 * 1.7e-6
    # the while's own time is what its body does not cover: next to nothing
    assert by_name["while"] < 0.1 * fusion
    # three bursts of ~6.5 us, ~21 ms apart: busy is their sum, two long gaps
    assert 3 * 6.0e-6 < r["busy_s"] < 3 * 7.0e-6
    long_gaps = [b - a for a, b in r["device0_gaps"] if b - a > 1e-3]
    assert len(long_gaps) == 2 and all(0.010 < g < 0.030 for g in long_gaps)
    assert r["busy_s"] < r["window_s"] < 0.06
    assert r["device0_collective_s"] == 0.0
    top = trace_reduce.top(by_name, 3)
    assert top[0][0] == "convolution_tanh_fusion.2" and len(top) == 3


def test_gap_labels_follow_the_clock_mark():
    reduced = {"device0_gaps": [(1.0, 2.0), (5.0, 5.5), (9.0, 9.1)],
               "host_minus_trace_clock_s": 100.0}
    intervals = [(101.0, 103.0, "tick A"), (104.0, 106.0, "tick B")]
    assert trace_reduce.label_gaps(reduced, intervals, 5) == [
        ["tick A", 1.0], ["tick B", 0.5], ["outside", pytest.approx(0.1)]]
    reduced["host_minus_trace_clock_s"] = None
    assert trace_reduce.label_gaps(reduced, intervals, 5)[0][0] == "unlabelled"


# ---- percentiles and lateness ----------------------------------------------

def test_percentile_matches_linear_interpolation():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert harness.percentile(xs, 50) == 3.0
    assert harness.percentile(xs, 95) == pytest.approx(4.8)
    assert harness.percentile(xs, 0) == 1.0 and harness.percentile(xs, 100) == 5.0
    assert harness.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        harness.percentile([], 50)


def test_spread_is_the_drivers():
    assert harness.iqr_share([10, 11, 12, 13, 14, 15]) == pytest.approx(
        (14.25 - 10.75) / 12.5)


def _run(**kw):
    return harness.Run(kind="open", config={}, traffic={}, chips=1, device={},
                       peaks={}, t_process_start=0.0, **kw)


def test_latency_counts_from_when_a_request_was_due():
    run = _run(t0=100.0, t1=110.0)
    late = harness.Request(id="a", prompt_len=4, max_tokens=3, due=101.0,
                           sent=101.4, token_times=[102.0, 102.1, 102.3])
    before = harness.Request(id="b", prompt_len=4, max_tokens=2, due=99.0,
                             sent=99.0, token_times=[99.5, 100.5])
    after = harness.Request(id="c", prompt_len=4, max_tokens=2, due=111.0,
                            sent=111.0, token_times=[112.0])
    run.requests = [late, before, after]
    ttft = harness.load_module("layer_metrics", "first_token_ms.p95")
    itl = harness.load_module("e2e_metrics", "itl_ms.p95")
    rate = harness.load_module("e2e_metrics", "serve_tokens_per_s")
    # from due (101.0), not from sent (101.4); only the request due in window
    assert ttft.samples(run) == [pytest.approx(1000.0)]
    # gaps whose later event is inside the window, of any request
    assert sorted(itl.samples(run)) == [pytest.approx(100.0),
                                        pytest.approx(200.0),
                                        pytest.approx(1000.0)]
    assert rate.read(run) == pytest.approx(4 / 10.0)
    assert [r.id for r in run.window_requests()] == ["a"]
    # the per-layer readers over the same samples
    assert ttft.read(run) == pytest.approx(1000.0)
    assert harness.load_module("layer_metrics", "first_token_ms.p50").read(
        run) == pytest.approx(1000.0)
    assert harness.load_module("layer_metrics", "itl_ms.p50").read(
        run) == pytest.approx(200.0)


def test_train_rate_and_mfu_arithmetic():
    run = _run(t0=0.0, t1=10.0)
    run.chips, run.tokens_per_step, run.flops_per_token = 4, 16384, 16.4e9
    run.peaks = {"bf16_flops_per_s": 197e12}
    run.steps = [{"i": i, "t_done": 1.0 + i} for i in range(10)]
    rate = harness.load_module("e2e_metrics", "train_tokens_per_s_chip")
    mfu = harness.load_module("layer_metrics", "mfu.train")
    step = harness.load_module("layer_metrics", "step_ms.p50")
    assert rate.read(run) == pytest.approx(4096.0)
    assert mfu.read(run) == pytest.approx(100 * 16.4e9 * 4096 / 197e12)
    assert step.read(run) == pytest.approx(1000.0)


@pytest.mark.parametrize("name", sorted(
    c["name"] for c in harness.load_manifest()["configs"]))
def test_family_flops_are_the_programs(name):
    """Every configuration of the manifest, through the family its file
    names: the benchmark's copy of the operations a token against the
    program's own count for the same sizes."""
    config = harness.load_json("configs", name + ".json")
    fam = harness.load_module("families", config["family"])
    mc = fam.model_config(config["sizes"])
    assert fam.train_flops_per_token(config["sizes"], 4096) == pytest.approx(
        mc.flops_per_token(4096))


FAMILIES = sorted({harness.load_json("configs", c["name"] + ".json")["family"]
                   for c in harness.load_manifest()["configs"]})
HARNESS_FILES = ("run.py", "harness.py", "serve_cell.py", "train_cell.py",
                 "rehearse.py", "tick_phases.py", "traffic.py",
                 "trace_reduce.py", "routing.py")


@pytest.mark.parametrize("family", FAMILIES)
def test_family_file_keeps_the_contract(family):
    """README.md, "The family file": the names every family has, the three a
    family that trains adds (all or none), and a tiny size for every key of
    `sizes` the harness itself reads."""
    import train_cell

    fam = harness.load_module("families", family)
    for name in ("model_config", "train_flops_per_token",
                 "reference_logits_at", "reference_loss_and_grad_norm",
                 "cache_bytes_per_token"):
        assert callable(getattr(fam, name)), name
    assert len({hasattr(fam, n) for n in train_cell.TRAINING_NAMES}) == 1
    assert {"vocab_size", "max_position_embeddings", "num_hidden_layers",
            "torch_dtype"} <= set(fam.TINY_SIZES)
    for c in harness.load_manifest()["configs"]:
        config = harness.load_json("configs", c["name"] + ".json")
        if config["family"] == family:   # nothing published stays unshrunk
            shapes = {k for k, v in config["sizes"].items()
                      if isinstance(v, int) and not isinstance(v, bool)}
            assert shapes <= set(fam.TINY_SIZES), shapes - set(fam.TINY_SIZES)


@pytest.mark.parametrize("name", HARNESS_FILES)
def test_harness_file_names_no_family_and_no_model(name):
    """Everything about a model reaches the harness through
    families/<family>.py; of `sizes` it reads only the keys every family's
    file must have."""
    import re

    with open(os.path.join(harness.HERE, name)) as f:
        text = f.read()
    assert "ray_tpu.models" not in text and "ray_tpu/models" not in text
    for family in FAMILIES:
        assert not re.search(rf"\b{family}\b", text, re.IGNORECASE), family
    keys = set(re.findall(r"""sizes\[["'](\w+)["']\]""", text))
    assert keys <= {"vocab_size", "max_position_embeddings",
                    "num_hidden_layers", "torch_dtype"}, keys


# ---- traffic ----------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(harness.HERE, "traffic"))))
def test_traffic_repeats_for_a_seed_and_keeps_its_sizes(name):
    spec = harness.load_json("traffic", name + ".json")
    if spec["kind"] == "train_steps":
        assert spec["global_batch"] > 0 and spec["seq"] > 0
        return
    spec = dict(spec, max_requests=2 * spec["pool"])
    a = traffic.make_requests(spec, 2**31 + 5, 32768)
    b = traffic.make_requests(spec, 2**31 + 5, 32768)
    c = traffic.make_requests(spec, 7, 32768)
    assert a == b
    assert a["requests"][0]["prompt"] != c["requests"][0]["prompt"]
    shared = (spec.get("shared_prefixes") or {}).get("len", 0)

    def sizes(plan):
        first = plan["requests"][:spec["pool"]]
        return (sorted(len(r["prompt"]) for r in first),
                sorted(r["max_tokens"] for r in first))

    # every seed: the same shapes in the same order, other ids
    assert [len(r["prompt"]) for r in a["requests"]] == [
        len(r["prompt"]) for r in c["requests"]]
    assert sizes(a) == sizes(c)
    lens, outs = sizes(a)
    assert lens[0] >= spec["prompt_len"]["min"] + shared
    assert lens[-1] <= spec["prompt_len"]["max"] + shared
    assert outs[0] >= spec["output_len"]["min"]
    assert outs[-1] <= spec["output_len"]["max"]
    if spec["kind"] == "open":
        due = [r["due_s"] for r in a["requests"]]
        assert due == sorted(due)
        # one pool of gaps sums to pool / rate exactly
        assert due[spec["pool"] - 1] == pytest.approx(
            spec["pool"] / spec["rate_per_s"])
        assert len(a["prefixes"]) == spec["shared_prefixes"]["count"]


def test_zipf_picks_fill_the_pool_in_proportion():
    picks = traffic.zipf_picks(8, 1.0, 128)
    counts = [picks.count(k) for k in range(8)]
    assert sum(counts) == 128 and counts == sorted(counts, reverse=True)
    assert counts[0] == pytest.approx(128 / sum(1 / k for k in range(1, 9)),
                                      abs=1)


# ---- the manifest -------------------------------------------------------------

def test_manifest_names_files_that_exist_and_moves_that_are_reported():
    m = harness.load_manifest()
    root = harness.ROOT
    cells = {c["name"]: c for c in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    for c in configs.values():
        assert os.path.exists(os.path.join(root, c["file"]))
        body = json.load(open(os.path.join(root, c["file"])))
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            harness.HERE, "families", body["family"] + ".py"))
    for cell in cells.values():
        assert cell["config"] in configs
        spec = harness.load_json("traffic", cell["traffic"] + ".json")
        assert os.path.exists(os.path.join(harness.HERE,
                                           spec["runner"] + ".py"))
    assert sum(c["chips"] == 4 for c in cells.values()) <= max(
        1, len(cells) // 4)
    e2e = {e["name"]: e for e in m["end_to_end"]}
    for e in e2e.values():
        assert os.path.exists(os.path.join(
            harness.HERE, "e2e_metrics", e["name"] + ".py"))
        assert set(e.get("workloads", cells)) <= set(cells)
    for p in m["per_layer"]:
        assert os.path.exists(os.path.join(
            harness.HERE, "layer_metrics", p["name"] + ".py"))
        moved = e2e[p["moves"]]
        for cell in p.get("workloads", cells):
            assert cell in cells
            assert cell in moved.get("workloads", cells), (p["name"], cell)
    for name in cells:
        reported = [e["name"] for e in harness.metrics_of(m, "end_to_end", name)]
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.metrics_of(m, "per_layer", name)


def test_manifest_keeps_the_contracts_limits():
    import re

    m = harness.load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    unit = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
    sources = {"device_trace", "program_span", "program_counter", "host_clock"}

    def line(text):
        return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text

    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["configs"]) <= 24 and 1 <= len(m["workloads"]) <= 24
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.fullmatch(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in m["paths"]))
        assert len(c["reduced"]) <= 16 and all(
            name.fullmatch(k) and not k.endswith(("_dim", "_rank", "_size"))
            for k in c["reduced"])
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(name.fullmatch(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and line(w["why"])
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= e["bound"] <= 0.1
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert p["source"] in sources and line(p["layer"])
    for x in m["end_to_end"] + m["per_layer"]:
        assert name.fullmatch(x["name"]) and unit.fullmatch(x["unit"])
        assert x["better"] in ("lower", "higher")
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(names) == len(set(names)) and "setup_s" in names
    assert len(json.dumps(m)) < 64 * 1024
