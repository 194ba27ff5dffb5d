"""Self-tests of what PR 45 added to the benchmark: the `kimi_linear` family
file (its contract and its counts, by hand at the published widths), the
configuration file's two copies of the published keys, the reference's two
copies and the reference against the program at `TINY_SIZES`, the two new
readers on a made-up run whose values are worked out by hand, and the controls
of the comparison.

    python -m pytest benchmarks/tests -q
"""

import json
import os
import types

import jax
import numpy as np
import pytest

import harness
import serve_cell

family = harness.load_module("families", "kimi_linear")
CONFIG = harness.load_json("configs", "kimi-linear-48b-l12-e32.json")
TRAFFIC = harness.load_json("traffic", "longout-closed64.json")
CELL = "kimilinear-longout-closed64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["kda_kernel_ms.tick", "kda_kernel_hbm.share"]
OWN = {"num_experts_published", "n_routed_experts", "first_held_expert",
       "max_position_embeddings", "gate_rank", "l2_norm_eps", "torch_dtype"}
S_BYTES = 32 * 128 * 128 * 4             # a layer's S a sequence


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


# ---- the family file and the configuration ----------------------------------

def test_family_counts_are_issue_45s_arithmetic():
    sizes = CONFIG["sizes"]
    # KDA mixer 39.51 M: q, k, v 28.31, o 9.44, the two rank-128 pairs 1.64,
    # beta 0.07, taps 0.05
    assert family.kda_params(sizes) == (
        3 * 2304 * 4096 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096)
        + 2304 * 32 + 4 * 3 * 4096) == 39_510_016
    # MLA mixer 29.11 M: q 14.16, kv_a 1.33, kv_b 4.19, o 9.44
    assert family.mla_params(sizes) == (
        2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304) \
        == 29_114_368
    expert, router, dense = 3 * 2304 * 1024, 2304 * 256, 3 * 2304 * 9216
    assert (expert, router, dense) == (7_077_888, 589_824, 63_700_992)
    by_hand = (2 * 20480 * 2304 + 9 * 39_510_016 + 3 * 29_114_368 + dense
               + 11 * (33 * expert + router))
    assert family.num_params(sizes) == by_hand == 3_176_767_488   # 3,177 M
    assert family.num_params(sizes) * 2 == pytest.approx(6.35e9, rel=1e-3)
    # a token: the 576-wide latent row of 3 layers in bfloat16
    assert family.cache_bytes_per_token(sizes) == 3 * 1152
    assert family.attention_flops_per_pair(sizes) == 3 * 32 * 320 * 2
    # a slot: 9 layers' S (18.9 MB) and tails of 3 rows x 12,288 (1.3 MB)
    assert 9 * S_BYTES == 18_874_368
    assert family.state_bytes_per_sequence(sizes) \
        == 9 * (S_BYTES + 3 * 12288 * 4) == 20_201_472
    # the kernel's bytes: S read once a sequence (no write-back counted); a
    # row's q, k, v, gate logs (4,096 each), beta (32) in and o (4,096) out,
    # float32
    assert family.kda_bytes(sizes, 0, 1) == 9 * S_BYTES
    assert family.kda_bytes(sizes, 1, 0) == 9 * 4 * (5 * 4096 + 32)
    assert family.kda_bytes(sizes, 192, 64) \
        == 192 * family.kda_bytes(sizes, 1, 0) \
        + 64 * family.kda_bytes(sizes, 0, 1)
    mc = family.model_config(sizes)
    assert (mc.num_hidden_layers, mc.vocab_size, mc.kda_head_dim, mc.max_seq,
            mc.experts_held, mc.num_experts) == (12, 20480, 128, 16384,
                                                 (0, 32), 256)
    assert mc.num_params() == family.num_params(sizes)
    assert mc.state_bytes_per_sequence == family.state_bytes_per_sequence(
        sizes)
    assert mc.layer_kinds().count("kda_moe") == 8
    assert not any(hasattr(family, n) for n in (
        "loss_fn", "param_logical_axes", "init_params"))
    assert callable(family.reference_loss_and_grad_norm)
    assert family.train_flops_per_token(sizes, 4096) == pytest.approx(
        mc.flops_per_token(4096))
    shapes = {k for k, v in sizes.items()
              if isinstance(v, int) and not isinstance(v, bool)}
    assert shapes <= set(family.TINY_SIZES), shapes - set(family.TINY_SIZES)
    tiny = family.model_config(dict(sizes, **family.TINY_SIZES))
    assert (tiny.num_hidden_layers, tiny.kda_num_heads, tiny.kda_head_dim,
            tiny.n_held) == (5, 4, 16, 8)


def test_configuration_files_two_copies_of_the_published_keys_agree():
    sizes = CONFIG["sizes"]
    assert OWN <= set(sizes)
    assert {k: v for k, v in sizes.items() if k not in OWN} == {
        k: CONFIG[k] for k in sizes if k not in OWN}
    assert list(CONFIG["reduced"]) == [
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size", "model_max_length"]
    for key, entry in CONFIG["reduced"].items():
        assert entry["published"] != CONFIG[key] and entry["why"], key
    assert sizes["max_position_embeddings"] == CONFIG["model_max_length"]
    assert sizes["n_routed_experts"] == sizes["num_experts"] == 32
    lin, was = (sizes["linear_attn_config"],
                CONFIG["reduced"]["linear_attn_config"]["published"])
    for key in ("num_heads", "head_dim", "short_conv_kernel_size"):
        assert lin[key] == was[key]                 # no width changed
    assert lin["kda_layers"] == [n for n in was["kda_layers"] if n <= 12]
    assert lin["full_attn_layers"] == [4, 8, 12]
    deployment = CONFIG["deployment"]
    assert deployment["max_batch_size"] == TRAFFIC["clients"] == 64
    manifest = [c for c in harness.load_manifest()["configs"]
                if c["name"] == "kimi-linear-48b-l12-e32"][0]
    assert manifest["reduced"] == list(CONFIG["reduced"])
    assert manifest["source"] == CONFIG["source"]
    assert manifest["file"] == "benchmarks/configs/" \
        "kimi-linear-48b-l12-e32.json"
    # the pool: 64 sequences of the longest request
    longest = TRAFFIC["prompt_len"]["max"] + TRAFFIC["output_len"]["max"]
    assert longest <= sizes["max_position_embeddings"]
    assert deployment["num_kv_blocks"] == 64 * -(-longest // 16) == 32768
    assert "eight" in CONFIG["stands_for"].lower()
    assert "12 of 27" in CONFIG["stands_for"]
    assert {"short_convolution", "l2_norm", "forget_gate", "beta",
            "output_gate", "mla_scale", "state_precision",
            "weights"} <= set(CONFIG["assumed"])
    assert "backward" in CONFIG["left_out"]


def test_the_traffic_is_the_issues():
    assert (TRAFFIC["kind"], TRAFFIC["clients"], TRAFFIC["ramp_s"],
            TRAFFIC["warm_s"], TRAFFIC["pool"], TRAFFIC["max_requests"],
            TRAFFIC["sampled_every"]) == ("closed", 64, 8.0, 12.0, 32, 1024,
                                          8)
    assert TRAFFIC["prompt_len"] == {"median": 1024, "sigma": 0.6,
                                     "min": 256, "max": 4096}
    assert TRAFFIC["output_len"] == {"median": 1024, "sigma": 0.7,
                                     "min": 256, "max": 4096}
    assert TRAFFIC["sampled"] == {"temperature": 0.8, "top_k": 50}
    assert "shared_prefixes" not in TRAFFIC


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_holds_every_key_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"Kimi-Linear-48B-A3B-Instruct"' in line][0]
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CONFIG, key
        if key in CONFIG["reduced"]:
            assert CONFIG["reduced"][key]["published"] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_cell_joins_the_readers_that_fit_it_and_not_the_others():
    manifest = harness.load_manifest()
    listed = {p["name"] for p in manifest["per_layer"]
              if CELL in p.get("workloads", ())}
    assert set(NEW) <= listed
    # a latent pool, held experts: those readers read; no window, no scan,
    # no retention, no shared prefix, no open queue: these find nothing
    assert {"paged_kernel_ms.tick", "paged_kernel_hbm.share",
            "latent_kernel_mxu.share", "expert_rows.mean",
            "expert_load_skew.mean"} <= listed
    assert not listed & {"window_kernel_ms.tick", "ssm_kernel_ms.tick",
                         "retention_kernel_ms.tick", "prefix_share",
                         "queue_ms.p95", "cross_rows_skipped.share"}
    new = [p for p in manifest["per_layer"] if p["name"] in NEW]
    assert [p["name"] for p in manifest["per_layer"]][-2:] == NEW
    layers = {p["layer"] for p in manifest["per_layer"]
              if p["name"] not in NEW}
    for p in new:
        assert p["layer"] in layers and p["workloads"] == [CELL]
        assert p["moves"] == "itl_ms.p95" and p["source"] == "device_trace"
    e2e = {e["name"] for e in harness.metrics_of(manifest, "end_to_end",
                                                 CELL)}
    assert e2e == {"setup_s", "itl_ms.p95", "serve_tokens_per_s"}
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-l12-e32", "longout-closed64", 1)
    assert manifest["workloads"][-1] == cell and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


# ---- the reference ----------------------------------------------------------

def _tiny():
    from ray_tpu.models import kimi_linear

    sizes = dict(CONFIG["sizes"], **family.TINY_SIZES)
    params = kimi_linear.init_params(family.model_config(sizes),
                                     jax.random.key(2))
    return sizes, params


def test_the_two_references_are_one_file_and_give_the_same_logits():
    from ray_tpu.models import kimi_linear_reference as ours

    theirs = family.reference
    with open(ours.__file__) as a, open(theirs.__file__) as b:
        text = a.read()
        assert text == b.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    sizes, params = _tiny()
    tokens = np.random.default_rng(6).integers(1, 256, (2, 20)).astype(
        np.int32)
    a, _ = ours.logits_at(params, tokens, [3, 19], sizes)
    b = family.reference_logits_at(params, tokens, [3, 19], sizes)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    value, norm = family.reference_loss_and_grad_norm(params, tokens, sizes)
    assert np.isfinite(value) and norm > 0


class _Server:
    """What `serve_cell.check_logits` reads of a server, around a bare
    runner at the tiny sizes."""

    def __init__(self, sizes, params):
        import threading

        from ray_tpu.llm.model_runner import ModelRunner

        runner = ModelRunner(family.model_config(sizes), params,
                             num_blocks=128, block_size=4,
                             attention_impl="reference", chunk_size=16,
                             max_batch=4)
        self.engine = types.SimpleNamespace(runner=runner)
        self._lock = threading.Lock()


def test_the_harness_check_passes_the_program_and_fails_the_controls():
    """`serve_cell.check_logits` as the cell runs it, the routed form (two
    prompts in chunks through `runner.step`, then decode positions, the
    reference following the program's experts), at the tiny sizes in
    float32: the sound reference passes far inside the tolerance with no
    shortfall, and the reference with one term dropped fails it."""
    sizes, params = _tiny()
    sound = serve_cell.check_logits(_Server(sizes, params), family, sizes, 3)
    assert sound["ok"] and sound["rel_err"] < 1e-4
    assert sound["routed_choices"] == 4 * 2 * 136
    assert sound["shortfall_max"] == 0.0
    starts = list(range(0, 128, 16)) + list(range(128, 136))
    for fault in (("state_not_carried", starts), "beta_one", "gate_a_head",
                  "no_delta", "no_nope_lanes"):
        def routed(p, t, pos, s, kept, fault=fault):
            logits, _ = family.reference.logits_at(p, t, pos, s, kept, fault)
            return logits, np.zeros(np.asarray(kept).shape[:3])

        faulty = types.SimpleNamespace(reference_logits_routed=routed)
        result = serve_cell.check_logits(_Server(sizes, params), faulty,
                                         sizes, 3)
        assert not result["ok"], (fault, result["rel_err"])


# ---- the readers ------------------------------------------------------------

def _run():
    run = harness.Run(
        kind="closed",
        config={"sizes": CONFIG["sizes"], "family": "kimi_linear"},
        traffic={}, chips=1, device={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = [
        {"t": 1009.9 + 0.05 * i, "dur_ms": 45.0, "kind": "mixed",
         "decode_rows": 63, "prefill_rows": seqs - 63, "used": rows,
         "kda_rows": rows, "kda_seqs": seqs, "kv_tokens": 1000 * seqs}
        for i, (seqs, rows) in enumerate(
            [(64, 191), (64, 191), (63, 63), (64, 127)])]
    run.trace = {"window_start_s": 9.94, "window_s": 0.1, "busy_s": 0.09,
                 "host_minus_trace_clock_s": 1000.0, "device0_gaps": [],
                 "device0_self_s_by_name": {
                     "kda_call.7": 0.030, "kda_call.9": 0.002,
                     "paged_attention_latent_call.3": 0.004,
                     "fusion.1": 0.03, "copy.3": 0.001}}
    return run


@pytest.mark.parametrize("name,expected", [
    # ticks 1 and 2 are in the slice: 32 ms of the kernel over them
    ("kda_kernel_ms.tick", 1e3 * 0.032 / 2),
    # their slots (64 + 63) and rows (191 + 63) through nine layers
    ("kda_kernel_hbm.share",
     100 * 9 * (127 * S_BYTES + 254 * 4 * (5 * 4096 + 32))
     / 0.032 / 819e9),
    # the KDA kernel's events are no paged kernel's: the latent kernel alone
    ("paged_kernel_ms.tick", 1e3 * 0.004 / 2),
    ("paged_kernel_hbm.share", 100 * 127_000 * 3456 / 0.004 / 819e9),
])
def test_readers_give_the_hand_computed_value(name, expected):
    assert _read(name, _run()) == pytest.approx(expected)


def test_the_share_reads_a_hundred_where_the_counted_bytes_move_at_peak():
    """Reading every slot once at the chip's peak, rows beside them, is what
    the share counts as 100%: a kernel that also rewrites every slot reads
    about half, and one that wrote a slot once in several rows less than
    100%."""
    run = _run()
    sizes = CONFIG["sizes"]
    moved = sum(family.kda_bytes(sizes, t["kda_rows"], t["kda_seqs"])
                for t in run.ticks[1:3])
    run.trace["device0_self_s_by_name"] = {"kda_call.7": moved / 819e9}
    assert _read("kda_kernel_hbm.share", run) == pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_older_programs_run(name):
    """The parent keeps none of the new fields and has no KDA kernel, and a
    family without KDA layers counts no such bytes: None, never an
    exception, with or without a trace."""
    run = _run()
    for tick in run.ticks:
        for field in ("kda_rows", "kda_seqs"):
            del tick[field]
    run.trace["device0_self_s_by_name"] = {"paged_attention_kv_call.3": 0.02}
    assert _read(name, run) is None
    run.trace = None
    assert _read(name, run) is None
    dense = _run()
    dense.config = {"sizes": {"num_hidden_layers": 2}, "family": "llama"}
    if name == "kda_kernel_hbm.share":
        assert _read(name, dense) is None
