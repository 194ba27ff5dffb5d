"""Self-tests of what PR 52 added to the benchmark: the `nemotron_h` family
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

family = harness.load_module("families", "nemotron_h")
CONFIG = harness.load_json("configs", "nemotron-3-super-l11-e128.json")
TRAFFIC = harness.load_json("traffic", "longout-closed64.json")
CELL = "nemotron3super-longout-closed64"
SIBLING = "kimilinear-longout-closed64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["ssd_kernel_ms.tick", "ssd_kernel_hbm.share"]
OWN = {"n_routed_experts_published", "first_held_expert", "torch_dtype"}
S_BYTES = 128 * 64 * 128 * 4             # a layer's S a sequence
ROW_BYTES = 4 * (2 * 8192 + 2 * 8 * 128 + 128)   # x, y, B, C, dt: float32


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


# ---- the family file and the configuration ----------------------------------

def test_family_counts_are_issue_52s_arithmetic():
    sizes = CONFIG["sizes"]
    # Mamba-2 layer 109.64 M: in_proj 76.02, out_proj 33.55, taps + bias 0.05
    assert family.mamba_params(sizes) == (
        4096 * (8192 + 10240 + 128) + 5 * 10240 + 3 * 128 + 8192
        + 8192 * 4096 + 4096) == 109_640_064
    # the attention layer 35.66 M: q 16.78, k and v 2.10, o 16.78
    assert family.attn_params(sizes) == (
        4096 * (32 + 4) * 128 + 4096 * 4096 + 4096) == 35_655_680
    assert family.expert_params(sizes) == 2 * 1024 * 2688 == 5_505_024
    # an expert layer beside its experts 54.53 M
    assert family.moe_params(sizes, 0) == (
        4096 * 512 + 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 4096) \
        == 54_530_560
    by_hand = (5 * 109_640_064 + 35_655_680
               + 5 * (54_530_560 + 128 * 5_505_024) + 2 * 32768 * 4096 + 4096)
    assert family.num_params(sizes) == by_hand == 4_648_163_712   # 4,648.2 M
    assert family.num_params(sizes) * 2 == pytest.approx(9.30e9, rel=1e-3)
    # a token: a K and a V row of 2 x 128 in bfloat16, one layer
    assert family.cache_bytes_per_token(sizes) == 1024
    assert family.attention_flops_per_pair(sizes) == 32 * 256 * 2
    # a slot: 5 layers' S (21.0 MB) and bf16 tails of 3 rows x 10,240
    assert 5 * S_BYTES == 20_971_520
    assert family.state_bytes_per_sequence(sizes) \
        == 5 * (S_BYTES + 3 * 10240 * 2) == 21_278_720
    # the kernel's floor: S in once a sequence (no write-back counted); a
    # row's x (8,192) in and y out, its groups' B and C (1,024 each) and dt
    # (128), float32
    assert family.ssd_bytes(sizes, 0, 1) == 5 * S_BYTES
    assert family.ssd_bytes(sizes, 1, 0) == 5 * ROW_BYTES
    assert family.ssd_bytes(sizes, 192, 64) \
        == 192 * family.ssd_bytes(sizes, 1, 0) \
        + 64 * family.ssd_bytes(sizes, 0, 1)
    mc = family.model_config(sizes)
    assert (mc.num_hidden_layers, mc.vocab_size, mc.max_seq, mc.experts_held,
            mc.n_routed_experts, mc.hybrid_override_pattern) == (
        11, 32768, 8192, (0, 128), 512, "MEMEMEM*EME")
    assert mc.num_params() == family.num_params(sizes)
    assert mc.state_bytes_per_sequence == family.state_bytes_per_sequence(
        sizes)
    assert not any(hasattr(family, n) for n in (
        "loss_fn", "param_logical_axes", "init_params"))
    assert callable(family.reference_loss_and_grad_norm)
    assert family.train_flops_per_token(sizes, 4096) == pytest.approx(
        mc.flops_per_token(4096))
    shapes = {k for k, v in sizes.items()
              if isinstance(v, int) and not isinstance(v, bool)}
    assert shapes <= set(family.TINY_SIZES), shapes - set(family.TINY_SIZES)
    tiny = family.model_config(dict(sizes, **family.TINY_SIZES))
    assert (tiny.num_hidden_layers, tiny.mamba_num_heads, tiny.n_held) == (
        6, 8, 8)
    with pytest.raises(SystemExit, match="does not model"):
        family.model_config(dict(sizes, mlp_hidden_act="silu"))


def test_configuration_files_two_copies_of_the_published_keys_agree():
    sizes = CONFIG["sizes"]
    assert OWN <= set(sizes)
    assert {k: v for k, v in sizes.items() if k not in OWN} == {
        k: CONFIG[k] for k in sizes if k not in OWN}
    assert list(CONFIG["reduced"]) == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers", "max_position_embeddings"]
    for key, entry in CONFIG["reduced"].items():
        assert entry["published"] != CONFIG[key] and entry["why"], key
    was = CONFIG["reduced"]["hybrid_override_pattern"]["published"]
    assert len(was) == 88 and sizes["hybrid_override_pattern"] == was[:11]
    assert (was.count("M"), was.count("E"), was.count("*")) == (40, 40, 8)
    assert sizes["n_routed_experts_published"] == 512
    deployment = CONFIG["deployment"]
    assert deployment["max_batch_size"] == TRAFFIC["clients"] == 64
    manifest = [c for c in harness.load_manifest()["configs"]
                if c["name"] == "nemotron-3-super-l11-e128"][0]
    assert manifest["reduced"] == list(CONFIG["reduced"])
    assert manifest["source"] == CONFIG["source"]
    assert manifest["file"] == "benchmarks/configs/" \
        "nemotron-3-super-l11-e128.json"
    # the pool: 64 sequences of the longest request
    longest = TRAFFIC["prompt_len"]["max"] + TRAFFIC["output_len"]["max"]
    assert longest <= sizes["max_position_embeddings"]
    assert deployment["num_kv_blocks"] == 64 * -(-longest // 16) == 32768
    assert "four" in CONFIG["stands_for"].lower()
    assert "11 of 88" in CONFIG["stands_for"]
    assert {"no_rotary_embedding", "what_reads_the_latent", "latent_bias",
            "router", "dt", "gated_norm", "in_proj_order", "precision",
            "weights"} <= set(CONFIG["assumed"])
    assert "MTP" in CONFIG["left_out"] and "LATENT" in CONFIG["left_out"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_holds_every_key_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line][0]
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CONFIG, key
        if key in CONFIG["reduced"]:
            assert CONFIG["reduced"][key]["published"] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_cell_joins_the_readers_that_fit_it_and_not_the_others():
    manifest = harness.load_manifest()
    of = lambda cell: {p["name"] for p in manifest["per_layer"]
                       if cell in p.get("workloads", ())}
    listed = of(CELL)
    assert set(NEW) <= listed
    # every reader of the sibling cell (the same traffic file) whose quantity
    # exists here; not the ones that key on another kernel or family
    assert of(SIBLING) - listed == {"latent_kernel_mxu.share",
                                    "kda_kernel_ms.tick",
                                    "kda_kernel_hbm.share"}
    assert listed - of(SIBLING) == set(NEW)
    assert not listed & {"window_kernel_ms.tick", "ssm_kernel_ms.tick",
                         "retention_kernel_ms.tick", "prefix_share",
                         "queue_ms.p95", "cross_rows_skipped.share"}
    new = [p for p in manifest["per_layer"] if p["name"] in NEW]
    names = [p["name"] for p in manifest["per_layer"]]
    assert names[names.index(NEW[0]):][:2] == NEW       # appended, in order
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
        "nemotron-3-super-l11-e128", "longout-closed64", 1)
    assert len(cell["why"]) <= 200
    assert harness.find_cell(manifest, SIBLING)["traffic"] == cell["traffic"]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


# ---- the reference ----------------------------------------------------------

def _tiny():
    from ray_tpu.models import nemotron_h

    sizes = dict(CONFIG["sizes"], **family.TINY_SIZES)
    params = nemotron_h.init_params(family.model_config(sizes),
                                    jax.random.key(2))
    return sizes, params


def test_the_two_references_are_one_file_and_give_the_same_logits():
    from ray_tpu.models import nemotron_h_reference as ours

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
    shortfall, and the reference with one term changed fails it."""
    sizes, params = _tiny()
    sound = serve_cell.check_logits(_Server(sizes, params), family, sizes, 3)
    assert sound["ok"] and sound["rel_err"] < 1e-4
    assert sound["routed_choices"] == 2 * 2 * 136
    assert sound["shortfall_max"] == 0.0
    starts = list(range(0, 128, 16)) + list(range(128, 136))
    for fault in (("state_not_carried", starts), "norm_all_lanes",
                  "group_zero", "no_routed_factor"):
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
        config={"sizes": CONFIG["sizes"], "family": "nemotron_h"},
        traffic={}, chips=1, device={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = [
        {"t": 1009.9 + 0.05 * i, "dur_ms": 45.0, "kind": "mixed",
         "decode_rows": 63, "prefill_rows": seqs - 63, "used": rows,
         "ssd_rows": rows, "ssd_seqs": seqs, "kv_tokens": 1000 * seqs}
        for i, (seqs, rows) in enumerate(
            [(64, 191), (64, 191), (63, 63), (64, 127)])]
    run.trace = {"window_start_s": 9.94, "window_s": 0.1, "busy_s": 0.09,
                 "host_minus_trace_clock_s": 1000.0, "device0_gaps": [],
                 "device0_self_s_by_name": {
                     "ssd_call.7": 0.010, "ssd_call.9": 0.002,
                     "tpu_custom_call.3": 0.0004,
                     "fusion.1": 0.03, "copy.3": 0.001}}
    return run


@pytest.mark.parametrize("name,expected", [
    # ticks 1 and 2 are in the slice: 12 ms of the kernel over them
    ("ssd_kernel_ms.tick", 1e3 * 0.012 / 2),
    # their slots (64 + 63) and rows (191 + 63) through five layers
    ("ssd_kernel_hbm.share",
     100 * 5 * (127 * S_BYTES + 254 * ROW_BYTES) / 0.012 / 819e9),
    # the SSD kernel's events are no paged kernel's: the K/V kernel alone
    ("paged_kernel_ms.tick", 1e3 * 0.0004 / 2),
    ("paged_kernel_hbm.share", 100 * 127_000 * 1024 / 0.0004 / 819e9),
])
def test_readers_give_the_hand_computed_value(name, expected):
    assert _read(name, _run()) == pytest.approx(expected)


def test_the_share_reads_a_hundred_where_the_counted_bytes_move_at_peak():
    """A tick whose kernel read exactly the floor at the chip's peak, every
    slot once and the rows beside them, is what the share counts as 100%: a
    kernel that also rewrites every slot reads about half, and no form of the
    layer can pass it."""
    run = _run()
    sizes = CONFIG["sizes"]
    moved = sum(family.ssd_bytes(sizes, t["ssd_rows"], t["ssd_seqs"])
                for t in run.ticks[1:3])
    run.trace["device0_self_s_by_name"] = {"ssd_call.7": moved / 819e9}
    assert _read("ssd_kernel_hbm.share", run) == pytest.approx(100.0)
    # this PR's kernel moves the floor and the write-back: under 51%
    written = sum(5 * S_BYTES * t["ssd_seqs"] for t in run.ticks[1:3])
    run.trace["device0_self_s_by_name"] = {
        "ssd_call.7": (moved + written) / 819e9}
    assert 49.0 < _read("ssd_kernel_hbm.share", run) < 51.0


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_older_programs_run(name):
    """The parent keeps none of the new fields and has no SSD kernel, and a
    family without Mamba-2 layers counts no such bytes: None, never an
    exception, with or without a trace."""
    run = _run()
    for tick in run.ticks:
        for field in ("ssd_rows", "ssd_seqs"):
            del tick[field]
    run.trace["device0_self_s_by_name"] = {"paged_attention_kv_call.3": 0.02}
    assert _read(name, run) is None
    run.trace = None
    assert _read(name, run) is None
    dense = _run()
    dense.config = {"sizes": {"num_hidden_layers": 2}, "family": "llama"}
    if name == "ssd_kernel_hbm.share":
        assert _read(name, dense) is None
