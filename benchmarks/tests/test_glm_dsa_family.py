"""Self-tests of what PR 49 added to the benchmark: the `glm_dsa` family file
(its contract and its counts, by hand at the published widths), the
configuration file's two copies of the published keys, the reference's two
copies and the reference against the program at `TINY_SIZES`, and the six new
readers on a made-up run whose values are worked out by hand: none of them
over 100% on a tick whose kernel read exactly the floor.

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

family = harness.load_module("families", "glm_dsa")
CONFIG = harness.load_json("configs", "glm-5.2-l8-e8.json")
TRAFFIC = harness.load_json("traffic", "longdoc-closed32.json")
CELL = "glm52-longdoc-closed32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["dsa_index_ms.tick", "dsa_index_hbm.share", "dsa_attend_ms.tick",
       "dsa_attend_hbm.share", "dsa_attend_mxu.share",
       "dsa_rows_skipped.share"]
OWN = {"n_routed_experts_published", "first_held_expert", "rope_theta",
       "torch_dtype"}


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


# ---- the family file and the configuration ----------------------------------

def test_family_counts_are_issue_49s_arithmetic():
    sizes = CONFIG["sizes"]
    # attention 165.0 M: W_qa 12.58, W_qb 33.55, W_kva 3.54, W_kb 6.29, W_vb
    # 8.39, W_o 100.66
    assert family.attention_params(sizes) == (
        6144 * 2048 + 2048 * 64 * 256 + 6144 * 576 + 512 * 64 * (192 + 256)
        + 64 * 256 * 6144) == 165_019_648
    # an indexer 9.37 M: W_qI 8.39, W_kI 0.79, W_w 0.20
    assert family.indexer_params(sizes) == (
        2048 * 32 * 128 + 6144 * 128 + 6144 * 32) == 9_371_648
    expert, router, dense = 3 * 6144 * 2048, 6144 * 256, 3 * 6144 * 12288
    assert (expert, router, dense) == (37_748_736, 1_572_864, 226_492_416)
    by_hand = (2 * 19360 * 6144 + 8 * 165_019_648 + 2 * 9_371_648 + dense
               + 7 * (9 * expert + router))
    assert family.num_params(sizes) == by_hand == 4_192_468_992   # 4,192 M
    assert family.num_params(sizes) * 2 == pytest.approx(8.38e9, rel=1e-3)
    # a token: the 576-wide latent row of 8 layers, a 128-wide index key of
    # each of 2, bfloat16
    assert family.cache_bytes_per_token(sizes) == 8 * 1152
    assert family.index_bytes_per_row(sizes) == 256
    assert family.index_layers(sizes) == 2
    assert family.attention_flops_per_pair(sizes) == 8 * 64 * 512 * 2
    mc = family.model_config(sizes)
    assert (mc.num_hidden_layers, mc.vocab_size, mc.index_topk, mc.max_seq,
            mc.experts_held, mc.n_routed_experts, mc.v_head_dim) == (
        8, 19360, 2048, 36864, (0, 8), 256, 256)
    assert mc.num_params() == family.num_params(sizes)
    assert mc.layer_kinds() == ("full_dense",) + (
        "shared_moe", "shared_moe", "shared_moe", "full_moe", "shared_moe",
        "shared_moe", "shared_moe")
    assert not any(hasattr(family, n) for n in (
        "loss_fn", "param_logical_axes", "init_params"))
    assert callable(family.reference_loss_and_grad_norm)
    assert family.train_flops_per_token(sizes, 4096) == pytest.approx(
        mc.flops_per_token(4096))
    shapes = {k for k, v in sizes.items()
              if isinstance(v, int) and not isinstance(v, bool)}
    assert shapes <= set(family.TINY_SIZES), shapes - set(family.TINY_SIZES)
    tiny = family.model_config(dict(sizes, **family.TINY_SIZES))
    assert (tiny.num_hidden_layers, tiny.index_topk, tiny.n_held,
            tiny.n_full_layers) == (4, 8, 8, 2)


def test_configuration_files_two_copies_of_the_published_keys_agree():
    sizes = CONFIG["sizes"]
    assert OWN <= set(sizes)
    assert {k: v for k, v in sizes.items() if k not in OWN} == {
        k: CONFIG[k] for k in sizes if k not in OWN}
    assert list(CONFIG["reduced"]) == [
        "num_hidden_layers", "first_k_dense_replace", "mlp_layer_types",
        "indexer_types", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers", "max_position_embeddings"]
    for key, entry in CONFIG["reduced"].items():
        assert entry["published"] != CONFIG[key] and entry["why"], key
    # the two lists are the published ones' layers 2-9
    for key in ("indexer_types", "mlp_layer_types"):
        assert CONFIG[key] == CONFIG["reduced"][key]["published"][2:10], key
    assert CONFIG["indexer_types"].count("full") * 3 \
        == CONFIG["indexer_types"].count("shared")
    assert sizes["rope_theta"] == CONFIG["rope_parameters"]["rope_theta"]
    assert sizes["n_routed_experts"] == 8
    deployment = CONFIG["deployment"]
    assert deployment["max_batch_size"] == TRAFFIC["clients"] == 32
    manifest = [c for c in harness.load_manifest()["configs"]
                if c["name"] == "glm-5.2-l8-e8"][0]
    assert manifest["reduced"] == list(CONFIG["reduced"])
    assert manifest["source"] == CONFIG["source"]
    assert manifest["file"] == "benchmarks/configs/glm-5.2-l8-e8.json"
    # the block table holds the longest request, the pool the traffic's live
    # tokens
    longest = (TRAFFIC["shared_prefixes"]["len"]
               + TRAFFIC["prompt_len"]["max"] + TRAFFIC["output_len"]["max"])
    assert longest <= sizes["max_position_embeddings"] == 36864
    live = (TRAFFIC["shared_prefixes"]["count"]
            * TRAFFIC["shared_prefixes"]["len"]
            + 32 * (TRAFFIC["prompt_len"]["max"]
                    + TRAFFIC["output_len"]["max"]))
    assert live <= deployment["num_kv_blocks"] * 16 == 327_680
    assert "32" in CONFIG["stands_for"] and "2-9" in CONFIG["stands_for"]
    assert {"indexer_inputs", "index_key_norm", "index_scales", "index_rope",
            "selection", "precision", "router", "weights"} <= set(
        CONFIG["assumed"])
    assert "MTP" in CONFIG["left_out"] and "Hadamard" in CONFIG["left_out"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_holds_every_key_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"name": "GLM-5.2"' in line][0]
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
    assert {"tick_ms.p50", "expert_rows.mean", "expert_load_skew.mean",
            "prefix_share", "prefill_ms.p50", "pool_copy_ms.tick"} <= listed
    # the dense counts times the family's bytes or operations would read
    # over 100% for a kernel that reads a sixteenth of them; no context here
    # is short enough for the dense latent kernel
    assert not listed & {"paged_kernel_hbm.share", "latent_kernel_mxu.share",
                         "paged_kernel_ms.tick", "window_kernel_ms.tick",
                         "ssm_kernel_ms.tick", "kda_kernel_ms.tick",
                         "queue_ms.p95"}
    assert [p["name"] for p in manifest["per_layer"]][-6:] == NEW
    layers = {p["layer"] for p in manifest["per_layer"]
              if p["name"] not in NEW}
    for p in manifest["per_layer"][-6:]:
        assert p["layer"] in layers and p["workloads"] == [CELL]
        assert p["moves"] == "itl_ms.p95"
        assert p["source"] == ("program_counter" if p["name"]
                               == "dsa_rows_skipped.share" else
                               "device_trace")
    e2e = {e["name"] for e in harness.metrics_of(manifest, "end_to_end",
                                                 CELL)}
    assert e2e == {"setup_s", "itl_ms.p95", "serve_tokens_per_s"}
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-5.2-l8-e8", "longdoc-closed32", 1)
    assert manifest["workloads"][-1] == cell and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


# ---- the reference ----------------------------------------------------------

def _tiny():
    from ray_tpu.models import glm_dsa

    sizes = dict(CONFIG["sizes"], **family.TINY_SIZES)
    params = glm_dsa.init_params(family.model_config(sizes),
                                 jax.random.key(2))
    return sizes, params


def test_the_two_references_are_one_file_and_give_the_same_logits():
    from ray_tpu.models import glm_dsa_reference as ours

    theirs = family.reference
    with open(ours.__file__) as a, open(theirs.__file__) as b:
        text = a.read()
        assert text == b.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    sizes, params = _tiny()
    tokens = np.random.default_rng(6).integers(1, 256, (2, 20)).astype(
        np.int32)
    a = ours.logits_at(params, tokens, [3, 19], sizes)[0]
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


def test_the_harness_check_passes_the_program_and_sees_a_selection_here():
    """`serve_cell.check_logits` as the cell runs it, the routed form, at the
    tiny sizes in float32, where `index_topk` is 8 and the check's 136
    positions run over it (at the published 2,048 its 264 do NOT: PERF.md
    section 7): the sound reference passes far inside the tolerance, and one
    that keeps the most recent rows fails it."""
    sizes, params = _tiny()
    sound = serve_cell.check_logits(_Server(sizes, params), family, sizes, 3)
    assert sound["ok"] and sound["rel_err"] < 1e-4
    assert sound["routed_choices"] == 2 * 2 * 136
    assert sound["shortfall_max"] == 0.0
    for fault in ("recent_rows", "rotate_half", "share_nothing"):
        def routed(p, t, pos, s, kept, fault=fault):
            logits = family.reference.logits_at(p, t, pos, s, kept,
                                                fault=fault)[0]
            return logits, np.zeros(np.asarray(kept).shape[:3])

        faulty = types.SimpleNamespace(reference_logits_routed=routed)
        result = serve_cell.check_logits(_Server(sizes, params), faulty,
                                         sizes, 3)
        assert not result["ok"], (fault, result["rel_err"])


# ---- the readers ------------------------------------------------------------

def _run():
    """Four ticks of 32 decode rows at contexts of 34,000 (one with a slice
    of 128 beside them); ticks 1 and 2 lie in the traced slice."""
    run = harness.Run(
        kind="closed",
        config={"sizes": CONFIG["sizes"], "family": "glm_dsa"},
        traffic={}, chips=1, device={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = [
        {"t": 1009.9 + 0.05 * i, "dur_ms": 45.0, "kind": "mixed",
         "decode_rows": 32, "prefill_rows": seqs - 32, "used": rows,
         "kv_tokens": 34_000 * seqs, "attn_pairs": 34_000 * rows,
         "dsa_pairs": 2048 * rows, "dsa_index_rows": 34_000 * seqs,
         "dsa_attend_rows": 2048 * seqs, "dsa_selected_rows": seqs}
        for i, (seqs, rows) in enumerate(
            [(33, 160), (33, 160), (32, 32), (32, 32)])]
    run.trace = {"window_start_s": 9.94, "window_s": 0.1, "busy_s": 0.09,
                 "host_minus_trace_clock_s": 1000.0, "device0_gaps": [],
                 "device0_self_s_by_name": {
                     "dsa_index_call.7": 0.004, "dsa_select_call.9": 0.001,
                     "dsa_attend_call.3": 0.010, "dsa_attend_call.5": 0.002,
                     "paged_attention_latent_call.3": 0.004,
                     "fusion.1": 0.03, "copy.3": 0.001}}
    return run


@pytest.mark.parametrize("name,expected", [
    # ticks 1 and 2 are in the slice: 5 ms of the two indexer kernels
    ("dsa_index_ms.tick", 1e3 * 0.005 / 2),
    # their rows' contexts (33 + 32 rows of 34,000) x 256 B x 2 layers
    ("dsa_index_hbm.share", 100 * 65 * 34_000 * 256 * 2 / 0.005 / 819e9),
    ("dsa_attend_ms.tick", 1e3 * 0.012 / 2),
    # a row's set once: 65 rows x 2,048 x 8 layers x 1,152 B
    ("dsa_attend_hbm.share", 100 * 65 * 2048 * 9216 / 0.012 / 819e9),
    # (160 + 32) query tokens x 2,048 pairs x 8 x 64 x 512 x 2
    ("dsa_attend_mxu.share",
     100 * 192 * 2048 * 524_288 / 0.012 / 197e12),
    # over the WINDOW's four ticks
    ("dsa_rows_skipped.share", 100 * (1 - 2048 / 34_000)),
])
def test_readers_give_the_hand_computed_value(name, expected):
    assert _read(name, _run()) == pytest.approx(expected)


def test_no_share_passes_a_hundred_where_the_kernel_reads_exactly_the_floor():
    """A tick of decode rows whose kernels moved exactly the counted bytes at
    the chip's peak reads 100%, and the operations' share of the same tick is
    far under it (a decode row is bound by its bytes). A kernel that reads a
    set a token, rows padded to 640 lanes, reads less."""
    run = _run()
    run.ticks[1] = dict(run.ticks[2])
    run.ticks[1]["t"] = 1009.95
    sizes = CONFIG["sizes"]
    rows = 64 * 2048 * family.cache_bytes_per_token(sizes)
    keys = 64 * 34_000 * 256 * 2
    run.trace["device0_self_s_by_name"] = {
        "dsa_attend_call.1": rows / 819e9, "dsa_index_call.1": keys / 819e9}
    assert _read("dsa_attend_hbm.share", run) == pytest.approx(100.0)
    assert _read("dsa_index_hbm.share", run) == pytest.approx(100.0)
    assert _read("dsa_attend_mxu.share", run) < 100.0


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_older_programs_run(name):
    """The parent keeps none of the new fields and has no such kernel, and a
    family without an indexer counts no such bytes: None, never an exception,
    with or without a trace."""
    run = _run()
    for tick in run.ticks:
        for field in ("dsa_pairs", "dsa_index_rows", "dsa_attend_rows",
                      "dsa_selected_rows"):
            del tick[field]
    run.trace["device0_self_s_by_name"] = {"paged_attention_kv_call.3": 0.02}
    assert _read(name, run) is None
    run.trace = None
    assert _read(name, run) is None
    dense = _run()
    dense.config = {"sizes": {"num_hidden_layers": 2}, "family": "llama"}
    dense.trace["device0_self_s_by_name"] = {"fusion.1": 0.02}
    assert _read(name, dense) is None or name == "dsa_rows_skipped.share"
