"""Self-tests of what PR 59 added to the benchmark: the `afmoe` family file
(its contract, its counts, its routed form), the configuration file's two
copies of the published keys, the reference's two copies, the harness's check
on the tiny program with its controls, and the three new readers on a made-up
run whose values are worked out by hand.

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

family = harness.load_module("families", "afmoe")
CONFIG = harness.load_json("configs", "trinity-large-l5-e32.json")
CELL = "trinitylarge-docqa-closed32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["expert_product_hbm.share", "window_tail_pages.hit",
       "window_pool_used.share"]


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


def _tiny():
    from ray_tpu.models import afmoe

    sizes = dict(CONFIG["sizes"], **family.TINY_SIZES)
    return sizes, afmoe.init_params(family.model_config(sizes),
                                    jax.random.key(2))


# ---- the family file and the configuration -----------------------------------

def test_family_counts_are_issue_59s_arithmetic():
    sizes = CONFIG["sizes"]
    assert family.cache_bytes_per_token(sizes) == 1 * 8 * 256 * 2 == 4096
    assert family.window_cache_bytes_per_token(sizes) == 4 * 4096 == 16384
    assert family.attention_flops_per_pair(sizes) == 48 * 256 * 2
    # a met expert's three matrices (56.6 MB) and a row in and out
    assert family.expert_bytes(sizes, 1, 0) == 3 * 3072 * 3072 * 2
    assert family.expert_bytes(sizes, 0, 1) == 2 * 3072 * 2
    mc = family.model_config(sizes)
    assert (mc.n_held, mc.num_experts, mc.vocab_size) == (32, 256, 25024)
    assert mc.layer_kinds() == ["window_dense", "window_moe", "window_moe",
                                "full_moe", "window_moe"]
    assert (mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim,
            mc.intermediate_size, mc.moe_intermediate_size,
            mc.num_experts_per_tok, mc.route_scale, mc.sliding_window) == (
        48, 8, 128, 12288, 3072, 4, 2.448, 4096)
    # the issue's 4,321.8 M: 153.7 + 176.16 + 4 x 998.0
    assert mc.num_params() == 4_321_837_056
    assert mc.num_params() * 2 == pytest.approx(8.64e9, rel=1e-3)
    assert family.train_flops_per_token(sizes, 4096) == pytest.approx(
        mc.flops_per_token(4096))
    assert not any(hasattr(family, n) for n in
                   ("loss_fn", "param_logical_axes", "init_params"))
    shapes = {k for k, v in sizes.items()
              if isinstance(v, int) and not isinstance(v, bool)}
    assert shapes <= set(family.TINY_SIZES), shapes - set(family.TINY_SIZES)
    tiny = family.model_config(dict(sizes, **family.TINY_SIZES))
    assert tiny.experts_held == (0, 8) and tiny.sliding_window == 8
    assert tiny.num_attention_heads // tiny.num_key_value_heads == 6


def test_configuration_files_two_copies_of_the_published_keys_agree():
    """Every key of the published config stands at the top level of the file
    and under `sizes`; `reduced` names exactly the keys that differ from the
    published value it records; the manifest's entry says the same."""
    sizes = CONFIG["sizes"]
    own = {"num_experts_published", "first_held_expert", "n_routed_experts",
           "torch_dtype"}
    assert {k: v for k, v in sizes.items() if k not in own} == {
        k: CONFIG[k] for k in sizes if k not in own}
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size", "max_position_embeddings"}
    for key, entry in CONFIG["reduced"].items():
        assert sizes[key] != entry["published"], key
    assert sizes["layer_types"] == CONFIG["reduced"]["layer_types"][
        "published"][:5]
    assert sizes["n_routed_experts"] == sizes["num_experts"] == 32
    manifest = [c for c in harness.load_manifest()["configs"]
                if c["name"] == "trinity-large-l5-e32"][0]
    assert manifest["reduced"] == list(CONFIG["reduced"])
    assert manifest["source"] == CONFIG["source"]
    assert set(CONFIG["deployment"]) == {"max_batch_size", "num_kv_blocks",
                                         "why"}
    assert "open" in CONFIG["assumed"]
    json.dumps(CONFIG)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_holds_every_key_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"Trinity-Large-Preview"' in line][0]
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CONFIG, key
        if key in CONFIG["reduced"]:
            assert CONFIG["reduced"][key]["published"] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_cell_joins_the_readers_that_fit_it_and_not_the_others():
    """The cell runs the existing traffic file, reports the three end-to-end
    metrics, joins every reader MiMo's or DeepSeek-V2's cell lists but the
    latent kernel's, and the three new entries list it ALONE (an entry that
    lists an existing cell makes the parent's run of that cell incorrect)."""
    manifest = harness.load_manifest()
    cell = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-large-l5-e32", "docqa-closed32", 1)
    assert manifest["workloads"][-1] is cell
    for metric in manifest["end_to_end"]:
        if metric["name"] in ("itl_ms.p95", "serve_tokens_per_s"):
            assert metric["workloads"][-1] == CELL
    others = ("mimov2flash-longdoc-closed32", "deepseekv2-docqa-closed32")
    for p in manifest["per_layer"]:
        listed = p["workloads"]
        if p["name"] in NEW:
            assert listed == [CELL], p["name"]
        elif p["name"] == "latent_kernel_mxu.share":
            assert CELL not in listed
        else:
            assert (CELL in listed) == any(o in listed for o in others), \
                p["name"]
    assert [p["name"] for p in manifest["per_layer"][-3:]] == NEW
    layers = {p["layer"] for p in manifest["per_layer"][:-3]}
    assert all(p["layer"] in layers for p in manifest["per_layer"][-3:])


def test_the_two_references_are_one_file_and_give_the_same_logits():
    from ray_tpu.models import afmoe_reference as ours

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


def test_routed_reference_follows_the_program_and_reports_shortfalls():
    """At TINY_SIZES: given the reference's own choice the shortfall is 0
    everywhere and the logits are the free-running ones; given a k-th expert
    that is the worst by score + bias, the shortfall is large."""
    sizes, params = _tiny()
    tokens = np.random.default_rng(1).integers(1, 256, (2, 12)).astype(
        np.int32)
    positions = [4, 11]
    free, found = family.reference.logits_at(params, tokens, positions, sizes)
    scores = found["scores"]
    assert scores.shape == (4, 2, 12, 16) and (scores > 0).all()
    own = np.argsort(-scores, axis=-1, kind="stable")[..., :4]
    logits, short = family.reference_logits_routed(
        params, tokens, positions, sizes, own)
    assert short.shape == (4, 2, 12) and not short.any()
    np.testing.assert_allclose(np.asarray(logits), np.asarray(free),
                               rtol=1e-5, atol=1e-5)
    worst = own.copy()
    worst[..., -1] = np.argmin(scores, axis=-1)
    _, short = family.reference_logits_routed(params, tokens, positions,
                                              sizes, worst)
    assert short.max() > 0.3


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
    float32, 136 positions against a window of 8: the sound reference passes
    far inside the tolerance with no shortfall, and the reference with one
    term changed fails it (the selection bias by the shortfall, the other
    five by the logits)."""
    sizes, params = _tiny()
    sound = serve_cell.check_logits(_Server(sizes, params), family, sizes, 3)
    assert sound["ok"] and sound["rel_err"] < 1e-4
    assert sound["routed_choices"] == 4 * 2 * 136
    assert sound["shortfall_max"] == 0.0
    for fault in family.reference.FAULTS:
        def routed(p, t, pos, s, kept, fault=fault):
            kept = np.asarray(kept)
            logits, found = family.reference.logits_at(p, t, pos, s, kept,
                                                       fault)
            layers, b, n, k = kept.shape
            import routing
            short = np.stack([routing.shortfall(
                found["scores"][i].reshape(b * n, -1),
                kept[i].reshape(b * n, k), k, 1, 1).reshape(b, n)
                for i in range(layers)])
            return logits, short

        faulty = types.SimpleNamespace(reference_logits_routed=routed)
        result = serve_cell.check_logits(_Server(sizes, params), faulty,
                                         sizes, 3)
        assert not result["ok"], (fault, result)
        if fault == "no_bias":
            assert result["shortfall_max"] > serve_cell.ROUTING_TIE_MARGIN


# ---- the readers ------------------------------------------------------------

def _run():
    """Four ticks of 16 ms in a 40 s window, the middle two inside a traced
    slice that holds 9 ms of the expert products; two prefix hits in the
    window, whose tails ticks 0 and 2 attached."""
    run = harness.Run(
        kind="closed",
        config={"sizes": CONFIG["sizes"], "family": "afmoe"},
        traffic={}, chips=1, device={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = [
        {"t": 1009.9 + 0.05 * i, "dur_ms": 16.0, "kind": "mixed",
         "decode_rows": 31, "expert_rows": rows, "expert_rows_max": 9,
         "experts_met": met, "window_tail_pages": tails,
         "window_pool_used": used, "window_kv_tokens": 126976}
        for i, (rows, met, tails, used) in enumerate(
            [(60, 30, 256, 9000), (140, 80, 0, 11000), (62, 28, 250, 12288),
             (58, 31, 0, 12000)])]
    run.stats_before = {"prefix_hits": 40}
    run.stats_after = {"prefix_hits": 42, "kv_groups": {
        "all": {"total": 12288, "free": 0, "live": 9000, "parked": 3288},
        "window": {"total": 12288, "free": 0, "live": 8600, "parked": 3688}}}
    run.trace = {"window_start_s": 9.94, "window_s": 0.1, "busy_s": 0.09,
                 "host_minus_trace_clock_s": 1000.0, "device0_gaps": [],
                 "device0_self_s_by_name": {
                     "grouped_dot_call.7": 0.008, "grouped_dot_call.9": 0.001,
                     "paged_attention_window_call.3": 0.009,
                     "fusion.1": 0.01, "copy.3": 0.001}}
    return run


@pytest.mark.parametrize("name,expected", [
    # ticks 1 and 2 are in the slice: 108 met experts' matrices and 202 rows
    # in and out over the products' 9 ms
    ("expert_product_hbm.share",
     100 * (108 * 3 * 3072 * 3072 * 2 + 202 * 2 * 3072 * 2) / 0.009 / 819e9),
    ("expert_product_ms.tick", 1e3 * 0.009 / 2),
    # 506 pages attached by the window's 2 hits
    ("window_tail_pages.hit", 253.0),
    # the pool's fullest tick over its 12,288 pages
    ("window_pool_used.share", 100.0),
])
def test_readers_give_the_hand_computed_value(name, expected):
    assert _read(name, _run()) == pytest.approx(expected)


def test_the_share_cannot_pass_a_hundred_where_every_expert_streams_at_peak():
    """All 128 held experts of the four routed layers met in both of the
    slice's ticks and streamed at the chip's peak, no row: exactly 100."""
    run = _run()
    for tick in run.ticks:
        tick.update(experts_met=128, expert_rows=0)
    seconds = 2 * 128 * 3 * 3072 * 3072 * 2 / 819e9
    run.trace["device0_self_s_by_name"] = {"grouped_dot_call.1": seconds}
    assert _read("expert_product_hbm.share", run) == pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_older_programs_run(name):
    """The parent keeps none of the new fields: None, never an exception,
    with or without a trace; and a family without `expert_bytes`, a window
    without a hit or a program without a window group have nothing to
    read."""
    run = _run()
    for tick in run.ticks:
        for field in ("experts_met", "window_tail_pages", "window_pool_used"):
            del tick[field]
    assert _read(name, run) is None
    run.trace = None
    assert _read(name, run) is None
    other = _run()
    other.config = {"sizes": {"num_hidden_layers": 2}, "family": "llama"}
    other.stats_after = {"prefix_hits": 40}
    assert _read(name, other) is None
