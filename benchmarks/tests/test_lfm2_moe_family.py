"""Self-tests of what PR 63 added to the benchmark: the `lfm2_moe` family file
(its contract, its counts, its routed form), the configuration file's two
copies of the published keys, the reference's two copies, the harness's check
on the tiny program with its controls, a rehearsal of the cell at TINY_SIZES,
and the two new readers on a made-up run whose values are worked out by hand.

    python -m pytest benchmarks/tests -q
"""

import json
import os
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

import harness
import serve_cell

family = harness.load_module("families", "lfm2_moe")
CONFIG = harness.load_json("configs", "lfm2-24b-a2b-l9.json")
CELL = "lfm2moe-longout-closed64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["conv_rows.mean", "xla_ops_ms.tick"]


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


def _tiny():
    from ray_tpu.models import lfm2_moe

    sizes = dict(CONFIG["sizes"], **family.TINY_SIZES)
    return sizes, lfm2_moe.init_params(family.model_config(sizes),
                                       jax.random.key(2))


# ---- the family file and the configuration -----------------------------------

def test_family_counts_are_issue_63s_arithmetic():
    """The configuration as the cell runs it: 5,177.9 M parameters (the
    embedding once: the head is tied), 4,096 B of cache a token over the two
    attention layers, 57,344 B a slot, 18.87 MB an expert met."""
    sizes = CONFIG["sizes"]
    mc = family.model_config(sizes)
    assert mc.num_params() == 5_177_911_296
    assert mc.experts_held == (0, 64) and mc.n_held == mc.num_experts == 64
    assert mc.layer_kinds() == ["conv_dense", "attn_moe", "conv_moe",
                                "conv_moe", "conv_moe", "attn_moe",
                                "conv_moe", "conv_moe", "conv_moe"]
    assert family.cache_bytes_per_token(sizes) == 2 * 2 * 8 * 64 * 2 == 4096
    assert family.state_bytes_per_sequence(sizes) == 7 * 2 * 2048 * 2 \
        == mc.state_bytes_per_sequence == 57_344
    assert family.expert_bytes(sizes, 1, 0) == 3 * 2048 * 1536 * 2 \
        == 18_874_368
    assert family.expert_bytes(sizes, 63 * 8, 64 * 4 * 8) == (
        63 * 8 * 18_874_368 + 2048 * 2 * 2048 * 2)
    assert family.attention_flops_per_pair(sizes) == 2 * 32 * 2 * 64 * 2
    assert family.train_flops_per_token(sizes, 4096) == pytest.approx(
        mc.flops_per_token(4096))
    deployment = CONFIG["deployment"]
    assert (deployment["max_batch_size"], deployment["num_kv_blocks"]) == (
        64, 32768)
    import train_cell
    assert not any(hasattr(family, n) for n in train_cell.TRAINING_NAMES)


def test_configuration_files_two_copies_of_the_published_keys_agree():
    """Every key of the published config stands at the top level of the file
    and under `sizes`; `reduced` names exactly the keys that differ from the
    published value it records; the manifest's entry says the same."""
    sizes = CONFIG["sizes"]
    own = {"head_dim", "num_experts_published", "first_held_expert",
           "n_routed_experts", "torch_dtype"}
    assert {k: v for k, v in sizes.items() if k not in own} == {
        k: CONFIG[k] for k in sizes if k not in own}
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "max_position_embeddings"}
    for key, entry in CONFIG["reduced"].items():
        assert sizes[key] != entry["published"], key
    assert sizes["layer_types"] == CONFIG["reduced"]["layer_types"][
        "published"][1:10]
    assert (sizes["n_routed_experts"] == sizes["num_experts"]
            == sizes["num_experts_published"] == 64)
    assert sizes["vocab_size"] == 65536 and sizes["first_held_expert"] == 0
    manifest = [c for c in harness.load_manifest()["configs"]
                if c["name"] == "lfm2-24b-a2b-l9"][0]
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
               if '"LFM2-24B-A2B"' in line][0]
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CONFIG, key
        if key in CONFIG["reduced"]:
            assert CONFIG["reduced"][key]["published"] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_cell_joins_the_readers_that_fit_it_and_not_the_others():
    """The cell runs the EXISTING traffic file of the two other routed
    hybrids, reports the two end-to-end metrics they report, joins every
    reader both of their cells list (none of a kernel it does not have), and
    the two new entries list it ALONE."""
    manifest = harness.load_manifest()
    cell = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b-l9", "longout-closed64", 1)
    assert manifest["workloads"][-1] is cell
    assert len(manifest["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    for metric in manifest["end_to_end"]:
        if metric["name"] in ("itl_ms.p95", "serve_tokens_per_s"):
            assert metric["workloads"][-1] == CELL
    others = ("kimilinear-longout-closed64",
              "nemotron3super-longout-closed64")
    for p in manifest["per_layer"]:
        listed = p["workloads"]
        if p["name"] in NEW:
            assert listed == [CELL], p["name"]
        elif p["name"] == "expert_product_hbm.share":
            assert listed == ["trinitylarge-docqa-closed32", CELL]
        else:
            assert (CELL in listed) == all(o in listed for o in others), \
                p["name"]
    assert [p["name"] for p in manifest["per_layer"][-2:]] == NEW
    layers = {p["layer"] for p in manifest["per_layer"][:-2]}
    assert all(p["layer"] in layers for p in manifest["per_layer"][-2:])


def test_the_two_references_are_one_file_and_give_the_same_logits():
    from ray_tpu.models import lfm2_moe_reference as ours

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
    float32, 136 positions: the sound reference passes far inside the
    tolerance with no shortfall, and the reference with one term changed
    fails it by the logits (the tail zeroed at the slices' and the decode
    rows' first positions)."""
    sizes, params = _tiny()
    sound = serve_cell.check_logits(_Server(sizes, params), family, sizes, 3)
    assert sound["ok"] and sound["rel_err"] < 1e-4
    assert sound["routed_choices"] == 4 * 2 * 136
    assert sound["shortfall_max"] == 0.0
    starts = list(range(0, 128, 16)) + list(range(128, 136))
    for fault in family.reference.FAULTS:
        if fault == "tail_zeroed":
            fault = (fault, starts)

        def routed(p, t, pos, s, kept, fault=fault):
            logits, _ = family.reference.logits_at(p, t, pos, s,
                                                   np.asarray(kept), fault)
            return logits, np.zeros(np.asarray(kept).shape[:3])

        faulty = types.SimpleNamespace(reference_logits_routed=routed)
        result = serve_cell.check_logits(_Server(sizes, params), faulty,
                                         sizes, 3)
        assert not result["ok"], (fault, result)


def test_a_rehearsal_of_the_cell_runs_every_listed_reader():
    """`rehearse.py` on the cell at TINY_SIZES with a traced slice: the same
    manifest entry, family file, runner and readers as the benchmark's
    command; the run is correct and every listed per-layer reader but the
    device's own (no TPU here: no Pallas event, no trace of the products)
    returns a number."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "rehearse.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "3",
         "--trace", "1"], capture_output=True, text=True, env=env,
        cwd=harness.ROOT, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines()
            if l.startswith("REHEARSAL")][-1]
    assert '"failed": 0' in line or '"failed":0' in line, line
    assert "conv_rows.mean" in line, line


# ---- the readers ------------------------------------------------------------

def _run():
    """Four ticks of 18 ms in a 40 s window, the middle two inside a traced
    slice."""
    run = harness.Run(
        kind="closed",
        config={"sizes": CONFIG["sizes"], "family": "lfm2_moe"},
        traffic={}, chips=1, device={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = [
        {"t": 1009.9 + 0.05 * i, "dur_ms": 18.0, "kind": "mixed",
         "decode_rows": 63, "conv_rows": rows, "conv_seqs": 64,
         "expert_rows": 32 * rows, "expert_rows_max": 9,
         "experts_met": 500}
        for i, rows in enumerate([64, 191, 64, 0])]
    run.trace = {"window_start_s": 9.94, "window_s": 0.1, "busy_s": 0.09,
                 "host_minus_trace_clock_s": 1000.0, "device0_gaps": [],
                 "device0_self_s_by_name": {
                     "grouped_dot_call.7": 0.020, "ragged-dot-none.2": 0.001,
                     "tpu_custom_call.3": 0.004,
                     "paged_attention_unified.9": 0.001,
                     "fusion.1": 0.003, "fusion.44": 0.0005,
                     "sort.2": 0.0004, "while": 0.0001, "copy.3": 0.001,
                     "copy-start.1": 0.0002}}
    return run


@pytest.mark.parametrize("name,expected", [
    # the three ticks that carried rows, x 7 conv layers
    ("conv_rows.mean", 7 * (64 + 191 + 64) / 3),
    # fusions, the sort, the loop's own time and the asynchronous copy: not
    # the Pallas calls, the products or the plain copy; ticks 1 and 2
    ("xla_ops_ms.tick", 1e3 * (0.003 + 0.0005 + 0.0004 + 0.0001 + 0.0002) / 2),
    ("expert_product_ms.tick", 1e3 * 0.021 / 2),
    ("paged_kernel_ms.tick", 1e3 * 0.005 / 2),
])
def test_readers_give_the_hand_computed_value(name, expected):
    assert _read(name, _run()) == pytest.approx(expected)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_older_programs_run(name):
    """A program that keeps no `conv_rows`, a configuration with no conv
    layer, a run without a trace: None, never an exception."""
    run = _run()
    for tick in run.ticks:
        del tick["conv_rows"]
    run.trace = None
    assert _read(name, run) is None
    other = _run()
    other.config = {"sizes": {"num_hidden_layers": 2}, "family": "llama"}
    other.trace = None
    assert _read(name, other) is None
