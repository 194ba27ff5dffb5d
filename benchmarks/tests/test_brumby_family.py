"""Self-tests of what PR 43 added to the benchmark: the `brumby` family file
(its contract and its counts), the configuration file's two copies of the
published keys, the reference's two copies and the reference against the
program at `TINY_SIZES`, the two new readers on a made-up run whose values
are worked out by hand, and the controls of the comparison.

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

family = harness.load_module("families", "brumby")
CONFIG = harness.load_json("configs", "brumby-14b-l6.json")
TRAFFIC = harness.load_json("traffic", "longgen-closed16.json")
CELL = "brumby14b-longgen-closed16"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["retention_kernel_ms.tick", "retention_kernel_hbm.share"]
STATE = 8 * (8256 * 128 + 8256) * 4      # a layer's S and z, useful bytes


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


# ---- the family file and the configuration ----------------------------------

def test_family_counts_are_issue_43s():
    sizes = CONFIG["sizes"]
    assert family.cache_bytes_per_token(sizes) == 0
    assert family.features(sizes) == 8256
    assert family.num_params(sizes) == 3_537_947_184      # 3,537.9 M
    assert family.num_params(sizes) * 2 == pytest.approx(7.08e9, rel=1e-3)
    # a slot: useful 204.5 MB, as it lies 206.07 MB (8,320 lanes of features)
    assert 6 * STATE == pytest.approx(204.5e6, rel=1e-3)
    assert family.state_bytes_per_sequence(sizes) \
        == 6 * 8 * 65 * 128 * 129 * 4 == 206_069_760
    # a sequence's S and z read once a layer; a row's q, o (40 heads), k, v
    # (8 heads) in bfloat16 and its 8 gates in float32
    assert family.retention_bytes(sizes, 0, 1) == 6 * STATE
    assert family.retention_bytes(sizes, 1, 0) \
        == 6 * (2 * 128 * (80 + 16) + 32)
    mc = family.model_config(sizes)
    assert (mc.num_hidden_layers, mc.vocab_size, mc.head_dim,
            mc.max_seq) == (6, 151936, 128, 32768)
    assert mc.num_params() == family.num_params(sizes)
    assert mc.state_bytes_per_sequence == family.state_bytes_per_sequence(
        sizes)
    assert not any(hasattr(family, n) for n in (
        "loss_fn", "param_logical_axes", "init_params"))
    assert family.train_flops_per_token(sizes, 4096) == pytest.approx(
        mc.flops_per_token(4096))
    shapes = {k for k, v in sizes.items()
              if isinstance(v, int) and not isinstance(v, bool)}
    assert shapes <= set(family.TINY_SIZES), shapes - set(family.TINY_SIZES)
    tiny = family.model_config(dict(sizes, **family.TINY_SIZES))
    assert (tiny.num_hidden_layers, tiny.num_attention_heads,
            tiny.num_key_value_heads, tiny.head_dim) == (2, 6, 2, 16)


def test_configuration_files_two_copies_of_the_published_keys_agree():
    sizes = CONFIG["sizes"]
    own = {"retention_eps", "torch_dtype"}
    assert own <= set(sizes)
    assert {k: v for k, v in sizes.items() if k not in own} == {
        k: CONFIG[k] for k in sizes if k not in own}
    assert set(CONFIG["reduced"]) == {"num_hidden_layers"}
    assert sizes["num_hidden_layers"] == 6 \
        != CONFIG["reduced"]["num_hidden_layers"]["published"]
    deployment = CONFIG["deployment"]
    assert deployment["max_batch_size"] == 16
    manifest = [c for c in harness.load_manifest()["configs"]
                if c["name"] == "brumby-14b-l6"][0]
    assert set(manifest["reduced"]) == set(CONFIG["reduced"])
    assert manifest["source"] == CONFIG["source"]
    assert manifest["file"] == "benchmarks/configs/brumby-14b-l6.json"
    # the accounting pages: 2 x 16 sequences of the longest request
    longest = TRAFFIC["prompt_len"]["max"] + TRAFFIC["output_len"]["max"]
    assert longest <= sizes["max_position_embeddings"]
    assert deployment["num_kv_blocks"] == 2 * 16 * -(-longest // 16)
    assert TRAFFIC["clients"] == deployment["max_batch_size"]
    assert {"degree", "gate", "qk_norm_and_rope", "scale", "retention_eps",
            "state_precision", "weights"} <= set(CONFIG["assumed"])


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_holds_every_key_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"Brumby-14B-Base"' in line][0]
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
    # no pool, no pages, no scan, no experts: those readers find nothing
    assert not listed & {"paged_kernel_ms.tick", "paged_kernel_hbm.share",
                         "pool_copy_ms.tick", "ssm_kernel_ms.tick",
                         "prefix_share", "queue_ms.p95", "expert_rows.mean"}
    new = [p for p in manifest["per_layer"] if p["name"] in NEW]
    assert [p["name"] for p in manifest["per_layer"]][-2:] == NEW
    layers = {p["layer"] for p in manifest["per_layer"]
              if p["name"] not in NEW}
    for p in new:       # "this cell is listed", not "one cell"
        assert p["layer"] in layers and CELL in p["workloads"]
        assert p["moves"] == "itl_ms.p95"
    e2e = {e["name"] for e in harness.metrics_of(manifest, "end_to_end",
                                                 CELL)}
    assert e2e == {"setup_s", "itl_ms.p95", "serve_tokens_per_s"}
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "brumby-14b-l6", "longgen-closed16", 1)


# ---- the reference ----------------------------------------------------------

def _tiny():
    from ray_tpu.models import brumby

    sizes = dict(CONFIG["sizes"], **family.TINY_SIZES)
    params = brumby.init_params(family.model_config(sizes),
                                jax.random.key(2))
    return sizes, params


def test_the_two_references_are_one_file_and_give_the_same_logits():
    from ray_tpu.models import brumby_reference as ours

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
    """`serve_cell.check_logits` as the cell runs it (two prompts in chunks
    through `runner.step`, then decode positions; the table it lays from
    `num_blocks` and `max_blocks_per_seq` is the accounting group's, which no
    program reads), at the tiny sizes in float32: the sound reference passes
    far inside the tolerance, and the reference with one term dropped fails
    it."""
    sizes, params = _tiny()
    sound = serve_cell.check_logits(_Server(sizes, params), family, sizes, 3)
    assert sound["ok"] and sound["rel_err"] < 1e-4
    starts = list(range(0, 128, 16)) + list(range(128, 136))
    for fault in (("state_not_carried", starts), "no_gate", "no_qk_norm"):
        faulty = types.SimpleNamespace(
            reference_logits_at=lambda p, t, pos, s, fault=fault:
            family.reference.logits_at(p, t, pos, s, fault)[0])
        result = serve_cell.check_logits(_Server(sizes, params), faulty,
                                         sizes, 3)
        assert not result["ok"], (fault, result["rel_err"])


# ---- the readers ------------------------------------------------------------

def _run():
    run = harness.Run(
        kind="closed", config={"sizes": CONFIG["sizes"], "family": "brumby"},
        traffic={}, chips=1, device={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = [
        {"t": 1009.9 + 0.05 * i, "dur_ms": 45.0, "kind": "mixed",
         "decode_rows": 15, "prefill_rows": seqs - 15, "used": rows,
         "retention_rows": rows, "retention_seqs": seqs}
        for i, (seqs, rows) in enumerate(
            [(16, 143), (16, 143), (15, 15), (16, 79)])]
    run.trace = {"window_start_s": 9.94, "window_s": 0.1, "busy_s": 0.09,
                 "host_minus_trace_clock_s": 1000.0, "device0_gaps": [],
                 "device0_self_s_by_name": {
                     "power_retention_call.7": 0.030,
                     "power_retention_call.9": 0.002,
                     "fusion.1": 0.03, "copy.3": 0.001}}
    return run


@pytest.mark.parametrize("name,expected", [
    # ticks 1 and 2 are in the slice: 32 ms of the kernel over them
    ("retention_kernel_ms.tick", 1e3 * 0.032 / 2),
    # their slots (16 + 15) and rows (143 + 15) through six layers
    ("retention_kernel_hbm.share",
     100 * 6 * (31 * STATE + 158 * (2 * 128 * 96 + 32)) / 0.032 / 819e9),
    # the kernel's events are no paged kernel's, and no plain copy is a pool's
    ("paged_kernel_ms.tick", 0.0),
])
def test_readers_give_the_hand_computed_value(name, expected):
    assert _read(name, _run()) == pytest.approx(expected)


def test_the_share_is_a_floor_that_cannot_pass_half_for_this_form():
    """Reading AND rewriting the state at the chip's peak is twice the bytes
    the share counts: a step-by-step form tops out near 50%."""
    run = _run()
    sizes = CONFIG["sizes"]
    ticks = run.ticks[1:3]
    moved = sum(2 * family.retention_bytes(sizes, 0, t["retention_seqs"])
                for t in ticks)
    run.trace["device0_self_s_by_name"] = {
        "power_retention_call.7": moved / 819e9}
    assert 50.0 < _read("retention_kernel_hbm.share", run) < 50.5


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_older_programs_run(name):
    """The parent keeps none of the new fields and has no retention kernel,
    and a family without retention layers counts no such bytes: None, never
    an exception, with or without a trace."""
    run = _run()
    for tick in run.ticks:
        for field in ("retention_rows", "retention_seqs"):
            del tick[field]
    run.trace["device0_self_s_by_name"] = {"paged_attention_kv_call.3": 0.02}
    assert _read(name, run) is None
    run.trace = None
    assert _read(name, run) is None
    dense = _run()
    dense.config = {"sizes": {"num_hidden_layers": 2}, "family": "llama"}
    if name == "retention_kernel_hbm.share":
        assert _read(name, dense) is None
