"""Self-tests of what PR 57 added to the benchmark: the `minicpm_sala` family
file (its contract and its counts, by hand at the published widths), the
configuration file's two copies of the published keys, the reference's two
copies and the harness's check against the program at `TINY_SIZES` (with
selection running) and its controls, and the five new readers on a made-up run
whose values are worked out by hand.

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

family = harness.load_module("families", "minicpm_sala")
CONFIG = harness.load_json("configs", "minicpm-sala-l16.json")
TRAFFIC = harness.load_json("traffic", "longdoc-closed32.json")
CELL = "minicpmsala-longdoc-closed32"
SIBLING = "glm52-longdoc-closed32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["block_select_ms.tick", "block_attend_ms.tick",
       "block_select_hbm.share", "block_attend_hbm.share",
       "block_pairs_skipped.share"]
OWN = {"first_published_layer", "published_layers", "kernel_size",
       "kernel_stride", "block_size", "topk", "init_blocks", "window_size",
       "dense_len", "torch_dtype"}
S_BYTES = 32 * 128 * 128 * 4             # a lightning layer's S a sequence
ROW_BYTES = 4 * 4 * 4096                 # q, k, v in and o out: float32


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


# ---- the family file and the configuration ----------------------------------

def test_family_counts_are_issue_57s_arithmetic():
    sizes = CONFIG["sizes"]
    # a lightning layer 285.2 M: q, k, v, gate, o 5 x 16.78 + the MLP 201.33
    assert family.lightning_params(sizes) == (
        5 * 4096 * 4096 + 3 * 4096 * 16384 + 3 * 128 + 2 * 4096) \
        == 285_221_248
    # a sparse layer 253.8 M: q, gate, o 3 x 16.78 + k, v 2 x 1.05 + 201.33
    # (the issue wrote the k and v term as 2 x 2.10; its sum is right)
    assert family.sparse_params(sizes) == (
        3 * 4096 * 4096 + 2 * 4096 * 256 + 3 * 4096 * 16384 + 2 * 128
        + 2 * 4096) == 253_763_840
    by_hand = (12 * 285_221_248 + 4 * 253_763_840 + 2 * 73448 * 4096 + 4096)
    assert family.num_params(sizes) == by_hand == 5_039_400_448   # 5,039 M
    assert family.num_params(sizes) * 2 == pytest.approx(10.08e9, rel=1e-3)
    # a token: a K and a V row of 2 x 128 bf16 and a 16th of a mean row, 4
    # layers
    assert family.cache_bytes_per_token(sizes) == 4 * (2 * 512 + 32) == 4224
    assert family.attention_flops_per_pair(sizes) == 4 * 32 * 256 * 2
    assert family.state_bytes_per_sequence(sizes) == 12 * S_BYTES \
        == 25_165_824
    # the kernels' floors
    assert family.ssd_bytes(sizes, 0, 1) == 12 * S_BYTES
    assert family.ssd_bytes(sizes, 1, 0) == 12 * ROW_BYTES
    assert family.select_bytes(sizes, 1, 0) == 4 * 512
    assert family.select_bytes(sizes, 0, 1) == 4 * (32 * 128 * 2 + 2 * 64 * 4)
    # one token's kept set a (sequence, kv head): 4,096 tokens of 128 K and
    # 128 V lanes = 2.1 MB
    assert family.attend_bytes(sizes, 1, 0) == 4 * 2 * 4096 * 2 * 128 * 2
    assert family.attend_bytes(sizes, 0, 1) == 4 * 2 * 32 * 128 * 2
    mc = family.model_config(sizes)
    assert (mc.num_hidden_layers, mc.vocab_size, mc.max_seq,
            mc.first_published_layer, mc.layers_of("sparse"),
            mc.layers_of("lightning")) == (16, 73448, 36864, 9, 4, 12)
    assert mc.num_params() == family.num_params(sizes)
    assert mc.state_bytes_per_sequence == family.state_bytes_per_sequence(
        sizes)
    assert not any(hasattr(family, n) for n in (
        "loss_fn", "param_logical_axes", "init_params",
        "reference_logits_routed"))
    assert callable(family.reference_loss_and_grad_norm)
    assert family.train_flops_per_token(sizes, 40000) == pytest.approx(
        mc.flops_per_token(40000))
    shapes = {k for k, v in sizes.items()
              if isinstance(v, int) and not isinstance(v, bool)}
    assert shapes <= set(family.TINY_SIZES), shapes - set(family.TINY_SIZES)
    tiny = family.model_config(dict(sizes, **family.TINY_SIZES))
    assert (tiny.num_hidden_layers, tiny.lightning_nh, tiny.dense_len) == (
        6, 4, 64)
    with pytest.raises(SystemExit, match="does not model"):
        family.model_config(dict(sizes, attn_use_rope=True))


def test_configuration_files_two_copies_of_the_published_keys_agree():
    sizes = CONFIG["sizes"]
    assert OWN <= set(sizes)
    assert {k: v for k, v in sizes.items() if k not in OWN} == {
        k: CONFIG[k] for k in sizes if k not in OWN}
    assert list(CONFIG["reduced"]) == [
        "num_hidden_layers", "mixer_types", "max_position_embeddings"]
    for key, entry in CONFIG["reduced"].items():
        assert entry["published"] != CONFIG[key] and entry["why"], key
    was = CONFIG["reduced"]["mixer_types"]["published"]
    assert len(was) == 32 and sizes["mixer_types"] == was[9:25]
    assert (was.count("minicpm4"), was.count("lightning-attn")) == (8, 24)
    assert sizes["mixer_types"].count("minicpm4") == 4
    deployment = CONFIG["deployment"]
    assert deployment["max_batch_size"] == TRAFFIC["clients"] == 32
    manifest = [c for c in harness.load_manifest()["configs"]
                if c["name"] == "minicpm-sala-l16"][0]
    assert manifest["reduced"] == list(CONFIG["reduced"])
    assert manifest["source"] == CONFIG["source"]
    assert manifest["file"] == "benchmarks/configs/minicpm-sala-l16.json"
    # the table: the longest request in whole blocks
    longest = (TRAFFIC["shared_prefixes"]["len"]
               + TRAFFIC["prompt_len"]["max"] + TRAFFIC["output_len"]["max"])
    assert longest <= sizes["max_position_embeddings"] == 36864
    # the pool: the documents once, every client's own tokens, room to park
    held = (TRAFFIC["shared_prefixes"]["count"]
            * TRAFFIC["shared_prefixes"]["len"]
            + 32 * (TRAFFIC["prompt_len"]["max"]
                    + TRAFFIC["output_len"]["max"]))
    assert held <= deployment["num_kv_blocks"] * 16 == 393216
    assert "two" in CONFIG["stands_for"].lower()
    assert "9-24" in CONFIG["stands_for"]
    assert {"sparse_config", "first_stage", "decays", "lightning_layer",
            "sparse_layer", "mup", "precision", "weights"} <= set(
        CONFIG["assumed"])
    assert "NOT CERTAIN" in CONFIG["assumed"]["decays"]
    # what no comparison here can see, named where a reader looks first
    assert all(f"`{item}`" in CONFIG["assumed"]["open"] for item in (
        "decays", "lightning_layer", "first_stage", "mup", "sparse_config"))


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_holds_every_key_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"name": "MiniCPM-SALA"' in line][0]
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CONFIG, key
        if key in CONFIG["reduced"]:
            assert CONFIG["reduced"][key]["published"] == value, key
        else:
            assert CONFIG[key] == value, key
    widths = [k for k in CONFIG["reduced"]
              if k.endswith(("_dim", "_rank", "_size")) and "hidden" in k]
    assert not widths


def test_the_cell_joins_the_readers_that_fit_it_and_not_the_others():
    manifest = harness.load_manifest()
    of = lambda cell: {p["name"] for p in manifest["per_layer"]
                       if cell in p.get("workloads", ())}
    listed = of(CELL)
    assert set(NEW) <= listed
    # every reader of the sibling cell (the same traffic file) whose quantity
    # exists here: not its indexer's and its experts'
    assert of(SIBLING) - listed == {
        "dsa_index_ms.tick", "dsa_index_hbm.share", "dsa_attend_ms.tick",
        "dsa_attend_hbm.share", "dsa_attend_mxu.share",
        "dsa_rows_skipped.share", "expert_rows.mean",
        "expert_load_skew.mean", "expert_product_ms.tick"}
    assert listed - of(SIBLING) == set(NEW) | {"ssd_kernel_ms.tick",
                                               "ssd_kernel_hbm.share"}
    # no dense-path row reaches the row kernel in this cell
    assert not listed & {"paged_kernel_ms.tick", "paged_kernel_hbm.share",
                         "window_kernel_ms.tick", "queue_ms.p95"}
    names = [p["name"] for p in manifest["per_layer"]]
    assert names[-5:] == NEW                            # appended, in order
    layers = {p["layer"] for p in manifest["per_layer"]
              if p["name"] not in NEW}
    for p in manifest["per_layer"][-5:]:
        assert p["layer"] == "model step, paged kernels" in layers
        assert p["workloads"] == [CELL] and p["moves"] == "itl_ms.p95"
    e2e = {e["name"] for e in harness.metrics_of(manifest, "end_to_end",
                                                 CELL)}
    assert e2e == {"setup_s", "itl_ms.p95", "serve_tokens_per_s"}
    cell = harness.find_cell(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "minicpm-sala-l16", "longdoc-closed32", 1)
    assert len(cell["why"]) <= 200
    assert harness.find_cell(manifest, SIBLING)["traffic"] == cell["traffic"]
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    assert manifest["workloads"][-1] == cell and len(
        manifest["workloads"]) == 11


# ---- the reference ----------------------------------------------------------

def _tiny():
    from ray_tpu.models import minicpm_sala

    sizes = dict(CONFIG["sizes"], **family.TINY_SIZES)
    params = minicpm_sala.init_params(family.model_config(sizes),
                                      jax.random.key(2))
    return sizes, params


def test_the_two_references_are_one_file_and_give_the_same_logits():
    from ray_tpu.models import minicpm_sala_reference as ours

    theirs = family.reference
    with open(ours.__file__) as a, open(theirs.__file__) as b:
        text = a.read()
        assert text == b.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    sizes, params = _tiny()
    tokens = np.random.default_rng(6).integers(1, 256, (2, 150)).astype(
        np.int32)
    a, found = ours.logits_at(params, tokens, [3, 149], sizes)
    assert found["selects"].sum() == 2 * 2 * (150 - 64)
    b = family.reference_logits_at(params, tokens, [3, 149], sizes)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    value, norm = family.reference_loss_and_grad_norm(params, tokens[:, :40],
                                                      sizes)
    assert np.isfinite(value) and norm > 0


class _Server:
    """What `serve_cell.check_logits` reads of a server, around a bare
    runner at the tiny sizes."""

    def __init__(self, sizes, params):
        import threading

        from ray_tpu.llm.model_runner import ModelRunner

        runner = ModelRunner(family.model_config(sizes), params,
                             num_blocks=128, block_size=16,
                             attention_impl="reference", chunk_size=32,
                             max_batch=4)
        self.engine = types.SimpleNamespace(runner=runner)
        self._lock = threading.Lock()


def test_the_harness_check_passes_the_program_and_fails_the_controls():
    """`serve_cell.check_logits` as the cell runs it, its first form (two
    prompts in chunks through `runner.step`, then decode positions), at the
    tiny sizes in float32, where its 256 + 8 positions pass `dense_len` 64
    and drop blocks from 257: the sound reference passes far inside the
    tolerance, and the reference with one term changed is told apart."""
    sizes, params = _tiny()
    sound = serve_cell.check_logits(_Server(sizes, params), family, sizes, 3)
    assert sound["ok"] and sound["rel_err"] < 1e-5
    assert sound["positions"] == 2 * 8
    starts = list(range(0, 256, 32)) + list(range(256, 264))
    for fault in (("state_not_carried", starts), "dense_above",
                  "shared_selection"):
        def changed(p, t, pos, s, fault=fault):
            return family.reference.logits_at(p, t, pos, s, fault=fault)[0]

        faulty = types.SimpleNamespace(reference_logits_at=changed)
        result = serve_cell.check_logits(_Server(sizes, params), faulty,
                                         sizes, 3)
        # (at these sizes a token keeps 4 of 5 blocks: what a changed
        # selection moves is under the harness's limit, which is bfloat16's,
        # and a thousand times over what the sound reference reads; on the
        # chip the check with selection is chip_smoke.py's, at 64 of 256)
        assert result["rel_err"] > 1e3 * sound["rel_err"], fault
        assert not result["ok"] or isinstance(fault, str), fault


# ---- the readers ------------------------------------------------------------

def _run():
    run = harness.Run(
        kind="closed",
        config={"sizes": CONFIG["sizes"], "family": "minicpm_sala"},
        traffic={}, chips=1, device={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = [
        {"t": 1009.9 + 0.05 * i, "dur_ms": 45.0, "kind": "mixed",
         "decode_rows": 31, "prefill_rows": seqs - 31, "used": rows,
         "ssd_rows": rows, "ssd_seqs": seqs, "select_rows": rows,
         "select_seqs": seqs, "pages_scored": 4 * 2048 + 150 * seqs,
         "attn_pairs": 34000 * rows, "block_pairs": 4080 * rows}
        for i, (seqs, rows) in enumerate(
            [(32, 159), (32, 159), (31, 31), (32, 95)])]
    run.trace = {"window_start_s": 9.94, "window_s": 0.1, "busy_s": 0.09,
                 "host_minus_trace_clock_s": 1000.0, "device0_gaps": [],
                 "device0_self_s_by_name": {
                     "block_select_call.7": 0.003, "dsa_select_call.2": 0.001,
                     "block_attend_call.4": 0.004,
                     "block_attend_rows_call.5": 0.006,
                     "paged_attention_kv_call.3": 0.0001,
                     "ssd_call.9": 0.008, "fusion.1": 0.03}}
    return run


SIZES = CONFIG["sizes"]


@pytest.mark.parametrize("name,expected", [
    # ticks 1 and 2 are in the slice
    ("block_select_ms.tick", 1e3 * 0.004 / 2),
    ("block_attend_ms.tick", 1e3 * 0.010 / 2),
    ("block_select_hbm.share", 100 * (
        family.select_bytes(SIZES, 8192 + 4800, 159)
        + family.select_bytes(SIZES, 8192 + 4650, 31)) / 0.004 / 819e9),
    ("block_attend_hbm.share", 100 * (
        family.attend_bytes(SIZES, 32, 159)
        + family.attend_bytes(SIZES, 31, 31)) / 0.010 / 819e9),
    ("block_pairs_skipped.share", 100 * (1 - 4080 / 34000)),
    ("ssd_kernel_ms.tick", 1e3 * 0.008 / 2),
    ("ssd_kernel_hbm.share", 100 * 12 * (
        63 * S_BYTES + 190 * ROW_BYTES) / 0.008 / 819e9),
])
def test_readers_give_the_hand_computed_value(name, expected):
    assert _read(name, _run()) == pytest.approx(expected)


def test_the_shares_read_a_hundred_where_the_counted_bytes_move_at_peak():
    run = _run()
    for name, entry, count in (
            ("block_select_hbm.share", "block_select_call.1",
             lambda t: family.select_bytes(SIZES, t["pages_scored"],
                                           t["select_rows"])),
            ("block_attend_hbm.share", "block_attend_call.1",
             lambda t: family.attend_bytes(SIZES, t["select_seqs"],
                                           t["select_rows"]))):
        moved = sum(count(t) for t in run.ticks[1:3])
        run.trace["device0_self_s_by_name"] = {entry: moved / 819e9}
        assert _read(name, run) == pytest.approx(100.0)
        # a form that reads whole 256-lane rows reads the kept sets twice
        run.trace["device0_self_s_by_name"] = {entry: 2 * moved / 819e9}
        assert _read(name, run) == pytest.approx(50.0)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_older_programs_run(name):
    """The parent keeps none of the new fields and has no such kernels, and a
    family without sparse layers counts no such bytes: None, never an
    exception, with or without a trace."""
    run = _run()
    for tick in run.ticks:
        for field in ("select_rows", "select_seqs", "pages_scored",
                      "block_pairs"):
            del tick[field]
    run.trace["device0_self_s_by_name"] = {"paged_attention_kv_call.3": 0.02}
    assert _read(name, run) is None
    run.trace = None
    assert _read(name, run) is None
    dense = _run()
    dense.config = {"sizes": {"num_hidden_layers": 2}, "family": "llama"}
    if name.endswith("hbm.share"):
        assert _read(name, dense) is None
