"""Self-tests of what PR 35 added to the benchmark: the `phi4flash` family
file (its contract and its counts), the configuration file's two copies of the
published keys, the reference's two copies and the reference against the
program at `TINY_SIZES`, the four new readers on a made-up run whose values
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

family = harness.load_module("families", "phi4flash")
CONFIG = harness.load_json("configs", "phi-4-mini-flash-l32.json")
TRAFFIC = harness.load_json("traffic", "reason-closed64.json")
CELL = "phi4flash-reason-closed64"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["ssm_kernel_ms.tick", "ssm_kernel_hbm.share", "shared_kv_hbm.share",
       "cross_rows_skipped.share"]


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


# ---- the family file and the configuration -----------------------------------

def test_family_counts_are_issue_35s():
    sizes = CONFIG["sizes"]
    assert family.cache_bytes_per_token(sizes) == 2 * 20 * 64 * 2 == 5120
    assert family.window_cache_bytes_per_token(sizes) == 8 * 5120
    assert family.cross_layers(sizes) == 7
    assert family.state_bytes_per_sequence(sizes) == 9 * 5120 * (64 + 6) \
        == 3_225_600
    # a row: x, dt, y (5,120 float32 each) and B, C (16 each); a slot: the
    # scan state in and out; nine layers
    assert family.ssm_bytes(sizes, 1, 0) == 9 * 4 * (3 * 5120 + 32)
    assert family.ssm_bytes(sizes, 0, 1) == 9 * 4 * 2 * 5120 * 16
    mc = family.model_config(sizes)
    assert (mc.num_hidden_layers, mc.vocab_size, mc.d_inner, mc.dt_rank) == (
        32, 200064, 5120, 160)
    assert mc.num_params() == family.num_params(sizes)
    assert mc.num_params() * 2 == pytest.approx(7.70e9, rel=1e-3)
    assert mc.state_bytes_per_sequence == family.state_bytes_per_sequence(
        sizes)
    assert family.train_flops_per_token(sizes, 4096) == pytest.approx(
        mc.flops_per_token(4096))
    assert not any(hasattr(family, n) for n in
                   ("loss_fn", "param_logical_axes", "init_params"))
    shapes = {k for k, v in sizes.items()
              if isinstance(v, int) and not isinstance(v, bool)}
    assert shapes <= set(family.TINY_SIZES), shapes - set(family.TINY_SIZES)
    tiny = family.model_config(dict(sizes, **family.TINY_SIZES))
    assert (tiny.num_hidden_layers, tiny.sliding_window,
            tiny.mamba_d_state) == (8, 8, 4)


def test_configuration_files_two_copies_of_the_published_keys_agree():
    sizes = CONFIG["sizes"]
    own = {"mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
           "torch_dtype"}
    assert own <= set(sizes)
    assert {k: v for k, v in sizes.items() if k not in own} == {
        k: CONFIG[k] for k in sizes if k not in own}
    assert set(CONFIG["reduced"]) == {"max_position_embeddings"}
    assert sizes["max_position_embeddings"] == 8192 \
        != CONFIG["reduced"]["max_position_embeddings"]["published"]
    assert CONFIG["deployment"]["max_batch_size"] == 64
    manifest = [c for c in harness.load_manifest()["configs"]
                if c["name"] == "phi-4-mini-flash-l32"][0]
    assert set(manifest["reduced"]) == set(CONFIG["reduced"])
    assert manifest["source"] == CONFIG["source"]
    # the longest request of the traffic fits the table's width
    assert (TRAFFIC["prompt_len"]["max"] + TRAFFIC["output_len"]["max"]
            <= sizes["max_position_embeddings"])
    assert TRAFFIC["clients"] == CONFIG["deployment"]["max_batch_size"]


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_holds_every_key_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"Phi-4-mini-flash-reasoning"' in line][0]
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
    assert not listed & {"paged_kernel_hbm.share", "prefix_share",
                         "queue_ms.p95"}
    new = [p for p in manifest["per_layer"] if p["name"] in NEW]
    assert [p["name"] for p in new] == NEW      # at the end, in this order
    layers = {p["layer"] for p in manifest["per_layer"]
              if p["name"] not in NEW}
    for p in new:
        assert p["layer"] in layers and p["workloads"] == [CELL]
    e2e = {e["name"] for e in harness.metrics_of(manifest, "end_to_end",
                                                 CELL)}
    assert e2e == {"setup_s", "itl_ms.p95", "serve_tokens_per_s"}


# ---- the reference ------------------------------------------------------------

def _tiny():
    from ray_tpu.models import phi4flash

    sizes = dict(CONFIG["sizes"], **family.TINY_SIZES)
    params = phi4flash.init_params(family.model_config(sizes),
                                   jax.random.key(2))
    return sizes, params


def test_the_two_references_are_one_file_and_give_the_same_logits():
    from ray_tpu.models import phi4flash_reference as ours

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
    through `runner.step`, then decode positions), at the tiny sizes in
    float32: the sound reference passes far inside the tolerance, and the
    reference with one term dropped fails it. At these sizes the check's 128
    positions pass the window of 8 many times; at the published widths its
    264 do not reach the window of 512 (PERF.md section 7)."""
    sizes, params = _tiny()
    sound = serve_cell.check_logits(_Server(sizes, params), family, sizes, 3)
    assert sound["ok"] and sound["rel_err"] < 1e-4
    starts = list(range(0, 128, 16)) + list(range(128, 136))
    for fault in (("state_not_carried", starts), ("tail_not_carried", starts),
                  "memory_after_gate", "no_lambda", "no_window"):
        faulty = types.SimpleNamespace(
            reference_logits_at=lambda p, t, pos, s, fault=fault:
            family.reference.logits_at(p, t, pos, s, fault)[0])
        result = serve_cell.check_logits(_Server(sizes, params), faulty,
                                         sizes, 3)
        assert not result["ok"], (fault, result["rel_err"])


# ---- the readers --------------------------------------------------------------

def _run():
    run = harness.Run(
        kind="closed", config={"sizes": CONFIG["sizes"],
                               "family": "phi4flash"},
        traffic={}, chips=1, device={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = [
        {"t": 1009.9 + 0.05 * i, "dur_ms": 45.0, "kind": "mixed",
         "decode_rows": 60, "prefill_rows": rows - 60, "used": used,
         "kv_tokens": 200_000, "cross_kv_tokens": 200_000,
         "ssm_rows": used, "ssm_seqs": rows, "cross_rows": rows,
         "state_snapshots": 0, "state_restores": 0}
        for i, (rows, used) in enumerate(
            [(62, 190), (61, 188), (64, 192), (60, 60)])]
    run.trace = {"window_start_s": 9.94, "window_s": 0.1, "busy_s": 0.09,
                 "host_minus_trace_clock_s": 1000.0, "device0_gaps": [],
                 "device0_self_s_by_name": {
                     "paged_attention_kv_call.11": 0.030,
                     "paged_attention_kv_call.12": 0.004,
                     "paged_attention_window_call.13": 0.003,
                     "ssm_scan_call.14": 0.0032, "ssm_scan_call.15": 0.0004,
                     "sort.6": 0.02, "fusion.1": 0.03}}
    return run


@pytest.mark.parametrize("name,expected", [
    # ticks 1 and 2 are in the slice: 3.6 ms of the scan kernel over them
    ("ssm_kernel_ms.tick", 1e3 * 0.0036 / 2),
    # their rows (188 + 192) and slots (61 + 64) through nine layers
    ("ssm_kernel_hbm.share", 100 * 9 * 4 * (380 * (3 * 5120 + 32)
                                            + 125 * 2 * 5120 * 16)
     / 0.0036 / 819e9),
    # 2 x 200,000 tokens once and 7 times more, 5,120 B each, over the 34 ms
    # of the two calls that are not the window form's
    ("shared_kv_hbm.share", 100 * 2 * 8 * 200_000 * 5120 / 0.034 / 819e9),
    # 1 - 62/190, 61/188, 64/192, 60/60: the mean, in percent
    ("cross_rows_skipped.share", 100 * (4 - 62 / 190 - 61 / 188 - 64 / 192
                                        - 1) / 4),
    # the scan's events are no paged kernel's: 37 ms over the two ticks
    ("paged_kernel_ms.tick", 1e3 * 0.037 / 2),
    ("window_kernel_ms.tick", 1e3 * 0.003 / 2),
])
def test_readers_give_the_hand_computed_value(name, expected):
    assert _read(name, _run()) == pytest.approx(expected)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_find_nothing_in_an_older_programs_run(name):
    """The parent keeps none of the new fields and has no scan kernel, and a
    family without state-space or cross layers counts no such bytes: None,
    never an exception, with or without a trace."""
    run = _run()
    for tick in run.ticks:
        for field in ("ssm_rows", "ssm_seqs", "cross_rows",
                      "cross_kv_tokens"):
            del tick[field]
    run.trace["device0_self_s_by_name"] = {"paged_attention_kv_call.3": 0.02}
    assert _read(name, run) is None
    run.trace = None
    assert _read(name, run) is None
    dense = _run()
    dense.config = {"sizes": {"num_hidden_layers": 2}, "family": "llama"}
    if name in ("ssm_kernel_hbm.share", "shared_kv_hbm.share"):
        assert _read(name, dense) is None
