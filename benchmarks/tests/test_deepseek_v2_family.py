"""Self-tests of what PR 29 added to the benchmark: the `deepseek_v2` family
file (its reference's router against `routing.reference_choice`, its routed
form, its counts), the configuration file's two copies of the published keys,
and the three new readers on a made-up run whose values are worked out by hand.

    python -m pytest benchmarks/tests -q
"""

import json

import numpy as np
import pytest

import harness
import routing

family = harness.load_module("families", "deepseek_v2")
CONFIG = harness.load_json("configs", "deepseek-v2-l5-e40.json")


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


def test_references_router_is_routing_reference_choice():
    """The plain reference keeps the experts `routing.reference_choice`
    keeps, ties to the lower index included: 500 rows of softmax scores over
    160 experts, half of them on a coarse grid."""
    rng = np.random.default_rng(0)
    scores = np.exp(rng.standard_normal((500, 160))).astype(np.float32)
    scores /= scores.sum(-1, keepdims=True)
    scores[:250] = np.round(scores[:250] * 200) / 200 + 1e-3
    got = np.asarray(family.reference.router_choice(scores, 6, 8, 3))
    np.testing.assert_array_equal(
        got, routing.reference_choice(scores, 6, 8, 3))
    assert (got.sum(-1) == 6).all()
    assert ((got.reshape(500, 8, 20).any(-1)).sum(-1) <= 3).all()


def test_routed_reference_follows_the_program_and_reports_shortfalls():
    """At TINY_SIZES: given the reference's own choice the shortfall is 0
    everywhere and the logits are the free-running ones; given a k-th expert
    that is the worst of another group, the shortfall is large."""
    import jax

    sizes = dict(CONFIG["sizes"], **family.TINY_SIZES)
    mc = family.model_config(sizes)
    assert mc.experts_held == (0, 8) and mc.n_routed_experts == 16
    from ray_tpu.models import deepseek_v2

    params = deepseek_v2.init_params(mc, jax.random.key(0))
    tokens = np.random.default_rng(1).integers(1, 256, (2, 10)).astype(
        np.int32)
    positions = [4, 9]
    free, scores = family.reference.logits_at(params, tokens, positions,
                                              sizes)
    assert np.asarray(family.reference_logits_at(
        params, tokens, positions, sizes)).shape == (2, 2, 256)
    own = np.stack([np.argsort(-np.where(routing.reference_choice(
        s.reshape(20, 16), 3, 4, 2), s.reshape(20, 16), -1), axis=-1,
        kind="stable")[:, :3].reshape(2, 10, 3) for s in scores])
    logits, short = family.reference_logits_routed(
        params, tokens, positions, sizes, own)
    assert short.shape == (2, 2, 10) and not short.any()
    np.testing.assert_allclose(np.asarray(logits), np.asarray(free),
                               rtol=1e-5, atol=1e-5)
    worst = own.copy()
    worst[..., -1] = np.argmin(scores, axis=-1)
    _, short = family.reference_logits_routed(params, tokens, positions,
                                              sizes, worst)
    assert short.max() > 0.5


def test_family_counts_are_issue_29s():
    sizes = CONFIG["sizes"]
    assert family.cache_bytes_per_token(sizes) == 5 * 576 * 2 == 5760
    assert family.attention_flops_per_pair(sizes) == 5 * 128 * (192 + 128) * 2
    mc = family.model_config(sizes)
    assert (mc.n_held, mc.n_routed_experts, mc.vocab_size) == (40, 160, 25600)
    assert mc.num_params() * 2 == pytest.approx(10.33e9, rel=1e-3)
    assert not any(hasattr(family, n) for n in
                   ("loss_fn", "param_logical_axes", "init_params"))


def test_configuration_files_two_copies_of_the_published_keys_agree():
    """Every shape key stands at the top level of the file (where a check of
    the file against the public config looks) and under `sizes` (what the
    harness reads); `reduced` names exactly the keys that differ from the
    published value it records."""
    sizes = CONFIG["sizes"]
    own = {"n_routed_experts_published", "first_held_expert", "torch_dtype"}
    assert {k: v for k, v in sizes.items() if k not in own} == {
        k: CONFIG[k] for k in sizes if k not in own}
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    for key, entry in CONFIG["reduced"].items():
        assert sizes[key] != entry["published"], key
    assert sizes["n_routed_experts_published"] == \
        CONFIG["reduced"]["n_routed_experts"]["published"] == 160
    manifest = [c for c in harness.load_manifest()["configs"]
                if c["name"] == "deepseek-v2-l5-e40"][0]
    assert set(manifest["reduced"]) == set(CONFIG["reduced"])
    assert manifest["source"] == CONFIG["source"]
    json.dumps(CONFIG)


def _run():
    """Four unified ticks of 25 ms in a 40 s window, the middle two inside
    a traced slice that holds 10 ms of paged kernel."""
    run = harness.Run(
        kind="closed", config={"sizes": CONFIG["sizes"],
                               "family": "deepseek_v2"},
        traffic={}, chips=1, device={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = [
        {"t": 1009.9 + 0.05 * i, "dur_ms": 25.0, "kind": "mixed",
         "decode_rows": 32, "kv_tokens": 280000, "attn_pairs": 300000,
         "routed_rows": 768, "expert_rows": rows, "expert_rows_max": busiest}
        for i, (rows, busiest) in enumerate(
            [(200, 20), (160, 12), (0, 0), (240, 24)])]
    run.trace = {"window_start_s": 9.94, "window_s": 0.1, "busy_s": 0.09,
                 "host_minus_trace_clock_s": 1000.0, "device0_gaps": [],
                 "device0_self_s_by_name": {"tpu_custom_call.2": 0.010,
                                            "ragged-dot-none.1": 0.02,
                                            "fusion.1": 0.03}}
    return run


@pytest.mark.parametrize("name,expected", [
    # ticks 1 and 2 (middles 1009.9625 and 1010.0125) are in the slice:
    # 600,000 pairs x 409,600 operations over 10 ms over 197 TFLOP/s
    ("latent_kernel_mxu.share", 100 * 600000 * 409600 / 0.010 / 197e12),
    # the same ticks' 560,000 context tokens x 5,760 B over 10 ms
    ("paged_kernel_hbm.share", 100 * 560000 * 5760 / 0.010 / 819e9),
    ("expert_rows.mean", (200 + 160 + 0 + 240) / 4),
    # 20 x 40 / 200, 12 x 40 / 160, 24 x 40 / 240; the tick with no row out
    ("expert_load_skew.mean", (4.0 + 3.0 + 4.0) / 3),
])
def test_new_readers_give_the_hand_computed_value(name, expected):
    assert _read(name, _run()) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["latent_kernel_mxu.share",
                                  "expert_rows.mean",
                                  "expert_load_skew.mean"])
def test_new_readers_find_nothing_in_an_older_programs_run(name):
    """The parent keeps none of the new fields, and a dense family counts no
    operations a pair: None, never an exception, with or without a trace."""
    run = _run()
    for tick in run.ticks:
        for field in ("attn_pairs", "routed_rows", "expert_rows",
                      "expert_rows_max"):
            del tick[field]
    assert _read(name, run) is None
    run.trace = None
    assert _read(name, run) is None
    dense = _run()
    dense.config = {"sizes": {"num_hidden_layers": 2}, "family": "llama"}
    if name == "latent_kernel_mxu.share":
        assert _read(name, dense) is None


def test_ragged_products_are_not_counted_as_paged_kernels():
    """XLA's own grouped products show as `ragged-dot-*` in a profile: no
    reader of the paged kernels takes them."""
    import tick_phases

    assert not tick_phases.is_custom_call("ragged-dot-none.1",
                                          tick_phases.PAGED_KERNELS)
    assert _read("paged_kernel_ms.tick", _run()) == pytest.approx(
        1e3 * 0.010 / 2)


def test_new_per_layer_entries_name_layers_perf_md_has():
    with open(harness.ROOT + "/PERF.md") as f:
        perf = f.read()
    new = [p for p in harness.load_manifest()["per_layer"] if p["name"] in (
        "latent_kernel_mxu.share", "expert_rows.mean",
        "expert_load_skew.mean")]
    assert len(new) == 3
    for p in new:
        assert "| " + p["layer"] + " |" in perf, p["layer"]
        assert p["workloads"] == ["deepseekv2-docqa-closed32"]
