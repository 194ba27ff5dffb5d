"""Self-tests of what PR 33 added to the benchmark: the `mimo_v2_flash` family
file (its contract, its counts, its routed form), the configuration file's two
copies of the published keys, the reference's two copies, the three new
readers on a made-up run whose values are worked out by hand, and the routed
check's faults run against a sigmoid top-8 of 256 router.

    python -m pytest benchmarks/tests -q
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import routing
import serve_cell

family = harness.load_module("families", "mimo_v2_flash")
CONFIG = harness.load_json("configs", "mimo-v2-flash-l7-e16.json")
CELL = "mimov2flash-longdoc-closed32"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _read(name, run):
    return harness.load_module("layer_metrics", name).read(run)


# ---- the family file and the configuration -----------------------------------

def test_family_counts_are_issue_33s():
    sizes = CONFIG["sizes"]
    assert family.cache_bytes_per_token(sizes) == 2 * 4 * 320 * 2 == 5120
    assert family.window_cache_bytes_per_token(sizes) == 5 * 8 * 320 * 2 \
        == 25600
    assert family.attention_flops_per_pair(sizes) == 2 * 64 * 320 * 2
    mc = family.model_config(sizes)
    assert (mc.n_held, mc.n_routed_experts, mc.vocab_size) == (16, 256, 19072)
    assert mc.hybrid_layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    assert mc.num_params() * 2 == pytest.approx(6.86e9, rel=1e-3)
    assert family.train_flops_per_token(sizes, 4096) == pytest.approx(
        mc.flops_per_token(4096))
    assert not any(hasattr(family, n) for n in
                   ("loss_fn", "param_logical_axes", "init_params"))
    shapes = {k for k, v in sizes.items()
              if isinstance(v, int) and not isinstance(v, bool)}
    assert shapes <= set(family.TINY_SIZES), shapes - set(family.TINY_SIZES)
    tiny = family.model_config(dict(sizes, **family.TINY_SIZES))
    assert tiny.experts_held == (0, 8) and tiny.sliding_window == 8


def test_configuration_files_two_copies_of_the_published_keys_agree():
    """Every key of the published config stands at the top level of the file
    and under `sizes`; `reduced` names exactly the keys that differ from the
    published value it records; the manifest's entry says the same."""
    sizes = CONFIG["sizes"]
    own = {"n_routed_experts_published", "first_held_expert", "torch_dtype"}
    assert {k: v for k, v in sizes.items() if k not in own} == {
        k: CONFIG[k] for k in sizes if k not in own}
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size", "max_position_embeddings"}
    for key, entry in CONFIG["reduced"].items():
        assert sizes[key] != entry["published"], key
    assert sizes["hybrid_layer_pattern"] == CONFIG["reduced"][
        "hybrid_layer_pattern"]["published"][:7]
    assert sizes["moe_layer_freq"] == CONFIG["reduced"]["moe_layer_freq"][
        "published"][:7]
    manifest = [c for c in harness.load_manifest()["configs"]
                if c["name"] == "mimo-v2-flash-l7-e16"][0]
    assert set(manifest["reduced"]) == set(CONFIG["reduced"])
    assert manifest["source"] == CONFIG["source"]
    json.dumps(CONFIG)


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
def test_configuration_holds_every_key_of_the_catalogs_row():
    with open(CATALOG) as f:
        row = [json.loads(line) for line in f
               if '"MiMo-V2-Flash"' in line][0]
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in CONFIG, key
        if key in CONFIG["reduced"]:
            assert CONFIG["reduced"][key]["published"] == value, key
        else:
            assert CONFIG[key] == value, key


def test_the_two_references_are_one_file_and_give_the_same_logits():
    from ray_tpu.models import mimo_v2_flash, mimo_v2_flash_reference as ours

    theirs = family.reference
    with open(ours.__file__) as a, open(theirs.__file__) as b:
        text = a.read()
        assert text == b.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    sizes = dict(CONFIG["sizes"], **family.TINY_SIZES)
    params = mimo_v2_flash.init_params(family.model_config(sizes),
                                       jax.random.key(2))
    tokens = np.random.default_rng(6).integers(1, 256, (2, 20)).astype(
        np.int32)
    a, sa = ours.logits_at(params, tokens, [3, 19], sizes)
    b, sb = theirs.logits_at(params, tokens, [3, 19], sizes)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(sa, sb)


def test_routed_reference_follows_the_program_and_reports_shortfalls():
    """At TINY_SIZES: given the reference's own choice the shortfall is 0
    everywhere and the logits are the free-running ones; given a k-th expert
    that is the worst by score + bias, the shortfall is large."""
    from ray_tpu.models import mimo_v2_flash

    sizes = dict(CONFIG["sizes"], **family.TINY_SIZES)
    params = mimo_v2_flash.init_params(family.model_config(sizes),
                                       jax.random.key(0))
    tokens = np.random.default_rng(1).integers(1, 256, (2, 12)).astype(
        np.int32)
    positions = [4, 11]
    free, scores = family.reference.logits_at(params, tokens, positions,
                                              sizes)
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


# ---- the three new readers ---------------------------------------------------

def _run():
    """Four ticks of 30 ms in a 40 s window, the middle two inside a traced
    slice that holds 24 ms of the full layers' kernel and 1.2 ms of the
    window form's."""
    run = harness.Run(
        kind="closed", config={"sizes": CONFIG["sizes"],
                               "family": "mimo_v2_flash"},
        traffic={}, chips=1, device={},
        peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
        t_process_start=0.0, t0=1000.0, t1=1040.0)
    run.ticks = [
        {"t": 1009.9 + 0.05 * i, "dur_ms": 30.0, "kind": "mixed",
         "decode_rows": 32, "kv_tokens": 1_070_000,
         "kv_pages_walked": walked, "window_pages_walked": window,
         "window_kv_tokens": 4200, "window_pages_freed": 3}
        for i, (walked, window) in enumerate(
            [(70000, 350), (80000, 400), (70000, 280), (100000, 500)])]
    run.trace = {"window_start_s": 9.94, "window_s": 0.1, "busy_s": 0.09,
                 "host_minus_trace_clock_s": 1000.0, "device0_gaps": [],
                 "device0_self_s_by_name": {
                     "paged_attention_kv_call.3": 0.024,
                     "paged_attention_window_call.7": 0.0008,
                     "paged_attention_window_call.9": 0.0004,
                     "ragged-dot-none.1": 0.02, "fusion.1": 0.03}}
    return run


@pytest.mark.parametrize("name,expected", [
    # ticks 1 and 2 are in the slice: 1.2 ms of the window form over them
    ("window_kernel_ms.tick", 1e3 * 0.0012 / 2),
    # their 8,400 window tokens x 25,600 B over 1.2 ms
    ("window_kernel_hbm.share", 100 * 8400 * 25600 / 0.0012 / 819e9),
    # 1 - 350/70000, 400/80000, 280/70000, 500/100000, the mean, in percent
    ("window_pages_skipped.share", 100 * (1 - (0.005 + 0.005 + 0.004
                                               + 0.005) / 4)),
    # both forms count as paged kernels: 25.2 ms over the slice's two ticks
    ("paged_kernel_ms.tick", 1e3 * 0.0252 / 2),
    # the full group's useful bytes under both forms' seconds
    ("paged_kernel_hbm.share", 100 * 2_140_000 * 5120 / 0.0252 / 819e9),
])
def test_readers_give_the_hand_computed_value(name, expected):
    assert _read(name, _run()) == pytest.approx(expected)


@pytest.mark.parametrize("name", ["window_kernel_ms.tick",
                                  "window_kernel_hbm.share",
                                  "window_pages_skipped.share"])
def test_new_readers_find_nothing_in_an_older_programs_run(name):
    """The parent keeps none of the new fields and has no window kernel, and
    a family without window layers counts no window bytes: None, never an
    exception, with or without a trace."""
    run = _run()
    for tick in run.ticks:
        for field in ("window_pages_walked", "window_kv_tokens",
                      "window_pages_freed"):
            del tick[field]
    run.trace["device0_self_s_by_name"] = {"paged_attention_kv_call.3": 0.02}
    assert _read(name, run) is None
    run.trace = None
    assert _read(name, run) is None
    dense = _run()
    dense.config = {"sizes": {"num_hidden_layers": 2}, "family": "llama"}
    if name == "window_kernel_hbm.share":
        assert _read(name, dense) is None


def test_new_per_layer_entries_name_a_layer_the_manifest_has():
    manifest = harness.load_manifest()
    new = [p for p in manifest["per_layer"] if p["name"].startswith("window_")]
    assert [p["name"] for p in new] == [
        "window_kernel_ms.tick", "window_kernel_hbm.share",
        "window_pages_skipped.share"]
    layers = {p["layer"] for p in manifest["per_layer"]
              if not p["name"].startswith("window_")}
    for p in new:
        assert p["layer"] in layers and p["workloads"] == [CELL]
        assert p["moves"] == "itl_ms.p95"
    with open(harness.ROOT + "/PERF.md") as f:
        assert "| model step, paged kernels |" in f.read()


# ---- the routed check against a sigmoid top-8 of 256 router ------------------
#
# A toy of the MiMo-V2-Flash block without its attention (routing is what the
# check is about; a position embedding keeps rows apart): one dense SwiGLU
# layer, then expert layers whose router scores 256 published experts by a
# sigmoid, keeps the 8 best by score + bias, and weighs them by their scores
# over the kept scores' sum. The toy HOLDS the first 16 experts. The "program"
# is that decoder on bf16 weights with bf16 activations (the router's product
# in float32, as the program's) behind a fake runner; the reference is the
# same equations in float32 on the same weights.

SIZES = {"vocab_size": 384, "max_position_embeddings": 64, "hidden_size": 256,
         "intermediate_size": 512, "moe_intermediate_size": 64,
         "num_hidden_layers": 7, "n_routed_experts": 256, "experts_held": 16,
         "num_experts_per_tok": 8, "eps": 1e-5}
SEEDS = [3, 2**31 + 11, 1234567891]
F32, BF16 = jnp.float32, jnp.bfloat16


def make_params(seed: int, s=SIZES, bias_width=0.2):
    d, v, fe = s["hidden_size"], s["vocab_size"], s["moe_intermediate_size"]
    routed, held = s["num_hidden_layers"] - 1, s["experts_held"]
    shapes = {"embed": (v, d), "pos": (s["max_position_embeddings"], d),
              "d_gate": (d, s["intermediate_size"]),
              "d_up": (d, s["intermediate_size"]),
              "d_down": (s["intermediate_size"], d),
              "router": (routed, d, s["n_routed_experts"]),
              "e_gate": (routed, held, d, fe), "e_up": (routed, held, d, fe),
              "e_down": (routed, held, fe, d), "head": (d, v)}
    keys = jax.random.split(jax.random.key(seed % (2**31 - 1)),
                            len(shapes) + 1)
    params = {name: (jax.random.normal(k, shape, F32)
                     / np.sqrt(1.0 if name in ("embed", "pos")
                               else shape[-2])).astype(BF16)
              for k, (name, shape) in zip(keys, shapes.items())}
    params["bias"] = bias_width * jax.random.uniform(
        keys[-1], (routed, s["n_routed_experts"]), F32)
    return params


def forward(params, tokens, positions, act, s=SIZES, follow=None, fault=None):
    """-> logits (..., T, vocab) float32, the experts kept (routed layers,
    ..., T, k), the selection scores s + e (routed layers, ..., T, E)."""
    eps, k, n_e = s["eps"], s["num_experts_per_tok"], s["n_routed_experts"]

    def mm(x, w, spec="...d,df->...f"):
        return jnp.einsum(spec, x.astype(F32), w.astype(F32),
                          precision="highest").astype(act)

    def norm(x):
        x32 = x.astype(F32)
        return (x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                    + eps)).astype(act)

    def swiglu(h, gate, up, down, spec=("...d,df->...f", "...f,fd->...d")):
        return mm(jax.nn.silu(mm(h, gate, spec[0])) * mm(h, up, spec[0]),
                  down, spec[1])

    x = (params["embed"][tokens].astype(F32)
         + params["pos"][positions].astype(F32)).astype(act)
    x = x + swiglu(norm(x), params["d_gate"], params["d_up"], params["d_down"])
    kept, choices = [], []
    for i in range(params["router"].shape[0]):
        h = norm(x)
        score = jax.nn.sigmoid(jnp.einsum(
            "...d,de->...e", h.astype(F32), params["router"][i].astype(F32),
            precision="highest"))
        choice = score + params["bias"][i]
        if follow is not None:
            ids = jnp.asarray(follow[i])
        else:
            by = np.asarray(score if fault == "no_bias" else choice)
            flat = by.reshape(-1, n_e)
            ids = np.argsort(-flat, axis=-1, kind="stable")[:, :k]
            if fault == "random_kth":   # the weakest kept expert: any other
                rng = np.random.default_rng(17)
                ids[:, -1] = [rng.choice(np.setdiff1d(np.arange(n_e), row))
                              for row in ids]
            ids = jnp.asarray(ids.reshape(score.shape[:-1] + (k,)))
        kept.append(ids)
        choices.append(choice)
        gates = jnp.take_along_axis(score, ids, -1)
        if fault != "no_renorm":
            gates = gates / gates.sum(-1, keepdims=True)
        weight = (jax.nn.one_hot(ids, n_e, dtype=F32)
                  * gates[..., None]).sum(-2)[..., :s["experts_held"]]
        out = swiglu(h, params["e_gate"][i], params["e_up"][i],
                     params["e_down"][i],
                     ("...d,hdf->...hf", "...hf,hfd->...hd"))
        x = x + (out.astype(F32) * weight[..., None]).sum(-2).astype(act)
    logits = jnp.einsum("...d,dv->...v", norm(x).astype(F32),
                        params["head"].astype(F32), precision="highest")
    return logits, jnp.stack(kept), jnp.stack(choices)


class ToyRunner:
    block_size, num_blocks, max_blocks_per_seq, chunk_size = 16, 64, 4, 16

    def __init__(self, params, fault=None):
        self.params, self.fault = params, fault

    def chunk_bucket(self, n):
        return 8 if n <= 8 else 16

    def step(self, tokens, q_positions, kv_lens, q_lens, block_tables):
        positions = q_positions[:, None] + np.arange(tokens.shape[1])[None]
        logits, kept, _ = forward(self.params, tokens,
                                  np.minimum(positions, 63), BF16,
                                  fault=self.fault)
        self.last_routing = np.asarray(kept, dtype=np.int32)
        return logits[np.arange(len(tokens)), q_lens - 1]


def _positions(tokens):
    return np.broadcast_to(np.arange(tokens.shape[-1]), tokens.shape)


def _routed(params, tokens, positions, sizes, kept):
    logits, _, choice = forward(params, tokens, _positions(tokens), F32,
                                follow=kept)
    e, k = sizes["n_routed_experts"], sizes["num_experts_per_tok"]
    short = routing.shortfall(
        np.asarray(choice).reshape(-1, e), np.asarray(kept).reshape(-1, k),
        k, 1, 1).reshape(kept.shape[:-1])
    return logits[:, np.asarray(positions)], short


TOY = types.SimpleNamespace(reference_logits_routed=_routed)


def _check(seed, fault=None, bias_width=0.2):
    import threading

    server = types.SimpleNamespace(
        engine=types.SimpleNamespace(runner=ToyRunner(
            make_params(seed, bias_width=bias_width), fault)),
        _lock=threading.Lock())
    return serve_cell.check_logits(server, TOY, SIZES, seed)


@pytest.mark.parametrize("seed", SEEDS)
def test_faithful_bf16_sigmoid_toy_passes(seed):
    """Scores crowd near the top of the sigmoid, so a bf16 hidden state
    swaps near ties often; each falls short by a small ratio."""
    result = _check(seed)
    print("sound", seed, result)
    assert result["ok"], result
    assert result["routed_choices"] == 6 * 2 * 40
    assert result["shortfall_max"] <= serve_cell.ROUTING_TIE_MARGIN / 2


@pytest.mark.parametrize("fault,fails_on", [
    ("random_kth", "shortfall"), ("no_bias", "shortfall"),
    ("no_renorm", "rel_err")])
def test_faulty_sigmoid_toy_fails_by_the_condition_meant_for_it(fault,
                                                                fails_on):
    """A k-th expert that is any expert and a router without the correction
    bias keep experts that are no near tie (HERE the bias is drawn in [0,
    0.2), wide beside the top scores' spread: 0.144-0.148); gates that are not
    renormalised move the logits (eight scores near 0.9 sum to ~7)."""
    for seed in SEEDS:
        result = _check(seed, fault)
        print(fault, seed, result)
        assert not result["ok"], result
        if fails_on == "shortfall":
            assert result["shortfall_max"] > serve_cell.ROUTING_TIE_MARGIN
        else:
            assert result["rel_err"] > serve_cell.LOGITS_REL_TOL


def test_the_margin_cannot_tell_a_router_without_a_narrow_bias():
    """A configuration that drew its bias in [0, 0.02) (this one draws in
    [0, 0.2): models/mimo_v2_flash.py, ROUTER_BIAS_WIDTH). Sigmoid scores of
    the kept experts crowd near 0.9, so a program without such a bias keeps
    experts
    that fall short by at most ~2%: under `ROUTING_TIE_MARGIN` 0.1, and the
    logits follow the program's experts, so the check passes it. Written
    down for a `benchmark` issue (PERF.md section 7); the margin is not this
    file's to move."""
    for seed in SEEDS:
        sound = _check(seed, bias_width=0.02)
        faulty = _check(seed, "no_bias", bias_width=0.02)
        print("narrow", seed, sound["shortfall_max"], faulty["shortfall_max"],
              faulty["routed_differ"], faulty["rel_err"])
        assert sound["ok"] and faulty["ok"]
        assert sound["shortfall_max"] < faulty["shortfall_max"] < 0.03
        assert faulty["routed_differ"] > 5 * sound["routed_differ"]
