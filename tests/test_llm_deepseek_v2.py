"""DeepSeek-V2 (models/deepseek_v2.py) against its plain reference, at tiny
sizes on the CPU in float32 with seeded weights.

Tolerances: program and reference are both float32 here and differ in the
order of their sums (absorbed against expanded attention, sorted ragged
products against an expert at a time), so logits agree to a few 1e-6 of their
largest value; 2e-5 leaves an order of magnitude and is four orders below
what one bf16 rounding (4e-3) would show.
"""

import math
import os
import sys

import numpy as np
import pytest

import ray_tpu  # noqa: F401

TOL = 2e-5
BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks")


def sizes_of(c):
    """The reference's `sizes` (a configuration file's keys) of a config."""
    return dict(
        hidden_size=c.hidden_size,
        num_attention_heads=c.num_attention_heads,
        qk_nope_head_dim=c.qk_nope_head_dim,
        qk_rope_head_dim=c.qk_rope_head_dim, kv_lora_rank=c.kv_lora_rank,
        rms_norm_eps=c.rms_norm_eps, rope_theta=c.rope_theta,
        rope_scaling=dict(
            factor=c.rope_factor, beta_fast=c.rope_beta_fast,
            beta_slow=c.rope_beta_slow, mscale=c.rope_mscale,
            mscale_all_dim=c.rope_mscale_all_dim,
            original_max_position_embeddings=c.rope_original_max_position),
        num_experts_per_tok=c.num_experts_per_tok, n_group=c.n_group,
        topk_group=c.topk_group,
        routed_scaling_factor=c.routed_scaling_factor,
        n_routed_experts=c.n_held,
        n_routed_experts_published=c.n_routed_experts,
        first_held_expert=c.experts_held[0])


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def ds(cpu_jax):
    from ray_tpu.models import deepseek_v2

    return deepseek_v2


def _runner(ds, held, impl="reference", seed=0, **kw):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner

    config = ds.DeepseekV2Config.tiny(experts_held=held)
    params = ds.init_params(config, jax.random.key(seed))
    return config, params, ModelRunner(
        config, params, num_blocks=32, block_size=4, attention_impl=impl,
        chunk_size=8, **kw)


def _tables(runner, n_seqs, pages):
    tables = np.zeros((n_seqs, runner.max_blocks_per_seq), np.int32)
    for i in range(n_seqs):
        tables[i, :pages] = runner.num_blocks - 1 - i * pages - np.arange(
            pages)
    return tables


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("held", [(0, 16), (4, 12)])
def test_chunked_prefill_then_decode_by_step_matches_the_reference(
        ds, impl, held):
    """Prefill in chunks through the paged latent cache, then teacher-forced
    decode, by `ModelRunner.step` ("pallas": the kernel in interpret mode):
    every last-position logits row against the reference's full forward pass,
    routing for itself and following `last_routing`."""
    from ray_tpu.models import deepseek_v2_reference as ref

    config, params, runner = _runner(ds, held, impl)
    total, n_prompt = 21, 16
    tokens = np.random.default_rng(0).integers(
        1, config.vocab_size, (2, total)).astype(np.int32)
    tables = _tables(runner, 2, -(-total // 4))
    got, routing = [], []

    def step(tok, start):
        n = tok.shape[1]
        bq = runner.chunk_bucket(n) if n > 1 else 1
        padded = np.zeros((2, bq), np.int32)
        padded[:, :n] = tok
        logits = np.asarray(runner.step(
            padded, np.full(2, start, np.int32),
            np.full(2, start + n, np.int32), np.full(2, n, np.int32), tables))
        kept = np.asarray(runner.last_routing)
        # int32 (routed layers, S, Bq, top_k), published ids.
        assert kept.shape == (config.n_moe_layers, 2, bq,
                              config.num_experts_per_tok)
        assert kept.dtype == np.int32
        routing.append(kept[:, :, :n])
        return logits

    for start in range(0, n_prompt, 8):
        logits = step(tokens[:, start:start + 8], start)
    got.append(logits)
    for pos in range(n_prompt, total):
        got.append(step(tokens[:, pos:pos + 1], pos))
    got = np.stack(got[:-1], axis=1)
    positions = list(range(n_prompt - 1, total - 1))
    sizes = sizes_of(config)
    want, scores = ref.logits_at(params, tokens, positions, sizes)
    assert _rel(got, want) < TOL
    kept = np.concatenate(routing, axis=2)
    assert kept.min() >= 0 and kept.max() < config.n_routed_experts
    # The router keeps experts this program does not hold, and reports them.
    if held != (0, 16):
        assert ((kept < held[0]) | (kept >= held[1])).any()
    followed, _ = ref.logits_at(params, tokens, positions, sizes, kept)
    assert _rel(got, followed) < TOL
    # In float32 the program keeps exactly what the reference would.
    chosen = np.zeros(scores.shape, bool)
    np.put_along_axis(chosen, kept, True, axis=-1)
    for layer in range(config.n_moe_layers):
        flat = scores[layer].reshape(-1, scores.shape[-1])
        mask = np.asarray(ref.router_choice(
            flat, config.num_experts_per_tok, config.n_group,
            config.topk_group))
        assert (mask == chosen[layer].reshape(mask.shape)).all()


def test_mixed_tick_matches_the_reference(ds):
    """The token-major backbone over one ragged batch: a prefill slice of
    one sequence and a decode row of another (its prompt prefilled by
    `step`), padded to a token bucket."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_v2_reference as ref

    config, params, runner = _runner(ds, (0, 8), "pallas")
    rng = np.random.default_rng(1)
    tokens = rng.integers(1, config.vocab_size, (2, 14)).astype(np.int32)
    tables = _tables(runner, 2, 4)
    # Sequence 0: 8 tokens by `step`; sequence 1: 13 tokens by `step`.
    padded = np.zeros((2, 16), np.int32)
    padded[0, :8], padded[1, :13] = tokens[0, :8], tokens[1, :13]
    runner.step(padded, np.zeros(2, np.int32), np.asarray([8, 13], np.int32),
                np.asarray([8, 13], np.int32), tables)
    # The mixed tick: sequence 0's next 5 tokens, sequence 1's 14th.
    flat = np.zeros(8, np.int32)
    flat[:5], flat[5] = tokens[0, 8:13], tokens[1, 13]
    x, runner.cache, aux = jax.jit(runner._backbone_mixed)(
        runner.params, runner.cache, flat, np.asarray([8, 13], np.int32),
        np.asarray([13, 14], np.int32), np.asarray([0, 5, 6], np.int32),
        tables)
    logits = np.asarray(x @ params["lm_head"])
    sizes = sizes_of(config)
    want0, _ = ref.logits_at(params, tokens[:1, :13], list(range(8, 13)),
                             sizes)
    want1, _ = ref.logits_at(params, tokens[1:], [13], sizes)
    assert _rel(logits[:5], want0[0]) < TOL
    assert _rel(logits[5:6], want1[0]) < TOL
    assert aux["routing"].shape == (config.n_moe_layers, 8,
                                    config.num_experts_per_tok)
    rows, busiest, met = (int(v) for v in np.asarray(aux["counts"]))
    # 6 real rows x top_k x routed layers picks; half the experts are held.
    assert 0 < busiest <= rows <= 6 * 3 * config.n_moe_layers
    assert 0 < met <= min(rows, config.n_held * config.n_moe_layers)


def test_absorbed_attention_equals_expanded(ds):
    """Scores against `[c_kv | k_rope]` with `q_nope W_kb`, values = `c_kv`,
    `W_vb` outside, against a key and a value a head a token."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(2)
    H, nope, rope, lat, v, n, page = 4, 16, 8, 32, 16, 11, 4
    W = 128
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)
    q_nope, q_rope = f32(1, n, H, nope), f32(1, n, H, rope)
    c_kv, k_rope = f32(n, lat), f32(n, rope)
    w_kb, w_vb = f32(H, nope, lat), f32(H, lat, v)
    pool = np.zeros((1, 4, page, W), np.float32)
    rows = np.concatenate([c_kv, k_rope, np.zeros((n, W - lat - rope),
                                                  np.float32)], -1)
    pool.reshape(-1, W)[:n] = rows
    q = np.concatenate([np.einsum("sqhn,hnl->sqhl", q_nope, w_kb), q_rope,
                        np.zeros((1, n, H, W - lat - rope), np.float32)], -1)
    o_lat = pa.latent_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(pool), 0,
        jnp.arange(4, dtype=jnp.int32)[None], jnp.asarray([n], jnp.int32),
        jnp.asarray([0], jnp.int32), scale=0.2, lat=lat)
    absorbed = np.einsum("sqhl,hlv->sqhv", np.asarray(o_lat), w_vb)[0]
    k = np.einsum("kl,hnl->khn", c_kv, w_kb)
    val = np.einsum("kl,hlv->khv", c_kv, w_vb)
    s = (np.einsum("qhn,khn->hqk", q_nope[0], k)
         + np.einsum("qhr,kr->hqk", q_rope[0], k_rope)) * 0.2
    s = np.where(np.tril(np.ones((n, n), bool))[None], s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    expanded = np.einsum("hqk,khv->qhv", p, val)
    np.testing.assert_allclose(absorbed, expanded, rtol=1e-4, atol=1e-5)


def test_yarn_numbers_at_the_published_keys(ds):
    """ISSUE 29's numbers: low 10, high 23, m 1.2608, scale 0.11472; below
    `low` a frequency as it is, above `high` divided by 40."""
    config = ds.DeepseekV2Config()
    inv_freq, low, high = ds.yarn_inv_freq(config)
    inv_freq = np.asarray(inv_freq)
    assert (low, high) == (10, 23)
    f = 10000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(inv_freq[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[23:], f[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / (23 - 10)
    np.testing.assert_allclose(
        inv_freq[16], f[16] / 40 * ramp + f[16] * (1 - ramp), rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert ds.attention_scale(config) == pytest.approx(0.11472, abs=1e-5)
    assert ds.attention_scale(config) == pytest.approx(192 ** -0.5 * m * m)
    cos, sin = ds.rope_at(config, np.asarray([0, 5]))
    assert np.asarray(cos)[0] == pytest.approx(1.0)      # mscale ratio 1
    np.testing.assert_allclose(np.asarray(sin)[1], np.sin(5 * inv_freq),
                               rtol=1e-5)
    assert config.row_width == 640


def _brute_force_choice(scores, k, n_group, topk_group):
    """`group_limited_greedy` in plain loops; ties to the lower index."""
    out = []
    per = scores.shape[1] // n_group
    for row in scores:
        groups = sorted(range(n_group), key=lambda g: (
            -row[g * per:(g + 1) * per].max(), g))[:topk_group]
        inside = [e for g in groups for e in range(g * per, (g + 1) * per)]
        out.append(sorted(inside, key=lambda e: (-row[e], e))[:k])
    return np.asarray(out)


def test_router_matches_brute_force_on_1000_rows_ties_included(ds):
    import jax
    import jax.numpy as jnp

    config = ds.DeepseekV2Config()      # 160 experts, 8 groups keep 3, top 6
    rng = np.random.default_rng(3)
    logits = rng.standard_normal((1000, 160)).astype(np.float32)
    scores = np.array(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    # Ties: half the rows' scores fall on a grid of 12 values, so best
    # experts of groups, and k-th experts, tie many times over.
    grid = np.round(scores[:500] * 200) / 200 + 1e-3
    scores[:500] = grid
    assert (np.diff(np.sort(scores[:500], axis=1), axis=1) == 0).any()
    ids, gates = ds.route(config, jnp.asarray(scores))
    ids, gates = np.asarray(ids), np.asarray(gates)
    want = _brute_force_choice(scores, 6, 8, 3)
    np.testing.assert_array_equal(ids, want)
    np.testing.assert_allclose(
        gates, np.take_along_axis(scores, want, axis=1) * 16.0, rtol=1e-6)


def _expert_weights(rng, config):
    d, f, e = config.hidden_size, config.moe_intermediate_size, \
        config.n_routed_experts
    scale = lambda *s: (rng.standard_normal(s) / math.sqrt(s[-2])).astype(
        np.float32)
    return scale(e, d, f), scale(e, d, f), scale(e, f, d)


def _swiglu64(x, gate, up, down):
    g = x @ gate
    return (g / (1 + np.exp(-g)) * (x @ up)) @ down


def test_four_shares_add_up_to_the_uncut_layer(ds):
    """Programs holding experts 0-3, 4-7, 8-11 and 12-15, each given the same
    rows and routing: their parts, with the shared expert counted once, sum to
    what the uncut layer gives (float64, every expert)."""
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    whole = ds.DeepseekV2Config.tiny()
    w_gate, w_up, w_down = _expert_weights(rng, whole)
    x = rng.standard_normal((24, whole.hidden_size)).astype(np.float32)
    scores = np.exp(rng.standard_normal((24, 16))).astype(np.float32)
    scores /= scores.sum(-1, keepdims=True)
    ids, gates = ds.route(whole, jnp.asarray(scores))
    valid = jnp.ones(24, bool)
    total, rows = 0.0, 0
    for first in range(0, 16, 4):
        share = ds.DeepseekV2Config.tiny(experts_held=(first, first + 4))
        lp = {"w_gate": jnp.asarray(w_gate[first:first + 4]),
              "w_up": jnp.asarray(w_up[first:first + 4]),
              "w_down": jnp.asarray(w_down[first:first + 4])}
        y, (n, *_) = ds.held_expert_ffn(share, jnp.asarray(x), ids, gates,
                                     valid, lp)
        total = total + np.asarray(y, np.float64)
        rows += int(n)
    assert rows == 24 * whole.num_experts_per_tok    # every pick, once
    want = np.zeros((24, whole.hidden_size))
    for t in range(24):
        for e, g in zip(np.asarray(ids)[t], np.asarray(gates)[t]):
            want[t] += float(g) * _swiglu64(
                x[t].astype(np.float64), w_gate[e], w_up[e], w_down[e])
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)


def test_no_token_is_dropped_when_every_row_picks_the_same_held_expert(ds):
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    config = ds.DeepseekV2Config.tiny(experts_held=(0, 4))
    w_gate, w_up, w_down = _expert_weights(rng, config)
    n = 64
    x = rng.standard_normal((n, config.hidden_size)).astype(np.float32)
    ids = jnp.tile(jnp.asarray([[2, 9, 13]], jnp.int32), (n, 1))
    gates = jnp.asarray(rng.uniform(0.1, 1.0, (n, 3)).astype(np.float32))
    lp = {"w_gate": jnp.asarray(w_gate[:4]), "w_up": jnp.asarray(w_up[:4]),
          "w_down": jnp.asarray(w_down[:4])}
    y, (rows, busiest, met) = ds.held_expert_ffn(
        config, jnp.asarray(x), ids, gates, jnp.ones(n, bool), lp)
    assert int(rows) == n and int(busiest) == n      # all 64 on expert 2
    want = np.asarray(gates)[:, :1] * _swiglu64(
        x.astype(np.float64), w_gate[2], w_up[2], w_down[2])
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-4)
    # Padding rows are routed but neither counted nor computed.
    valid = jnp.arange(n) < 10
    y, (rows, *_) = ds.held_expert_ffn(config, jnp.asarray(x), ids, gates,
                                    valid, lp)
    assert int(rows) == 10 and not np.asarray(y)[10:].any()


def test_tensor_parallel_and_lora_refuse_at_construction(ds, cpu_jax):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.serving import LLMConfig, build_engine
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    config = ds.DeepseekV2Config.tiny()
    params = ds.init_params(config, jax.random.key(0))
    mesh = build_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="tensor_parallel"):
        ModelRunner(config, params, num_blocks=8, block_size=4, mesh=mesh)
    with pytest.raises(ValueError, match="LoRA"):
        ModelRunner(config, params, num_blocks=8, block_size=4,
                    lora_manager=object())
    with pytest.raises(ValueError, match="tensor_parallel"):
        build_engine(LLMConfig(model_config=config, tensor_parallel=2,
                               num_kv_blocks=8, warmup_buckets="off"))


def test_init_params_stacks_in_the_configs_dtype_and_counts(ds):
    import jax
    import jax.numpy as jnp

    config = ds.DeepseekV2Config.tiny(experts_held=(4, 12),
                                      dtype=jnp.bfloat16)
    params = ds.init_params(config, jax.random.key(1))
    moe = params["moe_layers"]
    assert [e["w_gate"].shape for e in params["experts"]] == [(8, 64, 32)] * 2
    assert moe["router"].shape == (2, 64, 16)       # the published width
    assert params["dense_layers"]["w_gate"].shape == (1, 64, 96)
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(params))
    norms = sum(a.size for a in jax.tree.leaves(params)
                if a.ndim <= 2 and a.shape[-1] in (64, 48, 32)
                and bool((a == 1).all()))
    assert (sum(a.size for a in jax.tree.leaves(params)) - norms
            == config.num_params())
    # The published widths, as ISSUE 29 sizes them: 10.33 GB in bf16.
    cell = ds.DeepseekV2Config(vocab_size=25600, num_hidden_layers=5,
                               experts_held=(0, 40))
    assert cell.attention_params() == pytest.approx(149.2e6, rel=1e-3)
    assert cell.num_params() == pytest.approx(5164e6, rel=1e-3)
    # A share's operations count its share of the picks.
    assert (config.flops_per_token(128)
            < ds.DeepseekV2Config.tiny().flops_per_token(128))


def test_the_benchmarks_reference_is_the_programs_to_the_last_bit(ds):
    """`benchmarks/deepseek_v2_reference.py` imports nothing of the program;
    it is a copy of `ray_tpu/models/deepseek_v2_reference.py` and gives the
    same logits, bit for bit, at the family's TINY_SIZES."""
    import jax

    from ray_tpu.models import deepseek_v2_reference as ours

    sys.path.insert(0, BENCH)
    try:
        import harness

        family = harness.load_module("families", "deepseek_v2")
        config = harness.load_json("configs", "deepseek-v2-l5-e40.json")
    finally:
        sys.path.remove(BENCH)
    theirs = family.reference
    with open(ours.__file__) as a, open(theirs.__file__) as b:
        assert a.read() == b.read()
    sizes = dict(config["sizes"], **family.TINY_SIZES)
    mc = family.model_config(sizes)
    params = ds.init_params(mc, jax.random.key(2))
    tokens = np.random.default_rng(6).integers(1, 256, (2, 12)).astype(
        np.int32)
    a, sa = ours.logits_at(params, tokens, [3, 11], sizes)
    b, sb = theirs.logits_at(params, tokens, [3, 11], sizes)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(sa, sb)
    assert "ray_tpu" not in open(theirs.__file__).read().replace(
        "`ray_tpu/models/deepseek_v2_reference.py`", "")
