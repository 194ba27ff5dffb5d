"""MiMo-V2-Flash (models/mimo_v2_flash.py) against its plain reference, at
tiny sizes on the CPU in float32 with seeded weights, and the cache manager's
layer groups (llm/engine.py BlockManager) on the host alone.

Window 8 over pages of 4 with contexts of 40-60 tokens: every sequence passes
its window several times, so pages are freed behind it during prefill and
decode, and a prefix hit needs its window tail.

Tolerances: program and reference are both float32 here and differ in the
order of their sums (paged online softmax against a dense one, sorted ragged
products against an expert at a time): logits agree to a few 1e-6 of their
largest value; 2e-5 leaves an order of magnitude, and each of the four faults
below reads over 1e-2.
"""

import dataclasses
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
        num_key_value_heads=c.num_key_value_heads,
        swa_num_key_value_heads=c.swa_num_key_value_heads,
        head_dim=c.head_dim, v_head_dim=c.v_head_dim,
        partial_rotary_factor=c.partial_rotary_factor,
        rope_theta=c.rope_theta, swa_rope_theta=c.swa_rope_theta,
        sliding_window=c.sliding_window,
        attention_value_scale=c.attention_value_scale,
        hybrid_layer_pattern=list(c.hybrid_layer_pattern),
        moe_layer_freq=list(c.moe_layer_freq),
        n_routed_experts=c.n_held,
        n_routed_experts_published=c.n_routed_experts,
        first_held_expert=c.experts_held[0],
        num_experts_per_tok=c.num_experts_per_tok,
        layernorm_epsilon=c.layernorm_epsilon)


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def mm(cpu_jax):
    from ray_tpu.models import mimo_v2_flash

    return mimo_v2_flash


@pytest.fixture(scope="module")
def ref(cpu_jax):
    from ray_tpu.models import mimo_v2_flash_reference

    return mimo_v2_flash_reference


def _runner(mm, config=None, impl="reference", seed=0, params=None):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner

    config = config or mm.MimoV2FlashConfig.tiny()
    if params is None:
        params = mm.init_params(config, jax.random.key(seed))
    return config, params, ModelRunner(
        config, params, num_blocks=64, block_size=4, attention_impl=impl,
        chunk_size=16, max_batch=4)


def _step_logits(runner, tokens, n_prompt):
    """Chunked prefill of tokens[:, :n_prompt], then a token at a time, by
    `ModelRunner.step` given ONE table (the "all" group's: the runner lays
    the window group's ring itself), as the benchmark's check drives it.
    -> logits at positions n_prompt - 1 .. total - 2."""
    rows, total = tokens.shape
    pages = -(-total // runner.block_size)
    tables = np.zeros((rows, runner.max_blocks_per_seq), np.int32)
    for i in range(rows):
        tables[i, :pages] = runner.num_blocks - 1 - i * pages - np.arange(
            pages)
    full = lambda v: np.full(rows, v, np.int32)
    got = []
    for start in range(0, n_prompt, runner.chunk_size):
        n = min(runner.chunk_size, n_prompt - start)
        padded = np.zeros((rows, runner.chunk_size), np.int32)
        padded[:, :n] = tokens[:, start:start + n]
        logits = runner.step(padded, full(start), full(start + n), full(n),
                             tables)
    got.append(np.asarray(logits))
    for pos in range(n_prompt, total):
        got.append(np.asarray(runner.step(
            tokens[:, pos:pos + 1], full(pos), full(pos + 1), full(1),
            tables)))
    return np.stack(got[:-1], axis=1)


def _tokens(seed, rows, n):
    return np.random.default_rng(seed).integers(1, 256, (rows, n)).astype(
        np.int32)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("held", [(0, 16), (4, 12)])
def test_chunked_prefill_then_decode_by_step_matches_the_reference(
        mm, ref, impl, held):
    """Through the paged cache of both layer groups (a 40-token prompt in
    chunks of 16, then 8 tokens one at a time: five windows of 8), for the jnp
    attention and for the kernel in interpret mode; all experts held, and a
    share of them."""
    config, params, runner = _runner(
        mm, mm.MimoV2FlashConfig.tiny(experts_held=held), impl)
    assert runner.group_pages == {"all": 64, "window": 64}
    assert runner.table_widths["window"] == 8     # (8 + 16) / 4 + 2
    tokens = _tokens(1, 2, 48)
    got = _step_logits(runner, tokens, 40)
    want, scores = ref.logits_at(params, tokens, list(range(39, 47)),
                                 sizes_of(config))
    assert _rel(got, want) < TOL
    assert scores.shape == (4, 2, 48, 16)
    routing = np.asarray(runner.last_routing)     # the last decode step's
    assert routing.shape == (4, 2, 1, 4)
    np.testing.assert_array_equal(
        np.sort(routing[:, :, 0], -1),
        np.sort(np.argsort(-scores[:, :, 47], -1, kind="stable")[..., :4],
                -1))


# ---------------------------------------------------------------------------
# A K row split with no padding (ops/paged_attention.py, `KRow`; PR 64)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_split_k_rows_through_the_cache_match_the_reference(mm, ref, impl):
    """The tiny configuration at the published head widths (192 and 128), so
    that `layer_step` lays q and K split (a full layer's two kv heads share
    ONE lane tile of rests, a window layer's four share two): prefill in
    slices, then decode, through both groups' pools, for the jnp references
    and for the kernel interpreted."""
    config = mm.MimoV2FlashConfig.tiny(head_dim=192, v_head_dim=128)
    assert config.k_row(mm.FULL).rest == config.k_row(mm.WINDOW).rest == 64
    config, params, runner = _runner(mm, config, impl)
    assert [a.shape[-1] for a in runner.cache_arrays] == [384, 256, 768, 512]
    assert runner.kv_kernels["all"]["k_lanes"] == [128, 64]
    tokens = _tokens(5, 2, 40)
    got = _step_logits(runner, tokens, 32)
    want, _ = ref.logits_at(params, tokens, list(range(31, 39)),
                            sizes_of(config))
    assert _rel(got, want) < TOL


def _unified():
    import test_llm_unified

    return test_llm_unified


# (kv heads, query heads, head width, window): MiMo-V2-Flash's two kinds of
# layer at fewer query heads, one shared tile alone (K = 2), and a rest of 32
# lanes, four heads a shared tile. Decode rows (one past a tile's end, one
# inside the window) and a slice of two blocks in one step: pages of 4,
# blocks of 8 query tokens, tiles of 3 | 2 pages.
_SPLIT_FORMS = {
    "K4_full_192": (4, 8, 192, None),
    "K8_window128_sink_192": (8, 16, 192, 128),
    "K2_full_192": (2, 4, 192, None),
    "K4_full_160": (4, 8, 160, None),
}


def _split_case(form, seed=0):
    """-> (case as `_ragged_case` gives it, the heads apart; the row-pool
    arguments with q and K laid by `k_row`; the keywords; the layout)."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    unified = _unified()
    K, H, hd, window = _SPLIT_FORMS[form]
    q_lens, kv_lens = (1, 1, 12), (150, 37, 141)
    case = unified._ragged_case(seed=seed + K, q_lens=q_lens,
                                kv_lens=kv_lens, T=16, K=K, H=H, hd=hd,
                                vd=128)
    q, kp, vp, bt, kvl, q_pos, cu = case
    row = pa.k_row(K, hd)
    assert (row.whole, row.rest) == (128, hd - 128)
    args = unified._on_device(case, 1)
    args = (row.queries(args[0]), row.lay(args[1]),
            args[2].reshape(*vp.shape[:3], -1)) + args[3:]
    kw = dict(kv_heads=K, scale=hd ** -0.5)
    sink = None
    if window:
        sink = np.random.default_rng(H).standard_normal(H).astype(np.float32)
        ring = unified._ring_tables(bt, kvl, q_pos, window, kp.shape[2],
                                    window // kp.shape[2] + 5)
        args = args[:4] + (jnp.asarray(ring),) + args[5:]
        kw.update(window=window, sink=jnp.asarray(sink))
    return case, args, kw, row, sink


@pytest.mark.parametrize("form", sorted(_SPLIT_FORMS))
def test_kv_rows_kernel_over_split_k_rows_matches_reference(
        cpu_jax, monkeypatch, form):
    """`_kv_rows_kernel` interpreted = the jnp reference over K rows laid
    split (both take the layout from the operands' widths alone), and the
    reference = the attention written out from the heads as they were before
    they were laid, a head 192 (160) wide."""
    from ray_tpu.ops import paged_attention as pa

    unified = _unified()
    monkeypatch.setattr(pa, "kv_sizes",
                        lambda *a, **kw: pa.KVSizes(8, 3, 2, True))
    case, args, kw, row, sink = _split_case(form)
    q, cu = case[0], case[-1]
    assert args[0].shape[-1] == 256 and args[1].shape[-1] == row.lanes
    ref = np.asarray(pa.ragged_paged_attention_unified_reference(*args, **kw))
    out = np.asarray(pa.ragged_paged_attention_unified(*args, **kw))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    dense = unified._dense_attention(case, 1, kw.get("window"), sink,
                                     kw["scale"])
    np.testing.assert_allclose(ref[:cu[-1]], dense[:cu[-1]], rtol=1e-4,
                               atol=1e-5)


def test_a_rest_in_the_wrong_half_meets_the_neighbours_k(cpu_jax,
                                                         monkeypatch):
    """The control: q laid with every head's rest in the OTHER half of the
    shared tile is no small error. Its scores are the head's whole tile with
    its NEIGHBOUR's rest, which the kernel and the reference both compute,
    and which is the attention written out over K heads whose rests are
    swapped in pairs."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    unified = _unified()
    monkeypatch.setattr(pa, "kv_sizes",
                        lambda *a, **kw: pa.KVSizes(8, 3, 2, True))
    case, args, kw, row, _ = _split_case("K4_full_192", seed=3)
    q, kp = case[0], case[1]
    cu = case[-1]
    right = np.asarray(pa.ragged_paged_attention_unified(*args, **kw))
    laid = args[0]
    wrong = jnp.concatenate([laid[..., :128], laid[..., 192:],
                             laid[..., 128:192]], axis=-1)
    out = np.asarray(pa.ragged_paged_attention_unified(wrong, *args[1:],
                                                       **kw))
    assert _rel(out[:cu[-1]], right[:cu[-1]]) > 0.1
    ref = np.asarray(pa.ragged_paged_attention_unified_reference(
        wrong, *args[1:], **kw))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    swapped = kp.copy()
    swapped[..., 0::2, 128:], swapped[..., 1::2, 128:] = (
        kp[..., 1::2, 128:], kp[..., 0::2, 128:])
    dense = unified._dense_attention((q, swapped) + case[2:], 1, None, None,
                                     kw["scale"])
    np.testing.assert_allclose(out[:cu[-1]], dense[:cu[-1]], rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("K,hd,per", [(4, 192, 2), (8, 192, 2), (2, 192, 2),
                                      (4, 160, 4), (2, 320, 2)])
def test_a_split_k_row_comes_back_bit_for_bit(cpu_jax, K, hd, per):
    """`KRow.lay` -> `KRow.heads_of` is the identity on a K of (..., K, hd),
    no lane of the row is padding, head kh's rest lies in part kh % per of
    the shared tile kh // per, and a query rides with its rest there and
    zeros in the tile's other parts."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    row = pa.k_row(K, hd)
    whole, rest = hd // 128 * 128, hd % 128
    assert row == pa.KRow(K, whole, rest) and row.per == per
    assert (row.lanes, row.q_width) == (K * hd, whole + 128)
    assert pa.k_row_of(row.q_width, row.lanes, K) == row
    k = jax.random.normal(jax.random.key(K), (3, 5, K, hd), jnp.bfloat16)
    laid = row.lay(k)
    assert laid.shape == (3, 5, K * hd) and laid.dtype == k.dtype
    np.testing.assert_array_equal(
        np.asarray(row.heads_of(laid).astype(jnp.float32)),
        np.asarray(k.astype(jnp.float32)))
    flat, heads = np.asarray(laid.astype(jnp.float32)), np.asarray(
        k.astype(jnp.float32))
    H = 2 * K
    q = jax.random.normal(jax.random.key(1), (7, H, hd), jnp.bfloat16)
    rides = np.asarray(row.queries(q).astype(jnp.float32))
    assert rides.shape == (7, H, whole + 128)
    seen = np.asarray(row.as_queries_see(laid).astype(jnp.float32))
    for kh in range(K):
        at = K * whole + kh // per * 128 + kh % per * rest
        np.testing.assert_array_equal(flat[..., kh * whole:(kh + 1) * whole],
                                      heads[..., kh, :whole])
        np.testing.assert_array_equal(flat[..., at:at + rest],
                                      heads[..., kh, whole:])
        for h in (2 * kh, 2 * kh + 1):
            mine = slice(whole + kh % per * rest,
                         whole + (kh % per + 1) * rest)
            want = np.zeros((7, whole + 128), np.float32)
            want[:, :whole] = np.asarray(q.astype(jnp.float32))[:, h, :whole]
            want[:, mine] = np.asarray(q.astype(jnp.float32))[:, h, whole:]
            np.testing.assert_array_equal(rides[:, h], want)
            # the products the kernel adds up are the head's own
            np.testing.assert_allclose(
                rides[:, h] @ seen[0, 0, kh],
                np.asarray(q.astype(jnp.float32))[:, h] @ heads[0, 0, kh],
                rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K,hd", [(8, 128), (4, 256), (10, 128), (2, 24),
                                  (4, 176), (3, 192)])
def test_heads_of_whole_lane_tiles_lie_as_they_did(cpu_jax, K, hd):
    """Where a head is whole lane tiles (every family but this one: Phi's
    and LFM2's pairs, Trinity, Nemotron, SALA) the layout is the heads side
    by side and q as it is: `lay` a reshape, `queries` the SAME array. So is
    a head under one lane tile (the tiny configurations), one whose rest
    does not divide a lane tile, and kv heads whose rests fill no whole
    one."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    row = pa.k_row(K, hd)
    assert row == pa.KRow(K, hd, 0) == pa.k_row_of(hd, K * hd, K)
    assert (row.lanes, row.q_width, row.parts) == (K * hd, hd, [(0, hd, 1)])
    assert row.first_lane(0, 3) == 3 * hd
    k = jax.random.normal(jax.random.key(0), (2, 3, K, hd), jnp.float32)
    np.testing.assert_array_equal(np.asarray(row.lay(k)),
                                  np.asarray(k.reshape(2, 3, K * hd)))
    np.testing.assert_array_equal(np.asarray(row.heads_of(row.lay(k))),
                                  np.asarray(k))
    np.testing.assert_array_equal(np.asarray(row.as_queries_see(row.lay(k))),
                                  np.asarray(k))
    q = jnp.ones((5, 2 * K, hd))
    assert row.queries(q) is q
    with pytest.raises(ValueError, match="no layout"):
        pa.k_row_of(hd + 128, K * hd, K)


def _reference_greedy(ref, params, sizes, prompt, output):
    """The reference's greedy choice after prompt + output[:i] for every i,
    by ONE forward pass over the engine's own tokens: equal to `output` if
    and only if the reference, decoding greedily from the prompt, emits
    `output` (by induction over i)."""
    tokens = list(prompt) + list(output[:-1])
    positions = list(range(len(prompt) - 1, len(tokens)))
    logits, _ = ref.logits_at(params, np.asarray([tokens], np.int32),
                              positions, sizes)
    return np.argmax(np.asarray(logits)[0], axis=-1).tolist()


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_engine_matches_the_reference_with_and_without_a_prefix_hit(
        mm, ref, impl):
    """Mixed ticks through LLMEngine: greedy tokens equal the plain
    reference's, for a request served cold and for requests that hit a cached
    prefix on BOTH layer groups (44 shared tokens = 11 pages: the hit needs
    the "all" pages of [0, 44) and the window pages of [36, 44)); window pages
    are freed behind the window on the way."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = _runner(mm, impl=impl)
    engine = LLMEngine(runner, max_batch_size=4, prefill_chunk=16)
    rng = np.random.default_rng(3)
    shared = rng.integers(1, 256, 44).tolist()
    prompts = [shared + rng.integers(1, 256, n).tolist() for n in (9, 3)]
    prompts.append(rng.integers(1, 256, 30).tolist())
    sp = SamplingParams(max_tokens=10, temperature=0.0)
    sizes = sizes_of(config)
    cold = engine.generate([prompts[0]], sp)[0]
    assert len(cold.output_token_ids) == 10
    assert cold.output_token_ids == _reference_greedy(
        ref, params, sizes, prompts[0], cold.output_token_ids)
    freed = sum(t["window_pages_freed"] for t in engine.tick_records())
    assert freed >= (53 + 10 - 8 - 16) // 4
    groups = engine.stats()["kv_groups"]
    assert groups["window"]["live"] == groups["all"]["live"] == 0
    outs = engine.generate([prompts[1], prompts[2], prompts[0]], sp)
    for out, prompt in zip(outs, [prompts[1], prompts[2], prompts[0]]):
        assert len(out.output_token_ids) == 10
        assert out.output_token_ids == _reference_greedy(
            ref, params, sizes, prompt, out.output_token_ids)
    stats = engine.stats()
    assert stats["prefix_hits"] == 2 and stats["prefix_hits_cut_short"] == 0
    assert stats["prefix_tokens_saved"] == 44 + 52
    landing, tick = engine.tick_records()[-1], engine.tick_records()[-2]
    # the last call only lands the step in flight (one step of lookahead)
    assert landing["kv_pages_walked"] == 0 and not landing["lookahead"]
    assert tick["window_pages_walked"] < tick["kv_pages_walked"]
    assert tick["window_kv_tokens"] <= 8 * tick["decode_rows"]


def test_preemption_releases_and_rebuilds_both_groups(mm, ref):
    """A pool too small for three growing sequences: the newest is preempted
    (its pages of BOTH groups released), re-admitted and recomputed, window
    pages freed behind the window all along; every output is still the
    reference's greedy one, and nothing is left held."""
    import jax

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.sampling import SamplingParams

    config = mm.MimoV2FlashConfig.tiny()
    params = mm.init_params(config, jax.random.key(0))
    runner = ModelRunner(config, params, num_blocks=30, block_size=4,
                         attention_impl="reference", chunk_size=16,
                         max_batch=3)
    engine = LLMEngine(runner, max_batch_size=3, prefill_chunk=16,
                       enable_prefix_caching=False)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 256, 30).tolist() for _ in range(3)]
    preempted = []
    release = engine.block_manager.release
    engine.block_manager.release = lambda req: (
        preempted.append(req.id) if not req.finished_reason else None,
        release(req))[1]
    outs = engine.generate(prompts, SamplingParams(max_tokens=14,
                                                   temperature=0.0))
    assert preempted                 # the pool did run out
    sizes = sizes_of(config)
    for out, prompt in zip(outs, prompts):
        assert len(out.output_token_ids) == 14
        assert out.output_token_ids == _reference_greedy(
            ref, params, sizes, prompt, out.output_token_ids)
    groups = engine.stats()["kv_groups"]
    assert groups["all"]["live"] == groups["window"]["live"] == 0
    assert groups["all"]["free"] == 30


def _fault(mm, ref, params=None, **program):
    """rel error of a program whose configuration differs from the
    reference's in `program` (or whose parameters are `params`)."""
    import jax

    sound = mm.MimoV2FlashConfig.tiny()
    weights = mm.init_params(sound, jax.random.key(0))
    _, _, runner = _runner(
        mm, dataclasses.replace(sound, **program),
        params=params(weights) if params else weights)
    tokens = _tokens(2, 1, 40)
    want, _ = ref.logits_at(weights, tokens, list(range(31, 39)),
                            sizes_of(sound))
    return _rel(_step_logits(runner, tokens, 32), want)


def _without_sink(params):
    layers = dict(params["layers"])
    for kind, p in layers.items():
        if "sink" in p:     # exp(-1e30 - m) == 0: out of the denominator
            layers[kind] = dict(p, sink=np.full(p["sink"].shape, -1e30,
                                                np.float32))
    return dict(params, layers=layers)


@pytest.mark.parametrize("fault", [
    dict(params=_without_sink),
    dict(attention_value_scale=1.0),
    dict(partial_rotary_factor=1.0),
    dict(sliding_window=40),
], ids=["no_sink", "no_value_scale", "full_rope", "no_lower_window_edge"])
def test_a_program_with_one_term_dropped_fails(mm, ref, fault):
    """The sink logit, the value scale, the partial rope and the window's
    lower edge each move the logits by far more than the tolerance (a window
    of 40 over a 40-token context is no window)."""
    assert _fault(mm, ref) < TOL
    assert _fault(mm, ref, **fault) > 1e-2


def test_router_bias_moves_the_selection_and_not_the_gates(mm):
    import jax.numpy as jnp

    config = mm.MimoV2FlashConfig.tiny()
    rng = np.random.default_rng(5)
    scores = 1.0 / (1.0 + np.exp(-rng.standard_normal((200, 16))))
    bias = rng.uniform(0, 0.2, 16)
    ids, gates = mm.route(config, jnp.asarray(scores, jnp.float32),
                          jnp.asarray(bias, jnp.float32))
    ids, gates = np.asarray(ids), np.asarray(gates)
    want = np.argsort(-(scores + bias).astype(np.float32), -1,
                      kind="stable")[:, :4]
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want, -1))
    plain, _ = mm.route(config, jnp.asarray(scores, jnp.float32),
                        jnp.zeros(16, jnp.float32))
    assert (np.sort(np.asarray(plain), -1) != np.sort(ids, -1)).any()
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)
    kept = np.take_along_axis(scores, ids, -1)
    np.testing.assert_allclose(gates, kept / kept.sum(-1, keepdims=True),
                               rtol=1e-5)


@pytest.mark.parametrize("experts,held", [(256, 16), (16, 16), (16, 5)])
def test_the_drawn_bias_favours_every_share_of_experts_alike(mm, experts,
                                                             held):
    """Every share of `held` consecutive experts of every layer holds the same
    levels, in an order of its own, whatever the seed: the held experts' load
    does not move with it. Shares that are not whole: one grid over all."""
    import jax

    per = held if experts % held == 0 else experts
    levels = (np.arange(per) + 0.5) * (mm.ROUTER_BIAS_WIDTH / per)
    drawn = [np.asarray(mm._router_bias(jax.random.key(seed), 3, experts,
                                        held)) for seed in (1, 2)]
    for bias in drawn:
        assert bias.shape == (3, experts) and bias.dtype == np.float32
        shares = bias.reshape(3, experts // per, per)
        np.testing.assert_allclose(
            np.sort(shares, -1), np.broadcast_to(levels, shares.shape),
            rtol=1e-6)
    assert (drawn[0] != drawn[1]).any()
    if experts // per > 1:
        assert (drawn[0][0, :per] != drawn[0][0, per:2 * per]).any()


def test_sixteen_shares_add_up_to_the_uncut_layer(mm, ref):
    """Programs holding one expert each of a tiny layer's 16, given the same
    rows: their parts summed, with the router counted once (every share
    routes over all 16 and renormalises over all 4 kept, held or not), equal
    the uncut reference's layer."""
    import jax.numpy as jnp

    from ray_tpu.models.expert_share import held_expert_ffn

    rng = np.random.default_rng(4)
    whole = mm.MimoV2FlashConfig.tiny()
    d, f = whole.hidden_size, whole.moe_intermediate_size
    draw = lambda *s: (rng.standard_normal(s) / np.sqrt(s[-2])).astype(
        np.float32)
    experts = {"w_gate": draw(16, d, f), "w_up": draw(16, d, f),
               "w_down": draw(16, f, d)}
    p = {"router": jnp.asarray(draw(d, 16)),
         "router_bias": jnp.asarray(rng.uniform(0, 0.2, 16), jnp.float32)}
    x = jnp.asarray(rng.standard_normal((24, d)), jnp.float32)
    import jax

    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ p["router"])
        want, choice = ref._routed(
            x, p, {k: jnp.asarray(v) for k, v in experts.items()},
            dict(sizes_of(whole)))
    ids, gates = mm.route(whole, scores, p["router_bias"])
    np.testing.assert_array_equal(
        np.sort(np.asarray(ids), -1),
        np.sort(np.argsort(-np.asarray(choice), -1, kind="stable")[:, :4],
                -1))
    total, rows = 0.0, 0
    for first in range(16):
        share = mm.MimoV2FlashConfig.tiny(experts_held=(first, first + 1))
        lp = {k: jnp.asarray(v[first:first + 1]) for k, v in experts.items()}
        y, (n, *_) = held_expert_ffn(share, x, ids, gates, jnp.ones(24, bool),
                                  lp)
        total = total + np.asarray(y, np.float64)
        rows += int(n)
    assert rows == 24 * whole.num_experts_per_tok    # every pick, once
    np.testing.assert_allclose(total, np.asarray(want), rtol=1e-4, atol=1e-5)


def test_tensor_parallel_and_lora_refuse_at_construction(mm):
    block = mm.MimoV2FlashConfig.tiny().serving_block()
    with pytest.raises(ValueError, match="tensor_parallel"):
        block.refuse(tensor_parallel=2, lora=False)
    with pytest.raises(ValueError, match="LoRA"):
        block.refuse(tensor_parallel=1, lora=True)
    block.refuse(tensor_parallel=1, lora=False)


def test_pages_travel_for_one_group_only(mm):
    """Export, adoption and the prefix tiers carry one list of pages a
    sequence: for a block with two layer groups they refuse in one line (or,
    for the tiers a server attaches by default, stay off)."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.prefix_store import HostPrefixTier

    _, _, runner = _runner(mm)
    engine = LLMEngine(runner, max_batch_size=4, prefill_chunk=16)
    for call in (lambda: runner.gather_pages([0]),
                 lambda: runner.scatter_pages([0]),
                 lambda: engine.adopt_request({})):
        with pytest.raises(ValueError, match="layer groups"):
            call()
    engine.attach_prefix_store(host_tier=HostPrefixTier(1 << 20))
    assert engine.host_prefix_tier is None
    assert engine.block_manager.spill_fn is None
    assert engine.export_prefixes() is None


def test_one_table_is_taken_only_where_the_caller_owns_the_pool(mm):
    """The mixed step wants one table a layer group: given the "all" group's
    alone it refuses, since a ring laid by the runner could be a live
    sequence's pages; `step` (the benchmark's check, engine idle) lays it."""
    _, _, runner = _runner(mm)
    one = np.zeros((2, runner.max_blocks_per_seq), np.int32)
    with pytest.raises(ValueError, match="one block table a group"):
        z = lambda n: np.zeros(n, np.int32)
        runner.step_mixed_logits(z(16), z(2), z(2), z(3), one, z(2))
    assert set(runner._tables(one, owns_pool=True)) == {"all", "window"}
    assert runner._tables(runner.zero_tables(2)).keys() == {"all", "window"}


def test_counts_at_the_published_sizes(mm):
    """The configuration file's arithmetic (benchmarks/configs/
    mimo-v2-flash-l7-e16.json, `deployment.why`)."""
    c = mm.MimoV2FlashConfig(vocab_size=19072, experts_held=(0, 16),
                             max_position_embeddings=36864)
    assert c.attention_params(mm.FULL) == 89_128_960
    assert c.attention_params(mm.WINDOW) == 94_371_840
    assert c.expert_params() == 25_165_824
    assert c.num_params() == pytest.approx(3430e6, rel=1e-3)
    from ray_tpu.ops import paged_attention as pa

    assert c.rotary_dim == 64
    # a K row as it lies: 128 + 64 lanes a head, split, nothing padded
    full, window = c.k_row(mm.FULL), c.k_row(mm.WINDOW)
    assert (full, window) == (pa.KRow(4, 128, 64), pa.KRow(8, 128, 64))
    assert (full.lanes, window.lanes, full.q_width) == (768, 1536, 256)
    assert [a.shape[-1] for a in c.serving_block().cache_arrays(
        {"all": 16, "window": 16}, 16)] == [768, 512, 1536, 1024]
    assert c.serving_block().pallas_ok()
    assert not mm.MimoV2FlashConfig.tiny().serving_block().pallas_ok()
    assert [k for k, _ in mm.layer_kinds(c)] == [
        "full_dense", "window_moe", "full_moe", "window_moe"]
    block = c.serving_block()
    assert block.pool_layer == [0, 0, 1, 2, 3, 1, 4] and block.q_block == 32


def test_the_benchmarks_reference_is_the_programs_to_the_last_bit(mm):
    """`benchmarks/mimo_v2_flash_reference.py` imports nothing of the
    program; it is a copy of `ray_tpu/models/mimo_v2_flash_reference.py`."""
    ours = os.path.join(os.path.dirname(BENCH), "ray_tpu", "models",
                        "mimo_v2_flash_reference.py")
    with open(ours) as a, open(os.path.join(
            BENCH, "mimo_v2_flash_reference.py")) as b:
        text = a.read()
        assert text == b.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text


# ---------------------------------------------------------------------------
# The allocator by layer group (host only)
# ---------------------------------------------------------------------------

def _manager(pages=32, window_pages=16, window=8):
    from ray_tpu.llm.engine import BlockManager

    return BlockManager(pages, 4, side_groups={
        "window": (window_pages, window)})


def _request(prompt, rid="r"):
    from ray_tpu.llm.engine import _Request
    from ray_tpu.llm.sampling import SamplingParams

    return _Request(rid, list(prompt), SamplingParams())


def _prefill(bm, req, chunk=8):
    """What the engine does to a request's pages while its prompt runs in
    chunks: allocate, register full blocks, release behind the window."""
    hashes = bm.prefix_hashes(req.prompt)
    skipped = bm.match_prefix(req, hashes)
    registered = len(req.blocks)
    assert bm.allocate(req, len(req.prompt) + 1)
    done = skipped
    while done < len(req.prompt):
        done = min(done + chunk, len(req.prompt))
        bm.allocate_side(req, done)
        while registered < done // bm.block_size:
            bm.register_block(req, registered, hashes[registered])
            registered += 1
        bm.release_behind(req, done)
    return skipped


def test_window_pages_are_freed_behind_the_window_in_prefill_and_decode():
    bm = _manager()
    req = _request(range(1, 41))                 # 40 tokens, 10 pages
    _prefill(bm, req)
    window = bm.side["window"]
    # next position 40 sees 33..39: logical pages 8 and 9 stay
    assert [p >= 0 for p in req.side_blocks["window"]] == [False] * 8 + [
        True] * 2
    assert len(window.refcount) == 2 and len(req.blocks) == 11
    assert len(window.reusable) == 8            # registered: parked
    for n in range(41, 50):                     # decode: one token a tick
        bm.allocate(req, n + 1)
        bm.allocate_side(req, n)
        bm.release_behind(req, n - 1)
    held = [i for i, p in enumerate(req.side_blocks["window"]) if p >= 0]
    assert held == [10, 11, 12]                 # 48 sees 41..48
    bm.release(req)
    assert not window.refcount and not bm.refcount
    assert window.available() == 16 and bm._available() == 32
    assert req.side_blocks == {} and req.blocks == []


def test_a_prefix_hit_needs_the_window_tail_and_shares_it():
    bm = _manager()
    first = _request(range(1, 41), "a")
    _prefill(bm, first)
    bm.release(first)
    hit = [_request(list(range(1, 41)) + [90 + i] * 6, f"h{i}")
           for i in range(2)]
    for req in hit:
        assert bm.match_prefix(req, bm.prefix_hashes(req.prompt)) == 40
        assert req.side_blocks["window"][:8] == [-1] * 8 and req.side_lo == 8
    window = bm.side["window"]
    tail = hit[0].side_blocks["window"][8:10]
    assert tail == hit[1].side_blocks["window"][8:10]
    assert [window.refcount[p] for p in tail] == [2, 2]
    assert bm.prefix_hits == 2 and bm.prefix_hits_cut_short == 0
    bm.release(hit[0])
    assert [window.refcount[p] for p in tail] == [1, 1]
    bm.release(hit[1])
    assert not window.refcount
    # the tail a hit attached parks as most recently used
    assert list(window.reusable)[-2:] == tail


def test_a_hit_is_cut_short_where_the_window_tail_is_gone():
    """The "all" chain is whole (9 pages may be reused of a 40-token prompt)
    but window pages 4-7 were recycled (mid-prompt pages go first, the last
    parked first): the hit stops at page boundary 4, the longest that still
    has its window tail (pages 2, 3). With no window page left, nothing is
    attached."""
    bm = _manager(window_pages=12)
    first = _request(range(1, 41), "a")
    _prefill(bm, first)
    bm.release(first)
    window = bm.side["window"]
    hashes = bm.prefix_hashes(first.prompt)
    other = _request(range(100, 124), "b")      # 6 pages: 2 free + 4 parked
    bm.allocate(other, 25)
    bm.allocate_side(other, 24)
    assert [h in window.cached for h in hashes] == (
        [True] * 4 + [False] * 4 + [True] * 2)
    again = _request(first.prompt, "c")
    assert bm.match_prefix(again, hashes) == 16
    assert bm.prefix_hits_cut_short == 1 and bm.prefix_hits == 1
    assert len(again.blocks) == 4 and again.side_lo == 2
    assert again.side_blocks["window"] == [-1, -1] + [
        window.cached[h] for h in hashes[2:4]]
    gone = _manager(window_pages=12)
    first = _request(range(1, 41), "a")
    _prefill(gone, first)
    gone.release(first)
    gone.side["window"].forget()
    again = _request(first.prompt, "c")
    assert gone.match_prefix(again, gone.prefix_hashes(again.prompt)) == 0
    assert gone.prefix_hits_cut_short == 1 and gone.prefix_hits == 0
    assert again.blocks == [] and again.side_blocks == {"window": []}


def test_mid_prompt_window_pages_are_recycled_before_a_prompts_tail():
    bm = _manager(window_pages=12)
    req = _request(range(1, 41))
    _prefill(bm, req)
    bm.release(req)
    window = bm.side["window"]
    hashes = bm.prefix_hashes(req.prompt)
    order = list(window.reusable)
    tail = [window.cached[h] for h in hashes[8:10]]
    assert order[-2:] == tail                   # the prompt's last window
    assert set(order[:-2]) == {window.cached[h] for h in hashes[:8]}


def test_one_group_manager_is_the_manager_it_was():
    from ray_tpu.llm.engine import BlockManager

    bm = BlockManager(8, 4)
    assert bm.side == {} and list(bm.pools) == ["all"]
    a = _request(range(1, 14), "a")             # 13 tokens: 3 full blocks
    hashes = bm.prefix_hashes(a.prompt)
    assert bm.match_prefix(a, hashes) == 0 and bm.allocate(a, 14)
    assert a.blocks == [0, 1, 2, 3] and a.side_blocks == {}
    for i in range(3):
        bm.register_block(a, i, hashes[i])
    bm.allocate_side(a, 13)
    assert bm.release_behind(a, 13) == 0        # nothing to do
    bm.release(a)
    assert list(bm.reusable) == [0, 1, 2] and list(bm.free) == [
        4, 5, 6, 7, 3]
    b = _request(range(1, 14), "b")
    assert bm.match_prefix(b, hashes) == 12 and b.blocks == [0, 1, 2]
    assert bm.refcount == {0: 1, 1: 1, 2: 1} and not bm.reusable
    assert bm.prefix_hits == 1 and bm.prefix_hits_cut_short == 0


def test_register_block_holds_one_prompt_tuple_and_a_length():
    """Every block of a prompt names its token prefix by the request's ONE
    tuple and a length: memory linear in the prompt (a copy of the prefix a
    block was quadratic: 33.6 M entries for one 32,768-token document)."""
    from ray_tpu.llm.engine import BlockManager

    bm = BlockManager(1100, 16)
    req = _request(range(16384))
    hashes = bm.prefix_hashes(req.prompt)
    assert bm.allocate(req, len(req.prompt) + 1)
    for i, h in enumerate(hashes):
        bm.register_block(req, i, h)
    metas = [bm.digest_meta[h] for h in hashes]
    assert len(metas) == 1024
    assert all(m[2] is metas[0][2] for m in metas)      # one tuple, shared
    assert [m[3] for m in metas] == [16 * (i + 1) for i in range(1024)]
    slot, name, prompt, length = metas[5]
    assert (slot, name) == (0, "") and prompt[:length] == tuple(range(96))
    held = sum(sys.getsizeof(m) for m in metas) + sys.getsizeof(metas[0][2])
    assert held < 3 * 8 * len(req.prompt)
