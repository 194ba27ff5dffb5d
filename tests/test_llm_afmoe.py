"""Trinity-Large-Preview (models/afmoe.py) against its plain reference, at tiny
sizes on the CPU in float32 with seeded weights.

Window 8 over pages of 4 with contexts of 40-60 tokens: every sequence passes
its window several times, so pages are freed behind it during prefill and
decode, the rectangular step's ring wraps, and a prefix hit needs its window
tail. 12 query heads over 2 kv heads: six a kv head, as published.

Tolerances: program and reference are both float32 here and differ in the
order of their sums (paged online softmax against a dense one, sorted ragged
products against an expert at a time): logits agree to a few 1e-6 of their
largest value; 2e-5 leaves an order of magnitude. A router that rounds its
scores to bfloat16 reads over 1e-4, and each of the six terms left out (the
window mask, the full layer's missing rotation, the gate, R_post_mlp, the
selection bias, the route scale) over 1e-2.
"""

import os

import numpy as np
import pytest

import ray_tpu  # noqa: F401

TOL = 2e-5
BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmarks")


def sizes_of(c):
    """The reference's `sizes` (a configuration file's keys) of a config."""
    return c.reference_sizes()


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def am(cpu_jax):
    from ray_tpu.models import afmoe

    return afmoe


@pytest.fixture(scope="module")
def ref(cpu_jax):
    from ray_tpu.models import afmoe_reference

    return afmoe_reference


def _runner(am, config=None, impl="reference", seed=0, params=None,
            num_blocks=64, max_batch=4):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner

    config = config or am.AfmoeConfig.tiny()
    if params is None:
        params = am.init_params(config, jax.random.key(seed))
    return config, params, ModelRunner(
        config, params, num_blocks=num_blocks, block_size=4,
        attention_impl=impl, chunk_size=16, max_batch=max_batch)


def _step_logits(runner, tokens, n_prompt):
    """Chunked prefill of tokens[:, :n_prompt], then a token at a time, by
    `ModelRunner.step` given ONE table (the "all" group's: the runner lays
    the window group's ring itself), as the benchmark's check drives it.
    -> (logits at positions n_prompt - 1 .. total - 2, every layer's
    attention output before its gate at the decode rows (layers, rows, total
    - n_prompt, H hd))."""
    rows, total = tokens.shape
    pages = -(-total // runner.block_size)
    tables = np.zeros((rows, runner.max_blocks_per_seq), np.int32)
    for i in range(rows):
        tables[i, :pages] = runner.num_blocks - 1 - i * pages - np.arange(
            pages)
    full = lambda v: np.full(rows, v, np.int32)
    got, attended = [], []
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
        attended.append(np.asarray(runner.last_layer_outputs["attended"]))
    return np.stack(got[:-1], axis=1), np.concatenate(attended, axis=2)


def _tokens(seed, rows, n):
    return np.random.default_rng(seed).integers(1, 256, (rows, n)).astype(
        np.int32)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("held", [(0, 16), (4, 12)])
def test_chunked_prefill_then_decode_by_step_matches_the_reference(
        am, ref, impl, held):
    """Through the paged cache of both layer groups (a 40-token prompt in
    chunks of 16, then 8 tokens one at a time: five windows of 8), for the jnp
    attention and for the kernel in interpret mode at six query heads a kv
    head; all experts held, and a share of them. The logits, every layer's
    attention output before its gate, and the routing in published ids."""
    config, params, runner = _runner(
        am, am.AfmoeConfig.tiny(experts_held=held), impl)
    assert runner.group_pages == {"all": 64, "window": 64}
    assert runner.table_widths["window"] == 8     # (8 + 16) / 4 + 2
    tokens = _tokens(1, 2, 48)
    got, attended = _step_logits(runner, tokens, 40)
    want, found = ref.logits_at(params, tokens, list(range(39, 47)),
                                sizes_of(config), watch=list(range(40, 48)))
    assert _rel(got, want) < TOL
    assert attended.shape == found["attended"].shape == (5, 2, 8, 12 * 16)
    assert _rel(attended, found["attended"]) < TOL
    scores = found["scores"]
    assert scores.shape == (3, 2, 48, 16)
    routing = np.asarray(runner.last_routing)     # the last decode step's
    assert routing.shape == (3, 2, 1, 4)
    np.testing.assert_array_equal(
        np.sort(routing[:, :, 0], -1),
        np.sort(np.argsort(-scores[:, :, 47], -1, kind="stable")[..., :4],
                -1))


def _unified():
    import test_llm_unified

    return test_llm_unified


@pytest.mark.parametrize("form", ["G6_K2_one_width",
                                  "G6_K2_window_ring_sink"])
@pytest.mark.parametrize("walk", [
    "long_decode_rows", "mixed_tick_padding_block", "slice_of_two_passes"])
def test_kv_rows_kernel_at_six_query_heads_a_kv_head(cpu_jax, monkeypatch,
                                                     walk, form):
    """`_kv_rows_kernel` interpreted against its oracle where a kv head's
    rows are SIX a token (no whole sublane tile), full and in the window
    form: tests/test_llm_unified.py's case, run in this file's worker."""
    unified = _unified()
    unified.rows_kernel_case(monkeypatch, walk, unified.ROW_FORMS_G6[form])


# sha256 of the StableHLO text `_step_mixed` of the tiny configuration lowers
# to with the kernels interpreted (T = 16 tokens, S = 2 sequences, W = 1),
# taken on PR 63's tree and on PR 64's, which gave it to the character, and
# pinned again by PR 65, which MEANT to change it: a block of one token starts
# its page DMAs in runs (`pa.start_counted`), in every row-pool family's
# program.
STEP_MIXED_TEXT = (
    "62947e5ec284610a86d4522cbcf9718b077465fce6f8e32b124dbb0de12eaa56")


def test_the_step_program_of_whole_lane_tile_heads_is_the_parents(am):
    """PR 64 taught `_kv_rows_kernel` a second layout of a K row (split, for
    heads a lane tile and a half wide: `pa.KRow`) which it takes by the
    operands' widths alone. For heads of whole lane tiles, side by side,
    nothing may have changed: this family's mixed step program (a full and a
    window group through the row kernel, six query heads a kv head) lowers to
    the text the parent's tree lowered it to. A PR that MEANS to change this
    program pins the digest again (it is printed on failure) and says so."""
    import hashlib

    _, _, runner = _runner(am, impl="pallas")
    texts = []

    class Lowered(Exception):
        pass

    def lower(*args, jitted=runner._step_mixed_jit):
        texts.append(jitted.lower(*args).as_text())
        raise Lowered

    runner._step_mixed_jit = lower
    S, T = 2, 16
    z = lambda *n: np.zeros(n, np.int32)
    with pytest.raises(Lowered):
        runner.step_mixed(z(T), z(S), z(S), z(S + 1), runner.zero_tables(S),
                          z(S, 1), z(S, 1), z(S), np.zeros(S, np.float32),
                          z(S), np.ones(S, np.float32), z(S), z(S))
    digest = hashlib.sha256(texts[0].encode()).hexdigest()
    assert digest == STEP_MIXED_TEXT, digest


@pytest.mark.parametrize("fault", [
    "no_window", "full_rotated", "no_gate", "no_post_mlp_norm", "no_bias",
    "no_route_scale"])
def test_a_reference_with_one_term_changed_is_told_apart(am, ref, fault):
    """Each control of chip_smoke.py's `afmoe_check` moves the logits by far
    more than the tolerance; the window mask and the full layer's rotation
    also move the attention outputs of their own layers and of no layer
    before them."""
    config, params, runner = _runner(am)
    tokens = _tokens(2, 1, 48)
    got, attended = _step_logits(runner, tokens, 40)
    positions, watch = list(range(39, 47)), list(range(40, 48))
    assert set(ref.FAULTS) >= {fault}
    faulty, found = ref.logits_at(params, tokens, positions, sizes_of(config),
                                  fault=fault, watch=watch)
    assert _rel(got, faulty) > 1e-2
    first = {"no_window": 0, "full_rotated": 1}.get(fault)
    if first is not None:
        err = [_rel(attended[li], found["attended"][li]) for li in range(5)]
        assert all(e < TOL for e in err[:first]) and err[first] > 1e-2


def test_a_router_that_rounds_its_scores_fails(am, ref, monkeypatch):
    """The router's chain is float32 (`expert_share._wide`): a program whose
    scores pass through bfloat16 is outside the tolerance."""
    import jax.numpy as jnp

    tokens = _tokens(2, 1, 48)
    config, params, runner = _runner(am)
    want, _ = ref.logits_at(params, tokens, list(range(39, 47)),
                            sizes_of(config))
    assert _rel(_step_logits(runner, tokens, 40)[0], want) < TOL
    rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    monkeypatch.setattr(am, "_wide",
                        lambda dot, h, w: rounded(dot(rounded(h), w)))
    _, _, runner = _runner(am, params=params)
    assert _rel(_step_logits(runner, tokens, 40)[0], want) > 1e-4


def _reference_greedy(ref, params, sizes, prompt, output):
    """The reference's greedy choice after prompt + output[:i] for every i,
    by ONE forward pass over the engine's own tokens."""
    tokens = list(prompt) + list(output[:-1])
    positions = list(range(len(prompt) - 1, len(tokens)))
    logits, _ = ref.logits_at(params, np.asarray([tokens], np.int32),
                              positions, sizes)
    return np.argmax(np.asarray(logits)[0], axis=-1).tolist()


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_engine_matches_the_reference_with_and_without_a_prefix_hit(
        am, ref, impl):
    """Mixed ticks through LLMEngine: greedy tokens equal the plain
    reference's, for a request served cold and for requests that hit a cached
    prefix on BOTH layer groups (44 shared tokens = 11 pages: the hit needs
    the "all" pages of [0, 44) and the window pages of [36, 44): a tail of 2
    pages, which the record that admits the hit counts as
    `window_tail_pages`); window pages are freed behind the window on the
    way, and a record says how much of the window pool is in use and how many
    held experts had a row."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = _runner(am, impl=impl)
    engine = LLMEngine(runner, max_batch_size=4, prefill_chunk=16)
    rng = np.random.default_rng(3)
    shared = rng.integers(1, 256, 44).tolist()
    prompts = [shared + rng.integers(1, 256, n).tolist() for n in (9, 3)]
    prompts.append(rng.integers(1, 256, 30).tolist())
    sp = SamplingParams(max_tokens=10, temperature=0.0)
    sizes = sizes_of(config)
    cold = engine.generate([prompts[0]], sp)[0]
    assert cold.output_token_ids == _reference_greedy(
        ref, params, sizes, prompts[0], cold.output_token_ids)
    records = engine.tick_records()
    assert sum(t["window_pages_freed"] for t in records) \
        >= (53 + 10 - 8 - 16) // 4
    assert sum(t["window_tail_pages"] for t in records) == 0
    groups = engine.stats()["kv_groups"]
    assert groups["window"]["live"] == groups["all"]["live"] == 0
    seen = len(records)
    outs = engine.generate([prompts[1], prompts[2], prompts[0]], sp)
    for out, prompt in zip(outs, [prompts[1], prompts[2], prompts[0]]):
        assert out.output_token_ids == _reference_greedy(
            ref, params, sizes, prompt, out.output_token_ids)
    stats = engine.stats()
    assert stats["prefix_hits"] == 2 and stats["prefix_hits_cut_short"] == 0
    assert stats["prefix_tokens_saved"] == 44 + 52
    records = engine.tick_records()[seen:]
    # a tail of 2 pages a hit: [36, 44) behind boundary 44, [44, 52) behind 52
    assert sum(t["window_tail_pages"] for t in records) == 2 + 2
    window = stats["kv_groups"]["window"]
    used = [t["window_pool_used"] for t in records]
    assert 0 < max(used) <= window["total"]
    assert window["live"] + window["parked"] <= max(used)
    met = [t["experts_met"] for t in records if "experts_met" in t]
    assert met and all(0 <= m <= 3 * 16 for m in met) and max(met) > 4
    assert all(t["experts_met"] <= t["expert_rows"] for t in records
               if "experts_met" in t)
    landing, tick = records[-1], records[-2]
    # the last call only lands the step in flight (one step of lookahead)
    assert landing["kv_pages_walked"] == 0 and not landing["lookahead"]
    assert tick["window_pages_walked"] < tick["kv_pages_walked"]
    assert tick["window_kv_tokens"] <= 8 * tick["decode_rows"]


def test_a_hit_whose_window_tail_was_recycled_is_cut_short_and_still_right(
        am, ref):
    """A window pool of 16 pages beside 128 of the `all` group: after eight
    other prompts have passed through it, the first prompt's pages are still
    cached in `all` and its window tail is not. The next request that shares
    the whole prompt is cut short (here: to a miss), counted in
    `prefix_hits_cut_short`, attaches no tail, and decodes what the reference
    does."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = _runner(am, num_blocks=128, max_batch=1)
    assert runner.group_pages == {"all": 128, "window": 16}
    engine = LLMEngine(runner, max_batch_size=1, prefill_chunk=16)
    rng = np.random.default_rng(6)
    shared = rng.integers(1, 256, 44).tolist()
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    sizes = sizes_of(config)
    engine.generate([shared + [7, 8, 9]], sp)
    for _ in range(8):
        engine.generate([rng.integers(1, 256, 36).tolist()], sp)
    bm = engine.block_manager
    hashes = bm.prefix_hashes(shared)
    assert all(h in bm.pools["all"].cached for h in hashes[:11])
    assert not any(h in bm.side["window"].cached for h in hashes[9:11])
    again = shared + [11, 12]
    out = engine.generate([again], sp)[0]
    stats = engine.stats()
    assert stats["prefix_hits_cut_short"] == 1
    assert stats["prefix_tokens_saved"] < 44
    assert sum(t["window_tail_pages"] for t in engine.tick_records()) == 0
    assert out.output_token_ids == _reference_greedy(
        ref, params, sizes, again, out.output_token_ids)


@pytest.mark.parametrize("how", ["abort", "drop_all"])
def test_no_page_of_either_group_leaks(am, how):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    _, _, runner = _runner(am)
    engine = LLMEngine(runner, max_batch_size=4, prefill_chunk=16)
    rng = np.random.default_rng(2)
    ids = [engine.add_request(rng.integers(1, 256, 20).tolist(),
                              SamplingParams(max_tokens=6, temperature=0.0))
           for _ in range(3)]
    for _ in range(3):
        engine.step()
    groups = engine.stats()["kv_groups"]
    assert groups["all"]["live"] >= 15 and groups["window"]["live"] >= 6
    if how == "abort":
        for rid in ids:
            assert engine.abort_request(rid)
    else:
        engine.drop_all()
    for name, g in engine.stats()["kv_groups"].items():
        assert g["live"] == 0, name
        assert g["free"] + g["parked"] == g["total"] == 64, name


def test_eight_shares_add_up_to_the_uncut_layer_before_the_post_norm(am, ref):
    """Programs holding two experts each of a tiny layer's 16, given the same
    normed rows: what their `feed_forward` makes BEFORE `R_post_mlp` (in a
    deployment the exchange's combine stands before that norm), the shared
    expert counted once, equals the uncut reference's m; every share routes
    over all 16 and scales by the route scale over all 4 kept, held or
    not."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(4)
    whole = am.AfmoeConfig.tiny()
    d, f = whole.hidden_size, whole.moe_intermediate_size
    draw = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[-2]), jnp.float32)
    experts = {"w_gate": draw(16, d, f), "w_up": draw(16, d, f),
               "w_down": draw(16, f, d)}
    p = {"router": draw(d, 16),
         "router_bias": jnp.asarray(rng.uniform(0, 0.2, 16), jnp.float32),
         "shared_gate": draw(d, f), "shared_up": draw(d, f),
         "shared_down": draw(f, d)}
    w = jnp.asarray(rng.standard_normal((24, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        routed, shared, choice = ref.routed_ffn(w, p, experts,
                                                sizes_of(whole))
    want = np.asarray(routed + shared, np.float64)
    total, rows = -7 * np.asarray(shared, np.float64), 0
    for first in range(0, 16, 2):
        share = am.AfmoeConfig.tiny(experts_held=(first, first + 2))
        lp = dict(p, **{k: v[first:first + 2] for k, v in experts.items()})
        m, (ids, counts) = share.serving_block().feed_forward(
            "full_moe", w, jnp.ones(24, bool), lp)
        total = total + np.asarray(m, np.float64)
        rows += int(counts[0])
        assert int(counts[2]) <= 2 and int(counts[1]) <= int(counts[0])
    np.testing.assert_array_equal(
        np.sort(np.asarray(ids), -1),
        np.sort(np.argsort(-np.asarray(choice), -1, kind="stable")[:, :4],
                -1))
    assert rows == 24 * whole.num_experts_per_tok    # every pick, once
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    # the gates: the kept scores over their sum, times the route scale
    scores = jax.nn.sigmoid(w @ p["router"])
    _, gates = am.route_one_group(whole, scores, p["router_bias"],
                                  scale=whole.route_scale,
                                  eps=am.ROUTE_NORM_EPS)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), whole.route_scale,
                               rtol=1e-6)
    _, plain = am.route_one_group(whole, scores, p["router_bias"])
    np.testing.assert_allclose(np.asarray(plain).sum(-1), 1.0, rtol=1e-6)


def test_tensor_parallel_and_lora_refuse_at_construction(am):
    block = am.AfmoeConfig.tiny().serving_block()
    with pytest.raises(ValueError, match="afmoe: tensor_parallel > 1"):
        block.refuse(tensor_parallel=2, lora=False)
    with pytest.raises(ValueError, match="afmoe: LoRA"):
        block.refuse(tensor_parallel=1, lora=True)
    block.refuse(tensor_parallel=1, lora=False)
    with pytest.raises(ValueError, match="layer_types"):
        am.AfmoeConfig.tiny(layer_types=("chunked_attention",))
    with pytest.raises(ValueError, match="experts_held"):
        am.AfmoeConfig.tiny(experts_held=(8, 24))


def test_counts_at_the_published_sizes(am):
    """398.6 B parameters as published (the name's 400B), 12.8 B a token
    touches (A13B), and the benchmark's cut: 4,321.8 M."""
    whole = am.AfmoeConfig()
    assert whole.layers_of(am.WINDOW) == 45 and whole.layers_of(am.FULL) == 15
    assert whole.attention_params() == 62_914_560
    assert whole.expert_params() == 28_311_552
    assert round(whole.num_params() / 1e9, 1) == 398.6
    kinds = whole.layer_kinds()
    assert kinds[:6] == ["window_dense"] * 3 + ["full_dense"] + [
        "window_dense"] * 2
    assert set(kinds[6:]) == {"window_moe", "full_moe"}
    cut = am.AfmoeConfig(
        vocab_size=25024, layer_types=whole.layer_types[:5],
        num_dense_layers=1, experts_held=(0, 32),
        max_position_embeddings=9216)
    assert cut.num_params() == 4_321_837_056
    assert cut.layer_kinds() == ["window_dense", "window_moe", "window_moe",
                                 "full_moe", "window_moe"]
    block = cut.serving_block()
    assert block.q_block == 40 and block.routed_layers == 4
    assert block.pool_layer == [0, 1, 2, 0, 3]
    assert block.groups[1].ring_width(16, 128) == 266
    assert [(a.name, a.shape) for a in block.cache_arrays(
        {"all": 8, "window": 6}, 16)] == [
        ("k_all", (1, 8, 16, 1024)), ("v_all", (1, 8, 16, 1024)),
        ("k_window", (4, 6, 16, 1024)), ("v_window", (4, 6, 16, 1024))]


def test_the_benchmarks_reference_is_the_programs_to_the_last_bit(am):
    """The reference lives twice (the benchmark imports nothing of the
    program): the two files are the same text."""
    here = os.path.dirname(os.path.abspath(am.__file__))
    with open(os.path.join(here, "afmoe_reference.py")) as f:
        mine = f.read()
    with open(os.path.join(BENCH, "afmoe_reference.py")) as f:
        assert f.read() == mine


def test_the_reference_differentiates(am, ref):
    """`loss_and_grad_norm` (what the harness holds every family's file to)
    is finite, and its loss is the cross entropy of `logits_at`."""
    import jax

    config = am.AfmoeConfig.tiny(experts_held=(0, 8))
    params = am.init_params(config, jax.random.key(3))
    tokens = _tokens(5, 2, 13)
    value, norm = ref.loss_and_grad_norm(params, tokens, sizes_of(config))
    logits, _ = ref.logits_at(params, tokens[:, :-1], list(range(12)),
                              sizes_of(config))
    logp = jax.nn.log_softmax(logits, -1)
    want = -np.mean(np.take_along_axis(np.asarray(logp),
                                       tokens[:, 1:, None], -1))
    assert np.isfinite(norm) and norm > 0
    assert abs(value - want) < 1e-4
