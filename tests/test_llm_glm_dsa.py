"""GLM-5.2 (models/glm_dsa.py) against its plain reference, at tiny sizes on
the CPU with seeded weights: latent attention over the rows an indexer
selects, the selection shared by the layers behind it, an index-key pool
beside the latent pool in ONE layer group, leading dense layers and expert
layers with a shared expert, through `ModelRunner.step`, ragged mixed
launches, `LLMEngine` and `LLMServer`.

Four layers (full_dense, shared_dense, full_moe, shared_moe), 4 heads over
rows of 32 + 8 with 16-wide values, 4 index heads of 16, a selection of 8
rows; pages of 4, slices of 16, contexts of 40-60 tokens: every context runs
under AND over `index_topk`, across slices' edges and page boundaries.

Tolerance: in float32 program and reference differ in the order of their sums
(the absorbed form over gathered rows against the expanded keys under a mask):
logits agree to ~1e-6 of their largest value; 2e-5 leaves an order of
magnitude. Every control below reads over 1e-2.
"""

import os

import numpy as np
import pytest

import ray_tpu  # noqa: F401

TOL = 2e-5
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE = 4


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def gd(cpu_jax):
    from ray_tpu.models import glm_dsa

    return glm_dsa


@pytest.fixture(scope="module")
def ref(cpu_jax):
    from ray_tpu.models import glm_dsa_reference

    return glm_dsa_reference


def _runner(gd, config=None, impl="reference", seed=0, num_blocks=64,
            max_batch=4, chunk=16, params=None):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner

    config = config or gd.GlmDsaConfig.tiny()
    params = params or gd.init_params(config, jax.random.key(seed))
    return config, params, ModelRunner(
        config, params, num_blocks=num_blocks, block_size=PAGE,
        attention_impl=impl, chunk_size=chunk, max_batch=max_batch)


def _engine(gd, impl="reference", max_batch=4, num_blocks=64, config=None,
            **kw):
    from ray_tpu.llm.engine import LLMEngine

    config, params, runner = _runner(gd, config, impl=impl,
                                     num_blocks=num_blocks,
                                     max_batch=max_batch)
    return config, params, LLMEngine(runner, max_batch_size=max_batch,
                                     prefill_chunk=16, **kw)


def _tokens(seed, rows, n):
    return np.random.default_rng(seed).integers(1, 256, (rows, n)).astype(
        np.int32)


def _step_logits(runner, tokens, n_prompt):
    """Chunked prefill of tokens[:, :n_prompt], then a token at a time, by
    `ModelRunner.step`, as the benchmark's check drives it. -> (logits at
    positions n_prompt - 1 .. total - 2, the routing of every position, the
    selection [(positions, count)] a "full" layer of every position)."""
    rows, total = tokens.shape
    pages = -(-total // runner.block_size)
    tables = np.zeros((rows, runner.max_blocks_per_seq), np.int32)
    for i in range(rows):
        tables[i, :pages] = i * pages + np.arange(pages)
    full = lambda v: np.full(rows, v, np.int32)
    got, routing, picked, counts = [], [], [], []

    def step(tok, start, n):
        logits = runner.step(tok, full(start), full(start + n), full(n),
                             tables)
        routing.append(np.asarray(runner.last_routing)[:, :, :n])
        pos, count = runner.last_layer_outputs["selection"]
        picked.append(np.asarray(pos)[:, :, :n])
        counts.append(np.asarray(count)[:, :, :n])
        return logits

    for start in range(0, n_prompt, runner.chunk_size):
        n = min(runner.chunk_size, n_prompt - start)
        padded = np.zeros((rows, runner.chunk_size), np.int32)
        padded[:, :n] = tokens[:, start:start + n]
        logits = step(padded, start, n)
    got.append(np.asarray(logits))
    for pos in range(n_prompt, total):
        got.append(np.asarray(step(tokens[:, pos:pos + 1], pos, 1)))
    picked, counts = np.concatenate(picked, 2), np.concatenate(counts, 2)
    return (np.stack(got[:-1], axis=1), np.concatenate(routing, axis=2),
            list(zip(picked, counts)))


def _reference_greedy(ref, params, sizes, prompt, output):
    tokens = list(prompt) + list(output[:-1])
    positions = list(range(len(prompt) - 1, len(tokens)))
    logits = ref.logits_at(params, np.asarray([tokens], np.int32), positions,
                           sizes)[0]
    return np.argmax(np.asarray(logits)[0], axis=-1).tolist()


def _drain(engine):
    done = {}
    while engine.has_unfinished():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out
    return done


# ---- the files and the counts -----------------------------------------------

def test_the_reference_is_in_the_repo_twice_and_equal():
    with open(os.path.join(HERE, "ray_tpu", "models",
                           "glm_dsa_reference.py")) as f:
        program = f.read()
    with open(os.path.join(HERE, "benchmarks", "glm_dsa_reference.py")) as f:
        assert f.read() == program
    assert "ray_tpu" not in program.split('"""')[2]     # imports nothing


def test_the_published_layout_counts_the_models_parameters(gd):
    """ISSUE 49's arithmetic at the published widths: a latent layer's
    attention 165.0 M, an indexer 9.37 M, and the cell's cut (layers 2-9, 8
    held experts, an eighth of the vocabulary) 4,192 M; the published lists
    give 3 dense layers and 21 indexers in 78."""
    whole = gd.GlmDsaConfig()
    assert whole.layer_kinds()[:8] == (
        "full_dense", "full_dense", "full_dense", "shared_moe", "shared_moe",
        "shared_moe", "full_moe", "shared_moe")
    assert (whole.n_full_layers, whole.n_moe_layers) == (21, 75)
    assert whole.attention_params() == 165_019_648
    assert whole.indexer_params() == 9_371_648
    assert whole.row_width == 640
    cut = gd.GlmDsaConfig(
        vocab_size=19360, num_hidden_layers=8,
        indexer_types=whole.indexer_types[2:10],
        mlp_layer_types=whole.mlp_layer_types[2:10], experts_held=(0, 8),
        max_position_embeddings=36864)
    assert cut.layer_kinds() == ("full_dense",) + (
        "shared_moe", "shared_moe", "shared_moe", "full_moe", "shared_moe",
        "shared_moe", "shared_moe")
    assert abs(cut.num_params() - 4.192e9) < 2e6
    assert _cache_bytes_a_token(gd, cut) == 8 * 1280 + 2 * 256


def _cache_bytes_a_token(gd, config):
    """Bytes of cache a token holds, as the arrays lie."""
    arrays = gd.Block(config).cache_arrays({"all": 4}, 16)
    return sum(a.shape[0] * a.shape[-1] * 2 for a in arrays)


def test_the_interleaved_rotation_is_not_rotate_half(gd):
    """Lanes (2i, 2i + 1) against lanes (i, i + rope / 2): the same weights
    give other numbers; a lane's pair is where `rope_interleave` says."""
    import jax.numpy as jnp

    from ray_tpu.models.deepseek_v2 import rotate_half

    c = gd.GlmDsaConfig.tiny()
    x = jnp.asarray(np.random.default_rng(0).standard_normal((5, 2, 8)),
                    jnp.float32)
    cos, sin = gd.rope_at(c, jnp.arange(5) + 3)
    ours = np.asarray(gd.rotate_interleaved(x, cos, sin))
    assert np.abs(ours - np.asarray(rotate_half(x, cos, sin))).max() > 0.1
    angle = 4 * c.rope_theta ** (-2.0 * 1 / 8)      # position 4, pair i = 1
    np.testing.assert_allclose(
        ours[1, 0, 2:4],
        [x[1, 0, 2] * np.cos(angle) - x[1, 0, 3] * np.sin(angle),
         x[1, 0, 3] * np.cos(angle) + x[1, 0, 2] * np.sin(angle)], rtol=1e-5)
    np.testing.assert_allclose(np.linalg.norm(ours, axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)


# ---- against the reference --------------------------------------------------

@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_chunked_prefill_then_decode_by_step_matches_the_reference(
        gd, ref, impl):
    """Logits, not tokens: 40 positions in slices of 16 then 8 decode
    positions through the cache, contexts under and over index_topk = 8; the
    program's selection is the reference's own, row for row."""
    config, params, runner = _runner(gd, impl=impl)
    tokens = _tokens(0, 2, 48)
    got, routing, selection = _step_logits(runner, tokens, 40)
    want, _, index = ref.logits_at(params, tokens, list(range(39, 47)),
                                   config.reference_sizes())
    assert _rel(got, want) < TOL
    # following the program's experts and rows changes nothing: they are the
    # reference's
    same, _, _ = ref.logits_at(params, tokens, list(range(39, 47)),
                               config.reference_sizes(), kept=routing,
                               selection=selection)
    assert _rel(same, want) < 1e-6
    assert len(selection) == len(index) == config.n_full_layers == 2
    for (pos, count), scores in zip(selection, index):
        for t in (3, 7, 8, 20, 47):
            assert count[0, t] == min(t + 1, 8)
            best = sorted(np.argsort(-scores[0, t], kind="stable")[:8])
            assert list(pos[0, t, :count[0, t]]) == best[:count[0, t]]


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_selection_groups_of_one_four_and_two_match_the_reference(gd, ref,
                                                                  impl):
    """indexer_types (full, full, shared, shared, shared, full, shared): three
    selection groups of 1, 4 and 2 layers in a pool of 3 x 4 places (five
    unused). Prefill in slices, whose tokens select earlier tokens of their
    own slice, then decode rows: every group's layers attend under its one
    gather and bring the step's own rows themselves."""
    config = gd.GlmDsaConfig.tiny(
        num_hidden_layers=7,
        indexer_types=("full", "full", "shared", "shared", "shared", "full",
                       "shared"),
        mlp_layer_types=("dense", "dense") + ("sparse",) * 5)
    block = gd.Block(config)
    assert block.place == [(0, 0), (1, 0), (1, 1), (1, 2), (1, 3), (2, 0),
                           (2, 1)]
    latent, index = block.cache_arrays({"all": 64}, PAGE)
    assert latent.shape == (3, 64, PAGE, 4 * config.row_width)
    assert index.shape == (3, 64, PAGE, config.index_head_dim)
    config, params, runner = _runner(gd, config, impl=impl)
    tokens = _tokens(21, 2, 48)
    got, routing, selection = _step_logits(runner, tokens, 40)
    want, _, index = ref.logits_at(params, tokens, list(range(39, 47)),
                                   config.reference_sizes())
    assert _rel(got, want) < TOL
    assert len(selection) == len(index) == 3
    # a slice's token kept an earlier token of its own slice, in every group
    for pos, count in selection:
        t = 30      # the slice from 16 on: its own positions are 16 .. 30
        assert count[0, t] == 8 and (pos[0, t] >= 16).any()


def test_a_step_whose_contexts_all_fit_takes_the_dense_kernel(gd, ref,
                                                              monkeypatch):
    """index_topk 64 over 48 positions: nothing is scored or gathered (the
    entries are never RUN: their branch of the step is not taken), and the
    logits are the reference's."""
    import jax

    from ray_tpu.ops import sparse_latent as sl

    config = gd.GlmDsaConfig.tiny(index_topk=64)
    ran = []
    for name in ("dsa_index_reference", "gather_rows", "_attend_two_sets"):
        fn = getattr(sl, name)
        monkeypatch.setattr(sl, name, lambda *a, _fn=fn, _n=name, **kw: (
            jax.debug.callback(lambda: ran.append(_n)), _fn(*a, **kw))[1])
    config, params, runner = _runner(gd, config)
    tokens = _tokens(1, 2, 48)
    got, _, selection = _step_logits(runner, tokens, 40)
    want = ref.logits_at(params, tokens, list(range(39, 47)),
                         config.reference_sizes())[0]
    assert _rel(got, want) < TOL
    jax.effects_barrier()
    assert not ran and not selection[0][1].any()
    # "no selection" followed by the reference is its whole context (no row
    # of a softmax is left without one: a NaN would pass no comparison)
    same = ref.logits_at(params, tokens, list(range(39, 47)),
                         config.reference_sizes(), selection=selection)[0]
    assert _rel(same, want) < 1e-6


def test_a_shared_layer_reads_the_full_layers_set_and_scores_nothing(
        gd, monkeypatch):
    """Count the calls in one step's trace: two "full" layers score, select
    and gather, four layers attend, and a "shared" layer's rows are the
    gathered rows of the "full" layer before it."""
    import jax

    from ray_tpu.ops import sparse_latent as sl

    calls = {"dsa_index": 0, "dsa_select": 0, "dsa_attend": 0,
             "gather_selection": 0, "rows": []}

    def count(name, fn):
        def counted(*a, **kw):
            calls[name] += 1
            return fn(*a, **kw)
        return counted

    def layer(*a, sel, _fn=gd.latent_attention, **kw):
        calls["rows"].append(sel[4])
        return _fn(*a, sel=sel, **kw)

    for name in calls.keys() - {"rows"}:
        monkeypatch.setattr(sl, name, count(name, getattr(sl, name)))
    monkeypatch.setattr(gd, "latent_attention", layer)
    config, params, runner = _runner(gd)
    z = lambda *s: np.zeros(s, np.int32)
    jax.make_jaxpr(runner._step)(
        params, runner.cache, z(2, 16), z(2), z(2), z(2),
        {"all": z(2, runner.max_blocks_per_seq)})
    assert (calls["dsa_index"], calls["dsa_select"],
            calls["gather_selection"], calls["dsa_attend"]) == (2, 2, 2, 4)
    rows = calls["rows"]
    assert rows[0].shape[-1] == 2 * config.row_width
    assert len(rows) == config.num_hidden_layers == 4
    assert rows[0] is rows[1] and rows[1] is not rows[2]
    assert rows[3] is rows[2]


def test_ragged_mixed_steps_and_the_engine_match_the_reference(gd, ref):
    """Mixed ticks with one step of lookahead: six requests of unequal
    lengths through four rows, decode rows beside prompt slices; every greedy
    token is the reference's, and the records count what the selection
    spares."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(gd)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 256, n).tolist()
               for n in (37, 9, 22, 41, 5, 30)]
    ids = [engine.add_request(p, SamplingParams(
        max_tokens=6 + 3 * (i % 3), temperature=0.0))
        for i, p in enumerate(prompts)]
    done = _drain(engine)
    sizes = config.reference_sizes()
    for rid, prompt in zip(ids, prompts):
        out = done[rid].output_token_ids
        assert out == _reference_greedy(ref, params, sizes, prompt, out)
    ticks = [t for t in engine.tick_records() if t["used"]]
    assert any(t["prefill_rows"] and t["decode_rows"] for t in ticks)
    for t in ticks:
        assert t["dsa_index_rows"] == t["kv_tokens"]
        assert t["dsa_pairs"] <= t["attn_pairs"]
        assert t["dsa_attend_rows"] <= t["kv_tokens"]
        assert t["dsa_selected_rows"] <= t["prefill_rows"] + t["decode_rows"]
    late = [t for t in ticks if t["decode_rows"] and not t["prefill_rows"]]
    assert late and all(t["dsa_pairs"] == 8 * t["decode_rows"]
                        < t["attn_pairs"] for t in late[-3:])
    stats = engine.stats()
    for name in gd.Block.tick_fields:
        assert stats[name] == sum(t[name] for t in ticks) > 0


def test_a_mixed_launch_through_the_kernels_is_the_oracles(gd):
    """One ragged launch (a decode row at context 30, a 5-token slice from
    position 20, a prompt from 0) over the same cache: the three entries'
    kernels, interpreted, give the logits of their oracles."""
    import jax
    import jax.numpy as jnp

    _, params, a = _runner(gd)
    _, _, b = _runner(gd, impl="pallas", params=params)
    tokens = _tokens(12, 2, 32)
    tables = np.zeros((2, a.max_blocks_per_seq), np.int32)
    tables[:, :8] = np.arange(16).reshape(2, 8)
    full = lambda v: np.full(2, v, np.int32)
    for start in (0, 16):
        a.step(tokens[:, start:start + 16], full(start), full(start + 16),
               full(16), tables)
    b.cache = jax.tree.map(jnp.copy, a.cache)
    three = np.zeros((3, a.max_blocks_per_seq), np.int32)
    three[:2], three[2, :3] = tables, [20, 21, 22]
    flat = np.zeros(16, np.int32)
    flat[:13] = np.concatenate([tokens[0, 29:30], tokens[1, 20:25],
                                _tokens(13, 1, 7)[0]])
    args = (flat, np.asarray([29, 20, 0], np.int32),
            np.asarray([30, 25, 7], np.int32),
            np.asarray([0, 1, 6, 13], np.int32), {"all": three},
            np.asarray([0, 5, 12], np.int32))
    want = np.asarray(a.step_mixed_logits(*args))
    got = np.asarray(b.step_mixed_logits(*args))
    assert b.attention_impl == "pallas" and np.isfinite(want).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * np.abs(
        want).max())


def test_rows_on_one_document_through_the_kernels_are_the_oracles(
        gd, monkeypatch):
    """Three rows whose tables begin with the SAME eight pages (a document of
    32 tokens, two of the index kernel's tiles here, held by reference as the
    prefix cache holds it) and go on with pages of their own: a slice of 3, a
    decode row, a slice of 6, all from position 32. The index kernel walks
    the document once for the ten tokens, and the logits are the oracles'."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_latent as sl

    monkeypatch.setattr(sl, "INDEX_TILE", 16)
    jax.clear_caches()          # the entry's traces hold the tile they saw
    try:
        _, params, a = _runner(gd)
        _, _, b = _runner(gd, impl="pallas", params=params)
        document = _tokens(14, 1, 32)
        one = np.zeros((1, a.max_blocks_per_seq), np.int32)
        one[0, :8] = np.arange(8)
        for start in (0, 16):
            a.step(document[:, start:start + 16], np.full(1, start, np.int32),
                   np.full(1, start + 16, np.int32),
                   np.full(1, 16, np.int32), one)
        b.cache = jax.tree.map(jnp.copy, a.cache)
        three = np.zeros((3, a.max_blocks_per_seq), np.int32)
        three[:, :8] = np.arange(8)
        three[0, 8], three[1, 8], three[2, 8:10] = 20, 21, [22, 23]
        flat = np.zeros(16, np.int32)
        flat[:10] = _tokens(15, 1, 10)[0]
        q_pos = np.full(3, 32, np.int32)
        cu = np.asarray([0, 3, 4, 10], np.int32)
        args = (flat, q_pos, np.asarray([35, 33, 38], np.int32), cu,
                {"all": three}, np.asarray([2, 3, 9], np.int32))
        _, run = sl.shared_runs(jnp.asarray(three), jnp.asarray(q_pos),
                                jnp.asarray(cu[1:] - cu[:-1]), 4, 16)
        assert list(np.asarray(run)) == [2, 2, 2]
        want = np.asarray(a.step_mixed_logits(*args))
        got = np.asarray(b.step_mixed_logits(*args))
        assert b.attention_impl == "pallas" and np.isfinite(want).all()
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-5 * np.abs(
            want).max())
    finally:
        jax.clear_caches()


def test_the_counts_of_a_tick_by_hand(gd):
    block = gd.Block(gd.GlmDsaConfig.tiny())       # index_topk 8
    # a slice of 6 tokens from position 5 (contexts 6 .. 11), a decode row at
    # context 30, a row of 3 tokens that all see everything
    got = block.tick_counts([(6, 5, 11), (1, 29, 30), (3, 0, 3)])
    pairs = (6 + 7 + 8) + 3 * 8 + 8 + (1 + 2 + 3)
    assert got == {"dsa_pairs": pairs, "dsa_index_rows": 11 + 30 + 3,
                   "dsa_attend_rows": 8 + 8 + 3, "dsa_selected_rows": 2,
                   # one gather a selection GROUP (2), not one a layer (4)
                   "dsa_gathered_rows": 2 * pairs,
                   # no tables, nothing shared: every row's walk is its own
                   "dsa_index_walked_rows": 11 + 30 + 3}
    # two decode rows on one document of 2 of the index kernel's tiles (2,048
    # rows = 512 pages of 4) and 13 / 53 rows of their own: the document's
    # index keys are fetched once for both
    document = np.arange(100, 612)
    table = np.zeros((3, 600), np.int32)     # as the step lays it: padded
    table[0, :516] = np.append(document, [7, 8, 9, 10])
    table[1, :526] = np.append(document, np.arange(20, 34))
    got = block.tick_counts([(1, 2060, 2061), (1, 2100, 2101)], table, 4)
    assert got["dsa_index_rows"] == 2061 + 2101
    assert got["dsa_index_walked_rows"] == 2048 + 13 + 53
    # a step none of whose contexts is over index_topk gathers nothing
    assert block.tick_counts([(3, 0, 3), (1, 7, 8)])["dsa_gathered_rows"] == 0


def test_the_published_selection_size_through_the_model(gd, ref):
    """index_topk 2,048 at a context of 4,096 + 4, two layers (full, shared)
    at tiny widths: 2,048 + 2,048 positions in slices of 512, then decode
    rows, each over 2,048 selected rows of its 4,096."""
    config = gd.GlmDsaConfig.tiny(
        num_hidden_layers=2, indexer_types=("full", "shared"),
        mlp_layer_types=("dense", "sparse"), index_topk=2048,
        max_position_embeddings=4352, num_attention_heads=2)
    config, params, runner = _runner(gd, config, num_blocks=1100, chunk=512)
    tokens = _tokens(3, 1, 4100)
    got, _, selection = _step_logits(runner, tokens, 4096)
    want, _, index = ref.logits_at(params, tokens, list(range(4095, 4099)),
                                   config.reference_sizes())
    assert _rel(got, want) < TOL
    pos, count = selection[0]
    assert count[0, 2048] == count[0, 4099] == 2048
    assert count[0, 2047] == 0      # that slice's contexts all fit: dense
    kept = set(pos[0, 4099].tolist())
    assert len(kept) == 2048
    assert len(kept & set(range(4100 - 2048, 4100))) < 1300   # not "recent"
    assert kept == set(np.argsort(-index[0][0, 4099],
                                  kind="stable")[:2048].tolist())


# ---- the second pool follows the first --------------------------------------

def test_both_pools_travel_in_the_wire_view(gd):
    """gather_pages / scatter_pages carry (latent, index): a runner that
    adopts another's pages decodes the same logits, and without the index
    keys it does not."""
    config, params, a = _runner(gd)
    tokens = _tokens(5, 1, 33)
    full = lambda v: np.full(1, v, np.int32)
    table = np.zeros((1, a.max_blocks_per_seq), np.int32)
    table[0, :9] = np.arange(9) + 3
    for start in (0, 16):
        a.step(tokens[:, start:start + 16], full(start), full(start + 16),
               full(16), table)
    decode = lambda r, t: np.asarray(r.step(
        tokens[:, 32:33], full(32), full(33), full(1), t))
    want = decode(a, table)
    pages = a.gather_pages(list(range(3, 12)))
    # two selection groups of two layers, their rows side by side a token
    assert [p.shape for p in pages] == [(2, 1, 9, PAGE, 2 * 128),
                                        (2, 1, 9, PAGE, 16)]
    assert a.page_nbytes == (4 * 128 + 2 * 16) * PAGE * 4   # 40 -> 128 lanes
    there = np.zeros_like(table)
    there[0, :9] = np.arange(9) + 40
    for keep_index in (True, False):
        _, _, b = _runner(gd, params=params)
        b.scatter_pages(list(range(40, 49)), pages[0],
                        pages[1] if keep_index else np.zeros_like(pages[1]))
        got = decode(b, there)
        if keep_index:
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
        else:
            assert _rel(got, want) > 1e-2


def test_a_prefix_hit_brings_the_index_keys_with_the_latent_rows(gd, ref):
    """The same long prompt twice: the second request skips its prefill, and
    its tokens are the first's, a cold engine's and the reference's. The
    control: the cached pages' index keys zeroed before the hit (a pool that
    did not follow) changes them."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(gd, enable_prefix_caching=True)
    _, _, cold = _engine(gd)
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    prompt = _tokens(6, 1, 45)[0].tolist()
    first = engine.generate([prompt], sp)[0].output_token_ids
    saved = engine.block_manager.prefix_tokens_saved
    second = engine.generate([prompt], sp)[0].output_token_ids
    assert engine.block_manager.prefix_tokens_saved - saved >= 40
    assert second == first == cold.generate([prompt], sp)[0].output_token_ids
    assert first == _reference_greedy(ref, params, config.reference_sizes(),
                                      prompt, first)
    # the control
    runner = engine.runner
    runner.cache["index"] = runner.cache["index"] * 0
    lost = engine.generate([prompt], sp)[0].output_token_ids
    assert lost != first


def test_eviction_spills_both_pools_and_readmission_restores_them(gd):
    """Pages evicted from a 24-page pool come back from host RAM with their
    index keys: the re-admitted prompt decodes the same tokens."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.prefix_store import HostPrefixTier
    from ray_tpu.llm.sampling import SamplingParams

    _, _, runner = _runner(gd, num_blocks=24)
    engine = LLMEngine(runner, max_batch_size=4, prefill_chunk=16,
                       enable_prefix_caching=True)
    tier = HostPrefixTier(8 << 20, low_watermark=0.8)
    engine.attach_prefix_store(host_tier=tier)
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    first = _tokens(7, 1, 30)[0].tolist()
    want = engine.generate([first], sp)[0].output_token_ids
    for s in range(8, 12):                          # churn the pool
        engine.generate([_tokens(s, 1, 60)[0].tolist()], sp)
    engine.settle_spills()
    assert len(tier) > 0 and tier.stats()["spills"] >= 3
    entry = tier.hottest(1)[0]
    assert entry["arrays"] == ["latent", "index"]
    assert entry["index"].shape[:3] == (2, 1, 1)
    before = engine.prefill_tokens_computed
    assert engine.generate([first], sp)[0].output_token_ids == want
    assert engine.host_prefix_hits >= 3
    assert engine.prefill_tokens_computed - before < len(first)


def test_the_server_serves_on_the_normal_path(gd, ref):
    from ray_tpu.llm.serving import LLMConfig, LLMServer

    config = gd.GlmDsaConfig.tiny(experts_held=(0, 8))
    server = LLMServer(LLMConfig(
        model_config=config, num_kv_blocks=64, block_size=PAGE,
        max_batch_size=4, prefill_chunk=16, warmup_buckets="off",
        stream_timeout_s=120.0))
    try:
        prompt = _tokens(9, 1, 27)[0].tolist()
        request = {"prompt": prompt, "max_tokens": 5}
        out = [server.completions({**request, "request_id": f"s{i}"})[
            "choices"][0]["token_ids"] for i in range(2)]
        params = server.engine.runner.params
        assert out[0] == out[1] == _reference_greedy(
            ref, params, config.reference_sizes(), prompt, out[0])
        stats = server.engine_stats()
        assert stats["prefix_hits"] == 1 and stats["prefix_tokens_saved"] >= 24
        assert stats["dsa_pairs"] > 0 and stats["dsa_selected_rows"] > 0
    finally:
        server._handoff.close()


def test_what_the_block_cannot_do_refuses_by_name(gd):
    block = gd.Block(gd.GlmDsaConfig.tiny())
    with pytest.raises(ValueError, match="glm_dsa: tensor_parallel"):
        block.refuse(tensor_parallel=2, lora=False)
    with pytest.raises(ValueError, match="glm_dsa: LoRA"):
        block.refuse(tensor_parallel=1, lora=True)
    with pytest.raises(ValueError, match="must be a full one"):
        gd.GlmDsaConfig.tiny(indexer_types=("shared",) * 4)


def test_thirty_two_shares_add_up_to_the_uncut_layer(gd, ref):
    """Programs holding one expert each of a tiny layer's 32, given the same
    rows: their routed parts summed and the shared expert counted ONCE (every
    share routes over all 32 and renormalises over all 4 kept, held or not)
    equal the uncut reference's layer."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.expert_share import (_dot32, _ffn, held_expert_ffn,
                                             route_one_group)

    rng = np.random.default_rng(4)
    whole = gd.GlmDsaConfig.tiny(n_routed_experts=32, experts_held=(0, 32))
    d, f = whole.hidden_size, whole.moe_intermediate_size
    draw = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[-2]), jnp.float32)
    experts = {"w_gate": draw(32, d, f), "w_up": draw(32, d, f),
               "w_down": draw(32, f, d)}
    p = {"router": draw(d, 32),
         "router_bias": jnp.asarray(rng.uniform(0, 0.2, 32), jnp.float32),
         "shared_gate": draw(d, f), "shared_up": draw(d, f),
         "shared_down": draw(f, d)}
    x = jnp.asarray(rng.standard_normal((24, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ p["router"])
        want, _ = ref._routed(x, p, experts, whole.reference_sizes())
        ids, gates = route_one_group(whole, scores, p["router_bias"])
        total = np.asarray(_ffn(_dot32, x, p["shared_gate"], p["shared_up"],
                                p["shared_down"]), np.float64)
        rows = 0
        for first in range(32):
            share = gd.GlmDsaConfig.tiny(n_routed_experts=32,
                                         experts_held=(first, first + 1))
            lp = {k: v[first:first + 1] for k, v in experts.items()}
            y, (n, *_) = held_expert_ffn(
                share, x, ids, gates * whole.routed_scaling_factor,
                jnp.ones(24, bool), lp)
            total = total + np.asarray(y, np.float64)
            rows += int(n)
    assert rows == 24 * whole.num_experts_per_tok      # every pick, once
    np.testing.assert_allclose(total, np.asarray(want), rtol=1e-4, atol=1e-5)


# ---- controls: each MUST fail the comparison --------------------------------

@pytest.mark.parametrize("fault", ["recent_rows", "rotate_half",
                                   "no_index_bias", "share_nothing"])
def test_a_reference_done_otherwise_is_told_apart(gd, ref, fault):
    """The most recent index_topk rows in place of the selection (ISSUE 49's
    first control), the other rotation, an index key without its bias, a
    "shared" layer that attends to everything: each is far from the
    program."""
    config, params, runner = _runner(gd)
    tokens = _tokens(0, 2, 48)
    got, _, _ = _step_logits(runner, tokens, 40)
    bad = ref.logits_at(params, tokens, list(range(39, 47)),
                        config.reference_sizes(), fault=fault)[0]
    assert _rel(got, bad) > 1e-2


def test_a_program_that_keeps_the_most_recent_rows_fails(gd, ref,
                                                         monkeypatch):
    """The control in the program: `dsa_select` replaced by the most recent
    index_topk positions is far from the reference, in its logits and in the
    rows it kept."""
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_latent as sl

    def recent(scores, n, *, topk, **_):
        count = jnp.minimum(n, topk)
        pos = (n - count)[:, None] + jnp.arange(topk)[None, :]
        return (jnp.where(jnp.arange(topk)[None, :] < count[:, None], pos,
                          0).astype(jnp.int32), count.astype(jnp.int32))

    monkeypatch.setattr(sl, "dsa_select", recent)
    config, params, runner = _runner(gd)
    tokens = _tokens(0, 2, 48)
    got, _, selection = _step_logits(runner, tokens, 40)
    want, _, index = ref.logits_at(params, tokens, list(range(39, 47)),
                                   config.reference_sizes())
    assert _rel(got, want) > 1e-2
    pos, count = selection[0]
    best = set(np.argsort(-index[0][0, 47], kind="stable")[:8].tolist())
    assert len(best & set(pos[0, 47].tolist())) < 6
