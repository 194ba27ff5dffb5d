"""Kimi-Linear (models/kimi_linear.py) against its plain reference, at tiny
sizes on the CPU with seeded weights: Kimi Delta Attention layers (a gated
delta-rule state a sequence, a SLOT) beside latent attention layers (a paged
row pool) in ONE block, a leading dense layer and expert layers with a shared
expert, through `ModelRunner.step`, ragged mixed launches, `LLMEngine` and
`LLMServer`.

Five layers (KDA, KDA, MLA, KDA, MLA), 4 KDA heads of 16, 4 latent heads over
rows of 32 + 8; pages of 4, slices of 16, contexts of 40-60 tokens: every
sequence crosses several slices' edges and page boundaries (a slice of
several of the kernel's chunks: tests/test_kda.py).

Tolerance: in float32 program and reference differ in the order of their sums
(the chunked WY form and the absorbed latent form against the recurrence and
the expanded keys): logits agree to ~2e-6 of their largest value; 2e-5 leaves
an order of magnitude. Every control below reads over 1e-2 (a term dropped)
or over 1e-4 (a state kept in bfloat16).
"""

import os

import numpy as np
import pytest

import ray_tpu  # noqa: F401

TOL = 2e-5
NEAR = 1e-3     # of the logits' scale: `_greedy_miss`
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = list(range(0, 32, 16)) + list(range(32, 44))


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def km(cpu_jax):
    from ray_tpu.models import kimi_linear

    return kimi_linear


@pytest.fixture(scope="module")
def ref(cpu_jax):
    from ray_tpu.models import kimi_linear_reference

    return kimi_linear_reference


def _runner(km, config=None, impl="reference", seed=0, num_blocks=64,
            max_batch=4):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner

    config = config or km.KimiLinearConfig.tiny()
    params = km.init_params(config, jax.random.key(seed))
    return config, params, ModelRunner(
        config, params, num_blocks=num_blocks, block_size=4,
        attention_impl=impl, chunk_size=16, max_batch=max_batch)


def _engine(km, impl="reference", max_batch=4, num_blocks=64, **kw):
    from ray_tpu.llm.engine import LLMEngine

    config, params, runner = _runner(km, impl=impl, num_blocks=num_blocks,
                                     max_batch=max_batch)
    return config, params, LLMEngine(runner, max_batch_size=max_batch,
                                     prefill_chunk=16, **kw)


def _tokens(seed, rows, n):
    return np.random.default_rng(seed).integers(1, 256, (rows, n)).astype(
        np.int32)


def _step_logits(runner, tokens, n_prompt, after_step=None):
    """Chunked prefill of tokens[:, :n_prompt], then a token at a time, by
    `ModelRunner.step` given ONE table, the `all` group's (the runner lays the
    slots itself), as the benchmark's check drives it. -> (logits at
    positions n_prompt - 1 .. total - 2, the routing of every position)."""
    rows, total = tokens.shape
    pages = -(-total // runner.block_size)
    tables = np.zeros((rows, runner.max_blocks_per_seq), np.int32)
    for i in range(rows):
        tables[i, :pages] = i * pages + np.arange(pages)
    full = lambda v: np.full(rows, v, np.int32)
    got, routing = [], []

    def step(tok, start, n):
        logits = runner.step(tok, full(start), full(start + n), full(n),
                             tables)
        routing.append(np.asarray(runner.last_routing)[:, :, :n])
        if after_step is not None:
            after_step(runner)
        return logits

    for start in range(0, n_prompt, runner.chunk_size):
        n = min(runner.chunk_size, n_prompt - start)
        padded = np.zeros((rows, runner.chunk_size), np.int32)
        padded[:, :n] = tokens[:, start:start + n]
        logits = step(padded, start, n)
    got.append(np.asarray(logits))
    for pos in range(n_prompt, total):
        got.append(np.asarray(step(tokens[:, pos:pos + 1], pos, 1)))
    return np.stack(got[:-1], axis=1), np.concatenate(routing, axis=2)


def _reference_logits(ref, params, sizes, prompt, output):
    """The reference's logits after prompt + output[:i] for every i, by ONE
    forward pass over the engine's own tokens."""
    tokens = list(prompt) + list(output[:-1])
    positions = list(range(len(prompt) - 1, len(tokens)))
    logits, _ = ref.logits_at(params, np.asarray([tokens], np.int32),
                              positions, sizes)
    return np.asarray(logits)[0]


def _reference_greedy(ref, params, sizes, prompt, output):
    """The reference's greedy choice at each of those positions."""
    return np.argmax(_reference_logits(ref, params, sizes, prompt, output),
                     axis=-1).tolist()


def _greedy_miss(ref, params, sizes, prompt, output):
    """How far `output` is from a greedy run of the reference, a ROUNDING
    aside: the most, over its tokens, that the reference's logit for the
    token lies under the largest, over the logits' scale (0: every token is
    the argmax). Two float32 programs that order their sums differently may
    land either side of a tie nearer than NEAR; a slot that was restored
    wrongly misses by hundreds of times that (tests/test_llm_brumby.py's)."""
    logits = _reference_logits(ref, params, sizes, prompt, output)
    picked = logits[np.arange(len(output)), output]
    return float((logits.max(-1) - picked).max() / np.abs(logits).max())


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
                           "kimi_linear_reference.py")) as f:
        program = f.read()
    with open(os.path.join(HERE, "benchmarks",
                           "kimi_linear_reference.py")) as f:
        assert f.read() == program
    assert "ray_tpu" not in program.split('"""')[2]     # imports nothing


def test_the_published_layout_counts_the_models_parameters(km):
    """The published model whole, and the benchmark's cut (12 layers, 32 held
    experts, an eighth of the vocabulary), by hand."""
    M = 1e6
    whole = km.KimiLinearConfig()
    assert whole.kda_params() == 39_510_016 and whole.mla_params() == 29_114_368
    assert whole.expert_params() == 7_077_888
    assert whole.layer_kinds().count("kda_moe") == 19
    # 48 B: 26 expert layers of 256 + 1 experts, and a router
    assert 48.0e9 < whole.num_params() < 49.5e9
    cut = km.KimiLinearConfig(
        num_hidden_layers=12, kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11),
        full_attn_layers=(4, 8, 12), experts_held=(0, 32), vocab_size=20480,
        max_position_embeddings=16384)
    assert cut.layer_kinds() == ["kda_dense"] + ["kda_moe", "kda_moe",
                                                 "mla_moe", "kda_moe"] * 2 + [
        "kda_moe", "kda_moe", "mla_moe"]
    by_hand = (2 * 20480 * 2304 + 9 * 39_510_016 + 3 * 29_114_368
               + 3 * 2304 * 9216
               + 11 * (33 * 7_077_888 + 2304 * 256))
    assert cut.num_params() == by_hand
    assert round(by_hand / M) == 3177
    assert cut.state_bytes_per_sequence == 9 * 4 * (32 * 128 * 128
                                                    + 3 * 3 * 4096)


def test_the_gates_are_drawn_to_remember(km):
    """-log(alpha) before the token's own term lies in [1e-3, 1e-1] a
    channel: a state lives over hundreds to thousands of tokens."""
    import jax

    config = km.KimiLinearConfig.tiny()
    p = km.init_params(config, jax.random.key(3))["layers"]["kda_moe"]
    rate = np.exp(np.asarray(p["A_log"]))[..., None] * np.asarray(
        jax.nn.softplus(p["dt_bias"])).reshape(2, 4, 16)
    assert rate.min() >= 0.99e-3 and rate.max() <= 1.01e-1
    assert np.exp(np.asarray(p["A_log"])).min() >= 1.0
    assert np.exp(np.asarray(p["A_log"])).max() <= 16.0


# ---- through the runner -----------------------------------------------------

@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_chunked_prefill_then_decode_by_step_matches_the_reference(
        km, ref, impl):
    """Prefill slices then decode rows through BOTH caches (the latent pool's
    pages and the state group's slots), the reference routing for itself:
    in float32 no choice differs."""
    config, params, runner = _runner(km, impl=impl)
    from ray_tpu.ops import kda

    assert [(a.name, a.group) for a in runner.cache_arrays] == [
        ("latent", "all"), ("kda_state", "state"), ("kda_rows", "state"),
        ("kda_fill", "state"), ("kda_tail", "state")]
    assert runner.cache["latent"].shape[0] == 2
    assert runner.cache["kda_state"].shape[:2] == (3, 9)
    # a tile a block of 4 heads: `kda.FOLD` rows of [k | c | u] a head
    assert runner.cache["kda_rows"].shape == (3, 9, 1, kda.FOLD * 4, 3 * 16)
    tokens = _tokens(1, 2, 44)
    got, routing = _step_logits(runner, tokens, 32)
    want, scores = ref.logits_at(params, tokens, list(range(31, 43)),
                                 config.reference_sizes())
    assert _rel(got, want) < TOL
    assert routing.shape == (4, 2, 44, 4)
    np.testing.assert_array_equal(
        np.sort(routing, -1),
        np.sort(np.argsort(-scores, -1, kind="stable")[..., :4], -1))
    followed, _ = ref.logits_at(params, tokens, list(range(31, 43)),
                                config.reference_sizes(), routing)
    assert _rel(followed, want) < 1e-6


def test_the_state_stays_float32_under_bfloat16_weights(km):
    """The served precision: bfloat16 weights and latent rows; float32 S and
    convolution tails (the CPU has no bfloat16 ragged product, so the chip's
    check reads this precision: PERF.md section 6)."""
    import jax
    import jax.numpy as jnp

    config = km.KimiLinearConfig.tiny(dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: km.init_params(config, jax.random.key(0)))
    kept32 = {"A_log", "dt_bias", "b_g", "conv_w", "router_bias"}
    for kind, layer in params["layers"].items():
        assert {k for k, a in layer.items()
                if a.dtype == jnp.float32} == kept32 & set(layer), kind
    arrays = config.serving_block().cache_arrays({"all": 8, "state": 4}, 4)
    assert {a.name: (str(jnp.dtype(a.dtype)), a.shape) for a in arrays} == {
        "latent": ("bfloat16", (2, 8, 4, 128)),
        "kda_state": ("float32", (3, 5, 4, 16, 16)),
        "kda_rows": ("float32", (3, 5, 1, 32, 48)),
        "kda_fill": ("int32", (3, 5)),
        "kda_tail": ("float32", (3, 5, 1, 3 * 192))}


def _mixed_logits(runner, tokens, spans):
    """One `step_mixed_logits` launch a round: `spans` [[(row, start, n)]],
    each sequence's rows token-major in the order given, pages and a slot a
    row of `tokens`. -> {(row, position): logits} of every span's last
    token."""
    S = runner.batch_bucket(runner.max_batch)
    pages = -(-tokens.shape[1] // runner.block_size)
    out = {}
    for spans_now in spans:
        T = sum(n for _, _, n in spans_now)
        flat = np.zeros(-(-T // 8) * 8, np.int32)
        cu = np.zeros(S + 1, np.int32)
        q_pos, kv = np.zeros(S, np.int32), np.zeros(S, np.int32)
        tables = runner.zero_tables(S)
        rows_out = np.zeros(S, np.int32)
        at = 0
        for i, (row, start, n) in enumerate(spans_now):
            flat[at:at + n] = tokens[row, start:start + n]
            cu[i], cu[i + 1] = at, at + n
            q_pos[i], kv[i] = start, start + n
            tables["all"][i, :pages] = 5 + row * pages + np.arange(pages)
            tables["state"][i, 0] = row + 2      # not the row's own number
            rows_out[i] = at + n - 1
            at += n
        cu[len(spans_now) + 1:] = at
        logits = np.asarray(runner.step_mixed_logits(
            flat, q_pos, kv, cu, tables, rows_out))
        for i, (row, start, n) in enumerate(spans_now):
            out[row, start + n - 1] = logits[i]
    return out


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_ragged_mixed_steps_match_the_reference(km, ref, impl):
    """Token-major launches that hold a slice from position 0, a slice that
    continues mid-sequence, and decode rows, of three sequences of unequal
    length that join and leave: ONE KDA call a layer carries all of them."""
    config, params, runner = _runner(km, impl=impl)
    tokens = _tokens(4, 3, 40)
    spans = [[(0, 0, 16)],
             [(0, 16, 9), (1, 0, 13)],
             [(0, 25, 1), (1, 13, 16), (2, 0, 5)],
             [(0, 26, 1), (1, 29, 1), (2, 5, 16)],
             [(1, 30, 1), (2, 21, 1)],
             [(2, 22, 1)]]
    # (the interpreted kernels compile slowly: two shapes of launch there)
    got = _mixed_logits(runner, tokens, spans[:4] if impl == "pallas"
                        else spans)
    for row in range(3):
        positions = sorted(p for r, p in got if r == row)
        want, _ = ref.logits_at(params, tokens[row:row + 1], positions,
                                config.reference_sizes())
        have = np.stack([got[row, p] for p in positions])[None]
        assert _rel(have, want) < TOL, row


# ---- through the engine and the server --------------------------------------

def test_engine_matches_the_reference_as_sequences_join_and_leave(km, ref):
    """Mixed ticks with one step of lookahead: six requests of unequal
    lengths through four rows; every greedy token is the reference's, and the
    records count what the KDA calls, the latent kernel and the held experts
    carried."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(km)
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
    stats = engine.stats()
    assert stats["lookahead_ticks"] > 10
    ticks = [t for t in engine.tick_records() if t["kda_rows"]]
    assert all(t["kda_rows"] == t["used"] for t in ticks)
    assert all(t["kda_seqs"] == t["prefill_rows"] + t["decode_rows"]
               for t in ticks)
    assert all("ssm_rows" not in t and "retention_rows" not in t
               for t in ticks)
    # the latent layers' walk: a row of one token is one query block over
    # its context's pages
    assert all(t["q_blocks"] >= t["kda_seqs"] and t["kv_pages_walked"] > 0
               and t["kv_tokens"] > 0 and t["attn_pairs"] >= t["used"]
               for t in ticks)
    assert all(t["routed_rows"] == 4 * 4 * t["used"] for t in ticks)
    assert any(t["prefill_rows"] and t["decode_rows"] for t in ticks)
    assert stats["kda_rows"] == sum(t["kda_rows"] for t in ticks)
    assert stats["kda_seqs"] == sum(t["kda_seqs"] for t in ticks)
    records = engine.tick_records()       # it holds all 16 experts
    assert (sum(t.get("expert_rows", 0) for t in records)
            == sum(t["routed_rows"] for t in records) > 0)
    assert "ssm_rows" not in stats


def test_the_server_serves_through_both_caches(km, ref):
    """`LLMServer` (the replica's loop, warm-up, streams): a prompt of three
    slices and a decode run, greedy, is the reference's at every position;
    served again it restores the slot AND the page chain."""
    import jax.numpy as jnp

    from ray_tpu.llm.serving import LLMConfig, LLMServer

    config = km.KimiLinearConfig.tiny()
    server = LLMServer(LLMConfig(
        model_config=config, seed=5, num_kv_blocks=64, block_size=4,
        max_batch_size=4, prefill_chunk=16, warmup_buckets="light",
        stream_timeout_s=120.0))
    try:
        params = server.engine.runner.params
        assert params["embed"].dtype == jnp.float32
        prompt = np.random.default_rng(6).integers(1, 256, 45).tolist()
        request = {"prompt": prompt, "max_tokens": 10}
        out = [server.completions({**request, "request_id": f"s{i}"})[
            "choices"][0]["token_ids"] for i in range(2)]
        assert out[0] == out[1]
        assert _greedy_miss(ref, params, config.reference_sizes(), prompt,
                            out[0]) <= NEAR
        stats = server.engine_stats()
        assert stats["prefix_hits"] == 1 and stats["state_restores"] == 1
        assert stats["prefix_tokens_saved"] == 44
        assert server.engine.host_prefix_tier is None   # a slot cannot travel
    finally:
        server._handoff.close()


def test_a_prefix_hit_restores_slot_and_pages_and_an_eviction_frees_both(
        km, ref):
    """A prompt served twice: the second run attaches the page chain of the
    latent pool AND restores the snapshot taken where the first's prefill
    crossed its last whole page, and emits the uncached run's tokens, the
    reference's. Then the pool is filled: the parked pages are recycled,
    their snapshot's slot is freed with them, and the prompt is a miss that
    still emits the same tokens."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(km, num_blocks=40)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 256, 47).tolist()
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    cold = engine.generate([prompt], sp)[0].output_token_ids
    assert cold == _reference_greedy(ref, params, config.reference_sizes(),
                                     prompt, cold)
    stats = engine.stats()
    assert stats["state_snapshots"] == 1 and stats["state_restores"] == 0
    assert stats["kv_groups"]["state"] == {
        "total": 8, "free": 7, "live": 0, "parked": 1}
    assert stats["kv_groups"]["all"]["parked"] == 11
    slices = [t["prefill_tokens"] for t in engine.tick_records()
              if t["prefill_tokens"]]
    assert slices == [16, 16, 12, 3]        # cut at the boundary, 44
    warm = engine.generate([prompt], sp)[0].output_token_ids
    assert warm == cold
    stats = engine.stats()
    assert stats["prefix_hits"] == 1 and stats["state_restores"] == 1
    assert stats["prefix_tokens_saved"] == 44
    assert stats["prefix_hits_cut_short"] == 0
    # three unshared prompts of 48 + 6 tokens need 14 pages each: 42 > 40
    # less the live ones, so every parked page of `prompt` is recycled
    others = [rng.integers(1, 256, 48).tolist() for _ in range(3)]
    engine.generate(others, SamplingParams(max_tokens=6, temperature=0.0))
    groups = engine.stats()["kv_groups"]
    bm = engine.block_manager
    gone = [h for h in bm.prefix_hashes(prompt) if h not in bm.cached]
    assert gone and all(h not in bm.states.parked for h in gone)
    assert groups["state"]["live"] == 0
    assert groups["state"]["free"] + groups["state"]["parked"] == 8
    hits = engine.stats()["prefix_hits"]
    again = engine.generate([prompt], sp)[0].output_token_ids
    assert again == cold
    assert engine.stats()["prefix_tokens_saved"] - 44 * hits < 44


def test_requests_that_decode_past_two_folds_match_the_reference_path(
        km, ref):
    """Through the engine with the interpreted kernel, greedy tokens of
    requests that decode 19 rows (two folds of 8 and three rows more) are the
    plain reference's, once uncached and once restored from a snapshot
    (`copy_state` carries the buffer and its fill with S), and the device's
    fill afterwards is `fill_after`'s over the rows each request brought.
    (The `lax.scan` path crosses a fold in the test below.)"""
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.ops import kda
    from ray_tpu.ops.state_slots import fill_after

    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, 256, n).tolist() for n in (47, 10)]
    sp = SamplingParams(max_tokens=20, temperature=0.0)
    config, params, engine = _engine(km, impl="pallas", num_blocks=64)
    assert fill_after(kda.FOLD - 1, 1, False, kda.FOLD) == (0, True)
    cold = [o.output_token_ids for o in engine.generate(prompts, sp)]
    ticks = engine.tick_records()
    assert all("kda_seqs" in t for t in ticks)
    stats = engine.stats()
    assert stats["state_snapshots"] == 2 and stats["state_restores"] == 0
    warm = [o.output_token_ids for o in engine.generate(prompts, sp)]
    stats = engine.stats()
    assert stats["state_restores"] == 2 and warm == cold
    # the device's fill after the drain: a prompt's slices leave a buffer
    # empty, a request's 19 decode rows leave what `fill_after` says, in
    # every layer of every slot a request held (a snapshot's holds none)
    want = 0
    for _ in range(20 - 1):
        want, _ = fill_after(want, 1, False, kda.FOLD)
    fill = np.asarray(engine.runner.cache["kda_fill"])[:, :-1]
    live = [s for s in range(fill.shape[1]) if fill[:, s].any()]
    assert len(live) >= 2 and (fill[:, live] == want).all()
    for prompt, out in zip(prompts, cold):
        assert out == _reference_greedy(ref, params, config.reference_sizes(),
                                        prompt, out)


def test_a_snapshot_among_a_sequences_rows_carries_its_buffer(km):
    """A snapshot taken by `copy_state` while a slot's buffer holds rows (5
    decode steps after a prefill), restored into the slot after it has
    decoded on past a fold: the copy decodes the logits the sequence it was
    parked from did (the `lax.scan` path: tests/test_kda.py cuts the
    kernel's run the same way)."""
    from ray_tpu.ops import kda

    _, _, runner = _runner(km)
    tokens = _tokens(4, 2, 40)
    pages = 40 // runner.block_size
    tables = np.zeros((2, runner.max_blocks_per_seq), np.int32)
    for i in range(2):
        tables[i, :pages] = i * pages + np.arange(pages)
    full = lambda v: np.full(2, v, np.int32)

    def step(pos, n):
        tok = np.zeros((2, 16 if n > 1 else 1), np.int32)
        tok[:, :n] = tokens[:, pos:pos + n]
        return np.asarray(runner.step(tok, full(pos), full(pos + n), full(n),
                                      tables))

    step(0, 16)
    for pos in range(16, 21):
        step(pos, 1)
    assert np.asarray(runner.cache["kda_fill"])[:, :2].tolist() == [[5, 5]] * 3
    state = np.asarray(runner.cache["kda_state"])[:, 0].copy()
    runner.copy_state(0, 5)                     # parked among its rows
    first = [step(pos, 1) for pos in range(21, 33)]     # past a fold
    assert np.asarray(runner.cache["kda_fill"])[0, 0] == (5 + 12) % kda.FOLD
    assert not np.array_equal(np.asarray(runner.cache["kda_state"])[:, 0],
                              state)
    runner.copy_state(5, 0)
    assert np.asarray(runner.cache["kda_fill"])[:, 0].tolist() == [5, 5, 5]
    assert np.array_equal(np.asarray(runner.cache["kda_state"])[:, 0], state)
    again = [step(pos, 1) for pos in range(21, 33)]
    for a, b in zip(first, again):
        assert _rel(b[0], a[0]) < TOL


def test_a_hit_is_reported_only_where_pages_and_slot_are_both_there(km):
    """The page chain whole but the snapshot gone (its slot was taken for
    another prompt's): the hit is cut short to nothing, counted as such, and
    no page is attached."""
    from ray_tpu.llm.sampling import SamplingParams

    _, _, engine = _engine(km, num_blocks=64)
    prompt = np.random.default_rng(9).integers(1, 256, 30).tolist()
    sp = SamplingParams(max_tokens=3, temperature=0.0)
    cold = engine.generate([prompt], sp)[0].output_token_ids
    bm = engine.block_manager
    assert len(bm.states.parked) == 1
    bm.states.forget()                       # the slots ran out, oldest first
    warm = engine.generate([prompt], sp)[0].output_token_ids
    stats = engine.stats()
    assert warm == cold
    assert stats["prefix_hits"] == 0 and stats["prefix_hits_cut_short"] == 1
    assert stats["prefix_tokens_saved"] == 0 and stats["state_restores"] == 0


@pytest.mark.parametrize("how", ["abort", "drop_all"])
def test_no_slot_and_no_page_leaks(km, how):
    from ray_tpu.llm.sampling import SamplingParams

    _, _, engine = _engine(km)
    rng = np.random.default_rng(2)
    ids = [engine.add_request(rng.integers(1, 256, 20).tolist(),
                              SamplingParams(max_tokens=6, temperature=0.0))
           for _ in range(3)]
    for _ in range(3):
        engine.step()
    groups = engine.stats()["kv_groups"]
    assert groups["state"]["live"] == 3 and groups["all"]["live"] >= 15
    if how == "abort":
        for rid in ids:
            assert engine.abort_request(rid)
    else:
        engine.drop_all()
    groups = engine.stats()["kv_groups"]
    state = groups["state"]
    assert state["live"] == groups["all"]["live"] == 0
    assert state["free"] + state["parked"] == state["total"] == 8


def test_what_the_block_cannot_do_refuses_by_name(km):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(km)
    runner = engine.runner
    engine.add_request(list(range(1, 12)),
                       SamplingParams(max_tokens=4, temperature=0.0), "r")
    for _ in range(3):
        engine.step()
    # the pages have bytes and a wire view; it is the SLOT that cannot travel
    refused = (r"layer groups \['all', 'state'\].*the 'state' group's slots "
               "do not travel")
    with pytest.raises(ValueError, match="export_request.*" + refused):
        engine.export_request("r")
    with pytest.raises(ValueError, match="gather_pages.*" + refused):
        runner.gather_pages([0])
    assert engine.export_prefixes() is None
    with pytest.raises(ValueError, match="speculative_ngram.*state group"):
        LLMEngine(runner, max_batch_size=4, speculative_ngram=2)
    with pytest.raises(ValueError, match="layer group 'state': 8 slots"):
        LLMEngine(runner, max_batch_size=8)
    with pytest.raises(ValueError, match="kimi_linear: tensor_parallel > 1 "
                                         "is not supported"):
        runner.block.refuse(tensor_parallel=2, lora=False)
    with pytest.raises(ValueError, match="kimi_linear: LoRA"):
        runner.block.refuse(tensor_parallel=1, lora=True)


# ---- the shares add up --------------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer(km, ref):
    """Programs holding two experts each of a tiny layer's 16, given the same
    rows: their routed parts summed and the shared expert counted ONCE (every
    share routes over all 16 and renormalises over all 4 kept, held or not)
    equal the uncut reference's layer."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.expert_share import _dot32, _ffn, held_expert_ffn

    rng = np.random.default_rng(4)
    whole = km.KimiLinearConfig.tiny()
    d, f = whole.hidden_size, whole.moe_intermediate_size
    draw = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[-2]), jnp.float32)
    experts = {"w_gate": draw(16, d, f), "w_up": draw(16, d, f),
               "w_down": draw(16, f, d)}
    p = {"router": draw(d, 16),
         "router_bias": jnp.asarray(rng.uniform(0, 0.2, 16), jnp.float32),
         "shared_gate": draw(d, f), "shared_up": draw(d, f),
         "shared_down": draw(f, d)}
    x = jnp.asarray(rng.standard_normal((24, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ p["router"])
        want, choice = ref._routed(x, p, experts, whole.reference_sizes())
        ids, gates = km.route_one_group(whole, scores, p["router_bias"])
        np.testing.assert_array_equal(
            np.sort(np.asarray(ids), -1),
            np.sort(np.argsort(-np.asarray(choice), -1,
                               kind="stable")[:, :4], -1))
        total = np.asarray(_ffn(_dot32, x, p["shared_gate"], p["shared_up"],
                                p["shared_down"]), np.float64)
        rows = 0
        for first in range(0, 16, 2):
            share = km.KimiLinearConfig.tiny(experts_held=(first, first + 2))
            lp = {k: v[first:first + 2] for k, v in experts.items()}
            y, (n, *_) = held_expert_ffn(
                share, x, ids, gates * whole.routed_scaling_factor,
                jnp.ones(24, bool), lp)
            total = total + np.asarray(y, np.float64)
            rows += int(n)
    assert rows == 24 * whole.num_experts_per_token    # every pick, once
    np.testing.assert_allclose(total, np.asarray(want), rtol=1e-4, atol=1e-5)


# ---- controls: each MUST fail the comparison --------------------------------

@pytest.mark.parametrize("fault", [
    ("state_not_carried", STEPS), "beta_one", "gate_a_head", "no_delta",
    "no_nope_lanes"], ids=lambda f: f if isinstance(f, str) else f[0])
def test_a_reference_with_one_term_dropped_is_told_apart(km, ref, fault):
    config, params, runner = _runner(km)
    tokens = _tokens(2, 2, 44)
    got, routing = _step_logits(runner, tokens, 32)
    positions = list(range(31, 43))
    sizes = config.reference_sizes()
    sound, _ = ref.logits_at(params, tokens, positions, sizes, routing)
    assert _rel(got, sound) < TOL
    faulty, _ = ref.logits_at(params, tokens, positions, sizes, routing,
                              fault)
    assert _rel(got, faulty) > 1e-2


def test_a_program_that_drops_its_state_between_steps_fails(km, ref):
    """The control on the program's side: a runner whose state group (S and
    the convolution's tails) is zeroed after every step reads what the
    reference reads with the state not carried, and not what the sound
    reference reads."""
    import jax.numpy as jnp

    def zeroed(runner):
        runner.cache = {k: v if k == "latent" else jnp.zeros_like(v)
                        for k, v in runner.cache.items()}

    config, params, runner = _runner(km)
    tokens = _tokens(2, 2, 44)
    got, routing = _step_logits(runner, tokens, 32, after_step=zeroed)
    positions = list(range(31, 43))
    sizes = config.reference_sizes()
    sound, _ = ref.logits_at(params, tokens, positions, sizes, routing)
    faulty, _ = ref.logits_at(params, tokens, positions, sizes, routing,
                              ("state_not_carried", STEPS))
    assert _rel(got, sound) > 1e-2
    assert _rel(got, faulty) < TOL


def test_a_program_whose_state_is_bfloat16_fails_the_tolerance(km, ref):
    """The control that shows the tolerance tells the stated precision: the
    steps that read under 2e-5 with the float32 state read over 1e-4 with S
    and the rows buffered beside it rounded to bfloat16 after each."""
    import jax

    def rounded(runner):
        runner.cache = {k: jax.lax.reduce_precision(
            v, exponent_bits=8, mantissa_bits=7)
            if k in ("kda_state", "kda_rows") else v
            for k, v in runner.cache.items()}

    config, params, runner = _runner(km)
    tokens = _tokens(2, 2, 44)
    got, routing = _step_logits(runner, tokens, 32, after_step=rounded)
    sound, _ = ref.logits_at(params, tokens, list(range(31, 43)),
                             config.reference_sizes(), routing)
    assert _rel(got, sound) > 1e-4
