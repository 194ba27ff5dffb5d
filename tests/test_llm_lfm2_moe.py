"""LFM2-24B-A2B (models/lfm2_moe.py) against its plain reference, at tiny
sizes on the CPU in float32 with seeded weights: gated short-convolution
layers whose whole state is a two-row tail in a SLOT, beside GQA layers at a
head width of 64 in the pair form by runs (a paged K/V row pool of kv PAIRS),
a dense and an expert feed-forward, through `ModelRunner.step`, ragged mixed
launches, `LLMEngine` and `LLMServer`.

Six layers (conv_dense, attn_dense, conv_moe, conv_moe, attn_moe, conv_moe),
8 query / 4 kv heads of 64 (two pairs, runs of two), 16 experts at top-4;
pages of 4, slices of up to 16.

Tolerance: program and reference are both float32 here and differ in the
order of their sums (paged online softmax against a dense one, sorted ragged
products against an expert at a time, the FIR's three terms): logits and
every mixer's output agree to ~1e-6 of their largest value; 1e-5 leaves a
factor of eight. Every control below reads over 1e-2.
"""

import os

import numpy as np
import pytest

import ray_tpu  # noqa: F401

TOL = 1e-5
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def lm(cpu_jax):
    from ray_tpu.models import lfm2_moe

    return lfm2_moe


@pytest.fixture(scope="module")
def ref(cpu_jax):
    from ray_tpu.models import lfm2_moe_reference

    return lfm2_moe_reference


def _runner(lm, config=None, impl="reference", seed=0, num_blocks=96,
            max_batch=4):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner

    config = config or lm.Lfm2MoeConfig.tiny()
    params = lm.init_params(config, jax.random.key(seed))
    return config, params, ModelRunner(
        config, params, num_blocks=num_blocks, block_size=4,
        attention_impl=impl, chunk_size=16, max_batch=max_batch)


def _engine(lm, impl="reference", max_batch=4, num_blocks=64, **kw):
    from ray_tpu.llm.engine import LLMEngine

    config, params, runner = _runner(lm, impl=impl, num_blocks=num_blocks,
                                     max_batch=max_batch)
    return config, params, LLMEngine(runner, max_batch_size=max_batch,
                                     prefill_chunk=16, **kw)


def _tokens(seed, rows, n):
    return np.random.default_rng(seed).integers(1, 256, (rows, n)).astype(
        np.int32)


def _served(runner, tokens, slices, after_step=None):
    """tokens (rows, total) through `ModelRunner.step` given ONE table, the
    `all` group's (the runner lays the slots itself), as the benchmark's
    check drives it: slices of the lengths `slices` (each padded to the chunk:
    one program), then a token at a time. -> (logits after every step, at
    `positions`; the routing and every mixer's output of every position)."""
    rows, total = tokens.shape
    pages = -(-total // runner.block_size)
    tables = np.zeros((rows, runner.max_blocks_per_seq), np.int32)
    for i in range(rows):
        tables[i, :pages] = runner.num_blocks - 1 - i * pages - np.arange(
            pages)
    full = lambda v: np.full(rows, v, np.int32)
    got, positions, routing, mixed = [], [], [], []
    start = 0
    for n, bq in ([(n, runner.chunk_size) for n in slices]
                  + [(1, 1)] * (total - sum(slices))):
        padded = np.zeros((rows, bq), np.int32)
        padded[:, :n] = tokens[:, start:start + n]
        got.append(np.asarray(runner.step(
            padded, full(start), full(start + n), full(n), tables)))
        routing.append(np.asarray(runner.last_routing)[:, :, :n])
        mixed.append(np.asarray(runner.last_layer_outputs["mixed"])[:, :, :n])
        if after_step is not None:
            after_step(runner)
        start += n
        positions.append(start - 1)
    return (np.stack(got, axis=1), positions, np.concatenate(routing, axis=2),
            np.concatenate(mixed, axis=2))


def _reference_greedy(ref, params, sizes, prompt, output):
    """The reference's greedy choice after prompt + output[:i] for every i,
    by ONE forward pass over the engine's own tokens."""
    tokens = list(prompt) + list(output[:-1])
    positions = list(range(len(prompt) - 1, len(tokens)))
    logits, _ = ref.logits_at(params, np.asarray([tokens], np.int32),
                              positions, sizes)
    return np.argmax(np.asarray(logits)[0], axis=-1).tolist()


# ---- the files and the counts -----------------------------------------------

def test_the_reference_is_in_the_repo_twice_and_equal():
    with open(os.path.join(HERE, "ray_tpu", "models",
                           "lfm2_moe_reference.py")) as f:
        program = f.read()
    with open(os.path.join(HERE, "benchmarks", "lfm2_moe_reference.py")) as f:
        assert f.read() == program
    assert "ray_tpu" not in program.split('"""')[2]     # imports nothing


def test_the_published_layout_counts_the_models_parameters(lm):
    """The published model whole (its name: 24B, 2B a token), and the
    benchmark's cut (published layers 1-9, every expert, the whole
    vocabulary), by hand."""
    whole = lm.Lfm2MoeConfig()
    assert whole.conv_params() == 4 * 2048 * 2048 + 3 * 2048 == 16_783_360
    assert whole.attention_params() == 2 * 2048 * 64 * 40 == 10_485_760
    assert whole.expert_params() == 9_437_184
    kinds = whole.layer_kinds()
    assert [kinds.count(k) for k in ("conv_dense", "conv_moe", "attn_moe")
            ] == [2, 28, 10]
    assert [li for li, k in enumerate(whole.layer_types)
            if k == "full_attention"] == list(range(2, 40, 4))
    assert 23.8e9 < whole.num_params() < 23.9e9
    cut = lm.Lfm2MoeConfig(layer_types=whole.layer_types[1:10],
                           num_dense_layers=1, max_position_embeddings=8192)
    assert cut.layer_kinds() == ["conv_dense", "attn_moe"] + [
        "conv_moe"] * 3 + ["attn_moe"] + ["conv_moe"] * 3
    routed = 2048 * 64 + 64 * 9_437_184
    by_hand = (65536 * 2048 + 7 * 16_783_360 + 2 * 10_485_760
               + 3 * 2048 * 11776 + 8 * routed)
    assert cut.num_params() == by_hand
    assert round(by_hand / 1e5) == 51779         # 5,177.9 M
    assert cut.state_bytes_per_sequence == 7 * 2 * 2048 * 2 == 57_344


def test_the_tail_is_the_state_groups_only_array(lm):
    """The served precision: bfloat16 weights, K/V rows AND tail; the pool a
    row of kv pairs, nothing padded; float32 only the selection bias."""
    import jax
    import jax.numpy as jnp

    config = lm.Lfm2MoeConfig.tiny(dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: lm.init_params(config, jax.random.key(0)))
    assert "lm_head" not in params              # tied
    for kind, layer in params["layers"].items():
        assert {k for k, a in layer.items() if a.dtype == jnp.float32} == (
            {"router_bias"} & set(layer)), kind
    arrays = config.serving_block().cache_arrays({"all": 8, "state": 4}, 4)
    assert {a.name: (a.group, str(jnp.dtype(a.dtype)), a.shape)
            for a in arrays} == {
        "k_all": ("all", "bfloat16", (2, 8, 4, 4 * 64)),
        "v_all": ("all", "bfloat16", (2, 8, 4, 4 * 64)),
        "conv_tail": ("state", "bfloat16", (4, 5, 1, 2 * 64))}
    block = lm.Lfm2MoeConfig().serving_block()
    assert block.tail_tile == (32, 128) and block.run == 4
    assert block.kv_kernels(16)["all"].rows
    assert block.pallas_ok() and block.state_fields == ("conv_rows",
                                                        "conv_seqs")


# ---- (a) through the runner --------------------------------------------------

@pytest.mark.parametrize("impl,cut", [("reference", 0), ("reference", 1),
                                      ("reference", 2), ("reference", 3),
                                      ("pallas", 1)])
def test_slices_of_every_length_then_decode_match_the_reference(
        lm, ref, impl, cut):
    """Prefill in slices of EVERY length 1 .. 16 (after a first slice of `cut`
    tokens: the slices' borders fall at every offset of a page and the tail
    crosses each), then six decode rows, through the K/V pair pool's pages and
    the state group's slots: the LOGITS after every step and EVERY MIXER'S
    OUTPUT at every position are the reference's full forward pass's, the
    reference routing for itself (in float32 no choice differs)."""
    config, params, runner = _runner(lm, impl=impl)
    slices = ([cut] if cut else []) + list(range(1, 17))
    tokens = _tokens(1 + cut, 2, sum(slices) + 6)
    got, positions, routing, mixed = _served(runner, tokens, slices)
    want, found = ref.logits_at(params, tokens, positions,
                                config.reference_sizes(),
                                watch=list(range(tokens.shape[1])))
    assert _rel(got, want) < TOL
    assert mixed.shape == found["mixed"].shape == (6, 2, tokens.shape[1], 64)
    for layer in range(6):
        assert _rel(mixed[layer], found["mixed"][layer]) < TOL, layer
    assert routing.shape == (4, 2, tokens.shape[1], 4)
    np.testing.assert_array_equal(
        np.sort(routing, -1),
        np.sort(np.argsort(-found["scores"], -1, kind="stable")[..., :4], -1))


# ---- (b) mixed ticks ---------------------------------------------------------

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
def test_a_mixed_tick_gives_each_row_what_it_gets_alone(lm, ref, impl):
    """Token-major launches that hold a slice from position 0, slices that
    continue mid-sequence at odd offsets, decode rows and a padding sequence
    (three sequences in a bucket of four), of sequences that join and leave:
    every row reads what the reference reads of its sequence ALONE, and a
    padding sequence's slot (0: it holds no row) stays as it was."""
    config, params, runner = _runner(lm, impl=impl)
    tokens = _tokens(4, 3, 40)
    spans = [[(0, 0, 16)],
             [(0, 16, 9), (1, 0, 13)],
             [(0, 25, 1), (1, 13, 16), (2, 0, 5)],
             [(0, 26, 1), (1, 29, 1), (2, 5, 16)],
             [(1, 30, 1), (2, 21, 1)],
             [(2, 22, 1)]]
    got = _mixed_logits(runner, tokens, spans[:4] if impl == "pallas"
                        else spans)
    for row in range(3):
        positions = sorted(p for r, p in got if r == row)
        want, _ = ref.logits_at(params, tokens[row:row + 1], positions,
                                config.reference_sizes())
        have = np.stack([got[row, p] for p in positions])[None]
        assert _rel(have, want) < TOL, row
    tail = np.asarray(runner.cache["conv_tail"])
    assert not tail[:, 0].any() and not tail[:, 1].any()
    assert tail[:, 2].any() and tail[:, 4].any()


# ---- through the engine and the server ---------------------------------------

def test_engine_matches_the_reference_as_sequences_join_and_leave(lm, ref):
    """Mixed ticks with one step of lookahead: six requests of unequal
    lengths through four rows; every greedy token is the reference's, and the
    records count what the conv mixers, the K/V kernel and the held experts
    carried, under the block's own names."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(lm)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 256, n).tolist()
               for n in (37, 9, 22, 41, 5, 30)]
    ids = [engine.add_request(p, SamplingParams(
        max_tokens=6 + 3 * (i % 3), temperature=0.0))
        for i, p in enumerate(prompts)]
    done = {}
    while engine.has_unfinished():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out
    sizes = config.reference_sizes()
    for rid, prompt in zip(ids, prompts):
        out = done[rid].output_token_ids
        assert out == _reference_greedy(ref, params, sizes, prompt, out)
    stats = engine.stats()
    assert stats["lookahead_ticks"] > 10
    assert stats["kv_kernels"]["all"]["layout"] == "rows"
    ticks = [t for t in engine.tick_records() if t["conv_rows"]]
    assert all(t["conv_rows"] == t["used"] for t in ticks)
    assert all(t["conv_seqs"] == t["prefill_rows"] + t["decode_rows"]
               for t in ticks)
    assert all("ssm_rows" not in t and "ssd_rows" not in t for t in ticks)
    assert all(t["q_blocks"] >= t["conv_seqs"] and t["kv_pages_walked"] > 0
               for t in ticks)
    assert all(t["routed_rows"] == 4 * 4 * t["used"] for t in ticks)
    assert any(t["prefill_rows"] and t["decode_rows"] for t in ticks)
    assert stats["conv_rows"] == sum(t["conv_rows"] for t in ticks)
    assert stats["conv_seqs"] == sum(t["conv_seqs"] for t in ticks)
    records = engine.tick_records()       # it holds all 16 experts
    assert (sum(t.get("expert_rows", 0) for t in records)
            == sum(t["routed_rows"] for t in records) > 0)
    assert all(t.get("experts_met", 0) <= 4 * 16 for t in records)


def test_the_server_serves_through_both_caches(lm, ref):
    """`LLMServer` on the normal path (the replica's loop, warm-up, streams):
    a prompt of three slices and a decode run, greedy, is the reference's at
    every position; served again it restores the slot AND the page chain."""
    from ray_tpu.llm.serving import LLMConfig, LLMServer

    config = lm.Lfm2MoeConfig.tiny()
    server = LLMServer(LLMConfig(
        model_config=config, seed=5, num_kv_blocks=64, block_size=4,
        max_batch_size=4, prefill_chunk=16, warmup_buckets="light",
        stream_timeout_s=120.0))
    try:
        params = server.engine.runner.params
        prompt = np.random.default_rng(6).integers(1, 256, 45).tolist()
        request = {"prompt": prompt, "max_tokens": 10}
        out = [server.completions({**request, "request_id": f"s{i}"})[
            "choices"][0]["token_ids"] for i in range(2)]
        assert out[0] == out[1] == _reference_greedy(
            ref, params, config.reference_sizes(), prompt, out[0])
        stats = server.engine_stats()
        assert stats["prefix_hits"] == 1 and stats["state_restores"] == 1
        assert stats["prefix_tokens_saved"] == 44
        assert stats["conv_rows"] > 0 and stats["conv_seqs"] > 0
        assert server.engine.host_prefix_tier is None   # a slot cannot travel
    finally:
        server._handoff.close()


# ---- (c) the prefix cache over pages AND parked tails -------------------------

def test_a_prefix_hit_restores_the_parked_tail_and_an_eviction_frees_it(
        lm, ref):
    """A prompt served twice: the second run attaches the page chain of the
    K/V pool AND restores the tail parked where the first's prefill crossed
    its last whole page (its slice was cut there), and emits its own cold
    run's tokens, the reference's. A prompt that shares 30 tokens of it finds
    pages and no snapshot at that depth: cut short. Then the pool is filled:
    the parked pages are recycled, their snapshot's slot is freed with them,
    and the prompt, admitted again, is a miss that still emits the same
    tokens."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(lm, num_blocks=40)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 256, 47).tolist()
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    sizes = config.reference_sizes()
    cold = engine.generate([prompt], sp)[0].output_token_ids
    assert cold == _reference_greedy(ref, params, sizes, prompt, cold)
    stats = engine.stats()
    assert stats["state_snapshots"] == 1 and stats["state_restores"] == 0
    assert stats["kv_groups"]["state"] == {
        "total": 8, "free": 7, "live": 0, "parked": 1}
    slices = [t["prefill_tokens"] for t in engine.tick_records()
              if t["prefill_tokens"]]
    assert slices == [16, 16, 12, 3]        # cut at the boundary, 44
    # the parked slot holds g at positions 42 and 43 and not a later row
    parked = next(iter(engine.block_manager.states.parked.values()))
    tail = np.asarray(engine.runner.cache["conv_tail"])[:, parked]
    assert tail.any()
    warm = engine.generate([prompt], sp)[0].output_token_ids
    assert warm == cold
    stats = engine.stats()
    assert stats["prefix_hits"] == 1 and stats["state_restores"] == 1
    assert stats["prefix_tokens_saved"] == 44
    assert stats["prefix_hits_cut_short"] == 0
    np.testing.assert_array_equal(
        np.asarray(engine.runner.cache["conv_tail"])[:, parked], tail)

    shorter = prompt[:30] + rng.integers(1, 256, 9).tolist()
    out = engine.generate([shorter], sp)[0].output_token_ids
    assert out == _reference_greedy(ref, params, sizes, shorter, out)
    stats = engine.stats()
    assert stats["prefix_hits"] == 1 and stats["prefix_hits_cut_short"] == 1
    assert stats["prefix_tokens_saved"] == 44

    # unshared prompts of 48 + 6 tokens need 14 pages each: every parked page
    # of `prompt` is recycled
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


def test_a_hit_whose_snapshot_was_recycled_is_cut_short(lm):
    """The page chain whole but the parked tail gone (its slot was taken for
    another prompt's): the hit is cut short to nothing, counted as such, no
    page is attached, and the tokens are the cold run's."""
    from ray_tpu.llm.sampling import SamplingParams

    _, _, engine = _engine(lm, num_blocks=64)
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


def test_the_cache_on_and_off_give_one_stream(lm):
    from ray_tpu.llm.sampling import SamplingParams

    prompt = np.random.default_rng(21).integers(1, 256, 45).tolist()
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    streams = []
    for cached in (True, False):
        _, _, engine = _engine(lm, enable_prefix_caching=cached)
        runs = [engine.generate([prompt], sp)[0].output_token_ids
                for _ in range(2)]
        assert engine.stats()["prefix_hits"] == (1 if cached else 0)
        streams.append(runs)
    assert streams[0][0] == streams[0][1] == streams[1][0] == streams[1][1]


# ---- (d) nothing leaks -------------------------------------------------------

@pytest.mark.parametrize("how", ["abort", "drop_all", "finish", "evict"])
def test_no_slot_and_no_page_leaks(lm, how):
    """Three requests admitted and stepped, then aborted, dropped, run to
    their end, or run to their end under a pool so small that parked pages are
    recycled for live ones: no slot and no page stays live, and every slot is
    free or parked."""
    from ray_tpu.llm.sampling import SamplingParams

    _, _, engine = _engine(lm, num_blocks=24 if how == "evict" else 64)
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
    elif how == "drop_all":
        engine.drop_all()
    else:
        while engine.has_unfinished():
            engine.step()
        if how == "evict":      # three more: the first three's pages go
            engine.generate([rng.integers(1, 256, 20).tolist()
                             for _ in range(3)],
                            SamplingParams(max_tokens=6, temperature=0.0))
    groups = engine.stats()["kv_groups"]
    state = groups["state"]
    assert state["live"] == groups["all"]["live"] == 0
    assert state["free"] + state["parked"] == state["total"] == 8
    assert groups["all"]["free"] + groups["all"]["parked"] == (
        groups["all"]["total"])
    parked = engine.block_manager.states.parked
    assert all(h in engine.block_manager.cached for h in parked)


def test_what_the_block_cannot_do_refuses_by_name(lm):
    from ray_tpu.llm.engine import LLMEngine

    _, _, engine = _engine(lm)
    runner = engine.runner
    with pytest.raises(ValueError, match="speculative_ngram.*state group"):
        LLMEngine(runner, max_batch_size=4, speculative_ngram=2)
    with pytest.raises(ValueError, match="lfm2_moe: tensor_parallel > 1 "
                                         "is not supported"):
        runner.block.refuse(tensor_parallel=2, lora=False)
    with pytest.raises(ValueError, match="lfm2_moe: LoRA"):
        runner.block.refuse(tensor_parallel=1, lora=True)
    with pytest.raises(ValueError, match="experts_held"):
        lm.Lfm2MoeConfig.tiny(experts_held=(8, 24))
    with pytest.raises(ValueError, match="pair form pairs kv heads"):
        lm.Lfm2MoeConfig.tiny(num_key_value_heads=1)
    with pytest.raises(ValueError, match="a kind of layer"):
        lm.Lfm2MoeConfig.tiny(layer_types=("conv", "sliding_attention"))


# ---- (e) the share test ------------------------------------------------------

def _expert_layer(lm, rng):
    import jax.numpy as jnp

    whole = lm.Lfm2MoeConfig.tiny()
    d, f = whole.hidden_size, whole.moe_intermediate_size
    draw = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[-2]), jnp.float32)
    experts = {"w_gate": draw(16, d, f), "w_up": draw(16, d, f),
               "w_down": draw(16, f, d)}
    p = {"router": draw(d, 16),
         "router_bias": jnp.asarray(rng.uniform(0, 0.2, 16), jnp.float32)}
    x = jnp.asarray(rng.standard_normal((24, d)), jnp.float32)
    return whole, experts, p, x


@pytest.mark.parametrize("shares", [1, 2, 4])
def test_the_shares_add_up_to_the_uncut_layer(lm, ref, shares):
    """Programs holding 16 / `shares` experts each of a tiny layer's 16,
    given the same rows (every share routes over all 16 and renormalises over
    all 4 kept, held or not): their routed parts summed equal the uncut
    reference's layer, every pick computed once; and the whole share (0, 16)
    equals it alone."""
    import jax
    import jax.numpy as jnp

    whole, experts, p, x = _expert_layer(lm, np.random.default_rng(4))
    per = 16 // shares
    with jax.default_matmul_precision("highest"):
        want, choice = ref.routed_ffn(x, p, experts, whole.reference_sizes())
        total, rows = np.zeros(want.shape, np.float64), 0
        for first in range(0, 16, per):
            share = lm.Lfm2MoeConfig.tiny(experts_held=(first, first + per))
            lp = {**p, **{k: v[first:first + per]
                          for k, v in experts.items()}}
            y, (ids, counts) = share.serving_block().feed_forward(
                "conv_moe", x, jnp.ones(24, bool), lp)
            np.testing.assert_array_equal(
                np.sort(np.asarray(ids), -1),
                np.sort(np.argsort(-np.asarray(choice), -1,
                                   kind="stable")[:, :4], -1))
            total = total + np.asarray(y, np.float64)
            rows += int(counts[0])
    assert rows == 24 * 4                               # every pick, once
    np.testing.assert_allclose(total, np.asarray(want), rtol=1e-4, atol=1e-5)


# ---- (f) the pair form by runs -----------------------------------------------

def _old_pair_queries(q):
    """`pa.pair_queries` as it stood before it took a run (PR 62's tree)."""
    import jax.numpy as jnp

    *lead, H, hd = q.shape
    pairs = q.reshape(*lead, H // 2, 2, hd)
    zeros = jnp.zeros_like(pairs[..., 0, :])
    return jnp.stack(
        [jnp.concatenate([pairs[..., 0, :], zeros], axis=-1),
         jnp.concatenate([zeros, pairs[..., 1, :]], axis=-1)],
        axis=-2).reshape(*lead, H, 2 * hd)


def test_phis_operands_and_sizes_are_what_they_were(cpu_jax):
    """Differential attention's call, `pair_queries(q)`: bit for bit the
    operand of before, bfloat16 and float32, flat and rectangular; and the
    sizes the kernel takes at Phi-4-mini-flash's, MiMo-V2-Flash's,
    Nemotron-3-Super's and Trinity-Large-Preview's shapes as PR 62 left them,
    beside this block's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import (afmoe, lfm2_moe, mimo_v2_flash, nemotron_h,
                                phi4flash)
    from ray_tpu.ops import paged_attention as pa

    for shape, dtype in (((192, 40, 64), jnp.bfloat16),
                         ((3, 5, 8, 64), jnp.float32)):
        q = jax.random.normal(jax.random.key(7), shape, jnp.float32).astype(
            dtype)
        new, old = pa.pair_queries(q), _old_pair_queries(q)
        assert new.dtype == old.dtype and new.shape == old.shape
        np.testing.assert_array_equal(
            np.asarray(new.astype(jnp.float32)),
            np.asarray(old.astype(jnp.float32)))
    sizes = lambda c: {g: tuple(s[:4]) for g, s in
                       c.serving_block().kv_kernels(16).items()}
    assert sizes(phi4flash.Phi4FlashConfig()) == {
        "all": (48, 16, 32, True), "window": (48, 16, 16, True)}
    assert sizes(mimo_v2_flash.MimoV2FlashConfig()) == {
        "all": (32, 64, 64, True), "window": (32, 16, 16, True)}
    assert sizes(nemotron_h.NemotronHConfig()) == {"all": (64, 64, 64, True)}
    assert sizes(afmoe.AfmoeConfig()) == {
        "all": (40, 16, 32, True), "window": (40, 16, 48, True)}
    assert sizes(lfm2_moe.Lfm2MoeConfig()) == {"all": (64, 32, 64, True)}


@pytest.mark.parametrize("G", [1, 2, 4, 8])
@pytest.mark.parametrize("impl", ["reference", "kernel"])
def test_the_pair_form_by_runs_is_plain_gqa(cpu_jax, G, impl):
    """`pair_queries(q, G)` + `pair_outputs(o, G)` around the jnp reference
    and around the K/V row kernel (interpret mode) at `kv_heads` K / 2 over
    pools of kv PAIRS = plain grouped-query attention at `kv_heads` K over
    the SAME pools read a kv head at a time (a pool row is the same bytes
    either way: nothing is padded, nothing lies twice), for G = H / K in 1,
    2, 4, 8 at a head width of 64: a slice and a decode row."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(5 + G)
    K, hd, ps, S = 4, 64, 4, 2
    H = G * K
    ctx, n = np.asarray([29, 18]), np.asarray([5, 1])
    k_pool = jnp.asarray(rng.normal(size=(2, 16, ps, K * hd)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(2, 16, ps, K * hd)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(8, H, hd)), jnp.float32)
    tables = jnp.asarray([[3, 1, 4, 15, 9, 2, 6, 5],
                          [8, 7, 10, 11, 12, 0, 0, 0]], jnp.int32)
    cu = jnp.asarray([0, 5, 6], jnp.int32)
    scalars = (1, tables, jnp.asarray(ctx, jnp.int32),
               jnp.asarray(ctx - n, jnp.int32), cu)
    want = pa.ragged_paged_attention_unified_reference(
        q, k_pool, v_pool, *scalars, scale=hd ** -0.5, kv_heads=K)
    fn = (pa.ragged_paged_attention_unified_reference if impl == "reference"
          else pa.ragged_paged_attention_unified)
    got = pa.pair_outputs(fn(
        pa.pair_queries(q, G), k_pool, v_pool, *scalars, scale=hd ** -0.5,
        kv_heads=K // 2), G)
    assert got.shape == want.shape == (8, H, hd)
    np.testing.assert_allclose(np.asarray(got)[:6], np.asarray(want)[:6],
                               atol=2e-6)
    assert float(np.abs(np.asarray(want)[:6]).max()) > 0.5


# ---- (g) the router ----------------------------------------------------------

def test_the_router_is_the_published_one_written_out_by_hand(lm):
    """`route_one_group(config, scores, bias, scale=1, eps=1e-6)`: the 4
    largest of score + bias (ties to the lower id), gates the kept SCORES
    over their sum + 1e-6, times 1."""
    import jax.numpy as jnp

    from ray_tpu.models.expert_share import route_one_group

    config = lm.Lfm2MoeConfig.tiny()
    rng = np.random.default_rng(8)
    scores = 1.0 / (1.0 + np.exp(-rng.normal(size=(40, 16)))).astype(
        np.float32)
    scores[3, 5] = scores[3, 9] = scores[3].max() + 0.01       # a tie
    bias = rng.uniform(0, 0.2, 16).astype(np.float32)
    bias[5] = bias[9]
    ids, gates = route_one_group(config, jnp.asarray(scores),
                                 jnp.asarray(bias), scale=1.0, eps=lm.GATE_EPS)
    for row in range(40):
        choice = scores[row] + bias
        order = sorted(range(16), key=lambda e: (-choice[e], e))[:4]
        assert np.asarray(ids)[row].tolist() == order
        kept = scores[row, order]
        np.testing.assert_allclose(np.asarray(gates)[row],
                                   kept / (kept.sum() + 1e-6), rtol=1e-6)
    assert np.asarray(ids)[3, :2].tolist() == [5, 9]
    assert lm.GATE_EPS == 1e-6


# ---- (h) controls: each MUST fail (a) ----------------------------------------

SLICES = [16, 13, 3]
STARTS = [0, 16, 29] + list(range(32, 40))
CONTROLS = ["taps_reversed", ("tail_zeroed", STARTS), "bc_swapped",
            "fir_before_gate", "no_qk_norm", "no_rotation", "halves_swapped",
            "bias_in_gates", "gates_not_renormalised"]


@pytest.fixture(scope="module")
def served(lm):
    config, params, runner = _runner(lm)
    tokens = _tokens(2, 2, 40)
    return (config, params, tokens) + _served(runner, tokens, SLICES)


@pytest.mark.parametrize(
    "fault", CONTROLS, ids=lambda f: f if isinstance(f, str) else f[0])
def test_a_reference_with_one_term_changed_is_told_apart(ref, served, fault):
    """The sound reference following the program's experts agrees; with the
    taps reversed, the tail zeroed at every step's first row, B and C swapped,
    the FIR before the gate, QK-norm or the rotation dropped, the halves of a
    pair swapped, the bias in the gates or the gates not renormalised it does
    not, by three orders of magnitude over the tolerance."""
    config, params, tokens, got, positions, routing, _ = served
    sizes = config.reference_sizes()
    sound, _ = ref.logits_at(params, tokens, positions, sizes, routing)
    assert _rel(got, sound) < TOL
    faulty, _ = ref.logits_at(params, tokens, positions, sizes, routing,
                              fault)
    assert _rel(got, faulty) > 1e-2
    assert set(f if isinstance(f, str) else f[0]
               for f in CONTROLS) == set(ref.FAULTS)


def test_a_program_that_drops_its_tail_between_steps_fails(lm, ref, served):
    """The control on the program's side: a runner whose tails are zeroed
    after every step reads what the reference reads with g zero before every
    step's first row, and not what the sound reference reads."""
    import jax.numpy as jnp

    def zeroed(runner):
        runner.cache = {k: jnp.zeros_like(v) if k == "conv_tail" else v
                        for k, v in runner.cache.items()}

    config, params, tokens, _, positions, _, _ = served
    _, _, runner = _runner(lm)
    got, _, routing, _ = _served(runner, tokens, SLICES, after_step=zeroed)
    sizes = config.reference_sizes()
    sound, _ = ref.logits_at(params, tokens, positions, sizes, routing)
    faulty, _ = ref.logits_at(params, tokens, positions, sizes, routing,
                              ("tail_zeroed", STARTS))
    assert _rel(got, sound) > 1e-2
    assert _rel(got, faulty) < TOL
