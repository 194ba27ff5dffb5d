"""Nemotron-3-Super (models/nemotron_h.py) against its plain reference, at
tiny sizes on the CPU with seeded weights: Mamba-2 layers (a matrix state a
sequence and head, a SLOT) beside one attention layer that rotates nothing (a
paged K/V row pool) and LatentMoE layers (non-gated relu^2 experts in a latent
the layer enters and leaves once a row) in ONE block, through
`ModelRunner.step`, ragged mixed launches, `LLMEngine` and `LLMServer`.

Six layers (M, E, M, *, E, M), 8 Mamba heads of 16 in 2 groups over a state of
16, chunks of 8; 4 query / 2 kv heads of 16; pages of 4, slices of 16,
contexts of 40-60 tokens: every sequence crosses several slices' edges, page
boundaries and chunks (a slice of two of the kernel's chunks).

Tolerance: in float32 program and reference differ in the order of their sums
(the chunked form against the recurrence): logits agree to ~1e-6 of their
largest value; 2e-5 leaves an order of magnitude. Every control below reads
over 5e-2 (a term changed) or over 1e-4 (a state kept in bfloat16).
"""

import os

import numpy as np
import pytest

import ray_tpu  # noqa: F401

TOL = 2e-5
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = list(range(0, 32, 16)) + list(range(32, 44))
CONTROLS = ["norm_all_lanes", "group_zero", "no_routed_factor",
            ("state_not_carried", STEPS)]


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def nh(cpu_jax):
    from ray_tpu.models import nemotron_h

    return nemotron_h


@pytest.fixture(scope="module")
def ref(cpu_jax):
    from ray_tpu.models import nemotron_h_reference

    return nemotron_h_reference


def _runner(nh, config=None, impl="reference", seed=0, num_blocks=64,
            max_batch=4):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner

    config = config or nh.NemotronHConfig.tiny()
    params = nh.init_params(config, jax.random.key(seed))
    return config, params, ModelRunner(
        config, params, num_blocks=num_blocks, block_size=4,
        attention_impl=impl, chunk_size=16, max_batch=max_batch)


def _engine(nh, impl="reference", max_batch=4, num_blocks=64, **kw):
    from ray_tpu.llm.engine import LLMEngine

    config, params, runner = _runner(nh, impl=impl, num_blocks=num_blocks,
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
        if runner.last_routing is not None:
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
    return (np.stack(got[:-1], axis=1),
            np.concatenate(routing, axis=2) if routing else None)


def _reference_greedy(ref, params, sizes, prompt, output):
    """The reference's greedy choice after prompt + output[:i] for every i,
    by ONE forward pass over the engine's own tokens."""
    tokens = list(prompt) + list(output[:-1])
    positions = list(range(len(prompt) - 1, len(tokens)))
    logits, _ = ref.logits_at(params, np.asarray([tokens], np.int32),
                              positions, sizes)
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
                           "nemotron_h_reference.py")) as f:
        program = f.read()
    with open(os.path.join(HERE, "benchmarks",
                           "nemotron_h_reference.py")) as f:
        assert f.read() == program
    assert "ray_tpu" not in program.split('"""')[2]     # imports nothing


def test_the_published_layout_counts_the_models_parameters(nh):
    """The published model whole (its name: 120 B), and the benchmark's cut
    (published layers 0-10, 128 held experts, a quarter of the vocabulary), by
    hand."""
    whole = nh.NemotronHConfig()
    assert whole.mamba_params() == 109_640_064
    assert whole.attn_params() == 35_655_680
    assert whole.expert_params() == 5_505_024
    assert whole.moe_params(0) == 54_530_560
    kinds = whole.layer_kinds()
    assert [kinds.count(k) for k in ("mamba", "latent_moe", "attn")] == [
        40, 40, 8]
    assert 120.6e9 < whole.num_params() < 120.7e9
    cut = nh.NemotronHConfig(
        num_hidden_layers=11, hybrid_override_pattern="MEMEMEM*EME",
        experts_held=(0, 128), vocab_size=32768,
        max_position_embeddings=8192)
    assert cut.hybrid_override_pattern == whole.hybrid_override_pattern[:11]
    by_hand = (5 * 109_640_064 + 35_655_680
               + 5 * (54_530_560 + 128 * 5_505_024)
               + 2 * 32768 * 4096 + 4096)
    assert cut.num_params() == by_hand
    assert round(by_hand / 1e5) == 46482
    assert cut.state_bytes_per_sequence == 5 * (4 * 128 * 64 * 128
                                                + 2 * 3 * 10240)
    assert cut.conv_dim == 10240 and cut.d_inner == 8192


def test_the_decays_are_drawn_to_remember(nh):
    """Mamba-2's own draw: a step log-uniform in [1e-3, 1e-1] through the
    inverse softplus, A in [1, 16], D = 1: a decay a token from 0.2 to 0.999
    before the token's own term."""
    import jax

    config = nh.NemotronHConfig.tiny(mamba_num_heads=64, n_groups=2)
    p = nh.init_params(config, jax.random.key(3))["layers"]["mamba"]
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    A = np.exp(np.asarray(p["A_log"]))
    assert dt.min() >= 0.99e-3 and dt.max() <= 1.01e-1
    assert A.min() >= 1.0 and A.max() <= 16.0
    decay = np.exp(-dt * A)
    assert (decay > 0.97).mean() > 0.15 and decay.min() > 0.19
    assert (np.asarray(p["D"]) == 1).all()
    assert not np.asarray(p["conv_b"]).any()


# ---- through the runner -----------------------------------------------------

@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_chunked_prefill_then_decode_by_step_matches_the_reference(
        nh, ref, impl):
    """Prefill slices then decode rows through BOTH caches (the K/V row
    pool's pages and the state group's slots), LOGITS against the reference's
    full forward pass, the reference routing for itself: in float32 no choice
    differs."""
    config, params, runner = _runner(nh, impl=impl)
    assert [(a.name, a.group) for a in runner.cache_arrays] == [
        ("k_all", "all"), ("v_all", "all"), ("ssd_state", "state"),
        ("ssd_rows", "state"), ("ssd_fill", "state"), ("conv_tail", "state")]
    assert runner.cache["k_all"].shape == (1, 64, 4, 32)
    assert runner.cache["ssd_state"].shape == (3, 9, 8, 16, 16)
    # a tile a block of 4 heads: 8 rows of 2 pairs, their B, 4 rows of logs
    assert runner.cache["ssd_rows"].shape == (3, 9, 2, 8 * 2 + 8 + 4, 32)
    tokens = _tokens(1, 2, 44)
    got, routing = _step_logits(runner, tokens, 32)
    want, scores = ref.logits_at(params, tokens, list(range(31, 43)),
                                 config.reference_sizes())
    assert _rel(got, want) < TOL
    assert routing.shape == (2, 2, 44, 4)
    np.testing.assert_array_equal(
        np.sort(routing, -1),
        np.sort(np.argsort(-scores, -1, kind="stable")[..., :4], -1))
    followed, _ = ref.logits_at(params, tokens, list(range(31, 43)),
                                config.reference_sizes(), routing)
    assert _rel(followed, want) < 1e-6


def test_the_state_stays_float32_under_bfloat16_weights(nh):
    """The served precision: bfloat16 weights, K/V rows and convolution
    tails; float32 S (the CPU has no bfloat16 ragged product, so the chip's
    check reads this precision: PERF.md section 6). B and C lie as GROUPS: no
    array of the spec or of the parameters has a head axis for them."""
    import jax
    import jax.numpy as jnp

    config = nh.NemotronHConfig.tiny(dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: nh.init_params(config, jax.random.key(0)))
    kept32 = {"A_log", "dt_bias", "D", "router_bias"}
    for kind, layer in params["layers"].items():
        assert {k for k, a in layer.items()
                if a.dtype == jnp.float32} == kept32 & set(layer), kind
    assert params["experts"][0]["w1"].shape == (16, 32, 32)
    arrays = config.serving_block().cache_arrays({"all": 8, "state": 4}, 4)
    assert {a.name: (str(jnp.dtype(a.dtype)), a.shape) for a in arrays} == {
        "k_all": ("bfloat16", (1, 8, 4, 32)),
        "v_all": ("bfloat16", (1, 8, 4, 32)),
        "ssd_state": ("float32", (3, 5, 8, 16, 16)),
        "ssd_rows": ("float32", (3, 5, 2, 28, 32)),
        "ssd_fill": ("int32", (3, 5)),
        "conv_tail": ("bfloat16", (3, 5, 1, 3 * 192))}


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
def test_ragged_mixed_steps_match_the_reference(nh, ref, impl):
    """Token-major launches that hold a slice from position 0, a slice that
    continues mid-sequence, and decode rows, of three sequences of unequal
    length that join and leave: ONE SSD call a layer carries all of them."""
    config, params, runner = _runner(nh, impl=impl)
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


def test_attention_applies_no_rotation(nh, ref, monkeypatch):
    """An attention-only toy (two `*` layers, no state layer at all): the
    logits are the reference's, which knows no position, and they do not move
    by a bit when every position the step hands the layers is shifted by
    1,000: nothing reads them."""
    from ray_tpu.llm import model_runner

    config = nh.NemotronHConfig.tiny(num_hidden_layers=2,
                                     hybrid_override_pattern="**",
                                     max_position_embeddings=4096)
    tokens = _tokens(8, 2, 24)
    _, params, runner = _runner(nh, config)
    got, _ = _step_logits(runner, tokens, 16)
    want, _ = ref.logits_at(params, tokens, list(range(15, 23)),
                            config.reference_sizes())
    assert _rel(got, want) < TOL
    made = model_runner.StepContext

    def shifted(*, rope_pos, **kw):
        return made(rope_pos=rope_pos + 1000, **kw)

    monkeypatch.setattr(model_runner, "StepContext", shifted)
    _, _, moved = _runner(nh, config)
    again, _ = _step_logits(moved, tokens, 16)
    np.testing.assert_array_equal(again, got)


# ---- through the engine and the server --------------------------------------

def test_engine_matches_the_reference_as_sequences_join_and_leave(nh, ref):
    """Mixed ticks with one step of lookahead: six requests of unequal
    lengths through four rows; every greedy token is the reference's, and the
    records count what the SSD calls, the K/V kernel and the held experts
    carried, under the block's own names."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(nh)
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
    ticks = [t for t in engine.tick_records() if t["ssd_rows"]]
    assert all(t["ssd_rows"] == t["used"] for t in ticks)
    assert all(t["ssd_seqs"] == t["prefill_rows"] + t["decode_rows"]
               for t in ticks)
    assert all("ssm_rows" not in t and "kda_rows" not in t for t in ticks)
    # the attention layer's walk: a row of one token is one query block over
    # its context's pages
    assert all(t["q_blocks"] >= t["ssd_seqs"] and t["kv_pages_walked"] > 0
               and t["kv_tokens"] > 0 and t["attn_pairs"] >= t["used"]
               for t in ticks)
    assert all(t["routed_rows"] == 2 * 4 * t["used"] for t in ticks)
    assert any(t["prefill_rows"] and t["decode_rows"] for t in ticks)
    assert stats["ssd_rows"] == sum(t["ssd_rows"] for t in ticks)
    assert stats["ssd_seqs"] == sum(t["ssd_seqs"] for t in ticks)
    # every record holds both fields (a tick that only lands a step: zeros)
    assert all(0 <= t["ssd_seqs"] <= t["ssd_rows"]
               for t in engine.tick_records())
    records = engine.tick_records()       # it holds all 16 experts
    assert (sum(t.get("expert_rows", 0) for t in records)
            == sum(t["routed_rows"] for t in records) > 0)
    assert all(t.get("expert_rows_max", 0) <= t.get("expert_rows", 0)
               for t in records)
    assert "ssm_rows" not in stats


def test_the_server_serves_through_both_caches(nh, ref):
    """`LLMServer` on the normal path (the replica's loop, warm-up, streams):
    a prompt of three slices and a decode run, greedy, is the reference's at
    every position; served again it restores the slot AND the page chain."""
    import jax.numpy as jnp

    from ray_tpu.llm.serving import LLMConfig, LLMServer

    config = nh.NemotronHConfig.tiny()
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
        assert out[0] == out[1] == _reference_greedy(
            ref, params, config.reference_sizes(), prompt, out[0])
        stats = server.engine_stats()
        assert stats["prefix_hits"] == 1 and stats["state_restores"] == 1
        assert stats["prefix_tokens_saved"] == 44
        assert stats["ssd_rows"] > 0 and stats["ssd_seqs"] > 0
        assert server.engine.host_prefix_tier is None   # a slot cannot travel
    finally:
        server._handoff.close()


def test_a_prefix_hit_restores_slot_and_pages_and_an_eviction_frees_both(
        nh, ref):
    """A prompt served twice: the second run attaches the page chain of the
    K/V pool AND restores the snapshot taken where the first's prefill crossed
    its last whole page, and emits the uncached run's tokens, the
    reference's (the cache on and off give one stream). Then the pool is
    filled: the parked pages are recycled, their snapshot's slot is freed with
    them, and the prompt, admitted again, is a miss that still emits the same
    tokens."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(nh, num_blocks=40)
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
        nh, ref):
    """Through the engine with the interpreted kernel, greedy tokens of
    requests that decode 19 rows (two folds of 8 and three rows more) are the
    `lax.scan` path's and the plain reference's, once uncached and once
    restored from a snapshot (`copy_state` carries the buffer and its fill
    with S), and the device's fill afterwards is `fill_after`'s over the
    rows each request brought."""
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.ops import ssd
    from ray_tpu.ops.state_slots import fill_after

    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, 256, n).tolist() for n in (47, 10)]
    sp = SamplingParams(max_tokens=20, temperature=0.0)
    outs = {}
    for impl in ("reference", "pallas"):
        config, params, engine = _engine(nh, impl=impl, num_blocks=64)
        cold = [o.output_token_ids for o in engine.generate(prompts, sp)]
        ticks = engine.tick_records()
        assert all("ssd_seqs" in t for t in ticks)
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
            want, _ = fill_after(want, 1, False, ssd.FOLD)
        fill = np.asarray(engine.runner.cache["ssd_fill"])[:, :-1]
        live = [s for s in range(fill.shape[1]) if fill[:, s].any()]
        assert len(live) >= 2 and (fill[:, live] == want).all()
        outs[impl] = cold
    assert outs["pallas"] == outs["reference"]
    for prompt, out in zip(prompts, outs["pallas"]):
        assert out == _reference_greedy(ref, params, config.reference_sizes(),
                                        prompt, out)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_a_snapshot_among_a_sequences_rows_carries_its_buffer(nh, impl):
    """A snapshot taken by `copy_state` while a slot's buffer holds rows (5
    decode steps after a prefill), restored into the slot after it has
    decoded on past a fold: the copy decodes the logits the sequence it was
    parked from did."""
    from ray_tpu.ops import ssd

    _, _, runner = _runner(nh, impl=impl)
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
    assert np.asarray(runner.cache["ssd_fill"])[:, :2].tolist() == [[5, 5]] * 3
    runner.copy_state(0, 5)                     # parked among its rows
    first = [step(pos, 1) for pos in range(21, 33)]     # past a fold
    assert np.asarray(runner.cache["ssd_fill"])[0, 0] == (5 + 12) % ssd.FOLD
    runner.copy_state(5, 0)
    assert np.asarray(runner.cache["ssd_fill"])[:, 0].tolist() == [5, 5, 5]
    again = [step(pos, 1) for pos in range(21, 33)]
    for a, b in zip(first, again):
        assert _rel(b[0], a[0]) < TOL


def test_the_cache_on_and_off_give_one_stream(nh):
    """The prefix cache's hit (pages attached, slot restored) against an
    engine that prefills the whole prompt every time: the same greedy
    tokens."""
    from ray_tpu.llm.sampling import SamplingParams

    prompt = np.random.default_rng(21).integers(1, 256, 45).tolist()
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    streams = []
    for cached in (True, False):
        _, _, engine = _engine(nh, enable_prefix_caching=cached)
        runs = [engine.generate([prompt], sp)[0].output_token_ids
                for _ in range(2)]
        assert engine.stats()["prefix_hits"] == (1 if cached else 0)
        streams.append(runs)
    assert streams[0][0] == streams[0][1] == streams[1][0] == streams[1][1]


@pytest.mark.parametrize("how", ["abort", "drop_all"])
def test_no_slot_and_no_page_leaks(nh, how):
    from ray_tpu.llm.sampling import SamplingParams

    _, _, engine = _engine(nh)
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


def test_what_the_block_cannot_do_refuses_by_name(nh):
    from ray_tpu.llm.engine import LLMEngine

    config, params, engine = _engine(nh)
    runner = engine.runner
    with pytest.raises(ValueError, match="speculative_ngram.*state group"):
        LLMEngine(runner, max_batch_size=4, speculative_ngram=2)
    with pytest.raises(ValueError, match="layer group 'state': 8 slots"):
        LLMEngine(runner, max_batch_size=8)
    with pytest.raises(ValueError, match="nemotron_h: tensor_parallel > 1 "
                                         "is not supported"):
        runner.block.refuse(tensor_parallel=2, lora=False)
    with pytest.raises(ValueError, match="nemotron_h: LoRA"):
        runner.block.refuse(tensor_parallel=1, lora=True)
    with pytest.raises(ValueError, match="does not name 3 layers"):
        nh.NemotronHConfig.tiny(num_hidden_layers=3)
    with pytest.raises(ValueError, match="experts_held"):
        nh.NemotronHConfig.tiny(experts_held=(8, 24))


# ---- the expert layer -------------------------------------------------------

def _expert_layer(nh, rng):
    import jax.numpy as jnp

    whole = nh.NemotronHConfig.tiny()
    d, lat = whole.hidden_size, whole.moe_latent_size
    f, fs = (whole.moe_intermediate_size,
             whole.moe_shared_expert_intermediate_size)
    draw = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[-2]), jnp.float32)
    experts = {"w1": draw(16, lat, f), "w2": draw(16, f, lat)}
    p = {"router": draw(d, 16),
         "router_bias": jnp.asarray(rng.uniform(0, 0.2, 16), jnp.float32),
         "fc1_latent": draw(d, lat), "fc2_latent": draw(lat, d),
         "shared_up": draw(d, fs), "shared_down": draw(fs, d)}
    x = jnp.asarray(rng.standard_normal((24, d)), jnp.float32)
    return whole, experts, p, x


def test_four_shares_add_up_to_the_uncut_layer(nh, ref):
    """Programs holding four experts each of a tiny layer's 16, given the same
    rows: their routed parts summed (each LEAVES the latent for itself: the
    projection is linear and has no bias) and the shared expert counted ONCE
    (every share routes over all 16 and renormalises over all 4 kept, held or
    not) equal the uncut reference's layer."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.expert_share import (_dot32, _relu2, held_expert_ffn,
                                             relu2_expert)

    whole, experts, p, x = _expert_layer(nh, np.random.default_rng(4))
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ p["router"])
        want, choice = ref._routed(x, p, experts, whole.reference_sizes())
        ids, gates = nh.route_one_group(whole, scores, p["router_bias"])
        np.testing.assert_array_equal(
            np.sort(np.asarray(ids), -1),
            np.sort(np.argsort(-np.asarray(choice), -1,
                               kind="stable")[:, :4], -1))
        total = np.asarray(_dot32(_relu2(_dot32(x, p["shared_up"])),
                                  p["shared_down"]), np.float64)
        rows = 0
        for first in range(0, 16, 4):
            share = nh.NemotronHConfig.tiny(experts_held=(first, first + 4))
            lp = {k: v[first:first + 4] for k, v in experts.items()}
            y, (n, *_) = held_expert_ffn(
                share, x, ids, gates * whole.routed_scaling_factor,
                jnp.ones(24, bool), lp, expert=relu2_expert,
                enter=p["fc1_latent"], leave=p["fc2_latent"])
            total = total + np.asarray(y, np.float64)
            rows += int(n)
    assert rows == 24 * whole.num_experts_per_tok      # every pick, once
    np.testing.assert_allclose(total, np.asarray(want), rtol=1e-4, atol=1e-5)


def test_the_latent_is_entered_once_a_row(nh):
    """`enter` is applied to the N rows, not to the N x top_k pairs: the
    traced program holds one product with the latent's width on N rows."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.expert_share import held_expert_ffn, relu2_expert

    whole, experts, p, x = _expert_layer(nh, np.random.default_rng(5))
    ids = jnp.zeros((24, 4), jnp.int32)
    gates = jnp.ones((24, 4), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: held_expert_ffn(
        whole, x, ids, gates, jnp.ones(24, bool), experts,
        expert=relu2_expert, enter=p["fc1_latent"],
        leave=p["fc2_latent"])[0])(x)
    dots = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert [tuple(e.outvars[0].aval.shape) for e in dots] == [
        (24, whole.moe_latent_size), (24, whole.hidden_size)]


def test_swiglu_callers_get_what_they_got_before_the_generalisation(cpu_jax):
    """`held_expert_ffn` as the four SwiGLU blocks call it (no `expert`, no
    latent) against the function as it stood before it took a form: bit-equal
    outputs and counts."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import expert_share as es
    from ray_tpu.models.kimi_linear import KimiLinearConfig

    def before(config, x, ids, gates, valid, lp):
        n, k = ids.shape
        first, n_held = config.experts_held[0], config.n_held
        local = ids.reshape(-1) - first
        held = (local >= 0) & (local < n_held) & jnp.repeat(valid, k)
        local = jnp.where(held, local, n_held)
        order = jnp.argsort(local, stable=True)
        sizes = jnp.bincount(local, length=n_held + 1)[:n_held].astype(
            jnp.int32)
        xs = x[order // k]
        y = es._ffn(lambda a, w: jax.lax.ragged_dot(
            a, w, sizes, preferred_element_type=jnp.float32),
            xs, lp["w_gate"], lp["w_up"], lp["w_down"])
        gate = jnp.where(held, gates.reshape(-1), 0.0)[order]
        y = jnp.where(gate[:, None] != 0.0, y * gate[:, None], 0.0)
        y = y[jnp.argsort(order)].reshape(n, k, -1).sum(axis=1)
        return y, sizes.sum(), sizes.max()

    rng = np.random.default_rng(6)
    config = KimiLinearConfig.tiny(experts_held=(4, 12))
    d, f = config.hidden_size, config.moe_intermediate_size
    draw = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[-2]), jnp.float32)
    lp = {"w_gate": draw(8, d, f), "w_up": draw(8, d, f),
          "w_down": draw(8, f, d)}
    x = jnp.asarray(rng.standard_normal((24, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 16, (24, 4)), jnp.int32)
    gates = jnp.asarray(rng.uniform(0.1, 1.0, (24, 4)), jnp.float32)
    valid = jnp.asarray(rng.uniform(size=24) < 0.8)
    was = jax.jit(before, static_argnums=0)(config, x, ids, gates, valid, lp)
    now = jax.jit(es.held_expert_ffn, static_argnums=0)(
        config, x, ids, gates, valid, lp)
    # what it returned then: y, the rows computed, the busiest expert's;
    # since PR 59 the counts are one array, with the met experts third
    np.testing.assert_array_equal(np.asarray(was[0]), np.asarray(now[0]))
    np.testing.assert_array_equal(np.asarray(was[1:]), np.asarray(now[1][:2]))
    assert 0 < int(now[1][2]) <= 8


@pytest.mark.parametrize("form", ["relu2", "swiglu"])
def test_held_expert_ffn_is_the_same_by_either_product(cpu_jax, monkeypatch,
                                                       form):
    """`held_expert_ffn` with XLA's `ragged_dot` (what `grouped_dot.product`
    hands it off the chip) and with the Pallas kernel interpreted (what it
    hands it on a TPU): the same y, rows computed and
    busiest count; padding rows and absent experts ride behind the last
    group by both."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import expert_share as es
    from ray_tpu.models.kimi_linear import KimiLinearConfig
    from ray_tpu.ops import grouped_dot as gd

    rng = np.random.default_rng(7)
    config = KimiLinearConfig.tiny(experts_held=(4, 12))
    d, f = config.hidden_size, config.moe_intermediate_size
    draw = lambda *s: jnp.asarray(
        rng.standard_normal(s) / np.sqrt(s[-2]), jnp.float32)
    if form == "relu2":
        lp, expert = {"w1": draw(8, d, f), "w2": draw(8, f, d)}, es.relu2_expert
    else:
        lp = {"w_gate": draw(8, d, f), "w_up": draw(8, d, f),
              "w_down": draw(8, f, d)}
        expert = es.swiglu_expert
    x = jnp.asarray(rng.standard_normal((24, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 16, (24, 4)), jnp.int32)
    gates = jnp.asarray(rng.uniform(0.1, 1.0, (24, 4)), jnp.float32)
    valid = jnp.asarray(rng.uniform(size=24) < 0.8)
    run = lambda: jax.jit(
        lambda *a: es.held_expert_ffn(config, *a, expert=expert))(
            x, ids, gates, valid, lp)
    ragged = run()
    calls = []

    def kernel(sizes):
        def dot(a, w):
            calls.append(a.shape)
            return gd.grouped_dot(a, w, sizes, tiles=gd.GroupedSizes(8, 16))
        return dot

    monkeypatch.setattr(es.grouped_dot, "product", kernel)
    by_kernel = run()
    assert len(calls) == (2 if form == "relu2" else 3)
    np.testing.assert_allclose(np.asarray(by_kernel[0]),
                               np.asarray(ragged[0]), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(by_kernel[1]),
                                  np.asarray(ragged[1]))
    assert (np.asarray(ragged[1]) > 0).all()


# ---- controls: each MUST fail the comparison --------------------------------

@pytest.mark.parametrize(
    "fault", CONTROLS, ids=lambda f: f if isinstance(f, str) else f[0])
def test_a_reference_with_one_term_changed_is_told_apart(nh, ref, fault):
    config, params, runner = _runner(nh)
    tokens = _tokens(2, 2, 44)
    got, routing = _step_logits(runner, tokens, 32)
    positions = list(range(31, 43))
    sizes = config.reference_sizes()
    sound, _ = ref.logits_at(params, tokens, positions, sizes, routing)
    assert _rel(got, sound) < TOL
    faulty, _ = ref.logits_at(params, tokens, positions, sizes, routing,
                              fault)
    assert _rel(got, faulty) > 5e-2


def test_a_program_that_drops_its_state_between_steps_fails(nh, ref):
    """The control on the program's side: a runner whose S (the state, the
    rows buffered beside it and their count) is zeroed after every step reads
    what the reference reads with the state not carried, and not what the
    sound reference reads."""
    import jax.numpy as jnp

    def zeroed(runner):
        runner.cache = {k: jnp.zeros_like(v) if k.startswith("ssd_") else v
                        for k, v in runner.cache.items()}

    config, params, runner = _runner(nh)
    tokens = _tokens(2, 2, 44)
    got, routing = _step_logits(runner, tokens, 32, after_step=zeroed)
    positions = list(range(31, 43))
    sizes = config.reference_sizes()
    sound, _ = ref.logits_at(params, tokens, positions, sizes, routing)
    faulty, _ = ref.logits_at(params, tokens, positions, sizes, routing,
                              ("state_not_carried", STEPS))
    assert _rel(got, sound) > 5e-2
    assert _rel(got, faulty) < TOL


def test_a_program_whose_state_is_bfloat16_fails_the_tolerance(nh, ref):
    """The control that shows the tolerance tells the stated precision: the
    steps that read under 2e-5 with the float32 state read over 1e-4 with S
    rounded to bfloat16 after each."""
    import jax

    def rounded(runner):
        runner.cache = {k: jax.lax.reduce_precision(
            v, exponent_bits=8, mantissa_bits=7) if k == "ssd_state" else v
            for k, v in runner.cache.items()}

    config, params, runner = _runner(nh)
    tokens = _tokens(2, 2, 44)
    got, routing = _step_logits(runner, tokens, 32, after_step=rounded)
    sound, _ = ref.logits_at(params, tokens, list(range(31, 43)),
                             config.reference_sizes(), routing)
    assert _rel(got, sound) > 1e-4
