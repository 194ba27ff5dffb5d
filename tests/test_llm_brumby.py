"""Brumby (models/brumby.py) against its plain reference, at tiny sizes on the
CPU with seeded weights: power retention layers whose matrix-valued gated
state is a slot a sequence, a block with NO paged layer (the `all` group
holds no array: its pages are the engine's token accounting), through
`ModelRunner.step`, ragged mixed launches and `LLMEngine`.

Two layers, 6 query heads over 2 kv heads of 16 lanes; pages of 4, slices of
16, contexts of 40-60 tokens: every sequence crosses several chunk edges.

Tolerance: in float32 program and reference differ in the order of their sums
(the recurrence and the chunked form over features against the t x t weights):
logits agree to ~1e-6 of their largest value; 2e-5 leaves an order of
magnitude. Every control below reads over 1e-4 (a state kept in bfloat16) or
over 1e-2 (a term dropped).
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
def bm(cpu_jax):
    from ray_tpu.models import brumby

    return brumby


@pytest.fixture(scope="module")
def ref(cpu_jax):
    from ray_tpu.models import brumby_reference

    return brumby_reference


def _runner(bm, config=None, impl="reference", seed=0, num_blocks=64,
            max_batch=4):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner

    config = config or bm.BrumbyConfig.tiny()
    params = bm.init_params(config, jax.random.key(seed))
    return config, params, ModelRunner(
        config, params, num_blocks=num_blocks, block_size=4,
        attention_impl=impl, chunk_size=16, max_batch=max_batch)


def _engine(bm, impl="reference", max_batch=4, num_blocks=64, **kw):
    from ray_tpu.llm.engine import LLMEngine

    config, params, runner = _runner(bm, impl=impl, num_blocks=num_blocks,
                                     max_batch=max_batch)
    return config, params, LLMEngine(runner, max_batch_size=max_batch,
                                     prefill_chunk=16, **kw)


def _tokens(seed, rows, n):
    return np.random.default_rng(seed).integers(1, 256, (rows, n)).astype(
        np.int32)


def _step_logits(runner, tokens, n_prompt, after_step=None):
    """Chunked prefill of tokens[:, :n_prompt], then a token at a time, by
    `ModelRunner.step` given ONE table, the `all` group's (which no program
    reads; the runner lays the slots itself), as the benchmark's check
    drives it. -> logits at positions n_prompt - 1 .. total - 2."""
    rows, total = tokens.shape
    tables = np.zeros((rows, runner.max_blocks_per_seq), np.int32)
    full = lambda v: np.full(rows, v, np.int32)
    got = []

    def step(tok, start, n):
        logits = runner.step(tok, full(start), full(start + n), full(n),
                             tables)
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
    return np.stack(got[:-1], axis=1)


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
    land either side of a tie nearer than NEAR; a slot that kept another
    sequence's state misses by hundreds of times that (0.21 of the
    scale with `zero` ignored: CHANGES.md, PR 61)."""
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
                           "brumby_reference.py")) as a, \
            open(os.path.join(HERE, "benchmarks",
                              "brumby_reference.py")) as b:
        text = a.read()
        assert text == b.read()
    assert "import ray_tpu" not in text and "from ray_tpu" not in text
    assert "phi(" not in text       # the attention form: no features built


def test_the_published_layout_counts_the_models_parameters(bm):
    """40 layers of 330.35 M and 2 x 777.9 M of embedding and head: 14.8 B
    (the card's 14B counts no embedding); the cell's six layers 3,537.9 M; a
    slot as it lies 206.07 MB; the drawn tree has the counted values."""
    import jax

    c = bm.BrumbyConfig()
    assert (c.num_params() - bm.BrumbyConfig(
        num_hidden_layers=39).num_params()) == 330_352_904
    assert c.num_params() // 10 ** 6 == 14_769
    six = bm.BrumbyConfig(num_hidden_layers=6)
    assert six.num_params() == 3_537_947_184
    assert six.state_bytes_per_sequence == 6 * 8 * 65 * 128 * 129 * 4
    tiny = bm.BrumbyConfig.tiny()
    shapes = jax.eval_shape(lambda: bm.init_params(tiny, jax.random.key(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == tiny.num_params()


def test_the_gates_are_drawn_to_remember(bm):
    """The bias alone gives gates in [0.9, 0.999] a head and layer."""
    import jax

    params = bm.init_params(bm.BrumbyConfig.tiny(num_hidden_layers=4),
                            jax.random.key(3))
    g = np.asarray(jax.nn.sigmoid(params["layers"]["bg"]))
    assert g.shape == (4, 2)
    assert g.min() >= 0.9 - 1e-6 and g.max() <= 0.999 + 1e-6
    assert np.unique(np.round(g, 4)).size == g.size


# ---- logits through the cache against the full forward pass -----------------

@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_chunked_prefill_then_decode_by_step_matches_the_reference(
        bm, ref, impl):
    """Two prompts of 42 tokens in slices of 16, 16, 10 (the state crosses
    two chunk edges), then 8 teacher-forced decode steps: the chunked form,
    then the recurrent step, against the attention form's full pass."""
    config, params, runner = _runner(bm, impl=impl)
    assert runner.attention_impl == impl
    tokens = _tokens(0, 2, 50)
    want, _ = ref.logits_at(params, tokens, list(range(41, 49)),
                            config.reference_sizes())
    assert _rel(_step_logits(runner, tokens, 42), want) < TOL


def test_bfloat16_weights_stay_near_the_float32_reference(bm, ref):
    import jax.numpy as jnp

    config, params, runner = _runner(
        bm, bm.BrumbyConfig.tiny(dtype=jnp.bfloat16))
    assert {name: str(a.dtype) for name, a in runner.cache.items()} == {
        "ret_state": "float32", "ret_norm": "float32", "ret_rows": "float32",
        "ret_fill": "int32"}
    tokens = _tokens(1, 2, 48)
    want, _ = ref.logits_at(params, tokens, list(range(39, 47)),
                            config.reference_sizes())
    assert _rel(_step_logits(runner, tokens, 40), want) < 5e-2


def _mixed_logits(runner, tokens, spans):
    """One `step_mixed_logits` launch a round: `spans` [[(row, start, n)]],
    each sequence's rows token-major in the order given, a slot a row of
    `tokens`. -> {(row, position): logits} of every span's last token."""
    S = runner.batch_bucket(runner.max_batch)
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
def test_ragged_mixed_steps_match_the_reference(bm, ref, impl):
    """Token-major launches that hold a slice from position 0, a slice that
    continues mid-sequence, and decode rows, of three sequences of unequal
    length that join and leave: ONE retention call a layer carries all of
    them, and every last-row logits equals the reference's."""
    config, params, runner = _runner(bm, impl=impl)
    tokens = _tokens(4, 3, 40)
    spans = [[(0, 0, 16)],
             [(0, 16, 9), (1, 0, 13)],
             [(0, 25, 1), (1, 13, 16), (2, 0, 5)],
             [(0, 26, 1), (1, 29, 1), (2, 5, 16)],
             [(1, 30, 1), (2, 21, 1)],
             [(2, 22, 1)]]
    got = _mixed_logits(runner, tokens, spans)
    for row in range(3):
        positions = sorted(p for r, p in got if r == row)
        want, _ = ref.logits_at(params, tokens[row:row + 1], positions,
                                config.reference_sizes())
        have = np.stack([got[row, p] for p in positions])[None]
        assert _rel(have, want) < TOL, row


# ---- through the engine -----------------------------------------------------

def test_engine_matches_the_reference_as_sequences_join_and_leave(bm, ref):
    """Mixed ticks with one step of lookahead: six requests of unequal
    lengths through four rows; every greedy token is the reference's, and the
    records count what the retention calls carried."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(bm)
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
    ticks = [t for t in engine.tick_records() if t["retention_rows"]]
    assert all(t["retention_rows"] == t["used"] for t in ticks)
    assert all(t["retention_seqs"] == t["prefill_rows"] + t["decode_rows"]
               for t in ticks)
    assert all("ssm_rows" not in t and t["q_blocks"] == 0
               and t["kv_pages_walked"] == 0 for t in ticks)
    assert any(t["prefill_rows"] and t["decode_rows"] for t in ticks)
    assert stats["retention_rows"] == sum(t["retention_rows"] for t in ticks)
    assert stats["retention_seqs"] == sum(t["retention_seqs"] for t in ticks)
    assert "ssm_rows" not in stats


def test_a_block_with_no_paged_layer_admits_finishes_and_frees(bm):
    """The `all` group holds no array and no byte: its pages are the
    engine's accounting, handed out at admission and all back at the end."""
    from ray_tpu.llm.sampling import SamplingParams

    _, _, engine = _engine(bm, num_blocks=48)
    runner = engine.runner
    assert [g.name for g in runner.groups] == ["all", "state"]
    assert {a.group for a in runner.cache_arrays} == {"state"}
    assert sorted(runner.cache) == ["ret_fill", "ret_norm", "ret_rows",
                                    "ret_state"]
    assert runner.page_nbytes == 0 and runner.block.q_block is None
    rng = np.random.default_rng(5)
    for n in (20, 33, 7):
        engine.add_request(rng.integers(1, 256, n).tolist(),
                           SamplingParams(max_tokens=5, temperature=0.0))
    for _ in range(3):
        engine.step()
    groups = engine.stats()["kv_groups"]
    assert groups["all"]["live"] > 0 and groups["state"]["live"] == 3
    assert engine.stats()["free_kv_blocks"] < 48
    done = _drain(engine)
    assert len(done) == 3 and all(len(o.output_token_ids) == 5
                                  for o in done.values())
    stats = engine.stats()
    groups = stats["kv_groups"]
    assert groups["all"]["live"] == groups["state"]["live"] == 0
    assert stats["free_kv_blocks"] == stats["total_kv_blocks"] == 48
    assert groups["state"]["free"] + groups["state"]["parked"] == 8


def test_a_slot_reused_after_release_starts_from_zero(bm, ref):
    """One row, two slots: the third request takes the first one's slot,
    whose state nobody cleared, and still emits the reference's tokens."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(bm, max_batch=1,
                                     enable_prefix_caching=False)
    rng = np.random.default_rng(8)
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    slots = []
    for n in (30, 21, 12):
        prompt = rng.integers(1, 256, n).tolist()
        rid = engine.add_request(prompt, sp)
        engine.step()
        slots.append(engine.running[0].state_slot if engine.running
                     else engine.prefilling[0].state_slot)
        out = _drain(engine)[rid].output_token_ids
        # (the second request's fourth token is a tie to 1.6e-4 of the scale)
        assert _greedy_miss(ref, params, config.reference_sizes(), prompt,
                            out) <= NEAR
    assert slots[2] == slots[0] != slots[1]
    assert np.any(np.asarray(engine.runner.cache["ret_state"][:, slots[0]]))


def test_a_snapshot_parked_and_restored_gives_the_uncached_tokens(bm, ref):
    """A prompt served twice: the second run restores the snapshot taken
    where the first's prefill crossed its last whole page (a copy of the
    slot, on the device) and emits the same tokens, the reference's."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(bm, num_blocks=40)
    prompt = np.random.default_rng(3).integers(1, 256, 47).tolist()
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    cold = engine.generate([prompt], sp)[0].output_token_ids
    assert cold == _reference_greedy(ref, params, config.reference_sizes(),
                                     prompt, cold)
    stats = engine.stats()
    assert stats["state_snapshots"] == 1 and stats["state_restores"] == 0
    assert stats["kv_groups"]["state"] == {
        "total": 8, "free": 7, "live": 0, "parked": 1}
    slices = [t["prefill_tokens"] for t in engine.tick_records()
              if t["prefill_tokens"]]
    assert slices == [16, 16, 12, 3]        # cut at the boundary, 44
    warm = engine.generate([prompt], sp)[0].output_token_ids
    assert warm == cold
    stats = engine.stats()
    assert stats["prefix_hits"] == 1 and stats["state_restores"] == 1
    assert stats["prefix_tokens_saved"] == 44


@pytest.mark.parametrize("how", ["abort", "drop_all"])
def test_no_slot_leaks(bm, how):
    """(Finishing is `test_a_block_with_no_paged_layer_admits_finishes_and_
    frees`'.)"""
    from ray_tpu.llm.sampling import SamplingParams

    _, _, engine = _engine(bm)
    rng = np.random.default_rng(2)
    ids = [engine.add_request(rng.integers(1, 256, 20).tolist(),
                              SamplingParams(max_tokens=6, temperature=0.0))
           for _ in range(3)]
    for _ in range(3):
        engine.step()
    assert engine.stats()["kv_groups"]["state"]["live"] == 3
    if how == "abort":
        for rid in ids:
            assert engine.abort_request(rid)
    else:
        engine.drop_all()
    state = engine.stats()["kv_groups"]["state"]
    assert state["live"] == 0
    assert state["free"] + state["parked"] == state["total"] == 8


def test_what_the_block_cannot_do_refuses_by_name(bm):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(bm)
    runner = engine.runner
    engine.add_request(list(range(1, 12)),
                       SamplingParams(max_tokens=4, temperature=0.0), "r")
    for _ in range(3):
        engine.step()
    groups = r"layer groups \['all', 'state'\]"
    with pytest.raises(ValueError, match="export_request.*" + groups):
        engine.export_request("r")
    with pytest.raises(ValueError, match="gather_pages.*" + groups):
        runner.gather_pages([0])
    with pytest.raises(ValueError, match="scatter_pages.*" + groups):
        runner.scatter_pages([0])
    assert engine.export_prefixes() is None
    engine.attach_prefix_store(host_tier=object(), cluster_store=object())
    assert engine.host_prefix_tier is None and engine.cluster_store is None
    with pytest.raises(ValueError, match="speculative_ngram.*state group"):
        LLMEngine(runner, max_batch_size=4, speculative_ngram=2)
    with pytest.raises(ValueError, match="brumby: tensor_parallel"):
        runner.block.refuse(tensor_parallel=2, lora=False)
    with pytest.raises(ValueError, match="brumby: LoRA"):
        runner.block.refuse(tensor_parallel=1, lora=True)


# ---- controls: each MUST fail the comparison --------------------------------

@pytest.mark.parametrize("fault", [
    ("state_not_carried", STEPS), "no_gate", "no_qk_norm"],
    ids=lambda f: f if isinstance(f, str) else f[0])
def test_a_reference_with_one_term_dropped_is_told_apart(bm, ref, fault):
    config, params, runner = _runner(bm)
    tokens = _tokens(2, 2, 44)
    got = _step_logits(runner, tokens, 32)
    positions = list(range(31, 43))
    sizes = config.reference_sizes()
    sound, _ = ref.logits_at(params, tokens, positions, sizes)
    assert _rel(got, sound) < TOL
    faulty, _ = ref.logits_at(params, tokens, positions, sizes, fault)
    assert _rel(got, faulty) > 1e-2


def test_a_program_that_drops_its_state_between_steps_fails(bm, ref):
    """The control on the program's side: a runner whose state is zeroed
    after every step reads what the reference reads with the state not
    carried, and not what the sound reference reads."""
    import jax.numpy as jnp

    def zeroed(runner):
        runner.cache = {k: jnp.zeros_like(v) for k, v in runner.cache.items()}

    config, params, runner = _runner(bm)
    tokens = _tokens(2, 2, 44)
    got = _step_logits(runner, tokens, 32, after_step=zeroed)
    positions = list(range(31, 43))
    sizes = config.reference_sizes()
    sound, _ = ref.logits_at(params, tokens, positions, sizes)
    faulty, _ = ref.logits_at(params, tokens, positions, sizes,
                              ("state_not_carried", STEPS))
    assert _rel(got, sound) > 1e-2
    # (a decode row without a state keeps ONE weight, the square of its own
    # q . k: where that is near 0 the ratio has few digits, in any form)
    assert _rel(got, faulty) < 1e-3


@pytest.mark.parametrize("what", [("ret_state", "ret_norm"), ("ret_rows",)],
                         ids=["state", "buffer"])
def test_a_program_whose_state_is_bfloat16_fails_the_tolerance(bm, ref, what):
    """The control that shows the tolerance tells the stated precision: the
    steps that read under 2e-5 with the float32 state (the tests above) read
    over 1e-4 with S and z, or the rows buffered beside them, rounded to
    bfloat16 after each."""
    import jax

    def rounded(runner):
        runner.cache = {k: jax.lax.reduce_precision(
            v, exponent_bits=8, mantissa_bits=7) if k in what else v
            for k, v in runner.cache.items()}

    config, params, runner = _runner(bm)
    tokens = _tokens(2, 2, 44)
    positions = list(range(31, 43))
    sound, _ = ref.logits_at(params, tokens, positions,
                             config.reference_sizes())
    got = _step_logits(runner, tokens, 32, after_step=rounded)
    assert _rel(got, sound) > 1e-4


@pytest.fixture
def fold8(bm):
    """FOLD 8 (a tiny head's buffer holds 16 rows otherwise): the programs
    traced under it are dropped on the way out."""
    import jax

    from ray_tpu.ops import power_retention as pr

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pr, "FOLD", 8)
        jax.clear_caches()
        yield 8
    jax.clear_caches()


def test_requests_that_decode_past_two_folds_match_the_reference_path(
        bm, ref, fold8):
    """Through the engine with the interpreted kernel, greedy tokens of
    requests that decode 19 rows (two folds of 8 and three rows more) are the
    `lax.scan` path's and the plain reference's, once uncached and once
    restored from a snapshot (`copy_state` carries the buffer and its fill
    with S and z), and the device's fill afterwards is `fill_after`'s over
    the rows each request brought."""
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.ops.state_slots import fill_after

    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, 256, n).tolist() for n in (47, 10)]
    sp = SamplingParams(max_tokens=20, temperature=0.0)
    outs = {}
    for impl in ("reference", "pallas"):
        config, params, engine = _engine(bm, impl=impl, num_blocks=48)
        assert engine.runner.cache["ret_rows"].shape[3] == 2 * fold8 + 8
        cold = [o.output_token_ids for o in engine.generate(prompts, sp)]
        ticks = [t for t in engine.tick_records() if t["retention_seqs"]]
        stats = engine.stats()
        assert stats["state_snapshots"] == 2 and stats["state_restores"] == 0
        assert stats["retention_seqs"] == sum(
            t["retention_seqs"] for t in ticks)
        warm = [o.output_token_ids for o in engine.generate(prompts, sp)]
        stats = engine.stats()
        assert stats["state_restores"] == 2 and warm == cold
        # the device's fill after the drain: a prompt's slices leave a buffer
        # empty, a request's 19 decode rows leave what `fill_after` says, in
        # every layer of every slot a request held (a snapshot's holds none)
        want = 0
        for _ in range(20 - 1):
            want, _ = fill_after(want, 1, False, fold8)
        fill = np.asarray(engine.runner.cache["ret_fill"])[:, :-1]
        live = [s for s in range(fill.shape[1]) if fill[:, s].any()]
        assert len(live) >= 2 and (fill[:, live] == want).all()
        outs[impl] = cold
    assert outs["pallas"] == outs["reference"]
    for prompt, out in zip(prompts, outs["pallas"]):
        assert out == _reference_greedy(ref, params, config.reference_sizes(),
                                        prompt, out)


def test_a_snapshot_among_a_sequences_rows_carries_its_buffer(bm, fold8):
    """A snapshot taken by `copy_state` while a slot's buffer holds rows (5
    decode steps after a prefill), restored into another slot after the
    first has decoded on: the copy decodes the same logits as the sequence
    it was parked from did."""
    _, _, runner = _runner(bm, impl="pallas")
    tokens = _tokens(4, 2, 40)
    tables = np.zeros((2, runner.max_blocks_per_seq), np.int32)
    full = lambda v: np.full(2, v, np.int32)

    def step(pos, n):
        tok = np.zeros((2, 16 if n > 1 else 1), np.int32)
        tok[:, :n] = tokens[:, pos:pos + n]
        return np.asarray(runner.step(tok, full(pos), full(pos + n), full(n),
                                      tables))

    step(0, 16)
    for pos in range(16, 21):
        step(pos, 1)
    assert np.asarray(runner.cache["ret_fill"])[:, :2].tolist() == [[5, 5]] * 2
    runner.copy_state(0, 5)                     # parked among its rows
    first = [step(pos, 1) for pos in range(21, 33)]     # past a fold
    assert np.asarray(runner.cache["ret_fill"])[0, 0] == (5 + 12) % fold8
    runner.copy_state(5, 0)
    assert np.asarray(runner.cache["ret_fill"])[:, 0].tolist() == [5, 5]
    again = [step(pos, 1) for pos in range(21, 33)]
    for a, b in zip(first, again):
        assert _rel(b[0], a[0]) < TOL


def test_a_phi_record_counts_no_fold(bm):
    """The engine's state fields are the block's, two names each: Phi's stay
    the two it had (`ssm_rows`, `ssm_seqs`), and no record or sum of its
    engine holds another block's."""
    import jax

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.models import phi4flash as pm

    assert bm.Block.state_fields == ("retention_rows", "retention_seqs")
    config = pm.Phi4FlashConfig.tiny()
    runner = ModelRunner(
        config, pm.init_params(config, jax.random.key(0)), num_blocks=64,
        block_size=4, attention_impl="reference", chunk_size=16, max_batch=4)
    engine = LLMEngine(runner, max_batch_size=4, prefill_chunk=16)
    assert engine._state_fields == ("ssm_rows", "ssm_seqs")
    engine.generate([list(range(1, 30))],
                    SamplingParams(max_tokens=6, temperature=0.0))
    ticks = [t for t in engine.tick_records() if t.get("ssm_rows")]
    assert ticks and not any("retention_rows" in t for t in ticks)
    assert "retention_rows" not in engine.stats()
    assert engine.stats()["ssm_seqs"] == sum(t["ssm_seqs"] for t in ticks)
