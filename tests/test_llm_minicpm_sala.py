"""MiniCPM-SALA (models/minicpm_sala.py) against its plain reference, at tiny
sizes on the CPU with seeded weights: InfLLM-V2 block-sparse attention over a
paged K/V row pool with a page-mean cache beside the pages, and lightning
linear-attention layers whose state is a SLOT (ops/ssd.py with every head a
group of its own), in ONE block, through `ModelRunner.step`, ragged mixed
launches, `LLMEngine` and `LLMServer`.

Six layers (sparse, three lightning, sparse, lightning), 4 query / 2 kv heads
of 16, 4 lightning heads of 16; the PUBLISHED kernel (32), stride (16: the
page) and block (64), over a `dense_len` of 128 and 4 kept blocks of which
two are forced, so that a context of 300-400 tokens selects: a token picks 2
free blocks of up to 5, every kv head its own. Slices of 32.

Tolerance: in float32 program and reference differ in the order of their sums
(the chunked form against the recurrence, the page means' two halves against
a kernel's mean): logits agree to ~3e-7 of their largest value; 2e-5 leaves
an order of magnitude and more. Every control below reads over 1e-3: dense
attention above `dense_len` 3.7e-3, one selection for both kv heads 3.3e-3, a
state dropped at a slice's edge 9e-2, page means that did not follow a prefix
hit 1e-3 and more; first-stage scores in bfloat16 move kept blocks (the last
test).
"""

import os

import numpy as np
import pytest

import ray_tpu  # noqa: F401

TOL = 2e-5
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PAGE, CHUNK = 16, 32
PROMPT, TOTAL = 390, 402


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


@pytest.fixture(scope="module")
def ms(cpu_jax):
    from ray_tpu.models import minicpm_sala

    return minicpm_sala


@pytest.fixture(scope="module")
def ref(cpu_jax):
    from ray_tpu.models import minicpm_sala_reference

    return minicpm_sala_reference


def _runner(ms, config=None, impl="reference", seed=0, num_blocks=128,
            max_batch=4):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner

    config = config or ms.MiniCPMSALAConfig.tiny()
    params = ms.init_params(config, jax.random.key(seed))
    return config, params, ModelRunner(
        config, params, num_blocks=num_blocks, block_size=PAGE,
        attention_impl=impl, chunk_size=CHUNK, max_batch=max_batch)


def _engine(ms, impl="reference", max_batch=4, num_blocks=128, **kw):
    from ray_tpu.llm.engine import LLMEngine

    config, params, runner = _runner(ms, impl=impl, num_blocks=num_blocks,
                                     max_batch=max_batch)
    return config, params, LLMEngine(runner, max_batch_size=max_batch,
                                     prefill_chunk=CHUNK, **kw)


def _tokens(seed, rows, n):
    return np.random.default_rng(seed).integers(1, 256, (rows, n)).astype(
        np.int32)


def _step_logits(runner, tokens, n_prompt, after_step=None):
    """Chunked prefill of tokens[:, :n_prompt], then a token at a time, by
    `ModelRunner.step` given ONE table, the `all` group's, as the benchmark's
    check drives it. -> (logits at positions n_prompt - 1 .. total - 2, the
    kept blocks and their count of every position (sparse layers, rows,
    positions, K[, topk]))."""
    rows, total = tokens.shape
    pages = -(-total // runner.block_size)
    tables = np.zeros((rows, runner.max_blocks_per_seq), np.int32)
    for i in range(rows):
        tables[i, :pages] = 1 + i * pages + np.arange(pages)
    full = lambda v: np.full(rows, v, np.int32)
    got, kept = [], []

    def step(tok, start, n):
        logits = runner.step(tok, full(start), full(start + n), full(n),
                             tables)
        kept.append([np.asarray(a)[:, :, :n]
                     for a in runner.last_layer_outputs["selection"]])
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
            tuple(np.concatenate(parts, axis=2) for parts in zip(*kept)))


_RUNS = {}


def _served(ms, impl):
    """The same two rows served once an `impl`: (config, params, tokens, the
    program's logits, its kept blocks and their count)."""
    if impl not in _RUNS:
        config, params, runner = _runner(ms, impl=impl)
        tokens = _tokens(1, 2, TOTAL)
        _RUNS[impl] = (config, params, tokens,
                       *_step_logits(runner, tokens, PROMPT))
    return _RUNS[impl]


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
                           "minicpm_sala_reference.py")) as f:
        program = f.read()
    with open(os.path.join(HERE, "benchmarks",
                           "minicpm_sala_reference.py")) as f:
        assert f.read() == program


def test_the_published_layout_counts_the_models_parameters(ms):
    """The published 32 layers are the model's "9B"; published layers 9-24
    (`minicpm-sala-l16`) are 4 sparse + 12 lightning, the published 8 : 24,
    and 5,039 M parameters."""
    import jax

    whole = ms.MiniCPMSALAConfig()
    assert whole.layers_of("sparse") == 8 and whole.layers_of(
        "lightning") == 24
    assert round(whole.lightning_params() / 1e6, 1) == 285.2
    assert round(whole.sparse_params() / 1e6, 1) == 253.8
    assert round(whole.num_params() / 1e9, 2) == 9.48
    cut = ms.MiniCPMSALAConfig(
        num_hidden_layers=16, mixer_types=whole.mixer_types[9:25],
        first_published_layer=9, max_position_embeddings=36864)
    assert "".join(k[0] for k in cut.layer_kinds()) == "sllllllssllllsll"
    assert round(cut.num_params() / 1e6) == 5039
    assert cut.state_bytes_per_sequence == 12 * 32 * 128 * 128 * 4
    tiny = ms.MiniCPMSALAConfig.tiny()
    params = ms.init_params(tiny, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == tiny.num_params()


def test_the_decays_keep_their_published_depth(ms, ref):
    """lambda = exp(-2^(-8 h / 32) (1 - l / 31 + 1e-5)) at PUBLISHED layer l:
    a cut that starts at layer 9 keeps layers 10-15's decays, the slowest
    heads remember over hundreds of tokens, and program and reference hold
    the same numbers."""
    whole = ms.MiniCPMSALAConfig()
    cut = ms.MiniCPMSALAConfig(
        num_hidden_layers=16, mixer_types=whole.mixer_types[9:25],
        first_published_layer=9)
    s = np.asarray(cut.decays())
    assert s.shape == (12, 32)
    np.testing.assert_allclose(
        s[0], 2.0 ** (-8 * np.arange(1, 33) / 32) * (1 - 10 / 31 + 1e-5),
        rtol=1e-6)
    np.testing.assert_allclose(s, np.asarray(whole.decays())[8:20], rtol=1e-6)
    assert np.exp(-s).max() > 0.997 and np.exp(-s[:, 0]).max() < 0.85
    sizes = cut.reference_sizes()
    lightning = [i for i, k in enumerate(cut.layer_kinds())
                 if k == "lightning"]
    np.testing.assert_allclose(ref.decays(sizes)[lightning], s, rtol=1e-6)


# ---- the step programs against the reference --------------------------------

@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_chunked_prefill_then_decode_by_step_matches_the_reference(
        ms, ref, impl):
    """A prompt of 390 tokens in slices of 32, then 12 tokens through the
    cache, WITH selection running (every position from 257 on drops blocks):
    the logits are the reference's full forward pass, the kept blocks are the
    reference's own (no tie is near in float32), ascending, the first block
    and the token's own always among them, and the two kv heads' sets
    differ."""
    config, params, tokens, got, (blocks, count) = _served(ms, impl)
    sizes = config.reference_sizes()
    positions = list(range(PROMPT - 1, TOTAL - 1))
    want, found = ref.logits_at(params, tokens[:, :TOTAL - 1], positions,
                                sizes)
    assert _rel(got, want) < TOL
    assert found["selects"].sum() == 2 * 2 * (TOTAL - 1 - config.dense_len)
    kept = (blocks[:, :, :TOTAL - 1], count[:, :, :TOTAL - 1])
    followed, found = ref.logits_at(params, tokens[:, :TOTAL - 1], positions,
                                    sizes, kept=kept)
    assert not found["differ"].any() and found["shortfall"].max() == 0.0
    np.testing.assert_array_equal(np.asarray(followed), np.asarray(want))
    dropped = 0
    for t in range(config.dense_len, TOTAL - 1):
        own = t // config.block_size
        assert (count[:, :, t] == min(own + 1, config.topk)).all()
        sets = blocks[:, :, t, :, :min(own + 1, config.topk)]
        assert (np.diff(sets, axis=-1) > 0).all()
        assert (sets[..., 0] == 0).all() and (sets[..., -1] == own).all()
        dropped += int((sets[:, :, 0] != sets[:, :, 1]).any(-1).sum())
    assert (count[:, :, :config.dense_len] == 0).all()
    assert dropped > 50     # the kv heads choose for themselves


@pytest.mark.parametrize("fault", [
    "dense_above", "shared_selection",
    ("state_not_carried", [32, 64, 390, 395])], ids=str)
def test_a_reference_with_one_term_changed_is_told_apart(ms, ref, fault):
    config, params, tokens, got, _ = _served(ms, "reference")
    want, _ = ref.logits_at(params, tokens[:, :TOTAL - 1],
                            list(range(PROMPT - 1, TOTAL - 1)),
                            config.reference_sizes(), fault=fault)
    assert _rel(got, want) > 1e-3


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


def test_ragged_mixed_steps_match_the_reference(ms, ref):
    """Token-major launches that hold, side by side, a slice that CROSSES
    `dense_len` (its first tokens see everything, its last select), a slice
    deep in the sparse regime, decode rows that select and a decode row that
    does not: three sequences through one launch a round, each kind of row
    to its own entry (the interpreted kernels take this path in
    tests/test_block_sparse_ops.py and by `ModelRunner.step` above: a launch
    shape there compiles for ten seconds and more)."""
    config, params, runner = _runner(ms)
    tokens = _tokens(4, 3, 330)
    lead = [[(0, at, 32), (1, at, 32)] for at in range(0, 96, 32)]
    spans = lead + [
        [(0, 96, 24), (1, 96, 32)],
        [(0, 120, 16), (1, 128, 32), (2, 0, 24)],     # row 0 crosses 128
        [(0, 136, 1), (1, 160, 32), (2, 24, 1)],
        [(0, 137, 1), (1, 192, 32), (2, 25, 1)]]
    spans += [[(1, at, 32)] for at in range(224, 320, 32)]
    spans += [[(0, 138, 1), (1, 320, 1), (2, 26, 1)],
              [(0, 139, 1), (1, 321, 8)]]
    got = _mixed_logits(runner, tokens, spans)
    for row in range(3):
        positions = sorted(p for r, p in got if r == row)
        want, _ = ref.logits_at(params, tokens[row:row + 1], positions,
                                config.reference_sizes())
        have = np.stack([got[row, p] for p in positions])[None]
        assert _rel(have, want) < TOL, row


# ---- the engine and the server ----------------------------------------------

def test_engine_matches_the_reference_as_sequences_join_and_leave(ms, ref):
    """Mixed ticks with one step of lookahead: four requests of unequal
    lengths, two of them past `dense_len`; every greedy
    token is the reference's, and the records count what the lightning
    layers' calls carried and what the selection scored and spared, under
    the block's own names."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(ms)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 256, n).tolist()
               for n in (300, 9, 140, 41)]
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
    assert all(0 < t["block_pairs"] <= t["attn_pairs"] for t in ticks)
    assert any(t["block_pairs"] < t["attn_pairs"] for t in ticks)
    selecting = [t for t in ticks if t["select_rows"]]
    assert selecting and all(
        0 < t["select_seqs"] <= t["select_rows"] <= t["used"]
        and t["pages_scored"] >= 128 // PAGE for t in selecting)
    for name in ("block_pairs", "select_rows", "select_seqs", "pages_scored",
                 "ssd_rows", "ssd_seqs"):
        assert stats[name] == sum(t[name] for t in ticks), name
    assert stats["kv_kernels"]["all"]["layout"] == "rows"


def test_the_ticks_counts_are_the_equations_own(ms):
    """`tick_counts` by brute force: a token's pairs inside min(blocks, topk)
    blocks or all where it sees no more than `dense_len`; shared pages of the
    step's selecting contexts count once."""
    config = ms.MiniCPMSALAConfig.tiny()
    block = config.serving_block()
    rows = [(1, 400, 401), (32, 100, 132), (1, 20, 21), (3, 255, 258)]
    shared = list(range(1, 9))
    tables = np.zeros((4, 64), np.int32)
    tables[0, :26] = shared + list(range(100, 118))
    tables[1, :9] = shared + [50]
    tables[2, :2] = [60, 61]
    tables[3, :17] = shared + list(range(70, 79))
    got = block.tick_counts(rows, tables, PAGE)
    pairs = selects = 0
    for n, first, _ in rows:
        for p in range(first, first + n):
            if p + 1 <= config.dense_len:
                pairs += p + 1
            else:
                selects += 1
                pairs += p % 64 + 1 + 64 * (min(p // 64 + 1, 4) - 1)
    assert got == {"block_pairs": pairs, "select_rows": selects,
                   "select_seqs": 3, "pages_scored": 8 + 18 + 1 + 9}
    assert block.tick_counts(rows, None, PAGE)["pages_scored"] == 26 + 9 + 17
    assert block.tick_counts([], None, PAGE) == dict.fromkeys(
        block.tick_fields, 0)


def test_the_server_serves_through_both_caches(ms, ref):
    """`LLMServer` on the normal path (the replica's loop, warm-up, streams):
    a prompt past `dense_len` and a decode run, greedy, is the reference's at
    every position; served again it restores the slot AND the page chain,
    page means with it."""
    import jax.numpy as jnp

    from ray_tpu.llm.serving import LLMConfig, LLMServer

    config = ms.MiniCPMSALAConfig.tiny()
    server = LLMServer(LLMConfig(
        model_config=config, seed=5, num_kv_blocks=128, block_size=PAGE,
        max_batch_size=4, prefill_chunk=CHUNK, warmup_buckets="light",
        stream_timeout_s=120.0))
    try:
        params = server.engine.runner.params
        assert params["embed"].dtype == jnp.float32
        prompt = np.random.default_rng(6).integers(1, 256, 300).tolist()
        request = {"prompt": prompt, "max_tokens": 10}
        out = [server.completions({**request, "request_id": f"s{i}"})[
            "choices"][0]["token_ids"] for i in range(2)]
        assert out[0] == out[1] == _reference_greedy(
            ref, params, config.reference_sizes(), prompt, out[0])
        stats = server.engine_stats()
        assert stats["prefix_hits"] == 1 and stats["state_restores"] == 1
        assert stats["prefix_tokens_saved"] == 288
        assert stats["ssd_rows"] > 0 and stats["select_rows"] > 0
        assert server.engine.host_prefix_tier is None   # a slot cannot travel
    finally:
        server._handoff.close()


def test_a_prefix_hit_restores_state_and_page_means(ms, ref):
    """A document of 288 tokens served once; a request that shares it is a
    hit that restores the parked slot and attaches the document's pages, and
    its greedy tokens are the reference's over the whole prompt: the page
    means came with the pages."""
    from ray_tpu.llm.sampling import SamplingParams

    rng = np.random.default_rng(3)
    document = rng.integers(1, 256, 289).tolist()
    tail = rng.integers(1, 256, 40).tolist()
    config, params, engine = _engine(ms)
    engine.generate([document], SamplingParams(max_tokens=1, temperature=0.0))
    out = engine.generate([document + tail], SamplingParams(
        max_tokens=12, temperature=0.0))[0].output_token_ids
    stats = engine.stats()
    assert stats["prefix_hits"] == 1 and stats["state_restores"] == 1
    assert stats["prefix_tokens_saved"] == 288
    assert stats["state_snapshots"] == 2
    assert out == _reference_greedy(ref, params, config.reference_sizes(),
                                    document + tail, out)


@pytest.mark.parametrize("follows", [True, False],
                         ids=["means_follow", "means_left_behind"])
def test_a_hit_by_hand_needs_the_slot_and_the_page_means(ms, ref, follows):
    """What the engine's hit does, by hand on the runner, in LOGITS: a
    second sequence whose table names the first's 18 pages and whose slot is
    a copy of the first's after 288 tokens continues with a tail of its own
    and reads the reference's logits. Control: the same with the shared
    pages' means wiped (a pool that did not follow its pages) does not."""
    config, params, runner = _runner(ms)
    tokens = _tokens(7, 1, 330)
    spans = [[(0, at, 32)] for at in range(0, 288, 32)]
    _mixed_logits(runner, tokens, spans)
    runner.copy_state(0 + 2, 1 + 2)         # `_mixed_logits`' slots
    if not follows:
        runner.cache["k_mean"] = runner.cache["k_mean"] * 0
    S = runner.batch_bucket(runner.max_batch)
    pages = -(-330 // PAGE)
    tables = runner.zero_tables(S)
    tables["all"][0, :pages] = 5 + np.arange(pages)
    tables["all"][0, 18:pages] = 60 + np.arange(pages - 18)  # its own tail
    tables["state"][0, 0] = 1 + 2
    flat = np.zeros(48, np.int32)
    flat[:42] = tokens[0, 288:330]
    cu = np.full(S + 1, 42, np.int32)
    cu[0] = 0
    q_pos, kv = np.zeros(S, np.int32), np.zeros(S, np.int32)
    q_pos[0], kv[0] = 288, 330
    got = np.asarray(runner.step_mixed_logits(
        flat, q_pos, kv, cu, tables, np.full(S, 41, np.int32)))[0]
    want, _ = ref.logits_at(params, tokens, [329], config.reference_sizes())
    err = _rel(got, np.asarray(want)[0, 0])
    assert err < TOL if follows else err > 1e-3, err


def _park_as_most_recent(self, h, cost):
    """`SlotPool.park` as it was before a hit was what protects a snapshot:
    every one parks as the most recently used of one order."""
    if h in self.parked:
        self.hot.move_to_end(h)
        return None
    slot = self._take()
    self.hot[h] = slot
    return slot


def _park_first_in_first_out(park):
    """`SlotPool.park` with every snapshot's cost alike: the never-hit ones
    leave oldest first."""
    return lambda self, h, cost: park(self, h, 0)


@pytest.mark.parametrize("rule", ["by_cost_until_hit", "most_recent",
                                  "first_in_first_out"])
def test_a_shared_documents_snapshot_outlives_the_requests_on_another(
        ms, rule, monkeypatch):
    """Two shared prefixes served once, then 3 x `state_group_slots` requests
    on the first, each with a tail of its own (so each parks a snapshot
    nobody will ask for), then one on the second: it is a HIT with a
    restore. Under the rule the engine had ("most_recent": every snapshot
    parks as most recently used) the second document's snapshot has been
    recycled by then, and since a snapshot is cut at a prompt's last whole
    page only, the request prefills the whole document beside pages that are
    all cached: the fault this traffic meets first, shown here. A hit's
    protection alone does not mend it ("first_in_first_out" among the
    snapshots no hit has attached): the second document's is the OLDEST of
    those when the slots run out. What keeps it is what it cost to cut: 384
    tokens of prefill against a tail's 16, with a clock that a recycled
    tail has moved to 192 by then."""
    from ray_tpu.llm import engine as engine_mod
    from ray_tpu.llm.model_runner import state_group_slots
    from ray_tpu.llm.sampling import SamplingParams

    if rule == "most_recent":
        monkeypatch.setattr(engine_mod.SlotPool, "park", _park_as_most_recent)
    if rule == "first_in_first_out":
        monkeypatch.setattr(engine_mod.SlotPool, "park",
                            _park_first_in_first_out(engine_mod.SlotPool.park))
    config, params, engine = _engine(ms, max_batch=2, num_blocks=256)
    rng = np.random.default_rng(8)
    sp = SamplingParams(max_tokens=2, temperature=0.0)
    documents = [rng.integers(1, 256, 385).tolist() for _ in range(2)]
    for document in documents:
        engine.generate([document], sp)
    requests = 3 * state_group_slots(2)
    for _ in range(requests):
        engine.generate([documents[0] + rng.integers(1, 256, 20).tolist()],
                        sp)
    stats = engine.stats()
    assert stats["prefix_hits"] == requests == stats["state_restores"]
    engine.generate([documents[1] + rng.integers(1, 256, 20).tolist()], sp)
    stats = engine.stats()
    hit = rule == "by_cost_until_hit"
    assert stats["state_restores"] == requests + hit
    assert stats["prefix_tokens_saved"] == 384 * (requests + hit)
    if hit:
        assert engine.block_manager.states.clock == 192


def test_a_turn_that_extends_its_own_prompt_restores_under_slot_pressure(ms):
    """No slot is free (three unshared prompts hold the pool's three parked
    snapshots), then an unshared prompt, then another, each parking one into
    a slot it has to recycle, then a turn that extends the first of the two:
    a hit that restores that prompt's snapshot. Prompts alike cost alike, so
    the never-hit snapshots left oldest first; as the newest to leave first,
    the very next park would have taken it."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(ms, max_batch=2, num_blocks=256)
    rng = np.random.default_rng(9)
    sp = SamplingParams(max_tokens=2, temperature=0.0)
    prompt = lambda: rng.integers(1, 256, 49).tolist()
    for _ in range(3):
        engine.generate([prompt()], sp)
    states = engine.block_manager.states
    assert states.counts()["parked"] == 3 and states.counts()["free"] == 1
    mine = prompt()
    engine.generate([mine], sp)
    engine.generate([prompt()], sp)
    assert engine.stats()["state_snapshots"] == 5
    assert engine.stats()["state_restores"] == 0
    engine.generate([mine + rng.integers(1, 256, 20).tolist()], sp)
    stats = engine.stats()
    assert stats["state_restores"] == 1
    assert stats["prefix_tokens_saved"] == 48


def _pool(total):
    from ray_tpu.llm.engine import SlotPool

    return SlotPool(total)


def test_slot_pool_recycles_the_cheapest_to_cut_again_aged():
    """Never-hit snapshots leave by credit (the clock at parking + cost):
    the cheap one first, then the one parked at the clock it set, then the
    oldest of three that cost alike."""
    pool = _pool(4)
    slots = {h: pool.park(h, cost) for h, cost in (
        (b"a", 48), (b"b", 16), (b"c", 48), (b"d", 48))}
    assert sorted(slots.values()) == [0, 1, 2, 3] and not pool.free
    assert pool.park(b"a", 48) is None              # first writer wins
    assert pool.park(b"e", 16) == slots[b"b"] and pool.clock == 16
    assert pool.park(b"f", 48) == slots[b"b"] and pool.clock == 32  # e's
    assert pool.park(b"g", 48) == slots[b"a"] and pool.clock == 48
    assert list(pool.parked) == [b"c", b"d", b"f", b"g"] or set(
        pool.parked) == {b"c", b"d", b"f", b"g"}
    assert pool.credit == {b"c": 48, b"d": 48, b"f": 80, b"g": 96}


def test_slot_pool_keeps_a_hit_snapshot_while_another_is_left():
    """A snapshot a hit attached goes only when none is left that no hit
    has, the least recently hit first; `forget` frees either kind."""
    pool = _pool(3)
    slots = {h: pool.park(h, 16) for h in (b"a", b"b", b"c")}
    assert pool.hit(b"a") == slots[b"a"] and pool.hit(b"b") == slots[b"b"]
    assert pool.hit(b"a") == slots[b"a"]            # b: least recently hit
    assert b"a" not in pool.credit and len(pool.parked) == 3
    assert pool.park(b"d", 16) == slots[b"c"]
    assert pool.park(b"e", 16) == slots[b"c"]       # d: the one cold left
    pool.hit(b"e")
    assert pool.park(b"f", 16) == slots[b"b"]       # all hot: b, then a
    pool.forget(b"a")
    assert pool.counts() == {"total": 3, "free": 1, "live": 0, "parked": 2}
    pool.forget()
    assert pool.counts()["free"] == 3 and not pool.credit


def test_slot_pool_holds_a_document_for_its_first_hit():
    """One snapshot that cost 2,048 tokens and then 100 that cost 16-128
    each, in a pool of 8: the document's is there for its first hit (under
    first in, first out it was the eighth park's), and it does not stay for
    ever: the clock comes to its credit in another hundred."""
    pool = _pool(8)
    rng = np.random.default_rng(3)
    document = pool.park(b"document", 2048)
    for i in range(100):
        pool.park(b"tail-%d" % i, int(rng.integers(16, 129)))
    assert 512 < pool.clock < 2048 and b"document" in pool.parked
    for i in range(100, 204):
        pool.park(b"tail-%d" % i, int(rng.integers(16, 129)))
    assert pool.clock == 2048 and b"document" not in pool.parked
    assert document in pool.parked.values()


def test_what_the_block_cannot_do_refuses_by_name(ms):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner

    config = ms.MiniCPMSALAConfig.tiny()
    block = config.serving_block()
    with pytest.raises(ValueError, match="tensor_parallel"):
        block.refuse(tensor_parallel=2, lora=False)
    with pytest.raises(ValueError, match="LoRA"):
        block.refuse(tensor_parallel=1, lora=True)
    with pytest.raises(ValueError, match="two strides"):
        ms.MiniCPMSALAConfig.tiny(kernel_size=16)
    with pytest.raises(ValueError, match="forced blocks inside topk"):
        ms.MiniCPMSALAConfig.tiny(topk=1)
    with pytest.raises(ValueError, match="pages of 4 tokens"):
        ModelRunner(config, ms.init_params(config, jax.random.key(0)),
                    num_blocks=16, block_size=4, attention_impl="reference",
                    chunk_size=16, max_batch=2)


def test_first_stage_scores_in_bfloat16_move_kept_blocks(ms, ref):
    """What "scores float32" buys: the same two rows with the first stage's
    sums rounded to bfloat16 before the max-pool keep other blocks at some
    positions."""
    import jax.numpy as jnp

    from ray_tpu.ops import block_sparse as bs

    config, params, tokens, _, (blocks, _) = _served(ms, "reference")
    exact = bs.block_scores_reference

    def rounded(*args, **kw):
        R = exact(*args, **kw)
        return R.astype(jnp.bfloat16).astype(jnp.float32)

    try:
        bs.block_scores_reference = rounded
        _, _, runner = _runner(ms)
        _, (moved, _) = _step_logits(runner, tokens, PROMPT)
    finally:
        bs.block_scores_reference = exact
    assert (moved != blocks).any()
