"""Phi-4-mini-flash (models/phi4flash.py) against its plain reference, at tiny
sizes on the CPU with seeded weights: state-space layers whose state is a slot
a sequence beside the pages, one K/V layer read by the cross-decoder, gated
memory units, differential attention on row pools, the narrowing of a step's
rows before the cross-decoder, and the cache manager's state group
(llm/engine.py BlockManager, SlotPool) on the host alone.

Eight layers, every role present (0-3 Mamba / window, 4 Mamba + memory, 5
full, 6-7 GMU / cross); window 8 over pages of 4 with contexts of 40-60 tokens:
every sequence passes its window several times.

Tolerances: in float32 program and reference differ in the order of their sums
(paged online softmax against a dense one, a chunked scan against one scan):
logits agree to ~1e-6 of their largest value; 2e-5 leaves an order of
magnitude, and each control below reads over 1e-4 (most over 1e-2). In
bfloat16 (weights, K/V, products) the tiny model reads ~1e-2; 5e-2 holds it.
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
def pm(cpu_jax):
    from ray_tpu.models import phi4flash

    return phi4flash


@pytest.fixture(scope="module")
def ref(cpu_jax):
    from ray_tpu.models import phi4flash_reference

    return phi4flash_reference


def _runner(pm, config=None, impl="reference", seed=0, params=None,
            num_blocks=64, max_batch=4):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner

    config = config or pm.Phi4FlashConfig.tiny()
    if params is None:
        params = pm.init_params(config, jax.random.key(seed))
    return config, params, ModelRunner(
        config, params, num_blocks=num_blocks, block_size=4,
        attention_impl=impl, chunk_size=16, max_batch=max_batch)


def _step_logits(runner, tokens, n_prompt, after_step=None):
    """Chunked prefill of tokens[:, :n_prompt], then a token at a time, by
    `ModelRunner.step` given ONE table (the runner lays the window ring and
    the slots itself), as the benchmark's check drives it. -> logits at
    positions n_prompt - 1 .. total - 2."""
    rows, total = tokens.shape
    pages = -(-total // runner.block_size)
    tables = np.zeros((rows, runner.max_blocks_per_seq), np.int32)
    for i in range(rows):
        tables[i, :pages] = runner.num_blocks - 1 - i * pages - np.arange(
            pages)
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


def _tokens(seed, rows, n):
    return np.random.default_rng(seed).integers(1, 256, (rows, n)).astype(
        np.int32)


def test_the_reference_is_in_the_repo_twice_and_equal():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "ray_tpu", "models",
                           "phi4flash_reference.py")) as a, \
            open(os.path.join(BENCH, "phi4flash_reference.py")) as b:
        assert a.read() == b.read()


def test_the_published_layout_counts_the_model_cards_parameters(pm):
    """3,852 M: the layer layout reproduces the published 3.8B; a slot is
    3.23 MB; the drawn tree has the counted number of values."""
    import jax

    c = pm.Phi4FlashConfig()
    assert c.num_params() // 10 ** 6 == 3852
    assert c.state_bytes_per_sequence == 9 * 5120 * (16 * 4 + 3 * 2)
    tiny = pm.Phi4FlashConfig.tiny()
    tree = jax.eval_shape(lambda: pm.init_params(tiny, jax.random.key(0)))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree)) \
        == tiny.num_params()


# ---- (a) through ModelRunner.step, the mixed step and LLMEngine -----------

@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_chunked_prefill_then_decode_by_step_matches_the_reference(
        pm, ref, impl):
    """A 40-token prompt in chunks of 16, then 8 tokens one at a time (six
    windows of 8; the scan state and the convolution tail carried from step
    to step), for the jnp forms and for both kernels in interpret mode."""
    config, params, runner = _runner(pm, impl=impl)
    assert runner.group_pages == {"all": 64, "window": 64, "state": 8}
    assert runner.table_widths == {"all": 64, "window": 8, "state": 1}
    tokens = _tokens(1, 2, 48)
    got = _step_logits(runner, tokens, 40)
    want, _ = ref.logits_at(params, tokens, list(range(39, 47)),
                            sizes_of(config))
    assert _rel(got, want) < TOL


def test_bfloat16_weights_and_cache_stay_near_the_float32_reference(pm, ref):
    import jax.numpy as jnp

    config, params, runner = _runner(
        pm, pm.Phi4FlashConfig.tiny(dtype=jnp.bfloat16))
    assert runner.cache["ssm_state"].dtype == jnp.float32
    assert runner.cache["conv_tail"].dtype == jnp.bfloat16
    tokens = _tokens(1, 2, 48)
    want, _ = ref.logits_at(params, tokens, list(range(39, 47)),
                            sizes_of(config))
    assert _rel(_step_logits(runner, tokens, 40), want) < 5e-2


def _mixed_logits(runner, tokens, spans):
    """One `step_mixed_logits` launch a round: `spans` [[(row, start, n)]],
    each sequence's rows token-major in the order given, a slot a row of
    `tokens`. -> {(row, position): logits} of every span's last token."""
    S = runner.batch_bucket(runner.max_batch)
    pages = -(-tokens.shape[1] // runner.block_size)
    ring = runner.table_widths["window"]
    out = {}
    for spans_now in spans:
        T = sum(n for _, _, n in spans_now)
        Tb = -(-T // 8) * 8
        flat = np.zeros(Tb, np.int32)
        cu = np.zeros(S + 1, np.int32)
        q_pos, kv = np.zeros(S, np.int32), np.zeros(S, np.int32)
        tables = runner.zero_tables(S)
        rows_out = np.zeros(S, np.int32)
        at = 0
        for i, (row, start, n) in enumerate(spans_now):
            flat[at:at + n] = tokens[row, start:start + n]
            cu[i], cu[i + 1] = at, at + n
            q_pos[i], kv[i] = start, start + n
            tables["all"][i, :pages] = row * pages + np.arange(pages)
            tables["window"][i] = 63 - row * ring - np.arange(ring)
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
def test_ragged_mixed_steps_match_the_reference(pm, ref, impl):
    """Token-major launches that hold a slice from position 0, a slice that
    continues mid-sequence, and decode rows, of three sequences of unequal
    length that join and leave: every last-row logits equals the reference's
    full forward pass at that position."""
    config, params, runner = _runner(pm, impl=impl)
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
                                sizes_of(config))
        have = np.stack([got[row, p] for p in positions])[None]
        assert _rel(have, want) < TOL, row


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


def _engine(pm, impl="reference", max_batch=4, num_blocks=64, **kw):
    from ray_tpu.llm.engine import LLMEngine

    config, params, runner = _runner(pm, impl=impl, num_blocks=num_blocks,
                                     max_batch=max_batch)
    return config, params, LLMEngine(runner, max_batch_size=max_batch,
                                     prefill_chunk=16, **kw)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_engine_matches_the_reference_as_sequences_join_and_leave(
        pm, ref, impl):
    """Mixed ticks with one step of lookahead: six requests of unequal
    lengths through four rows, so that sequences join while others decode and
    leave at different ticks; every greedy token is the reference's."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(pm, impl)
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
    sizes = sizes_of(config)
    for rid, prompt in zip(ids, prompts):
        out = done[rid].output_token_ids
        assert out == _reference_greedy(ref, params, sizes, prompt, out)
    stats = engine.stats()
    assert stats["lookahead_ticks"] > 10
    ticks = [t for t in engine.tick_records() if t["ssm_rows"]]
    assert all(t["ssm_rows"] == t["used"] for t in ticks)
    assert all(t["ssm_seqs"] == t["cross_rows"]
               == t["prefill_rows"] + t["decode_rows"] for t in ticks)
    assert any(t["prefill_rows"] and t["decode_rows"] for t in ticks)
    groups = stats["kv_groups"]
    assert groups["state"]["live"] == groups["all"]["live"] == 0


# ---- (b) the scan kernel ---------------------------------------------------

def test_scan_kernel_matches_the_lax_scan_on_ragged_segments(cpu_jax):
    """A decode row, a slice, a slice that starts mid-sequence from a stored
    state, a sequence that starts at position 0 in a slot that held another's
    state, and a padding sequence, in one launch: y and every slot equal the
    `lax.scan` form's; slots of no sequence (and the other layers') are left
    as they were."""
    import jax.numpy as jnp

    from ray_tpu.ops import ssm_scan as ss

    rng = np.random.default_rng(0)
    R, d_i, N, slots, layers = 48, 256, 4, 6, 2
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    dt, x, B, C = f32(R, d_i) - 2.0, f32(R, d_i), f32(R, N), f32(R, N)
    A = -jnp.exp(f32(N, d_i))
    state = f32(*ss.state_shape(layers, slots, N, d_i))
    starts = jnp.asarray([0, 1, 18, 29, 34], jnp.int32)
    lens = jnp.asarray([1, 17, 11, 5, 0], jnp.int32)
    slot = jnp.asarray([3, 0, 5, 1, 2], jnp.int32)
    zero = jnp.asarray([False, False, False, True, True])
    args = (dt, x, B, C, A, state, 1, slot, starts, lens, zero)
    y0, s0 = ss.ssm_scan(*args, impl="reference")
    y1, s1 = ss.ssm_scan(*args, impl="pallas")
    assert np.abs(np.asarray(y0)).max() > 1.0
    np.testing.assert_allclose(y1, y0, atol=2e-5)
    np.testing.assert_allclose(s1, s0, atol=2e-5)
    np.testing.assert_array_equal(s0[0], state[0])
    for idle in (2, 4):     # a padding sequence's slot, and nobody's
        np.testing.assert_array_equal(s0[1, idle], state[1, idle])
    np.testing.assert_array_equal(np.asarray(y0)[34:], 0.0)
    # the sequence that starts at 0 does not see what its slot held
    fresh = state.at[1, 1].set(0.0)
    y2, _ = ss.ssm_scan(dt, x, B, C, A, fresh, 1, slot, starts, lens, zero,
                        impl="pallas")
    np.testing.assert_array_equal(np.asarray(y2)[29:34],
                                  np.asarray(y1)[29:34])


def test_ragged_convolution_reads_the_tail_and_leaves_the_next(cpu_jax):
    import jax.numpy as jnp

    from ray_tpu.ops import ssm_scan as ss

    rng = np.random.default_rng(1)
    d_i, taps = 8, 4
    u = jnp.asarray(rng.normal(size=(12, d_i)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(3, taps - 1, d_i)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(taps, d_i)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(d_i,)), jnp.float32)
    starts, lens = np.asarray([0, 1, 8]), np.asarray([1, 7, 2])
    seq = np.repeat(np.arange(3), lens)
    seq = np.concatenate([seq, np.full(12 - len(seq), 2)])
    local = np.arange(12) - starts[seq]
    got, after = ss.ragged_conv(u, tail, w, b, jnp.asarray(seq),
                                jnp.asarray(local), jnp.asarray(starts),
                                jnp.asarray(lens))
    for s in range(3):
        rows = np.concatenate([np.asarray(tail[s]),
                               np.asarray(u[starts[s]:starts[s] + lens[s]])])
        for t in range(lens[s]):
            want = np.asarray(b) + sum(
                np.asarray(w[j]) * rows[t + j] for j in range(taps))
            np.testing.assert_allclose(got[starts[s] + t], want, atol=1e-5)
        np.testing.assert_allclose(after[s], rows[-(taps - 1):], atol=0)


# ---- (c) the pair form of the K/V kernel ------------------------------------

@pytest.mark.parametrize("window", [None, 8])
def test_pair_form_matches_two_softmaxes_a_pair(cpu_jax, window):
    """Row pools of kv PAIRS and half-zero query rows through the K/V kernel
    (interpret mode), full and window form, against the two softmaxes of the
    equations computed head by head over the same context."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    rng = np.random.default_rng(5)
    H, K, hd, ps, pages, S = 8, 4, 16, 4, 16, 2
    ctx = np.asarray([29, 18])
    n = np.asarray([5, 1])              # a slice and a decode row
    k = rng.normal(size=(S, 32, K, hd)).astype(np.float32)
    v = rng.normal(size=(S, 32, K, hd)).astype(np.float32)
    q = rng.normal(size=(6, H, hd)).astype(np.float32)
    width = 8 if window is None else 5
    k_pool = np.zeros((1, pages * S, ps, K * hd), np.float32)
    v_pool = np.zeros_like(k_pool)
    tables = np.zeros((S, width), np.int32)
    for s in range(S):
        for p in range(-(-ctx[s] // ps)):
            if window is not None and p < (ctx[s] - n[s] - window + 1) // ps:
                continue        # behind every window: no page
            page = s * pages + p
            tables[s, p % width if window is not None else p] = page
            k_pool[0, page] = k[s, p * ps:(p + 1) * ps].reshape(ps, -1)
            v_pool[0, page] = v[s, p * ps:(p + 1) * ps].reshape(ps, -1)
    cu = np.asarray([0, 5, 6], np.int32)
    got = pa.ragged_paged_attention_unified(
        pa.pair_queries(jnp.asarray(q)), jnp.asarray(k_pool),
        jnp.asarray(v_pool), 0, jnp.asarray(tables),
        jnp.asarray(ctx, jnp.int32), jnp.asarray(ctx - n, jnp.int32),
        jnp.asarray(cu), scale=hd ** -0.5, window=window, kv_heads=K // 2)
    got = np.asarray(got).reshape(6, H // 2, 2, 2 * hd)
    for s in range(S):
        for t in range(n[s]):
            pos = ctx[s] - n[s] + t
            lo = 0 if window is None else max(0, pos - window + 1)
            for head in range(H):
                kv = head // (H // K) // 2 * 2 + head % 2
                scores = k[s, lo:pos + 1, kv] @ q[cu[s] + t, head] * hd ** -0.5
                p = np.exp(scores - scores.max())
                pair = head // (H // K) // 2
                values = v[s, lo:pos + 1, 2 * pair:2 * pair + 2].reshape(
                    -1, 2 * hd)
                np.testing.assert_allclose(
                    got[cu[s] + t, head // 2, head % 2],
                    (p / p.sum()) @ values, atol=2e-5)


# ---- (d) the narrowing -------------------------------------------------------

def test_narrowed_rows_give_the_logits_of_all_rows(pm, monkeypatch):
    """The cross-decoder over the S last rows gives what it gives over all T
    rows (it holds no cache: a row that is not its sequence's last leaves
    nothing there), and the engine counts S rows a tick."""
    tokens = _tokens(6, 3, 40)
    spans = [[(0, 0, 16), (1, 0, 9)], [(0, 16, 12), (1, 9, 1), (2, 0, 7)]]
    _, _, narrow = _runner(pm)
    assert narrow._narrows
    got = _mixed_logits(narrow, tokens, spans)
    monkeypatch.setattr(pm.Block, "narrow_at", None)
    _, _, wide = _runner(pm)
    assert not wide._narrows
    want = _mixed_logits(wide, tokens, spans)
    for key in want:
        assert _rel(got[key], want[key]) < TOL


# ---- (e) prefix hits need a snapshot ----------------------------------------

def test_a_prefix_hit_goes_down_to_the_snapshot_and_no_deeper(pm, ref):
    """A prompt served twice: the second run is a hit down to the snapshot
    taken where the first's prefill crossed its last whole page, and emits
    the same tokens. A prompt that shares a shorter prefix finds pages deeper
    than any snapshot: cut short to a miss. Once the snapshot is evicted (its
    page recycled) the same prompt misses."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(pm, num_blocks=40)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 256, 47).tolist()      # 11 whole pages + 3
    sp = SamplingParams(max_tokens=8, temperature=0.0)
    sizes = sizes_of(config)
    cold = engine.generate([prompt], sp)[0].output_token_ids
    assert cold == _reference_greedy(ref, params, sizes, prompt, cold)
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
    assert stats["prefix_hits_cut_short"] == 0
    assert sum(t["state_restores"] for t in engine.tick_records()) == 1

    shorter = prompt[:30] + rng.integers(1, 256, 9).tolist()
    out = engine.generate([shorter], sp)[0].output_token_ids
    assert out == _reference_greedy(ref, params, sizes, shorter, out)
    stats = engine.stats()
    assert stats["prefix_hits"] == 1 and stats["prefix_hits_cut_short"] == 1
    assert stats["prefix_tokens_saved"] == 44

    # Churn the 40-page pool until the first prompt's pages are recycled.
    for _ in range(4):
        engine.generate([rng.integers(1, 256, 50).tolist()], sp)
    hashes = engine.block_manager.prefix_hashes(prompt)
    assert hashes[10] not in engine.block_manager.states.parked
    before = engine.stats()["prefix_hits"]
    again = engine.generate([prompt], sp)[0].output_token_ids
    assert again == cold and engine.stats()["prefix_hits"] == before


def test_match_prefix_wants_pages_a_window_tail_and_a_snapshot(cpu_jax):
    """The allocator alone: a chain of 6 pages with snapshots under pages 2
    and 4 hits at 4 (the deepest boundary with all three); without the window
    tail there, at 2... down to a miss."""
    from ray_tpu.llm.engine import BlockManager, _Request
    from ray_tpu.llm.sampling import SamplingParams

    def manager():
        bm = BlockManager(32, 4, side_groups={"window": (32, 8)},
                          state_slots=4)
        owner = _Request("a", list(range(1, 30)), SamplingParams())
        owner.prefix_hashes = bm.prefix_hashes(owner.prompt)
        bm.allocate(owner, 29)
        bm.allocate_side(owner, 29)
        bm.hold_state(owner)
        for j in range(6):
            bm.register_block(owner, j, owner.prefix_hashes[j])
        return bm, owner

    bm, owner = manager()
    for j in (1, 3):
        assert bm.states.park(owner.prefix_hashes[j], 4 * (j + 1)) is not None
    assert bm.states.park(owner.prefix_hashes[3], 16) is None   # first wins
    req = _Request("b", owner.prompt, SamplingParams())
    assert bm.match_prefix(req, owner.prefix_hashes) == 16
    assert req.restore_from == bm.states.parked[owner.prefix_hashes[3]]
    assert bm.prefix_hits_cut_short == 1            # the chain went to 6
    assert bm.group_counts()["state"] == {
        "total": 4, "free": 1, "live": 1, "parked": 2}

    bm, owner = manager()
    for j in (1, 3):
        bm.states.park(owner.prefix_hashes[j], 4 * (j + 1))
    window = bm.side["window"]      # page 3's window tail is pages 2, 3
    page = window.cached.pop(owner.prefix_hashes[2])
    del window.block_hash[page]
    req = _Request("c", owner.prompt, SamplingParams())
    assert bm.match_prefix(req, owner.prefix_hashes) == 8
    bm.states.forget(owner.prefix_hashes[1])
    req = _Request("d", owner.prompt, SamplingParams())
    assert bm.match_prefix(req, owner.prefix_hashes) == 0
    assert req.restore_from is None


# ---- (f) preemption, (g) slots ----------------------------------------------

def test_preemption_restarts_from_a_zero_state(pm, ref):
    """A pool too small for three growing sequences: the newest is preempted
    (its pages and its slot released), re-admitted into whatever slot is free
    and recomputed from position 0; every output is still the reference's
    greedy one, and nothing is left held."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(pm, max_batch=3, num_blocks=30,
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
    assert preempted
    sizes = sizes_of(config)
    for out, prompt in zip(outs, prompts):
        assert out.output_token_ids == _reference_greedy(
            ref, params, sizes, prompt, out.output_token_ids)
    groups = engine.stats()["kv_groups"]
    assert groups["state"] == {"total": 6, "free": 6, "live": 0, "parked": 0}
    assert groups["all"]["free"] == 30


@pytest.mark.parametrize("how", ["finish", "abort", "drop_all"])
def test_no_slot_leaks(pm, how):
    from ray_tpu.llm.sampling import SamplingParams

    _, _, engine = _engine(pm)
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
    elif how == "drop_all":
        engine.drop_all()
    else:
        while engine.has_unfinished():
            engine.step()
    state = engine.stats()["kv_groups"]["state"]
    assert state["live"] == 0
    assert state["free"] + state["parked"] == state["total"] == 8
    if how == "drop_all":
        assert state["parked"] == 0


# ---- (h) what cannot carry a slot refuses in one line -----------------------

def test_what_cannot_carry_a_slot_refuses(pm):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, engine = _engine(pm)
    runner = engine.runner
    engine.add_request(list(range(1, 12)),
                       SamplingParams(max_tokens=4, temperature=0.0), "r")
    for _ in range(3):
        engine.step()
    groups = r"layer groups \['all', 'window', 'state'\]"
    with pytest.raises(ValueError, match="export_request.*" + groups):
        engine.export_request("r")
    with pytest.raises(ValueError, match="adopt_request.*" + groups):
        engine.adopt_request({"id": "x", "prompt": [1], "output": [2],
                              "seed": 0, "params": {}})
    with pytest.raises(ValueError, match="gather_pages.*" + groups):
        runner.gather_pages([0])
    with pytest.raises(ValueError, match="scatter_pages.*" + groups):
        runner.scatter_pages([0])
    assert engine.adopt_prefix({"weights_version": 0, "entries": []}) == 0
    assert engine.export_prefixes() is None
    engine.attach_prefix_store(host_tier=object(), cluster_store=object())
    assert engine.host_prefix_tier is None and engine.cluster_store is None
    with pytest.raises(ValueError, match="speculative_ngram.*state group"):
        LLMEngine(runner, max_batch_size=4, speculative_ngram=2)
    with pytest.raises(ValueError, match="phi4flash: tensor_parallel"):
        runner.block.refuse(tensor_parallel=2, lora=False)
    with pytest.raises(ValueError, match="phi4flash: LoRA"):
        runner.block.refuse(tensor_parallel=1, lora=True)
    with pytest.raises(ValueError, match="one block table a group"):
        runner.step_mixed_logits(
            np.zeros(8, np.int32), np.zeros(4, np.int32),
            np.zeros(4, np.int32), np.zeros(5, np.int32),
            np.zeros((4, 64), np.int32), np.zeros(4, np.int32))


# ---- (i) controls: each MUST fail the comparison ----------------------------

STEPS = list(range(0, 32, 16)) + list(range(32, 44))


def _zero(name):
    def after_step(runner):
        import jax.numpy as jnp

        runner.cache[name] = jnp.zeros_like(runner.cache[name])
    return after_step


@pytest.mark.parametrize("fault", [
    ("state_not_carried", STEPS), ("tail_not_carried", STEPS),
    "memory_after_gate", "no_lambda", "no_window", "bf16_state"],
    ids=lambda f: f if isinstance(f, str) else f[0])
def test_a_reference_with_one_term_dropped_is_told_apart(pm, ref, fault):
    """The sound program against the reference with ONE term left out (the
    scan state or the convolution tail not carried from step to step, the
    memory taken after the gate, the lambda term dropped, the window
    ignored, the scan state rounded to bfloat16 every step): each moves the
    logits by far more than the tolerance the sound pair meets."""
    config, params, runner = _runner(pm)
    tokens = _tokens(2, 2, 44)
    got = _step_logits(runner, tokens, 32)
    positions = list(range(31, 43))
    sound, _ = ref.logits_at(params, tokens, positions, sizes_of(config))
    assert _rel(got, sound) < TOL
    faulty, _ = ref.logits_at(params, tokens, positions, sizes_of(config),
                              fault)
    assert _rel(got, faulty) > (1e-4 if fault == "bf16_state" else 1e-2)


@pytest.mark.parametrize("name", ["ssm_state", "conv_tail"])
def test_a_program_that_drops_its_slot_between_steps_fails(pm, ref, name):
    """The same two controls on the program's side: a runner whose slot
    array is zeroed after every step reads what the reference reads with
    that term not carried, and not what the sound reference reads."""
    config, params, runner = _runner(pm)
    tokens = _tokens(2, 2, 44)
    got = _step_logits(runner, tokens, 32, after_step=_zero(name))
    positions = list(range(31, 43))
    sound, _ = ref.logits_at(params, tokens, positions, sizes_of(config))
    fault = ("state_not_carried" if name == "ssm_state"
             else "tail_not_carried", STEPS)
    faulty, _ = ref.logits_at(params, tokens, positions, sizes_of(config),
                              fault)
    assert _rel(got, sound) > 1e-2
    assert _rel(got, faulty) < TOL


# ---- the sampler filters the rows that sample --------------------------------

@pytest.mark.parametrize("sampling", [3, 12], ids=["few", "every"])
def test_sampling_rows_get_the_filter_of_every_row(pm, sampling):
    """`_filter_sampled` gathers the rows with a temperature (up to a quarter
    of them) and filters those alone; past that it filters every row. Either
    way a sampling row reads what `_filter_logits` over all rows gives it."""
    import jax.numpy as jnp

    _, _, runner = _runner(pm)
    rng = np.random.default_rng(sampling)
    n = 16
    logits = jnp.asarray(rng.normal(size=(n, 256)) * 4, jnp.float32)
    temps = np.zeros(n, np.float32)
    rows = rng.permutation(n)[:sampling]
    temps[rows] = rng.uniform(0.5, 1.2, sampling)
    top_ks = jnp.asarray(rng.integers(0, 40, n), jnp.int32)
    top_ps = jnp.asarray(rng.uniform(0.5, 1.0, n), jnp.float32)
    temps = jnp.asarray(temps)
    got = runner._filter_sampled(logits, temps, top_ks, top_ps)
    want = runner._filter_logits(logits, temps, top_ks, top_ps)
    np.testing.assert_array_equal(np.asarray(got)[rows],
                                  np.asarray(want)[rows])
    assert np.isfinite(np.asarray(got)).all() or sampling == 12
