"""The Mamba-2 recurrence (ops/ssd.py) at tiny sizes on the CPU: three
statements of one layer that must agree: the kernel's recurrent step (a decode
row), its chunked form (a slice), and the `lax.scan` oracle beside them, which
is the recurrence as the publication writes it; and a fourth, numpy in
float64, that the oracle itself is held to.

Eight heads of 16 values in 2 groups over a state of 16; the kernel runs
interpreted with chunks of 8 rows (so that a slice is several chunks and
lengths do not divide).

A decode row does not rewrite its state (PR 56): it joins a BUFFER beside it,
folded in once in `fold` rows (4 here, by the buffer's shape), so what the
tests compare is `ssd.folded(state, buffer, fill)`, the recurrence's S_t, and
the fill against `fill_after`'s host arithmetic.

Tolerance: float32 sums in another order (the chunked form sums a chunk's rows
by a matrix product the recurrence never forms): outputs and states agree to
~1e-6 of the largest; 2e-5 leaves an order of magnitude. A state kept in
bfloat16 reads over 1e-3 (the last test).
"""

import functools

import numpy as np
import pytest

import ray_tpu  # noqa: F401
from ray_tpu.ops.state_slots import fill_after

TOL = 2e-5
H, P, G, N, LAYERS, SLOTS, FOLD = 8, 16, 2, 16, 2, 7, 4


@pytest.fixture(scope="module")
def ssd(cpu_jax):
    from ray_tpu.ops import ssd

    return ssd


_STEPS = {}


def _step(ssd, impl, chunk=8):
    """`ssd`, jitted once an `impl`, a chunk and a shape."""
    import jax

    if (impl, chunk) not in _STEPS:
        _STEPS[impl, chunk] = jax.jit(functools.partial(
            ssd.ssd, impl=impl, chunk=chunk))
    return _STEPS[impl, chunk]


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max()
                 / max(np.abs(want).max(), 1e-30))


def _rows(seed, R):
    """x, dt (log-uniform in [1e-3, 1e-1], as the model draws its steps), A
    (-U(1, 16) a head), B and C a GROUP, float32."""
    rng = np.random.default_rng(seed)
    return tuple(np.asarray(a, np.float32) for a in (
        rng.normal(size=(R, H, P)),
        np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size=(R, H))),
        -rng.uniform(1.0, 16.0, size=(H,)),
        rng.normal(size=(R, G, N)), rng.normal(size=(R, G, N))))


def _state(ssd, seed=9, fold=FOLD):
    """(state, buffer, fill): a random state beside EMPTY buffers (whose
    stale rows are not zeros: nobody may read them)."""
    rng = np.random.default_rng(seed)
    return (np.asarray(rng.normal(
        size=ssd.state_shape(LAYERS, SLOTS, H, P, N)), np.float32),
        np.asarray(rng.normal(size=ssd.buffer_shape(
            LAYERS, SLOTS, H, G, P, N, fold)), np.float32),
        np.zeros(ssd.fill_shape(LAYERS, SLOTS), np.int32))


def _settled(ssd, held):
    """The recurrence's S_t of every layer and slot."""
    return np.asarray(ssd.folded(*held))


def _by_hand(rows, s0, lo, hi):
    """The recurrence for ONE sequence, rows [lo, hi), in float64, a HEAD at a
    time with its group's B and C: s0 (H, P, N). -> (y (hi - lo, H, P), the
    state after)."""
    x, dt, A, B, C = (np.asarray(a, np.float64) for a in rows)
    S = np.asarray(s0, np.float64).copy()
    out = []
    for t in range(lo, hi):
        y = np.zeros((H, P))
        for h in range(H):
            g = h // (H // G)
            S[h] = (np.exp(dt[t, h] * A[h]) * S[h]
                    + dt[t, h] * np.outer(x[t, h], B[t, g]))
            y[h] = S[h] @ C[t, g]
        out.append(y)
    return np.stack(out), S


# One call's rows: (lens, zero) of its sequences in the order of their rows,
# then, where the rows do not lie end to end from row 0 in 8-row steps, the
# rows before each sequence (rows of no sequence) and the call's R.
CASES = {
    "a_decode_row": ([1], [0]),
    "a_slice_of_whole_chunks": ([16], [0]),
    "a_slice_that_ends_inside_a_chunk": ([21], [0]),
    "a_slice_from_position_zero": ([13], [1]),
    "one_that_starts_at_zero_beside_one_that_continues": ([21, 11], [1, 0]),
    "decode_rows_and_slices_and_a_sequence_without_rows": (
        [1, 19, 0, 3, 1], [0, 1, 0, 0, 0]),
    "starts_off_the_tiles_and_rows_of_nobody": (
        [1, 5, 1, 1], [0, 1, 0, 0], [3, 2, 0, 4], 21),
    "a_slice_between_rows_that_overhangs_onto_them": (
        [1, 1, 1, 19, 1, 1], [0, 0, 0, 1, 0, 0]),
    "a_last_slice_that_overhangs_onto_the_spare_rows": (
        [1, 1, 21], [0, 0, 0], [0, 0, 0], 23),
    "sequences_without_rows_among_live_ones": (
        [0, 1, 0, 0, 9, 1, 0], [0, 0, 1, 0, 0, 0, 0], [0, 0, 0, 0, 2, 0, 0],
        13),
    "one_row_and_no_more": ([1], [1], [0], 1),
}


def _call(lens, zero, gaps=None, R=None):
    lens = np.asarray(lens)
    gaps = np.zeros_like(lens) if gaps is None else np.asarray(gaps)
    starts = np.cumsum(lens + gaps) - lens
    if R is None:
        R = -(-int(starts[-1] + lens[-1]) // 8) * 8
    assert R >= starts[-1] + lens[-1]
    slots = np.arange(len(lens))[::-1].copy()       # not the rows' order
    return (slots.astype(np.int32), starts.astype(np.int32),
            lens.astype(np.int32), np.asarray(zero, bool)), R


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_oracle_over_ragged_rows(ssd, case):
    """Decode rows and slices in ONE call: the kernel's outputs and the slots
    it wrote are the oracle's, rows of no sequence read zero, the slots of
    sequences without rows and every other layer are left as they were, and
    the junk slot is nobody's to read."""
    args, R = _call(*CASES[case])
    slots, starts, lens, zero = args
    rows, held = _rows(3, R), _state(ssd)
    want_y, *want = _step(ssd, "reference")(*rows, *held, 1, *args)
    got_y, *got = _step(ssd, "pallas")(*rows, *held, 1, *args)
    assert _rel(got_y, want_y) < TOL
    live = slots[lens > 0]
    got_s, want_s, state = (_settled(ssd, h) for h in (got, want, held))
    assert _rel(got_s[1, live], want_s[1, live]) < TOL
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    idle = [s for s in range(SLOTS) if s not in set(live.tolist())]
    for mine, was in zip(got, held):
        np.testing.assert_array_equal(np.asarray(mine)[1, idle],
                                      was[1, idle])
        np.testing.assert_array_equal(np.asarray(mine)[0], was[0])
    owned = np.zeros(R, bool)
    for s0, n in zip(starts, lens):
        owned[s0:s0 + n] = True
    assert not np.asarray(got_y)[~owned].any()


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_both_are_the_recurrence_by_hand(ssd, impl):
    """The oracle (and the kernel) against the equations in float64, a head
    at a time with its group's B and C: a slice that continues from its slot
    and one that starts from zeros."""
    args, R = _call([19, 6], [0, 1])
    slots, starts, lens, zero = args
    rows, held = _rows(5, R), _state(ssd)
    y, *after = _step(ssd, impl)(*rows, *held, 0, *args)
    for i in range(2):
        s0 = np.zeros((H, P, N)) if zero[i] else held[0][0, slots[i]]
        want_y, want_s = _by_hand(rows, s0, starts[i], starts[i] + lens[i])
        assert _rel(np.asarray(y)[starts[i]:starts[i] + lens[i]],
                    want_y) < TOL
        assert _rel(_settled(ssd, after)[0, slots[i]], want_s) < TOL


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunked_form_is_the_recurrence(ssd, chunk):
    """A sequence of 40 rows as ONE slice (the chunked form, several chunks
    that hand the state on) and as 40 decode rows, one call each, through the
    slot: the same outputs and the same state."""
    rows, held = _rows(7, 40), _state(ssd)
    slot = np.array([2], np.int32)
    one = lambda v: np.array([v], np.int32)
    whole_y, *whole = _step(ssd, "pallas", chunk)(
        *rows, *held, 1, slot, one(0), one(40), np.array([False]))
    ys = []
    for t in range(40):
        x, dt, A, B, C = rows
        y, *held = _step(ssd, "pallas", chunk)(
            x[t:t + 1], dt[t:t + 1], A, B[t:t + 1], C[t:t + 1], *held, 1,
            slot, one(0), one(1), np.array([False]))
        ys.append(np.asarray(y)[0])
    assert _rel(whole_y, np.stack(ys)) < TOL
    assert _rel(_settled(ssd, whole)[1, 2], _settled(ssd, held)[1, 2]) < TOL


def test_a_state_lives_over_the_rows_it_is_drawn_for(ssd):
    """With the model's own draw of dt and A a tenth of the heads keep over a
    third of a state after 100 rows, so that a carry dropped between chunks
    shows: the slice from its slot and the slice from zeros differ."""
    args, R = _call([24], [0])
    rows, held = _rows(11, R), _state(ssd)
    kept, *_ = _step(ssd, "pallas")(*rows, *held, 0, *args)
    dropped, *_ = _step(ssd, "pallas")(*rows, *held, 0, *args[:3],
                                       np.array([True]))
    assert _rel(dropped, kept) > 0.1


def test_a_bfloat16_state_is_told_apart(ssd):
    """The state rounded to bfloat16 between two calls reads three orders over
    the tolerance."""
    import jax

    args, R = _call([9, 1], [0, 0])
    rows, held = _rows(13, R), _state(ssd)
    _, s1, buf, fill = _step(ssd, "pallas")(*rows, *held, 0, *args)
    sound, *_ = _step(ssd, "pallas")(*rows, s1, buf, fill, 0, *args)
    rounded, *_ = _step(ssd, "pallas")(
        *rows, jax.lax.reduce_precision(s1, exponent_bits=8,
                                        mantissa_bits=7), buf, fill, 0, *args)
    assert _rel(rounded, sound) > 1e-3


# ---- the buffer beside the state (PR 56) -----------------------------------

# Calls in turn on three sequences (slots 4, 1, 6): each (lens, zero). The
# fold is 4 rows, so the decode rows cross several folds; a slice finds a
# part-filled buffer; a fresh one-row sequence folds at once; a sequence
# sits calls out.
RUNS = {
    "decode_rows_across_several_folds": [
        ([5, 3, 1], [1, 1, 1])] + [([1, 1, 1], [0, 0, 0])] * 11,
    "a_slice_finds_a_part_filled_buffer": [
        ([6, 1, 2], [1, 1, 1]), ([1, 1, 1], [0, 0, 0]),
        ([1, 1, 1], [0, 0, 0]), ([7, 1, 9], [0, 0, 0]),
        ([1, 1, 1], [0, 0, 0]), ([1, 3, 1], [0, 0, 0]),
        ([1, 1, 1], [0, 0, 0])],
    "a_sequence_sits_calls_out_and_one_starts_again": [
        ([3, 4, 1], [1, 1, 1]), ([1, 0, 1], [0, 0, 0]),
        ([1, 0, 1], [0, 0, 0]), ([0, 1, 1], [0, 0, 1]),
        ([1, 1, 0], [0, 0, 0]), ([1, 1, 1], [0, 1, 0]),
        ([1, 1, 1], [0, 0, 0]), ([1, 0, 1], [0, 0, 0])],
}


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("run", sorted(RUNS))
def test_the_buffered_rows_are_the_recurrence_by_hand(ssd, run, impl):
    """Over calls that cross several folds: every row's y and `folded(state,
    buffer, fill)` after every call are the recurrence by hand, row for row;
    the fill is `fill_after`'s host arithmetic; a sequence without a row
    keeps state, buffer and fill as they were."""
    slots = np.array([4, 1, 6], np.int32)
    held = _state(ssd, 17)
    by_hand = [np.zeros((H, P, N)) for _ in slots]
    fills = [0, 0, 0]
    folds = 0
    for call, (lens, zero) in enumerate(RUNS[run]):
        (_, starts, lens, zero), R = _call(lens, zero)
        rows = _rows(100 + call, R)
        before = [np.asarray(a) for a in held]
        y, *held = _step(ssd, impl)(*rows, *held, 1, slots, starts, lens,
                                    zero)
        settled = _settled(ssd, held)
        for i, slot in enumerate(slots):
            if lens[i] == 0:
                for now, was in zip(held, before):
                    np.testing.assert_array_equal(np.asarray(now)[1, slot],
                                                  was[1, slot])
                continue
            if zero[i]:
                by_hand[i] = np.zeros((H, P, N))
            want_y, by_hand[i] = _by_hand(rows, by_hand[i], starts[i],
                                          starts[i] + lens[i])
            assert _rel(np.asarray(y)[starts[i]:starts[i] + lens[i]],
                        want_y) < TOL, (call, i)
            assert _rel(settled[1, slot], by_hand[i]) < TOL, (call, i)
            fills[i], folded = fill_after(fills[i], int(lens[i]),
                                          bool(zero[i]), FOLD)
            folds += folded
            assert int(np.asarray(held[2])[1, slot]) == fills[i], (call, i)
        for now, was in zip(held, before):
            np.testing.assert_array_equal(np.asarray(now)[0], was[0])
    assert folds >= 4


@pytest.mark.parametrize("fold", [4, 8])
def test_a_decode_row_leaves_its_state_where_it_lies(ssd, fold):
    """Between two folds a decode row changes its slot's buffer and fill
    and NOT its state: the array is bit for bit what the fold left, by the
    kernel and by the oracle, at either fold."""
    one = lambda v: np.array([v], np.int32)
    for impl in ("reference", "pallas"):
        held = _state(ssd, 21, fold)
        states = []
        for t in range(2 * fold + 1):
            x, dt, A, B, C = _rows(200 + t, 1)
            _, *held = _step(ssd, impl)(x, dt, A, B, C, *held, 0, one(3),
                                        one(0), one(1), np.array([t == 0]))
            states.append(np.asarray(held[0])[0, 3])
            assert int(np.asarray(held[2])[0, 3]) == (
                0 if t == 0 else t % fold)
        for t in range(1, 2 * fold + 1):
            same = np.array_equal(states[t], states[t - 1])
            assert same == (t % fold != 0), (impl, t)


def test_the_buffers_shape_is_the_ops_to_lay(ssd):
    """16 heads a step at the published widths: a tile of 8 x 8 + 8 + 16 = 88
    rows of 128 lanes (44 KB beside the block's 512 KB of S); an odd block
    of heads has no pairs."""
    assert ssd.heads_a_step(128, 8, 64) == 16
    assert ssd.buffer_shape(5, 128, 128, 8, 64, 128) == (5, 129, 8, 88, 128)
    assert ssd.buffer_shape(2, 7, H, G, P, N, 4) == (2, 8, 2, 16, 32)
    assert ssd.fill_shape(5, 128) == (5, 129)
    with pytest.raises(ValueError):
        ssd.buffer_shape(1, 1, 6, 2, 16, 16)


# ---- heads whose B and C are their own (G = H) -------------------------------

# Calls of three sequences, (lens, zero) each: decode rows that join the
# buffer and fold it (twice, at a fold of 4), a sequence without a row, slices
# that end inside a chunk and find a part-filled buffer.
OWN_CALLS = [([1, 21, 1], [1, 1, 0]), ([1, 1, 1], [0, 0, 0]),
             ([1, 1, 1], [0, 0, 0]), ([1, 0, 1], [0, 0, 0]),
             ([1, 1, 1], [0, 0, 0]), ([9, 1, 1], [0, 0, 0]),
             ([1, 1, 17], [0, 0, 0])]


@pytest.mark.parametrize("impl", ["pallas", "reference"])
@pytest.mark.parametrize("heads,width,fold,chunk,calls", [
    (32, 16, 4, 8, 7), (4, 128, 8, 16, 4)],
    ids=["32x16_two_blocks", "4x128_published_widths"])
def test_heads_with_keys_of_their_own_are_the_recurrence_by_hand(
        ssd, impl, heads, width, fold, chunk, calls):
    """Every head a group of its own (a linear-attention layer: B = k, C = q,
    dt 1, one FIXED decay a head), at a head of 16 and at the published 128 x
    128: the interpreted kernel and the oracle alike give, over calls that
    cross folds and mix decode rows with slices, the recurrence written a
    head at a time in float64 with that head's own B and C, in every row's y
    and in `folded(..., own=True)`; the tile holds a B a head, packed as dt x
    is (`buffer_shape`)."""
    import jax

    Hn, Pn = heads, width
    rng = np.random.default_rng(5)
    layers, slots = 2, 5
    state = np.asarray(rng.normal(size=ssd.state_shape(
        layers, slots, Hn, Pn, Pn)), np.float32)
    shape = ssd.buffer_shape(layers, slots, Hn, Hn, Pn, Pn, fold)
    hb = ssd.heads_a_step(Hn, Hn, Pn)
    assert hb == min(16, Hn)
    assert shape == (layers, slots + 1, Hn // hb, fold * hb + hb, 2 * Pn)
    held = (state, np.asarray(rng.normal(size=shape), np.float32),
            np.zeros(ssd.fill_shape(layers, slots), np.int32))
    A = -rng.uniform(0.01, 1.0, size=(Hn,)).astype(np.float32)
    step = jax.jit(functools.partial(ssd.ssd, impl=impl, chunk=chunk))
    by_hand = [None] * slots
    fills = [0] * slots
    for lens, zero in OWN_CALLS[:calls]:
        R = sum(lens) + 3
        x, B, C = (np.asarray(rng.normal(size=(R, Hn, Pn)), np.float32)
                   for _ in range(3))
        starts = np.cumsum([0] + lens[:-1]).astype(np.int32)
        y, *held = step(x, np.ones((R, Hn), np.float32), A, B, C, *held, 1,
                        np.arange(3, dtype=np.int32), starts,
                        np.asarray(lens, np.int32), np.asarray(zero))
        want = np.zeros((R, Hn, Pn))
        for s, (n, fresh) in enumerate(zip(lens, zero)):
            if not n:
                continue
            if fresh or by_hand[s] is None:
                by_hand[s] = (np.zeros((Hn, Pn, Pn)) if fresh
                              else state[1, s].astype(np.float64))
            fills[s], _ = fill_after(fills[s], n, bool(fresh), fold)
            for t in range(starts[s], starts[s] + n):
                for h in range(Hn):
                    by_hand[s][h] = (np.exp(A[h]) * by_hand[s][h]
                                     + np.outer(x[t, h], B[t, h]))
                    want[t, h] = by_hand[s][h] @ C[t, h]
        assert _rel(y, want) < TOL
        settled = np.asarray(ssd.folded(*(a[1] for a in held), own=True))
        for s in range(3):
            if by_hand[s] is not None:
                assert _rel(settled[s], by_hand[s]) < TOL, s
        assert np.asarray(held[2])[1, :3].tolist() == fills[:3]
