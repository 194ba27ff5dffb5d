"""Kimi Delta Attention (ops/kda.py) at tiny sizes on the CPU: statements of
one layer that must agree: the kernel's buffered step (a decode row: answered
from the state as the last fold left it and the rows buffered since, one row
written), its chunked WY form (a slice), and the `lax.scan` oracle beside
them, which is the recurrence as the publication writes it from `folded`; and
two in numpy float64 that the oracle itself is held to: the recurrence by
hand, and the buffered algebra by hand, with a term dropped for a control.

Four heads of 16 keys x 16 values, a buffer of 4 rows (`kda.FOLD`, 8, where a
test says so); the kernel runs interpreted with chunks of 16 rows in blocks of
8 (so that a slice is several chunks, a chunk has blocks on and below the
diagonal, and lengths do not divide) or of 8 in one block. A call starts from
slots whose buffers hold 0 .. fold - 1 rows and STALE rows behind them.

Tolerance: float32 sums in another order (the chunked form solves a triangular
system the recurrence never forms; the buffered step sums the rows since the
fold apart from S0): outputs and states agree to ~1e-6 of the largest; 2e-5
leaves an order of magnitude. A state kept in bfloat16 reads over 1e-3 (the
last test).
"""

import functools

import numpy as np
import pytest

import ray_tpu  # noqa: F401
from ray_tpu.ops.state_slots import fill_after

TOL = 2e-5
H, DK, DV, LAYERS, SLOTS = 4, 16, 16, 2, 6
FOLD = 4


@pytest.fixture(scope="module")
def kda(cpu_jax):
    from ray_tpu.ops import kda

    return kda


_STEPS = {}


def _step(kda, impl, chunk=16, sub=8):
    """`kda`, jitted once an `impl`, a chunk and a shape."""
    import jax

    key = (impl, chunk, sub)
    if key not in _STEPS:
        _STEPS[key] = jax.jit(functools.partial(
            kda.kda, impl=impl, chunk=chunk, sub=sub))
    return _STEPS[key]


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max()
                 / max(np.abs(want).max(), 1e-30))


def _unit(a):
    return a / np.linalg.norm(a, axis=-1, keepdims=True)


def _rows(seed, R, lowest=0.9):
    """q (scaled), k (unit), v, the gates' logs (gates uniform in [lowest,
    1) a channel) and beta in (0.1, 0.9), float32."""
    rng = np.random.default_rng(seed)
    return tuple(np.asarray(a, np.float32) for a in (
        _unit(rng.normal(size=(R, H, DK))) * DK ** -0.5,
        _unit(rng.normal(size=(R, H, DK))), rng.normal(size=(R, H, DV)),
        np.log(rng.uniform(lowest, 1.0, size=(R, H, DK))),
        rng.uniform(0.1, 0.9, size=(R, H))))


def _held(kda, seed=9, fold=FOLD, fill=None, lowest=0.5):
    """(state, buffer, fill) of every slot: S random, a buffer of `fold` rows
    [k | c | u] a head (c the logs summed down the rows) of which `fill`
    (random, or the one given, everywhere) are held and the rest is STALE."""
    rng = np.random.default_rng(seed)
    state = rng.normal(size=kda.state_shape(LAYERS, SLOTS, H, DK, DV))
    shape = kda.buffer_shape(LAYERS, SLOTS, H, DK, DV, fold)
    hb = kda.heads_a_step(H, DK)
    lead = shape[:3] + (fold, hb)
    buf = np.concatenate([
        _unit(rng.normal(size=lead + (DK,))),
        np.cumsum(np.log(rng.uniform(lowest, 1.0, size=lead + (DK,))), -3),
        rng.normal(size=lead + (DV,))], -1).reshape(shape)
    fills = (rng.integers(0, fold, kda.fill_shape(LAYERS, SLOTS))
             if fill is None else np.full(kda.fill_shape(LAYERS, SLOTS),
                                          fill))
    return (np.asarray(state, np.float32), np.asarray(buf, np.float32),
            np.asarray(fills, np.int32))


def _folded_by_hand(state, buf, fill):
    """`kda.folded` for ONE slot in float64: state (H, DK, DV), buf (J, T,
    LW), fill a number."""
    S = np.asarray(state, np.float64).copy()
    J = buf.shape[0]
    hb = H // J
    rows = np.asarray(buf, np.float64).reshape(J, -1, hb, buf.shape[-1])
    rows = np.moveaxis(rows, 1, 0).reshape(-1, H, buf.shape[-1])  # (r, H, LW)
    if fill == 0:
        return S
    k, c, u = rows[..., :DK], rows[..., DK:2 * DK], rows[..., 2 * DK:]
    S = np.exp(c[fill - 1])[..., None] * S
    for j in range(fill):
        S += (k[j] * np.exp(c[fill - 1] - c[j]))[..., None] * u[j][:, None, :]
    return S


def _by_hand(rows, s0):
    """The recurrence for ONE sequence in float64: rows as `_rows` gives
    them, s0 (H, DK, DV). -> (o (R, H, DV), the state after)."""
    q, k, v, log_a, beta = (np.asarray(a, np.float64) for a in rows)
    S = np.asarray(s0, np.float64).copy()
    out = []
    for t in range(q.shape[0]):
        S = np.exp(log_a[t])[..., None] * S
        u = beta[t][:, None] * (v[t] - np.einsum("hkv,hk->hv", S, k[t]))
        S = S + k[t][..., None] * u[:, None, :]
        out.append(np.einsum("hkv,hk->hv", S, q[t]))
    return np.stack(out), S


def _buffered_by_hand(rows, s0, fold, drop=None):
    """ops/kda.py's docstring in float64 for ONE sequence of decode rows: a
    row answered from S0 and the rows buffered since the fold, the buffer
    folded once in `fold` rows. `drop` leaves one term out: "correction"
    (sum_(j<t) m(k_t, j) u_j) or "decay" (the e^(c_t - c_j) inside m).
    -> (o, the state `folded` would give after the last row)."""
    q, k, v, log_a, beta = (np.asarray(a, np.float64) for a in rows)
    S0 = np.asarray(s0, np.float64).copy()
    ks, cs, us, out = [], [], [], []

    def m(x, j, c_t):
        decay = 1.0 if drop == "decay" else np.exp(c_t - cs[j])
        return np.sum(x * ks[j] * decay, -1)                       # (H,)

    def folded_now():
        if not ks:
            return S0
        S = np.exp(cs[-1])[..., None] * S0
        for j in range(len(ks)):
            S = S + (ks[j] * np.exp(cs[-1] - cs[j]))[..., None] \
                * us[j][:, None, :]
        return S

    for t in range(q.shape[0]):
        c_t = (cs[-1] if cs else 0.0) + log_a[t]
        seen = np.einsum("hkv,hk->hv", S0, k[t] * np.exp(c_t))
        if drop != "correction":
            for j in range(len(ks)):
                seen = seen + m(k[t], j, c_t)[:, None] * us[j]
        u_t = beta[t][:, None] * (v[t] - seen)
        ks.append(k[t]), cs.append(c_t), us.append(u_t)
        o = np.einsum("hkv,hk->hv", S0, q[t] * np.exp(c_t))
        for j in range(len(ks)):
            o = o + m(q[t], j, c_t)[:, None] * us[j]
        out.append(o)
        if len(ks) == fold:
            S0 = folded_now()
            ks, cs, us = [], [], []
    return np.stack(out), folded_now()


@pytest.mark.parametrize("drop,least", [(None, 0.0), ("correction", 1e-2),
                                        ("decay", 1e-2)])
def test_the_buffered_algebra_is_the_recurrence_and_every_term_counts(
        drop, least):
    """Forward substitution from S0 over the rows since the fold IS the
    recurrence (1e-12 in float64, across five folds), and with gates down to
    0.3 a channel neither the correction by the buffered rows nor the decay
    between two of them can be forgotten unseen: each control reads over
    1e-2, five hundred times the tolerance the kernel is held to."""
    rows = _rows(11, 23, lowest=0.3)
    s0 = np.random.default_rng(12).normal(size=(H, DK, DV))
    want_o, want_s = _by_hand(rows, s0)
    got_o, got_s = _buffered_by_hand(rows, s0, FOLD, drop)
    if drop is None:
        assert _rel(got_o, want_o) < 1e-12 and _rel(got_s, want_s) < 1e-12
    else:
        assert _rel(got_o, want_o) > least and least > 100 * TOL


# One call's rows: (lens, zero) of its sequences in the order of their rows,
# then, where the rows do not lie end to end from row 0 in 8-row steps, the
# rows before each sequence (rows of no sequence) and the call's R.
CASES = {
    "a_decode_row": ([1], [0]),
    "a_slice": ([16], [0]),
    "a_slice_that_ends_mid_chunk": ([21], [0]),
    "a_slice_from_position_zero": ([13], [1]),
    "two_sequences": ([21, 11], [1, 0]),
    "rows_and_slices_and_a_sequence_without_rows": ([1, 19, 0, 3, 1],
                                                    [0, 1, 0, 0, 0]),
    # The kernel reads rows where they lie: sequences that start off the 8-row
    # tiles, and chunks whose overhang falls on other sequences' rows.
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
    # (until PR 60 a decode row's step was the oracle's arithmetic in the
    # oracle's order and this case was held to the last bit; the buffered
    # step sums the rows since the fold apart from S0)
    "decode_rows_off_the_tiles_beside_a_slice": (
        [1, 1, 11, 1], [0, 1, 0, 0], [1, 0, 3, 2], 22),
}


def _call(lens, zero, gaps=None, R=None):
    import jax.numpy as jnp

    lens = np.asarray(lens)
    gaps = np.zeros_like(lens) if gaps is None else np.asarray(gaps)
    starts = np.cumsum(lens + gaps) - lens
    if R is None:
        R = -(-int(starts[-1] + lens[-1]) // 8) * 8
    assert R >= starts[-1] + lens[-1]
    slots = np.asarray([3, 0, 5, 4, 2, 1, 0][:len(lens)])
    # (a sequence without a row may name a live one's slot: it takes none)
    assert len(set(slots[lens > 0].tolist())) == int((lens > 0).sum())
    return R, tuple(jnp.asarray(a, jnp.int32)
                    for a in (slots, starts, lens, zero))


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("form", ["blocks16x8", "oneblock8"])
def test_the_kernel_is_the_oracles(kda, case, form):
    chunk, sub = {"blocks16x8": (16, 8), "oneblock8": (8, 8)}[form]
    R, seqs = _call(*CASES[case])
    rows, held = _rows(1, R, lowest=0.5), _held(kda)
    want = _step(kda, "reference")(*rows, *held, 1, *seqs)
    got = _step(kda, "pallas", chunk, sub)(*rows, *held, 1, *seqs)
    assert _rel(got[0], want[0]) < TOL
    # (the slot behind the last is nobody's: the oracle writes there what its
    # sequences without a row read)
    assert _rel(kda.folded(*got[1:])[:, :SLOTS],
                kda.folded(*want[1:])[:, :SLOTS]) < TOL
    # rows of no sequence are zero, as the oracle's
    slots, starts, lens, zero = (np.asarray(a) for a in seqs)
    live = np.zeros(R, bool)
    for at, n in zip(starts, lens):
        live[at:at + n] = True
    assert not np.asarray(got[0])[~live].any()
    assert np.isfinite(np.asarray(got[0])).all()
    got, want = ([np.asarray(a) for a in side[1:]] for side in (got, want))
    # layer 0, the slots of no sequence and of a sequence WITHOUT A ROW are
    # as they were, to the last byte: state, buffer and fill
    touched = set(slots[lens > 0].tolist())
    others = [i for i in range(SLOTS) if i not in touched]
    for now, was in zip(got, held):
        assert np.array_equal(now[0], was[0])
        assert np.array_equal(now[1, others], was[1, others])
    # the fill's rule: `fill_after` is the kernel's and the oracle's, a row
    # that joins leaves S where it was and lies behind the rows held
    assert np.array_equal(got[2][:, :SLOTS], want[2][:, :SLOTS])
    for slot, n, z in zip(slots, lens, zero):
        if n == 0:
            continue
        f0 = 0 if z else int(held[2][1, slot])
        after, folds = fill_after(f0, int(n), bool(z), FOLD)
        assert got[2][1, slot] == after
        if n == 1 and not folds:
            assert after == f0 + 1
            assert np.array_equal(got[0][1, slot], held[0][1, slot])
        hb = kda.heads_a_step(H, DK)
        assert _rel(got[1][1, slot, :, :after * hb],
                    want[1][1, slot, :, :after * hb]) < TOL if after else True


@pytest.mark.parametrize("fill", range(8))
def test_decode_rows_across_two_folds_from_every_fill(kda, fill):
    """2 x `kda.FOLD` + 3 decode rows of two sequences, a call a row, from a
    buffer that holds `fill` rows (and stale ones behind them), with gates
    down to 0.3 a channel (`_buffered_by_hand`'s controls say what a
    forgotten term reads there): kernel and oracle give the float64
    recurrence's outputs from `folded`, row after row, their fills go round
    by `fill_after`, and after the last row (state, buffer, fill) fold to the
    recurrence's state."""
    import jax.numpy as jnp

    fold = kda.FOLD
    assert fold == 8 and fill < fold
    n = 2 * fold + 3
    rows = _rows(20 + fill, 2 * n, lowest=0.3)
    start = _held(kda, seed=30 + fill, fold=fold, fill=fill, lowest=0.3)
    _, seqs = _call([1, 1], [0, 0], None, 2)
    hands = [_by_hand(tuple(a[i::2] for a in rows), _folded_by_hand(
        start[0][1, slot], start[1][1, slot], fill))
        for i, slot in enumerate((3, 0))]
    for impl in ("reference", "pallas"):
        held, outs, f = start, [], fill
        for t in range(n):
            o, *held = _step(kda, impl)(
                *(jnp.asarray(a[2 * t:2 * t + 2]) for a in rows), *held, 1,
                *seqs)
            outs.append(np.asarray(o))
            f, _ = fill_after(f, 1, False, fold)
            assert np.asarray(held[2])[1, [3, 0]].tolist() == [f, f]
        assert f == (fill + n) % fold
        for i, slot in enumerate((3, 0)):
            assert _rel(np.stack(outs)[:, i], hands[i][0]) < TOL, impl
            assert _rel(kda.folded(*held)[1, slot], hands[i][1]) < TOL, impl


def test_a_first_row_folds_at_once(kda):
    """A sequence's first row (`zero`) over a slot that holds a state and
    buffered rows of somebody gone: the zeros reach the slot with the row,
    S = k u^T with u = b v exactly, the buffer is left empty."""
    _, seqs = _call([1], [1], [0], 1)
    rows, held = _rows(7, 1), _held(kda, fill=3)
    for impl in ("reference", "pallas"):
        o, state, _, fill = _step(kda, impl)(*rows, *held, 0, *seqs)
        q, k, v, _, beta = (a[0] for a in rows)
        u = beta[:, None] * v
        assert np.asarray(fill)[0, 3] == 0
        assert _rel(np.asarray(state)[0, 3], k[..., None] * u[:, None]) < TOL
        assert _rel(np.asarray(o)[0],
                    np.sum(q * k, -1, keepdims=True) * u) < TOL


def test_the_wrapper_lays_no_plane(kda):
    """Around the `pallas_call` the wrapper moves no row: no gather and no
    scatter in `kda`'s jaxpr but the fills' (a number a sequence), and no
    array of more than R + CHUNK rows (the planes were (H, ceil128(R + 8 S +
    CHUNK), W), gathered and transposed by XLA a layer: 13% of a tick of
    `kimilinear-longout-closed64`, PERF.md section 6, PR 47), whatever the
    segments' starts."""
    import jax

    R, seqs = _call(*CASES["starts_off_the_tiles_and_rows_of_nobody"])
    chunk = 16
    held = _held(kda)
    jaxpr = jax.make_jaxpr(functools.partial(
        kda.kda, impl="pallas", interpret=True, chunk=chunk, sub=8))(
        *_rows(1, R), *held, 1, *seqs)

    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            if eqn.primitive.name == "pallas_call":
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)

    seen = list(equations(jaxpr.jaxpr))
    assert "pallas_call" in {e.primitive.name for e in seen}
    whole = {a.shape for a in held}
    most = (R + chunk) * H * (3 * DK + 2 * DV)
    for e in seen:
        shapes = [getattr(v.aval, "shape", ())
                  for v in list(e.invars) + list(e.outvars)]
        name = e.primitive.name
        if (name.startswith(("gather", "scatter"))
                or name in ("dynamic_slice", "sort", "transpose")):
            assert all(np.prod(shape) <= held[2].size for shape in shapes), (
                name, shapes)
        for shape in shapes:
            if shape and shape not in whole:
                assert shape[0] <= R + chunk and np.prod(shape) <= most, (
                    name, shape)


@pytest.mark.parametrize("lowest", [0.5, 0.05], ids=["gates0.5", "gates0.05"])
def test_the_chunked_form_is_the_recurrence_with_small_gates(kda, lowest):
    """Gates down to 0.5 a channel (0.5 ** 16 a chunk; 0.05 ** 16 = 1e-21):
    the chunk's running product is never inverted, so nothing overflows, and
    the chunked form still is the recurrence, which the float64 loop is held
    to as well."""
    R, seqs = _call([40], [0])
    rows, held = _rows(2, R, lowest), _held(kda, fill=0)
    want_o, want_s, *_ = _step(kda, "reference")(*rows, *held, 0, *seqs)
    got_o, got_s, *_ = _step(kda, "pallas")(*rows, *held, 0, *seqs)
    assert np.isfinite(np.asarray(got_o)).all()
    assert _rel(got_o, want_o) < TOL and _rel(got_s, want_s) < TOL
    hand_o, hand_s = _by_hand(rows, held[0][0, 3])
    assert _rel(np.asarray(want_o)[:40], hand_o[:40]) < TOL
    assert _rel(np.asarray(want_s)[0, 3], hand_s) < TOL


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_a_sequence_continued_across_calls_is_one_recurrence(kda, impl):
    """A slice from position 0, a slice that continues it, decode rows, a
    slice AFTER buffered rows (it folds them first and leaves the buffer
    empty) and a row more, each a call of its own: outputs and the last state
    are the float64 loop's over all the rows."""
    rows = _rows(3, 48)
    held = _held(kda)
    outs, fills = [], []
    for lo, n, zero in ((0, 19, 1), (19, 10, 0), (29, 1, 0), (30, 1, 0),
                        (31, 1, 0), (32, 9, 0), (41, 1, 0)):
        _, seqs = _call([n], [zero])
        part = tuple(np.concatenate([a[lo:lo + n],
                                     np.zeros((-n % 8,) + a.shape[1:],
                                              a.dtype)]) for a in rows)
        o, *held = _step(kda, impl)(*part, *held, 1, *seqs)
        outs.append(np.asarray(o)[:n])
        fills.append(int(held[2][1, 3]))
    assert fills == [0, 0, 1, 2, 3, 0, 1]
    hand_o, hand_s = _by_hand(tuple(a[:42] for a in rows),
                              np.zeros((H, DK, DV)))
    assert _rel(np.concatenate(outs), hand_o) < TOL
    assert _rel(np.asarray(kda.folded(*held))[1, 3], hand_s) < TOL


def test_a_snapshot_cut_mid_buffer_then_continued_is_the_uncut_run(kda):
    """State, buffer AND fill copied to another slot after 16 rows and 3
    decode rows (what `copy_state` does for the prefix cache, the buffer
    holding three of its four rows) fold to the recurrence's state there, and
    continued from the copy past a fold they equal the run that was never
    cut."""
    import jax.numpy as jnp

    rows = _rows(4, 32)
    step = _step(kda, "pallas")
    _, first = _call([16], [1])
    _, *held = step(*(a[:16] for a in rows), *_held(kda), 0, *first)
    one = lambda t, seqs, held: step(*(np.concatenate(
        [a[t:t + 1], np.zeros((7,) + a.shape[1:], a.dtype)]) for a in rows),
        *held, 0, *seqs)
    _, seqs = _call([1], [0])
    for t in range(16, 19):
        _, *held = one(t, seqs, held)
    assert int(held[2][0, 3]) == 3
    held = [jnp.asarray(a).at[:, 1].set(a[:, 3]) for a in held]  # slot 3 -> 1
    hand_o, hand_s = _by_hand(tuple(a[:19] for a in rows),
                              np.zeros((H, DK, DV)))
    assert _rel(np.asarray(kda.folded(*held))[0, 1], hand_s) < TOL
    seqs = (jnp.asarray([1], jnp.int32),) + seqs[1:]
    outs = []
    for t in range(19, 32):
        o, *held = one(t, seqs, held)
        outs.append(np.asarray(o)[0])
    assert int(held[2][0, 1]) == (3 + 13) % FOLD
    hand_o, hand_s = _by_hand(rows, np.zeros((H, DK, DV)))
    assert _rel(np.stack(outs), hand_o[19:]) < TOL
    assert _rel(np.asarray(kda.folded(*held))[0, 1], hand_s) < TOL


def test_a_state_kept_in_bfloat16_is_told_apart(kda):
    """The tolerance tells the stated precision: 24 decode rows whose state
    and buffered rows are rounded to bfloat16 after each read over 1e-3 of
    the float32 run."""
    import jax

    rows = _rows(5, 24)
    sound = rounded = _held(kda)
    _, seqs = _call([1], [0])
    outs = {"sound": [], "rounded": []}
    bf16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                              mantissa_bits=7)
    for t in range(24):
        part = tuple(np.concatenate([a[t:t + 1], np.zeros(
            (7,) + a.shape[1:], a.dtype)]) for a in rows)
        o, *sound = _step(kda, "pallas")(*part, *sound, 1, *seqs)
        outs["sound"].append(np.asarray(o)[0])
        o, state, buf, fill = _step(kda, "pallas")(*part, *rounded, 1, *seqs)
        rounded = (bf16(state), bf16(buf), fill)
        outs["rounded"].append(np.asarray(o)[0])
    assert _rel(np.stack(outs["rounded"]), np.stack(outs["sound"])) > 1e-3
