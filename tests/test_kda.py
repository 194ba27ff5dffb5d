"""Kimi Delta Attention (ops/kda.py) at tiny sizes on the CPU: three
statements of one layer that must agree: the kernel's recurrent step (a decode
row), its chunked WY form (a slice), and the `lax.scan` oracle beside them,
which is the recurrence as the publication writes it; and a fourth, numpy in
float64, that the oracle itself is held to.

Four heads of 16 keys x 16 values; the kernel runs interpreted with chunks of
16 rows in blocks of 8 (so that a slice is several chunks, a chunk has blocks
on and below the diagonal, and lengths do not divide) or of 8 in one block.

Tolerance: float32 sums in another order (the chunked form solves a triangular
system the recurrence never forms): outputs and states agree to ~1e-6 of the
largest; 2e-5 leaves an order of magnitude. A state kept in bfloat16 reads
over 1e-3 (the last test).
"""

import functools

import numpy as np
import pytest

import ray_tpu  # noqa: F401

TOL = 2e-5
H, DK, DV, LAYERS, SLOTS = 4, 16, 16, 2, 6


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


def _rows(seed, R, lowest=0.9):
    """q (scaled), k (unit), v, the gates' logs (gates uniform in [lowest,
    1) a channel) and beta in (0.1, 0.9), float32."""
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    return tuple(np.asarray(a, np.float32) for a in (
        unit(rng.normal(size=(R, H, DK))) * DK ** -0.5,
        unit(rng.normal(size=(R, H, DK))), rng.normal(size=(R, H, DV)),
        np.log(rng.uniform(lowest, 1.0, size=(R, H, DK))),
        rng.uniform(0.1, 0.9, size=(R, H))))


def _state(kda, seed=9):
    return np.asarray(np.random.default_rng(seed).normal(
        size=kda.state_shape(LAYERS, SLOTS, H, DK, DV)), np.float32)


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
    rows, state = _rows(1, R), _state(kda)
    want_o, want_s = _step(kda, "reference")(*rows, state, 1, *seqs)
    got_o, got_s = _step(kda, "pallas", chunk, sub)(*rows, state, 1, *seqs)
    assert _rel(got_o, want_o) < TOL
    assert _rel(got_s, want_s) < TOL
    # rows of no sequence are zero, as the oracle's
    slots, starts, lens, _ = (np.asarray(a) for a in seqs)
    live = np.zeros(R, bool)
    for at, n in zip(starts, lens):
        live[at:at + n] = True
    assert not np.asarray(got_o)[~live].any()
    assert np.isfinite(np.asarray(got_o)).all()
    # layer 0 and the slots of no sequence are as they were
    touched = set(slots[lens > 0].tolist())
    others = [i for i in range(SLOTS) if i not in touched]
    assert np.array_equal(np.asarray(got_s)[0], state[0])
    assert np.array_equal(np.asarray(got_s)[1, others], state[1, others])


def test_a_decode_rows_step_is_the_oracles_to_the_last_bit(kda):
    """The recurrent step is the oracle's arithmetic in the oracle's order:
    decode rows that start off the tiles, beside a slice, equal it exactly
    (o and S), which a tolerance would not show."""
    R, seqs = _call([1, 1, 11, 1], [0, 1, 0, 0], [1, 0, 3, 2], 22)
    rows, state = _rows(6, R), _state(kda)
    want_o, want_s = _step(kda, "reference")(*rows, state, 0, *seqs)
    got_o, got_s = _step(kda, "pallas")(*rows, state, 0, *seqs)
    for at, slot in ((1, 3), (2, 0), (19, 4)):
        assert np.array_equal(np.asarray(got_o)[at], np.asarray(want_o)[at])
        assert np.array_equal(np.asarray(got_s)[0, slot],
                              np.asarray(want_s)[0, slot])


def test_the_wrapper_lays_no_plane(kda):
    """Around the `pallas_call` the wrapper moves no row: no gather and no
    scatter in `kda`'s jaxpr, and no array of more than R + CHUNK rows (the
    planes were (H, ceil128(R + 8 S + CHUNK), W), gathered and transposed by
    XLA a layer: 13% of a tick of `kimilinear-longout-closed64`, PERF.md
    section 6, PR 47), whatever the segments' starts."""
    import jax

    R, seqs = _call(*CASES["starts_off_the_tiles_and_rows_of_nobody"])
    chunk = 16
    jaxpr = jax.make_jaxpr(functools.partial(
        kda.kda, impl="pallas", interpret=True, chunk=chunk, sub=8))(
        *_rows(1, R), _state(kda), 1, *seqs)

    def equations(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn
            if eqn.primitive.name == "pallas_call":
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(sub)

    seen = list(equations(jaxpr.jaxpr))
    names = {e.primitive.name for e in seen}
    assert "pallas_call" in names
    assert not [n for n in names if n.startswith(("gather", "scatter"))
                or n in ("dynamic_slice", "sort", "transpose")], names
    state_shape = kda.state_shape(LAYERS, SLOTS, H, DK, DV)
    most = (R + chunk) * H * (3 * DK + 2 * DV)
    for e in seen:
        for v in list(e.invars) + list(e.outvars):
            shape = getattr(v.aval, "shape", ())
            if shape and shape != state_shape:
                assert shape[0] <= R + chunk and np.prod(shape) <= most, (
                    e.primitive.name, shape)


@pytest.mark.parametrize("lowest", [0.5, 0.05], ids=["gates0.5", "gates0.05"])
def test_the_chunked_form_is_the_recurrence_with_small_gates(kda, lowest):
    """Gates down to 0.5 a channel (0.5 ** 16 a chunk; 0.05 ** 16 = 1e-21):
    the chunk's running product is never inverted, so nothing overflows, and
    the chunked form still is the recurrence, which the float64 loop is held
    to as well."""
    R, seqs = _call([40], [0])
    rows, state = _rows(2, R, lowest), _state(kda)
    want_o, want_s = _step(kda, "reference")(*rows, state, 0, *seqs)
    got_o, got_s = _step(kda, "pallas")(*rows, state, 0, *seqs)
    assert np.isfinite(np.asarray(got_o)).all()
    assert _rel(got_o, want_o) < TOL and _rel(got_s, want_s) < TOL
    hand_o, hand_s = _by_hand(rows, state[0, 3])
    assert _rel(np.asarray(want_o)[:40], hand_o[:40]) < TOL
    assert _rel(np.asarray(want_s)[0, 3], hand_s) < TOL


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_a_sequence_continued_across_calls_is_one_recurrence(kda, impl):
    """A slice from position 0, a slice that continues it, then decode rows,
    each a call of its own: outputs and the last state are the float64
    loop's over all the rows."""
    rows = _rows(3, 48)
    state = _state(kda)
    outs = []
    for lo, n, zero in ((0, 19, 1), (19, 13, 0), (32, 1, 0), (33, 1, 0)):
        _, seqs = _call([n], [zero])
        part = tuple(np.concatenate([a[lo:lo + n],
                                     np.zeros((-n % 8,) + a.shape[1:],
                                              a.dtype)]) for a in rows)
        o, state = _step(kda, impl)(*part, state, 1, *seqs)
        outs.append(np.asarray(o)[:n])
    hand_o, hand_s = _by_hand(tuple(a[:34] for a in rows),
                              np.zeros((H, DK, DV)))
    assert _rel(np.concatenate(outs), hand_o) < TOL
    assert _rel(np.asarray(state)[1, 3], hand_s) < TOL


def test_a_snapshot_restored_then_continued_is_the_uncut_run(kda):
    """The state copied to another slot after 16 rows (what `copy_state`
    does for the prefix cache) and continued from there equals the run that
    was never cut."""
    import jax.numpy as jnp

    rows = _rows(4, 32)
    step = _step(kda, "pallas")
    _, first = _call([16], [1])
    _, state = step(*(a[:16] for a in rows), _state(kda), 0, *first)
    state = jnp.asarray(state).at[:, 1].set(state[:, 3])      # slot 3 -> 1
    slots, starts, lens, zero = _call([16], [0])[1]
    o, state = step(*(a[16:] for a in rows), state, 0,
                    jnp.asarray([1], jnp.int32), starts, lens, zero)
    hand_o, hand_s = _by_hand(rows, np.zeros((H, DK, DV)))
    assert _rel(o, hand_o[16:]) < TOL
    assert _rel(np.asarray(state)[0, 1], hand_s) < TOL


def test_a_state_kept_in_bfloat16_is_told_apart(kda):
    """The tolerance tells the stated precision: 24 decode rows whose state is
    rounded to bfloat16 after each read over 1e-3 of the float32 run."""
    import jax

    rows = _rows(5, 24)
    sound = rounded = _state(kda)
    _, seqs = _call([1], [0])
    outs = {"sound": [], "rounded": []}
    for t in range(24):
        part = tuple(np.concatenate([a[t:t + 1], np.zeros(
            (7,) + a.shape[1:], a.dtype)]) for a in rows)
        o, sound = _step(kda, "pallas")(*part, sound, 1, *seqs)
        outs["sound"].append(np.asarray(o)[0])
        o, rounded = _step(kda, "pallas")(*part, rounded, 1, *seqs)
        rounded = jax.lax.reduce_precision(rounded, exponent_bits=8,
                                           mantissa_bits=7)
        outs["rounded"].append(np.asarray(o)[0])
    assert _rel(np.stack(outs["rounded"]), np.stack(outs["sound"])) > 1e-3
