"""The contract of state beside pages (ray_tpu/ops/state_slots.py), held over
the four wrappers that take it: ops/ssm_scan.py (the slot half: it has no
buffer), ops/power_retention.py, ops/ssd.py and ops/kda.py, at the smallest
shapes their interpreted kernels take. What one kernel's arithmetic owes its
own reference stays in tests/test_kda.py, tests/test_ssd_ops.py and
tests/test_power_retention.py.

A slot's arrays are made by the ORACLE itself, so that no layout is written
down here: a slice from position 0 (S written, the buffer empty), then decode
rows that join until the buffer holds what a case asks. Every call of a
kernel has one shape (24 rows, 4 sequences), so each wrapper is compiled once
an `impl`.
"""

import functools
import types

import numpy as np
import pytest

import ray_tpu  # noqa: F401
from ray_tpu.ops.state_slots import fill_after

TOL = 2e-5
R, SEQS, LAYERS, SLOTS, LAYER = 24, 4, 2, 5, 1
KERNELS = ["ssm_scan", "power_retention", "ssd", "kda"]
BUFFERED = KERNELS[1:]


def _normal(rng, *shape):
    return rng.normal(size=shape).astype(np.float32)


def _gates(rng, *shape):
    return np.log(rng.uniform(0.5, 0.999, size=shape)).astype(np.float32)


def _ssm_scan():
    from ray_tpu.ops import ssm_scan as op

    N, D = 4, 128
    return types.SimpleNamespace(
        fn=op.ssm_scan, kw={}, fold=None, folded=lambda state: state,
        shapes=[op.state_shape(LAYERS, SLOTS, N, D)],
        rows=lambda rng: (_normal(rng, R, D), _normal(rng, R, D),
                          _normal(rng, R, N), _normal(rng, R, N),
                          -rng.uniform(1, 4, (N, D)).astype(np.float32)))


def _power_retention():
    from ray_tpu.ops import power_retention as op

    H, K, HD = 4, 2, 8
    sizes = (LAYERS, SLOTS, K, HD)

    def rows(rng):
        # q and k share a direction: a weight is (q . k) ** 2, and the square
        # of a difference of large terms has no digits to compare
        both = _normal(rng, HD)
        return (both + 0.4 * _normal(rng, R, H, HD),
                both + 0.4 * _normal(rng, R, K, HD), _normal(rng, R, K, HD),
                _gates(rng, R, K))

    return types.SimpleNamespace(
        fn=op.power_retention, kw=dict(scale=HD ** -0.5, eps=1e-6),
        fold=op.fold_rows(HD), rows=rows,
        folded=lambda *held: np.concatenate(
            [np.asarray(a).reshape(LAYERS, SLOTS + 1, -1)
             for a in op.folded(*held)], -1),
        shapes=[op.state_shape(*sizes), op.norm_shape(*sizes),
                op.buffer_shape(*sizes), op.fill_shape(LAYERS, SLOTS)])


def _ssd():
    from ray_tpu.ops import ssd as op

    H, P, G, N, fold = 8, 16, 2, 16, 4
    return types.SimpleNamespace(
        fn=op.ssd, kw=dict(chunk=8), fold=fold, folded=op.folded,
        shapes=[op.state_shape(LAYERS, SLOTS, H, P, N),
                op.buffer_shape(LAYERS, SLOTS, H, G, P, N, fold),
                op.fill_shape(LAYERS, SLOTS)],
        rows=lambda rng: (
            _normal(rng, R, H, P),
            np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (R, H))).astype(
                np.float32),
            -rng.uniform(1, 16, (H,)).astype(np.float32),
            _normal(rng, R, G, N), _normal(rng, R, G, N)))


def _kda():
    from ray_tpu.ops import kda as op

    H, DK, DV, fold = 4, 16, 16, 4
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    return types.SimpleNamespace(
        fn=op.kda, kw=dict(chunk=16, sub=8), fold=fold, folded=op.folded,
        shapes=[op.state_shape(LAYERS, SLOTS, H, DK, DV),
                op.buffer_shape(LAYERS, SLOTS, H, DK, DV, fold),
                op.fill_shape(LAYERS, SLOTS)],
        rows=lambda rng: (
            unit(_normal(rng, R, H, DK)) * DK ** -0.5,
            unit(_normal(rng, R, H, DK)), _normal(rng, R, H, DV),
            _gates(rng, R, H, DK),
            rng.uniform(0.1, 0.9, (R, H)).astype(np.float32)))


_CASES = {}


@pytest.fixture(scope="module")
def case(cpu_jax):
    """name -> the kernel's sizes, rows and wrapper, jitted once an impl."""
    def get(name):
        if name not in _CASES:
            c = _CASES[name] = globals()["_" + name]()
            c.step = {impl: cpu_jax.jit(functools.partial(
                c.fn, impl=impl, **c.kw)) for impl in ("reference", "pallas")}
        return _CASES[name]

    return get


def _call(c, impl, rows, held, slots, starts, lens, zero):
    """-> (y, the slot arrays after), numpy."""
    y, *held = c.step[impl](
        *rows, *held, LAYER, np.asarray(slots, np.int32),
        np.asarray(starts, np.int32), np.asarray(lens, np.int32),
        np.asarray(zero, bool))
    return np.asarray(y), [np.array(a) for a in held]


def _held(c, fills, seed=0):
    """Slots 0 .. 3 of every layer's arrays after a slice of 5 rows from
    position 0 and, where the kernel buffers rows, `fills[i]` decode rows
    that joined slot i's buffer; slot 4 and the junk slot as zeros."""
    rng = np.random.default_rng(seed)
    held = [np.zeros(s, np.int32 if len(s) == 2 else np.float32)
            for s in c.shapes]
    slots, starts = np.arange(SEQS), 6 * np.arange(SEQS)
    _, held = _call(c, "reference", c.rows(rng), held, slots, starts,
                    [5] * SEQS, [True] * SEQS)
    for t in range(max(fills) if c.fold else 0):
        _, held = _call(c, "reference", c.rows(rng), held, slots, starts,
                        [int(f > t) for f in fills], [False] * SEQS)
    if c.fold:
        assert held[-1][LAYER, :SEQS].tolist() == list(fills)
    return held


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max()
                 / max(np.abs(want).max(), 1e-30))


def _mix(c):
    """A call of every kind: a decode row that JOINS (slot 2), a slice of 9
    rows over a buffer that holds rows (slot 0), a decode row that FOLDS
    (slot 3: the buffer is full with it) and a sequence WITHOUT a row, which
    names slot 1 and even says `zero`. -> (held, the call's arguments)."""
    full = c.fold - 1 if c.fold else 0
    held = _held(c, (1, 2, 1, full))
    return held, (c.rows(np.random.default_rng(7)), held, [2, 0, 3, 1],
                  [0, 3, 14, 20], [1, 9, 1, 0], [False, False, False, True])


@pytest.mark.parametrize("name", KERNELS)
def test_a_sequence_without_a_row_leaves_its_slot_and_its_fill_alone(
        case, name):
    """The junk slot (the last) takes what a padding sequence writes: the slot
    it names is as it was to the last byte, in every array, under both
    impls, and so is every slot and layer the call did not name."""
    c = case(name)
    held, args = _mix(c)
    # what only the junk slot holds: a fill, or (no buffer) a state, which
    # the padding sequence's `zero` then clears
    held[-1][LAYER, SLOTS] = 2
    for impl in ("reference", "pallas"):
        _, after = _call(c, impl, *args)
        for now, was in zip(after, held):
            assert np.array_equal(now[LAYER, [1, 4]], was[LAYER, [1, 4]])
            assert np.array_equal(now[0], was[0])
        assert not after[-1][LAYER, SLOTS].any()


@pytest.mark.parametrize("name", KERNELS)
def test_zero_restarts_a_slot_whatever_it_held(case, name):
    """`zero` is the only clearing there is: a slot full of another
    sequence's state, buffered rows and fill answers a sequence that starts
    at position 0, a decode row or a slice, as a slot of zeros does with the
    flag and without it, and holds the same S_t afterwards."""
    c = case(name)
    used = _held(c, (1, 2, 1, 2))
    rng = np.random.default_rng(3)
    for a in used:                          # and what nobody would write
        if a.dtype != np.int32:
            a[LAYER, :SEQS] += 3.0 * _normal(rng, *a[LAYER, :SEQS].shape)
    clean = [np.zeros_like(a) for a in used]
    rows = c.rows(np.random.default_rng(5))
    seqs = ([0, 1, 2, 3], [0, 3, 14, 20], [1, 9, 1, 0])
    for impl in ("reference", "pallas"):
        want_y, want = _call(c, impl, rows, clean, *seqs, [False] * SEQS)
        for held in (used, clean):
            y, after = _call(c, impl, rows, held, *seqs, [True] * SEQS)
            assert _rel(y, want_y) < TOL
            assert _rel(c.folded(*after)[LAYER, :3],
                        c.folded(*want)[LAYER, :3]) < TOL
            if c.fold:                  # both rules end on an empty buffer
                assert after[-1][LAYER, :3].tolist() == [0, 0, 0]


@pytest.mark.parametrize("name", KERNELS)
def test_the_oracle_and_the_interpreted_kernel_agree(case, name):
    """(output, state, buffer, fill) of `impl="reference"` and of the kernel
    for a decode row that joins, one that folds and a slice in one call: the
    outputs and S as it is WRITTEN agree, the fills are equal, and the buffer
    agrees through `folded`, which reads the rows the fill says it holds
    (the rows behind them are stale and nobody's)."""
    c = case(name)
    held, args = _mix(c)
    (y, got), (want_y, want) = (_call(c, impl, *args)
                                for impl in ("pallas", "reference"))
    assert _rel(y, want_y) < TOL
    assert np.array_equal(y[[1, 2, 12, 13, 15, 23]], np.zeros_like(y[:6]))
    assert _rel(got[0][LAYER], want[0][LAYER]) < TOL
    assert _rel(c.folded(*got)[LAYER], c.folded(*want)[LAYER]) < TOL
    if c.fold:
        assert np.array_equal(got[-1], want[-1])
        # the row that joined left S alone; the fold and the slice wrote it
        assert np.array_equal(got[0][LAYER, 2], held[0][LAYER, 2])
        assert not np.array_equal(got[0][LAYER, 3], held[0][LAYER, 3])
        assert not np.array_equal(got[0][LAYER, 0], held[0][LAYER, 0])


@pytest.mark.parametrize("name", BUFFERED)
def test_the_fill_after_a_call_is_fill_afters(case, name):
    """`fill_after`, the host arithmetic, is the fill both impls write: over
    the mixed call, and over decode rows that take one slot's buffer from
    empty through a fold and on."""
    c = case(name)
    held, args = _mix(c)
    slots, lens, zero = args[2], args[4], args[5]
    want = {s: fill_after(int(held[-1][LAYER, s]), n, z, c.fold)[0]
            for s, n, z in zip(slots, lens, zero) if n}
    assert sorted(want.values()) == [0, 0, 2]
    for impl in ("reference", "pallas"):
        _, after = _call(c, impl, *args)
        assert {s: int(after[-1][LAYER, s]) for s in want} == want
    rows, fill, folds = c.rows(np.random.default_rng(11)), 1, 0
    for _ in range(c.fold + 1):
        _, held = _call(c, "pallas", rows, held, [2, 0, 3, 1], [0, 3, 14, 20],
                        [1, 0, 0, 0], [False] * SEQS)
        fill, folded = fill_after(fill, 1, False, c.fold)
        folds += folded
        assert held[-1][LAYER, 2] == fill
    assert folds == 1 and fill == 2


def test_joins_is_fill_after_over_arrays(cpu_jax):
    """The rule's two forms, over every (fill, rows, fresh) of a buffer of 4
    rows: a call leaves `fill + 1` where `joins` says so, else 0."""
    from ray_tpu.ops import state_slots

    cells = [(f, n, z) for f in range(4) for n in (1, 2, 9)
             for z in (False, True)]
    fill, lens, zero = (np.asarray(a) for a in zip(*cells))
    f0 = np.where(zero, 0, fill)
    stay = np.asarray(state_slots.joins(lens, zero, f0, 4))
    assert np.where(stay, f0 + 1, 0).tolist() == [
        fill_after(*cell, 4)[0] for cell in cells]
    assert state_slots.fill_shape(5, 128) == (5, 129)
