"""Power retention (ops/power_retention.py) at tiny sizes on the CPU: the
degree-2 features, and four statements of one layer that must agree: the
kernel's recurrent step (a decode row), its chunked form (a slice), the
`lax.scan` oracle beside it, and the ATTENTION form of the plain reference
(the t x t weights with the gates' cumulative logs, which builds neither
features nor a state).

Heads of 16 (9 chunks of 16 lanes), 6 query heads over 2 kv heads (3:1) or 5
over 1 (the model's 5:1); the kernel runs interpreted, its CHUNK patched to 8
so that a slice is several chunks and lengths do not divide, and its FOLD to
8 so that a decode run crosses folds.

A decode row does not rewrite its state: kernel and oracle hand back the state
as the last fold left it, the rows buffered since and their count, by one
rule, so all four are compared as they lie, and `folded` (the buffer folded
in) is the recurrence's S_t and z_t.

Tolerance: float32 sums in another order. Outputs are ratios of sums of
squares, so they agree to ~1e-6 of the largest; 2e-5 leaves an order of
magnitude. A state kept in bfloat16 reads over 1e-4, a buffer over 1e-3 (the
last test).
"""

import math

import numpy as np
import pytest

import ray_tpu  # noqa: F401
from ray_tpu.ops.state_slots import fill_after

TOL = 2e-5
HD, EPS, FOLD = 16, 1e-6, 8
SCALE = HD ** -0.5


@pytest.fixture(scope="module")
def pr(cpu_jax):
    from ray_tpu.ops import power_retention

    return power_retention


@pytest.fixture(scope="module")
def chunk8(pr):
    """CHUNK 8 and FOLD 8 for this module's kernels (`power_retention_call`
    keeps its traces by shape: cleared on the way in and out)."""
    import jax

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pr, "CHUNK", 8)
        patch.setattr(pr, "FOLD", FOLD)
        jax.clear_caches()
        _STEPS.clear()
        yield
    jax.clear_caches()
    _STEPS.clear()


_STEPS = {}


def _step(pr, impl):
    """`power_retention`, jitted once an `impl` and a shape."""
    import functools

    import jax

    if impl not in _STEPS:
        _STEPS[impl] = jax.jit(functools.partial(
            pr.power_retention, scale=SCALE, eps=EPS, impl=impl))
    return _STEPS[impl]


def _rel(got, want):
    """Largest difference over the largest wanted value (0 where both are
    all zeros)."""
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max()
                 / max(np.abs(want).max(), 1e-30))


def _rows(seed, R, H, K, gate=(0.5, 0.999)):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.key(seed), 5)
    # q and k share a direction, so that no q . k is near 0: a weight that is
    # the square of a difference of large terms has no digits to compare.
    both = jax.random.normal(ks[4], (HD,))
    q = both + 0.4 * jax.random.normal(ks[0], (R, H, HD))
    k = both + 0.4 * jax.random.normal(ks[1], (R, K, HD))
    v = jax.random.normal(ks[2], (R, K, HD))
    g = jax.random.uniform(ks[3], (R, K), minval=gate[0], maxval=gate[1])
    return q, k, v, jnp.log(g)


def _empty(pr, layers, slots, K):
    import jax.numpy as jnp

    return (jnp.zeros(pr.state_shape(layers, slots, K, HD)),
            jnp.zeros(pr.norm_shape(layers, slots, K, HD)),
            jnp.zeros(pr.buffer_shape(layers, slots, K, HD)),
            jnp.zeros(pr.fill_shape(layers, slots), jnp.int32))


def _filled(pr, layers, slots, K, seed=9, tokens=12):
    """Slots that hold `tokens` earlier tokens' state each: S = sum phi(k)
    v^T and z = sum phi(k) (a state of no keys' making has no meaning: its
    normaliser may cancel), and slot s's buffer (s + 2) % FOLD rows more."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.key(seed), 5)
    lead = (layers, slots + 1, K, tokens)
    f = pr.phi((1.0 + 0.4 * jax.random.normal(ks[0], lead + (HD,)))
               * SCALE ** 0.5)
    v = jax.random.normal(ks[1], lead + (HD,))
    F = pr.fold_rows(HD)
    rows = lead[:3] + (F, HD)
    c = jnp.cumsum(jnp.log(jax.random.uniform(
        ks[4], lead[:3] + (HD,), minval=0.5, maxval=0.999)), axis=-1)
    fill = jnp.broadcast_to((jnp.arange(slots + 1) + 2) % F,
                            (layers, slots + 1))
    last = jnp.take_along_axis(
        c, jnp.maximum(fill - 1, 0)[..., None, None], axis=-1)
    gates = jnp.zeros(lead[:3] + (8, HD)).at[..., 0, :].set(c).at[
        ..., 1, :].set(last)
    buf = jnp.concatenate([
        (1.0 + 0.4 * jax.random.normal(ks[2], rows)) * SCALE ** 0.5,
        jax.random.normal(ks[3], rows), gates], axis=3)
    return (jnp.einsum("nskfrl,nskfc->nskrcl", f, v),
            jnp.moveaxis(f.sum(3), 2, 3), buf, fill.astype(jnp.int32))


def _close(pr, got, want, slots=slice(None)):
    """Two caches (state, norm, buf, fill) agree on `slots` of every layer:
    the fills, S and z as they lie, the buffers' live rows and gates, and
    the recurrence's state (`folded`)."""
    fill = np.asarray(want[3][:, slots])
    np.testing.assert_array_equal(np.asarray(got[3][:, slots]), fill)
    for a, b in zip(got[:2], want[:2]):
        assert _rel(a[:, slots], b[:, slots]) < TOL
    F = pr.fold_rows(HD)
    live = np.arange(F) < fill[..., None, None]             # (L, s, 1, F)
    mask = np.concatenate([live, live, np.zeros(live.shape[:3] + (8,), bool)],
                          axis=-1)[..., None]
    a, b = (np.asarray(x[2][:, slots]) for x in (got, want))
    assert _rel(np.where(mask, a, 0), np.where(mask, b, 0)) < TOL
    assert _rel(np.where(live, a[..., 2 * F, :F], 0),
                np.where(live, b[..., 2 * F, :F], 0)) < TOL
    some = fill[..., None, None] > 0
    assert _rel(np.where(some, a[..., 2 * F + 1, :], 0),
                np.where(some, b[..., 2 * F + 1, :], 0)) < TOL
    for a, b in zip(pr.folded(*(x[:, slots] for x in got)),
                    pr.folded(*(x[:, slots] for x in want))):
        assert _rel(a, b) < TOL


def attention_form(q, k, v, log_g):
    """One sequence from an empty state, the reference's form: q (T, H, hd),
    k / v (T, K, hd), log_g (T, K) -> o (T, H, hd), in float64."""
    q, k, v, log_g = (np.asarray(a, np.float64) for a in (q, k, v, log_g))
    T, H, _ = q.shape
    G = H // k.shape[1]
    through = np.cumsum(log_g, axis=0)
    out = np.zeros_like(q)
    for h in range(H):
        j = h // G
        a = (SCALE * q[:, h] @ k[:, j].T) ** 2
        since = through[:, j][:, None] - through[:, j][None, :]
        a = np.tril(a * np.exp(np.minimum(since, 0.0)))
        out[:, h] = a @ v[:, j] / (a.sum(1, keepdims=True) + EPS)
    return out


# ---- the features -----------------------------------------------------------

@pytest.mark.parametrize("hd", [2, 8, 16, 128])
def test_phi_is_the_square_of_the_inner_product(pr, hd):
    import jax

    x = jax.random.normal(jax.random.key(hd), (7, hd))
    y = jax.random.normal(jax.random.key(hd + 1), (7, hd))
    got = np.einsum("nrl,nrl->n", pr.phi(x), pr.phi(y))
    want = np.asarray((x * y).sum(-1)) ** 2
    assert pr.phi(x).shape == (7, hd // 2 + 1, hd)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5 * hd)


def test_phi_holds_every_product_once_and_the_last_chunks_twice(pr):
    """hd (hd + 1) / 2 distinct products in (hd / 2 + 1) hd lanes: the
    squares, every pair at distance 1 .. hd / 2 - 1 once with sqrt 2, and the
    hd / 2 pairs at distance hd / 2 twice with 1: 8,256 in 8,320 at 128."""
    import jax.numpy as jnp

    hd = 8
    x = jnp.asarray([2.0, 3, 5, 7, 11, 13, 17, 19])
    f = np.asarray(pr.phi(x))
    pairs = {}
    for r in range(hd // 2 + 1):
        w = 1.0 if r in (0, hd // 2) else math.sqrt(2.0)
        for lane in range(hd):
            a, b = sorted((int(x[lane]), int(x[(lane - r) % hd])))
            assert f[r, lane] == pytest.approx(w * a * b)
            pairs[(a, b)] = pairs.get((a, b), 0) + 1
    assert len(pairs) == hd * (hd + 1) // 2
    assert sorted(set(pairs.values())) == [1, 2]
    assert sum(1 for n in pairs.values() if n == 2) == hd // 2
    assert pr.chunks(128) * 128 == 8320 and 128 * 129 // 2 == 8256


# ---- kernel = oracle = attention form ---------------------------------------

RAGGED = {     # six sequences each (one compile a head ratio): lens, starts
    "decode_rows": ([1, 1, 1, 1, 0, 0], [0, 1, 2, 3, 4, 4]),
    "one_slice": ([13, 0, 0, 0, 0, 0], [0, 13, 13, 13, 13, 13]),
    "rows_and_slices": ([1, 11, 1, 5, 0, 0], [0, 1, 12, 13, 18, 18]),
    "chunk_edges": ([8, 9, 7, 16, 0, 0], [0, 8, 17, 24, 40, 40]),
}


@pytest.mark.parametrize("case,heads", [
    (case, (5, 1)) for case in sorted(RAGGED)] + [("rows_and_slices", (6, 2))],
    ids=lambda v: v if isinstance(v, str) else "%dto%d" % (v[0] // v[1], 1))
def test_kernel_matches_the_oracle_on_ragged_rows(pr, chunk8, case, heads):
    """Decode rows take the recurrent step, slices the chunked form, in one
    call, from slots that hold another step's state or start from zero."""
    import jax.numpy as jnp

    H, K = heads
    lens, starts = (np.asarray(a, np.int32) for a in RAGGED[case])
    R = 40
    q, k, v, log_g = _rows(len(case), R, H, K)
    slots = np.asarray([3, 0, 5, 2, 1, 4][:len(lens)], np.int32)
    zero = np.arange(len(lens)) % 3 == 2
    cache = _filled(pr, 2, 6, K)
    assert cache[0].shape == pr.state_shape(2, 6, K, HD)
    assert cache[1].shape == pr.norm_shape(2, 6, K, HD)
    assert cache[2].shape == pr.buffer_shape(2, 6, K, HD) \
        == (2, 7, K, 2 * FOLD + 8, HD)
    args = (q, k, v, log_g, *cache, 1, jnp.asarray(slots),
            jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(zero))
    want = _step(pr, "reference")(*args)
    got = _step(pr, "pallas")(*args)
    assert _rel(got[0], want[0]) < TOL
    _close(pr, got[1:], want[1:], slice(0, 6))  # the junk slot is nobody's
    # layer 0 and the slots of nobody are as they were
    used = slots[lens > 0]
    idle = np.setdiff1d(np.arange(6), used)
    for new, old in zip(got[1:], cache):
        np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(old[0]))
        np.testing.assert_array_equal(np.asarray(new[1, idle]),
                                      np.asarray(old[1, idle]))


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("gate", [(1e-4, 1e-3), (0.9999, 1.0), (0.3, 0.999)],
                         ids=["near0", "near1", "mixed"])
def test_steps_from_zero_match_the_attention_form(pr, chunk8, impl, gate):
    """A sequence of 29 tokens in slices of 11, 8 and 1 + 1 + .. (chunk
    lengths that do not divide it), from a zero state: every output is the
    attention form's over the whole sequence. A gate near 0 forgets all but
    the token itself; a gate near 1 keeps everything."""
    import jax.numpy as jnp

    H, K, T = 5, 1, 29
    q, k, v, log_g = _rows(7, T, H, K, gate)
    want = attention_form(q, k, v, log_g)
    cache = _empty(pr, 1, 1, K)
    step, got = _step(pr, impl), []
    pad = lambda a: jnp.pad(a, ((0, 16),) + ((0, 0),) * (a.ndim - 1))
    q, k, v, log_g = pad(q), pad(k), pad(v), pad(log_g)
    for start, n in [(0, 11), (11, 8)] + [(t, 1) for t in range(19, T)]:
        rows = slice(start, start + 16)         # one shape, n rows real
        o, *cache = step(
            q[rows], k[rows], v[rows], log_g[rows], *cache, 0,
            jnp.asarray([0]), jnp.asarray([0]), jnp.asarray([n]),
            jnp.asarray([start == 0]))
        got.append(np.asarray(o)[:n])
        assert not np.any(np.asarray(o)[n:])
    assert _rel(np.concatenate(got), want) < TOL


def test_rows_outside_every_segment_read_zero_and_touch_nothing(pr, chunk8):
    import jax.numpy as jnp

    q, k, v, log_g = _rows(3, 24, 6, 2)
    o, s1, z1, _, _ = _step(pr, "pallas")(
        q, k, v, log_g, *_empty(pr, 1, 2, 2), 0, jnp.asarray([1, 0]),
        jnp.asarray([4, 16]), jnp.asarray([3, 0]), jnp.asarray([True, True]))
    o = np.asarray(o)
    assert np.all(o[:4] == 0) and np.all(o[7:] == 0) and np.all(o[4:7] != 0)
    assert np.all(np.asarray(s1[0, 0]) == 0) and np.any(np.asarray(s1[0, 1]))
    assert np.all(np.asarray(z1[0, 2]) == 0)        # the junk slot's z


RUNS = {"fill0": 0, "fill1": 1, "fillF-1": FOLD - 1, "fillF": FOLD,
        "fill2F+3": 2 * FOLD + 3}
# Six sequences a call, on a cache whose slot s holds (s + 2) % FOLD buffered
# rows (`_filled`): lens, starts, slots, zero.
MIXES = {
    # one-row sequences at fills 5, 2, 7 (the call folds it), 4 and a slice
    # at 3 (folds what it finds, leaves none)
    "rows_beside_a_slice": ([1, 1, 9, 1, 1, 0], [0, 1, 2, 11, 12, 13],
                            [3, 0, 1, 5, 2, 4], [0, 0, 0, 0, 0, 0]),
    # slots that hold another sequence's state AND buffer, started at 0
    "stale_buffer_fresh_row": ([1, 1, 1, 0, 0, 0], [0, 1, 2, 3, 3, 3],
                               [3, 5, 0, 1, 2, 4], [1, 1, 0, 0, 0, 0]),
    "stale_buffer_fresh_slice": ([10, 1, 3, 0, 0, 0], [0, 10, 11, 14, 14, 14],
                                 [3, 0, 5, 1, 2, 4], [1, 0, 1, 0, 0, 0]),
    # (a preempted or restored sequence's next slice)
    "slice_on_a_part_filled_buffer": ([17, 2, 1, 0, 0, 0],
                                      [0, 17, 19, 20, 20, 20],
                                      [4, 5, 2, 0, 1, 3], [0, 0, 0, 0, 0, 0]),
}


@pytest.mark.parametrize("case", list(RUNS) + list(MIXES))
def test_a_decode_row_joins_the_buffer_and_a_fold_empties_it(pr, chunk8,
                                                             case):
    """The kernel against the step-by-step oracle, each carrying its own
    state, buffer and fill. A run: four sequences prefilled from zero (5, 3,
    9 and 2 rows), then so many decode steps that fills of 0, 1, FOLD - 1,
    FOLD and 2 FOLD + 3 rows are crossed, the first sequence's outputs also
    against the attention form over all its rows. A mix: one call over
    slots that hold state and part-filled buffers."""
    import jax.numpy as jnp

    H, K = 5, 1
    if case in MIXES:
        lens, starts, slots, zero = (np.asarray(a, np.int32)
                                     for a in MIXES[case])
        cache = _filled(pr, 2, 6, K)
        np.testing.assert_array_equal(np.asarray(cache[3][1, :6]),
                                      [2, 3, 4, 5, 6, 7])
        args = (*_rows(len(case), 24, H, K), *cache, 1, jnp.asarray(slots),
                jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(zero > 0))
        want = _step(pr, "reference")(*args)
        got = _step(pr, "pallas")(*args)
        assert _rel(got[0], want[0]) < TOL
        _close(pr, got[1:], want[1:], slice(0, 6))
        after = {int(sl): fill_after(int(cache[3][1, sl]), int(n), bool(z),
                                     FOLD)[0]
                 for sl, n, z in zip(slots, lens, zero) if n}
        assert {sl: int(got[4][1, sl]) for sl in after} == after
        return
    steps = RUNS[case]
    prompt = np.asarray([5, 3, 9, 2, 0, 0], np.int32)
    slots = jnp.asarray([3, 0, 5, 2, 1, 4])
    caches = {"reference": _empty(pr, 2, 6, K), "pallas": _empty(pr, 2, 6, K)}
    seen = {k: [] for k in ("q", "k", "v", "log_g", "o")}
    for t in range(steps + 1):
        lens = prompt if t == 0 else np.asarray([1, 1, 1, 1, 0, 0], np.int32)
        starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
        x = _rows(100 + t, 24, H, K)
        o = {}
        for impl in caches:
            o[impl], *caches[impl] = _step(pr, impl)(
                *x, *caches[impl], 1, slots, jnp.asarray(starts),
                jnp.asarray(lens), jnp.asarray([t == 0] * 6))
        assert _rel(o["pallas"], o["reference"]) < TOL, t
        for name, a in zip(seen, x + (o["pallas"],)):
            seen[name].append(np.asarray(a)[:lens[0]])
    _close(pr, caches["pallas"], caches["reference"], slice(0, 6))
    assert int(caches["pallas"][3][1, 3]) == steps % FOLD
    whole = {k: np.concatenate(v) for k, v in seen.items()}
    assert _rel(whole["o"], attention_form(
        whole["q"], whole["k"], whole["v"], whole["log_g"])) < TOL


@pytest.mark.parametrize("what,floor", [("state", 1e-4), ("buffer", 1e-3)])
def test_a_bfloat16_state_is_told_apart(pr, what, floor):
    """The control of the tolerance: the same steps with the state (S and z),
    or the rows buffered beside it (k, v and the gates' log), rounded to
    bfloat16 after each differ from the attention form by 3.2e-4 (a state
    that is written once a fold of 16 rows is ROUNDED once a fold: rewritten
    at every row it read over 1e-3) and by over 1e-3."""
    import jax
    import jax.numpy as jnp

    H, K, T = 6, 2, 48
    q, k, v, log_g = _rows(5, T, H, K, (0.99, 0.999))
    want = attention_form(q, k, v, log_g)
    bf16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                              mantissa_bits=7)
    rounded = {"state": lambda s, z, b, f: (bf16(s), bf16(z), b, f),
               "buffer": lambda s, z, b, f: (s, z, bf16(b), f)}[what]
    step, errs = _step(pr, "reference"), {}
    for name, keep in (("float32", lambda *a: a), ("bfloat16", rounded)):
        cache = _empty(pr, 1, 1, K)
        got = []
        for t in range(T):
            o, *cache = step(
                q[t:t + 1], k[t:t + 1], v[t:t + 1], log_g[t:t + 1], *cache,
                0, jnp.asarray([0]), jnp.asarray([0]), jnp.asarray([1]),
                jnp.asarray([t == 0]))
            cache = keep(*cache)
            got.append(np.asarray(o))
        errs[name] = _rel(np.concatenate(got), want)
    assert errs["float32"] < TOL < floor < errs["bfloat16"], errs
