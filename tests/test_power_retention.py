"""Power retention (ops/power_retention.py) at tiny sizes on the CPU: the
degree-2 features, and four statements of one layer that must agree: the
kernel's recurrent step (a decode row), its chunked form (a slice), the
`lax.scan` oracle beside it, and the ATTENTION form of the plain reference
(the t x t weights with the gates' cumulative logs, which builds neither
features nor a state).

Heads of 16 (9 chunks of 16 lanes), 6 query heads over 2 kv heads (3:1) or 5
over 1 (the model's 5:1); the kernel runs interpreted, its CHUNK patched to 8
so that a slice is several chunks and lengths do not divide.

Tolerance: float32 sums in another order. Outputs are ratios of sums of
squares, so they agree to ~1e-6 of the largest; 2e-5 leaves an order of
magnitude. A state kept in bfloat16 reads over 1e-3 (the last test).
"""

import math

import numpy as np
import pytest

import ray_tpu  # noqa: F401

TOL = 2e-5
HD, EPS = 16, 1e-6
SCALE = HD ** -0.5


@pytest.fixture(scope="module")
def pr(cpu_jax):
    from ray_tpu.ops import power_retention

    return power_retention


@pytest.fixture(scope="module")
def chunk8(pr):
    """CHUNK 8 for this module's kernels (`power_retention_call` keeps its
    traces by shape: cleared on the way in and out)."""
    import jax

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pr, "CHUNK", 8)
        jax.clear_caches()
        yield
    jax.clear_caches()


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
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


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
            jnp.zeros(pr.norm_shape(layers, slots, K, HD)))


def _filled(pr, layers, slots, K, seed=9, tokens=12):
    """Slots that hold `tokens` earlier tokens' state each: S = sum phi(k)
    v^T and z = sum phi(k) (a state of no keys' making has no meaning: its
    normaliser may cancel)."""
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.key(seed), 2)
    lead = (layers, slots + 1, K, tokens)
    f = pr.phi((1.0 + 0.4 * jax.random.normal(ks[0], lead + (HD,)))
               * SCALE ** 0.5)
    v = jax.random.normal(ks[1], lead + (HD,))
    return (jnp.einsum("nskfrl,nskfc->nskrcl", f, v),
            jnp.moveaxis(f.sum(3), 2, 3))


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
    state, norm = _filled(pr, 2, 6, K)
    assert state.shape == pr.state_shape(2, 6, K, HD)
    assert norm.shape == pr.norm_shape(2, 6, K, HD)
    args = (q, k, v, log_g, state, norm, 1, jnp.asarray(slots),
            jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(zero))
    want = _step(pr, "reference")(*args)
    got = _step(pr, "pallas")(*args)
    assert _rel(got[0], want[0]) < TOL
    for a, b in zip(got[1:], want[1:]):     # the junk slot is nobody's
        assert _rel(a[:, :6], b[:, :6]) < TOL
    # layer 0 and the slots of nobody are as they were
    used = slots[lens > 0]
    idle = np.setdiff1d(np.arange(6), used)
    for new, old in ((got[1], state), (got[2], norm)):
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
    state, norm = _empty(pr, 1, 1, K)
    step, got = _step(pr, impl), []
    pad = lambda a: jnp.pad(a, ((0, 16),) + ((0, 0),) * (a.ndim - 1))
    q, k, v, log_g = pad(q), pad(k), pad(v), pad(log_g)
    for start, n in [(0, 11), (11, 8)] + [(t, 1) for t in range(19, T)]:
        rows = slice(start, start + 16)         # one shape, n rows real
        o, state, norm = step(
            q[rows], k[rows], v[rows], log_g[rows], state, norm, 0,
            jnp.asarray([0]), jnp.asarray([0]), jnp.asarray([n]),
            jnp.asarray([start == 0]))
        got.append(np.asarray(o)[:n])
        assert not np.any(np.asarray(o)[n:])
    assert _rel(np.concatenate(got), want) < TOL


def test_rows_outside_every_segment_read_zero_and_touch_nothing(pr, chunk8):
    import jax.numpy as jnp

    q, k, v, log_g = _rows(3, 24, 6, 2)
    state, norm = _empty(pr, 1, 2, 2)
    o, s1, z1 = _step(pr, "pallas")(
        q, k, v, log_g, state, norm, 0, jnp.asarray([1, 0]),
        jnp.asarray([4, 16]), jnp.asarray([3, 0]), jnp.asarray([True, True]))
    o = np.asarray(o)
    assert np.all(o[:4] == 0) and np.all(o[7:] == 0) and np.all(o[4:7] != 0)
    assert np.all(np.asarray(s1[0, 0]) == 0) and np.any(np.asarray(s1[0, 1]))
    assert np.all(np.asarray(z1[0, 2]) == 0)        # the junk slot's z


def test_a_bfloat16_state_is_told_apart(pr):
    """The control of the tolerance: the same steps with the state rounded to
    bfloat16 after each differ from the attention form by over 1e-3."""
    import jax
    import jax.numpy as jnp

    H, K, T = 6, 2, 48
    q, k, v, log_g = _rows(5, T, H, K, (0.99, 0.999))
    want = attention_form(q, k, v, log_g)
    bf16 = lambda a: jax.lax.reduce_precision(a, exponent_bits=8,
                                              mantissa_bits=7)
    step, errs = _step(pr, "reference"), {}
    for name, keep in (("float32", lambda a: a), ("bfloat16", bf16)):
        state, norm = _empty(pr, 1, 1, K)
        got = []
        for t in range(T):
            o, state, norm = step(
                q[t:t + 1], k[t:t + 1], v[t:t + 1], log_g[t:t + 1], state,
                norm, 0, jnp.asarray([0]), jnp.asarray([0]),
                jnp.asarray([1]), jnp.asarray([t == 0]))
            state, norm = keep(state), keep(norm)
            got.append(np.asarray(o))
        errs[name] = _rel(np.concatenate(got), want)
    assert errs["float32"] < TOL < 1e-3 < errs["bfloat16"], errs
