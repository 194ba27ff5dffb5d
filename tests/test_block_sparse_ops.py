"""Block-sparse attention's three entries (ops/block_sparse.py) at tiny sizes
on the CPU: each interpreted kernel against its `jax.numpy` oracle, over flat
mixed batches of decode rows and slices whose contexts lie on both sides of
`dense_len`, and what the selection must always hold.

4 query / 2 kv heads of 16, pages of 4, blocks of 16 (4 pages), 6 kept of
which the first and the last two are forced, `dense_len` 64; contexts up to
1,200 tokens so that the second stage's walk crosses several tiles (a tile is
64 pages) and a token's blocks lie in more than one lane row of its mask.

Tolerance: float32 sums in another order: 2e-5 of the largest value, an order
of magnitude over what is read.
"""

import numpy as np
import pytest

import ray_tpu  # noqa: F401

TOL = 2e-5
H, K, HD, PS = 4, 2, 16, 4

# (context lengths, query tokens) of a step's sequences, in row order
BATCHES = {
    "slices beside decode rows": ([200, 70, 130, 255], [1, 9, 20, 1]),
    "nothing selects": ([30, 40], [1, 5]),
    "a slice that crosses dense_len": ([90, 200], [40, 33]),
    "several tiles of the walk": ([1200, 700, 1100], [1, 70, 1]),
}


@pytest.fixture(scope="module")
def bs(cpu_jax):
    from ray_tpu.ops import block_sparse

    return block_sparse


def _geometry(bs):
    g = bs.Geometry(page=PS, block=16, topk=6, init_blocks=1, window=32,
                    dense_len=64)
    g.check()
    return g


def _batch(seed, lens, q_lens, pages=1000, width=320):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    S, T = len(lens), sum(q_lens) + 3
    table = np.zeros((S, width), np.int32)
    free = rng.permutation(pages - 1) + 1
    at = 0
    for s, n in enumerate(lens):
        need = -(-n // PS)
        table[s, :need] = free[at:at + need]
        at += need
    f32 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    k_pool = f32(2, pages, PS, K * HD)
    kv = jnp.asarray(lens, jnp.int32)
    return dict(
        q=f32(T, H, HD), k_pool=k_pool, v_pool=f32(2, pages, PS, K * HD),
        means=jnp.zeros((2, pages, K * HD)).at[1].set(k_pool[1].mean(1)),
        tables=jnp.asarray(table), kv_lens=kv,
        q_pos=kv - jnp.asarray(q_lens, jnp.int32),
        cu=jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32))


def _selection(bs, b, impl="reference"):
    g = _geometry(bs)
    rows = (b["tables"], b["kv_lens"], b["q_pos"], b["cu"])
    R = bs.block_scores(b["q"], b["means"], 1, *rows, kv_heads=K, scale=0.25,
                        geometry=g, impl=impl, interpret=True)
    _, positions, _, selects = bs.token_rows(
        b["cu"], b["q_pos"], b["kv_lens"], b["q"].shape[0], g.dense_len)
    blocks, count = bs.block_select(R, positions, selects, geometry=g,
                                    impl=impl, interpret=True)
    return R, positions, selects, blocks, count


@pytest.mark.parametrize("name", list(BATCHES))
def test_the_kernels_are_their_oracles(bs, name):
    b = _batch(0, *BATCHES[name])
    g = _geometry(bs)
    want = _selection(bs, b)
    got = _selection(bs, b, "pallas")
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=TOL, atol=1e-6)
    for a, c in zip(got[3:], want[3:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    rows = (b["tables"], b["kv_lens"], b["q_pos"], b["cu"])
    args = (b["q"], b["k_pool"], b["v_pool"], 1, *rows, *want[3:])
    kw = dict(kv_heads=K, scale=0.25, geometry=g)
    attended = bs.block_attend(*args, impl="reference", **kw)
    kernel = bs.block_attend(*args, impl="pallas", interpret=True, **kw)
    real = np.arange(b["q"].shape[0]) < int(b["cu"][-1])
    err = np.abs(np.asarray(kernel) - np.asarray(attended))[real].max()
    assert err < TOL * max(1.0, float(np.abs(np.asarray(attended)).max()))
    # a sequence whose context is no longer than dense_len is not this
    # entry's: zeros, and nothing of it selected
    seq = np.asarray(bs.pa.token_seq_ids(b["cu"], b["q"].shape[0],
                                         len(BATCHES[name][0])))
    short = np.asarray(b["kv_lens"])[seq] <= g.dense_len
    assert not np.asarray(attended)[short & real].any()
    assert not np.asarray(want[4])[~np.asarray(want[2])].any()


@pytest.mark.parametrize("name", ["slices beside decode rows",
                                  "several tiles of the walk"])
def test_what_a_selection_always_holds(bs, name):
    """Ascending blocks no later than the token's own, min(own + 1, topk) of
    them, the first block and the window's among them, and of the free ones
    exactly the best-scored (by the oracle's own max-pool, done here by
    hand)."""
    b = _batch(1, *BATCHES[name])
    g = _geometry(bs)
    R, positions, selects, blocks, count = map(np.asarray, _selection(bs, b))
    checked = 0
    for t in np.flatnonzero(selects):
        own = positions[t] // g.block
        for kh in range(K):
            n = count[t, kh]
            kept = blocks[t, kh, :n].tolist()
            assert n == min(own + 1, g.topk) and kept == sorted(set(kept))
            forced = {0} | set(range(max(0, own - g.window_blocks + 1),
                                     own + 1))
            assert forced <= set(kept) and max(kept) == own
            score = [max(R[t, kh, max(0, 4 * blk - 1):4 * blk + 4])
                     for blk in range(own + 1)]
            free = sorted(set(range(own + 1)) - forced,
                          key=lambda blk: (-score[blk], blk))
            assert set(kept) - forced == set(free[:n - len(forced)])
            checked += 1
    assert checked > 20


def test_page_means_are_made_again_from_the_pool(bs):
    import jax.numpy as jnp

    rng = np.random.default_rng(2)
    k_pool = jnp.asarray(rng.normal(size=(2, 12, PS, K * HD)), jnp.float32)
    means = jnp.full((2, 12, K * HD), 7.0)
    out = np.asarray(bs.page_means(means, k_pool, 1,
                                   jnp.asarray([3, 3, 9, 12, 12])))
    np.testing.assert_allclose(out[1, [3, 9]],
                               np.asarray(k_pool[1, [3, 9]]).mean(1),
                               rtol=1e-6)
    untouched = np.ones(12, bool)
    untouched[[3, 9]] = False
    assert (out[1, untouched] == 7.0).all() and (out[0] == 7.0).all()


def test_the_entries_keep_their_names(bs):
    """The three entries are jitted under names of their own and their
    kernels are tagged, so a device trace's events read `block_select_call`,
    `block_attend_call` and `block_attend_rows_call` (the benchmark's readers
    find them by `block_select` and `block_attend`)."""
    import jax

    b = _batch(0, *BATCHES["slices beside decode rows"])
    g = _geometry(bs)
    rows = (b["tables"], b["kv_lens"], b["q_pos"], b["cu"])

    def step(q, means, k_pool, v_pool):
        R = bs.block_scores(q, means, 1, *rows, kv_heads=K, scale=0.25,
                            geometry=g, impl="pallas", interpret=True)
        _, positions, _, selects = bs.token_rows(
            b["cu"], b["q_pos"], b["kv_lens"], q.shape[0], g.dense_len)
        sel = bs.block_select(R, positions, selects, geometry=g,
                              impl="pallas", interpret=True)
        return bs.block_attend(q, k_pool, v_pool, 1, *rows, *sel, kv_heads=K,
                               scale=0.25, geometry=g, impl="pallas",
                               interpret=True)

    text = str(jax.make_jaxpr(step)(b["q"], b["means"], b["k_pool"],
                                    b["v_pool"]))
    for name in ("block_select_call", "dsa_select_call", "block_attend_call",
                 "block_attend_rows_call"):
        assert f"name={name}" in text, name
