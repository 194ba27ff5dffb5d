"""ops/sparse_latent.py: each entry (its kernel interpreted) against its plain
oracle over ragged rows, contexts under and over `topk`, ties, a context that
ends inside a page."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import sparse_latent as sl

PS = 4          # tokens a page


def _batch(key, lens, q_lens, *, HI=4, dI=16, W=32, H=2, pages=64, T=None,
           width=None):
    """A flat mixed batch: sequence s has context lens[s] after this step's
    q_lens[s] tokens. Pools drawn whole, tables a permutation of the pages."""
    S = len(lens)
    T = T or int(sum(q_lens)) + 3
    width = width or -(-max(lens) // PS) + 1
    ks = jax.random.split(key, 6)
    perm = np.asarray(jax.random.permutation(ks[0], pages))[:S * width]
    tables = jnp.asarray(perm.reshape(S, width), jnp.int32)
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)
    kv_lens = jnp.asarray(lens, jnp.int32)
    q_pos = kv_lens - jnp.asarray(q_lens, jnp.int32)
    return dict(
        qi=jax.random.normal(ks[1], (T, HI, dI), jnp.float32),
        w=jax.random.normal(ks[2], (T, HI), jnp.float32),
        index_pool=jax.random.normal(ks[3], (2, pages, PS, dI), jnp.float32),
        q=jax.random.normal(ks[4], (T, H, W), jnp.float32),
        pool=jax.random.normal(ks[5], (3, pages, PS, W), jnp.float32),
        tables=tables, kv_lens=kv_lens, q_pos=q_pos, cu=cu)


RAGGED = [
    # decode rows alone, one context ending inside a page
    ([9, 16, 1, 23], [1, 1, 1, 1]),
    # a slice of many blocks beside decode rows; an empty sequence
    ([40, 7, 0, 30], [19, 1, 0, 8]),
    # a prompt from position 0
    ([11], [11]),
]


def _doc(first, pages):
    return list(range(first, first + pages))


# Tables that share (PS = 4, tiles of 16 rows = 4 places): name, tables (a
# row's pages; the step's table pads them with zeros), contexts after the
# step's tokens, the step's tokens a sequence, and the tiles each sequence
# shares with its leader under the plan.
A, B = _doc(40, 12), _doc(20, 8)          # two documents: 3 tiles, 2 tiles
SHARED = {
    "two on one run, ragged tails": (
        [A + [1, 2], A + [3, 4, 5, 6]], [55, 61], [1, 1], [3, 3]),
    "five on one run": (
        [A + [1, 2], A + [3], A + [4, 5, 6], A[:9] + [7, 8], A + [9]],
        [53, 49, 60, 41, 50], [1, 1, 1, 1, 1], [3, 3, 3, 2, 3]),
    "two runs in one step, one sequence alone": (
        [A + [1], B + [2], _doc(60, 9), B + [3, 4], A + [5, 6]],
        [50, 35, 33, 39, 54], [1, 1, 1, 1, 1], [3, 2, 0, 2, 3]),
    "a run shorter than a tile": (
        [A[:3] + [1, 2, 3], A[:3] + [4, 5, 6]], [22, 23], [1, 1], [0, 0]),
    "agree in place 0, part at place 3": (
        [A[:3] + [1] + A[4:], A[:3] + [2] + A[4:]], [45, 47], [1, 1], [0, 0]),
    "a whole tile and part of the next": (
        # (the leader's own second tile rides its shared block too)
        [A[:6] + [1, 2, 3], A[:6] + [4, 5, 6, 7]], [33, 37], [1, 1], [2, 1]),
    "a page id repeated deeper in another table": (
        [A + [1], [2, 3, 4, 5] + A], [50, 63], [1, 1], [0, 0]),
    "a slice over a block beside decode rows on its document": (
        [A + _doc(1, 6), A + [7], A + [8, 9]], [69, 50, 55], [19, 1, 1],
        [3, 3, 3]),
    "a slice that starts inside the run's last tile": (
        [A + _doc(1, 3), A + [7]], [58, 52], [19, 1], [2, 3]),
}


def _shared_batch(key, tables, lens, q_lens, *, HI=4, dI=16, pages=80):
    S, width = len(tables), max(len(t) for t in tables) + 1
    table = np.zeros((S, width), np.int32)
    for s, t in enumerate(tables):
        table[s, :len(t)] = t
    ks = jax.random.split(key, 3)
    T = int(sum(q_lens)) + 3
    kv_lens = jnp.asarray(lens, jnp.int32)
    return dict(
        qi=jax.random.normal(ks[0], (T, HI, dI), jnp.float32),
        w=jax.random.normal(ks[1], (T, HI), jnp.float32),
        index_pool=jax.random.normal(ks[2], (2, pages, PS, dI), jnp.float32),
        tables=jnp.asarray(table), kv_lens=kv_lens,
        q_pos=kv_lens - jnp.asarray(q_lens, jnp.int32),
        cu=jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32))


def _index_cases():
    for lens, q_lens in RAGGED:
        yield pytest.param(None, lens, q_lens, None, True,
                           id=f"unshared-{len(lens)}")
    for name, (tables, lens, q_lens, runs) in SHARED.items():
        yield pytest.param(tables, lens, q_lens, runs, True, id=name)
    tables, lens, q_lens, runs = SHARED["five on one run"]
    yield pytest.param(tables, lens, q_lens, runs, False, id="not live")


@pytest.mark.parametrize("tables,lens,q_lens,runs,live", _index_cases())
def test_index_kernel_is_the_oracle(tables, lens, q_lens, runs, live,
                                    monkeypatch):
    monkeypatch.setattr(sl, "INDEX_TILE", 16)    # several tiles a context
    monkeypatch.setattr(sl, "INDEX_Q_BLOCK", 8)  # a slice of several blocks
    b = (_batch(jax.random.key(0), lens, q_lens) if tables is None
         else _shared_batch(jax.random.key(0), tables, lens, q_lens))
    args = (b["qi"], b["w"], b["index_pool"], 1, b["tables"], b["kv_lens"],
            b["q_pos"], b["cu"])
    want = sl.dsa_index(*args, impl="reference", live=live)
    got = sl.dsa_index(*args, impl="pallas", interpret=True, live=live)
    seen = np.isfinite(np.asarray(want))
    assert (np.isfinite(np.asarray(got)) == seen).all()
    np.testing.assert_allclose(np.asarray(got)[seen], np.asarray(want)[seen],
                               rtol=1e-5, atol=1e-5)
    if not live:
        assert not seen.any()
        return
    # what a row sees: its own position and everything before it
    _, positions, n, valid = sl.flat_rows(b["cu"], b["q_pos"], b["kv_lens"],
                                          b["qi"].shape[0])
    assert (seen.sum(1) == np.asarray(n)).all()
    assert (np.asarray(n)[np.asarray(valid)]
            == np.asarray(positions)[np.asarray(valid)] + 1).all()
    # what the plan shares: the tiles each sequence rides its leader's walk
    n_q = b["cu"][1:] - b["cu"][:-1]
    _, run = sl.shared_runs(b["tables"], b["q_pos"], n_q, 16 // PS, 16)
    assert list(np.asarray(run)) == (runs or [0] * len(lens))


@pytest.mark.parametrize("name", list(SHARED) + ["unshared"])
@pytest.mark.parametrize("block", [8, 16])
def test_the_host_counts_the_walks_the_plan_makes(name, block, monkeypatch):
    """`index_walked_rows` (a tick record's `dsa_index_walked_rows`) is the
    traced plan's `walked` on the same tables, and by hand where two decode
    rows share a document."""
    monkeypatch.setattr(sl, "INDEX_TILE", 16)
    monkeypatch.setattr(sl, "INDEX_Q_BLOCK", block)
    if name == "unshared":
        lens, q_lens = RAGGED[1]
        b = _batch(jax.random.key(0), lens, q_lens)
    else:
        tables, lens, q_lens, _ = SHARED[name]
        b = _shared_batch(jax.random.key(0), tables, lens, q_lens)
    plan = sl.index_walks(b["tables"], b["kv_lens"], b["q_pos"], b["cu"],
                          64, block, 16 // PS, PS)
    rows = [(q, n - q, n) for n, q in zip(lens, q_lens)]
    got = sl.index_walked_rows(rows, np.asarray(b["tables"]), PS)
    assert got == int(plan["walked"])
    if name == "unshared":      # every block to its own last token
        assert got == sl.index_walked_rows(rows, None, PS)
        assert got == sum(min(n, n - q + at + block)
                          for n, q in zip(lens, q_lens)
                          for at in range(0, q, block))
    if name == "two on one run, ragged tails":
        # the document's 48 rows once, then 55 - 48 and 61 - 48 of their own
        assert got == 48 + 7 + 13


@pytest.mark.parametrize("topk", [4, 8, 64])
def test_select_kernel_is_the_oracle_with_ties(topk):
    """Contexts under and over topk; scores drawn from FIVE values, so that
    the topk-th ties with many and the lower positions must win."""
    T, L = 11, 50
    k1, k2 = jax.random.split(jax.random.key(topk))
    scores = jax.random.randint(k1, (T, L), 0, 5).astype(jnp.float32) - 2.0
    scores = scores.at[3].set(jax.random.normal(k2, (L,)))    # and no ties
    n = jnp.asarray([0, 1, 3, 50, 50, 7, 8, 9, 33, 49, 5], jnp.int32)
    scores = jnp.where(jnp.arange(L)[None, :] < n[:, None], scores, -jnp.inf)
    want_pos, want_n = sl.dsa_select(scores, n, topk=topk, impl="reference")
    got_pos, got_n = sl.dsa_select(scores, n, topk=topk, impl="pallas",
                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))
    np.testing.assert_array_equal(np.asarray(got_pos), np.asarray(want_pos))
    # by hand, one row: the best `topk` by (score, lower position first)
    row = np.asarray(scores[4])
    best = sorted(sorted(range(L), key=lambda p: (-row[p], p))[:topk])
    assert list(np.asarray(got_pos[4])[:min(topk, L)]) == best[:topk]


def test_select_lowers_to_no_sort():
    scores = jnp.zeros((8, 256), jnp.float32)
    text = jax.jit(lambda s, n: sl.dsa_select(
        s, n, topk=32, impl="pallas", interpret=True)).lower(
        scores, jnp.full((8,), 256, jnp.int32)).as_text()
    assert "sort" not in text and "scatter" not in text


@pytest.mark.parametrize("lens,q_lens", RAGGED)
def test_attend_kernel_is_the_oracle_and_the_dense_kernel_under_topk(
        lens, q_lens):
    """Over the selected rows the kernel is its oracle; where every row is
    selected both are the dense latent attention."""
    b = _batch(jax.random.key(1), lens, q_lens)
    T = b["q"].shape[0]
    seq, _, n, valid = sl.flat_rows(b["cu"], b["q_pos"], b["kv_lens"], T)
    kw = dict(scale=0.3, lat=24)
    for topk in (4, 64):
        scores = sl.dsa_index(b["qi"], b["w"], b["index_pool"], 0,
                              b["tables"], b["kv_lens"], b["q_pos"], b["cu"],
                              impl="reference")
        pos, count = sl.dsa_select(scores, n, topk=topk, impl="reference")
        rows = sl.pool_rows(pos, b["tables"], seq, PS, impl="reference")
        want = sl.dsa_attend_reference(b["q"], rows, count, b["pool"], 2,
                                       **kw)
        # every selected row cached (the pool holds the step's own already):
        # no row of the step's is attended to a second time
        args = (b["q"], sl.gather_selection(b["pool"], 2, rows), count, count,
                jnp.zeros((T, b["q"].shape[-1])), jnp.zeros((T, T), bool))
        for extra in (dict(impl="reference"),
                      dict(impl="pallas", interpret=True)):
            got = sl.dsa_attend(*args, place=0, **extra, **kw)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-5, atol=2e-5)
    dense = pa.latent_paged_attention_unified_reference(
        b["q"], b["pool"], 2, b["tables"], b["kv_lens"], b["q_pos"], b["cu"],
        **kw)
    live = np.asarray(valid & (n > 0))
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(dense)[live],
                               rtol=2e-5, atol=2e-5)


# A mixed step over a pool whose rows hold a group's layers side by side:
# (context after the step, tokens of the step) a sequence, index_topk 8.
GROUP_STEP = [
    (16, 6),    # a prompt slice from 10: its tokens select earlier ones of it
    (30, 1),    # a decode row that selects its own token
    (25, 1),    # a decode row that does not
    (5, 2),     # still under index_topk, in a step that selects
    (16, 4),    # a second slice over the SAME positions 12..15 as the first's
]


def _group_step(S: int, G: int = 2, W: int = 32, H: int = 2, topk: int = 8,
                pages: int = 64):
    """-> the step's operands: a grouped pool (G, pages, PS, S x W) drawn
    whole (what it holds at the step's own positions is STALE), positions
    ascending by hand (forced in: a slice's first token for its later ones,
    position 29 for the decode row at 30; forced out: 24 for the row at 25),
    and every layer's own rows of the step."""
    lens, q_lens = zip(*GROUP_STEP)
    b = _batch(jax.random.key(7), list(lens), list(q_lens), W=W, H=H,
               pages=pages)
    T = b["q"].shape[0]
    seq, at, n, valid = sl.flat_rows(b["cu"], b["q_pos"], b["kv_lens"], T)
    rng = np.random.default_rng(7)
    first = np.asarray(b["q_pos"])[np.asarray(seq)]
    positions = np.zeros((T, topk), np.int32)
    for t in range(T):
        ctx, p = int(n[t]), int(at[t])
        if ctx <= topk:
            positions[t, :ctx] = np.arange(ctx)
            continue
        must = ({int(first[t])} if p > first[t] else set()) | (
            {29} if p == 29 else set())
        rest = sorted(set(range(ctx)) - must - ({24} if p == 24 else set()))
        keep = must | set(rng.choice(rest, topk - len(must),
                                     replace=False).tolist())
        positions[t] = sorted(keep)
    count = jnp.minimum(n, topk).astype(jnp.int32)
    ks = jax.random.split(jax.random.key(8), 2)
    return dict(
        b, seq=seq, at=at, valid=valid, positions=jnp.asarray(positions),
        count=count, first=jnp.asarray(first),
        grouped=jax.random.normal(ks[0], (G, pages, PS, S * W)),
        own=jax.random.normal(ks[1], (S, T, W)))


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("place", [0, 1, 2])
def test_one_gather_a_group_and_the_steps_own_rows_is_the_oracle(place,
                                                                 impl):
    """The mechanism against `dsa_attend_reference` on ONE layer's pool AFTER
    that layer's write, for every layer of a group of three: the group's rows
    gathered ONCE before any layer wrote this step's, each layer attending to
    the cached prefix of its lane block and to the step's own rows as it
    wrote them."""
    from ray_tpu.llm.model_runner import pool_write_rows

    S, G, g, W = 3, 2, 1, 32
    b = _group_step(S, G, W)
    T, kw = b["q"].shape[0], dict(scale=0.3, lat=24)
    rows = sl.pool_rows(b["positions"], b["tables"], b["seq"], PS,
                        impl="reference")
    picked = sl.gather_selection(b["grouped"], g, rows)   # before the writes
    cached, mask = sl.step_rows(b["positions"], b["count"], b["seq"],
                                b["at"], b["first"], b["valid"])
    # what the cases are there for
    cached_, mask_ = np.asarray(cached), np.asarray(mask)
    count_, seq_ = np.asarray(b["count"]), np.asarray(b["seq"])
    assert (mask_.sum(1) + cached_ == count_).all()
    assert mask_[5, 0] and mask_[5].sum() >= 2         # a slice's earlier rows
    assert mask_[6, 6] and not mask_[7].any()          # the two decode rows
    assert cached_[8] == 3 and cached_[9] == 3         # under index_topk
    assert not (mask_ & (seq_[:, None] != seq_[None, :])).any()
    assert mask_[10:14].any() and not mask_[:, T - 3:].any()    # no padding

    # The layer's write, then the oracle over its pool as one layer's.
    ids = np.where(np.asarray(b["valid"]), np.asarray(b["tables"])[
        seq_, np.asarray(b["at"]) // PS], b["grouped"].shape[1])
    written = pool_write_rows(b["grouped"], (g, place), jnp.asarray(ids),
                              b["at"] % PS, b["own"][place])
    lanes = slice(place * W, (place + 1) * W)
    # the window alone was written
    keep = np.ones(S * W, bool)
    keep[lanes] = False
    np.testing.assert_array_equal(np.asarray(written)[..., keep],
                                  np.asarray(b["grouped"])[..., keep])
    want = sl.dsa_attend_reference(b["q"], rows, b["count"],
                                   written[..., lanes], g, **kw)
    extra = dict(interpret=True) if impl == "pallas" else {}
    got = sl.dsa_attend(b["q"], picked, b["count"], cached, b["own"][place],
                        mask, place=place, impl=impl, **extra, **kw)
    live = np.asarray(b["valid"])
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(got)[~live].any()
    # the stale rows matter: the gathered operand alone is not the oracle
    stale = sl.dsa_attend(b["q"], picked, b["count"], b["count"],
                          b["own"][place], jnp.zeros_like(mask), place=place,
                          impl="reference", **kw)
    assert np.abs(np.asarray(stale) - np.asarray(want))[live].max() > 1e-2


@pytest.mark.parametrize("place", [0, 2])
def test_the_dense_latent_kernel_reads_a_lane_block_of_a_groups_rows(place):
    """A pool whose rows are wider than the query's: `layer` is (group,
    place), the kernel's page DMA takes the layer's lanes and is the
    reference over that layer's pool alone; a pool of the query's width and
    an int layer is untouched (the other tests of this kernel)."""
    S, g, W = 3, 1, 32
    b = _group_step(S, W=W)
    kw = dict(scale=0.3, lat=24)
    args = (b["tables"], b["kv_lens"], b["q_pos"], b["cu"])
    one_layer = b["grouped"][..., place * W:(place + 1) * W]
    want = pa.latent_paged_attention_unified_reference(
        b["q"], one_layer, g, *args, **kw)
    for fn, extra in ((pa.latent_paged_attention_unified_reference, {}),
                      (pa.latent_paged_attention_unified,
                       dict(interpret=True))):
        got = fn(b["q"], b["grouped"], (g, place), *args, **extra, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_the_published_selection_size_at_a_context_of_4096():
    """index_topk 2,048 of 4,096 rows at tiny widths: the sizes the cell
    selects at, exercised once: kernel and oracle keep the same rows, and not
    the most recent ones."""
    L, topk = 4096, 2048
    scores = jax.random.normal(jax.random.key(2), (3, L))
    n = jnp.asarray([L, 3000, 2048], jnp.int32)
    scores = jnp.where(jnp.arange(L)[None, :] < n[:, None], scores, -jnp.inf)
    want, _ = sl.dsa_select(scores, n, topk=topk, impl="reference")
    got, count = sl.dsa_select(scores, n, topk=topk, impl="pallas",
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert list(np.asarray(count)) == [2048, 2048, 2048]
    recent = set(range(L - topk, L))
    assert len(recent & set(np.asarray(got[0]).tolist())) < 0.6 * topk
    assert list(np.asarray(got[2])) == list(range(2048))


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_an_entry_that_is_not_live_does_nothing_and_says_nothing(impl):
    """`live` False (a step none of whose contexts is over topk): no score,
    no position, zeros attended, whatever the operands hold, NaN included."""
    lens, q_lens = RAGGED[1]
    b = _batch(jax.random.key(3), lens, q_lens)
    T = b["q"].shape[0]
    seq, _, n, _ = sl.flat_rows(b["cu"], b["q_pos"], b["kv_lens"], T)
    live = jnp.asarray(False)
    kw = dict(impl=impl, live=live)
    if impl == "pallas":
        kw["interpret"] = True
    scores = sl.dsa_index(b["qi"], b["w"], b["index_pool"] * jnp.nan, 0,
                          b["tables"], b["kv_lens"], b["q_pos"], b["cu"],
                          **kw)
    assert np.isneginf(np.asarray(scores)).all()
    pos, count = sl.dsa_select(
        jax.random.normal(jax.random.key(4), scores.shape), n, topk=8, **kw)
    assert not np.asarray(pos).any() and not np.asarray(count).any()
    kw.pop("interpret", None)
    rows = sl.pool_rows(pos + 5, b["tables"], seq, PS, **kw)
    assert not np.asarray(rows).any()
    if impl == "pallas":
        kw["interpret"] = True
    live = kw.pop("live")
    picked = sl.gather_selection(b["pool"] * jnp.nan, 2, rows + 1, live=live)
    cached, mask = sl.step_rows(pos + 5, count + 3, seq, seq, seq, seq >= 0,
                                live=live)
    assert not np.asarray(picked).any() and not np.asarray(mask).any()
    out = sl.dsa_attend(b["q"], picked, count + 3, cached + 2, b["q"][:, 0],
                        ~mask, place=0, scale=0.3, lat=24, live=live, **kw)
    assert not np.asarray(out).any()


def test_kernels_keep_their_entries_names_in_a_step_that_may_not_run_them():
    """What `live` is for: under a `lax.cond` a kernel's instruction is named
    `tpu_custom_call.<n>`; called with a flag it keeps `<entry>.<n>`, which is
    how a trace's reader finds it (tests/test_tpu_compile.py compiles the
    same for the chip: here the lowered text holds each entry's name)."""
    b = _batch(jax.random.key(5), *RAGGED[0])
    T = b["q"].shape[0]

    def step(live):
        seq, _, n, _ = sl.flat_rows(b["cu"], b["q_pos"], b["kv_lens"], T)
        kw = dict(impl="pallas", interpret=True, live=live)
        scores = sl.dsa_index(b["qi"], b["w"], b["index_pool"], 0,
                              b["tables"], b["kv_lens"], b["q_pos"], b["cu"],
                              **kw)
        pos, count = sl.dsa_select(scores, n, topk=8, **kw)
        rows = sl.pool_rows(pos, b["tables"], seq, PS, impl="pallas",
                            live=live)
        cached, mask = sl.step_rows(pos, count, seq, seq, seq, seq >= 0,
                                    live=live)
        return sl.dsa_attend(
            b["q"], sl.gather_selection(b["pool"], 2, rows, live=live),
            count, cached, b["q"][:, 0], mask, place=0, scale=0.3, lat=24,
            **kw)

    text = jax.jit(step).lower(jnp.asarray(True)).as_text()
    for entry in ("dsa_index_call", "dsa_select_call", "dsa_attend_call"):
        assert "@" + entry in text, entry


def test_pool_rows_by_products_is_the_lookup_an_element():
    """Pages of 16 under chunks of 128 positions, page ids up to 20,479 (two
    parts under 256): the one-hot products give the element-wise lookup's
    rows, and lower to no gather of single elements."""
    T, S, K, ps, width, pages = 9, 3, 64, 16, 24, 20480
    k1, k2, k3 = jax.random.split(jax.random.key(6), 3)
    tables = jax.random.randint(k1, (S, width), 0, pages, jnp.int32)
    seq = jax.random.randint(k2, (T,), 0, S, jnp.int32)
    positions = jnp.sort(jax.random.randint(k3, (T, K), 0, width * ps,
                                            jnp.int32), axis=1)
    want = sl.pool_rows(positions, tables, seq, ps, impl="reference")
    got = sl.pool_rows(positions, tables, seq, ps, impl="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.asarray(want).max()) > 256 * 16 * 16      # both parts used
    text = jax.jit(lambda p: sl.pool_rows(p, tables, seq, ps, impl="pallas")
                   ).lower(positions).as_text()
    assert "slice_sizes = array<i64: 1, 1>" not in text
    # pages of 4 (the tiny sizes) take the plain lookup: same rows
    small = sl.pool_rows(positions // 4, tables, seq, 4, impl="pallas")
    np.testing.assert_array_equal(
        np.asarray(small), np.asarray(sl.pool_rows_reference(
            positions // 4, tables, seq, 4)))
