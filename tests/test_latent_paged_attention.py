"""The latent (MLA) paged-attention kernel (ops/paged_attention.py,
`_latent_kernel`) against its jnp references, in interpret mode on the CPU:
float32, pages of 4 tokens, 8 heads of 128 lanes of which the first 64 are
the values (the narrowest row that keeps whole lane tiles).

A case is (q_lens, kv_lens, T); sizes are (query tokens a block of many,
pages a step of a block of one token, pages a step of a block of many), put
where `latent_q_block` / `latent_kv_pages` would put the swept ones. Page 0
of the pool is NaN and no table names it before a context's end, every entry
past the end does: a kernel that started a DMA for a page past the end (the
form before PR 36 read the last page again) reads NaN into a product.
"""

import numpy as np
import pytest

import ray_tpu  # noqa: F401

H, W, LAT, PS, LAYERS = 8, 128, 64, 4, 3
SCALE = 0.25

_WALKS = {
    # contexts of one token, of no whole number of tiles, of exactly two
    "decode_only": ((1, 1, 1, 1), (9, 1, 20, 16), 8),
    "slices_only": ((9, 5), (21, 5), 16),
    # the slice is 3 blocks of 4 (4 + 4 + 1): its last block is partly real,
    # its first two exit (q_pos + n) short of kv_len
    "mixed": ((1, 9, 1), (12, 21, 5), 16),
    "slice_deep_in_its_context": ((1, 6), (7, 30), 8),
    # a padding sequence between real ones, padding tokens after
    "padding_sequence_and_tokens": ((1, 0, 2), (6, 0, 9), 16),
    "slice_from_position_zero": ((9,), (9,), 16),
    # A block's last step starts the FIRST tile of the block behind it: of
    # that block's sequence and tile size, not its own (one -> many -> one).
    "one_many_one": ((1, 6, 1, 5, 1), (40, 33, 37, 5, 9), 16),
    # contexts of exactly k tiles (48, 96, 192 tokens: every size's tile
    # divides one of them), of k tiles and one page, of one ragged tile
    "whole_tiles_and_one_page_more": (
        (1, 1, 1, 1, 1, 1), (48, 52, 96, 3, 192, 49), 8),
    "slices_of_whole_tiles": ((8, 1, 8), (48, 96, 100), 24),
    # the last real block is a slice's, and only padding follows it
    "last_block_then_padding": ((1, 7), (30, 41), 16),
    # rows that hold three layers' side by side: a page's DMA is a window
    "windowed_pool": ((1, 6, 1), (40, 33, 9), 16),
}

_SIZES = [(4, 2, 1), (4, 1, 2), (2, 3, 3), (8, 4, 2), (4, 16, 16)]
SIDE_BY_SIDE = 3


def _case(seed, q_lens, kv_lens, T, side_by_side=1):
    rng = np.random.default_rng(seed)
    S = len(q_lens)
    max_pages = max(-(-int(n) // PS) for n in kv_lens) + 1
    cu = np.zeros(S + 1, np.int32)
    cu[1:] = np.cumsum(q_lens)
    assert cu[-1] <= T
    kv_lens = np.asarray(kv_lens, np.int32)
    q_pos = np.maximum(kv_lens - np.asarray(q_lens, np.int32), 0)
    pool = rng.standard_normal(
        (LAYERS, 1 + S * max_pages, PS, side_by_side * W)).astype(np.float32)
    tables = rng.permutation(S * max_pages).astype(np.int32).reshape(
        S, max_pages) + 1
    for s, n in enumerate(kv_lens):
        tables[s, -(-int(n) // PS):] = 0
    q = rng.standard_normal((T, H, W)).astype(np.float32)
    return q, pool, tables, kv_lens, q_pos, cu


def _sizes(monkeypatch, pa, sizes):
    TQ, one, many = sizes
    monkeypatch.setattr(pa, "latent_q_block", lambda heads, width: TQ)
    monkeypatch.setattr(pa, "latent_kv_pages", lambda *a: (one, many))
    # a tile of 3 pages is a run of 2 and one more
    monkeypatch.setattr(pa, "PAGE_RUN", 2)
    # of a whole tile of 4 pages one is started before the wait, one behind
    # the scores, two behind the values
    monkeypatch.setattr(pa, "LATENT_ASK", (16, 32))


@pytest.mark.parametrize("sizes", _SIZES, ids=lambda s: "q%d-one%d-many%d" % s)
@pytest.mark.parametrize("walk", sorted(_WALKS))
def test_latent_kernel_matches_reference(cpu_jax, monkeypatch, walk, sizes):
    """Both entry points against the references: the token-major entry on
    the case as given, the rectangular entry on each sequence's own rows.
    The pool's layers differ and the tables are shuffled, so a wrong layer or
    page fails; with page 0 poisoned the outputs stay finite and equal, so no
    page past a context's end was read."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    _sizes(monkeypatch, pa, sizes)
    q_lens, kv_lens, T = _WALKS[walk]
    windowed = walk.startswith("windowed")
    q, pool, tables, kvl, q_pos, cu = _case(
        len(walk), q_lens, kv_lens, T, SIDE_BY_SIDE if windowed else 1)
    layer = jnp.int32(len(walk) % LAYERS)
    if windowed:        # (group, place): the layer's lane block of the rows
        layer = (layer, jnp.int32(len(walk) % SIDE_BY_SIDE))
    kw = dict(scale=SCALE, lat=LAT)
    tail = (jnp.asarray(tables), jnp.asarray(kvl), jnp.asarray(q_pos))
    flat = (jnp.asarray(q), jnp.asarray(pool), layer) + tail + (
        jnp.asarray(cu),)
    ref = np.asarray(pa.latent_paged_attention_unified_reference(*flat, **kw))
    poisoned = pool.copy()
    poisoned[:, 0] = np.nan
    out = np.asarray(pa.latent_paged_attention_unified(
        flat[0], jnp.asarray(poisoned), *flat[2:], **kw))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    assert not out[cu[-1]:].any()
    # the rectangular entry: every sequence padded to the longest row
    Bq = max(q_lens)
    rect = np.zeros((len(q_lens), Bq, H, W), np.float32)
    for s, n in enumerate(q_lens):
        rect[s, :n] = q[cu[s]:cu[s + 1]]
    rargs = (jnp.asarray(rect), jnp.asarray(pool), layer) + tail
    rref = np.asarray(pa.latent_paged_attention_reference(*rargs, **kw))
    rout = np.asarray(pa.latent_paged_attention(
        rargs[0], jnp.asarray(poisoned), *rargs[2:], **kw))
    for s, n in enumerate(q_lens):
        np.testing.assert_allclose(rout[s, :n], rref[s, :n], rtol=1e-5,
                                   atol=1e-5, err_msg=f"sequence {s}")
        np.testing.assert_allclose(rout[s, :n], out[cu[s]:cu[s + 1]],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("walk", ["mixed", "padding_sequence_and_tokens"])
def test_no_row_of_a_padding_block_or_slot_is_read_back(cpu_jax, monkeypatch,
                                                       walk):
    """The kernel writes nothing for a padding block and only its real
    tokens' rows matter of a real one: with every other row of the blocks'
    outputs poisoned, the flat result is the reference's, and the kernel
    reports its real blocks first."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    TQ = 4
    _sizes(monkeypatch, pa, (TQ, 2, 1))
    q_lens, kv_lens, T = _WALKS[walk]
    q, pool, tables, kvl, q_pos, cu = _case(3, q_lens, kv_lens, T)
    S = len(q_lens)
    kw = dict(scale=SCALE, lat=LAT)
    padded = -(-(T + TQ) // pa.LATENT_Q_PAD) * pa.LATENT_Q_PAD
    seq, local, blk_n, slot_tok, first = pa.query_blocks(
        jnp.asarray(cu), padded, S, TQ)
    nb_real = int(np.sum(np.asarray(blk_n) > 0))
    assert nb_real == sum(-(-n // TQ) for n in q_lens)
    assert (np.asarray(blk_n)[:nb_real] > 0).all()
    blocks = np.array(pa.paged_attention_latent_call(
        jnp.pad(jnp.asarray(q), ((0, padded - T), (0, 0), (0, 0))),
        seq.astype(jnp.int32),
        (jnp.asarray(q_pos)[seq] + local * TQ).astype(jnp.int32),
        blk_n.astype(jnp.int32), slot_tok[:, 0].astype(jnp.int32),
        jnp.int32(nb_real), jnp.asarray(pool), jnp.int32(1),
        jnp.asarray(tables), jnp.asarray(kvl), TQ=TQ, kv_pages=(2, 1),
        interpret=True, **kw))
    assert blocks.shape == (S + padded // TQ, TQ * H, LAT)
    for b, n in enumerate(np.asarray(blk_n)):
        blocks[b, int(n) * H:] = np.nan
    out = np.asarray(pa.blocks_to_tokens(
        jnp.asarray(blocks), jnp.asarray(cu), first, T, S, TQ, H))
    ref = np.asarray(pa.latent_paged_attention_unified_reference(
        jnp.asarray(q), jnp.asarray(pool), jnp.int32(1), jnp.asarray(tables),
        jnp.asarray(kvl), jnp.asarray(q_pos), jnp.asarray(cu), **kw))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ask", [(3, 4), (24, 16), (64, 64)],
                         ids=lambda a: "ask%d-%d" % a)
def test_a_fast_step_starts_its_pages_between_the_products(cpu_jax,
                                                           monkeypatch, ask):
    """Tiles of 64 pages of 4 tokens and values 256 wide: a one-token block
    takes its scores in two chunks of columns and its values in two, and a
    fast step starts the next tile's pages before its wait and behind each
    chunk (at (64, 64) more are asked for than a tile holds: none twice)."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "latent_q_block", lambda heads, width: 2)
    monkeypatch.setattr(pa, "latent_kv_pages", lambda *a: (64, 32))
    monkeypatch.setattr(pa, "LATENT_ASK", ask)
    rng = np.random.default_rng(sum(ask))
    wide, lat, heads = 384, 256, 2
    kvl = np.array([256 * 3 + 5, 256 * 2, 40], np.int32)
    q_lens = np.array([1, 1, 3], np.int32)
    pages = -(-int(kvl.max()) // PS) + 1
    pool = rng.standard_normal((2, 1 + 3 * pages, PS, wide)).astype(
        np.float32)
    pool[:, 0] = np.nan
    tables = rng.permutation(3 * pages).astype(np.int32).reshape(3, -1) + 1
    for s, n in enumerate(kvl):
        tables[s, -(-int(n) // PS):] = 0
    q = rng.standard_normal((8, heads, wide)).astype(np.float32)
    args = (jnp.asarray(q), jnp.asarray(pool), jnp.int32(1),
            jnp.asarray(tables), jnp.asarray(kvl), jnp.asarray(kvl - q_lens),
            jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32))
    kw = dict(scale=SCALE / 2, lat=lat)
    out = np.asarray(pa.latent_paged_attention_unified(*args, **kw))
    clean = np.nan_to_num(pool)
    ref = np.asarray(pa.latent_paged_attention_unified_reference(
        args[0], jnp.asarray(clean), *args[2:], **kw))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sizes", [(4, 2, 1), (4, 1, 2), (2, 3, 3)],
                         ids=lambda s: "q%d-one%d-many%d" % s)
def test_blocks_behind_one_that_starts_nothing(cpu_jax, monkeypatch, sizes):
    """Blocks laid by hand, as no caller lays them: a padding block and a
    block of no pages BETWEEN real ones. Neither starts the first tile of
    the block behind it, which then starts its own, in the slot the walk
    left off at; every real block's rows are the reference's, the block of
    no pages writes zeros."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    TQ, one, many = sizes
    _sizes(monkeypatch, pa, sizes)
    # (sequence, tokens, context): sequence 3 holds nothing
    laid = [(0, 1, 40), (0, 0, 0), (1, TQ, 33), (3, 1, 0), (2, 1, 21),
            (0, 0, 0), (1, 2, 12), (3, 1, 0), (3, 0, 0)]
    q, pool, tables, _, _, _ = _case(7, (1,) * 4, (40, 33, 21, 0),
                                     TQ * len(laid))
    kw = dict(scale=SCALE, lat=LAT)
    seq, n, ctx = (np.asarray(x, np.int32) for x in zip(*laid))
    kvl = np.array([40, 33, 21, 0], np.int32)
    pos = np.maximum(ctx - n, 0)
    tok = np.arange(len(laid), dtype=np.int32) * TQ
    blocks = np.asarray(pa.paged_attention_latent_call(
        jnp.asarray(q), jnp.asarray(seq), jnp.asarray(pos), jnp.asarray(n),
        jnp.asarray(tok), jnp.int32(len(laid)), jnp.asarray(pool),
        jnp.int32(2), jnp.asarray(tables), jnp.asarray(kvl), TQ=TQ,
        kv_pages=(one, many), interpret=True, **kw))
    for b, (s, count, context) in enumerate(laid):
        got = blocks[b, :count * H].reshape(count, H, LAT)
        if not context:
            assert not got.any(), b
            continue
        want = pa.latent_paged_attention_unified_reference(
            jnp.asarray(q[tok[b]:tok[b] + count]), jnp.asarray(pool),
            jnp.int32(2), jnp.asarray(tables[s:s + 1]),
            jnp.asarray([context], jnp.int32), jnp.asarray(pos[b:b + 1]),
            jnp.asarray([0, count], jnp.int32), **kw)
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5,
                                   atol=1e-5, err_msg=f"block {b}")


@pytest.mark.parametrize("heads,width,lat", [
    (128, 640, 512), (64, 640, 512), (16, 640, 512), (128, 384, 256),
    (256, 1152, 1024)],
    ids=["deepseek-v2", "64-heads", "16-heads", "narrow-rows", "wide-rows"])
def test_sizes_follow_the_widths_and_fit_vmem(cpu_jax, heads, width, lat):
    """`latent_q_block` / `latent_kv_pages` give the swept sizes at the
    published widths and, at others, sizes whose buffers fit the budget under
    the 16 MB a kernel is scoped on the v5e: whole sublane tiles of query
    tokens, whole pages, tiles of whole lane tiles."""
    from ray_tpu.ops import paged_attention as pa

    ps = 16
    TQ = pa.latent_q_block(heads, width)
    one, many = pa.latent_kv_pages(heads, width, lat, ps)
    assert 1 <= TQ <= 64 and (TQ < 8 or TQ % 8 == 0)
    assert one >= 1 and many >= 1
    assert (one * ps) % 128 == 0 and (many * ps) % 128 == 0
    need = pa.latent_vmem_bytes(heads, width, lat, ps, TQ, one, many)
    assert need <= pa.LATENT_VMEM_BUDGET < 16 * 2 ** 20
    if (heads, width) == (128, 640):
        assert TQ == pa.LATENT_Q_BLOCK
        assert (one * ps, many * ps) == (pa.LATENT_TILE_ONE,
                                         pa.LATENT_TILE_MANY)
