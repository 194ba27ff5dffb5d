"""Ragged paged attention: the serving-decode hot op.

Reference analog: the paged-attention CUDA kernels inside vLLM, which the
reference repo only places (python/ray/llm/_internal/serve/deployments/llm/
vllm/vllm_engine.py:222). TPU-native design: one kernel serves BOTH decode
(one query token per sequence) and chunked prefill (a block of query tokens
per sequence) — "ragged" means each sequence in the batch has its own query
count and context length; shapes stay static (bucketed) and per-sequence
lengths arrive as scalar-prefetch operands.

Layouts:
  q:            (S, Bq, H, hd)  — rectangular: Bq query tokens per sequence
                                  (1 for decode, chunk size for prefill)
                (T, H, hd)      — token-major: sequence s owns rows
                                  [cu_q_lens[s], cu_q_lens[s+1])
  k/v pool:     (L, P, ps, K, hd) — the WHOLE pool as it lies on the device
                                  (llm/model_runner.py, "The KV pool's
                                  layout"): page-major, one token's (K, hd)
                                  minor, K = kv heads. The V pool may be
                                  narrower, (L, P, ps, K, vd): q and k share
                                  hd, the output is vd wide
                (L, P, ps, K * hd) — a ROW POOL: a token's row whole on
                                  the lanes (`kv_heads=K` says how many heads
                                  lie in it), for head counts that are no
                                  whole number of sublane tiles: XLA lays (10,
                                  128) bf16 out as 16 rows, 1.6 x the bytes
                                  in HBM and in every page DMA (compiled for
                                  a described v5e, PR 35), and a row of 1,280
                                  lanes as it is. A kv head's part of a tile
                                  is then a static slice of whole lane tiles
  layer:        () int32        — which layer's pages to read
  block_tables: (S, max_pages)  int32, logical page i of seq s -> pool page
  kv_lens:      (S,) int32      — context length INCLUDING this step's tokens
  q_positions:  (S,) int32      — absolute position of a sequence's first
                                  query token
  window:       static int|None — token i sees j with 0 <= i - j < window
                                  (None: every j <= i). With a window the
                                  block table is a RING: logical page p of
                                  seq s is block_tables[s, p % width], and a
                                  page behind every window is never looked up
  sink:         (H,) float32|None — a logit a head that joins the softmax's
                                  denominator and carries no value

`(K, hd)` minor is the layout of the WRITE (XLA's scatter of a step's new rows
has a `(K, hd)` update window and wants it minor; any other declaration is
re-laid out whole in every step program: PERF.md, PR 27), and the kernel
takes it as it lies: the pools are `memory_space=ANY` operands, the layer a
scalar-prefetch operand, so no layer's pages are sliced out, transposed or
copied on the way in. One DMA is one page of ALL kv heads, `(ps, K, hd)`
contiguous in HBM (32 KB at 16 x 8 x 128 bf16). Off the device, pages travel
in the wire view `(L, K, n, ps, hd)` (`ModelRunner.gather_pages` /
`scatter_pages`).

One Pallas kernel, `_kv_kernel`, serves both entry points. Its grid walks
QUERY BLOCKS of ONE sequence (`query_blocks`): a decode row is a block of one
token (its H rows), a prefill slice or a draft-verify row is cut into
ceil(n / Q_BLOCK) blocks, and a block walks its sequence's pages up to its
own last token (`min(kv_len, q_pos + n)`: the causal exit), KV_PAGES pages a
loop step. A block reads its own tokens' rows out of the flat q (one DMA,
behind which the first tile's pages are started); all of a step's page DMAs
are in flight before the first wait and the next tile's are started before
this tile's products (two slots). A tile lands as `(tile, K, hd)`. A block of
one token reads it as `(tile x K, hd)`: column `j * K + kh` of the scores is
context token j under kv head kh, its H rows are multiplied against every
column and the other heads' columns masked (the same eight MXU weight tiles
as eight per-head products of G rows each, no strided read: 1.9 x faster
than the per-head form on the v5e). A block of many tokens turns the tile
to `(K, tile, hd)` and multiplies every kv head's Q_BLOCK x G rows against
that head's `(tile, hd)` in one product batched over the heads (against a
strided read a head: 7-24% faster on a 128-token slice, and an eighth of
the kernel's trace). Both products take the pool's dtype with float32
accumulation; the scale is applied to the float32 scores; the softmax state
is float32. Cost is O(actual context), never O(max context); with a window
it is O(window): a block starts its walk at the page that holds its first
token's oldest visible position, and no DMA is started for a page behind it.
A sink logit is the softmax state's first column: the state starts at (m, l,
acc) = (sink, 1, 0) and not at (-inf, 0, 0). Measured alone
(16 layers a call, Mistral-7B widths, PERF.md section 6, PR 32): 62% of the
v5e's 819 GB/s on 28 decode rows x ~700 tokens.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_tag
from ray_tpu.ops.attention import vma_of

NEG_INF = -1e30

# Query tokens a block (a 128-token slice reads its context 128 / Q_BLOCK
# times) and pool pages a loop step (a tile of KV_PAGES * ps context tokens).
# Swept on the v5e at Mistral-7B's widths over {16, 32, 64, 128} x {4, 8, 16,
# 32} (PERF.md section 6, PR 32): 16 pages are best for decode rows at ~700
# tokens, 32 for slices at 2.4k; 128 tokens with 16 pages run out of VMEM.
# At 64 heads with 256-lane q and K rows (models/mimo_v2_flash.py; PR 33)
# `q_block` gives 32 tokens a block, and 16 pages stay: 32 decode rows at
# ~33.9k tokens read 8.90 / 7.73 / 7.61 ms a layer at 8 / 16 / 24 pages, a
# 128-token slice at 33k 3.41 / 2.76 / 2.91, and 32 pages run out of VMEM
# (17.2 MB of the 16 MB scoped limit; it shows on the chip, not at compile).
Q_BLOCK = 64
KV_PAGES = 16


def q_block(heads: int) -> int:
    """Query tokens a block for a model of `heads` query heads: Q_BLOCK up to
    32 heads (where it was swept), fewer beyond so that a block's rows (tokens
    x heads) and with them its float32 scores stay the size that fits VMEM; a
    whole number of sublane tiles (40 heads: 48)."""
    return min(Q_BLOCK, max(8, Q_BLOCK * 32 // heads // 8 * 8))


def pair_queries(q):
    """The PAIR FORM of the K/V kernel's operands, for differential attention
    at a head width of half a lane tile (models/phi4flash.py): a pool row is
    one kv pair, K `[k_2j | k_2j+1]` and V `[v_2j | v_2j+1]`, 2 hd wide, K /
    2 of them a token's row, and q (..., H, hd) rides as (..., H, 2 hd) with an even head's values
    in the first half of its row and an odd head's in the second, zeros in
    the other. The kernel's product of such a row with a K row is the head's
    product with its OWN k, its softmax the head's, its value sum over the
    whole V row: heads 2p and 2p + 1 come back as the two softmax sums of
    query pair p over kv pair p // (H / K), which the caller subtracts. No K
    or V value lies in HBM twice and the kernel is `_kv_kernel`, full and
    window form, over row pools (`kv_heads` = K / 2; pass `scale` = 1 /
    sqrt(hd): the row is 2 hd wide)."""
    *lead, H, hd = q.shape
    pairs = q.reshape(*lead, H // 2, 2, hd)
    zeros = jnp.zeros_like(pairs[..., 0, :])
    return jnp.stack(
        [jnp.concatenate([pairs[..., 0, :], zeros], axis=-1),
         jnp.concatenate([zeros, pairs[..., 1, :]], axis=-1)],
        axis=-2).reshape(*lead, H, 2 * hd)


def _gather_context(pool, layer, pages):
    """(S, n * ps, K, w): pages `pages` (S, n) of `layer`."""
    S, n = pages.shape
    _, _, ps, K, w = pool.shape
    return pool[layer][pages].reshape(S, n * ps, K, w)


def ragged_paged_attention_reference(
        q, k_pool, v_pool, layer, block_tables, kv_lens, q_positions, *,
        scale: Optional[float] = None, window: Optional[int] = None,
        sink=None, kv_heads: Optional[int] = None):
    """jnp reference (CPU tests + fallback). Gathers the full padded context
    (with a window: the ring's pages from the first query token's oldest
    visible page on); the Pallas kernel below is the O(actual-context)
    implementation."""
    S, Bq, H, hd = q.shape
    if kv_heads is not None:    # row pools: the same rows, (K, w) apart
        k_pool, v_pool = (p.reshape(*p.shape[:3], kv_heads, -1)
                          for p in (k_pool, v_pool))
    ps, K = k_pool.shape[2], k_pool.shape[3]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    width = block_tables.shape[1]
    if window is None:
        first, pages = jnp.zeros((S,), jnp.int32), block_tables
    else:
        first = jnp.maximum(q_positions - (window - 1), 0) // ps
        pages = jnp.take_along_axis(
            block_tables, (first[:, None] + jnp.arange(width)) % width,
            axis=1)
    k = _gather_context(k_pool, layer, pages)
    v = _gather_context(v_pool, layer, pages)
    max_ctx = k.shape[1]
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("sqhd,skhd->shqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    k_pos = ((first * ps)[:, None] + jnp.arange(max_ctx))[:, None, None, :]
    q_abs = (q_positions[:, None] + jnp.arange(Bq)[None, :])[:, None, :, None]
    mask = (k_pos < kv_lens[:, None, None, None]) & (q_abs >= k_pos)
    if window is not None:
        mask &= q_abs - k_pos < window
    logits = jnp.where(mask, logits, NEG_INF)
    if sink is not None:    # one more column, of no value
        column = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None], (S, H, Bq, 1))
        probs = jax.nn.softmax(jnp.concatenate([logits, column], axis=-1),
                               axis=-1)[..., :-1].astype(v.dtype)
    else:
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("shqk,skhd->sqhd", probs, v)


def token_seq_ids(cu_q_lens, T: int, S: int):
    """Sequence id per flat token (count of cu boundaries at or below it),
    clamped into [0, S-1] so padding tokens index real scalar rows; the
    caller masks them out separately (tok >= cu_q_lens[S])."""
    tok = jnp.arange(T)
    seq = jnp.sum(tok[:, None] >= cu_q_lens[None, 1:], axis=1).astype(
        jnp.int32)
    return jnp.minimum(seq, S - 1)


def ragged_paged_attention_unified_reference(
        q, k_pool, v_pool, layer, block_tables, kv_lens, q_positions,
        cu_q_lens, *, scale: Optional[float] = None,
        window: Optional[int] = None, sink=None,
        kv_heads: Optional[int] = None):
    """Token-major unified reference: q is flat (T, H, hd), sequences own
    contiguous row spans delimited by cu_q_lens (S+1 cumulative starts).

    Implemented by scattering the flat rows back into the rectangular
    (S, T, H, hd) layout and calling ragged_paged_attention_reference —
    per-row math is THE SAME FUNCTION, so a row's output does not depend on
    which rows share its launch (the CPU-CI anchor of the engine's tests
    against the plain forward pass)."""
    T, H, hd = q.shape
    S = kv_lens.shape[0]
    seq = token_seq_ids(cu_q_lens, T, S)
    local = jnp.arange(T) - cu_q_lens[seq]
    valid = jnp.arange(T) < cu_q_lens[S]
    # Padding tokens scatter to column T (out of bounds -> dropped): never
    # a wrapped negative index, which would silently overwrite real rows.
    qr = jnp.zeros((S, T, H, hd), q.dtype).at[
        seq, jnp.where(valid, local, T)].set(q, mode="drop")
    out_r = ragged_paged_attention_reference(
        qr, k_pool, v_pool, layer, block_tables, kv_lens, q_positions,
        scale=scale, window=window, sink=sink, kv_heads=kv_heads)
    out = out_r[seq, jnp.minimum(local, T - 1)]
    return jnp.where(valid[:, None, None], out, jnp.zeros_like(out))


# ---------------------------------------------------------------------------
# Query blocks of one sequence (both Pallas kernels' grids)
# ---------------------------------------------------------------------------

def query_blocks(cu_q_lens, T: int, S: int, TQ: int):
    """Cut a flat mixed batch into blocks of up to TQ query tokens of ONE
    sequence, at most NB = S + T // TQ of them. Returns (seq, local, blk_n,
    slot_tok, first): block b holds blk_n[b] tokens (0: a padding block) of
    sequence seq[b], of which it is block local[b]; slot_tok (NB, TQ) are the
    flat tokens of its slots; sequence s's first block is first[s]."""
    NB = S + T // TQ
    n_s = cu_q_lens[1:] - cu_q_lens[:-1]                      # (S,)
    blocks_s = (n_s + TQ - 1) // TQ                           # blocks a seq
    end = jnp.cumsum(blocks_s)
    first = end - blocks_s                                    # its first
    b = jnp.arange(NB)
    seq = jnp.minimum(jnp.sum(b[:, None] >= end[None, :], axis=1), S - 1)
    local = b - first[seq]                                    # block of seq
    blk_n = jnp.where(b < end[S - 1],
                      jnp.clip(n_s[seq] - local * TQ, 0, TQ), 0)
    slot_tok = (cu_q_lens[seq] + local * TQ)[:, None] + jnp.arange(TQ)
    return seq, local, blk_n, slot_tok, first


def blocks_to_tokens(out, cu_q_lens, first, T: int, S: int, TQ: int, H: int):
    """The blocks' outputs (NB, TQ * H, w) gathered back into the flat token
    order (T, H, w), padding tokens zero."""
    NB, _, w = out.shape
    tok_seq = token_seq_ids(cu_q_lens, T, S)
    tok_local = jnp.arange(T) - cu_q_lens[tok_seq]
    tok_slot = (first[tok_seq] + tok_local // TQ) * TQ + tok_local % TQ
    flat = out.reshape(NB * TQ, H, w)[jnp.clip(tok_slot, 0, NB * TQ - 1)]
    valid = jnp.arange(T) < cu_q_lens[S]
    return jnp.where(valid[:, None, None], flat, jnp.zeros_like(flat))


# ---------------------------------------------------------------------------
# K/V paged attention: the Pallas kernel
# ---------------------------------------------------------------------------

def _kv_kernel(blk_seq_ref, blk_pos_ref, blk_n_ref, blk_tok_ref, meta_ref,
               block_tables_ref, kv_lens_ref,               # scalar prefetch
               q_hbm, kpool_hbm, vpool_hbm,                 # tensor inputs
               *rest,                                       # [sink], out, scratch
               ps: int, KB: int, scale: float, TQ: int, H: int, K: int,
               window: Optional[int], has_sink: bool, flat: bool = False):
    """Grid: (NB,). Block b is up to TQ query tokens of sequence blk_seq[b]:
    blk_n[b] of them are real (0: a padding block, which does nothing), the
    first is flat token blk_tok[b] of q_hbm (tokens, H, hd) at absolute
    position blk_pos[b]. meta = (layer, real blocks). o_ref: (1, TQ * H, vd),
    rows token-major (t * H + h). q and the pools stay in HBM: a block reads
    its own tokens' rows, and k_scr / v_scr hold two tiles of KB pages,
    (tile, K, hd) and (tile, K, vd). sink_ref, where the layer has one: (H, 1)
    float32. `flat`: the pools are ROW POOLS, (L, P, ps, K * hd) and (L, P,
    ps, K * vd), a token's row whole on the lanes (the module docstring says
    why): the tiles are (tile, K * hd) and (tile, K * vd), a kv head's part a
    static slice of whole lane tiles, and a block of one token takes the
    batched product too."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if has_sink:
        sink_ref, *rest = rest
    o_ref, q_scr, k_scr, v_scr, sems, q_sem = rest
    b = pl.program_id(0)
    s = blk_seq_ref[b]
    n = blk_n_ref[b]
    q_pos = blk_pos_ref[b]
    tok0 = blk_tok_ref[b]
    layer = meta_ref[0]
    G = H // K
    hd = q_scr.shape[-1]
    vd = v_scr.shape[-1] // K if flat else v_scr.shape[-1]
    width = block_tables_ref.shape[1]
    # No row of the block sees past its last real token, and with a window
    # none sees a page before the one that holds q_pos - (window - 1).
    kv_len = jnp.minimum(kv_lens_ref[s], q_pos + n)
    page0 = 0 if window is None else jnp.maximum(
        q_pos - (window - 1), 0) // ps
    n_pages = pl.cdiv(kv_len, ps) - page0
    n_tiles = pl.cdiv(n_pages, KB)
    tile = KB * ps

    @pl.when(b == 0)
    def _():
        # A tile's slots past the context's last page are not DMA'd: what
        # they hold is masked out of the scores but multiplied (by zero) in
        # the second product, so it has to be finite from the first block on.
        k_scr[...] = jnp.zeros_like(k_scr)
        v_scr[...] = jnp.zeros_like(v_scr)

    def tile_dma(slot, i, go):
        """Start (or wait for) the real pages of tile i: one DMA a page of
        all kv heads, each to its place in the slot."""
        def page_dma(j, _):
            logical = page0 + i * KB + j
            page = block_tables_ref[
                s, logical if window is None else logical % width]
            rows = pl.ds(pl.multiple_of(j * ps, ps), ps)
            for pool, scr, sem in ((kpool_hbm, k_scr, sems.at[0, slot]),
                                   (vpool_hbm, v_scr, sems.at[1, slot])):
                go(pltpu.make_async_copy(
                    pool.at[layer, page], scr.at[slot, rows], sem))
            return _

        jax.lax.fori_loop(0, jnp.minimum(KB, n_pages - i * KB), page_dma, 0)

    def fetch_q(nq: int):
        """The block's first nq tokens' rows into q_scr, the first tile's
        pages started behind them."""
        copy = pltpu.make_async_copy(
            q_hbm.at[pl.ds(tok0, nq)], q_scr.at[pl.ds(0, nq)], q_sem)
        copy.start()
        tile_dma(0, 0, lambda c: c.start())
        copy.wait()

    def scores(q, k):
        """q (..., rows, hd) . k (..., cols, hd) -> (..., rows, cols), float32,
        scaled; a leading axis is a batch of kv heads."""
        heads = tuple(range(q.ndim - 2))
        return jax.lax.dot_general(
            q, k, (((q.ndim - 1,), (k.ndim - 1,)), (heads, heads)),
            preferred_element_type=jnp.float32) * scale

    def fold(state, sc, ok, v):
        """One online-softmax step of masked float32 scores sc (..., rows,
        cols) against values v (..., cols, vd)."""
        m, l, acc = state
        sc = jnp.where(ok, sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        # Explicit zero where masked: a row whose tile is all masked would
        # otherwise add exp(NEG_INF - NEG_INF) == 1 a column.
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        heads = tuple(range(v.ndim - 2))
        acc_new = alpha * acc + jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((p.ndim - 1,), (v.ndim - 2,)), (heads, heads)),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    def init(rows, sink):
        """The softmax state before the first tile; `sink` rows + (1,): the
        rows' sink logits, a first column of no value."""
        acc = jnp.zeros(rows + (vd,), dtype=jnp.float32)
        if sink is not None:
            return sink, jnp.ones(rows + (1,), dtype=jnp.float32), acc
        return (jnp.full(rows + (1,), NEG_INF, dtype=jnp.float32),
                jnp.zeros(rows + (1,), dtype=jnp.float32), acc)

    def pipelined(step, state):
        """state after step(i, slot, state) over the block's tiles (the
        first already started), tile i + 1 in flight while tile i is
        computed."""
        def body(i, state):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_tiles)
            def _():
                tile_dma(1 - slot, i + 1, lambda c: c.start())

            tile_dma(slot, i, lambda c: c.wait())
            return step(i, slot, state)

        return jax.lax.fori_loop(0, n_tiles, body, state)

    def walk_one():
        """A block of one token: its H rows against the tile read as
        (tile x K, hd), the other kv heads' columns masked."""
        cols = tile * K
        fetch_q(1)
        q = q_scr[0]                                         # (H, hd)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        row_kh = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // G
        mine = (col % K) == row_kh                           # (H, cols)
        k_off = page0 * ps + col // K                        # (1, cols)

        def step(i, slot, state):
            k = k_scr[slot].reshape(cols, hd)
            v = v_scr[slot].reshape(cols, vd)
            k_pos = i * tile + k_off
            ok = mine & (k_pos < kv_len)
            if window is not None:
                ok &= q_pos - k_pos < window
            return fold(state, scores(q, k), ok, v)

        m, l, acc = pipelined(
            step, init((H,), sink_ref[...] if has_sink else None))
        o_ref[0, :H] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    def by_head(scr, slot, w):
        """The tile in `slot` as (K, tile, w)."""
        if not flat:
            return jnp.swapaxes(scr[slot], 0, 1)
        rows = scr[slot]                                     # (tile, K * w)
        return jnp.stack([rows[:, kh * w:(kh + 1) * w] for kh in range(K)])

    def walk_heads(nq: int = TQ):
        """A block of up to nq tokens: the tile turned to (K, tile, hd), and
        every kv head's nq * G rows against that head's (tile, hd) in one
        product batched over the heads."""
        rows = nq * G
        fetch_q(nq)
        q = jnp.swapaxes(q_scr[:nq].reshape(nq, K, G, hd), 0, 1).reshape(
            K, rows, hd)
        q_abs = q_pos + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows, 1), 1) // G
        k_off = page0 * ps + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, tile), 2)

        def step(i, slot, state):
            k_pos = i * tile + k_off
            ok = (k_pos < kv_len) & (q_abs >= k_pos)         # (1, rows, tile)
            if window is not None:
                ok &= q_abs - k_pos < window
            k = by_head(k_scr, slot, hd)                     # (K, tile, hd)
            return fold(state, scores(q, k), ok, by_head(v_scr, slot, vd))

        sink = None
        if has_sink:    # row t * G + g of kv head kh is head kh * G + g
            sink = jnp.tile(sink_ref[...].reshape(K, G, 1), (1, nq, 1))
        m, l, acc = pipelined(step, init((K, rows), sink))
        out = (acc / jnp.maximum(l, 1e-30)).reshape(K, nq, G, vd)
        o_ref[0, :nq * H] = jnp.swapaxes(out, 0, 1).reshape(
            nq * H, vd).astype(o_ref.dtype)

    @pl.when((n > 0) & (n_tiles == 0))
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when((n_tiles > 0) & (n == 1))
    def _():
        walk_heads(1) if flat else walk_one()

    if TQ > 1:
        @pl.when((n_tiles > 0) & (n > 1))
        def _():
            walk_heads()


def _interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        from ray_tpu.ops import is_tpu_backend

        interpret = not is_tpu_backend()
    return interpret


# Jitted so that JAX traces the kernel once a process for each set of
# shapes (and NAMED for whoever reads a profile: inlined, the kernel's HLO
# instruction takes the jitted function's name, `paged_attention_kv_call.<n>`
# or, with a window, `paged_attention_window_call.<n>`, and the benchmark's
# reduction finds its kernels by `paged_attention_`): the token-major entry
# pads q to a multiple of Q_PAD tokens, so every token bucket of an engine's
# ladder up to Q_PAD - Q_BLOCK brings the same shapes and a step program's
# start pays the kernel's lowering alone (the trace is a third of what this
# kernel adds to a warm start: PERF.md, PR 32).
Q_PAD = 256


def _kv_call(q, blk_seq, blk_pos, blk_n, blk_tok, nb_real, k_pool, v_pool,
             layer, block_tables, kv_lens, sink, *, scale, TQ, kv_pages,
             window, interpret, kv_heads=None):
    """q (tokens, H, hd), every block's TQ tokens from blk_tok[b] in bounds
    -> the blocks' outputs (NB, TQ * H, vd). Of a padding block (b >=
    nb_real) nothing is written. `kv_heads`: the pools are row pools of that
    many kv heads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, H, hd = q.shape
    NB = blk_seq.shape[0]
    flat = kv_heads is not None
    if flat:
        ps, K = k_pool.shape[2], kv_heads
        vd = v_pool.shape[-1] // K
    else:
        _, _, ps, K, _ = k_pool.shape
        vd = v_pool.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    def out_block(b, seq, pos, n, tok, meta, *_):
        # A padding block keeps the last real block's buffer (and leaves it
        # alone), so nothing of it is written back.
        return jnp.minimum(b, jnp.maximum(meta[1] - 1, 0)), 0, 0

    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),   # q: a block reads its rows
        pl.BlockSpec(memory_space=pl.ANY),   # the K pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),   # the V pool stays in HBM
    ]
    operands = [q, k_pool, v_pool]
    if sink is not None:
        in_specs.append(pl.BlockSpec((H, 1), lambda b, *_: (0, 0)))
        operands.append(sink.astype(jnp.float32).reshape(H, 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(NB,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, TQ * H, vd), out_block),
        scratch_shapes=[
            pltpu.VMEM((TQ, H, hd), q.dtype),
            pltpu.VMEM((2, kv_pages * ps) + k_pool.shape[3:], k_pool.dtype),
            pltpu.VMEM((2, kv_pages * ps) + v_pool.shape[3:], v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    kernel = functools.partial(
        _kv_kernel, ps=ps, KB=kv_pages, scale=scale, TQ=TQ, H=H, K=K,
        window=window, has_sink=sink is not None,
        **({"flat": True} if flat else {}))
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(nb_real, jnp.int32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (NB, TQ * H, vd), q.dtype, vma=vma_of(q, k_pool, v_pool)),
        interpret=interpret,
        **kernel_tag("paged_attention_unified" if window is None
                     else "paged_attention_window"),
    )(blk_seq, blk_pos, blk_n, blk_tok, meta, block_tables, kv_lens,
      *operands)


_KV_STATIC = ("scale", "TQ", "kv_pages", "window", "interpret", "kv_heads")


@functools.partial(jax.jit, static_argnames=_KV_STATIC)
def paged_attention_kv_call(*args, **static):
    return _kv_call(*args, **static)


@functools.partial(jax.jit, static_argnames=_KV_STATIC)
def paged_attention_window_call(*args, **static):
    """The same kernel in its window form, under a name of its own so that
    a profile tells a window layer's kernel from a full layer's."""
    return _kv_call(*args, **static)


def _kv_entry(window: Optional[int]):
    return (paged_attention_kv_call if window is None
            else paged_attention_window_call)


def ragged_paged_attention_unified(q, k_pool, v_pool, layer, block_tables,
                                   kv_lens, q_positions, cu_q_lens, *,
                                   scale: Optional[float] = None,
                                   window: Optional[int] = None, sink=None,
                                   kv_heads: Optional[int] = None,
                                   interpret: Optional[bool] = None):
    """Pallas unified ragged paged attention: ONE launch for a mixed batch
    where each sequence contributes its own query-token count (decode = 1,
    spec verify = k+1, prefill chunk = up to chunk tokens). Layouts in the
    module docstring; rows past cu_q_lens[S] are padding and come back zero.
    The kernel reads each query block's rows out of the flat q and writes
    the blocks' outputs, which are gathered back into the flat order."""
    T, H, hd = q.shape
    S = kv_lens.shape[0]
    TQ = q_block(H)
    padded = -(-(T + TQ) // Q_PAD) * Q_PAD       # a last block's TQ tokens
    seq, local, blk_n, slot_tok, first = query_blocks(
        cu_q_lens, padded, S, TQ)
    out = _kv_entry(window)(
        jnp.pad(q, ((0, padded - T), (0, 0), (0, 0))),
        seq.astype(jnp.int32),
        (q_positions[seq] + local * TQ).astype(jnp.int32),
        blk_n.astype(jnp.int32), slot_tok[:, 0].astype(jnp.int32),
        jnp.sum(blk_n > 0), k_pool, v_pool, layer, block_tables, kv_lens,
        sink, scale=scale, TQ=TQ, kv_pages=KV_PAGES, window=window,
        interpret=_interpret(interpret),
        **({"kv_heads": kv_heads} if kv_heads else {}))
    return blocks_to_tokens(out, cu_q_lens, first, T, S, TQ, H)


def ragged_paged_attention(q, k_pool, v_pool, layer, block_tables, kv_lens,
                           q_positions, *, scale: Optional[float] = None,
                           window: Optional[int] = None, sink=None,
                           kv_heads: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Pallas ragged paged attention, rectangular: every sequence brings Bq
    query tokens (1: decode). The same kernel; the blocks are the
    rectangle's own rows, ceil(Bq / q_block) a sequence."""
    S, Bq, H, hd = q.shape
    TQ = min(q_block(H), Bq)
    per_seq = -(-Bq // TQ)
    pad = per_seq * TQ - Bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    NB = S * per_seq
    local = jnp.tile(jnp.arange(per_seq, dtype=jnp.int32), S)
    seq = jnp.repeat(jnp.arange(S, dtype=jnp.int32), per_seq)
    out = _kv_entry(window)(
        q.reshape(NB * TQ, H, hd), seq, q_positions[seq] + local * TQ,
        jnp.clip(Bq - local * TQ, 0, TQ),
        jnp.arange(NB, dtype=jnp.int32) * TQ, NB, k_pool, v_pool, layer,
        block_tables, kv_lens, sink, scale=scale, TQ=TQ, kv_pages=KV_PAGES,
        window=window, interpret=_interpret(interpret),
        **({"kv_heads": kv_heads} if kv_heads else {}))
    return out.reshape(S, per_seq * TQ, H, -1)[:, :Bq]


# ---------------------------------------------------------------------------
# Latent (MLA) paged attention
# ---------------------------------------------------------------------------
#
# A latent cache holds ONE row a token a layer, shared by every head:
# `[c_kv | k_rope | 0...]`, `W` wide (llm/model_runner.py, "The latent pool",
# says how a row lies in HBM). In the absorbed form a head's query is as wide
# as the row, `[q_nope W_kb^T | q_rope | 0...]`, its scores are one product
# with the row and its values are the row's first `lat` columns; W_kb and W_vb
# are applied to the query and to the output OUTSIDE these functions, by the
# model's layer step.
#
#   q:      (S, Bq, H, W) rectangular | (T, H, W) flat, as above
#   pool:   (L, P, ps, W): the WHOLE pool as it lies; `layer` picks the
#           layer by scalar prefetch, so no layer's pages are sliced out or
#           transposed on the way in (what ROADMAP S2 asks of the K/V kernels)
#   out:    (S, Bq, H, lat) | (T, H, lat)
#
# One Pallas kernel serves both entry points. Its grid walks QUERY BLOCKS of
# up to `q_block` tokens of ONE sequence (`query_blocks` above: a prefill
# slice is cut into ceil(n / q_block) of them, a decode row is a block of one
# token), so a sequence's context is read once a block and not once a token:
# a 128-token slice at an 8k context reads it 16 times at q_block 8. The
# context is
# DMA'd `kv_pages` pages at a time into one (kv_pages * ps, W) tile, so the
# two products of a step are (rows, W) x (W, 128) and (rows, 128) x (128,
# lat) at the default sizes: whole MXU passes, in bf16 with float32
# accumulation. A block of one token runs the same loop on its H rows alone.
# Operations a context byte (H = 128, W = 640, lat = 512, bf16): a decode row
# 128 x (640 + 512) x 2 / 1280 = 230, the v5e's ridge (240); a q_block of 8,
# 8 x that: bound by the MXU, which is why prefill keeps the absorbed form
# too: expanding K and V from a tile costs 2 x 512 x 128 x 256 operations a
# context token a block before any score, more than the absorbed form's 8 x
# 128 x 1152 x 2 until a block holds ~160 tokens, and a slice holds 128.

LATENT_Q_BLOCK = 8
LATENT_KV_PAGES = 8


def latent_paged_attention_reference(q, pool, layer, block_tables, kv_lens,
                                     q_positions, *, scale: float, lat: int):
    """jnp reference of the absorbed form over the full padded context."""
    S, Bq, H, W = q.shape
    ps = pool.shape[2]
    max_ctx = block_tables.shape[1] * ps
    rows = pool[layer][block_tables].reshape(S, max_ctx, W)
    logits = jnp.einsum("sqhw,skw->shqk", q, rows,
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(max_ctx)[None, None, None, :]
    q_abs = (q_positions[:, None] + jnp.arange(Bq)[None, :])[:, None, :, None]
    mask = (k_pos < kv_lens[:, None, None, None]) & (q_abs >= k_pos)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(rows.dtype)
    return jnp.einsum("shqk,skl->sqhl", probs, rows[..., :lat],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def latent_paged_attention_unified_reference(
        q, pool, layer, block_tables, kv_lens, q_positions, cu_q_lens, *,
        scale: float, lat: int):
    """Token-major reference: the flat rows scattered into the rectangle,
    then the SAME function as the rectangular reference (as
    ragged_paged_attention_unified_reference does for K/V pages)."""
    T, H, W = q.shape
    S = kv_lens.shape[0]
    seq = token_seq_ids(cu_q_lens, T, S)
    local = jnp.arange(T) - cu_q_lens[seq]
    valid = jnp.arange(T) < cu_q_lens[S]
    qr = jnp.zeros((S, T, H, W), q.dtype).at[
        seq, jnp.where(valid, local, T)].set(q, mode="drop")
    out_r = latent_paged_attention_reference(
        qr, pool, layer, block_tables, kv_lens, q_positions, scale=scale,
        lat=lat)
    out = out_r[seq, jnp.minimum(local, T - 1)]
    return jnp.where(valid[:, None, None], out, jnp.zeros_like(out))


def _latent_kernel(blk_seq_ref, blk_pos_ref, blk_n_ref, layer_ref,
                   block_tables_ref, kv_lens_ref,            # scalar prefetch
                   q_ref, pool_hbm,                          # tensor inputs
                   o_ref,                                    # output
                   kv_scr, sems,                             # scratch
                   *, ps: int, KB: int, scale: float, TQ: int, H: int,
                   lat: int):
    """Grid: (NB,). Block q_ref: (1, TQ * H, W), o_ref: (1, TQ * H, lat): the
    rows of up to TQ query tokens of sequence blk_seq[b], token-major; blk_n[b]
    of them are real (0: a padding block), the first at absolute position
    blk_pos[b]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    s = blk_seq_ref[b]
    n = blk_n_ref[b]
    q_pos = blk_pos_ref[b]
    layer = layer_ref[0]
    # No row of the block sees past its last real token.
    kv_len = jnp.minimum(kv_lens_ref[s], q_pos + n)
    n_pages = pl.cdiv(kv_len, ps)
    n_tiles = pl.cdiv(n_pages, KB)
    tile = KB * ps

    def tile_dma(slot, i):
        """The KB pages of tile i, each to its place in the slot; past the
        context's last page the last page again (finite rows, masked)."""
        copies = []
        for j in range(KB):
            page = block_tables_ref[s, jnp.minimum(i * KB + j, n_pages - 1)]
            copies.append(pltpu.make_async_copy(
                pool_hbm.at[layer, page],
                kv_scr.at[slot, pl.ds(j * ps, ps)], sems.at[slot]))
        return copies

    def walk(nq: int):
        rows = nq * H
        q = q_ref[0, :rows]                                  # (rows, W)
        q_abs = q_pos + jax.lax.broadcasted_iota(
            jnp.int32, (rows, tile), 0) // H

        for c in tile_dma(0, 0):
            c.start()

        def body(i, carry):
            m, l, acc = carry
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_tiles)
            def _():
                for c in tile_dma(1 - slot, i + 1):
                    c.start()

            for c in tile_dma(slot, i):
                c.wait()
            kv = kv_scr[slot]                                # (tile, W)
            sc = jax.lax.dot_general(
                q, kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (rows, tile)
            k_pos = i * tile + jax.lax.broadcasted_iota(
                jnp.int32, (rows, tile), 1)
            ok = (k_pos < kv_len) & (q_abs >= k_pos)
            sc = jnp.where(ok, sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
            # Explicit zero where masked: a row whose tile is all masked
            # would otherwise add exp(NEG_INF - NEG_INF) == 1 a column.
            p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + p.sum(axis=-1, keepdims=True)
            acc_new = alpha * acc + jnp.dot(
                p.astype(kv.dtype), kv[:, :lat],
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m0 = jnp.full((rows, 1), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((rows, 1), dtype=jnp.float32)
        a0 = jnp.zeros((rows, lat), dtype=jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, n_tiles, body, (m0, l0, a0))
        o_ref[0, :rows] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    @pl.when(n_tiles == 0)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when((n_tiles > 0) & (n == 1))
    def _():
        walk(1)

    if TQ > 1:
        @pl.when((n_tiles > 0) & (n > 1))
        def _():
            walk(TQ)


def _latent_call(q_blocks, blk_seq, blk_pos, blk_n, pool, layer,
                 block_tables, kv_lens, *, scale, lat, TQ, H, kv_pages,
                 interpret):
    """q_blocks (NB, TQ * H, W) -> (NB, TQ * H, lat)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    NB, rows, W = q_blocks.shape
    ps = pool.shape[2]
    if interpret is None:
        from ray_tpu.ops import is_tpu_backend

        interpret = not is_tpu_backend()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(NB,),
        in_specs=[
            pl.BlockSpec((1, rows, W), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, rows, lat), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, kv_pages * ps, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    kernel = functools.partial(
        _latent_kernel, ps=ps, KB=kv_pages, scale=scale, TQ=TQ, H=H, lat=lat)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (NB, rows, lat), q_blocks.dtype, vma=vma_of(q_blocks, pool)),
        interpret=interpret,
        **kernel_tag("paged_attention_latent_unified"),
    )(blk_seq, blk_pos, blk_n, jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_tables, kv_lens, q_blocks, pool)


def latent_paged_attention_unified(q, pool, layer, block_tables, kv_lens,
                                   q_positions, cu_q_lens, *, scale: float,
                                   lat: int, q_block: int = LATENT_Q_BLOCK,
                                   kv_pages: int = LATENT_KV_PAGES,
                                   interpret: Optional[bool] = None):
    """Pallas latent paged attention over a flat mixed batch (layouts as
    ragged_paged_attention_unified; pool and `layer` as above). The flat rows
    are gathered into query blocks of one sequence each, at most S + T //
    q_block of them, and the blocks' outputs gathered back."""
    T, H, W = q.shape
    S = kv_lens.shape[0]
    TQ = q_block
    seq, local, blk_n, slot_tok, first = query_blocks(cu_q_lens, T, S, TQ)
    NB = seq.shape[0]
    q_blocks = jnp.take(q, slot_tok.reshape(-1), axis=0, mode="clip")
    out = _latent_call(
        q_blocks.reshape(NB, TQ * H, W), seq.astype(jnp.int32),
        (q_positions[seq] + local * TQ).astype(jnp.int32),
        blk_n.astype(jnp.int32), pool, layer, block_tables, kv_lens,
        scale=scale, lat=lat, TQ=TQ, H=H, kv_pages=kv_pages,
        interpret=interpret)
    return blocks_to_tokens(out, cu_q_lens, first, T, S, TQ, H)


def latent_paged_attention(q, pool, layer, block_tables, kv_lens,
                           q_positions, *, scale: float, lat: int,
                           q_block: int = LATENT_Q_BLOCK,
                           kv_pages: int = LATENT_KV_PAGES,
                           interpret: Optional[bool] = None):
    """Pallas latent paged attention, rectangular: every sequence brings Bq
    query tokens (1: decode). The same kernel; the blocks are the rectangle's
    own rows, ceil(Bq / q_block) a sequence."""
    S, Bq, H, W = q.shape
    TQ = min(q_block, Bq)
    per_seq = -(-Bq // TQ)
    pad = per_seq * TQ - Bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    local = jnp.tile(jnp.arange(per_seq, dtype=jnp.int32), S)
    seq = jnp.repeat(jnp.arange(S, dtype=jnp.int32), per_seq)
    out = _latent_call(
        q.reshape(S * per_seq, TQ * H, W), seq,
        q_positions[seq] + local * TQ,
        jnp.clip(Bq - local * TQ, 0, TQ), pool, layer, block_tables, kv_lens,
        scale=scale, lat=lat, TQ=TQ, H=H, kv_pages=kv_pages,
        interpret=interpret)
    return out.reshape(S, per_seq * TQ, H, lat)[:, :Bq]
