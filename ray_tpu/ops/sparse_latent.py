"""DeepSeek Sparse Attention over paged pools (DeepSeek-V3.2-Exp, "Boosting
Long-Context Efficiency with DeepSeek Sparse Attention"): a query token
attends to the `topk` context rows a lightning indexer scores highest, and to
its whole context where that holds no more than `topk` rows.

Three pieces, each a jitted entry with a name of its own (the Pallas kernel
inside it is an event `<entry>.<n>` of a device trace), each beside a plain
`jax.numpy` oracle (the tests' oracle and the path off the chip), all over a
flat mixed batch as ops/paged_attention.py lays it (T tokens, sequence s owns
rows [cu_q_lens[s], cu_q_lens[s + 1]), its first token at absolute position
q_positions[s], its context kv_lens[s] rows after the step's own):

  `dsa_index`    scores (T, Lmax) float32 of every token against the paged
                 index keys of its context: `I[t, s] = sum_j w[t, j] relu(q[t,
                 j] . k[s])` for `s < n_t` = min(position + 1, kv_len), -inf
                 elsewhere. q (T, HI, dI) and the pool (layers, pages, page,
                 dI) in the model's dtype, w (T, HI) float32 with the scales
                 folded in. The kernel (`dsa_index_call`) walks pages a block
                 of up to INDEX_Q_BLOCK query tokens at a time, INDEX_TILE
                 context rows a step. The score has no softmax across keys,
                 so a tile of keys fetched once serves any tokens that see
                 it: a run of pages that several of a step's sequences hold
                 in the same leading places of their block tables (a shared
                 document: the prefix cache shares pages by reference) is
                 walked ONCE, by blocks that stack those sequences' tokens
                 (`shared_runs`, `index_walks`: the plan, traced from the
                 step's own tables; no flag, tables that share nothing give
                 every walk one sequence); what a sequence holds alone is
                 walked by blocks of its own tokens from the first tile past
                 its run. `index_walked_rows` counts the same plan's keys on
                 the host.
  `dsa_select`   (positions (T, topk) int32 ascending, count (T,)): the
                 positions of the `min(n_t, topk)` largest scores, ties to the
                 lower position, WITHOUT a sort: the topk-th largest score is
                 found by selection (32 compare-and-count passes over a row's
                 scores as a sortable int32, the kernel of `dsa_select_call`,
                 eight tokens a block with their scores in VMEM), and the
                 positions at or above it are compacted with prefix sums and
                 products (`_compact`): no sort, no scatter, no gather of
                 single elements. Slots past the count hold position 0.
  `pool_rows`    where the selected positions lie in a pool's flat rows (page
                 id x page + slot), once a selection.
  `gather_rows`  the selected rows out of a paged pool (groups, pages, page,
                 width), (T, topk, width): XLA's gather, which costs a
                 row 8.7 ns + 6.3 ns a KB on the v5e (16.8 ns at 1,280 B, 41.0
                 at 5,120 B: PERF.md section 6, PR 50; a row is less than a
                 tile of a page, which Mosaic's DMA does not slice, so no
                 kernel fetches its own). So it runs once a SELECTION: a
                 model whose consecutive layers attend under one selection
                 (models/glm_dsa.py) lays their rows side by side a token,
                 `width` = S x W, and every one of the S layers reads its own
                 lane block of the one gathered operand.
  `step_rows`    what the gathered operand cannot hold: a selected position
                 that belongs to THIS step (a slice's earlier tokens, a decode
                 row's own) is written by each layer in its turn, after the
                 gather. `dsa_select` returns positions ascending, so of a
                 token's rows the first `cached` lie before its sequence's
                 first position of the step, and the rest are rows of the
                 step's own tokens, which a (T, T) mask names: same sequence,
                 selected, not after me. Compares, once a selection.
  `dsa_attend`   latent attention in the absorbed form over the SELECTED rows:
                 q (T, H, W) as ops/paged_attention.py's latent kernel takes
                 it, against TWO key sets under one softmax: the first
                 `cached[t]` rows of lane block `place` of the gathered operand
                 (T, topk, S x W), and the step's own rows of this layer (T,
                 W), as the layer wrote them, under `step_rows`' mask (the
                 kernel of `dsa_attend_call`, a token a grid step, its block of
                 the operand (1, topk, W) at lane block `place`: nothing is
                 sliced or copied by XLA). -> (T, H, lat).
                 `dsa_attend_reference` is the oracle of all of it: ONE
                 layer's rows gathered from its pool after that layer's write.

Contexts no longer than `topk` need none of this (every row is selected): a
model's block sends a step none of whose contexts is longer to the dense
latent kernel (models/glm_dsa.py). It cannot do so with a `lax.cond` around
these entries: a kernel inside a conditional's branch loses its entry's name
(its event is `tpu_custom_call.<n>`, which no reader can tell from another
kernel's). So every entry takes `live`, a scalar bool: where it is False the
kernel's grid steps do nothing and fetch nothing (their blocks all map to
block 0, their walks are empty), what XLA runs around the kernel is under a
`lax.cond` of its own, and the result is zeros (no position selected, no row
attended to).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import kernel_tag
from ray_tpu.ops.attention import vma_of
from ray_tpu.ops.paged_attention import (NEG_INF, _interpret, query_blocks,
                                         token_seq_ids)

LANE = 128
# Query tokens a block of the index kernel stacks against one tile of keys,
# tokens a head-weight product, and context rows a step of the walk (64 pages
# of 16). The products: (tokens x HI, dI) x (dI, tile), and for every
# INDEX_SUB tokens (SUB, SUB x HI) x (SUB x HI, tile), float32 out (it is
# block-diagonal: over the whole block its cost would grow with the square).
# 16 by one sweep on the chip (PERF.md section 6, PR 51): a shared walk's step
# is the MXU's, ~0.18 us a token a tile, so a larger block saves descriptors
# only, and one that holds fewer tokens than it has slots pays for all.
INDEX_Q_BLOCK = 16
INDEX_SUB = 8
INDEX_TILE = 1024
# Tokens a block of the selection kernel: their scores, (8, Lmax) int32, lie
# in VMEM whole (1.2 MB at 36,864 positions, twice for the pipeline).
SELECT_ROWS = 8
INT_MIN = -2 ** 31


def flat_rows(cu_q_lens, q_positions, kv_lens, T: int):
    """(seq, positions, n, valid) of the T flat tokens: a token's sequence,
    its absolute position, the context rows it sees (0 for a padding
    token)."""
    S = kv_lens.shape[0]
    seq = token_seq_ids(cu_q_lens, T, S)
    positions = q_positions[seq] + jnp.arange(T) - cu_q_lens[seq]
    valid = jnp.arange(T) < cu_q_lens[S]
    n = jnp.where(valid, jnp.minimum(positions + 1, kv_lens[seq]), 0)
    return seq, positions, n.astype(jnp.int32), valid


# ------------------------------------------------------------------- index

def dsa_index_reference(q, w, pool, layer, block_tables, kv_lens,
                        q_positions, cu_q_lens):
    """The oracle: every token against its sequence's whole padded context."""
    T = q.shape[0]
    ps = pool.shape[2]
    Lmax = block_tables.shape[1] * ps
    seq, _, n, _ = flat_rows(cu_q_lens, q_positions, kv_lens, T)
    keys = pool[layer][block_tables].reshape(
        block_tables.shape[0], Lmax, -1)[seq]               # (T, Lmax, dI)
    dots = jnp.einsum("thd,tkd->thk", q, keys,
                      preferred_element_type=jnp.float32)
    scores = jnp.einsum("th,thk->tk", w, jnp.maximum(dots, 0.0),
                        precision=jax.lax.Precision.HIGHEST)
    return jnp.where(jnp.arange(Lmax)[None, :] < n[:, None], scores, -jnp.inf)


def shared_runs(block_tables, q_positions, n_q, KB: int, tile: int):
    """Which leading page runs the step's sequences hold in common. For each
    sequence s with query tokens: leader[s], the lowest-numbered such sequence
    whose table agrees with its own over the whole first tile (KB places), and
    run[s], the TILES it shares with that leader: the places that agree one
    after another from place 0 (a page id that turns up again deeper shares
    nothing), cut to whole tiles and to what lies wholly before the sequence's
    first query position, so that every token of s sees every key of a shared
    tile. A run nobody else walks is no run: run[s] is 0 unless two sequences
    at least share their leader's. -> (leader (S,), run (S,)) int32."""
    S, P = block_tables.shape
    has_q = n_q > 0
    place = jnp.arange(P, dtype=jnp.int32)
    agree = jnp.min(jnp.where(
        block_tables[:, None, :] == block_tables[None, :, :], P, place),
        axis=-1)                                            # (S, S) places
    same = (agree >= KB) & has_q[:, None] & has_q[None, :]
    leader = jnp.where(has_q, jnp.argmax(same, axis=1),
                       jnp.arange(S)).astype(jnp.int32)
    run = jnp.where(has_q, jnp.minimum(agree[jnp.arange(S), leader] // KB,
                                       q_positions // tile), 0)
    members = jnp.sum((leader[None, :] == jnp.arange(S)[:, None])
                      & (run > 0)[None, :], axis=1)
    return leader, jnp.where(members[leader] >= 2, run, 0).astype(jnp.int32)


def index_walks(block_tables, kv_lens, q_positions, cu_q_lens, T: int,
                TQ: int, KB: int, ps: int):
    """The index kernel's blocks for a step of T flat tokens: SHARED walks
    first (up to TQ tokens of any sequences under one leader, over the
    leader's pages of the tiles they share: `shared_runs`), then every
    sequence's OWN walks (up to TQ of its tokens from its first tile past its
    run, as `query_blocks` cuts them), real blocks before padding ones. ->
    a dict: per block `seq` (whose table it walks), `pos` (its first token's
    position; past every key for a shared block, which masks nothing), `n`
    (real tokens; 0: a padding block), `first` (its first tile), `len` (the
    context rows it walks up to), `tok` (NB, TQ) its slots' flat tokens;
    `real` the count of real blocks; per token `shared` and `own` (block,
    slot) and `run` (its sequence's shared tiles); `walked`, the index keys
    the walks fetch."""
    S = kv_lens.shape[0]
    tile = KB * ps
    leader, run = shared_runs(block_tables, q_positions,
                              cu_q_lens[1:] - cu_q_lens[:-1], KB, tile)
    tok = jnp.arange(T, dtype=jnp.int32)
    tok_seq = token_seq_ids(cu_q_lens, T, S)
    tok_run = jnp.where(tok < cu_q_lens[S], run[tok_seq], 0)
    stacked, group = tok_run > 0, leader[tok_seq]
    # A stacked token's place among its leader's, in the flat order.
    peers = stacked[None, :] & (group[None, :] == group[:, None])
    rank = jnp.sum(peers & (tok[None, :] < tok[:, None]), axis=1)
    count = jnp.sum(stacked[None, :]
                    & (group[None, :] == jnp.arange(S)[:, None]), axis=1)
    blocks = (count + TQ - 1) // TQ                         # (S,) a leader
    first_sh = jnp.cumsum(blocks) - blocks
    NBs = S // 2 + T // TQ          # a leader has two sequences at least
    slot = jnp.where(stacked, (first_sh[group] + rank // TQ) * TQ + rank % TQ,
                     -1)
    hit = slot[None, :] == jnp.arange(NBs * TQ)[:, None]    # (slots, T)
    sh_tok = jnp.sum(jnp.where(hit, tok[None, :], 0), axis=1).reshape(NBs, TQ)
    sh_n = jnp.sum(hit, axis=1).reshape(NBs, TQ).sum(axis=1)
    sh_run = jnp.max(jnp.where(hit, tok_run[None, :], 0),
                     axis=1).reshape(NBs, TQ).max(axis=1)
    nbs = jnp.sum(blocks)
    seq, local, own_n, own_tok, first = query_blocks(cu_q_lens, T, S, TQ)
    own_pos = q_positions[seq] + local * TQ
    both = dict(
        seq=(leader[tok_seq[sh_tok[:, 0]]], seq),
        pos=(jnp.full((NBs,), 2 ** 30), own_pos),
        n=(sh_n, own_n),
        first=(jnp.zeros((NBs,), jnp.int32), run[seq]),
        len=(sh_run * tile, jnp.minimum(kv_lens[seq], own_pos + own_n)),
        tok=(sh_tok, own_tok))
    # Real blocks first: shared block b stays b, own block b moves to nbs + b.
    NB = NBs + seq.shape[0]
    b = jnp.arange(NB)
    src = jnp.where(b < nbs, b, jnp.minimum(NBs + b - nbs, NB - 1))
    plan = {k: jnp.concatenate([x.astype(jnp.int32) for x in v])[src]
            for k, v in both.items()}
    real = nbs + jnp.sum(own_n > 0)
    plan["n"] = jnp.where(b < real, plan["n"], 0)
    tok_local = tok - cu_q_lens[tok_seq]
    return dict(
        plan, real=real.astype(jnp.int32), run=tok_run,
        shared=(slot // TQ, slot % TQ),
        own=(nbs + first[tok_seq] + tok_local // TQ, tok_local % TQ),
        walked=jnp.sum(jnp.where(
            plan["n"] > 0,
            jnp.maximum(plan["len"] - plan["first"] * tile, 0), 0)))


def index_walked_rows(rows, tables, ps: int) -> int:
    """`index_walks`' `walked` on the host, for a tick's record: rows
    [(tokens, first position, context after them)] in the step's order and
    the step's block table (sequences, places), a row's pages padded with
    zeros (`tables` None: no two rows share a page). `shared_runs`'
    arithmetic without the (S, S, places) comparison: a sequence is compared
    with its leader alone, whom its first tile's bytes name.
    tests/test_dsa_ops.py holds the two equal."""
    TQ = INDEX_Q_BLOCK
    KB = max(1, INDEX_TILE // ps)
    tile = KB * ps
    R = len(rows)
    run = [0] * R
    if tables is not None and R:
        table = np.asarray(tables)[:R]
        heads = {}
        lead = np.asarray([
            heads.setdefault(bytes(table[s, :KB]), s) if n > 0 else s
            for s, (n, _, _) in enumerate(rows)])
        differ = table != table[lead]
        at = differ.argmax(axis=1)
        agree = np.where(differ[np.arange(R), at], at, table.shape[1])
        mine = np.minimum(agree // KB, [first // tile if n > 0 else 0
                                        for n, first, _ in rows])
        members = np.bincount(lead[mine > 0], minlength=R)
        # a run nobody else walks is no run
        run = np.where(members[lead] >= 2, mine, 0).tolist()
    walked, stacks = 0, {}
    for s, (n, first, kv_len) in enumerate(rows):
        if run[s]:
            stacks.setdefault(int(lead[s]), []).extend([run[s]] * n)
        for at in range(0, n, TQ):
            last = min(kv_len, first + min(at + TQ, n))
            walked += max(0, last - run[s] * tile)
    return walked + tile * sum(
        max(runs[at:at + TQ]) for runs in stacks.values()
        for at in range(0, len(runs), TQ))


def _index_kernel(blk_seq_ref, blk_pos_ref, blk_n_ref, blk_first_ref,
                  blk_len_ref, meta_ref, block_tables_ref,  # scalar prefetch
                  q_ref, w_ref, pool_hbm,                   # inputs
                  o_ref,                                    # output
                  k_scr, sems,
                  *, ps: int, KB: int, TQ: int, HI: int):
    """Grid: (NB,). Block b is up to TQ query tokens (`index_walks` says
    whose) against the pages of sequence blk_seq[b] from tile blk_first[b]
    up to context row blk_len[b]. q_ref (1, TQ * HI, dI): the block's tokens'
    heads, token-major. w_ref (1, TQ, SUB * HI): the head weights, token t's
    in columns [(t % SUB) HI, (t % SUB + 1) HI) of row t, so that the
    weighted sum over the heads of SUB tokens is one product. o_ref (1, NT,
    TQ, tile): the block's scores a tile of context, -inf where a token does
    not see; tiles the block does not walk are not written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    s = blk_seq_ref[b]
    n = blk_n_ref[b]
    q_pos = blk_pos_ref[b]
    t0 = blk_first_ref[b]
    kv_len = blk_len_ref[b]
    layer = meta_ref[0]
    tile = KB * ps
    n_pages = pl.cdiv(kv_len, ps)
    n_tiles = pl.cdiv(n_pages, KB)

    @pl.when(b == 0)
    def _():
        k_scr[...] = jnp.zeros_like(k_scr)

    def walk(nq: int):
        subs = -(-nq // INDEX_SUB)                          # products a tile
        rows = min(nq, INDEX_SUB) * HI

        def page_dma(slot, i, j):
            return pltpu.make_async_copy(
                pool_hbm.at[layer, block_tables_ref[s, i * KB + j]],
                k_scr.at[slot, pl.ds(pl.multiple_of(j * ps, ps), ps)],
                sems.at[slot])

        def real_pages(slot, i, go):
            def one(j, _):
                go(page_dma(slot, i, j))
                return _

            jax.lax.fori_loop(0, jnp.minimum(KB, n_pages - i * KB), one, 0)

        real_pages(0, t0, lambda c: c.start())
        k_off = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        q_abs = q_pos + jax.lax.broadcasted_iota(
            jnp.int32, (INDEX_SUB, 1), 0)

        def wait_whole(slot):
            """One wait for a whole tile's KB pages (the semaphore counts
            bytes)."""
            whole = k_scr.at[slot]
            pltpu.make_async_copy(whole, whole, sems.at[slot]).wait()

        def score(i, slot):
            k_pos = i * tile + k_off
            for g in range(subs):
                dots = jax.lax.dot_general(
                    q_ref[0, g * rows:(g + 1) * rows], k_scr[slot],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)      # (rows, tile)
                at = pl.ds(g * INDEX_SUB, INDEX_SUB)
                sc = jnp.dot(w_ref[0, at, :rows], jnp.maximum(dots, 0.0),
                             preferred_element_type=jnp.float32,
                             precision=jax.lax.Precision.HIGHEST)
                ok = (k_pos < kv_len) & (k_pos <= q_abs + g * INDEX_SUB)
                o_ref[0, i, at] = jnp.where(ok, sc, -jnp.inf)

        def fast(i, carry):
            """A whole tile before a whole tile (a shared walk's every tile
            but its last): the next tile's KB page DMAs start without a
            branch or a count, unrolled into the products' own instruction
            stream, after this tile's wait (ops/paged_attention.py,
            `_latent_kernel`, says why in that order)."""
            slot = jax.lax.rem(i - t0, 2)
            wait_whole(slot)

            def start(j, carry):
                page_dma(1 - slot, i + 1, j).start()
                return carry

            jax.lax.fori_loop(0, KB, start, 0, unroll=True)
            score(i, slot)
            return carry

        def last(i, carry):
            slot = jax.lax.rem(i - t0, 2)

            @pl.when(i + 1 < n_tiles)
            def _():
                real_pages(1 - slot, i + 1, lambda c: c.start())

            @pl.when(n_pages - i * KB >= KB)
            def _():
                wait_whole(slot)

            @pl.when(n_pages - i * KB < KB)
            def _():
                real_pages(slot, i, lambda c: c.wait())

            score(i, slot)
            return carry

        n_fast = jnp.maximum(n_pages // KB - 1, t0)
        jax.lax.fori_loop(t0, n_fast, fast, 0)
        jax.lax.fori_loop(n_fast, n_tiles, last, 0)

    @pl.when((n == 1) & (n_tiles > t0))
    def _():
        walk(1)

    @pl.when((n > 1) & (n_tiles > t0))
    def _():
        walk(TQ)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dsa_index_call(q, w, pool, layer, block_tables, kv_lens, q_positions,
                   cu_q_lens, live, *, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, HI, dI = q.shape
    ps = pool.shape[2]
    TQ, SUB = INDEX_Q_BLOCK, INDEX_SUB
    KB = max(1, INDEX_TILE // ps)
    tile = KB * ps
    NT = -(-block_tables.shape[1] // KB)
    plan = index_walks(block_tables, kv_lens, q_positions, cu_q_lens, T, TQ,
                       KB, ps)
    NB = plan["seq"].shape[0]
    # Not live: no block is real, so none walks or writes.
    blk_n = jnp.where(live, plan["n"], 0)
    nb_real = jnp.where(live, plan["real"], 0)
    slot_tok = jnp.clip(plan["tok"], 0, T - 1)              # (NB, TQ)
    qb = q[slot_tok].reshape(NB, TQ * HI, dI)
    # Token t of a block: its head weights in columns [(t % SUB) HI, + HI).
    wb = (jnp.eye(SUB, dtype=w.dtype)[jnp.arange(TQ) % SUB][None, :, :, None]
          * w[slot_tok][:, :, None, :]).reshape(NB, TQ, SUB * HI)

    def own_block(shape):
        # A padding block keeps the last real block's buffers and leaves them
        # alone, so nothing of it is fetched or written back.
        return pl.BlockSpec(shape, lambda b, seq, pos, n, first, length, meta,
                            *_: (jnp.minimum(b, jnp.maximum(meta[1] - 1, 0)),)
                            + (0,) * (len(shape) - 1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(NB,),
        in_specs=[
            own_block((1, TQ * HI, dI)),
            own_block((1, TQ, SUB * HI)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=own_block((1, NT, TQ, tile)),
        scratch_shapes=[
            pltpu.VMEM((2, tile, dI), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      nb_real.astype(jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_index_kernel, ps=ps, KB=KB, TQ=TQ, HI=HI),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NB, NT, TQ, tile), jnp.float32,
                                       vma=vma_of(q, pool)),
        interpret=interpret,
        **kernel_tag("dsa_index"),
    )(plan["seq"], plan["pos"], blk_n, plan["first"], plan["len"], meta,
      block_tables, qb, wb, pool)
    Lmax = block_tables.shape[1] * ps

    def token_major():
        """The blocks' rows back in the flat order: a token's tiles side by
        side, its run's out of its shared block, the rest out of its own."""
        at = jnp.arange(NT)[None, :]
        shared = at < plan["run"][:, None]                  # (T, NT)
        blk, slot = (jnp.where(shared, a[:, None], b[:, None])
                     for a, b in zip(plan["shared"], plan["own"]))
        scores = out[jnp.clip(blk, 0, NB - 1), at, slot].reshape(
            T, NT * tile)
        _, _, n, _ = flat_rows(cu_q_lens, q_positions, kv_lens, T)
        return jnp.where(jnp.arange(Lmax)[None, :] < n[:, None],
                         scores[:, :Lmax], -jnp.inf)

    return jax.lax.cond(
        live, token_major, lambda: jnp.full((T, Lmax), -jnp.inf, jnp.float32))


def dsa_index(q, w, pool, layer, block_tables, kv_lens, q_positions,
              cu_q_lens, *, impl: str, live=True,
              interpret: Optional[bool] = None):
    if impl != "pallas":
        return jax.lax.cond(
            live, lambda: dsa_index_reference(
                q, w, pool, layer, block_tables, kv_lens, q_positions,
                cu_q_lens),
            lambda: jnp.full((q.shape[0], block_tables.shape[1]
                              * pool.shape[2]), -jnp.inf, jnp.float32))
    return dsa_index_call(q, w, pool, layer, block_tables, kv_lens,
                          q_positions, cu_q_lens, jnp.asarray(live),
                          interpret=_interpret(interpret))


# ------------------------------------------------------------------ select

def dsa_select_reference(scores, n, topk: int):
    """The oracle (it sorts): the positions of the min(n, topk) largest of a
    row's first n scores, ties to the lower position, ascending."""
    T, L = scores.shape
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    keep = (rank < topk) & (jnp.arange(L)[None, :] < n[:, None])
    front = jnp.argsort(~keep, axis=-1, stable=True)[:, :topk]
    front = jnp.pad(front, ((0, 0), (0, max(0, topk - L))))
    count = jnp.minimum(n, topk).astype(jnp.int32)
    return (jnp.where(jnp.arange(topk)[None, :] < count[:, None], front,
                      0).astype(jnp.int32), count)


def sortable(scores):
    """float32 -> int32 that orders as the floats do (-inf lowest; no NaN
    comes out of the index)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _threshold_kernel(live_ref, keys_ref, thr_ref, *, topk: int):
    """keys_ref (rows, L) int32 -> thr_ref (rows, LANE): in every lane the
    largest v with at least `topk` keys >= v (INT_MIN where a row has fewer:
    there is none), by deciding v's bits from the sign down. Nothing where
    live_ref[0] is 0."""
    from jax.experimental import pallas as pl

    pl.when(live_ref[0] > 0)(
        functools.partial(_threshold, keys_ref, thr_ref, topk))


def _threshold(keys_ref, thr_ref, topk: int):
    x = keys_ref[...]

    def reaches(v):
        return jnp.sum((x >= v).astype(jnp.int32), axis=-1,
                       keepdims=True) >= topk

    v = jnp.where(reaches(jnp.zeros((x.shape[0], 1), jnp.int32)),
                  jnp.int32(0), jnp.int32(INT_MIN))

    def bit(i, v):
        cand = v | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(reaches(cand), cand, v)

    v = jax.lax.fori_loop(0, 31, bit, v)
    thr_ref[...] = jnp.broadcast_to(v, thr_ref.shape)


def _cumsum_lanes(m3):
    """Inclusive prefix sums along the last axis (LANE wide) of 0/1 values:
    a product with a triangle of ones, exact."""
    tri = (jnp.arange(LANE)[:, None] <= jnp.arange(LANE)[None, :])
    return jnp.einsum("tmc,cd->tmd", m3.astype(jnp.bfloat16),
                      tri.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32).astype(jnp.int32)


def _compact(mask, topk: int):
    """mask (T, L) bool, at most topk True a row -> the positions of its True
    entries ascending, (T, topk) int32 (0 past their count). The context in
    chunks of LANE positions: slot j lies in the chunk that the chunks'
    prefix counts say, that chunk's ranks come to every slot by ONE product
    with a one-hot of its chunk (exact: ranks under 128 in bfloat16), and the
    position is where the rank equals what is left of j."""
    T, L = mask.shape
    M = -(-L // LANE)
    m3 = jnp.pad(mask, ((0, 0), (0, M * LANE - L))).reshape(T, M, LANE)
    local = _cumsum_lanes(m3)                               # inclusive
    count = local[:, :, -1]                                 # (T, M)
    upto = jnp.cumsum(count, axis=1)
    j = jnp.arange(topk, dtype=jnp.int32)
    chunk = jnp.sum(upto[:, None, :] <= j[None, :, None], axis=-1)
    chunk = jnp.minimum(chunk, M - 1)                       # (T, topk)
    onehot = chunk[..., None] == jnp.arange(M)[None, None, :]
    before = jnp.sum(jnp.where(onehot, (upto - count)[:, None, :], 0),
                     axis=-1)
    rank = jnp.where(m3, local - 1, -1)                     # (T, M, LANE)
    ranks = jnp.einsum("tkm,tmc->tkc", onehot.astype(jnp.bfloat16),
                       rank.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    hit = ranks == (j[None, :] - before)[..., None].astype(jnp.float32)
    within = jnp.sum(jnp.where(hit, jnp.arange(LANE)[None, None, :], 0),
                     axis=-1)
    filled = j[None, :] < upto[:, -1:]
    return jnp.where(filled, chunk * LANE + within, 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def dsa_select_call(scores, n, live, *, topk: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, L = scores.shape
    R = SELECT_ROWS
    Tp, Lp = -(-T // R) * R, -(-L // LANE) * LANE
    keys = sortable(scores)
    flag = live.astype(jnp.int32).reshape(1)
    thr = pl.pallas_call(
        functools.partial(_threshold_kernel, topk=topk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Tp // R,),
            # Not live: every step's block is block 0, fetched once.
            in_specs=[pl.BlockSpec((R, Lp), lambda i, f: (i * f[0], 0))],
            out_specs=pl.BlockSpec((R, LANE), lambda i, f: (i * f[0], 0))),
        out_shape=jax.ShapeDtypeStruct((Tp, LANE), jnp.int32,
                                       vma=vma_of(scores)),
        interpret=interpret,
        **kernel_tag("dsa_select"),
    )(flag, jnp.pad(keys, ((0, Tp - T), (0, Lp - L)),
                    constant_values=INT_MIN))[:T, :1]

    def positions():
        seen = jnp.arange(L)[None, :] < n[:, None]
        above = seen & (keys > thr)
        tied = seen & (keys == thr)
        # Of the scores AT the threshold, the first `topk - above` by
        # position.
        need = topk - jnp.sum(above, axis=-1, keepdims=True)
        M = Lp // LANE
        t3 = jnp.pad(tied, ((0, 0), (0, Lp - L))).reshape(T, M, LANE)
        local = _cumsum_lanes(t3)
        chunks = jnp.cumsum(local[:, :, -1], axis=1)
        tie_rank = (local + (chunks - local[:, :, -1])[..., None]).reshape(
            T, Lp)[:, :L]
        keep = jnp.where(n[:, None] <= topk, seen,
                         above | (tied & (tie_rank <= need)))
        return _compact(keep, topk), jnp.minimum(n, topk).astype(jnp.int32)

    return jax.lax.cond(live, positions, lambda: _nothing(T, topk))


def _nothing(T: int, topk: int):
    return jnp.zeros((T, topk), jnp.int32), jnp.zeros((T,), jnp.int32)


def dsa_select(scores, n, *, topk: int, impl: str, live=True,
               interpret: Optional[bool] = None):
    if impl != "pallas":
        return jax.lax.cond(
            live, lambda: dsa_select_reference(scores, n, topk),
            lambda: _nothing(scores.shape[0], topk))
    return dsa_select_call(scores, n, jnp.asarray(live), topk=topk,
                           interpret=_interpret(interpret))


# ------------------------------------------------------------------ attend

def pool_rows_reference(positions, block_tables, seq, ps: int):
    """Where positions (T, K) of each token's sequence lie in a pool's flat
    rows (pages x page): page id x page + slot. The oracle: a lookup an
    element."""
    page = jnp.take_along_axis(block_tables[seq], positions // ps, axis=1)
    return page * ps + positions % ps


def pool_rows(positions, block_tables, seq, ps: int, *, impl: str,
              live=True):
    """`pool_rows_reference` without a gather of single elements (65,536 of
    them a decode tick of 32 rows: ~4 ms on the v5e, PERF.md section 6, PR
    49), once a SELECTION and not once a layer that attends under it. The
    table a token in chunks of LANE positions' pages; a slot's chunk's page
    ids come by one product with the one-hot of its chunk (ids split in two
    parts under 256, exact in bfloat16), and its own is the one at its place
    in the chunk."""
    T, K = positions.shape
    if impl != "pallas" or LANE % ps or block_tables.shape[1] % (LANE // ps):
        return jax.lax.cond(
            live, lambda: pool_rows_reference(positions, block_tables, seq,
                                              ps),
            lambda: jnp.zeros((T, K), positions.dtype))

    def rows():
        per = LANE // ps                                    # pages a chunk
        table = block_tables[seq].reshape(T, -1, per)       # (T, M, per)
        M = table.shape[1]
        parts = jnp.concatenate([table // 256, table % 256], axis=-1)
        onehot = (positions // LANE)[..., None] == jnp.arange(M)
        mine = jnp.einsum("tkm,tmp->tkp", onehot.astype(jnp.bfloat16),
                          parts.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
        at = (positions % LANE // ps)[..., None] == jnp.arange(per)
        hi = jnp.sum(jnp.where(at, mine[..., :per], 0.0), axis=-1)
        lo = jnp.sum(jnp.where(at, mine[..., per:], 0.0), axis=-1)
        page = (hi * 256 + lo).astype(positions.dtype)
        return page * ps + positions % ps

    return jax.lax.cond(live, rows,
                        lambda: jnp.zeros((T, K), positions.dtype))


def gather_rows(pool, layer, rows):
    """Flat rows `rows` (T, K) (`pool_rows`) of `layer` out of the paged pool
    (layers, pages, page, width): (T, K, width). One gather over the pool as
    it lies: no layer is sliced out. Where S layers' rows lie side by side a
    token (width = S x W), `layer` is their group and the result holds all S
    layers' rows."""
    L, P, ps, W = pool.shape
    return pool.reshape(L, P * ps, W)[
        jnp.full_like(rows, 0) + jnp.asarray(layer, rows.dtype), rows]


def gather_selection(pool, group, rows, *, live=True):
    """`gather_rows` where the step selects (`live`), else zeros: the ONE
    gather of a selection, for every layer of `group`."""
    T, K = rows.shape
    return jax.lax.cond(
        live, lambda: gather_rows(pool, group, rows),
        lambda: jnp.zeros((T, K, pool.shape[-1]), pool.dtype))


def step_rows(positions, count, seq, token_positions, first, valid, *,
              live=True):
    """Which of a token's selected rows the step itself brings: positions (T,
    K) ascending with count (T,) real (`dsa_select`), seq (T,) a token's
    sequence, token_positions (T,) its absolute position, first (T,) its
    sequence's first position of this step, valid (T,). -> (cached (T,)
    int32: the selected positions before `first`, a PREFIX of the token's
    rows; mask (T, T) bool: token t attends to step token u). Zeros where not
    live."""
    T, K = positions.shape

    def compare():
        real = jnp.arange(K)[None, :] < count[:, None]
        cached = jnp.sum(real & (positions < first[:, None]), axis=-1)
        # Selected: u's position is one of t's (the rows past the count hold
        # position 0, which `real` keeps out).
        picked = jnp.any(
            real[:, None, :]
            & (positions[:, None, :] == token_positions[None, :, None]),
            axis=-1)
        mask = (picked & (seq[:, None] == seq[None, :])
                & (token_positions[None, :] >= first[:, None])
                & (token_positions[None, :] <= token_positions[:, None])
                & valid[:, None] & valid[None, :])
        return cached.astype(jnp.int32), mask

    return jax.lax.cond(
        live, compare,
        lambda: (jnp.zeros((T,), jnp.int32), jnp.zeros((T, T), bool)))


def dsa_attend_reference(q, rows, count, pool, layer, *, scale: float,
                         lat: int):
    picked = gather_rows(pool, layer, rows)
    logits = jnp.einsum("thw,tkw->thk", q, picked,
                        preferred_element_type=jnp.float32) * scale
    real = (jnp.arange(rows.shape[1])[None, :] < count[:, None])[:, None, :]
    logits = jnp.where(real, logits, NEG_INF)
    probs = jnp.where(real, jax.nn.softmax(logits, axis=-1), 0.0)
    return jnp.einsum("thk,tkl->thl", probs.astype(picked.dtype),
                      picked[..., :lat],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _attend_two_sets(q, picked, cached, own, mask, *, scale: float,
                     lat: int):
    """The jnp form of the kernel: q (T, H, W) against the first cached[t] of
    picked (T, K, W) and the rows of own (T, W) that mask (T, T) names, one
    softmax over both."""
    K = picked.shape[1]
    logits = jnp.concatenate(
        [jnp.einsum("thw,tkw->thk", q, picked,
                    preferred_element_type=jnp.float32),
         jnp.einsum("thw,uw->thu", q, own,
                    preferred_element_type=jnp.float32)], axis=-1) * scale
    real = jnp.concatenate(
        [jnp.arange(K)[None, :] < cached[:, None], mask], axis=1)[:, None, :]
    probs = jnp.where(real, jax.nn.softmax(
        jnp.where(real, logits, NEG_INF), axis=-1), 0.0).astype(picked.dtype)
    return (jnp.einsum("thk,tkl->thl", probs[..., :K], picked[..., :lat],
                       preferred_element_type=jnp.float32)
            + jnp.einsum("thu,ul->thl", probs[..., K:], own[:, :lat],
                         preferred_element_type=jnp.float32)).astype(q.dtype)


def _attend_kernel(count_ref, cached_ref, live_ref, q_ref, rows_ref, own_ref,
                   mask_ref, o_ref, *, scale: float, lat: int):
    """Grid: (T,). One token: q_ref (1, H, W) against its own gathered rows
    rows_ref (1, K, W), of which the first cached[t] are real, and against
    the step's own rows own_ref (Tp, W) where mask_ref (1, 1, Tp) is set;
    count[t] real rows in all (none: zeros)."""
    from jax.experimental import pallas as pl

    t = pl.program_id(0)

    @pl.when(count_ref[t] == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(count_ref[t] > 0)
    def _():
        q, rows, own = q_ref[0], rows_ref[0], own_ref[...]

        def scores(keys, real):
            sc = jax.lax.dot_general(
                q, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (H, keys)
            return jnp.where(real, sc, NEG_INF)

        real = jax.lax.broadcasted_iota(
            jnp.int32, (1, rows.shape[0]), 1) < cached_ref[t]
        mine = mask_ref[0] > 0                               # (1, Tp)
        sc, so = scores(rows, real), scores(own, mine)
        m = jnp.maximum(sc.max(axis=-1, keepdims=True),
                        so.max(axis=-1, keepdims=True))
        p = jnp.where(real, jnp.exp(sc - m), 0.0)
        po = jnp.where(mine, jnp.exp(so - m), 0.0)
        acc = (jnp.dot(p.astype(rows.dtype), rows[:, :lat],
                       preferred_element_type=jnp.float32)
               + jnp.dot(po.astype(own.dtype), own[:, :lat],
                         preferred_element_type=jnp.float32))
        total = p.sum(axis=-1, keepdims=True) + po.sum(axis=-1,
                                                       keepdims=True)
        o_ref[0] = (acc / jnp.maximum(total, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "lat", "interpret"))
def dsa_attend_call(q, picked, count, cached, own, mask, live, place, *,
                    scale: float, lat: int, interpret: bool):
    """`place` is traced (it rides in the scalar prefetch beside `live`): a
    group's layers share one trace and one lowering of the kernel."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, W = q.shape
    K = picked.shape[1]
    Tp = -(-T // LANE) * LANE       # the step's own rows on whole lane tiles
    count = jnp.where(live, count, 0)
    own = jnp.pad(own, ((0, Tp - T), (0, 0)))
    mask = jnp.pad(mask.astype(jnp.int32), ((0, 0), (0, Tp - T)))[:, None, :]

    flag = jnp.stack([live.astype(jnp.int32), place.astype(jnp.int32)])

    def own_block(t, count, cached, flag):  # not live: block 0, fetched once
        return t * flag[0], 0, 0

    return pl.pallas_call(
        functools.partial(_attend_kernel, scale=scale, lat=lat),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(T,),
            in_specs=[pl.BlockSpec((1, H, W), own_block),
                      # This layer's lane block of the group's operand.
                      pl.BlockSpec((1, K, W), lambda t, count, cached, flag:
                                   (t * flag[0], 0, flag[1])),
                      pl.BlockSpec((Tp, W), lambda t, *_: (0, 0)),
                      pl.BlockSpec((1, 1, Tp), own_block)],
            out_specs=pl.BlockSpec((1, H, lat), lambda t, *_: (t, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((T, H, lat), q.dtype,
                                       vma=vma_of(q, picked)),
        interpret=interpret,
        **kernel_tag("dsa_attend"),
    )(count, cached, flag, q, picked, own, mask)


def dsa_attend(q, picked, count, cached, own, mask, *, place: int,
               scale: float, lat: int, impl: str, live=True,
               interpret: Optional[bool] = None):
    """q (T, H, W) over lane block `place` of picked (T, K, S x W)
    (`gather_selection`) and the step's own rows own (T, W) of this layer;
    count (T,) (`dsa_select`), cached (T,) and mask (T, T) (`step_rows`)."""
    W = q.shape[-1]
    if impl != "pallas":
        return jax.lax.cond(
            live, lambda: _attend_two_sets(
                q, picked[..., place * W:(place + 1) * W], cached, own, mask,
                scale=scale, lat=lat),
            lambda: jnp.zeros(q.shape[:2] + (lat,), q.dtype))
    return dsa_attend_call(q, picked, count, cached, own, mask,
                           jnp.asarray(live), jnp.asarray(place), scale=scale,
                           lat=lat, interpret=_interpret(interpret))
