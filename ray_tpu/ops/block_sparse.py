"""Block-sparse attention over a paged K/V ROW pool (InfLLM-V2, the MiniCPM4
report, arXiv:2506.07900): a query token whose context is longer than
`dense_len` attends to the `topk` BLOCKS of `block` tokens that its own
queries score highest against POOLED keys, every kv head its own set, some
blocks forced; a shorter context is attended to whole.

What is pooled lies beside the pages: ONE row a page a layer, the mean of the
page's keys (`page_means`; a kv head's lanes side by side as in the K row it
averages). With pages of `page` tokens a KERNEL of the first stage covers two
pages (size 2 x page, stride page), so kernel j's key is the mean of page
means j and j + 1, and it counts for a token that sees n tokens once it is
whole: j < n // page - 1 (`whole_kernels`). For a query token t (position t,
n = t + 1) and kv head g:

  r[h, j]  = softmax over the whole kernels j of q[t, h] . kbar[j, g] x scale
  R[g, j]  = sum of r[h, j] over the heads h of g          (`block_scores`)
  s[g, b]  = max of R[g, j] over the kernels that meet block b:
             j = b B - 1 .. b B + B - 1, B pages a block; +inf for the first
             `init_blocks` and the `window_blocks` that end at the token's
             own                                              (`block_select`)
  kept     = the min(t // block + 1, topk) best blocks b <= t // block, ties
             to the lower, ascending (ops/sparse_latent.py's `dsa_select`: the
             k-th largest by selection, no sort)
  o[t, h]  = softmax over the tokens s <= t of g(h)'s kept blocks of
             q[t, h] . k[s, g] x scale, times v               (`block_attend`)

All of it over a flat mixed batch as ops/paged_attention.py lays it (T tokens,
sequence s owns rows [cu_q_lens[s], cu_q_lens[s + 1]), its first token at
q_positions[s], kv_lens[s] tokens after the step's own). Three jitted entries
with names of their own (a device trace's events `<entry>.<n>`), each beside a
plain `jax.numpy` oracle (the tests' oracle and the path off the chip):

  `block_select_call`      the first stage's kernel: a block of up to
                           SELECT_Q_BLOCK tokens of one sequence against that
                           sequence's page means, which XLA gathers once a
                           step in table order (S, pages, K x hd): the two
                           products, the pair means by a lane roll, both
                           softmaxes' float32 and the sum over a kv head's
                           heads, -> R a token, kv head and kernel.
  `block_attend_call`      the second stage of a sequence that brings ONE row
                           (a decode row): its kv heads' kept blocks' pages
                           NAMED BY A TABLE a (row, kv head), walked by
                           ops/paged_attention.py's row kernel as a sequence
                           of its own: the row rides in the pair form (a kv
                           head's 16 query heads against whole 256-lane rows,
                           zeros in the other head's lanes), so a page is one
                           DMA a pool as in the dense walk and nothing is
                           gathered row by row.
  `block_attend_rows_call` the second stage of a sequence that brings MORE
                           rows (a prompt slice, a draft under test): its
                           tokens each keep their own blocks, so the slice
                           walks the UNION, its context whole, once a query
                           block under a (token, kv head, block) mask: exact,
                           every page read once a query block as the dense
                           walk reads it, the mask two small products a pass
                           (`_kv_rows_kernel`, `block_tokens`). What the
                           other form would cost is in PERF.md section 6.

A sequence whose context holds no more than `dense_len` tokens takes the dense
row kernel as it is (the caller's: models/minicpm_sala.py); the rule is a
TOKEN's, so a slice that crosses `dense_len` gives its first tokens every
block (`keep_bits`). No entry stands inside a `lax.cond` (a kernel there
loses its entry's name: ops/sparse_latent.py says so): each takes `live`, or
lengths that are zero for the sequences that are not its own, and then walks
and fetches nothing.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_tag
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import sparse_latent as sl
from ray_tpu.ops.attention import vma_of
from ray_tpu.ops.paged_attention import NEG_INF, _interpret

LANE = 128
F32 = jnp.float32
# Query tokens a block of the first stage's kernel: with 16 heads a kv head
# its products are (128, hd) x (hd, pages).
SELECT_Q_BLOCK = 8


class Geometry(NamedTuple):
    """The published `sparse_config`, in tokens, and the page it is laid on:
    a first-stage kernel is two pages (kernel_size 2 x page, kernel_stride
    page)."""
    page: int
    block: int = 64
    topk: int = 64
    init_blocks: int = 1
    window: int = 2048
    dense_len: int = 8192

    @property
    def block_pages(self) -> int:
        return self.block // self.page

    @property
    def window_blocks(self) -> int:
        return self.window // self.block

    def check(self) -> None:
        if (self.block % self.page or self.window % self.block
                or self.topk < self.init_blocks + self.window_blocks):
            raise ValueError(f"blocks of whole pages, a window of whole "
                             f"blocks and forced blocks inside topk: {self}")


def whole_kernels(n, page: int):
    """Kernels of two pages, stride one, that lie wholly inside n tokens."""
    return jnp.maximum(n // page - 1, 0)


def token_rows(cu_q_lens, q_positions, kv_lens, T: int, dense_len: int):
    """(seq, positions, n, selects) of the T flat tokens: `sl.flat_rows` and
    whether the token's context is longer than `dense_len`."""
    seq, positions, n, _ = sl.flat_rows(cu_q_lens, q_positions, kv_lens, T)
    return seq, positions, n, n > dense_len


# -------------------------------------------------------------- page means

def page_means(means, k_pool, layer, pages):
    """The means (layers, pages, K x hd) with the rows of `pages` (T,), the
    pages a step's tokens wrote to (out of bounds HIGH: a padding token's,
    dropped), made anew from `k_pool` (layers, pages, page, K x hd) AFTER the
    step's write: float32 sums, stored in the pool's dtype. A page that is
    not full yet gets a mean nobody reads (no whole kernel covers it) and is
    made again by the step that fills it."""
    P = k_pool.shape[1]
    rows = k_pool[layer, jnp.minimum(pages, P - 1)].astype(F32).mean(axis=1)
    return means.at[layer, pages].set(rows.astype(means.dtype), mode="drop")


# ------------------------------------------------------------ first stage

def block_scores_reference(q, means, layer, block_tables, kv_lens,
                           q_positions, cu_q_lens, *, kv_heads: int,
                           scale: float, page: int):
    """The oracle: R (T, K, pages) float32, every token against its
    sequence's whole padded table; zeros at kernels that are not whole."""
    T, H, hd = q.shape
    K = kv_heads
    seq, _, n, _ = sl.flat_rows(cu_q_lens, q_positions, kv_lens, T)
    m = means[layer][block_tables].astype(F32)              # (S, NP, K x hd)
    kbar = 0.5 * (m + jnp.roll(m, -1, axis=1))
    kbar = kbar.reshape(*kbar.shape[:2], K, hd)[seq]        # (T, NP, K, hd)
    s = jnp.einsum("tkgd,tjkd->tkgj", q.astype(F32).reshape(T, K, H // K, hd),
                   kbar, precision=jax.lax.Precision.HIGHEST) * scale
    ok = (jnp.arange(m.shape[1])[None, :]
          < whole_kernels(n, page)[:, None])[:, None, None, :]
    r = jnp.where(ok, jax.nn.softmax(jnp.where(ok, s, NEG_INF), axis=-1), 0.0)
    return r.sum(axis=2)


def _select_kernel(seq_ref, pos_ref, n_ref, q_ref, m_ref, o_ref, *, K: int,
                   G: int, TQ: int, page: int, scale: float):
    """Grid (NB,): block b is up to TQ tokens of sequence seq[b] from position
    pos[b] on (n[b] real; 0: nothing to do). q_ref (1, K, TQ x G, hd): a kv
    head's rows together, row t G + g query head kh G + g of token t; m_ref
    (1, NP, K x hd): the sequence's page means in table order; o_ref (1, K,
    TQ, NP) float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    NP = m_ref.shape[1]
    hd = q_ref.shape[-1]
    rows = TQ * G

    @pl.when(n_ref[b] > 0)
    def _():
        token = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // G
        whole = whole_kernels(pos_ref[b] + token + 1, page)
        ok = jax.lax.broadcasted_iota(jnp.int32, (1, NP), 1) < whole
        for kh in range(K):
            d = jax.lax.dot_general(
                q_ref[0, kh], m_ref[0, :, kh * hd:(kh + 1) * hd],
                (((1,), (1,)), ((), ())), preferred_element_type=F32)
            # kernel j: the mean of pages j and j + 1
            s = (d + pltpu.roll(d, NP - 1, 1)) * (0.5 * scale)
            s = jnp.where(ok, s, NEG_INF)
            p = jnp.where(ok, jnp.exp(s - s.max(axis=-1, keepdims=True)), 0.0)
            r = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
            o_ref[0, kh] = r.reshape(TQ, G, NP).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("kv_heads", "scale", "page",
                                             "interpret"))
def block_select_call(q, m_seq, selects, q_positions, cu_q_lens, *,
                      kv_heads: int, scale: float, page: int,
                      interpret: bool):
    """q (T, H, hd); m_seq (S, NP, K x hd) each sequence's page means in
    table order, NP a multiple of LANE; selects (S,) bool: the sequences
    whose tokens are scored (the others' blocks do nothing). -> R (T, K, NP)
    float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, hd = q.shape
    S, NP, _ = m_seq.shape
    K, TQ = kv_heads, SELECT_Q_BLOCK
    G = H // K
    seq, local, blk_n, slot_tok, first = pa.query_blocks(cu_q_lens, T, S, TQ)
    NB = seq.shape[0]
    blk_n = jnp.where(selects[seq], blk_n, 0).astype(jnp.int32)
    qb = jnp.swapaxes(q[jnp.clip(slot_tok, 0, T - 1)].reshape(
        NB, TQ, K, G, hd), 1, 2).reshape(NB, K, TQ * G, hd)
    mine = lambda b, seq, pos, n: jnp.where(n[b] > 0, b, 0)
    out = pl.pallas_call(
        functools.partial(_select_kernel, K=K, G=G, TQ=TQ, page=page,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(NB,),
            in_specs=[
                pl.BlockSpec((1, K, TQ * G, hd), lambda b, *a: (
                    mine(b, *a), 0, 0, 0)),
                # A block that does nothing keeps sequence 0's means: blocks
                # of one sequence follow one another and fetch them once.
                pl.BlockSpec((1, NP, K * hd), lambda b, seq, pos, n: (
                    jnp.where(n[b] > 0, seq[b], 0), 0, 0))],
            out_specs=pl.BlockSpec((1, K, TQ, NP), lambda b, *a: (
                b, 0, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((NB, K, TQ, NP), F32,
                                       vma=vma_of(q, m_seq)),
        interpret=interpret,
        **kernel_tag("block_select"),
    )(seq.astype(jnp.int32),
      (q_positions[seq] + local * TQ).astype(jnp.int32), blk_n, qb, m_seq)
    flat = pa.blocks_to_tokens(
        jnp.swapaxes(out, 1, 2).reshape(NB, TQ * K, NP), cu_q_lens, first, T,
        S, TQ, K)
    return jnp.where(selects[pa.token_seq_ids(cu_q_lens, T, S)][
        :, None, None], flat, 0.0)


def block_scores(q, means, layer, block_tables, kv_lens, q_positions,
                 cu_q_lens, *, kv_heads: int, scale: float, geometry: Geometry,
                 impl: str, interpret: Optional[bool] = None):
    """R (T, K, NP) float32, NP the table's width rounded up to whole lane
    tiles: zeros for a sequence whose context is no longer than `dense_len`
    and for kernels that are not whole. The page means are gathered once a
    step, and only where some sequence selects."""
    S, width = block_tables.shape
    NP = -(-width // LANE) * LANE
    selects = kv_lens > geometry.dense_len
    live = jnp.any(selects)
    if impl != "pallas":
        return jax.lax.cond(
            live, lambda: jnp.pad(jnp.where(
                selects[pa.token_seq_ids(cu_q_lens, q.shape[0], S)][
                    :, None, None],
                block_scores_reference(
                    q, means, layer, block_tables, kv_lens, q_positions,
                    cu_q_lens, kv_heads=kv_heads, scale=scale,
                    page=geometry.page), 0.0),
                ((0, 0), (0, 0), (0, NP - width))),
            lambda: jnp.zeros((q.shape[0], kv_heads, NP), F32))
    tables = jnp.pad(block_tables, ((0, 0), (0, NP - width)))
    m_seq = jax.lax.cond(
        live, lambda: means[layer][tables],
        lambda: jnp.zeros((S, NP, means.shape[-1]), means.dtype))
    return block_select_call(q, m_seq, selects, q_positions, cu_q_lens,
                             kv_heads=kv_heads, scale=scale,
                             page=geometry.page,
                             interpret=_interpret(interpret))


def block_select(R, positions, selects, *, geometry: Geometry, impl: str,
                 interpret: Optional[bool] = None):
    """R (T, K, NP) (`block_scores`), a token's position and whether it
    selects -> (blocks (T, K, topk) int32 ascending, count (T, K) int32; 0
    for a token that does not select): the max-pool to blocks, the forced
    blocks, and `dsa_select`'s k-th largest by selection."""
    T, K, NP = R.shape
    B = geometry.block_pages
    NBLK = NP // B
    by_block = R[..., :NBLK * B].reshape(T, K, NBLK, B)
    # kernel b B - 1 is the last of block b - 1's pages and meets block b too
    before = jnp.pad(by_block[..., :-1, B - 1], ((0, 0), (0, 0), (1, 0)))
    score = jnp.maximum(by_block.max(axis=-1), before)
    own = (positions // geometry.block)[:, None, None]
    at = jnp.arange(NBLK)[None, None, :]
    forced = (at < geometry.init_blocks) | (at > own - geometry.window_blocks)
    # (a block past the token's own scores as `dsa_select` expects of what a
    # row does not see: lowest)
    score = jnp.where(at <= own, jnp.where(forced, jnp.inf, score),
                      -jnp.inf).reshape(T * K, NBLK)
    n = jnp.where(selects[:, None], own[:, 0] + 1, 0)
    n = jnp.broadcast_to(n, (T, K)).reshape(T * K).astype(jnp.int32)
    blocks, count = sl.dsa_select(score, n, topk=geometry.topk, impl=impl,
                                  live=jnp.any(selects), interpret=interpret)
    return blocks.reshape(T, K, -1), count.reshape(T, K)


def keep_bits(blocks, count, positions, selects, block: int, NBLK: int):
    """(T, K, NBLK) bool: the blocks a token's kv head attends to: its kept
    set, or every block up to its own for a token that does not select."""
    T, K, topk = blocks.shape
    at = jnp.arange(NBLK)
    real = jnp.arange(topk)[None, None, :] < count[..., None]
    kept = jnp.any(real[..., None] & (blocks[..., None] == at), axis=2)
    whole = at[None, None, :] <= (positions // block)[:, None, None]
    return jnp.where(selects[:, None, None], kept, whole)


# ----------------------------------------------------------- second stage

def block_attend_reference(q, k_pool, v_pool, layer, block_tables, kv_lens,
                           q_positions, cu_q_lens, keep, *, kv_heads: int,
                           scale: float, block: int):
    """The oracle: every token against its sequence's whole padded context
    under `keep` (T, K, NBLK) and the causal mask."""
    T, H, hd = q.shape
    K, G = kv_heads, H // kv_heads
    ps = k_pool.shape[2]
    seq, positions, n, _ = sl.flat_rows(cu_q_lens, q_positions, kv_lens, T)
    ctx = lambda pool: pool[layer][block_tables].reshape(
        block_tables.shape[0], -1, K, pool.shape[-1] // K)[seq]
    k, v = ctx(k_pool), ctx(v_pool)                         # (T, L, K, w)
    L = k.shape[1]
    logits = jnp.einsum("tkgd,tckd->tkgc", q.reshape(T, K, G, hd), k,
                        preferred_element_type=F32) * scale
    at = jnp.arange(L)
    seen = ((at[None, :] < n[:, None])[:, None, :]
            & jnp.take_along_axis(
                keep, jnp.broadcast_to((at // block)[None, None, :],
                                       (T, K, L)).clip(0, keep.shape[-1] - 1),
                axis=-1))[:, :, None, :]
    probs = jnp.where(seen, jax.nn.softmax(
        jnp.where(seen, logits, NEG_INF), axis=-1), 0.0).astype(v.dtype)
    out = jnp.einsum("tkgc,tckd->tkgd", probs, v, preferred_element_type=F32)
    return out.reshape(T, H, -1).astype(q.dtype)


_ATTEND_STATIC = ("kv_heads", "scale", "block", "interpret")


@functools.partial(jax.jit, static_argnames=_ATTEND_STATIC)
def block_attend_call(q, k_pool, v_pool, layer, block_tables, starts, one,
                      positions, blocks, count, *, kv_heads: int,
                      scale: float, block: int, interpret: bool):
    """The sequences of ONE row that selects (`one` (S,) bool; the row is
    flat row starts[s] at position positions[s], its kept blocks blocks[s]
    (K, topk) ascending, count[s] (K,) of them real, its own block the last):
    every (sequence, kv head) is a sequence of its own to the row kernel,
    whose table names the kept blocks' pages and whose context ends at the
    row's place in its own block. q rides in the pair form: the kv head's G
    query heads, zeros in the other heads' lanes of the 256-lane row, so the
    kernel is the row kernel of ONE kv head of K x hd lanes, a page one DMA a
    pool. -> (T, H, vd): the rows of `one`, zeros elsewhere."""
    T, H, hd = q.shape
    S, K = one.shape[0], kv_heads
    G, ps, B = H // K, k_pool.shape[2], block // k_pool.shape[2]
    vd = v_pool.shape[-1] // K
    lanes = jnp.eye(K, dtype=q.dtype)[None, :, None, :, None]
    qp = (q[starts].reshape(S, K, G, 1, hd) * lanes).reshape(
        S * K, G, K * hd)
    width = block_tables.shape[1] // B * B
    pages = block_tables[:, :width].reshape(S, -1, B)[
        jnp.arange(S)[:, None, None], blocks].reshape(S * K, -1)
    lens = jnp.where(one[:, None], (count - 1) * block
                     + (positions % block)[:, None] + 1, 0).reshape(S * K)
    lens = lens.astype(jnp.int32)
    sizes = pa.kv_sizes(G, 1, K * hd, K * vd, ps, k_pool.dtype.itemsize,
                        rows=True)
    at = jnp.arange(S * K, dtype=jnp.int32)
    out = pa._kv_call(
        qp, at, jnp.maximum(lens - 1, 0), (lens > 0).astype(jnp.int32), at,
        S * K, k_pool, v_pool, layer, pages.astype(jnp.int32), lens, None,
        scale=scale, TQ=1, kv_pages=(sizes.pages_one, sizes.pages_one),
        window=None, interpret=interpret, kv_heads=1, tag="block_attend")
    own = jnp.einsum("skgkd->skgd", out.reshape(S, K, G, K, vd))
    return jnp.zeros((T, H, vd), q.dtype).at[
        jnp.where(one, starts, T)].set(own.reshape(S, H, vd), mode="drop")


@functools.partial(jax.jit, static_argnames=_ATTEND_STATIC)
def block_attend_rows_call(q, k_pool, v_pool, layer, block_tables, kv_lens,
                           q_positions, cu_q_lens, keep, *, kv_heads: int,
                           scale: float, block: int, interpret: bool):
    """The sequences of MORE rows (kv_lens 0 for every other: its blocks walk
    nothing and come back zero): the dense walk of
    `pa.ragged_paged_attention_unified` under keep (T, K, NBLK) bool, laid as
    the row kernel reads it: a lane row a (token, kv head, tile of the
    walk)."""
    T, H, hd = q.shape
    S, K = kv_lens.shape[0], kv_heads
    ps = k_pool.shape[2]
    sizes = pa._sizes_of(q, k_pool, v_pool, kv_heads, None)
    TQ, tile = sizes.q_block, sizes.pages_many * ps
    per = tile // block                                     # blocks a tile
    if tile % block or per > LANE:
        raise ValueError(f"a walk's tile of {tile} tokens is no whole number "
                         f"of blocks of {block} (at most {LANE})")
    if LANE % per:
        raise ValueError(f"{per} blocks a tile do not divide {LANE} lanes")
    # Lane rows a kv head: LANE // per tiles of the walk side by side.
    R = -(-block_tables.shape[1] * ps // (tile * (LANE // per)))
    padded = -(-(T + TQ) // pa.Q_PAD) * pa.Q_PAD
    bits = jnp.pad(keep, ((0, padded - T), (0, 0),
                          (0, max(0, R * LANE - keep.shape[-1]))))
    bits = bits[..., :R * LANE].reshape(padded, K * R, LANE)
    seq, local, blk_n, slot_tok, first = pa.query_blocks(
        cu_q_lens, padded, S, TQ)
    # a query block's own tokens' rows, a (kv head, lane row) leading
    bits = jnp.swapaxes(bits[jnp.clip(slot_tok, 0, padded - 1)], 1, 2)
    out = pa._kv_call(
        jnp.pad(q, ((0, padded - T), (0, 0), (0, 0))), seq.astype(jnp.int32),
        (q_positions[seq] + local * TQ).astype(jnp.int32),
        blk_n.astype(jnp.int32), slot_tok[:, 0].astype(jnp.int32),
        jnp.sum(blk_n > 0), k_pool, v_pool, layer, block_tables, kv_lens,
        None, scale=scale, TQ=TQ,
        kv_pages=(sizes.pages_one, sizes.pages_many), window=None,
        interpret=interpret, kv_heads=K, keep=bits.astype(k_pool.dtype),
        block_tokens=block, tag="block_attend_rows")
    return pa.blocks_to_tokens(out, cu_q_lens, first, T, S, TQ, H)


def block_attend(q, k_pool, v_pool, layer, block_tables, kv_lens,
                 q_positions, cu_q_lens, blocks, count, *, kv_heads: int,
                 scale: float, geometry: Geometry, impl: str,
                 interpret: Optional[bool] = None):
    """Attention of the sequences whose context is longer than `dense_len`
    (zeros for the others' rows): q (T, H, hd) over the row pools (layers,
    pages, page, K x hd) under blocks (T, K, topk), count (T, K)
    (`block_select`)."""
    T = q.shape[0]
    S = kv_lens.shape[0]
    g = geometry
    seq, positions, _, selects = token_rows(cu_q_lens, q_positions, kv_lens,
                                            T, g.dense_len)
    NBLK = -(-block_tables.shape[1] * k_pool.shape[2] // g.block)
    keep = keep_bits(blocks, count, positions, selects, g.block, NBLK)
    sparse = kv_lens > g.dense_len
    lens = cu_q_lens[1:] - cu_q_lens[:-1]
    kw = dict(kv_heads=kv_heads, scale=scale, block=g.block)
    if impl != "pallas":
        out = block_attend_reference(
            q, k_pool, v_pool, layer, block_tables,
            jnp.where(sparse, kv_lens, 0), q_positions, cu_q_lens, keep, **kw)
        return jnp.where(sparse[seq][:, None, None], out, 0)
    kw["interpret"] = _interpret(interpret)
    one = sparse & (lens == 1)
    starts = jnp.clip(cu_q_lens[:-1], 0, T - 1)
    row = block_attend_call(
        q, k_pool, v_pool, layer, block_tables, starts, one,
        kv_lens - 1, blocks[starts], count[starts], **kw)
    rows = block_attend_rows_call(
        q, k_pool, v_pool, layer, block_tables,
        jnp.where(sparse & (lens > 1), kv_lens, 0), q_positions, cu_q_lens,
        keep, **kw)
    return jnp.where(one[seq][:, None, None], row,
                     jnp.where(sparse[seq][:, None, None], rows, 0))
