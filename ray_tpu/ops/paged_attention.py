"""Ragged paged attention: the serving-decode hot op.

Reference analog: the paged-attention CUDA kernels inside vLLM, which the
reference repo only places (python/ray/llm/_internal/serve/deployments/llm/
vllm/vllm_engine.py:222). TPU-native design: one kernel serves BOTH decode
(one query token per sequence) and chunked prefill (a block of query tokens
per sequence) — "ragged" means each sequence in the batch has its own query
count and context length; shapes stay static (bucketed) and per-sequence
lengths arrive as scalar-prefetch operands.

Layouts:
  q:            (S, Bq, H, hd)  — Bq = query tokens per sequence this step
                                  (1 for decode, chunk size for prefill)
  k/v pages:    (K, P, ps, hd)  — ONE layer's pages, K = kv heads: the kernel
                                  view of the pool (below)
  block_tables: (S, max_pages)  int32, logical page i of seq s -> pool page
  kv_lens:      (S,) int32      — context length INCLUDING this step's tokens
  q_positions:  (S,) int32      — absolute position of q[s, 0]

The pool these pages come from is `(L, P, ps, K, hd)` on the device:
page-major, with one token's `(K, hd)` minor (llm/model_runner.py, "The KV
pool's layout", says so once, in code, and why at length). That is the layout
of the WRITE, not of the read: XLA's scatter of a step's new rows has a `(K,
hd)` update window and wants it minor, so a pool declared any other way is
re-laid out whole on the way into the layer scan and again on the way out
(declared `(L, K, P, ps, hd)`, as before PR 27: four pool-sized copies in
every step program and a second pool of temporaries). `(L, P, K, ps, hd)`, a
head's page contiguous as the DMAs below would like it, is no way out: XLA
re-lays the carry to `{4,2,3,1,0}` and the copies are back. So each layer
slices its pages out and transposes them into the kernel view above; a kernel
that takes the whole pool, the layer by scalar prefetch, and one page of all K
heads a DMA is ROADMAP S2. Off the device, pages travel in the wire view `(L,
K, n, ps, hd)` (`ModelRunner.gather_pages` / `scatter_pages`).

The Pallas kernel walks only ceil(kv_len/ps) real pages per sequence
(double-buffered HBM->VMEM DMA), so decode cost is O(actual context), not
O(max context) — the property the round-1 jnp gather lacked.
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


def ragged_paged_attention_reference(
        q, k_pages, v_pages, block_tables, kv_lens, q_positions, *,
        scale: Optional[float] = None):
    """jnp reference (CPU tests + fallback). Gathers the full padded context;
    the Pallas kernel below is the O(actual-context) implementation."""
    S, Bq, H, hd = q.shape
    K, P, ps, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    max_ctx = max_pages * ps
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    # (K, S, max_pages, ps, hd) -> (S, max_ctx, K, hd)
    k = k_pages[:, block_tables].transpose(1, 2, 3, 0, 4).reshape(
        S, max_ctx, K, hd)
    v = v_pages[:, block_tables].transpose(1, 2, 3, 0, 4).reshape(
        S, max_ctx, K, hd)
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("sqhd,skhd->shqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(max_ctx)[None, None, None, :]
    q_abs = (q_positions[:, None] + jnp.arange(Bq)[None, :])[:, None, :, None]
    mask = (k_pos < kv_lens[:, None, None, None]) & (q_abs >= k_pos)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("shqk,skhd->sqhd", probs, v)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _rpa_kernel(block_tables_ref, kv_lens_ref, q_pos_ref,   # scalar prefetch
                q_ref, kpages_hbm, vpages_hbm,              # tensor inputs
                o_ref,                                      # output
                k_scr, v_scr, sems,                         # scratch
                *, ps: int, scale: float, Bq: int, G: int, hd: int,
                max_pages: int):
    """Grid: (S, K). Block q_ref/o_ref: (1, 1, Bq*G, hd) — the query rows of
    kv-head `kh` for sequence `s`. KV pages stay in HBM; each page is
    double-buffer DMA'd into VMEM and folded into an online softmax."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    kh = pl.program_id(1)
    kv_len = kv_lens_ref[s]
    q_pos = q_pos_ref[s]
    n_pages = pl.cdiv(kv_len, ps)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (Bq*G, hd)
    rows = Bq * G
    # Absolute position of each query row (row r belongs to query r // G).
    q_abs = q_pos + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 0) // G

    def page_dma(slot, i):
        page = block_tables_ref[s, i]
        return (pltpu.make_async_copy(kpages_hbm.at[kh, page], k_scr.at[slot],
                                      sems.at[slot, 0]),
                pltpu.make_async_copy(vpages_hbm.at[kh, page], v_scr.at[slot],
                                      sems.at[slot, 1]))

    @pl.when(n_pages > 0)
    def _():
        # Padding sequences (kv_len == 0) must not start a DMA that the
        # zero-iteration loop below would never wait on.
        kd, vd = page_dma(0, 0)
        kd.start()
        vd.start()

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_pages)
        def _():
            nk, nv = page_dma(1 - slot, i + 1)
            nk.start()
            nv.start()

        kw, vw = page_dma(slot, i)
        kw.wait()
        vw.wait()
        k_page = k_scr[slot].astype(jnp.float32)          # (ps, hd)
        v_page = v_scr[slot].astype(jnp.float32)
        sc = q @ k_page.T                                 # (rows, ps)
        k_pos = i * ps + jax.lax.broadcasted_iota(jnp.int32, (rows, ps), 1)
        valid = (k_pos < kv_len) & (q_abs >= k_pos)
        sc = jnp.where(valid, sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        acc_new = alpha * acc + p @ v_page
        return m_new, l_new, acc_new

    m0 = jnp.full((rows, 1), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((rows, 1), dtype=jnp.float32)
    a0 = jnp.zeros((rows, hd), dtype=jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def token_seq_ids(cu_q_lens, T: int, S: int):
    """Sequence id per flat token (count of cu boundaries at or below it),
    clamped into [0, S-1] so padding tokens index real scalar rows; the
    caller masks them out separately (tok >= cu_q_lens[S])."""
    tok = jnp.arange(T)
    seq = jnp.sum(tok[:, None] >= cu_q_lens[None, 1:], axis=1).astype(
        jnp.int32)
    return jnp.minimum(seq, S - 1)


def ragged_paged_attention_unified_reference(
        q, k_pages, v_pages, block_tables, kv_lens, q_positions, cu_q_lens,
        *, scale: Optional[float] = None):
    """Token-major unified reference: q is flat (T, H, hd), sequences own
    contiguous row spans delimited by cu_q_lens (S+1 cumulative starts).

    Implemented by scattering the flat rows back into the rectangular
    (S, T, H, hd) layout and calling ragged_paged_attention_reference —
    per-row math is THE SAME FUNCTION, so a unified mixed launch is
    bit-identical to the split rectangular launches it replaces (the CPU-CI
    anchor for the engine's unified-vs-split-tick identity tests)."""
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
        qr, k_pages, v_pages, block_tables, kv_lens, q_positions,
        scale=scale)
    out = out_r[seq, jnp.minimum(local, T - 1)]
    return jnp.where(valid[:, None, None], out, jnp.zeros_like(out))


def _rua_kernel(block_tables_ref, kv_lens_ref, q_pos_ref, cu_ref,  # prefetch
                q_ref, kpages_hbm, vpages_hbm,                     # tensors
                o_ref,                                             # output
                k_scr, v_scr, sems,                                # scratch
                *, ps: int, scale: float, TB: int, G: int, hd: int, S: int):
    """Grid: (T // TB, K). Block q_ref/o_ref: (1, TB, G, hd) — TB flat
    query tokens for kv head `kh`; a block may span several sequences, so
    rows carry their own sequence id (derived from the prefetched
    cu_q_lens) and every page contribution is masked per row. KV pages
    stay in HBM; each sequence in the block walks only its own
    ceil(kv_len/ps) pages, double-buffer DMA'd into VMEM and folded into
    an online softmax."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    blk = pl.program_id(0)
    kh = pl.program_id(1)
    rows = TB * G
    q = q_ref[0].astype(jnp.float32).reshape(rows, hd) * scale

    # Global token index per row (row r belongs to token r // G).
    tok = blk * TB + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // G
    n_real = cu_ref[S]
    row_valid = tok < n_real

    def count_seq(s, acc):
        return acc + (tok >= cu_ref[s]).astype(jnp.int32)

    seq = jax.lax.fori_loop(
        1, S + 1, count_seq, jnp.zeros((rows, 1), jnp.int32))
    seq = jnp.minimum(seq, S - 1)

    def seq_of(t):
        def cnt(s, acc):
            return acc + jnp.where(t >= cu_ref[s], 1, 0)

        return jnp.minimum(jax.lax.fori_loop(1, S + 1, cnt, 0), S - 1)

    s_lo = seq_of(blk * TB)
    s_hi = seq_of(jnp.minimum(blk * TB + TB - 1, jnp.maximum(n_real - 1, 0)))

    def seq_body(s, carry):
        m, l, acc = carry
        kv_len = kv_lens_ref[s]
        n_pages = pl.cdiv(kv_len, ps)
        mine = (seq == s) & row_valid                       # (rows, 1)
        q_abs = q_pos_ref[s] + (tok - cu_ref[s])            # (rows, 1)

        def page_dma(slot, i):
            page = block_tables_ref[s, i]
            return (pltpu.make_async_copy(kpages_hbm.at[kh, page],
                                          k_scr.at[slot], sems.at[slot, 0]),
                    pltpu.make_async_copy(vpages_hbm.at[kh, page],
                                          v_scr.at[slot], sems.at[slot, 1]))

        @pl.when(n_pages > 0)
        def _():
            kd, vd = page_dma(0, 0)
            kd.start()
            vd.start()

        def body(i, carry):
            m, l, acc = carry
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_pages)
            def _():
                nk, nv = page_dma(1 - slot, i + 1)
                nk.start()
                nv.start()

            kw, vw = page_dma(slot, i)
            kw.wait()
            vw.wait()
            k_page = k_scr[slot].astype(jnp.float32)        # (ps, hd)
            v_page = v_scr[slot].astype(jnp.float32)
            sc = q @ k_page.T                               # (rows, ps)
            k_pos = i * ps + jax.lax.broadcasted_iota(
                jnp.int32, (rows, ps), 1)
            ok = mine & (k_pos < kv_len) & (q_abs >= k_pos)
            sc = jnp.where(ok, sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
            # Explicit zero where masked: rows of OTHER sequences see an
            # all-NEG_INF page, and exp(NEG_INF - NEG_INF) == 1 would leak
            # phantom mass into their (still-empty) softmax state.
            p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + p.sum(axis=-1, keepdims=True)
            acc_new = alpha * acc + p @ v_page
            return m_new, l_new, acc_new

        return jax.lax.fori_loop(0, n_pages, body, (m, l, acc))

    m0 = jnp.full((rows, 1), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((rows, 1), dtype=jnp.float32)
    a0 = jnp.zeros((rows, hd), dtype=jnp.float32)
    m, l, acc = jax.lax.fori_loop(s_lo, s_hi + 1, seq_body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)
    o_ref[0] = out.reshape(TB, G, hd).astype(o_ref.dtype)


def ragged_paged_attention_unified(q, k_pages, v_pages, block_tables,
                                   kv_lens, q_positions, cu_q_lens, *,
                                   scale: Optional[float] = None,
                                   q_block: int = 8,
                                   interpret: Optional[bool] = None):
    """Pallas unified ragged paged attention: ONE launch for a mixed batch
    where each sequence contributes its own query-token count (decode = 1,
    spec verify = k+1, prefill chunk = up to chunk tokens).

    Layouts (vs the rectangular entry above):
      q:         (T, H, hd) flat token-major; sequence s owns rows
                 [cu_q_lens[s], cu_q_lens[s+1]); rows past cu_q_lens[S]
                 are padding
      cu_q_lens: (S+1,) int32 cumulative query starts
      block_tables/kv_lens/q_positions: per-sequence, as the rectangular
                 entry (q_positions[s] = absolute position of the FIRST
                 query token of s)

    T must be a multiple of q_block (the engine pads to token-budget
    buckets, all multiples of 8)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    T, H, hd = q.shape
    K, P, ps, _ = k_pages.shape
    S = kv_lens.shape[0]
    G = H // K
    TB = q_block
    if T % TB:
        raise ValueError(f"T={T} not a multiple of q_block={TB}")
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if interpret is None:
        from ray_tpu.ops import is_tpu_backend

        interpret = not is_tpu_backend()

    # (T, H, hd) -> (K, T, G, hd): one kv head's query rows contiguous.
    qt = q.reshape(T, K, G, hd).transpose(1, 0, 2, 3)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(T // TB, K),
        in_specs=[
            pl.BlockSpec((1, TB, G, hd), lambda blk, kh, *_: (kh, blk, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # k pages stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # v pages stay in HBM
        ],
        out_specs=pl.BlockSpec((1, TB, G, hd),
                               lambda blk, kh, *_: (kh, blk, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ps, hd), k_pages.dtype),
            pltpu.VMEM((2, ps, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(
        _rua_kernel, ps=ps, scale=scale, TB=TB, G=G, hd=hd, S=S)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (K, T, G, hd), q.dtype, vma=vma_of(qt, k_pages, v_pages)),
        interpret=interpret,
        **kernel_tag("paged_attention_unified"),
    )(block_tables, kv_lens, q_positions, cu_q_lens, qt, k_pages, v_pages)
    return out.transpose(1, 0, 2, 3).reshape(T, H, hd)


def ragged_paged_attention(q, k_pages, v_pages, block_tables, kv_lens,
                           q_positions, *, scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """Pallas ragged paged attention (see module docstring for layouts)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, Bq, H, hd = q.shape
    K, P, ps, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    G = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    if interpret is None:
        from ray_tpu.ops import is_tpu_backend

        interpret = not is_tpu_backend()

    # (S, Bq, H, hd) -> (S, K, Bq*G, hd): rows of one kv head contiguous.
    qt = q.reshape(S, Bq, K, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        S, K, Bq * G, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(S, K),
        in_specs=[
            pl.BlockSpec((1, 1, Bq * G, hd), lambda s, kh, *_: (s, kh, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # k pages stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # v pages stay in HBM
        ],
        out_specs=pl.BlockSpec((1, 1, Bq * G, hd),
                               lambda s, kh, *_: (s, kh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ps, hd), k_pages.dtype),
            pltpu.VMEM((2, ps, hd), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(
        _rpa_kernel, ps=ps, scale=scale, Bq=Bq, G=G, hd=hd,
        max_pages=max_pages)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (S, K, Bq * G, hd), q.dtype, vma=vma_of(qt, k_pages, v_pages)),
        interpret=interpret,
        **kernel_tag("paged_attention_rect"),
    )(block_tables, kv_lens, q_positions, qt, k_pages, v_pages)
    return out.reshape(S, K, Bq, G, hd).transpose(0, 2, 1, 3, 4).reshape(
        S, Bq, H, hd)


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
# up to `q_block` tokens of ONE sequence (a prefill slice is cut into
# ceil(n / q_block) of them, a decode row is a block of one token), so a
# sequence's context is read once a block and not once a token: a
# 128-token slice at an 8k context reads it 16 times at q_block 8, where a
# block of 8 flat tokens that may span 8 decode rows, as `_rua_kernel` has it,
# would compute every row against every sequence's pages. The context is
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
    q_blocks = jnp.take(q, slot_tok.reshape(-1), axis=0, mode="clip")
    out = _latent_call(
        q_blocks.reshape(NB, TQ * H, W), seq.astype(jnp.int32),
        (q_positions[seq] + local * TQ).astype(jnp.int32),
        blk_n.astype(jnp.int32), pool, layer, block_tables, kv_lens,
        scale=scale, lat=lat, TQ=TQ, H=H, kv_pages=kv_pages,
        interpret=interpret)
    tok_seq = token_seq_ids(cu_q_lens, T, S)
    tok_local = jnp.arange(T) - cu_q_lens[tok_seq]
    tok_slot = (first[tok_seq] + tok_local // TQ) * TQ + tok_local % TQ
    flat = out.reshape(NB * TQ, H, lat)[jnp.clip(tok_slot, 0, NB * TQ - 1)]
    valid = jnp.arange(T) < cu_q_lens[S]
    return jnp.where(valid[:, None, None], flat, jnp.zeros_like(flat))


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
