"""Attention ops: XLA-fused reference + Pallas flash attention (fwd + bwd).

Design (TPU-first):
  * flash_attention is DIFFERENTIABLE (custom_vjp): the forward kernel
    also emits the per-row logsumexp; the backward recomputes attention
    blockwise in two Pallas kernels (dQ; dK/dV) — FlashAttention-2's
    schedule — so training never materializes the (b, h, s, s) logits.
  * The core returns (out, lse) so sequence-parallel callers
    (parallel/ring.py) can merge per-chunk results by logsumexp; the lse
    cotangent folds into the backward's delta term (ds = p*(dp-Δ+g_lse)).
  * mha_reference stays as the O(s^2)-memory jnp reference: XLA fuses the
    fp32 softmax into the matmuls; it is the numerics oracle in tests and
    the fallback for shapes the kernels don't tile well.
  * Serving/prefill uses the same forward kernel (no backward needed):
    online softmax over KV blocks, O(seq) memory, causal-block skipping —
    the TTFT hot path the reference outsources to vLLM's CUDA kernels.
  * GQA (n_kv_heads < n_heads): the flash kernels read K/V UNREPEATED —
    BlockSpec index maps (_kv_row) steer each q-head program at its kv
    head, and dK/dV group sums are explicit (grouped inner grid in the
    tiled pass; a post-kernel reshape-sum in the resident pass).
    mha_reference still uses logical repeat_kv with autodiff summing.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_tag

NEG_INF = -1e30

# Lane width of the LSE/delta side outputs. Mosaic requires the last two
# block dims to be (8, 128)-divisible or equal to the array dims, so scalar
# per-row values are carried in a 128-lane fp32 plane (column 0 is the
# value; the rest is broadcast) exactly like the reference TPU kernel
# (jax/experimental/pallas/ops/tpu/flash_attention.py MIN_BLOCK_SIZE).
LANES = 128

# Longest padded sequence for which the backward / forward use the
# whole-sequence-resident kernels (above it, the O(block)-VMEM tiled
# kernels take over — see _flash_bwd_rule / _flash_call). The resident
# kernels skip causal-dead KV blocks entirely (no tile DMA) and are ~18%
# faster where they fit; residency grows linearly with seq and busts the
# ~16 MB scoped VMEM near 8k (bwd) / 16k (fwd). Module-level so tests can
# force the tiled paths at interpret-friendly sizes.
_BWD_RESIDENT_MAX_ROWS = 4096
_FWD_RESIDENT_MAX_ROWS = 8192


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """(batch, seq, kv_heads, hd) -> (batch, seq, kv_heads*n_rep, hd)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d)


def mha_reference(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, scale: Optional[float] = None,
                  positions_q: Optional[jax.Array] = None,
                  positions_kv: Optional[jax.Array] = None) -> jax.Array:
    """q: (b, sq, h, d); k/v: (b, skv, hkv, d). Returns (b, sq, h, d).

    fp32 softmax; XLA fuses this chain on TPU. The causal mask compares
    absolute positions when provided (needed for ring/sequence parallelism).
    """
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    if hkv != h:
        k = repeat_kv(k, h // hkv)
        v = repeat_kv(v, h // hkv)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        pos_q = positions_q if positions_q is not None else jnp.arange(sq)
        pos_k = positions_kv if positions_kv is not None else jnp.arange(k.shape[1])
        mask = pos_q[:, None] >= pos_k[None, :]
        logits = jnp.where(mask[None, None, :, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# ---------------------------------------------------------------------------
# Pallas flash-attention forward (TPU)
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                      seq_kv: int, true_kv: int, causal: bool, scale: float,
                      block_q: int):
    """Grid: (batch*heads, num_q_blocks). Blocks:
    q_ref: (block_q, d), k_ref/v_ref: (seq_kv, d) resident, o_ref:
    (block_q, d), lse_ref: (block_q, LANES) — per-row logsumexp of the
    SCALED logits broadcast across lanes (column 0 is authoritative),
    consumed by the backward kernels and by ring-attention merges.

    Online softmax over KV blocks; with causal=True, KV blocks entirely above
    the diagonal are skipped (the scheduling win of flash attention).
    """
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale  # block: (1, block_q, d)
    d = q.shape[-1]

    m = jnp.full((block_q, 1), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((block_q, 1), dtype=jnp.float32)
    acc = jnp.zeros((block_q, d), dtype=jnp.float32)

    q_start = qi * block_q
    num_k_blocks = pl.cdiv(seq_kv, block_k)
    # Causal: only iterate KV blocks whose start is <= the last query row.
    max_kb = jnp.where(
        causal, (q_start + block_q - 1) // block_k + 1, num_k_blocks)

    def body(kb, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = q @ k_blk.T  # (block_q, block_k)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if true_kv != seq_kv:  # padded tail block: mask padded keys
            s = jnp.where(k_pos < true_kv, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        acc_new = alpha * acc + p @ v_blk
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, max_kb, body, (m, l, acc))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if lse_ref is not None:
        lse_ref[0] = jnp.broadcast_to(m + jnp.log(jnp.maximum(l, 1e-30)),
                                      (block_q, LANES))


def _flash_fwd_kernel_nolse(q_ref, k_ref, v_ref, o_ref, **kw):
    """Forward without the LSE side output: the serving/prefill path needs
    only `out`, and the (bh, sq, LANES) fp32 lane plane would be ~128x the
    useful bytes of pure HBM write traffic on the TTFT hot path."""
    _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, None, **kw)


def _flash_fwd_kernel_tiled(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref,
                            l_ref, acc_ref, *, block_k: int,
                            num_k_blocks: int, true_kv: int, seq_kv: int,
                            causal: bool, scale: float, block_q: int):
    """Long-context forward. Grid: (batch*heads, num_q_blocks,
    num_k_blocks) — the KV walk is a grid dimension so one (block_k, d)
    tile is VMEM-resident at a time (the whole-sequence-resident kernel
    above busts the ~16 MB scoped VMEM near seq 16k). Online-softmax
    state (m, l, acc) lives in f32 scratch persisting across the inner
    grid steps; outputs are written on the last one."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kb = pl.program_id(2)
    q_start = qi * block_q
    k_start = kb * block_k

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, dtype=m_ref.dtype)
        l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    live = ((k_start <= q_start + block_q - 1) if causal
            else (kb >= 0))  # traced either way for pl.when

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32) * scale
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = q @ k_blk.T  # (block_q, block_k)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if true_kv != seq_kv:  # padded tail block: mask padded keys
            s = jnp.where(k_pos < true_kv, s, NEG_INF)
        m = m_ref[:, 0:1]
        l = l_ref[:, 0:1]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + p @ v_blk
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == num_k_blocks - 1)
    def _write():
        m = m_ref[:, 0:1]
        l = l_ref[:, 0:1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0] = jnp.broadcast_to(
                m + jnp.log(jnp.maximum(l, 1e-30)), (block_q, LANES))


def _flash_fwd_kernel_tiled_nolse(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                                  acc_ref, **kw):
    _flash_fwd_kernel_tiled(q_ref, k_ref, v_ref, o_ref, None, m_ref, l_ref,
                            acc_ref, **kw)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, acc_ref, *, block_k: int, num_k_blocks: int,
                         true_kv: int, seq_kv: int, causal: bool,
                         scale: float, block_q: int):
    """dQ pass. Grid: (batch*heads, num_q_blocks, num_k_blocks) — the KV
    walk is a GRID dimension, not an in-kernel loop, so only one
    (block_k, d) K/V tile is VMEM-resident at a time (Mosaic pipelines the
    tile DMAs) and VMEM stays O(block) at any sequence length; the old
    whole-sequence-resident layout blew the ~16 MB scoped VMEM budget at
    seq 8192. dQ accumulates in an f32 scratch that persists across the
    innermost grid steps; the out block is written once, on the last step.
    Recomputes p blockwise from (q, k, lse) — no stored logits. delta_ref
    carries rowsum(dO*O) - g_lse (the lse cotangent folds in; see
    _flash_bwd_rule)."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kb = pl.program_id(2)
    q_start = qi * block_q
    k_start = kb * block_k

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)

    # Causal: KV blocks entirely above the diagonal contribute nothing —
    # compute (not the tile DMA) is skipped for them.
    live = ((k_start <= q_start + block_q - 1) if causal
            else (kb >= 0))  # traced either way for pl.when

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:, 0:1]    # (block_q, 1) from the lane plane
        delta = delta_ref[0][:, 0:1]
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = (q @ k_blk.T) * scale
        p = jnp.exp(s - lse)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        if true_kv != seq_kv:
            p = jnp.where(k_pos < true_kv, p, 0.0)
        dp = do @ v_blk.T
        ds = p * (dp - delta)
        acc_ref[...] += ds @ k_blk

    @pl.when(kb == num_k_blocks - 1)
    def _write():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *,
                          block_q: int, num_q_blocks: int, n_rep: int,
                          true_kv: int, mask_kv_tail: bool, causal: bool,
                          scale: float, block_k: int):
    """dK/dV pass, GQA-native. Grid: (batch*kv_heads, num_k_blocks,
    n_rep * num_q_blocks) — one program per KV head; the inner grid walks
    every (group member g, q block qi) pair with (g, qi) = divmod(inner,
    num_q_blocks), the BlockSpec index maps steering the q-side tiles to
    q-head row kvh*n_rep + g (same VMEM-bounding rationale as the dQ
    pass). dK/dV accumulate the whole group's contribution in f32 scratch
    and are written once, on the last inner step. Causal skip mirrors the
    forward: q blocks strictly above the diagonal are dead. Padded q rows
    (beyond true seq) contribute nothing even unmasked: their dO and
    delta are zero-padded, so ds == 0 and p^T @ dO adds 0."""
    from jax.experimental import pallas as pl

    kb = pl.program_id(1)
    qin = pl.program_id(2)
    qi = qin % num_q_blocks
    k_start = kb * block_k
    q_start = qi * block_q
    num_inner = n_rep * num_q_blocks

    @pl.when(qin == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros(dk_acc_ref.shape, dk_acc_ref.dtype)
        dv_acc_ref[...] = jnp.zeros(dv_acc_ref.shape, dv_acc_ref.dtype)

    live = ((q_start + block_q - 1 >= k_start) if causal
            else (qin >= 0))  # traced either way for pl.when

    @pl.when(live)
    def _accumulate():
        k_blk = k_ref[0].astype(jnp.float32)   # (block_k, d)
        v_blk = v_ref[0].astype(jnp.float32)
        q_blk = q_ref[0].astype(jnp.float32)   # (block_q, d)
        do_blk = do_ref[0].astype(jnp.float32)
        lse_blk = lse_ref[0][:, 0:1]
        delta_blk = delta_ref[0][:, 0:1]
        s = (q_blk @ k_blk.T) * scale   # (block_q, block_k)
        p = jnp.exp(s - lse_blk)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        if mask_kv_tail:  # padded tail keys must not receive dK/dV
            p = jnp.where(k_pos < true_kv, p, 0.0)
        dv_acc_ref[...] += p.T @ do_blk
        dp = do_blk @ v_blk.T
        ds = p * (dp - delta_blk)
        dk_acc_ref[...] += ds.T @ q_blk

    @pl.when(qin == num_inner - 1)
    def _write():
        dk_ref[0] = (dk_acc_ref[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, seq_kv: int, true_kv: int,
                         causal: bool, scale: float, block_q: int):
    """Whole-sequence-resident dQ pass (grid (batch*heads, num_q_blocks)):
    K/V live in VMEM for the whole program, and the in-kernel fori SKIPS
    causal-dead KV blocks entirely (no tile DMA, no compute) — ~18%
    faster than the tiled variant at seq 2048, but residency grows with
    seq and busts the ~16 MB VMEM budget near 8k (the tiled kernels
    take over there; see _flash_bwd_rule). Recomputes p blockwise
    from (q, k, lse) — no stored logits. delta_ref carries
    rowsum(dO*O) - g_lse (the lse cotangent folds in here; see _flash_bwd).
    """
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, 0:1]        # (block_q, 1) from the lane plane
    delta = delta_ref[0][:, 0:1]
    d = q.shape[-1]

    q_start = qi * block_q
    num_k_blocks = pl.cdiv(seq_kv, block_k)
    max_kb = jnp.where(
        causal, (q_start + block_q - 1) // block_k + 1, num_k_blocks)

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = (q @ k_blk.T) * scale
        p = jnp.exp(s - lse)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        if true_kv != seq_kv:
            p = jnp.where(k_pos < true_kv, p, 0.0)
        dp = do @ v_blk.T
        ds = p * (dp - delta)
        return dq + ds @ k_blk

    dq = jax.lax.fori_loop(0, max_kb, body,
                           jnp.zeros((block_q, d), dtype=jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, seq_q: int,
                          true_kv: int, mask_kv_tail: bool, causal: bool,
                          scale: float, block_k: int):
    """Whole-sequence-resident dK/dV pass (see the dQ twin above for the
    residency-vs-seq tradeoff). Loops over q blocks at
    or below the diagonal (causal skip mirrored from the forward). Padded q
    rows (seq_q is the PADDED length) contribute nothing without masking:
    their dO and delta are zero-padded, so ds == 0 and p^T @ dO adds 0."""
    from jax.experimental import pallas as pl

    kb = pl.program_id(1)
    k_blk = k_ref[0].astype(jnp.float32)   # (block_k, d)
    v_blk = v_ref[0].astype(jnp.float32)
    d = k_blk.shape[-1]

    k_start = kb * block_k
    num_q_blocks = pl.cdiv(seq_q, block_q)
    # Causal: q blocks strictly above the diagonal contribute nothing.
    min_qb = jnp.where(causal, k_start // block_q, 0)

    def body(qi, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        do_blk = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse_blk = lse_ref[0, pl.ds(qi * block_q, block_q), :][:, 0:1]
        delta_blk = delta_ref[0, pl.ds(qi * block_q, block_q), :][:, 0:1]
        s = (q_blk @ k_blk.T) * scale   # (block_q, block_k)
        p = jnp.exp(s - lse_blk)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        if mask_kv_tail:  # padded tail keys must not receive dK/dV
            p = jnp.where(k_pos < true_kv, p, 0.0)
        dv_new = dv + p.T @ do_blk
        dp = do_blk @ v_blk.T
        ds = p * (dp - delta_blk)
        dk_new = dk + ds.T @ q_blk
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(
        min_qb, num_q_blocks, body,
        (jnp.zeros((block_k, d), dtype=jnp.float32),
         jnp.zeros((block_k, d), dtype=jnp.float32)))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def vma_of(*xs):
    """Union of the inputs' varying-mesh-axes sets: a pallas_call out_shape
    inside shard_map must declare how its outputs vary (check_vma); outside
    shard_map this is the empty set."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _sds(shape, dtype, vma):
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _fold(x):
    """(b, s, h, d) -> (b*h, s, d) for the kernels' grid layout."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _kv_row(h: int, hkv: int):
    """Index-map arithmetic for GQA: q-head grid row -> kv-head row.

    Q is folded to (b*h, s, d) rows bi*h + hi; K/V stay UNREPEATED at
    (b*hkv, s, d) rows bi*hkv + hi//n_rep. Mapping the kv head in the
    BlockSpec instead of materializing repeat_kv skips the repeated
    K/V copies entirely (2x K/V HBM traffic and residuals for the
    llama GQA configs), which is where long-context bandwidth goes."""
    n_rep = h // hkv
    return lambda bh: (bh // h) * hkv + (bh % h) // n_rep


def _flash_call(q, k, v, causal, scale, block_q, block_k, interpret,
                emit_lse: bool = True):
    """Run the forward kernel; q: (b, s, h, d), k/v: (b, s, hkv, d) with
    hkv dividing h (GQA handled natively via _kv_row index maps — no
    repeated copies). Returns (out, lse) with lse shaped (b, h, sq) in
    fp32; with emit_lse=False returns (out, None) and the kernel writes
    no LSE plane (serving hot path)."""
    from jax.experimental import pallas as pl

    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    kvr = _kv_row(h, hkv)
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    vma = vma_of(q, k, v)
    qt, kt, vt = _fold(q), _fold(k), _fold(v)
    # Pad sequence dims up to block multiples: in-kernel pl.ds slices CLAMP
    # at the array edge, which would silently mislabel tail rows. Padded
    # keys are masked inside the kernels (true_kv); padded q rows are
    # sliced off the outputs.
    sq_p = -(-sq // block_q) * block_q
    skv_p = -(-skv // block_k) * block_k
    if sq_p != sq:
        qt = jnp.pad(qt, ((0, 0), (0, sq_p - sq), (0, 0)))
    if skv_p != skv:
        kt = jnp.pad(kt, ((0, 0), (0, skv_p - skv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, skv_p - skv), (0, 0)))
    if skv_p <= _FWD_RESIDENT_MAX_ROWS:
        grid = (b * h, sq_p // block_q)
        kw = dict(block_k=block_k, seq_kv=skv_p, true_kv=skv, causal=causal,
                  scale=scale, block_q=block_q)
        out_specs = [pl.BlockSpec((1, block_q, d),
                                  lambda bh, qi: (bh, qi, 0))]
        out_shape = [_sds((b * h, sq_p, d), q.dtype, vma)]
        if emit_lse:
            kernel = functools.partial(_flash_fwd_kernel, **kw)
            out_specs.append(
                pl.BlockSpec((1, block_q, LANES),
                             lambda bh, qi: (bh, qi, 0)))
            out_shape.append(_sds((b * h, sq_p, LANES), jnp.float32, vma))
        else:
            kernel = functools.partial(_flash_fwd_kernel_nolse, **kw)
        res = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
                pl.BlockSpec((1, skv_p, d),
                             lambda bh, qi: (kvr(bh), 0, 0)),
                pl.BlockSpec((1, skv_p, d),
                             lambda bh, qi: (kvr(bh), 0, 0)),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
            **kernel_tag("flash_fwd"),
        )(qt, kt, vt)
    else:
        # Long-context: KV walk as a grid dimension, O(block) VMEM (see
        # _flash_fwd_kernel_tiled).
        from jax.experimental.pallas import tpu as pltpu

        num_qb, num_kb = sq_p // block_q, skv_p // block_k
        kw = dict(block_k=block_k, num_k_blocks=num_kb, true_kv=skv,
                  seq_kv=skv_p, causal=causal, scale=scale, block_q=block_q)
        out_specs = [pl.BlockSpec((1, block_q, d),
                                  lambda bh, qi, kb: (bh, qi, 0))]
        out_shape = [_sds((b * h, sq_p, d), q.dtype, vma)]
        if emit_lse:
            kernel = functools.partial(_flash_fwd_kernel_tiled, **kw)
            out_specs.append(
                pl.BlockSpec((1, block_q, LANES),
                             lambda bh, qi, kb: (bh, qi, 0)))
            out_shape.append(_sds((b * h, sq_p, LANES), jnp.float32, vma))
        else:
            kernel = functools.partial(_flash_fwd_kernel_tiled_nolse, **kw)
        res = pl.pallas_call(
            kernel,
            grid=(b * h, num_qb, num_kb),
            in_specs=[
                pl.BlockSpec((1, block_q, d),
                             lambda bh, qi, kb: (bh, qi, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda bh, qi, kb: (kvr(bh), kb, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda bh, qi, kb: (kvr(bh), kb, 0)),
            ],
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((block_q, LANES), jnp.float32),
                            pltpu.VMEM((block_q, LANES), jnp.float32),
                            pltpu.VMEM((block_q, d), jnp.float32)],
            interpret=interpret,
            **kernel_tag("flash_fwd_tiled"),
        )(qt, kt, vt)
    out = _unfold(res[0][:, :sq], b, h)
    if not emit_lse:
        return out, None
    return out, res[1][:, :sq, 0].reshape(b, h, sq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_call(q, k, v, causal, scale, block_q, block_k, interpret)


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret):
    from jax.ad_checkpoint import checkpoint_name

    out, lse = _flash_call(q, k, v, causal, scale, block_q, block_k,
                           interpret)
    # Name the kernel's outputs so a checkpoint policy can SAVE them
    # (save_only_these_names): the flash backward needs exactly (q, k, v,
    # out, lse), and q/k/v are cheap dot recomputes from the saved layer
    # input — with out+lse saved, the rematerialized backward DCEs the
    # whole O(s^2) forward kernel instead of re-running it. That is the
    # "flash" remat policy (models/llama.py), the long-context middle
    # ground between "dots" (too much memory past 8k) and full remat
    # (recomputes the quadratic kernel).
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return (out, lse), (q, k, v, out, lse)


def _flash_bwd_resident_calls(qt, kt, vt, dot, lse_t, delta, *, b, h, hkv,
                              d, sq, skv, sq_p, skv_p, block_q, block_k,
                              causal, scale, vma, interpret, q_dtype,
                              k_dtype, v_dtype):
    """Backward via the whole-sequence-resident kernels (small-seq fast
    path; see the implementation-choice comment in _flash_bwd_rule).

    GQA: K/V are read unrepeated via _kv_row index maps. The dK/dV pass
    still runs one program per Q head (its per-(bh, kb) scratchless
    accumulation cannot also sum across heads), so it emits per-q-head
    partials at (b*h, skv, d) and the group sum happens outside — small
    seq only, so the extra HBM is bounded."""
    from jax.experimental import pallas as pl

    kvr = _kv_row(h, hkv)
    n_rep = h // hkv
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel_resident, block_k=block_k,
                          seq_kv=skv_p, true_kv=skv, causal=causal,
                          scale=scale, block_q=block_q),
        grid=(b * h, sq_p // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, skv_p, d), lambda bh, qi: (kvr(bh), 0, 0)),
            pl.BlockSpec((1, skv_p, d), lambda bh, qi: (kvr(bh), 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda bh, qi: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0)),
        out_shape=_sds((b * h, sq_p, d), q_dtype, vma),
        interpret=interpret,
        **kernel_tag("flash_bwd_dq_resident"),
    )(qt, kt, vt, dot, lse_t, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel_resident, block_q=block_q,
                          seq_q=sq_p, true_kv=skv,
                          mask_kv_tail=skv_p != skv,
                          causal=causal, scale=scale, block_k=block_k),
        grid=(b * h, skv_p // block_k),
        in_specs=[
            pl.BlockSpec((1, sq_p, d), lambda bh, kb: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb: (kvr(bh), kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb: (kvr(bh), kb, 0)),
            pl.BlockSpec((1, sq_p, d), lambda bh, kb: (bh, 0, 0)),
            pl.BlockSpec((1, sq_p, LANES), lambda bh, kb: (bh, 0, 0)),
            pl.BlockSpec((1, sq_p, LANES), lambda bh, kb: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, kb: (bh, kb, 0)),
        ],
        out_shape=[
            # f32 partials ONLY when a group sum follows (n_rep > 1);
            # plain MHA writes the final dtype directly — no widened HBM
            # traffic, no extra cast pass.
            _sds((b * h, skv_p, d),
                 jnp.float32 if n_rep > 1 else k_dtype, vma),
            _sds((b * h, skv_p, d),
                 jnp.float32 if n_rep > 1 else v_dtype, vma),
        ],
        interpret=interpret,
        **kernel_tag("flash_bwd_dkv_resident"),
    )(qt, kt, vt, dot, lse_t, delta)
    if n_rep > 1:
        # Per-q-head partials -> kv-head grads. Head order after _fold is
        # hi = kvh*n_rep + g, so adjacent rows within a group sum.
        dk = dk.reshape(b, hkv, n_rep, skv_p, d).sum(axis=2).reshape(
            b * hkv, skv_p, d).astype(k_dtype)
        dv = dv.reshape(b, hkv, n_rep, skv_p, d).sum(axis=2).reshape(
            b * hkv, skv_p, d).astype(v_dtype)
    return (_unfold(dq[:, :sq], b, h),
            _unfold(dk[:, :skv], b, hkv),
            _unfold(dv[:, :skv], b, hkv))


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret, res, cts):
    from jax.experimental import pallas as pl

    q, k, v, out, lse = res
    g_out, g_lse = cts
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    kvr = _kv_row(h, hkv)
    n_rep = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    vma = vma_of(q, k, v, g_out)
    qt, kt, vt = _fold(q), _fold(k), _fold(v)
    dot = _fold(g_out.astype(jnp.float32))
    ot = _fold(out.astype(jnp.float32))
    lse_t = lse.reshape(b * h, sq)
    # delta = rowsum(dO*O); an lse cotangent shifts it (d lse/d s = p, so
    # ds = p*(dp - delta + g_lse) == p*(dp - (delta - g_lse))).
    delta = jnp.sum(dot * ot, axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.reshape(b * h, sq).astype(jnp.float32)

    # Same tail-block padding as the forward (pl.ds clamps at array edges).
    # lse pads with +1e30 so padded q rows give p = exp(s - 1e30) == 0;
    # dO/delta pad with zeros, making padded rows exact no-ops.
    sq_p = -(-sq // block_q) * block_q
    skv_p = -(-skv // block_k) * block_k
    if sq_p != sq:
        pad = ((0, 0), (0, sq_p - sq))
        qt = jnp.pad(qt, pad + ((0, 0),))
        dot = jnp.pad(dot, pad + ((0, 0),))
        lse_t = jnp.pad(lse_t, pad, constant_values=1e30)
        delta = jnp.pad(delta, pad)
    if skv_p != skv:
        kt = jnp.pad(kt, ((0, 0), (0, skv_p - skv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, skv_p - skv), (0, 0)))

    # Expand per-row scalars into the 128-lane plane the kernels read
    # (Mosaic tiling: a 2D (bh, s) array cannot be blocked (1, block_q)).
    lse_t = jnp.broadcast_to(lse_t[..., None], (b * h, sq_p, LANES))
    delta = jnp.broadcast_to(delta[..., None], (b * h, sq_p, LANES))

    from jax.experimental.pallas import tpu as pltpu

    # Two implementations of each pass (same math, same numerics):
    #   * resident — whole-sequence K/V (dQ) / q-side tensors (dK/dV) in
    #     VMEM, causal-dead blocks skipped entirely. Fastest, but VMEM
    #     residency grows linearly with seq (dK/dV pass: ~1.8 KB/row ->
    #     ~15 MB at 8k, past the ~16 MB scoped budget).
    #   * tiled — the walked axis is a grid dimension, one (block, d)
    #     tile resident at a time, f32 scratch accumulation: O(block)
    #     VMEM at ANY seq, ~18% slower at 2048 (dead blocks still DMA).
    # Pick resident while the bigger pass fits comfortably.
    resident = max(sq_p, skv_p) <= _BWD_RESIDENT_MAX_ROWS
    if resident:
        return _flash_bwd_resident_calls(
            qt, kt, vt, dot, lse_t, delta, b=b, h=h, hkv=hkv, d=d, sq=sq,
            skv=skv, sq_p=sq_p, skv_p=skv_p, block_q=block_q,
            block_k=block_k, causal=causal, scale=scale, vma=vma,
            interpret=interpret, q_dtype=q.dtype, k_dtype=k.dtype,
            v_dtype=v.dtype)

    num_qb, num_kb = sq_p // block_q, skv_p // block_k
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k,
                          num_k_blocks=num_kb, true_kv=skv, seq_kv=skv_p,
                          causal=causal, scale=scale, block_q=block_q),
        grid=(b * h, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, kb: (kvr(bh), kb, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, kb: (kvr(bh), kb, 0)),
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES),
                         lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, LANES),
                         lambda bh, qi, kb: (bh, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi, kb: (bh, qi, 0)),
        out_shape=_sds((b * h, sq_p, d), q.dtype, vma),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        **kernel_tag("flash_bwd_dq"),
    )(qt, kt, vt, dot, lse_t, delta)

    # dK/dV GQA-native: one program per KV head; the inner grid walks
    # every (group member, q block) pair — n_rep * num_qb steps — and the
    # f32 scratch accumulates the whole group's contribution before one
    # write at (b*hkv) rows. Q-side index maps decompose the inner index
    # as (g, qi) = divmod(qin, num_qb); q-head row = bkv-derived batch *
    # h + kv_head * n_rep + g (head order after _fold is kvh*n_rep + g).
    def _q_row(bkv, qin):
        return ((bkv // hkv) * h + (bkv % hkv) * n_rep + qin // num_qb)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q,
                          num_q_blocks=num_qb, n_rep=n_rep, true_kv=skv,
                          mask_kv_tail=skv_p != skv, causal=causal,
                          scale=scale, block_k=block_k),
        grid=(b * hkv, num_kb, n_rep * num_qb),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda bkv, kb, qin: (_q_row(bkv, qin),
                                               qin % num_qb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bkv, kb, qin: (bkv, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bkv, kb, qin: (bkv, kb, 0)),
            pl.BlockSpec((1, block_q, d),
                         lambda bkv, kb, qin: (_q_row(bkv, qin),
                                               qin % num_qb, 0)),
            pl.BlockSpec((1, block_q, LANES),
                         lambda bkv, kb, qin: (_q_row(bkv, qin),
                                               qin % num_qb, 0)),
            pl.BlockSpec((1, block_q, LANES),
                         lambda bkv, kb, qin: (_q_row(bkv, qin),
                                               qin % num_qb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bkv, kb, qin: (bkv, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda bkv, kb, qin: (bkv, kb, 0)),
        ],
        out_shape=[
            _sds((b * hkv, skv_p, d), k.dtype, vma),
            _sds((b * hkv, skv_p, d), v.dtype, vma),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        **kernel_tag("flash_bwd_dkv"),
    )(qt, kt, vt, dot, lse_t, delta)

    return (_unfold(dq[:, :sq], b, h), _unfold(dk[:, :skv], b, hkv),
            _unfold(dv[:, :skv], b, hkv))


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _flash_prep(q, k, v, scale, interpret):
    """Shared defaults for the flash entry points. K/V stay at their
    native kv-head count — the kernels map kv heads via _kv_row index
    arithmetic instead of materializing repeat_kv."""
    h, hkv = q.shape[2], k.shape[2]
    if h % hkv != 0:
        raise ValueError(f"n_heads {h} not divisible by n_kv_heads {hkv}")
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        from ray_tpu.ops import is_tpu_backend

        interpret = not is_tpu_backend()
    return k, v, scale, interpret


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None,
                    return_lse: bool = False):
    """Differentiable Pallas flash attention (fwd + custom_vjp bwd).
    q: (b, sq, h, d), k/v: (b, skv, hkv, d). With return_lse=True also
    returns the (b, h, sq) logsumexp (for sequence-parallel merges)."""
    k, v, scale, interpret = _flash_prep(q, k, v, scale, interpret)
    out, lse = _flash(q, k, v, causal, scale, block_q, block_k, interpret)
    return (out, lse) if return_lse else out


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, scale: Optional[float] = None,
                        block_q: int = 512, block_k: int = 512,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Forward-only entry point (serving hot path; no residual outputs)."""
    k, v, scale, interpret = _flash_prep(q, k, v, scale, interpret)
    out, _ = _flash_call(q, k, v, causal, scale, block_q, block_k, interpret,
                         emit_lse=False)
    return out


def resolve_attention_impl(impl: str, q_shape) -> str:
    """"auto" -> "flash" on TPU when the head dim tiles the MXU lane width
    and the sequence is long enough to tile, else the fused reference.
    Anything else passes through."""
    if impl != "auto":
        return impl
    from ray_tpu.ops import is_tpu_backend

    return ("flash" if is_tpu_backend() and q_shape[-1] % 128 == 0
            and q_shape[1] >= 256 else "reference")


def attention(q, k, v, *, causal: bool = True, scale: Optional[float] = None,
              impl: str = "auto") -> jax.Array:
    """Dispatch: "reference" (XLA-fused jnp), "flash" (Pallas fwd+bwd —
    O(seq) memory, differentiable), "auto" (resolve_attention_impl)."""
    impl = resolve_attention_impl(impl, q.shape)
    if impl == "reference":
        return mha_reference(q, k, v, causal=causal, scale=scale)
    if impl == "flash":
        return flash_attention(q, k, v, causal=causal, scale=scale)
    raise ValueError(f"unknown attention impl {impl!r}")
