"""Ring attention: sequence/context parallelism over the `sp` mesh axis.

Absent from the reference entirely (SURVEY §2.4 marks SP/CP "must be built
natively"). Design: the sequence dimension is sharded over `sp`; each device
holds one query block and rotates KV blocks around the ICI ring with
`lax.ppermute`. Each arriving chunk is attended with the Pallas flash
kernel (ops/attention.py — O(seq) memory, never materializing the
(b, h, s, s) logits) and chunks merge by logsumexp. Communication overlaps
compute naturally because XLA pipelines the ppermute with the per-chunk
kernels.

Chunk masking exploits that shards are aligned, equal-length runs of the
global sequence: a KV chunk from rank src is — relative to this rank's
queries — entirely in the past (src < my: unmasked), the diagonal
(src == my: standard causal), or entirely in the future (src > my: fully
masked, contributes nothing). So the flash kernel needs no absolute
positions; a 3-way lax.switch picks the case per step.

Differentiable end-to-end: the flash kernel has a custom_vjp (its lse
output's cotangent folds into the backward delta term), the lse merge is
plain jnp, and ppermute has a transpose rule (backward re-rotates blocks
in reverse).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import NEG_INF, flash_attention, repeat_kv


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = "sp", causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Call INSIDE shard_map with seq sharded over `axis_name`.

    q: (b, seq_local, h, d); k/v: (b, seq_local, hkv, d) — the local shard.
    Device i holds tokens [i*seq_local, (i+1)*seq_local).
    """
    b, sq, h, d = q.shape
    # K/V circulate the ring UNREPEATED (flash_attention is GQA-native via
    # _kv_row index maps): n_rep-times less ppermute traffic and HBM
    # residency per hop for GQA configs.
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    sp = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)

    def chunk_attn(k_blk, v_blk, src):
        """(out, lse) for one KV chunk via the flash kernel; 3-way switch
        on the chunk's position relative to the diagonal."""

        def past(_):
            return flash_attention(q, k_blk, v_blk, causal=False,
                                   scale=scale, return_lse=True)

        def diagonal(_):
            return flash_attention(q, k_blk, v_blk, causal=True,
                                   scale=scale, return_lse=True)

        def future(_):
            # Constants must carry the same varying-mesh-axes set as the
            # flash branches or lax.switch rejects the branch types.
            from ray_tpu.ops.attention import vma_of

            vma = vma_of(q, k_blk, v_blk)
            z = jnp.zeros((b, sq, h, d), dtype=q.dtype)
            neg = jnp.full((b, h, sq), NEG_INF, dtype=jnp.float32)
            if vma:
                z = lax.pvary(z, tuple(vma))
                neg = lax.pvary(neg, tuple(vma))
            return z, neg

        if not causal:
            return past(None)
        case = jnp.int32(0) + (src == my) + 2 * (src > my)
        return lax.switch(case, [past, diagonal, future], None)

    def merge(out, lse, blk_out, blk_lse):
        """Numerically-stable softmax merge of two normalized partials."""
        lse_new = jnp.logaddexp(lse, blk_lse)           # (b, h, sq)
        w_old = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
        w_blk = jnp.exp(blk_lse - lse_new).transpose(0, 2, 1)[..., None]
        return (out.astype(jnp.float32) * w_old
                + blk_out.astype(jnp.float32) * w_blk), lse_new

    # Step 0 attends the LOCAL chunk (src == my: the diagonal — every row
    # has at least its own token, so the carry lse starts finite and the
    # merge never sees exp(-inf - -inf)).
    out, lse = chunk_attn(k, v, my)
    out = out.astype(jnp.float32)
    k_blk, v_blk = k, v
    perm = [(p, (p + 1) % sp) for p in range(sp)]
    # Python loop: sp is static, XLA unrolls and pipelines ppermute/compute.
    for i in range(1, sp):
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        src = (my - i) % sp
        blk_out, blk_lse = chunk_attn(k_blk, v_blk, src)
        out, lse = merge(out, lse, blk_out, blk_lse)
    return out.astype(q.dtype)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      axis_name: str = "sp", causal: bool = True,
                      scale: Optional[float] = None,
                      attn_fn=None) -> jax.Array:
    """DeepSpeed-Ulysses-style SP: all_to_all swaps the sharded dim from
    sequence to heads, runs full-sequence attention locally on h/sp heads,
    and swaps back. Better for moderate sequence lengths; requires
    h % sp == 0. Call inside shard_map with seq sharded over `axis_name`."""
    from ray_tpu.ops.attention import mha_reference

    b, sq, h, d = q.shape
    sp = lax.axis_size(axis_name)
    assert h % sp == 0, f"heads {h} not divisible by sp {sp}"
    hkv = k.shape[2]
    if hkv % sp != 0:
        # The head-axis all_to_all needs sp to divide the kv-head count.
        # Repeat K/V only as much as that requires (the local attention
        # handles any remaining GQA grouping itself); full repeat to h is
        # the fallback when the minimal factor doesn't divide h evenly.
        r = sp // math.gcd(hkv, sp)
        if h % (hkv * r) != 0:
            r = h // hkv
        k = repeat_kv(k, r)
        v = repeat_kv(v, r)

    def to_heads(x):
        # (b, sq_local, h, d) -> (b, sq_global, h/sp, d)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    fn = attn_fn or (lambda a, b_, c: mha_reference(a, b_, c, causal=causal,
                                                    scale=scale))
    out = fn(qh, kh, vh)
    return to_seq(out)
