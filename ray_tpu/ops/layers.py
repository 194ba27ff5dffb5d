"""Elementwise / normalization / rotary ops.

Plain jnp: XLA fuses these into surrounding matmuls on TPU; dedicated pallas
kernels only pay off for the attention inner loop (see ops/attention.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)).astype(dtype)


def rope_frequencies(head_dim: int, max_seq: int, theta: float = 500000.0):
    """Precompute RoPE cos/sin tables: (max_seq, head_dim//2), float32."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array,
               positions: jax.Array | None = None) -> jax.Array:
    """x: (..., seq, heads, head_dim). cos/sin: (max_seq, head_dim//2).
    positions: (..., seq) absolute positions; default arange."""
    seq = x.shape[-3]
    if positions is None:
        c = cos[:seq]
        s = sin[:seq]
        c = c[None, :, None, :]
        s = s[None, :, None, :]
    else:
        c = cos[positions][..., :, None, :]
        s = sin[positions][..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def layer_norm(x: jax.Array, weight: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    """LayerNorm with weight and bias over the last axis, float32 inside."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    centred = x32 - mean
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    normed = centred * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)
