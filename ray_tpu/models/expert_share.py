"""What blocks that hold a SHARE of a layer's experts have in common
(models/deepseek_v2.py, models/mimo_v2_flash.py): the held experts' part of a
routed feed-forward, and the products that keep a float32 operand whole.

A deployment splits a layer's experts over chips. A program holds the experts
`config.experts_held = (first, stop)` (published ids) and the router at its
published width: it routes every token over all experts, computes what ITS
experts contribute, and leaves out what absent experts would add (their
chips' partial results, summed by an exchange this repo does not have yet:
ROADMAP). No token is dropped and no capacity is set.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.layers import swiglu


def held_expert_ffn(config, x, ids, gates, valid, lp):
    """What the HELD experts (`config.experts_held`, `config.n_held`)
    contribute to rows `x` (N, d) routed to `ids` with `gates`: the pairs
    that hit a held expert sorted by expert, one ragged product a
    projection; pairs of absent experts (and of padding rows, `valid` False)
    ride behind the last group with gate 0. Returns (y (N, d) float32, rows
    computed, the busiest held expert's rows)."""
    n, k = ids.shape
    first, n_held = config.experts_held[0], config.n_held
    local = ids.reshape(-1) - first
    held = (local >= 0) & (local < n_held) & jnp.repeat(valid, k)
    local = jnp.where(held, local, n_held)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=n_held + 1)[:n_held].astype(jnp.int32)
    xs = x[order // k]                                          # (N k, d)
    y = _ffn(lambda a, w: jax.lax.ragged_dot(
        a, w, sizes, preferred_element_type=jnp.float32),
        xs, lp["w_gate"], lp["w_up"], lp["w_down"])
    gate = jnp.where(held, gates.reshape(-1), 0.0)[order]
    y = jnp.where(gate[:, None] != 0.0, y * gate[:, None], 0.0)
    y = y[jnp.argsort(order)].reshape(n, k, -1).sum(axis=1)
    return y, sizes.sum(), sizes.max()


# ---- products that keep a float32 operand whole (why: deepseek_v2.py,
# "precision") ---------------------------------------------------------------

def _wide(dot, h, w):
    """dot(h, w) -> float32 with float32 h kept whole: as its bf16 rounding
    plus the bf16 rounding of what that lost, two passes over bf16 weights
    (which have no low part of their own)."""
    if w.dtype != jnp.bfloat16:
        return dot(h, w)
    hi = h.astype(jnp.bfloat16)
    lo = (h - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return dot(hi, w) + dot(lo, w)


def _ffn(dot, h, gate, up, down):
    """SwiGLU with `dot(a, w) -> float32`: -> float32."""
    hidden = swiglu(dot(h, gate), dot(h, up)).astype(h.dtype)
    return dot(hidden, down)


def _dot32(a, w):
    return jnp.matmul(a, w, preferred_element_type=jnp.float32)
