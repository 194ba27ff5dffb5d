"""Plain reference for the Llama-shaped decoder family (Mistral, Yi, ...):
pre-norm RMSNorm, rotary positions (rotate-half, as the published Hugging Face
implementations have it), grouped-query causal attention, SwiGLU, untied head.

Written from the published description in straightforward `jax.numpy`:
float32 activations, `jax.default_matmul_precision("highest")` (on a TPU a
float32 matmul otherwise runs in bf16 passes), no kernels, no cache, no
batching tricks, nothing imported from the program. It reads the program's
parameter tree (the same bf16 weights the cell serves or trains), one layer at
a time, so that a 16-layer model's float32 copy never exists at once.

`sizes` is the configuration file's published keys: `hidden_size`,
`num_attention_heads`, `num_key_value_heads`, `head_dim`, `rms_norm_eps`,
`rope_theta`.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def _rotary(x, theta):
    """x: (b, s, heads, head_dim), positions 0..s-1."""
    s, hd = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, p, *, n_heads, n_kv, head_dim, eps, theta):
    b, s, _ = x.shape
    p = jax.tree.map(lambda a: a.astype(F32), p)
    h = _rms_norm(x, p["attn_norm"], eps)
    q = _rotary((h @ p["wq"]).reshape(b, s, n_heads, head_dim), theta)
    k = _rotary((h @ p["wk"]).reshape(b, s, n_kv, head_dim), theta)
    v = (h @ p["wv"]).reshape(b, s, n_kv, head_dim)
    group = n_heads // n_kv
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(head_dim))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + attn.reshape(b, s, n_heads * head_dim) @ p["wo"]
    h = _rms_norm(x, p["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ p["w_gate"]) * (h @ p["w_up"])) @ p["w_down"]


def _layer_kwargs(sizes: Dict) -> Dict:
    return dict(n_heads=sizes["num_attention_heads"],
                n_kv=sizes["num_key_value_heads"],
                head_dim=sizes["head_dim"], eps=sizes["rms_norm_eps"],
                theta=sizes["rope_theta"])


def hidden(params: Dict, tokens, sizes: Dict):
    """tokens (b, s) -> final-norm hidden states (b, s, d), float32; the
    layers in a Python loop, one jitted layer each."""
    layer = jax.jit(partial(_layer, **_layer_kwargs(sizes)))
    n_layers = params["layers"]["wq"].shape[0]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        for i in range(n_layers):
            x = layer(x, jax.tree.map(lambda a: a[i], params["layers"]))
        return _rms_norm(x, params["final_norm"].astype(F32),
                         sizes["rms_norm_eps"])


def logits_at(params: Dict, tokens, positions, sizes: Dict):
    """Logits (b, len(positions), vocab) at the given positions of a full
    forward pass over tokens (b, s)."""
    x = hidden(params, tokens, sizes)[:, jnp.asarray(positions)]
    with jax.default_matmul_precision("highest"):
        return x @ params["lm_head"].astype(F32)


def loss(params: Dict, tokens, sizes: Dict):
    """Mean next-token cross entropy of tokens (b, s+1); differentiable with
    respect to `params` (give it float32 parameters for `jax.grad`)."""
    kw = _layer_kwargs(sizes)
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[inputs]
        for i in range(params["layers"]["wq"].shape[0]):
            x = _layer(x, jax.tree.map(lambda a: a[i], params["layers"]),
                       **kw)
        x = _rms_norm(x, params["final_norm"].astype(F32), kw["eps"])
        logp = jax.nn.log_softmax(x @ params["lm_head"].astype(F32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def loss_and_grad_norm(params: Dict, tokens, sizes: Dict):
    """The reference's loss and the global L2 norm of its `jax.grad` with
    respect to a float32 copy of the parameters."""
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    value, grads = jax.jit(jax.value_and_grad(
        partial(loss, sizes=sizes)))(p32, tokens)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return float(value), float(norm)
