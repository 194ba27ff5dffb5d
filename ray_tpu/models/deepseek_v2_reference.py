"""Plain reference for DeepSeek-V2 (https://huggingface.co/deepseek-ai/
DeepSeek-V2, `config.json` + `modeling_deepseek.py`): latent attention in the
EXPANDED form (a key and a value a head a context token), YaRN rotary
positions, a leading dense layer, `group_limited_greedy` expert layers with a
shared expert.

Written from the published description in straightforward `jax.numpy`:
float32 activations, `jax.default_matmul_precision("highest")`, no kernel, no
cache, no batching trick, nothing imported from the program or the benchmark
(this file lives twice, as `ray_tpu/models/deepseek_v2_reference.py` for the
tier-1 tests and as `benchmarks/deepseek_v2_reference.py`;
tests/test_llm_deepseek_v2.py holds the two equal). It reads the program's
parameter tree, the same bf16 weights the cell serves, a layer at a time and
an expert at a time, so that no float32 copy of a layer's experts is ever
alive.

`sizes` is the configuration file's keys: the published ones, and
`n_routed_experts` = the experts HELD, `n_routed_experts_published` = the
router's width, `first_held_expert` = the first held published id. The
reference is given the same share as the program: it routes over all
published experts and adds what the held ones contribute plus the shared
expert; what absent experts would add is left out of both.

Departures from the published code, each a relabelling of random weights:
`kv_b_proj` is read split per head (`w_kb (H, nope, lat)`, `w_vb (H, lat,
v)`); the rope dimensions are de-interleaved already (rotate-half pairs i and
i + rope / 2); an expert layer's weights are `params["experts"][layer]`,
stacked `(held, d, f)`.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight


def yarn(sizes: Dict):
    """(inv_freq (rope / 2,), low, high, softmax scale) from `rope_scaling`."""
    rs = sizes["rope_scaling"]
    dim, base = sizes["qk_rope_head_dim"], float(sizes["rope_theta"])
    orig = rs["original_max_position_embeddings"]

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    freq = base ** (-2.0 * i / dim)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = (freq / rs["factor"]) * ramp + freq * (1.0 - ramp)
    m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    qk = sizes["qk_nope_head_dim"] + dim
    return inv_freq.astype(np.float32), low, high, qk ** -0.5 * m * m


def _rotary(x, inv_freq, table_scale):
    """x (b, s, heads, rope), positions 0..s-1, rotate-half."""
    s, half = x.shape[1], x.shape[-1] // 2
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angle) * table_scale)[None, :, None, :]
    sin = (jnp.sin(angle) * table_scale)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, p, *, sizes_key):
    H, nope, rope, lat, eps, inv_freq, table_scale, scale = sizes_key
    inv_freq = jnp.asarray(inv_freq, F32)
    b, s, _ = x.shape
    h = _rms_norm(x, p["attn_norm"], eps)
    q = (_rms_norm(h @ p["wq_a"], p["q_norm"], eps) @ p["wq_b"]).reshape(
        b, s, H, nope + rope)
    q_nope = q[..., :nope]
    q_rope = _rotary(q[..., nope:], inv_freq, table_scale)
    kv = h @ p["wkv_a"]
    c_kv = _rms_norm(kv[..., :lat], p["kv_norm"], eps)
    k_rope = _rotary(kv[..., None, lat:], inv_freq, table_scale)   # one head
    k_nope = jnp.einsum("bsl,hnl->bshn", c_kv, p["w_kb"])
    v = jnp.einsum("bsl,hlv->bshv", c_kv, p["w_vb"])
    scores = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope[:, :, 0])) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhv->bqhv", jax.nn.softmax(scores, -1), v)
    x = x + o.reshape(b, s, -1) @ p["wo"]
    return x, _rms_norm(x, p["mlp_norm"], eps)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _top_mask(values, count: int):
    """True at the `count` largest of each row; ties: the lower index."""
    order = jnp.argsort(-values, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < count


def router_choice(scores, top_k: int, n_group: int, topk_group: int):
    """`group_limited_greedy`: a mask (N, E) of the `top_k` best experts
    inside the `topk_group` best groups (a group's score its best
    expert's)."""
    n, e = scores.shape
    groups = _top_mask(scores.reshape(n, n_group, -1).max(-1), topk_group)
    inside = jnp.repeat(groups, e // n_group, axis=1)
    return _top_mask(jnp.where(inside, scores, -jnp.inf), top_k)


def _sizes_key(sizes: Dict):
    inv_freq, _, _, scale = yarn(sizes)
    rs = sizes["rope_scaling"]
    m = lambda ms: 0.1 * ms * math.log(rs["factor"]) + 1.0
    return (sizes["num_attention_heads"], sizes["qk_nope_head_dim"],
            sizes["qk_rope_head_dim"], sizes["kv_lora_rank"],
            sizes["rms_norm_eps"], tuple(float(f) for f in inv_freq),
            m(rs["mscale"]) / m(rs["mscale_all_dim"]), scale)


def _layer_slice(stacked: Dict, i: int):
    return {k: v[i].astype(F32) for k, v in stacked.items()}


def hidden(params: Dict, tokens, sizes: Dict, routing=None):
    """tokens (b, s) -> (final-norm hidden states (b, s, d) float32, router
    scores (routed layers, b, s, published experts) as numpy). With `routing`
    (routed layers, b, s, top_k published ids) the expert layers take THOSE
    experts, with this reference's own gate values for them."""
    key = _sizes_key(sizes)
    attention = jax.jit(partial(_attention, sizes_key=key))
    swiglu = jax.jit(_swiglu)
    top_k = sizes["num_experts_per_tok"]
    first = sizes["first_held_expert"]
    held = sizes["n_routed_experts"]
    scaling = sizes["routed_scaling_factor"]
    experts = ("w_gate", "w_up", "w_down")
    all_scores = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        b, s, d = x.shape
        dense = params["dense_layers"]
        for i in range(dense["wq_a"].shape[0]):
            p = _layer_slice(dense, i)
            x, h = attention(x, p)
            x = x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
        moe = params["moe_layers"]
        for i in range(moe["wq_a"].shape[0]):
            p = _layer_slice(moe, i)
            x, h = attention(x, p)
            flat = h.reshape(b * s, d)
            scores = jax.nn.softmax(flat @ p["router"], axis=-1)
            all_scores.append(np.asarray(scores).reshape(b, s, -1))
            if routing is None:
                chosen = router_choice(scores, top_k, sizes["n_group"],
                                       sizes["topk_group"])
            else:
                ids = jnp.asarray(routing[i]).reshape(b * s, top_k)
                chosen = jnp.zeros(scores.shape, bool).at[
                    jnp.arange(b * s)[:, None], ids].set(True)
            gates = jnp.where(chosen, scores, 0.0) * scaling
            y = swiglu(flat, p["shared_gate"], p["shared_up"],
                       p["shared_down"])
            for e in range(held):          # one expert's float32 copy alive
                w = [params["experts"][i][name][e].astype(F32)
                     for name in experts]
                y = y + gates[:, first + e, None] * swiglu(flat, *w)
            x = x + y.reshape(b, s, d)
        x = _rms_norm(x, params["final_norm"].astype(F32),
                      sizes["rms_norm_eps"])
    return x, np.stack(all_scores) if all_scores else np.zeros((0, b, s, 0))


def logits_at(params: Dict, tokens, positions, sizes: Dict,
              routing: Optional[np.ndarray] = None):
    """(logits (b, len(positions), vocab), router scores): a full forward
    pass over tokens (b, s), read at `positions`."""
    x, scores = hidden(params, tokens, sizes, routing)
    with jax.default_matmul_precision("highest"):
        return (x[:, jnp.asarray(positions)]
                @ params["lm_head"].astype(F32)), scores


def loss(params: Dict, tokens, sizes: Dict):
    """Mean next-token cross entropy of tokens (b, s+1), differentiable
    with respect to float32 `params` (the router's choice is not)."""
    key = _sizes_key(sizes)
    top_k, first = sizes["num_experts_per_tok"], sizes["first_held_expert"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inputs]
        b, s, d = x.shape
        for name in ("dense_layers", "moe_layers"):
            stacked = params[name]
            for i in range(stacked["wq_a"].shape[0]):
                p = {k: v[i] for k, v in stacked.items()}
                if name == "moe_layers":
                    p.update(params["experts"][i])
                x, h = _attention(x, p, sizes_key=key)
                if name == "dense_layers":
                    x = x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
                    continue
                flat = h.reshape(b * s, d)
                scores = jax.nn.softmax(flat @ p["router"], axis=-1)
                chosen = router_choice(scores, top_k, sizes["n_group"],
                                       sizes["topk_group"])
                gates = (jnp.where(chosen, scores, 0.0)
                         * sizes["routed_scaling_factor"])
                y = _swiglu(flat, p["shared_gate"], p["shared_up"],
                            p["shared_down"])
                for e in range(sizes["n_routed_experts"]):
                    y = y + gates[:, first + e, None] * _swiglu(
                        flat, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
                x = x + y.reshape(b, s, d)
        x = _rms_norm(x, params["final_norm"], sizes["rms_norm_eps"])
        logp = jax.nn.log_softmax(x @ params["lm_head"], -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def loss_and_grad_norm(params: Dict, tokens, sizes: Dict):
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    value, grads = jax.jit(jax.value_and_grad(
        partial(loss, sizes=sizes)))(p32, tokens)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return float(value), float(norm)
