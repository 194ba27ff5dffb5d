"""Plain reference for MiMo-V2-Flash (https://huggingface.co/XiaomiMiMo/
MiMo-V2-Flash, `config.json`): full and window attention layers mixed, a
leading dense layer, sigmoid top-k expert layers.

The forward pass, as published (hidden 4096, 48 layers, vocab 152,576, untied
head, RMSNorm eps 1e-5, SiLU; pre-norm residual decoder, final norm):

  * Attention, both kinds. H = 64 query heads, qk width 192, v width 128, K
    kv heads. `q = W_q h` (4096 -> 64 x 192), `k = W_k h` (4096 -> K x 192),
    `v = attention_value_scale x W_v h` (4096 -> K x 128, scale 0.707), `o =
    W_o concat_h(o_h)` (64 x 128 -> 4096); no bias, no q/k norm. Rope on the
    first int(192 x partial_rotary_factor 0.334) = 64 dimensions of every q
    and k head, the other 128 unrotated. Scores `s_ij = q_i . k_j /
    sqrt(192)`; head h reads kv head h // (H / K).
  * Full layer (`hybrid_layer_pattern` 0): K = 4 (`num_key_value_heads`),
    `rope_theta` 5e6, causal, plain softmax (`add_full_attention_sink_bias`
    false).
  * Window layer (1): K = 8 (`swa_num_key_value_heads`), `swa_rope_theta`
    1e4, token i sees j with 0 <= i - j < 128 (`sliding_window`), and a
    learned logit `b_h` a head (`add_swa_attention_sink_bias`) joins the
    softmax's denominator and carries no value: `o_i = sum_j exp(s_ij - m)
    v_j / (sum_j exp(s_ij - m) + exp(b_h - m))`.
  * Layer 0 (`moe_layer_freq` 0): SwiGLU of width 16384. Every later layer:
    router `s = sigmoid(W_r h)` over 256 experts (`scoring_func` sigmoid),
    selection of the top 8 by `s + e` (`topk_method` noaux_tc, `e` the
    correction bias, one number an expert; `n_group` = `topk_group` = 1: no
    groups), gates `g_k = s_k / sum_kept s` (`norm_topk_prob`; the sum is
    over all 8 kept, held here or not), no scaling factor, no shared expert;
    each expert a SwiGLU of width 2048.

Departures and assumptions (the configuration file lists them under
`assumed`): rope pairs dimensions (i, i + 32) of the rotated 64 (rotate-half);
the window's closed end (i - j < 128 counts j = i) is the family's
convention; `attention_chunk_size` 128 names no term of the forward pass; the
value scale is applied to v before it is cached; from the seed `b_h` is
drawn N(3, 1) and `e` uniform in [0, 0.2), the same values dealt to every
share of experts. Left out: the three multi-token-prediction layers of the
published model (no key of `config.json` describes them) and the vision and
audio encoders of V2.5.

Written from that description in straightforward `jax.numpy`: float32
activations, `jax.default_matmul_precision("highest")`, no kernel, no cache,
no batching trick, nothing imported from the program or the benchmark (this
file lives twice, as `ray_tpu/models/mimo_v2_flash_reference.py` for the
tier-1 tests and as `benchmarks/mimo_v2_flash_reference.py`;
tests/test_llm_mimo_v2_flash.py holds the two equal). It reads the program's
parameter tree, the same bf16 weights the cell serves, a layer at a time and
an expert at a time: `params["layers"][kind]` stacks the layers of one kind
("full_dense", "window_moe", "full_moe") in the published order, and
`params["experts"][i]` is the i-th expert layer's held experts, stacked
`(held, d, f)`.

`sizes` is the configuration file's keys: the published ones, and
`n_routed_experts` = the experts HELD, `n_routed_experts_published` = the
router's width, `first_held_expert` = the first held published id. The
reference is given the same share as the program: it routes over all
published experts and adds what the held ones contribute; what absent experts
would add is left out of both.
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


def _rotary(x, theta: float, rot: int):
    """x (b, s, heads, width), positions 0..s-1: the first `rot` dimensions
    rotated as rotate-half pairs (i, i + rot / 2), the rest as they are."""
    s = x.shape[1]
    inv_freq = theta ** (-jnp.arange(0, rot, 2, dtype=F32) / rot)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


@partial(jax.jit, static_argnames="key")
def _attention(x, p, *, key):
    """One attention sub-layer and the norm before the feed-forward: -> (x,
    h). `key` = (H, K, qk, v, rot, theta, value scale, window | None, eps);
    `p["sink"]` (H,) where the layer has a sink."""
    H, K, qk, vd, rot, theta, v_scale, window, eps = key
    b, s, _ = x.shape
    h = _rms_norm(x, p["attn_norm"], eps)
    q = _rotary((h @ p["wq"]).reshape(b, s, H, qk), theta, rot)
    k = _rotary((h @ p["wk"]).reshape(b, s, K, qk), theta, rot)
    v = (v_scale * (h @ p["wv"])).reshape(b, s, K, vd)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(qk)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    scores = jnp.where(seen[None, None], scores, -jnp.inf)
    if "sink" in p:
        column = jnp.broadcast_to(p["sink"][None, :, None, None],
                                  (b, H, s, 1))
        probs = jax.nn.softmax(jnp.concatenate([scores, column], -1),
                               -1)[..., :-1]
    else:
        probs = jax.nn.softmax(scores, -1)
    o = jnp.einsum("bhqk,bkhv->bqhv", probs, v)
    x = x + o.reshape(b, s, -1) @ p["wo"]
    return x, _rms_norm(x, p["mlp_norm"], eps)


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _top_mask(values, count: int):
    """True at the `count` largest of each row; ties: the lower index."""
    order = jnp.argsort(-values, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < count


def layer_plan(sizes: Dict):
    """[(kind, index in that kind's stack, attention key)] in the published
    order."""
    rot = int(sizes["head_dim"] * sizes["partial_rotary_factor"])
    plan, seen = [], {}
    for window, moe in zip(sizes["hybrid_layer_pattern"],
                           sizes["moe_layer_freq"]):
        kind = ("window" if window else "full") + ("_moe" if moe
                                                   else "_dense")
        key = (sizes["num_attention_heads"],
               sizes["swa_num_key_value_heads"] if window
               else sizes["num_key_value_heads"],
               sizes["head_dim"], sizes["v_head_dim"], rot,
               float(sizes["swa_rope_theta"] if window
                     else sizes["rope_theta"]),
               float(sizes["attention_value_scale"]),
               sizes["sliding_window"] if window else None,
               float(sizes["layernorm_epsilon"]))
        plan.append((kind, seen.get(kind, 0), key))
        seen[kind] = seen.get(kind, 0) + 1
    return plan


def _routed(flat, p, experts, sizes: Dict, kept=None, cast=None):
    """An expert layer's feed-forward over rows `flat` (N, d): -> (y, the
    selection scores s + e (N, published experts)). With `kept` (N, top_k
    published ids) the layer takes THOSE experts, with this reference's own
    gates for them. One expert's weights are alive at a time."""
    top_k = sizes["num_experts_per_tok"]
    first, held = sizes["first_held_expert"], sizes["n_routed_experts"]
    s = jax.nn.sigmoid(flat @ p["router"])
    choice = s + p["router_bias"]
    if kept is None:
        chosen = _top_mask(choice, top_k)
    else:
        chosen = jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], kept].set(True)
    gates = jnp.where(chosen, s, 0.0)
    gates = gates / gates.sum(-1, keepdims=True)
    y = jnp.zeros_like(flat)
    for e in range(held):
        w = [experts[name][e] if cast is None else cast(experts[name][e])
             for name in ("w_gate", "w_up", "w_down")]
        y = y + gates[:, first + e, None] * _swiglu(flat, *w)
    return y, choice


def hidden(params: Dict, tokens, sizes: Dict, kept=None):
    """tokens (b, s) -> (final-norm hidden states (b, s, d) float32, the
    selection scores s + e (routed layers, b, s, published experts) as
    numpy). `kept` (routed layers, b, s, top_k): the experts to take."""
    to32 = lambda a: a.astype(F32)
    swiglu = jax.jit(_swiglu)
    all_scores, routed = [], 0
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        b, s, d = x.shape
        for kind, i, key in layer_plan(sizes):
            p = {k: to32(v[i]) for k, v in params["layers"][kind].items()}
            x, h = _attention(x, p, key=key)
            if kind.endswith("_dense"):
                x = x + swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
                continue
            flat = h.reshape(b * s, d)
            ids = None if kept is None else jnp.asarray(
                kept[routed]).reshape(b * s, -1)
            y, choice = _routed(flat, p, params["experts"][routed], sizes,
                                ids, cast=to32)
            all_scores.append(np.asarray(choice).reshape(b, s, -1))
            routed += 1
            x = x + y.reshape(b, s, d)
        x = _rms_norm(x, params["final_norm"].astype(F32),
                      sizes["layernorm_epsilon"])
    return x, np.stack(all_scores) if all_scores else np.zeros((0, b, s, 0))


def logits_at(params: Dict, tokens, positions, sizes: Dict,
              kept: Optional[np.ndarray] = None):
    """(logits (b, len(positions), vocab), selection scores): a full forward
    pass over tokens (b, s), read at `positions`."""
    x, scores = hidden(params, tokens, sizes, kept)
    with jax.default_matmul_precision("highest"):
        return (x[:, jnp.asarray(positions)]
                @ params["lm_head"].astype(F32)), scores


def loss(params: Dict, tokens, sizes: Dict):
    """Mean next-token cross entropy of tokens (b, s+1), differentiable with
    respect to float32 `params` (the router's choice is not)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    routed = 0
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inputs]
        b, s, d = x.shape
        for kind, i, key in layer_plan(sizes):
            p = {k: v[i] for k, v in params["layers"][kind].items()}
            x, h = _attention(x, p, key=key)
            if kind.endswith("_dense"):
                x = x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
                continue
            y, _ = _routed(h.reshape(b * s, d), p, params["experts"][routed],
                           sizes)
            routed += 1
            x = x + y.reshape(b, s, d)
        x = _rms_norm(x, params["final_norm"], sizes["layernorm_epsilon"])
        logp = jax.nn.log_softmax(x @ params["lm_head"], -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def loss_and_grad_norm(params: Dict, tokens, sizes: Dict):
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    value, grads = jax.jit(jax.value_and_grad(
        partial(loss, sizes=sizes)))(p32, tokens)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return float(value), float(norm)
