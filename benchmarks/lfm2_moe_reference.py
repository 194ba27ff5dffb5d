"""Plain reference for LFM2-24B-A2B (https://huggingface.co/LiquidAI/
LFM2-24B-A2B, `config.json`, `model_type: lfm2_moe`): gated short-convolution
layers three to one beside grouped-query attention layers with QK-norm and
rotate-half RoPE, leading dense SwiGLU layers, and sigmoid top-k expert layers
with a selection bias and no shared expert.

The forward pass, as published (hidden 2048, 40 layers, vocab 65,536, head
tied to the embedding, RMSNorm eps 1e-5 with a plain gain each, SiLU):

  * `x_0 = E[token]`. Every layer, `u = R_op(x)` (`operator_norm`).
  * A `conv` layer: `[B | C | z] = W_in u` (2048 -> 3 x 2048, cut in that
    order, no bias); `g_t = B_t * z_t`; `c_t = w_0 g_{t-2} + w_1 g_{t-1} +
    w_2 g_t` a channel (depthwise, causal, `conv_L_cache` = 3 taps, g zero
    before position 0, no bias, NO activation); `a_t = W_out (C_t * c_t)`.
  * A `full_attention` layer: `q = W_q u` (32 x 64), `k = W_k u`, `v = W_v u`
    (8 x 64), no bias; `q <- RMSNorm_64(q)`, `k <- RMSNorm_64(k)` a head
    (`q_layernorm`, `k_layernorm`: one gain vector of 64 a layer); q and k
    rotated over the whole head (rotate-half, theta 1e6); `o_i,h = sum_{j <=
    i} softmax_j(q_i,h . k_j,g(h) / sqrt(64)) v_j,g(h)`, `g(h) = h // 4`; `a =
    W_o o`.
  * `x <- x + a`, then `w = R_ffn(x)` (`ffn_norm`). Layers below
    `num_dense_layers` (2): `m = W_2 (silu(W_1 w) * W_3 w)`, 11,776 wide.
    Every later layer: `s = sigmoid(W_r w)` over all 64 experts, `ids` = the 4
    largest of `s + b` (`expert_bias`: it moves the selection and not the
    gates; ties to the lower id), `c_k = s[ids_k] / (sum_k s[ids_k] + 1e-6)`
    (`norm_topk_prob`) x `routed_scaling_factor` 1, `m = sum_k c_k
    Expert_ids_k(w)`, each a SwiGLU 2048 -> 1536 -> 2048. `x <- x + m`.
  * Final RMSNorm (`embedding_norm`), `logits = E x`.

Departures and assumptions (the configuration file lists them under
`assumed`): `head_dim` is hidden / heads; the head is tied; the cut order B,
C, z and the tap order are a permutation of random weights; the conv mixer has
no activation; the QK gains are shared by the heads and stand before the
rotation; rotate-half pairs dimensions (i, i + 32); `expert_bias` is drawn
from the seed, a grid in [0, 0.2) over all experts. The reference is given the
same SHARE of a layer's experts as the program (here: all of them): it routes
over all published experts and adds what the held ones contribute.

Written from that description in straightforward `jax.numpy`: float32
activations, `jax.default_matmul_precision("highest")`, a layer at a time, the
convolution as an explicit sum over three shifted copies of the whole
sequence, attention over the whole sequence with a mask (QUERY_BLOCK queries
at a time), experts one at a time; no kernel, no cache, no slot, no pair form,
nothing imported from the program or the benchmark (this file lives twice, as
`ray_tpu/models/lfm2_moe_reference.py` for the tier-1 tests and as
`benchmarks/lfm2_moe_reference.py`; tests/test_llm_lfm2_moe.py holds the two
equal). It reads the program's parameter tree, the same bf16 weights the cell
serves: `params["layers"][kind]` stacks the layers of one kind ("conv_dense",
"conv_moe", "attn_moe", "attn_dense") in the published order, and
`params["experts"][i]` is the i-th expert layer's held experts, stacked
`(held, d, f)`.

`sizes` is the configuration file's keys: the published ones, `head_dim`, and
`n_routed_experts` = the experts HELD, `num_experts_published` = the router's
width, `first_held_expert` = the first held published id.

`fault` names ONE term changed, for the controls of a check that must fail
(FAULTS; `("tail_zeroed", starts)`: g is taken as zero before every position
of `starts`, what a program that drops the tail at a slice's border computes).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
VOCAB_BLOCK = 16384     # rows of the embedding a block of the head
QUERY_BLOCK = 256       # queries of an attention layer a block
MLP_ROWS = 2048         # rows of a feed-forward a block
GATE_EPS = 1e-6         # what the kept scores' sum takes before it divides
FAULTS = ("taps_reversed", "tail_zeroed", "bc_swapped", "fir_before_gate",
          "no_qk_norm", "no_rotation", "halves_swapped", "bias_in_gates",
          "gates_not_renormalised")


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotate(x, theta):
    """x (b, s, heads, hd) rotated at positions 0..s-1: the whole head, lane
    i with lane i + hd / 2."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = jnp.arange(x.shape[1], dtype=F32)[None, :, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _short_conv(u, p, taps: int, fault=None):
    """The gated short convolution over the normed rows u (b, s, d): the FIR
    as a sum over `taps` shifted copies of the whole sequence."""
    name, starts = fault if isinstance(fault, tuple) else (fault, ())
    b, s, d = u.shape
    bcz = u @ p["in_proj"]
    B, C, z = bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:]
    if name == "bc_swapped":
        B, C = C, B
    w = p["conv_w"][::-1] if name == "taps_reversed" else p["conv_w"]
    g = z if name == "fir_before_gate" else B * z
    t = np.arange(s)
    c = jnp.zeros_like(g)
    for j in range(taps):
        shift = taps - 1 - j        # w[j] meets g_{t - shift}
        moved = jnp.pad(g, ((0, 0), (shift, 0), (0, 0)))[:, :s]
        if name == "tail_zeroed" and shift:
            # position t - shift lies before a start that t has reached
            lost = np.zeros(s, bool)
            for at in starts:
                lost |= (t >= at) & (t - shift < at)
            moved = jnp.where(jnp.asarray(lost)[None, :, None], 0.0, moved)
        c = c + w[j] * moved
    if name == "fir_before_gate":
        c = B * c
    return (C * c) @ p["out_proj"]


@jax.jit
def _queries(q, k, v, q0):
    """Queries [q0, q0 + Q) of one layer: q (b, Q, H, hd) against the whole
    k, v (b, s, H, hd), a kv head repeated to its query heads."""
    hd = q.shape[-1]
    i = q0 + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    scores = jnp.einsum("bqhd,bjhd->bqhj", q, k) / math.sqrt(hd)
    probs = jax.nn.softmax(
        jnp.where((j <= i)[None, :, None, :], scores, -jnp.inf), -1)
    return jnp.einsum("bqhj,bjhd->bqhd", probs, v)


def _attention(u, p, sizes: Dict, fault=None):
    """Grouped-query attention over the normed rows u (b, s, d)."""
    H, K, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                sizes["head_dim"])
    eps = sizes["norm_eps"]
    b, s, _ = u.shape
    q = (u @ p["wq"]).reshape(b, s, H, hd)
    k = (u @ p["wk"]).reshape(b, s, K, hd)
    v = (u @ p["wv"]).reshape(b, s, K, hd)
    if fault != "no_qk_norm":
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    if fault != "no_rotation":
        theta = float(sizes["rope_parameters"]["rope_theta"])
        q, k = _rotate(q, theta), _rotate(k, theta)
    of = np.arange(H) // (H // K)               # g(h)
    if fault == "halves_swapped":               # the pair's other kv head
        of = of ^ 1
    k, v = k[:, :, of], v[:, :, of]
    Q = min(QUERY_BLOCK, s)
    pad = -s % Q
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    o = jnp.concatenate([_queries(qp[:, a:a + Q], k, v, a)
                         for a in range(0, s + pad, Q)], 1)[:, :s]
    return o.reshape(b, s, H * hd) @ p["wo"]


@jax.jit
def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
            ) @ down.astype(F32)


def _mlp(h, gate, up, down):
    """(N, d) rows, MLP_ROWS at a time."""
    return jnp.concatenate([_swiglu(h[a:a + MLP_ROWS], gate, up, down)
                            for a in range(0, h.shape[0], MLP_ROWS)], 0)


def _top_mask(values, count: int):
    """True at the `count` largest of each row; ties: the lower index."""
    order = jnp.argsort(-values, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < count


def routed_ffn(flat, p, experts, sizes: Dict, kept=None, fault=None):
    """An expert layer's feed-forward over rows `flat` (N, d): -> (what the
    HELD experts add (N, d), the selection scores s + b (N, published
    experts)). With `kept` (N, top_k published ids) the layer takes THOSE
    experts, with this reference's own gates for them. One expert's weights
    are alive at a time."""
    top_k = sizes["num_experts_per_tok"]
    first, held = sizes["first_held_expert"], sizes["n_routed_experts"]
    s = jax.nn.sigmoid(flat @ p["router"])
    choice = s + p["router_bias"]
    if kept is None:
        chosen = _top_mask(choice, top_k)
    else:
        chosen = jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], kept].set(True)
    gates = jnp.where(chosen, choice if fault == "bias_in_gates" else s, 0.0)
    if fault != "gates_not_renormalised":
        gates = gates / (gates.sum(-1, keepdims=True) + GATE_EPS)
    gates = gates * sizes["routed_scaling_factor"]
    y = jnp.zeros_like(flat)
    for e in range(held):
        y = y + gates[:, first + e, None] * _mlp(
            flat, *(experts[name][e] for name in ("w_gate", "w_up",
                                                  "w_down")))
    return y, choice


def layer_plan(sizes: Dict):
    """[(kind, index in that kind's stack)] in the published order."""
    plan, seen = [], {}
    for li, name in enumerate(sizes["layer_types"]):
        kind = (("conv" if name == "conv" else "attn")
                + ("_dense" if li < sizes["num_dense_layers"] else "_moe"))
        plan.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return plan


def hidden(params: Dict, tokens, sizes: Dict, kept=None, fault=None,
           watch=None):
    """tokens (b, s) -> (final-norm hidden states (b, s, d) float32, {
    "scores": the selection scores s + b (routed layers, b, s, published
    experts), "mixed": every layer's mixer output a (what the conv or the
    attention adds to the stream) at positions `watch` (layers, b,
    len(watch), d)} as numpy). `kept` (routed layers, b, s, top_k): the
    experts to take."""
    eps = sizes["norm_eps"]
    all_scores, mixed, routed = [], [], 0
    at = jnp.asarray([] if watch is None else watch, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        b, s, d = x.shape
        for kind, i in layer_plan(sizes):
            p = {k: v[i] if k.startswith("w_") else v[i].astype(F32)
                 for k, v in params["layers"][kind].items()}
            u = _rms(x, p["operator_norm"], eps)
            a = (_short_conv(u, p, sizes["conv_L_cache"], fault)
                 if kind.startswith("conv") else
                 _attention(u, p, sizes, fault))
            mixed.append(np.asarray(a[:, at]))
            x = x + a
            flat = _rms(x, p["ffn_norm"], eps).reshape(b * s, d)
            if kind.endswith("_dense"):
                m = _mlp(flat, p["w_gate"], p["w_up"], p["w_down"])
            else:
                ids = None if kept is None else jnp.asarray(
                    kept[routed]).reshape(b * s, -1)
                m, choice = routed_ffn(flat, p, params["experts"][routed],
                                       sizes, ids, fault)
                all_scores.append(np.asarray(choice).reshape(b, s, -1))
                routed += 1
            x = x + m.reshape(b, s, d)
        x = _rms(x, params["final_norm"].astype(F32), eps)
    return x, {"scores": (np.stack(all_scores) if all_scores
                          else np.zeros((0, b, s, 0))),
               "mixed": np.stack(mixed)}


def logits_at(params: Dict, tokens, positions, sizes: Dict,
              kept: Optional[np.ndarray] = None, fault=None, watch=None):
    """(logits (b, len(positions), vocab) float32, what `hidden` found): a
    full forward pass over tokens (b, s), read at `positions`; the mixers'
    outputs at `watch` (`positions` where None). The head is the embedding."""
    x, found = hidden(params, tokens, sizes, kept, fault,
                      positions if watch is None else watch)
    x = x[:, jnp.asarray(positions)]
    embed = params["embed"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [x @ embed[lo:lo + VOCAB_BLOCK].astype(F32).T
             for lo in range(0, embed.shape[0], VOCAB_BLOCK)], -1), found


def loss(params: Dict, tokens, sizes: Dict):
    """Mean next-token cross entropy of tokens (b, s+1), differentiable with
    respect to float32 `params` (the router's choice is not)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    eps = sizes["norm_eps"]
    routed = 0
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inputs]
        b, s, d = x.shape
        for kind, i in layer_plan(sizes):
            p = {k: v[i] for k, v in params["layers"][kind].items()}
            u = _rms(x, p["operator_norm"], eps)
            x = x + (_short_conv(u, p, sizes["conv_L_cache"])
                     if kind.startswith("conv") else _attention(u, p, sizes))
            flat = _rms(x, p["ffn_norm"], eps).reshape(b * s, d)
            if kind.endswith("_dense"):
                m = _mlp(flat, p["w_gate"], p["w_up"], p["w_down"])
            else:
                m, _ = routed_ffn(flat, p, params["experts"][routed], sizes)
                routed += 1
            x = x + m.reshape(b, s, d)
        x = _rms(x, params["final_norm"], eps)
        logp = jax.nn.log_softmax(x @ params["embed"].T, -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def loss_and_grad_norm(params: Dict, tokens, sizes: Dict):
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    value, grads = jax.value_and_grad(partial(loss, sizes=sizes))(p32, tokens)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return float(value), float(norm)
