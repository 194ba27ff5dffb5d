"""Plain reference for Trinity-Large-Preview (https://huggingface.co/arcee-ai/
Trinity-Large-Preview, `config.json`, `model_type: afmoe`): gated
grouped-query attention with QK-norm under sandwich norms, rotated window
layers three to one beside unrotated full layers, leading dense layers, and
sigmoid top-k expert layers beside one shared expert.

The forward pass, as published (hidden 3072, 60 layers, vocab 200,192, untied
head, RMSNorm eps 1e-5 with a gain each, SiLU):

  * `x_0 = sqrt(3072) E[token]` (`mup_enabled`).
  * Every layer, `u = R_in(x)`: `q = W_q u` (48 x 128), `k = W_k u`, `v = W_v
    u` (8 x 128), `z = W_g u` (48 x 128), no bias. `q <- RMSNorm_128(q)`, `k
    <- RMSNorm_128(k)` a head (`q_norm`, `k_norm`: one gain vector of 128 a
    layer). A `sliding_attention` layer rotates q and k over the whole head
    (rotate-half, theta 10,000) and token i sees j with 0 <= i - j < 4,096;
    a `full_attention` layer rotates NOTHING and sees every j <= i. `o_i,h =
    sum_j softmax_j(q_i,h . k_j,g(h) / sqrt(128)) v_j,g(h)`, `g(h) = h // 6`;
    `o <- o * sigmoid(z)`; `a = W_o o`; `x <- x + R_post_attn(a)`.
  * `w = R_pre_mlp(x)`. Layers below `num_dense_layers` (6): `m = W_d
    (silu(W_g w) * W_u w)`, 12,288 wide. Every later layer: `s = sigmoid(W_r
    w)` over all 256 experts, `ids` = the 4 largest of `s + b`
    (`expert_bias`: it moves the selection and not the gates; ties to the
    lower id), `c_k = 2.448 x s[ids_k] / (sum_k s[ids_k] + 1e-20)`
    (`route_norm`, `route_scale`), `m = sum_k c_k Expert_ids_k(w) +
    Shared(w)`, each a SwiGLU 3072 -> 3072 -> 3072. `x <- x + R_post_mlp(m)`.
  * Final RMSNorm, `logits = W_head x`.

Departures and assumptions (the configuration file lists them under
`assumed`): the window counts the token itself; rotate-half pairs dimensions
(i, i + 64); the gate reads the normed input and multiplies before `W_o`;
`expert_bias` is drawn from the seed, a grid in [0, 0.2) dealt to every share
of experts alike; "depth-scaled" names how the post-norm gains are
initialised, `load_balance_coeff`, `use_grouped_mm` and the group keys (all
1) name no term of the forward pass. The reference is given the same SHARE
of a layer's experts as the program: it routes over all published experts and
adds what the held ones contribute and the shared expert; what absent experts
would add is left out of both, and `R_post_mlp` of that partial sum goes on.

Written from that description in straightforward `jax.numpy`: float32
activations, `jax.default_matmul_precision("highest")`, attention a block of
QUERY_BLOCK queries at a time and the feed-forward MLP_ROWS positions at a
time so that 8k tokens fit, no kernel, no cache, nothing imported from the
program or the benchmark (this file lives twice, as `ray_tpu/models/
afmoe_reference.py` for the tier-1 tests and as `benchmarks/
afmoe_reference.py`; tests/test_llm_afmoe.py holds the two equal). It reads
the program's parameter tree, the same bf16 weights the cell serves, a layer
at a time and an expert at a time: `params["layers"][kind]` stacks the layers
of one kind ("window_dense", "window_moe", "full_moe", "full_dense") in the
published order, and `params["experts"][i]` is the i-th expert layer's held
experts, stacked `(held, d, f)`.

`sizes` is the configuration file's keys: the published ones, and
`n_routed_experts` = the experts HELD, `num_experts_published` = the router's
width, `first_held_expert` = the first held published id.

`fault` names ONE term left out or put in, for the controls of a check that
must fail (FAULTS).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
VOCAB_BLOCK = 16384     # columns of the head a block
QUERY_BLOCK = 256       # queries of an attention layer a block
MLP_ROWS = 2048         # rows of a feed-forward a block
ROUTE_NORM_EPS = 1e-20
FAULTS = ("no_window", "full_rotated", "no_gate", "no_post_mlp_norm",
          "no_bias", "no_route_scale")


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


@partial(jax.jit, static_argnames=("window",))
def _queries(q, k, v, q0, *, window):
    """Queries [q0, q0 + Q) of one layer: q (b, Q, K, G, hd) against the
    whole k, v (b, s, K, hd). -> o (b, Q, K, G, hd)."""
    hd = q.shape[-1]
    i = q0 + jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    scores = jnp.einsum("bqkgd,bjkd->bqkgj", q, k) / math.sqrt(hd)
    probs = jax.nn.softmax(
        jnp.where(seen[None, :, None, None, :], scores, -jnp.inf), -1)
    return jnp.einsum("bqkgj,bjkd->bqkgd", probs, v)


def _attention(u, p, sizes: Dict, window: bool, fault=None):
    """What one attention sublayer makes of the normed rows u (b, s, d),
    BEFORE `R_post_attn`. -> (a (b, s, d), the attention's output before its
    gate (b, s, H hd))."""
    H, K, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                sizes["head_dim"])
    eps = sizes["rms_norm_eps"]
    b, s, _ = u.shape
    q = _rms((u @ p["wq"]).reshape(b, s, H, hd), p["q_norm"], eps)
    k = _rms((u @ p["wk"]).reshape(b, s, K, hd), p["k_norm"], eps)
    v = (u @ p["wv"]).reshape(b, s, K, hd)
    if window or fault == "full_rotated":
        theta = float(sizes["rope_theta"])
        q, k = _rotate(q, theta), _rotate(k, theta)
    q = q.reshape(b, s, K, H // K, hd)
    span = (sizes["sliding_window"]
            if window and fault != "no_window" else None)
    Q = min(QUERY_BLOCK, s)
    pad = -s % Q
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
    o = jnp.concatenate([_queries(qp[:, a:a + Q], k, v, a, window=span)
                         for a in range(0, s + pad, Q)], 1)[:, :s]
    o = o.reshape(b, s, H * hd)
    gated = o if fault == "no_gate" else o * jax.nn.sigmoid(u @ p["wg"])
    return gated @ p["wo"], o


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
    """An expert layer's feed-forward over rows `flat` (N, d), BEFORE
    `R_post_mlp`: -> (what the HELD experts add (N, d), what the shared
    expert adds (N, d), the selection scores s + b (N, published experts)).
    With `kept` (N, top_k published ids) the layer takes THOSE experts, with
    this reference's own gates for them. One expert's weights are alive at a
    time."""
    top_k = sizes["num_experts_per_tok"]
    first, held = sizes["first_held_expert"], sizes["n_routed_experts"]
    s = jax.nn.sigmoid(flat @ p["router"])
    choice = s if fault == "no_bias" else s + p["router_bias"]
    if kept is None:
        chosen = _top_mask(choice, top_k)
    else:
        chosen = jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], kept].set(True)
    gates = jnp.where(chosen, s, 0.0)
    gates = gates / (gates.sum(-1, keepdims=True) + ROUTE_NORM_EPS)
    if fault != "no_route_scale":
        gates = gates * sizes["route_scale"]
    y = jnp.zeros_like(flat)
    for e in range(held):
        y = y + gates[:, first + e, None] * _mlp(
            flat, *(experts[name][e] for name in ("w_gate", "w_up",
                                                  "w_down")))
    shared = _mlp(flat, p["shared_gate"], p["shared_up"], p["shared_down"])
    return y, shared, choice


def layer_plan(sizes: Dict):
    """[(kind, index in that kind's stack)] in the published order."""
    plan, seen = [], {}
    for li, name in enumerate(sizes["layer_types"]):
        kind = (("window" if name == "sliding_attention" else "full")
                + ("_dense" if li < sizes["num_dense_layers"] else "_moe"))
        plan.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return plan


def hidden(params: Dict, tokens, sizes: Dict, kept=None, fault=None,
           watch=None):
    """tokens (b, s) -> (final-norm hidden states (b, s, d) float32, {
    "scores": the selection scores s + b (routed layers, b, s, published
    experts), "attended": every layer's attention output before its gate at
    positions `watch` (layers, b, len(watch), H hd)} as numpy). `kept`
    (routed layers, b, s, top_k): the experts to take."""
    eps = sizes["rms_norm_eps"]
    all_scores, attended, routed = [], [], 0
    at = jnp.asarray([] if watch is None else watch, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        if sizes.get("mup_enabled"):
            x = x * math.sqrt(sizes["hidden_size"])
        b, s, d = x.shape
        for kind, i in layer_plan(sizes):
            p = {k: v[i] if k.startswith(("w_", "shared_")) else
                 v[i].astype(F32) for k, v in params["layers"][kind].items()}
            a, o = _attention(_rms(x, p["attn_norm"], eps), p, sizes,
                              kind.startswith("window"), fault)
            attended.append(np.asarray(o[:, at]))
            x = x + _rms(a, p["post_attn_norm"], eps)
            flat = _rms(x, p["mlp_norm"], eps).reshape(b * s, d)
            if kind.endswith("_dense"):
                m = _mlp(flat, p["w_gate"], p["w_up"], p["w_down"])
            else:
                ids = None if kept is None else jnp.asarray(
                    kept[routed]).reshape(b * s, -1)
                y, shared, choice = routed_ffn(
                    flat, p, params["experts"][routed], sizes, ids, fault)
                m = y + shared
                all_scores.append(np.asarray(choice).reshape(b, s, -1))
                routed += 1
            m = m.reshape(b, s, d)
            x = x + (m if fault == "no_post_mlp_norm"
                     else _rms(m, p["post_mlp_norm"], eps))
        x = _rms(x, params["final_norm"].astype(F32), eps)
    return x, {"scores": (np.stack(all_scores) if all_scores
                          else np.zeros((0, b, s, 0))),
               "attended": np.stack(attended)}


def logits_at(params: Dict, tokens, positions, sizes: Dict,
              kept: Optional[np.ndarray] = None, fault=None, watch=None):
    """(logits (b, len(positions), vocab) float32, what `hidden` found): a
    full forward pass over tokens (b, s), read at `positions`; the attention
    outputs at `watch` (`positions` where None)."""
    x, found = hidden(params, tokens, sizes, kept, fault,
                      positions if watch is None else watch)
    x = x[:, jnp.asarray(positions)]
    head = params["lm_head"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [x @ head[:, lo:lo + VOCAB_BLOCK].astype(F32)
             for lo in range(0, head.shape[1], VOCAB_BLOCK)], -1), found


def loss(params: Dict, tokens, sizes: Dict):
    """Mean next-token cross entropy of tokens (b, s+1), differentiable with
    respect to float32 `params` (the router's choice is not)."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    eps = sizes["rms_norm_eps"]
    routed = 0
    with jax.default_matmul_precision("highest"):
        x = params["embed"][inputs]
        if sizes.get("mup_enabled"):
            x = x * math.sqrt(sizes["hidden_size"])
        b, s, d = x.shape
        for kind, i in layer_plan(sizes):
            p = {k: v[i] for k, v in params["layers"][kind].items()}
            a, _ = _attention(_rms(x, p["attn_norm"], eps), p, sizes,
                              kind.startswith("window"))
            x = x + _rms(a, p["post_attn_norm"], eps)
            flat = _rms(x, p["mlp_norm"], eps).reshape(b * s, d)
            if kind.endswith("_dense"):
                m = _mlp(flat, p["w_gate"], p["w_up"], p["w_down"])
            else:
                y, shared, _ = routed_ffn(flat, p, params["experts"][routed],
                                          sizes)
                m = y + shared
                routed += 1
            x = x + _rms(m.reshape(b, s, d), p["post_mlp_norm"], eps)
        x = _rms(x, params["final_norm"], eps)
        logp = jax.nn.log_softmax(x @ params["lm_head"], -1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def loss_and_grad_norm(params: Dict, tokens, sizes: Dict):
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    value, grads = jax.value_and_grad(partial(loss, sizes=sizes))(p32, tokens)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return float(value), float(norm)
