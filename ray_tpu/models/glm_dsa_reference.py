"""Plain reference for GLM-5.2 (https://huggingface.co/zai-org/GLM-5.2,
`config.json`, `model_type: glm_moe_dsa`; the attention is DeepSeek Sparse
Attention as published with DeepSeek-V3.2-Exp, "Boosting Long-Context
Efficiency with DeepSeek Sparse Attention", and its released inference code's
`Indexer`): latent attention over the `index_topk` context rows a lightning
indexer scores highest, the selection shared by the layers that follow an
indexer's, leading dense layers, sigmoid-routed expert layers with a shared
expert.

The forward pass, as published (hidden 6144, 78 layers numbered from 0 as in
`indexer_types` / `mlp_layer_types`, vocabulary 154,880, untied head, RMSNorm
eps 1e-5, SiLU): every layer `h = x + Attn(RMSNorm(x))`, `x' = h +
FF(RMSNorm(h))`; a final RMSNorm; the head. For the normed input x_t of token
t at position t:

  * Latent projections, every layer: `cq_t = RMSNorm(W_qa x_t)` (2048); `q_t
    = W_qb cq_t` -> (64 heads, 192 + 64); `[c_t | kr_t] = W_kva x_t` (512 +
    64), `c_t <- RMSNorm(c_t)`; the 64 rope lanes of every q_t,h and of kr_t
    rotated at position t, theta 8e6, INTERLEAVED pairs: lanes (2i, 2i + 1)
    turn by t theta^(-2i / 64); `k_s,h = [W_kb,h c_s | kr_s]`, `v_s,h =
    W_vb,h c_s` (256 wide); scale 256^(-1/2).
  * Indexer, a layer whose `indexer_types` entry is "full", 32 heads of 128:
    `qI_t = W_qI cq_t` -> (32, 128); `kI_t = LayerNorm(W_kI x_t)` (weight and
    bias, eps 1e-6), ONE key a token; the first 64 lanes of every qI_t,j and
    of kI_t rotated as above; `w_t = W_w x_t` (32); `I_t,s = 32^(-1/2)
    128^(-1/2) sum_j w_t,j ReLU(qI_t,j . kI_s)` for s <= t. The selection
    S_t: the positions of the min(t + 1, index_topk) largest I_t,s, ties to
    the lower position.
  * A "shared" layer has no indexer: its S_t is the nearest "full" layer's
    before it.
  * Attention: `o_t,h = sum_{s in S_t} softmax_{s in S_t}(q_t,h . k_s,h / 16)
    v_s,h`; `Attn = W_o [o_t,h]_h`. In the EXPANDED form here: a key and a
    value a head a context token.
  * Feed-forward: a "dense" layer SwiGLU of 12288. A "sparse" layer `s =
    sigmoid(W_r h)` over 256 experts, the 8 best by `s + b` (`b` the
    correction bias of `noaux_tc`: it moves the selection and not the gates;
    one group), gates `g = 2.5 s_kept / (sum s_kept + 1e-20)`, plus one
    shared expert; every expert a SwiGLU of 2048.

The one departure from the published code: it passes qI and kI through a
Hadamard transform (orthogonal: every qI . kI is as it was) and quantises both
to FP8 (not this configuration's dtype, bfloat16); both are left out. What
`config.json` does not carry stands under `assumed` in the configuration's
file. `kv_b_proj` is read split per head (`w_kb (H, nope, lat)`, `w_vb (H,
lat, v)`): a relabelling of random weights. Left out:
`num_nextn_predict_layers` is 0.

`fault` names one thing done otherwise, for the controls of chip_smoke.py's
`glm_dsa_check` and the tests: "recent_rows" (S_t = the most recent
index_topk positions), "rotate_half" (pairs (i, i + 32)), "no_index_bias"
(the LayerNorm of kI without its bias), "share_nothing" (a "shared" layer
attends to its whole context).

Written from that description in straightforward `jax.numpy`: float32
activations, `jax.default_matmul_precision("highest")`, a layer at a time,
the queries in blocks of `Q_BLOCK` (a score matrix of 8,192 x 8,192 a head
does not fit a chip beside the weights; the arithmetic of a row is the same),
no kernel, no cache, nothing imported from the program or the benchmark (this
file lives twice, as `ray_tpu/models/glm_dsa_reference.py` for the tier-1
tests and as `benchmarks/glm_dsa_reference.py`; tests/test_llm_glm_dsa.py
holds the two equal). It reads the program's parameter tree, the same bf16
weights the cell serves: `params["layers"][kind]` stacks the layers of one
kind ("full_dense", "full_moe", "shared_dense", "shared_moe") in the
published order, `params["experts"][i]` is the i-th expert layer's held
experts.

`sizes` is the configuration file's keys: the published ones,
`n_routed_experts` = the experts HELD, `n_routed_experts_published` = the
router's width, `first_held_expert` = the first held published id,
`rope_theta` out of `rope_parameters`. The reference is given the same share
as the program: it routes over all published experts and adds what the held
ones and the shared one contribute; what absent experts would add is left out
of both.
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
Q_BLOCK = 512           # query positions a block of the attention


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotate(x, theta: float, fault=None):
    """x (b, s, heads, rope) at positions 0..s-1: interleaved pairs (2i, 2i +
    1) turn by t theta^(-2i / rope)."""
    s, rope = x.shape[1], x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(rope // 2, dtype=F32) / rope)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    if fault == "rotate_half":
        x1, x2 = x[..., :rope // 2], x[..., rope // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _top_mask(values, count: int):
    """True at the `count` largest of each row; ties: the lower index."""
    order = jnp.argsort(-values, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < count


@partial(jax.jit, static_argnames=("key",))
def _index_scores(h, cq, p, *, key):
    """I (b, s, s) float32 of a "full" layer, -inf where s' > t. `key` =
    (HI, dI, rope, theta, fault)."""
    HI, dI, rope, theta, fault = key
    b, s, _ = h.shape
    front = lambda a: jnp.concatenate(
        [_rotate(a[..., :rope], theta, fault), a[..., rope:]], -1)
    qi = front((cq @ p["wq_i"]).reshape(b, s, HI, dI))
    k = h @ p["wk_i"]
    mean = jnp.mean(k, -1, keepdims=True)
    k = (k - mean) * jax.lax.rsqrt(
        jnp.mean(jnp.square(k - mean), -1, keepdims=True) + 1e-6) \
        * p["k_norm_w"]
    if fault != "no_index_bias":
        k = k + p["k_norm_b"]
    ki = front(k[:, :, None, :])[:, :, 0]
    w = (h @ p["w_w"]) / math.sqrt(HI * dI)                  # (b, s, HI)
    out = []
    for lo in range(0, s, Q_BLOCK):
        dots = jnp.einsum("bqjd,bkd->bqjk", qi[:, lo:lo + Q_BLOCK], ki)
        out.append(jnp.einsum("bqj,bqjk->bqk", w[:, lo:lo + Q_BLOCK],
                              jnp.maximum(dots, 0.0)))
    scores = jnp.concatenate(out, 1)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    return jnp.where(seen[None], scores, -jnp.inf)


@partial(jax.jit, static_argnames=("key",))
def _project(x, p, *, key):
    """-> (the normed input h, the normed query latent cq)."""
    (eps,) = key
    h = _rms(x, p["attn_norm"], eps)
    return h, _rms(h @ p["wq_a"], p["q_norm"], eps)


@partial(jax.jit, static_argnames=("key",))
def _attention(h, cq, p, chosen, *, key):
    """What a latent layer adds to x, each token over the context rows
    `chosen` (b, s, s) bool allows it. `key` = (H, lat, nope, rope, v, eps,
    theta, fault)."""
    H, lat, nope, rope, vd, eps, theta, fault = key
    b, s, _ = h.shape
    q = (cq @ p["wq_b"]).reshape(b, s, H, nope + rope)
    kv = h @ p["wkv_a"]
    c = _rms(kv[..., :lat], p["kv_norm"], eps)
    k_rope = _rotate(kv[..., None, lat:], theta, fault)      # (b, s, 1, rope)
    q_rope = _rotate(q[..., nope:], theta, fault)
    k_nope = jnp.einsum("bsl,hnl->bshn", c, p["w_kb"])
    v = jnp.einsum("bsl,hlv->bshv", c, p["w_vb"])
    out = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(lo + Q_BLOCK, s)
        scores = (jnp.einsum("bqhn,bkhn->bhqk", q[:, lo:hi, :, :nope], k_nope)
                  + jnp.einsum("bqhr,bkr->bhqk", q_rope[:, lo:hi],
                               k_rope[:, :, 0])) / math.sqrt(nope + rope)
        probs = jax.nn.softmax(jnp.where(
            chosen[:, None, lo:hi], scores, -jnp.inf), -1)
        out.append(jnp.einsum("bhqk,bkhv->bqhv", probs, v))
    o = jnp.concatenate(out, 1)
    return o.reshape(b, s, H * vd) @ p["wo"]


@jax.jit
def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
        @ down.astype(F32)


def layer_plan(sizes: Dict):
    """[(kind, index in that kind's stack)] in the published order."""
    plan, seen = [], {}
    for ix, ff in zip(sizes["indexer_types"], sizes["mlp_layer_types"]):
        kind = f"{ix}_{'moe' if ff == 'sparse' else 'dense'}"
        plan.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return plan


def _routed(flat, p, experts, sizes: Dict, kept=None):
    """An expert layer's feed-forward over rows `flat` (N, d): -> (y, the
    selection scores s + b (N, published experts)). With `kept` (N, top_k
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
    gates = sizes["routed_scaling_factor"] * gates / (
        gates.sum(-1, keepdims=True) + 1e-20)
    y = _swiglu(flat, p["shared_gate"], p["shared_up"], p["shared_down"])
    for e in range(held):
        y = y + gates[:, first + e, None] * _swiglu(
            flat, *(experts[name][e] for name in ("w_gate", "w_up",
                                                  "w_down")))
    return y, choice


def selection_mask(positions, count, s: int):
    """(b, s, s) bool from a program's selection: positions (b, s, topk), of
    which the first count (b, s) are real. A count of 0 is a token of a step
    that selected nothing (no context of it held more than index_topk rows:
    the dense kernel): it attends to everything before it."""
    positions, count = jnp.asarray(positions), jnp.asarray(count)
    b, _, k = positions.shape
    real = jnp.arange(k)[None, None, :] < count[..., None]
    picked = jnp.zeros((b, s, s + 1), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        jnp.where(real, positions, s)].set(True)[..., :s]
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    return jnp.where(count[..., None] > 0, picked, causal[None])


def _forward(params: Dict, tokens, sizes: Dict, kept=None, selection=None,
             fault=None):
    """tokens (b, s) -> (final-norm hidden states (b, s, d) float32, [the
    selection scores s + b (b s, published experts) a routed layer], [the
    index scores I (b, s, s) a "full" layer]). `selection`: [(
    positions, count)] a "full" layer, the rows to attend to in place of this
    reference's own choice."""
    eps, theta = sizes["rms_norm_eps"], float(sizes["rope_theta"])
    topk, rope = sizes["index_topk"], sizes["qk_rope_head_dim"]
    b, s = tokens.shape
    att_key = (sizes["num_attention_heads"], sizes["kv_lora_rank"],
               sizes["qk_nope_head_dim"], rope, sizes["v_head_dim"], eps,
               theta, fault)
    idx_key = (sizes["index_n_heads"], sizes["index_head_dim"], rope, theta,
               fault)
    mixer = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
             "w_kb", "w_vb", "wo", "wq_i", "wk_i", "k_norm_w", "k_norm_b",
             "w_w")
    causal = jnp.broadcast_to(
        jnp.arange(s)[None, :] <= jnp.arange(s)[:, None], (b, s, s))
    all_scores, all_index, routed, full = [], [], 0, 0
    chosen = causal
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        d = x.shape[-1]
        for kind, i in layer_plan(sizes):
            p = {k: v[i] for k, v in params["layers"][kind].items()}
            mix = {k: v.astype(F32) for k, v in p.items() if k in mixer}
            h, cq = _project(x, mix, key=(eps,))
            if kind.startswith("full"):
                index = _index_scores(h, cq, mix, key=idx_key)
                all_index.append(index)
                if selection is not None:
                    chosen = selection_mask(*selection[full], s)
                elif fault == "recent_rows":
                    chosen = causal & (jnp.arange(s)[None, :]
                                       > jnp.arange(s)[:, None] - topk)[None]
                else:
                    chosen = causal if s <= topk else _top_mask(
                        index, topk) & causal
                full += 1
            x = x + _attention(
                h, cq, mix,
                causal if fault == "share_nothing"
                and kind.startswith("shared") else chosen, key=att_key)
            h = _rms(x, p["mlp_norm"].astype(F32), eps)
            if kind.endswith("_dense"):
                x = x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"])
                continue
            ids = None if kept is None else jnp.asarray(
                kept[routed]).reshape(b * s, -1)
            y, choice = _routed(
                h.reshape(b * s, d),
                {k: v.astype(F32) if k.startswith("router") else v
                 for k, v in p.items()},
                params["experts"][routed], sizes, ids)
            all_scores.append(choice)
            routed += 1
            x = x + y.reshape(b, s, d)
        return (_rms(x, params["final_norm"].astype(F32), eps), all_scores,
                all_index)


def hidden(params: Dict, tokens, sizes: Dict, kept=None, selection=None,
           fault=None):
    """tokens (b, s) -> (final-norm hidden states (b, s, d) float32, the
    selection scores s + b (routed layers, b, s, published experts) as numpy,
    the index scores [(b, s, s)] a "full" layer). `kept` (routed layers, b,
    s, top_k): the experts to take; `selection`: the rows to attend to."""
    x, scores, index = _forward(params, tokens, sizes, kept, selection, fault)
    b, s = tokens.shape
    return x, (np.stack([np.asarray(c).reshape(b, s, -1) for c in scores])
               if scores else np.zeros((0, b, s, 0))), [
        np.asarray(i) for i in index]


def logits_at(params: Dict, tokens, positions, sizes: Dict,
              kept: Optional[np.ndarray] = None, selection=None, fault=None):
    """(logits (b, len(positions), vocab) float32, selection scores, index
    scores): a full forward pass over tokens (b, s), read at `positions`; the
    head is `lm_head` (d, vocab), untied."""
    x, scores, index = hidden(params, tokens, sizes, kept, selection, fault)
    x = x[:, jnp.asarray(positions)]
    head = params["lm_head"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [x @ head[:, lo:lo + VOCAB_BLOCK].astype(F32)
             for lo in range(0, head.shape[1], VOCAB_BLOCK)], -1), scores, \
            index


def loss(params: Dict, tokens, sizes: Dict):
    """Mean next-token cross entropy of tokens (b, s+1), differentiable with
    respect to float32 `params` (neither the router's choice nor the
    indexer's is)."""
    x, _, _ = _forward(params, tokens[:, :-1], sizes)
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(x @ params["lm_head"].astype(F32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def loss_and_grad_norm(params: Dict, tokens, sizes: Dict):
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    value, grads = jax.value_and_grad(partial(loss, sizes=sizes))(p32, tokens)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return float(value), float(norm)
