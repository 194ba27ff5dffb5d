"""Plain reference for Kimi-Linear-48B-A3B-Instruct (https://huggingface.co/
moonshotai/Kimi-Linear-48B-A3B-Instruct, `config.json`, `model_type:
kimi_linear`; arXiv:2510.26692, "Kimi Linear: An Expressive, Efficient
Attention Architecture"): Kimi Delta Attention layers three to one beside
latent attention layers that rotate nothing, a leading dense layer, expert
layers with a shared expert.

The forward pass, as published (hidden 2304, 27 layers numbered from 1 as in
`linear_attn_config`, vocabulary 163,840, untied head, RMSNorm eps 1e-5,
SiLU): every layer `h = x + Mix(RMSNorm(x))`, `x' = h + FF(RMSNorm(h))`; a
final RMSNorm; the head.

  * KDA layer (`kda_layers`), H = 32 heads of d = 128, for the normed input
    x_t: `q~, k~, v~ = W_q x_t, W_k x_t, W_v x_t` (4096 each); each passes a
    causal depthwise convolution of `short_conv_kernel_size` = 4 taps along
    the sequence (zeros before position 0) and SiLU: `q'_t = SiLU(sum_j w_j
    q~_(t-3+j))`. A head at a time `q_t = d^(-1/2) q'_t / |q'_t|`, `k_t =
    k'_t / |k'_t|`, `v_t = v'_t`. Forget gate, A VECTOR A HEAD: `a_t = W_f2
    (W_f1 x_t)` (rank 128), `log alpha_t = -exp(A_log_h) softplus(a_t +
    dt_bias)`, one value a key channel, 0 < alpha <= 1. Write strength
    `beta_t = sigmoid(w_b,h . x_t)`, a scalar a head. State S (128 keys x 128
    values) a head, zero at position 0:
        S' = diag(alpha_t) S_(t-1);  u_t = beta_t (v_t - S'^T k_t);
        S_t = S' + k_t u_t^T;  o_t = S_t^T q_t.
    Output `y_t = W_o [RMSNorm_128(o_t,h) * sigmoid(W_g2 (W_g1 x_t) +
    b_g)_h]_h`, the norm a head with one learned weight of 128.
  * MLA layer (`full_attn_layers`), 32 heads: `q = W_q x_t` -> (32, 128 + 64)
    (`q_lora_rank` null); `[c | k_r] = W_kva x_t` (512 + 64), `c <-
    RMSNorm(c)`; `k_h = [W_kb,h c | k_r]`, `v_h = W_vb,h c` (128 each); NO
    rotation of the 64 (`mla_use_nope`: `rope_theta` is inert); causal softmax
    of `q_h . k_h / sqrt(192)`; `W_o` over the 32 x 128 values.
  * Feed-forward: layer 1 (`first_k_dense_replace` 1) SwiGLU of 9216. Every
    later layer `s = sigmoid(W_r h)` over 256 experts, the 8 best by `s + b`
    (`b` the correction bias: it moves the selection and not the gates;
    `num_expert_group` 1, `topk_group` 1), gates `g = 2.446 s_kept / (sum
    s_kept + 1e-20)` (`moe_renormalize`, `routed_scaling_factor`; the sum is
    over all 8 kept, held here or not), plus one shared expert; every expert
    a SwiGLU of 1024.

Departures and assumptions (the configuration file lists them under
`assumed`; from the publication and its released code, `config.json` carries
none of them): no bias on the convolutions and SiLU after them; the L2 norm as
`x rsqrt(sum x^2 + 1e-6)`; `A_log` one a head, `dt_bias` one a key channel;
the rank of W_f and W_g 128; `b_g`; the scale 192^(-1/2). `kv_b_proj` is kept
split per head as `w_kb (H, 128, 512)` and `w_vb (H, 512, 128)`, and q, k, v of
a KDA layer as one matrix `wqkv` with their convolutions' taps side by side:
relabellings of random weights. Left out: `num_nextn_predict_layers` is 0.

`fault` names one term dropped, for the controls of chip_smoke.py's
`kda_check` and the tests: "beta_one", "gate_a_head" (the gate's log averaged
over a head's channels), "no_delta" (`S'^T k` left out: plain gated
accumulation), "no_nope_lanes" (the 64 unrotated lanes left out of the
scores), and ("state_not_carried", starts): S and the convolution's history
start anew at every position in `starts`.

Written from that description in straightforward `jax.numpy`: float32
activations, `jax.default_matmul_precision("highest")`, the recurrence a row
at a time, no kernel, no cache, no batching trick, nothing imported from the
program or the benchmark (this file lives twice, as `ray_tpu/models/
kimi_linear_reference.py` for the tier-1 tests and as `benchmarks/
kimi_linear_reference.py`; tests/test_llm_kimi_linear.py holds the two equal).
It reads the program's parameter tree, the same bf16 weights the cell serves, a
layer at a time and an expert at a time: `params["layers"][kind]` stacks the
layers of one kind ("kda_dense", "kda_moe", "mla_moe") in the published order,
`params["experts"][i]` is the i-th expert layer's held experts.

`sizes` is the configuration file's keys: the published ones, `num_experts` =
the experts HELD, `num_experts_published` = the router's width,
`first_held_expert` = the first held published id, `l2_norm_eps`. The
reference is given the same share as the program: it routes over all published
experts and adds what the held ones and the shared one contribute; what absent
experts would add is left out of both.
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


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


@partial(jax.jit, static_argnames=("key",))
def _kda(x, p, fresh, *, key):
    """What one KDA layer adds to x (b, s, d). `key` = (H, hd, eps, l2 eps,
    fault); `fresh` (s,) bool: the positions at which S and the convolution's
    history start anew (position 0, unless a control says more)."""
    H, hd, eps, l2, fault = key
    b, s, _ = x.shape
    h = _rms(x, p["attn_norm"], eps)
    raw = h @ p["wqkv"]                                     # (b, s, 3 H hd)
    taps = p["conv_w"].shape[0]
    since = jnp.arange(s) - jax.lax.cummax(
        jnp.where(fresh, jnp.arange(s), 0))                 # rows of history
    conv = jnp.zeros_like(raw)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.pad(raw, ((0, 0), (back, 0), (0, 0)))[:, :s]
        conv = conv + p["conv_w"][j] * jnp.where(
            (since >= back)[None, :, None], shifted, 0.0)
    q, k, v = (a.reshape(b, s, H, hd)
               for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    unit = lambda a: a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + l2)
    q, k = unit(q) / math.sqrt(hd), unit(k)
    log_a = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(
        (h @ p["w_f1"]) @ p["w_f2"] + p["dt_bias"]).reshape(b, s, H, hd)
    beta = jax.nn.sigmoid(h @ p["w_beta"])                  # (b, s, H)
    if fault == "gate_a_head":
        log_a = jnp.broadcast_to(log_a.mean(-1, keepdims=True), log_a.shape)
    if fault == "beta_one":
        beta = jnp.ones_like(beta)

    def step(S, xs):
        q_t, k_t, v_t, la_t, b_t, fresh_t = xs
        S = jnp.where(fresh_t, 0.0, S)
        held = jnp.exp(la_t)[..., None] * S                 # (b, H, hd, hd)
        seen = (0.0 if fault == "no_delta"
                else jnp.einsum("bhkv,bhk->bhv", held, k_t))
        S = held + k_t[..., None] * (b_t[..., None] * (v_t - seen))[
            ..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    t = lambda a: jnp.moveaxis(a, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((b, H, hd, hd), F32),
                        (t(q), t(k), t(v), t(log_a), t(beta), fresh))
    o = _rms(jnp.moveaxis(o, 0, 1), p["o_norm"], eps)       # (b, s, H, hd)
    gate = jax.nn.sigmoid((h @ p["w_g1"]) @ p["w_g2"] + p["b_g"])
    return (o.reshape(b, s, H * hd) * gate) @ p["wo"]


@partial(jax.jit, static_argnames=("key",))
def _mla(x, p, *, key):
    """What one latent attention layer adds to x. `key` = (H, lat, nope,
    rope, v, eps, fault)."""
    H, lat, nope, rope, vd, eps, fault = key
    b, s, _ = x.shape
    h = _rms(x, p["attn_norm"], eps)
    q = (h @ p["wq"]).reshape(b, s, H, nope + rope)
    kv = h @ p["wkv_a"]
    c = _rms(kv[..., :lat], p["kv_norm"], eps)
    k_nope = jnp.einsum("bsl,hnl->bshn", c, p["w_kb"])
    v = jnp.einsum("bsl,hlv->bshv", c, p["w_vb"])
    scores = jnp.einsum("bqhn,bkhn->bhqk", q[..., :nope], k_nope)
    if fault != "no_nope_lanes":
        scores = scores + jnp.einsum("bqhr,bkr->bhqk", q[..., nope:],
                                     kv[..., lat:])
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(
        seen[None, None], scores / math.sqrt(nope + rope), -jnp.inf), -1)
    o = jnp.einsum("bhqk,bkhv->bqhv", probs, v)
    return o.reshape(b, s, H * vd) @ p["wo"]


@jax.jit
def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))) \
        @ down.astype(F32)


def _top_mask(values, count: int):
    """True at the `count` largest of each row; ties: the lower index."""
    order = jnp.argsort(-values, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < count


def layer_plan(sizes: Dict):
    """[(kind, index in that kind's stack)] in the published order."""
    kda = set(sizes["linear_attn_config"]["kda_layers"])
    plan, seen = [], {}
    for li in range(sizes["num_hidden_layers"]):
        kind = (("kda" if li + 1 in kda else "mla")
                + ("_dense" if li < sizes["first_k_dense_replace"]
                   else "_moe"))
        plan.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return plan


def _routed(flat, p, experts, sizes: Dict, kept=None):
    """An expert layer's feed-forward over rows `flat` (N, d): -> (y, the
    selection scores s + b (N, published experts)). With `kept` (N, top_k
    published ids) the layer takes THOSE experts, with this reference's own
    gates for them. One expert's weights are alive at a time."""
    top_k = sizes["num_experts_per_token"]
    first, held = sizes["first_held_expert"], sizes["num_experts"]
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


def _forward(params: Dict, tokens, sizes: Dict, kept=None, fault=None):
    """tokens (b, s) -> (final-norm hidden states (b, s, d) float32, [the
    selection scores s + b (b s, published experts) a routed layer])."""
    name, starts = fault if isinstance(fault, tuple) else (fault, ())
    eps, lin = sizes["rms_norm_eps"], sizes["linear_attn_config"]
    b, s = tokens.shape
    fresh = jnp.zeros((s,), bool).at[0].set(True)
    if name == "state_not_carried":
        fresh = fresh.at[jnp.asarray(starts, jnp.int32)].set(True)
    kda_key = (lin["num_heads"], lin["head_dim"], eps,
               sizes.get("l2_norm_eps", 1e-6), name)
    mla_key = (sizes["num_attention_heads"], sizes["kv_lora_rank"],
               sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"],
               sizes["v_head_dim"], eps, name)
    mixer = ("attn_norm", "wqkv", "conv_w", "w_f1", "w_f2", "A_log",
             "dt_bias", "w_beta", "w_g1", "w_g2", "b_g", "o_norm", "wo",
             "wq", "wkv_a", "kv_norm", "w_kb", "w_vb")
    all_scores, routed = [], 0
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        d = x.shape[-1]
        for kind, i in layer_plan(sizes):
            p = {k: v[i] for k, v in params["layers"][kind].items()}
            mix = {k: v.astype(F32) for k, v in p.items() if k in mixer}
            x = x + (_kda(x, mix, fresh, key=kda_key)
                     if kind.startswith("kda") else _mla(x, mix, key=mla_key))
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
        return _rms(x, params["final_norm"].astype(F32), eps), all_scores


def hidden(params: Dict, tokens, sizes: Dict, kept=None, fault=None):
    """tokens (b, s) -> (final-norm hidden states (b, s, d) float32, the
    selection scores s + b (routed layers, b, s, published experts) as
    numpy). `kept` (routed layers, b, s, top_k): the experts to take."""
    x, scores = _forward(params, tokens, sizes, kept, fault)
    b, s = tokens.shape
    return x, (np.stack([np.asarray(c).reshape(b, s, -1) for c in scores])
               if scores else np.zeros((0, b, s, 0)))


def logits_at(params: Dict, tokens, positions, sizes: Dict,
              kept: Optional[np.ndarray] = None, fault=None):
    """(logits (b, len(positions), vocab) float32, selection scores): a full
    forward pass over tokens (b, s), read at `positions`; the head is
    `lm_head` (d, vocab), untied."""
    x, scores = hidden(params, tokens, sizes, kept, fault)
    x = x[:, jnp.asarray(positions)]
    head = params["lm_head"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [x @ head[:, lo:lo + VOCAB_BLOCK].astype(F32)
             for lo in range(0, head.shape[1], VOCAB_BLOCK)], -1), scores


def loss(params: Dict, tokens, sizes: Dict):
    """Mean next-token cross entropy of tokens (b, s+1), differentiable with
    respect to float32 `params` (the router's choice is not)."""
    x, _ = _forward(params, tokens[:, :-1], sizes)
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(x @ params["lm_head"].astype(F32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def loss_and_grad_norm(params: Dict, tokens, sizes: Dict):
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    value, grads = jax.value_and_grad(partial(loss, sizes=sizes))(p32, tokens)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return float(value), float(norm)
