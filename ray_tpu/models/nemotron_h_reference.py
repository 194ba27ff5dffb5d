"""Plain reference for NVIDIA-Nemotron-3-Super-120B-A12B-BF16 (https://
huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16, `config.json`,
`model_type: nemotron_h`; the Mamba-2 layer is "Transformers are SSMs",
arXiv:2405.21060, as Nemotron-H, arXiv:2504.03624, lays it; the expert layer
is the LatentMoE of NVIDIA's Nemotron 3 report).

The forward pass, as published (hidden 4096, 88 layers, vocabulary 131,072,
untied head, RMSNorm eps 1e-5): every layer i `x <- x + Mixer_i(RMSNorm_i(x))`,
ONE mixer a layer by the i-th character of `hybrid_override_pattern`; a final
RMSNorm; the head. `u_t` is the normed input of token t.

  * `M`, Mamba-2 (H = 128 heads of P = 64: d_in 8192; state N = 128; G = 8
    groups; the convolution over 8192 + 2 x 8 x 128 = 10,240 channels):
    `[z_t | xBC_t | dt_t] = W_in u_t` (8192 | 10,240 | 128, no bias);
    `xBC'_t = silu(b_c + sum_{j=0..3} w_c[j] * xBC_(t-3+j))` (depthwise,
    causal, zeros before position 0); `xBC'_t -> x_t` (128 x 64), `B_t`,
    `C_t` (8 x 128 each); `D_t,h = softplus(dt_t,h + dt_bias_h)`; `a_t,h =
    exp(D_t,h A_h)`, `A_h = -exp(A_log_h)`; the state S (64 x 128) a head,
    zero at position 0, with g(h) = h // 16:
        S_t,h = a_t,h S_(t-1),h + D_t,h x_t,h B_t,g(h)^T;
        y_t,h = S_t,h C_t,g(h) + Dskip_h x_t,h.
    `w_t = y_t * silu(z_t)`; RMSNorm over EACH of the 8 groups of 1024 lanes
    of `w_t` apart, times a weight of 8192; `Mixer = W_out(.)`.
  * `*`, attention: `q = W_q u` (32 x 128), `k, v = W_k u, W_v u` (2 x 128),
    no bias, NOTHING rotated (position reaches the model through the
    state-space layers alone); causal softmax of `q_h . k_(h // 16) /
    sqrt(128)`; `W_o` over the 32 x 128 values.
  * `E`, LatentMoE: `s = sigmoid(W_r u)` over 512 experts; the 22 best by `s
    + b` (`b` the correction bias: it moves the selection and not the gates;
    `n_group` 1, `topk_group` 1); `g = 5 s_kept / (sum s_kept + 1e-20)` (the
    sum over all 22 kept, held here or not); `l = W_f1 u` (1024); `E_e(l) =
    W2_e relu(W1_e l)^2` (2688 wide, no gate projection); `Mixer = W_f2 (sum_e
    g_e E_e(l)) + W2_s relu(W1_s u)^2` (shared: 5376 on the full hidden).

Departures and assumptions (the configuration file lists them under
`assumed`; `config.json` carries none of them): no rotary embedding; the
router and the shared expert read the FULL hidden state and only the routed
experts the latent; no bias on the latent's projections; `dt` not clamped;
the gated norm gates BEFORE it normalises; `W_in`'s rows in the order z | xBC |
dt. Left out: the MTP layer.

`fault` names one term changed, for the controls of chip_smoke.py's
`nemotron_h_check` and the tests: "norm_all_lanes" (the gated norm over all
8192 lanes at once), "group_zero" (every head reads B and C of group 0),
"no_routed_factor" (the factor 5 left out), and ("state_not_carried", starts):
S starts anew at every position in `starts` (the chunk's incoming state
dropped; the convolution's history is kept).

Written from that description in straightforward `jax.numpy`: float32
activations, `jax.default_matmul_precision("highest")`, the recurrence a row
at a time, no kernel, no cache, no batching trick, nothing imported from the
program or the benchmark (this file lives twice, as `ray_tpu/models/
nemotron_h_reference.py` for the tier-1 tests and as `benchmarks/
nemotron_h_reference.py`; tests/test_llm_nemotron_h.py holds the two equal).
It reads the program's parameter tree, the same bf16 weights the cell serves, a
layer at a time and an expert at a time: `params["layers"][kind]` stacks the
layers of one kind ("mamba", "attn", "latent_moe") in the published order,
`params["experts"][i]` is the i-th expert layer's held experts.

`sizes` is the configuration file's keys: the published ones,
`n_routed_experts` = the experts HELD, `n_routed_experts_published` = the
router's width, `first_held_expert` = the first held published id. The
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
KINDS = {"M": "mamba", "*": "attn", "E": "latent_moe"}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _softplus(x):
    return jnp.where(x > 20.0, x, jnp.log1p(jnp.exp(jnp.minimum(x, 20.0))))


@partial(jax.jit, static_argnames=("key",))
def _mamba(h, p, fresh, *, key):
    """What one Mamba-2 layer adds, of the normed rows h (b, s, d). `key` =
    (H, P, G, N, eps, fault); `fresh` (s,) bool: the positions at which S
    starts anew (position 0, unless a control says more)."""
    H, P, G, N, eps, fault = key
    b, s, _ = h.shape
    di = H * P
    zxbcdt = h @ p["in_proj"]
    z, xbc = zxbcdt[..., :di], zxbcdt[..., di:di + di + 2 * G * N]
    dt = _softplus(zxbcdt[..., 2 * di + 2 * G * N:] + p["dt_bias"])  # (b,s,H)
    taps = p["conv_w"].shape[0]
    conv = jnp.broadcast_to(p["conv_b"], xbc.shape)
    for j in range(taps):
        back = taps - 1 - j
        conv = conv + p["conv_w"][j] * jnp.pad(
            xbc, ((0, 0), (back, 0), (0, 0)))[:, :s]
    conv = jax.nn.silu(conv)
    x = conv[..., :di].reshape(b, s, H, P)
    B = conv[..., di:di + G * N].reshape(b, s, G, N)
    C = conv[..., di + G * N:].reshape(b, s, G, N)
    if fault == "group_zero":
        B = jnp.broadcast_to(B[:, :, :1], B.shape)
        C = jnp.broadcast_to(C[:, :, :1], C.shape)
    B, C = (jnp.repeat(a, H // G, axis=2) for a in (B, C))    # a head's own
    a = jnp.exp(dt * -jnp.exp(p["A_log"]))                    # (b, s, H)

    def step(S, xs):
        x_t, B_t, C_t, dt_t, a_t, fresh_t = xs
        S = jnp.where(fresh_t, 0.0, S)
        S = (a_t[..., None, None] * S
             + (dt_t[..., None] * x_t)[..., None] * B_t[..., None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, C_t)

    t = lambda v: jnp.moveaxis(v, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), F32),
                        (t(x), t(B), t(C), t(dt), t(a), fresh))
    y = jnp.moveaxis(y, 0, 1) + p["D"][:, None] * x           # (b, s, H, P)
    w = y.reshape(b, s, di) * jax.nn.silu(z)
    if fault == "norm_all_lanes":
        w = _rms(w, p["gate_norm"], eps)
    else:
        w = _rms(w.reshape(b, s, G, di // G),
                 p["gate_norm"].reshape(G, di // G), eps).reshape(b, s, di)
    return w @ p["out_proj"]


@partial(jax.jit, static_argnames=("key",))
def _attn(h, p, *, key):
    """What one attention layer adds, of the normed rows h. `key` = (H, K,
    hd)."""
    H, K, hd = key
    b, s, _ = h.shape
    q = (h @ p["wq"]).reshape(b, s, K, H // K, hd)
    k = (h @ p["wk"]).reshape(b, s, K, hd)
    v = (h @ p["wv"]).reshape(b, s, K, hd)
    scores = jnp.einsum("bqkgd,bckd->bkgqc", q, k) / math.sqrt(hd)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    o = jnp.einsum("bkgqc,bckd->bqkgd", probs, v)
    return o.reshape(b, s, H * hd) @ p["wo"]


@jax.jit
def _relu2_mlp(h, up, down):
    return jnp.square(jax.nn.relu(h @ up.astype(F32))) @ down.astype(F32)


def _top_mask(values, count: int):
    """True at the `count` largest of each row; ties: the lower index."""
    order = jnp.argsort(-values, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < count


def layer_plan(sizes: Dict):
    """[(kind, index in that kind's stack)] in the published order."""
    plan, seen = [], {}
    for c in sizes["hybrid_override_pattern"]:
        kind = KINDS[c]
        plan.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return plan


def _routed(flat, p, experts, sizes: Dict, kept=None, fault=None):
    """A LatentMoE layer over the normed rows `flat` (N, d): -> (y, the
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
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    if fault != "no_routed_factor":
        gates = sizes["routed_scaling_factor"] * gates
    latent = flat @ p["fc1_latent"].astype(F32)
    inside = jnp.zeros_like(latent)
    for e in range(held):
        inside = inside + gates[:, first + e, None] * _relu2_mlp(
            latent, experts["w1"][e], experts["w2"][e])
    y = inside @ p["fc2_latent"].astype(F32) + _relu2_mlp(
        flat, p["shared_up"], p["shared_down"])
    return y, choice


def _forward(params: Dict, tokens, sizes: Dict, kept=None, fault=None):
    """tokens (b, s) -> (final-norm hidden states (b, s, d) float32, [the
    selection scores s + b (b s, published experts) a routed layer])."""
    name, starts = fault if isinstance(fault, tuple) else (fault, ())
    eps = sizes["layer_norm_epsilon"]
    b, s = tokens.shape
    fresh = jnp.zeros((s,), bool).at[0].set(True)
    if name == "state_not_carried":
        fresh = fresh.at[jnp.asarray(starts, jnp.int32)].set(True)
    mamba_key = (sizes["mamba_num_heads"], sizes["mamba_head_dim"],
                 sizes["n_groups"], sizes["ssm_state_size"], eps, name)
    attn_key = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                sizes["head_dim"])
    all_scores, routed = [], 0
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        d = x.shape[-1]
        for kind, i in layer_plan(sizes):
            p = {k: v[i] for k, v in params["layers"][kind].items()}
            h = _rms(x, p.pop("norm").astype(F32), eps)
            if kind == "mamba":
                x = x + _mamba(h, {k: v.astype(F32) for k, v in p.items()},
                               fresh, key=mamba_key)
            elif kind == "attn":
                x = x + _attn(h, {k: v.astype(F32) for k, v in p.items()},
                              key=attn_key)
            else:
                ids = None if kept is None else jnp.asarray(
                    kept[routed]).reshape(b * s, -1)
                y, choice = _routed(
                    h.reshape(b * s, d),
                    {k: v.astype(F32) if k.startswith("router") else v
                     for k, v in p.items()},
                    params["experts"][routed], sizes, ids, name)
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
