"""Plain reference for Phi-4-mini-flash-reasoning (https://huggingface.co/
microsoft/Phi-4-mini-flash-reasoning, `config.json`; the decoder-hybrid-decoder
of arXiv:2507.06607): state-space layers, window attention, one full attention
layer whose K and V every later attention layer reads (a cross-decoder), gated
memory units, differential attention.

With d = hidden_size 2,560, d_i = 2 d = 5,120, N = 16, R = ceil(d / 16) = 160,
K_c = 4, H = 40 query heads, K = 20 kv heads, hd = d / H = 64, ff = 10,240,
L = 32, w = sliding_window 512, eps = layer_norm_eps 1e-5:

  * Every layer i: `x = x + mix_i(LN(x)); x = x + fc2(silu(g) * u)` with
    `[g | u] = fc1(LN'(x))` (fc1: d -> 2 ff, fc2: ff -> d, no bias). LN is
    LayerNorm with weight and bias. After the last layer a final LayerNorm;
    logits `= h E^T`, E the embedding (tied, no bias). No positional encoding
    anywhere: the recurrence carries order.
  * Role of layer i (`mb_per_layer` 2): i even and i <= L/2: Mamba; i odd and
    i < L/2: window attention; i = L/2: a Mamba layer that also hands its scan
    output on as the memory M; i = L/2 + 1: full causal attention, whose K and
    V are THE cache; i >= L/2 + 2: the cross-decoder, a gated memory unit
    where i is even, cross attention (its own q over layer L/2 + 1's K and V)
    where i is odd.
  * Mamba (Mamba-1, arXiv:2312.00752): `[u | z] = W_in h` (d -> 2 d_i);
    `c_t = silu(b_c + sum_{j<4} w_c[j] * u_{t-3+j})` (depthwise, causal, zeros
    before position 0); `[r | B_t | C_t] = W_x c_t` (d_i -> R + 2 N);
    `dt_t = softplus(W_dt r + b_dt)` (R -> d_i); `A = -exp(A_log)` (d_i x N);
    `s_t = exp(dt_t A) * s_{t-1} + (dt_t * c_t) B_t^T` (d_i x N, s before
    position 0 zero); `y_t = s_t C_t + D * c_t`; out `W_out (y_t * silu(z_t))`.
    Layer L/2 keeps M_t = y_t, BEFORE the gate.
  * Gated memory unit: `W_2 (M_t * silu(W_1 h_t))`, W_1: d -> d_i, W_2: d_i ->
    d, M_t the memory at the same position t.
  * Differential attention (arXiv:2410.05258), in window layers, the full
    layer and cross layers: q (H heads), k, v (K heads) of width hd from one
    projection d -> (H + 2 K) hd with bias (cross layers: q only, d -> H hd).
    Heads pair up, even with odd: query pair p = (q_{2p}, q_{2p+1}), kv pair
    j = (k_{2j}, k_{2j+1}), V_j = [v_{2j} | v_{2j+1}] (width 2 hd); pair p
    reads kv pair p // 2. With the layer's mask (causal; causal and t - s < w
    in a window layer) and scale 1 / sqrt(hd):
    `o_p = softmax(q_{2p} k_{2j}^T) V_j - lambda_i softmax(q_{2p+1} k_{2j+1}^T)
    V_j`, `lambda_i = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i)`,
    `lambda_init(i) = 0.8 - 0.6 exp(-0.3 i)`; then `RMSNorm_{2 hd}(o_p) * (1 -
    lambda_init(i))` (a learned weight of 2 hd, eps 1e-5), the H / 2 outputs
    concatenated, output projection d -> d with bias.

Departures and assumptions (the configuration file lists them under
`assumed`): the config carries no Mamba size, so d_state 16, d_conv 4, expand
2 and dt_rank ceil(d / 16) are the family's defaults; the pairing of heads,
lambda's form and the 2 hd-wide RMSNorm are the Differential Transformer's;
attention projections carry a bias and the feed-forward none (`mlp_bias`
false); the layer roles by index are SambaY's. Left out: dropout
(`embd_pdrop`, `resid_pdrop` 0) and training.

Written from that description in straightforward `jax.numpy`: float32
activations, `jax.default_matmul_precision("highest")`, a Python loop over
layers and a `lax.scan` over time, no kernel, no cache, nothing imported from
the program or the benchmark (this file lives twice, as
`ray_tpu/models/phi4flash_reference.py` for the tier-1 tests and as
`benchmarks/phi4flash_reference.py`; tests/test_llm_phi4flash.py holds the two
equal). It reads the program's parameter tree, the same weights the cell
serves, a layer at a time, attention in blocks of query positions and the head
in blocks of the vocabulary, so that 2 x 1,032 positions at the published
widths fit beside a served model: `params["layers"]` holds "self" (layers 0 ..
L/2 - 1 as pairs), "mid" (L/2, L/2 + 1) and "cross" (the pairs from L/2 + 2),
each {"mamba" | "gmu", "attn"} stacked over its pairs.

`fault` names ONE term left out, for the controls of the comparison that
holds the program to this file (a sound program read against a faulty
reference differs as a faulty program would against the sound one):
("state_not_carried", starts) and ("tail_not_carried", starts) zero the scan
state / the convolution's three rows at the positions `starts` (where a
program's steps begin); "memory_after_gate" takes M_t = y_t * silu(z_t);
"no_lambda" drops the lambda term; "no_window" lets a window layer see every
token; "bf16_state" rounds the scan state to bfloat16 after every step.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 256           # query positions a block of attention scores
VOCAB_BLOCK = 32768     # rows of the embedding a block of the head


def mamba_sizes(sizes: Dict):
    d = sizes["hidden_size"]
    return (sizes.get("mamba_expand", 2) * d, sizes.get("mamba_d_state", 16),
            sizes.get("mamba_dt_rank") or -(-d // 16),
            sizes.get("mamba_d_conv", 4))


def layer_plan(sizes: Dict):
    """[(role, group, index in the group's stack, published index)]."""
    L = sizes["num_hidden_layers"]
    half = L // 2
    plan = []
    for i in range(L):
        if i < half:
            plan.append(("mamba" if i % 2 == 0 else "window", "self", i // 2,
                         i))
        elif i <= half + 1:
            plan.append(("memory" if i == half else "full", "mid", 0, i))
        else:
            plan.append(("gmu" if i % 2 == 0 else "cross", "cross",
                         (i - half - 2) // 2, i))
    return plan


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


@partial(jax.jit, static_argnames="eps")
def _mlp(x, p, *, eps):
    h = _layer_norm(x, p["mlp_norm_w"], p["mlp_norm_b"], eps)
    g, u = jnp.split(h @ p["fc1"], 2, axis=-1)
    return x + (jax.nn.silu(g) * u) @ p["fc2"]


@partial(jax.jit, static_argnames=("key",))
def _mamba(x, p, resets, *, key):
    """One Mamba mixer: -> (x + out, y before the gate, y after it). `key` =
    (d_i, N, R, K_c, eps, bf16 state); `resets` (2, s) bool: positions where
    the scan state / the convolution's rows before the position are zeroed
    (a control's; all False otherwise)."""
    d_i, N, R, K_c, eps, bf16_state = key
    b, s, _ = x.shape
    h = _layer_norm(x, p["norm_w"], p["norm_b"], eps)
    u, z = jnp.split(h @ p["in_proj"], 2, axis=-1)              # (b, s, d_i)
    # Row t of tap j is u_{t - (K_c - 1) + j}, zero before position 0 and,
    # under the control, before the last step start at or below t.
    t = jnp.arange(s)
    start = jax.lax.cummax(jnp.where(resets[1], t, 0))
    conv = p["conv_b"]
    for j in range(K_c):
        shift = K_c - 1 - j
        src = t - shift
        tap = jnp.where((src >= start)[None, :, None],
                        jnp.roll(u, shift, axis=1), 0.0)
        conv = conv + p["conv_w"][j] * tap
    c = jax.nn.silu(conv)
    rbc = c @ p["x_proj"]
    r, B, C = rbc[..., :R], rbc[..., R:R + N], rbc[..., R + N:]
    dt = jax.nn.softplus(r @ p["dt_proj"] + p["dt_bias"])       # (b, s, d_i)
    A = -jnp.exp(p["A_log"])                                    # (d_i, N)

    def step(state, xs):
        dt_t, c_t, B_t, C_t, reset = xs
        state = jnp.where(reset, 0.0, state)
        state = (jnp.exp(dt_t[..., None] * A) * state
                 + (dt_t * c_t)[..., None] * B_t[:, None, :])
        if bf16_state:   # not a cast pair: XLA elides those on a TPU
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.einsum("bcn,bn->bc", state, C_t)

    move = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((b, d_i, N), F32),
                        (move(dt), move(c), move(B), move(C), resets[0]))
    y = move(y) + p["D"] * c
    gated = y * jax.nn.silu(z)
    return x + gated @ p["out_proj"], y, gated


@partial(jax.jit, static_argnames="eps")
def _gmu(x, p, memory, *, eps):
    h = _layer_norm(x, p["norm_w"], p["norm_b"], eps)
    return x + (memory * jax.nn.silu(h @ p["w1"])) @ p["w2"]


@partial(jax.jit, static_argnames=("key",))
def _qkv(x, p, *, key):
    H, K, hd, eps = key
    b, s, _ = x.shape
    h = _layer_norm(x, p["norm_w"], p["norm_b"], eps)
    qkv = h @ p["wqkv"] + p["bqkv"]
    q = qkv[..., :H * hd].reshape(b, s, H, hd)
    if qkv.shape[-1] == H * hd:         # a cross layer: q alone
        return q, None, None
    k = qkv[..., H * hd:(H + K) * hd].reshape(b, s, K, hd)
    v = qkv[..., (H + K) * hd:].reshape(b, s, K, hd)
    return q, k, v


@partial(jax.jit, static_argnames=("key",))
def _diff_attention(x, p, q, k, v, lam0, *, key):
    """x + the differential attention of q (b, s, H, hd) over k, v (b, s, K,
    hd); `lam0` = lambda_init(i). `key` = (window | None, use lambda, eps)."""
    window, use_lambda, eps = key
    b, s, H, hd = q.shape
    K = k.shape[2]
    pairs, kv_pairs = H // 2, K // 2
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)
    qp = q.reshape(b, s, pairs, 2, hd)
    kp = jnp.repeat(k.reshape(b, s, kv_pairs, 2, hd), pairs // kv_pairs, 2)
    vp = jnp.repeat(v.reshape(b, s, kv_pairs, 2 * hd), pairs // kv_pairs, 2)
    j = jnp.arange(s)[None, :]
    out = []
    for lo in range(0, s, Q_BLOCK):
        i = jnp.arange(lo, min(lo + Q_BLOCK, s))[:, None]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        scores = jnp.einsum("bqpcd,bkpcd->bpcqk", qp[:, lo:lo + Q_BLOCK],
                            kp) / math.sqrt(hd)
        probs = jax.nn.softmax(
            jnp.where(seen[None, None, None], scores, -jnp.inf), -1)
        a = jnp.einsum("bpcqk,bkpv->bqpcv", probs, vp)     # (b, q, p, 2, 2hd)
        o = a[..., 0, :] - lam * a[..., 1, :] if use_lambda else a[..., 0, :]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * p["subln"] * (1.0 - lam0)
        out.append(o.reshape(b, -1, pairs * 2 * hd))
    return x + jnp.concatenate(out, 1) @ p["wo"] + p["bo"]


def hidden(params: Dict, tokens, sizes: Dict, fault=None):
    """tokens (b, s) -> the final LayerNorm's hidden states (b, s, d),
    float32."""
    name, starts = (fault if isinstance(fault, tuple) else (fault, ()))
    H, K = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d, eps = sizes["hidden_size"], sizes["layer_norm_eps"]
    hd = d // H
    d_i, N, R, K_c = mamba_sizes(sizes)
    b, s = tokens.shape
    resets = jnp.zeros((2, s), bool)
    if name in ("state_not_carried", "tail_not_carried"):
        resets = resets.at[int(name == "tail_not_carried"),
                           jnp.asarray(starts)].set(True)
    memory = kv = None
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        for role, group, at, i in layer_plan(sizes):
            stack = params["layers"][group][
                "attn" if role in ("window", "full", "cross") else
                "gmu" if role == "gmu" else "mamba"]
            p = {k: v[at].astype(F32) for k, v in stack.items()}
            if role in ("mamba", "memory"):
                x, y, gated = _mamba(x, p, resets, key=(
                    d_i, N, R, K_c, eps, name == "bf16_state"))
                if role == "memory":
                    memory = gated if name == "memory_after_gate" else y
            elif role == "gmu":
                x = _gmu(x, p, memory, eps=eps)
            else:
                q, k, v = _qkv(x, p, key=(H, K, hd, eps))
                if role == "full":
                    kv = (k, v)
                elif role == "cross":
                    k, v = kv
                window = (sizes["sliding_window"]
                          if role == "window" and name != "no_window"
                          else None)
                x = _diff_attention(x, p, q, k, v, lambda_init(i), key=(
                    window, name != "no_lambda", eps))
            x = _mlp(x, p, eps=eps)
        return _layer_norm(x, params["final_norm_w"].astype(F32),
                           params["final_norm_b"].astype(F32), eps)


def logits_at(params: Dict, tokens, positions, sizes: Dict,
              fault: Optional[object] = None):
    """(logits (b, len(positions), vocab) float32, None): a full forward pass
    over tokens (b, s), read at `positions`; the head is the embedding."""
    x = hidden(params, tokens, sizes, fault)[:, jnp.asarray(positions)]
    embed = params["embed"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [x @ embed[lo:lo + VOCAB_BLOCK].astype(F32).T
             for lo in range(0, embed.shape[0], VOCAB_BLOCK)], -1), None


def loss(params: Dict, tokens, sizes: Dict):
    """Mean next-token cross entropy of tokens (b, s+1), differentiable with
    respect to float32 `params`."""
    x = hidden(params, tokens[:, :-1], sizes)
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(x @ params["embed"].astype(F32).T, -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def loss_and_grad_norm(params: Dict, tokens, sizes: Dict):
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    value, grads = jax.value_and_grad(partial(loss, sizes=sizes))(p32, tokens)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return float(value), float(norm)
