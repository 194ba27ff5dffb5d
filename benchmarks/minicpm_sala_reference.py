"""Plain reference for MiniCPM-SALA (https://huggingface.co/openbmb/
MiniCPM-SALA, `config.json`, `model_type: minicpm_sala`; OpenBMB's
MiniCPM-SALA report, 2026-02; the sparse mixer `minicpm4` is InfLLM-V2 of the
MiniCPM4 report, arXiv:2506.07900; the linear mixer is Lightning Attention-2,
arXiv:2401.04658, with MiniMax-01's decays by depth).

The forward pass, as published (hidden 4096, 32 layers, vocabulary 73,448,
untied head, RMSNorm eps 1e-6): `x_0 = scale_emb E[token]` (12); every layer
i `x <- x + c Mixer_i(RMSNorm(x))`, then `x <- x + c MLP(RMSNorm(x))`, `c =
scale_depth / sqrt(mup_denominator)` = 1.4 / sqrt(32), `MLP(u) = W_d (silu(W_g
u) * W_u u)`; a final RMSNorm; `logits = W_head (x / (hidden_size /
dim_model_base))` (/ 16). `u_t` is the normed input of token t, `g(h) = h //
16` the kv head of query head h.

  * `lightning-attn`: `q, k, v = W_q u, W_k u, W_v u` (32 heads x 128 each);
    `q, k <- RMSNorm_128(q), RMSNorm_128(k)` a head (`qk_norm`), then rotated
    at t (`lightning_use_rope`: theta 10,000, the whole head, lane i with lane
    i + 64); `S_t,h = lambda_h S_(t-1),h + v_t,h k_t,h^T` (128 x 128, zero
    before position 0), `o_t,h = S_t,h q_t,h / sqrt(128)`; `o <-
    RMSNorm_128(o)` a head (`use_output_norm`), `o <- o * sigmoid(W_z u)`
    (`use_output_gate`), `Mixer = W_o o`. `lambda_h = exp(-s_h)`, `s_h =
    2^(-8 h / 32) (1 - l / 31 + 1e-5)`, h = 1..32, at PUBLISHED layer l.
  * `minicpm4`: `q = W_q u` (32 x 128), `k, v = W_k u, W_v u` (2 x 128), no
    bias, QK-norm as above, NOTHING rotated (`attn_use_rope` false). With n =
    t + 1 context tokens. n <= `dense_len`: `o_t,h = sum_(s<=t) softmax_s(q_t,h
    . k_s,g / sqrt(128)) v_s,g`. Else: kernel j covers tokens [16 j, 16 j +
    32) and counts once whole (16 j + 32 <= n): `kbar_j,g` the mean of its
    keys; `r_t,h,j = softmax_j(q_t,h . kbar_j,g(h) / sqrt(128))` over those
    kernels; `R_t,g,j = sum_(h in g) r_t,h,j`; block b = tokens [64 b, 64 b +
    64): `score_t,g,b = max_(j = 4b-1 .. 4b+3) R_t,g,j`; block 0
    (`init_blocks` 1) and the 32 blocks that end at the token's own
    (`window_size` 2,048) score +inf; the kv head keeps its `topk` 64 best
    blocks b <= t // 64 (ties: the lower), forced ones among them; `o_t,h` the
    softmax over s <= t in g(h)'s kept blocks. Then `o <- o * sigmoid(W_z u)`
    (`attn_use_output_gate`), `Mixer = W_o o`.

Departures and assumptions (the configuration file lists them under
`assumed`; `config.json` carries none of them): `sparse_config`'s values are
MiniCPM4's; the softmax stands BEFORE the sum over a kv head's queries and the
max-pool; forced blocks count inside the 64; a part-filled kernel is not
scored; the decays as above; no feature map on q and k beyond the norm; the
output norm is an RMSNorm a head with a weight of 128; both gates are sigmoids
of a projection of `u`; the residual's factor divides by
sqrt(`mup_denominator`); rotation is the `rotate_half` convention.

`kept`, where given, is the program's selection ((sparse layers, b, s, K,
topk) block ids and (sparse layers, b, s, K) their count, 0 for a token that
did not select): the reference then FOLLOWS it, with its own scores and its
own everything else, and reports for every (layer, token, kv head) the
SHORTFALL of a choice it would not have made: 1 - the program's worst free
block's score over the reference's `topk`-th, 0 where the sets agree (a bf16
tie moves a block and one block moves every later layer: a routed family's
check does the same for experts). `fault` names one term changed, for the
controls of chip_smoke.py's `minicpm_sala_check` and the tests:
"dense_above" (dense attention whatever the context), "shared_selection"
(both kv heads attend under kv head 0's blocks), ("state_not_carried", starts)
(S starts anew at every position in `starts`).

Written from that description in straightforward `jax.numpy`: float32
activations, `jax.default_matmul_precision("highest")`, the recurrence a row
at a time, the sparse layer a block of QUERY_BLOCK queries at a time so that
16k tokens fit, no kernel, no cache, nothing imported from the program or the
benchmark (this file lives twice, as `ray_tpu/models/
minicpm_sala_reference.py` for the tier-1 tests and as `benchmarks/
minicpm_sala_reference.py`; tests/test_llm_minicpm_sala.py holds the two
equal). It reads the program's parameter tree, the same bf16 weights the cell
serves, a layer at a time: `params["layers"][kind]` stacks the layers of one
kind ("sparse", "lightning") in the published order.

`sizes` is the configuration file's keys: the published ones, and
`first_published_layer` / `published_layers` (where the run's first layer
stands: the decays'), `kernel_size`, `kernel_stride`, `block_size`, `topk`,
`init_blocks`, `window_size`, `dense_len`.
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
QUERY_BLOCK = 256       # queries of a sparse layer a block
MLP_ROWS = 4096         # positions of an MLP a block
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


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


@partial(jax.jit, static_argnames=("key",))
def _lightning(h, p, decay, fresh, *, key):
    """What one lightning layer's mixer gives, of the normed rows h (b, s,
    d). `key` = (H, hd, theta, eps); decay (H,) = s_h; `fresh` (s,) bool: the
    positions at which S starts anew (position 0, unless a control says
    more)."""
    H, hd, theta, eps = key
    b, s, _ = h.shape
    heads = lambda w: (h @ w).reshape(b, s, H, hd)
    q = _rotate(_rms(heads(p["wq"]), p["q_norm"], eps), theta)
    k = _rotate(_rms(heads(p["wk"]), p["k_norm"], eps), theta)
    v = heads(p["wv"])
    lam = jnp.exp(-decay)[None, :, None, None]

    def step(S, xs):
        q_t, k_t, v_t, fresh_t = xs
        S = jnp.where(fresh_t, 0.0, S)
        S = lam * S + v_t[..., :, None] * k_t[..., None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, q_t) / math.sqrt(hd)

    t = lambda a: jnp.moveaxis(a, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((b, H, hd, hd), F32),
                        (t(q), t(k), t(v), fresh))
    o = _rms(jnp.moveaxis(o, 0, 1), p["o_norm"], eps).reshape(b, s, H * hd)
    return (o * jax.nn.sigmoid(h @ p["wz"])) @ p["wo"]


def _top_mask(values, count):
    """True at the `count` (..., 1) largest of each row; ties: the lower
    index."""
    order = jnp.argsort(-values, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return rank < count


@partial(jax.jit, static_argnames=("key",))
def _sparse_queries(q, k, v, kbar, q0, kept, kept_n, *, key):
    """Queries [q0, q0 + Q) of one sparse layer: q (b, Q, K, G, hd) against
    the whole k, v (b, s, K, hd) and the kernels' keys kbar (b, NK, K, hd).
    kept (b, Q, K, topk) / kept_n (b, Q, K): the blocks to attend under where
    kept_n > 0, else the reference's own. -> (o (b, Q, K, G, hd), shortfall
    (b, Q, K), differ (b, Q, K) bool, selects (b, Q))."""
    (stride, per, topk, init, window, dense_len, fault) = key
    b, Q, K, G, hd = q.shape
    s, NK = k.shape[1], kbar.shape[1]
    block = per * stride
    NB = -(-s // block)
    t = q0 + jnp.arange(Q)                                      # positions
    n = t + 1
    selects = n > dense_len if fault != "dense_above" else n < 0
    # first stage: softmax over the whole kernels, summed over a kv head's
    # queries, max-pooled to blocks
    whole = jnp.maximum(n // stride - 1, 0)
    ok = jnp.arange(NK)[None, :] < whole[:, None]               # (Q, NK)
    sc = jnp.einsum("bqkgd,bjkd->bqkgj", q, kbar) / math.sqrt(hd)
    okb = ok[None, :, None, None, :]
    r = jnp.where(okb, jax.nn.softmax(jnp.where(okb, sc, -jnp.inf), -1), 0.0)
    r = jnp.where(whole[None, :, None, None, None] > 0, r, 0.0)
    R = r.sum(3)                                            # (b, Q, K, NK)
    R = jnp.pad(R, ((0, 0),) * 3 + ((1, NB * per - NK),))   # R[j] at j + 1
    by_block = jnp.stack([R[..., a:a + NB * per:per]
                          for a in range(per + 1)], -1).max(-1)
    own = t // block
    at = jnp.arange(NB)
    forced = (at[None, :] < init) | (at[None, :] > own[:, None]
                                     - window // block)
    score = jnp.where(forced[None, :, None, :], jnp.inf, by_block)
    score = jnp.where((at[None, :] <= own[:, None])[None, :, None, :], score,
                      -jnp.inf)
    count = jnp.minimum(own + 1, topk)[None, :, None, None]
    mine = _top_mask(score, count)                          # (b, Q, K, NB)
    # the program's choice, where it made one
    theirs = jnp.any((kept[..., None] == at) & (
        jnp.arange(kept.shape[-1])[:, None] < kept_n[..., None, None]), -2)
    follow = (kept_n > 0)[..., None]
    keep = jnp.where(follow, theirs, mine)
    kth = jnp.min(jnp.where(mine, score, jnp.inf), -1)
    worst = jnp.min(jnp.where(keep, score, jnp.inf), -1)
    short = jnp.where(jnp.isfinite(kth) & (kth > 0) & jnp.isfinite(worst),
                      jnp.maximum(0.0, 1.0 - worst / kth), 0.0)
    differ = jnp.any(keep != mine, -1)
    sel = selects[None, :, None]
    short, differ = jnp.where(sel, short, 0.0), differ & sel
    if fault == "shared_selection":
        keep = jnp.broadcast_to(keep[:, :, :1], keep.shape)
    # second stage: the tokens of the kept blocks (all where the token sees
    # no more than dense_len), not after me
    seen = jnp.arange(s)[None, :] <= t[:, None]                 # (Q, s)
    inside = jnp.repeat(keep, block, axis=-1)[..., :s]      # (b, Q, K, s)
    seen = seen[None, :, None, :] & jnp.where(sel[..., None], inside, True)
    logits = jnp.einsum("bqkgd,bckd->bqkgc", q, k) / math.sqrt(hd)
    probs = jax.nn.softmax(
        jnp.where(seen[:, :, :, None, :], logits, -jnp.inf), -1)
    return (jnp.einsum("bqkgc,bckd->bqkgd", probs, v), short, differ,
            jnp.broadcast_to(selects[None], (b, Q)))


def _sparse(h, p, sizes: Dict, kept=None, fault=None):
    """What one sparse layer's mixer gives, of the normed rows h (b, s, d),
    QUERY_BLOCK queries at a time. -> (rows, shortfall (b, s, K), differ (b,
    s, K), selects (b, s), the attention's output before its gate (b, s, H
    hd))."""
    H, K, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                sizes["head_dim"])
    eps, stride = sizes["rms_norm_eps"], sizes["kernel_stride"]
    b, s, _ = h.shape
    q = _rms((h @ p["wq"]).reshape(b, s, K, H // K, hd), p["q_norm"], eps)
    k = _rms((h @ p["wk"]).reshape(b, s, K, hd), p["k_norm"], eps)
    v = (h @ p["wv"]).reshape(b, s, K, hd)
    # kernel j: tokens [stride j, stride j + 2 stride), two pages' means
    pages = s // stride
    m = k[:, :pages * stride].reshape(b, pages, stride, K, hd).mean(2)
    kbar = 0.5 * (m[:, :-1] + m[:, 1:]) if pages > 1 else jnp.zeros(
        (b, 1, K, hd), F32)
    key = (stride, sizes["block_size"] // stride, sizes["topk"],
           sizes["init_blocks"], sizes["window_size"], sizes["dense_len"],
           fault)
    topk = sizes["topk"]
    if kept is None:
        ids = jnp.zeros((b, s, K, topk), jnp.int32)
        ns = jnp.zeros((b, s, K), jnp.int32)
    else:
        ids, ns = (jnp.asarray(a, jnp.int32) for a in kept)
    Q = min(QUERY_BLOCK, s)
    pad = -s % Q
    padq = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    qp, ids, ns = padq(q), padq(ids), padq(ns)
    outs = [_sparse_queries(qp[:, a:a + Q], k, v, kbar, a, ids[:, a:a + Q],
                            ns[:, a:a + Q], key=key)
            for a in range(0, s + pad, Q)]
    o, short, differ, selects = (jnp.concatenate(parts, 1)[:, :s]
                                 for parts in zip(*outs))
    o = o.reshape(b, s, H * hd)
    return ((o * jax.nn.sigmoid(h @ p["wz"])) @ p["wo"], short, differ,
            selects, o)


@jax.jit
def _mlp_rows(h, gate, up, down):
    return (jax.nn.silu(h @ gate.astype(F32)) * (h @ up.astype(F32))
            ) @ down.astype(F32)


def _mlp(h, gate, up, down):
    """MLP_ROWS positions at a time: 16k positions' hidden layer is 1 GB."""
    return jnp.concatenate([_mlp_rows(h[:, a:a + MLP_ROWS], gate, up, down)
                            for a in range(0, h.shape[1], MLP_ROWS)], 1)


def layer_plan(sizes: Dict):
    """[(kind, index in that kind's stack)] in the published order."""
    plan, seen = [], {}
    for name in sizes["mixer_types"]:
        kind = KINDS[name]
        plan.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return plan


def decays(sizes: Dict):
    """s_h of every layer of the run (0 for a sparse one), (layers, H)."""
    H = sizes["lightning_nh"]
    slopes = 2.0 ** (-8.0 * np.arange(1, H + 1) / H)
    depth = [1.0 - (sizes["first_published_layer"] + li)
             / (sizes["published_layers"] - 1) + 1e-5
             for li in range(len(sizes["mixer_types"]))]
    return np.asarray(depth)[:, None] * slopes[None, :]


def _forward(params: Dict, tokens, sizes: Dict, kept=None, fault=None,
             watch=None):
    """tokens (b, s) -> (final-norm hidden states over 16 (b, s, d) float32,
    per sparse layer (shortfall, differ, selects, the attention's output at
    positions `watch`))."""
    name, starts = fault if isinstance(fault, tuple) else (fault, ())
    eps = sizes["rms_norm_eps"]
    b, s = tokens.shape
    fresh = jnp.zeros((s,), bool).at[0].set(True)
    if name == "state_not_carried":
        fresh = fresh.at[jnp.asarray(starts, jnp.int32)].set(True)
    c = sizes["scale_depth"] / math.sqrt(sizes["mup_denominator"])
    light_key = (sizes["lightning_nh"], sizes["lightning_head_dim"],
                 float(sizes["rope_theta"]), eps)
    s_h = decays(sizes)
    found, sparse = [], 0
    with jax.default_matmul_precision("highest"):
        x = sizes["scale_emb"] * params["embed"][tokens].astype(F32)
        for li, (kind, i) in enumerate(layer_plan(sizes)):
            p = {k: v[i].astype(F32) if not k.startswith("w_") else v[i]
                 for k, v in params["layers"][kind].items()}
            h = _rms(x, p["norm"], eps)
            if kind == "lightning":
                out = _lightning(h, p, jnp.asarray(s_h[li], F32), fresh,
                                 key=light_key)
            else:
                mine = None if kept is None else (kept[0][sparse],
                                                  kept[1][sparse])
                out, *rest, attended = _sparse(h, p, sizes, mine, name)
                found.append(rest + [attended[:, jnp.asarray(
                    [] if watch is None else watch, jnp.int32)]])
                sparse += 1
            x = x + c * out
            x = x + c * _mlp(_rms(x, p["mlp_norm"], eps), p["w_gate"],
                             p["w_up"], p["w_down"])
        x = _rms(x, params["final_norm"].astype(F32), eps)
        return x / (sizes["hidden_size"] / sizes["dim_model_base"]), found


def hidden(params: Dict, tokens, sizes: Dict, kept=None, fault=None,
           watch=None):
    """tokens (b, s) -> (hidden states before the head (b, s, d) float32,
    {"shortfall", "differ" (sparse layers, b, s, K), "selects" (sparse
    layers, b, s), "attended" (sparse layers, b, len(watch), H hd)} as
    numpy)."""
    x, found = _forward(params, tokens, sizes, kept, fault, watch)
    names = ("shortfall", "differ", "selects", "attended")
    return x, {name: np.stack([np.asarray(f[i]) for f in found])
               for i, name in enumerate(names)} if found else {}


def logits_at(params: Dict, tokens, positions, sizes: Dict,
              kept: Optional[tuple] = None, fault=None, watch=None):
    """(logits (b, len(positions), vocab) float32, what `hidden` found, the
    sparse layers' attention outputs at `watch` (`positions` where None)
    among it): a full forward pass over tokens (b, s), read at `positions`;
    the head is `lm_head` (d, vocab), untied."""
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
    respect to float32 `params` (the selection is not)."""
    x, _ = _forward(params, tokens[:, :-1], sizes)
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(x @ params["lm_head"].astype(F32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def loss_and_grad_norm(params: Dict, tokens, sizes: Dict):
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    value, grads = jax.value_and_grad(partial(loss, sizes=sizes))(p32, tokens)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return float(value), float(norm)
