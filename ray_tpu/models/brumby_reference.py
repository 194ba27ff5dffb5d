"""Plain reference for Brumby-14B-Base (https://huggingface.co/manifestai/
Brumby-14B-Base, `config.json`): a dense decoder whose layers mix tokens by
power retention (arXiv:2507.04239, "Scaling Context Requires Rethinking
Attention": power attention of degree p = 2 with a gate), in the block of the
Qwen3-14B decoder the model was retrained from.

With d = hidden_size 5,120, H = 40 query heads and K = 8 kv heads of hd = 128
(G = H / K = 5 query heads a kv head), ff = 17,408, L = 40, eps = rms_norm_eps
1e-6, theta = rope_theta 1e6, no bias but the gate's:

  * Every layer: `x = x + W_o [o_1 .. o_H]` of `h = RMSNorm(x)`, then
    `x = x + W_down (silu(W_gate h') * W_up h')` of `h' = RMSNorm'(x)`. After
    the last layer a final RMSNorm; logits `= h W_head` (untied).
  * `q_t = W_q h_t` (H heads), `k_t = W_k h_t`, `v_t = W_v h_t` (K heads);
    q and k pass a learned hd-wide RMSNorm a head, then rope over all hd
    dimensions (the halves rotated against each other, angle t * theta^(-2i /
    hd)).
  * `log g_t = log sigmoid(W_g h_t + b_g)`, one gate a kv head, float32.
  * For query head h of kv head j = h // G and every s <= t:
    `a_ts = exp(sum_{r=s+1..t} log g_rj) * (q_th . k_sj / sqrt(hd)) ** 2`,
    `o_th = sum_s a_ts v_sj / (sum_s a_ts + eps_r)`, eps_r = 1e-6.

Departures and assumptions (the configuration file lists them under
`assumed`): the public config carries none of the retention's own sizes, so
the degree is the paper's 2; the gate is a sigmoid of a linear map of the
layer's input with a bias, ONE A KV HEAD (K and V, and so the state, are a kv
head's); q / k norms and rope are kept from Qwen3; the scale inside the square
is 1 / sqrt(hd) (it cancels in the ratio and only meets eps_r); eps_r and the
float32 state are this repo's. Left out: training.

Written from that description in straightforward `jax.numpy`: float32
activations, `jax.default_matmul_precision("highest")`, a Python loop over
layers, the ATTENTION form (the t x t weights with the gates' cumulative
logs), in blocks of query positions and the head in blocks of the vocabulary,
so that it fits beside a served model. It never builds the degree-2 features
or a state, so it shares nothing with what it checks (ops/power_retention.py).
No kernel, no cache, nothing imported from the program or the benchmark (this
file lives twice, as `ray_tpu/models/brumby_reference.py` for the tier-1
tests and as `benchmarks/brumby_reference.py`; tests/test_llm_brumby.py holds
the two equal). It reads the program's parameter tree, the same weights the
cell serves: `params["layers"]` stacked over the layers.

`fault` names ONE term left out, for the controls of the comparison that
holds the program to this file (a sound program read against a faulty
reference differs as a faulty program would against the sound one):
("state_not_carried", starts): a position sees nothing before the last of
`starts` at or below it (where a program's steps begin, if it dropped the
state there); "no_gate": g = 1; "no_qk_norm": q and k skip their RMSNorm.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 256           # query positions a block of retention weights
VOCAB_BLOCK = 16384     # columns of the head a block


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (b, s, heads, hd): the halves rotated against each other."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    c, sn = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], -1)


@partial(jax.jit, static_argnames=("key",))
def _retention(x, p, first, *, key):
    """x + the power retention of one layer. `key` = (H, K, hd, eps, eps_r,
    theta, gated, qk norm); `first` (s,): the first position each position
    sees (0 unless a control says otherwise)."""
    H, K, hd, eps, eps_r, theta, gated, qk_norm = key
    b, s, _ = x.shape
    G = H // K
    h = _rms(x, p["attn_norm"], eps)
    q = (h @ p["wq"]).reshape(b, s, H, hd)
    k = (h @ p["wk"]).reshape(b, s, K, hd)
    v = (h @ p["wv"]).reshape(b, s, K, hd)
    log_g = jax.nn.log_sigmoid(h @ p["wg"] + p["bg"])           # (b, s, K)
    if not gated:
        log_g = jnp.zeros_like(log_g)
    if qk_norm:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    through = jnp.repeat(jnp.cumsum(log_g, axis=1), G, axis=2)  # (b, s, H)
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    j = jnp.arange(s)[None, :]
    out = []
    for lo in range(0, s, Q_BLOCK):
        hi = min(lo + Q_BLOCK, s)
        i = jnp.arange(lo, hi)[:, None]
        seen = ((j <= i) & (j >= first[lo:hi, None]))[None, None]
        score = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k) / math.sqrt(hd)
        since = (jnp.moveaxis(through[:, lo:hi], 1, 2)[..., None]
                 - jnp.moveaxis(through, 1, 2)[:, :, None, :])
        a = jnp.where(seen, score * score
                      * jnp.exp(jnp.where(seen, since, 0.0)), 0.0)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v)
        o = o / (jnp.moveaxis(a.sum(-1), 1, 2)[..., None] + eps_r)
        out.append(o.reshape(b, hi - lo, H * hd))
    return x + jnp.concatenate(out, 1) @ p["wo"]


@jax.jit
def _times(a, w):
    """a @ w in float32: ONE weight made float32 at a time (a layer's three
    feed-forward matrices are 1.07 GB in float32 at the published widths,
    beside a served model)."""
    return a @ w.astype(F32)


def _mlp(x, p, *, eps):
    h = _rms(x, p["mlp_norm"].astype(F32), eps)
    hidden = jax.nn.silu(_times(h, p["w_gate"])) * _times(h, p["w_up"])
    return x + _times(hidden, p["w_down"])


def hidden(params: Dict, tokens, sizes: Dict, fault=None):
    """tokens (b, s) -> the final RMSNorm's hidden states (b, s, d),
    float32."""
    name, starts = (fault if isinstance(fault, tuple) else (fault, ()))
    eps = sizes["rms_norm_eps"]
    s = tokens.shape[1]
    first = jnp.zeros((s,), jnp.int32)
    if name == "state_not_carried":
        first = jax.lax.cummax(first.at[jnp.asarray(starts)].set(
            jnp.asarray(starts, jnp.int32)))
    key = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
           sizes["head_dim"], eps, sizes.get("retention_eps", 1e-6),
           float(sizes["rope_theta"]), name != "no_gate",
           name != "no_qk_norm")
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(F32)
        for i in range(sizes["num_hidden_layers"]):
            p = {k: v[i] for k, v in params["layers"].items()}
            x = _retention(x, {k: v.astype(F32) for k, v in p.items()
                               if not k.startswith(("w_", "mlp_"))},
                           first, key=key)
            x = _mlp(x, p, eps=eps)
            del p
        return _rms(x, params["final_norm"].astype(F32), eps)


def logits_at(params: Dict, tokens, positions, sizes: Dict,
              fault: Optional[object] = None):
    """(logits (b, len(positions), vocab) float32, None): a full forward pass
    over tokens (b, s), read at `positions`; the head is `lm_head` (d,
    vocab), untied."""
    x = hidden(params, tokens, sizes, fault)[:, jnp.asarray(positions)]
    head = params["lm_head"]
    with jax.default_matmul_precision("highest"):
        return jnp.concatenate(
            [x @ head[:, lo:lo + VOCAB_BLOCK].astype(F32)
             for lo in range(0, head.shape[1], VOCAB_BLOCK)], -1), None


def loss(params: Dict, tokens, sizes: Dict):
    """Mean next-token cross entropy of tokens (b, s+1), differentiable with
    respect to float32 `params` (the attention form is plain `jax.numpy`)."""
    x = hidden(params, tokens[:, :-1], sizes)
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(x @ params["lm_head"].astype(F32), -1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def loss_and_grad_norm(params: Dict, tokens, sizes: Dict):
    p32 = jax.tree.map(lambda a: a.astype(F32), params)
    value, grads = jax.value_and_grad(partial(loss, sizes=sizes))(p32, tokens)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    return float(value), float(norm)
