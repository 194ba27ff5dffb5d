"""Mixtral-family sparse MoE decoder: expert parallelism done the TPU way.

The reference has no expert parallelism of its own — EP exists only inside
vLLM (SURVEY §2.4 EP row: "Absent (vLLM-internal)"). Here it is first-class:
experts are a stacked weight dimension with logical axis "expert" sharded
over the `ep` mesh axis, and token routing is expressed as dense
dispatch/combine einsums over a static per-expert capacity. Under GSPMD this
compiles to the canonical all-to-all dispatch → grouped matmul → all-to-all
combine schedule over ICI; shapes stay static (XLA/MXU-friendly) and dropped
tokens fall out of the capacity mask instead of dynamic shapes.

Architecture: Llama-3 attention (RMSNorm/RoPE/GQA) with the dense SwiGLU MLP
replaced by a top-k softmax router + E SwiGLU experts (Mixtral conventions:
top-k gates renormalized to sum to 1). Aux losses: switch-style load
balancing and router z-loss.

This is the TRAINING layer (capacity dispatch, tokens over the capacity
dropped; ROADMAP S5 replaces it). The serving expert layer lives in
`models/deepseek_v2.py` (`route`, `held_expert_ffn`): a share of the
published experts held, every token routed over all of them, the pairs that
hit a held expert sorted by expert through ragged products, no drops. S5
should reuse that grouped routing rather than grow a third.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import attention_sublayer, next_token_ce
from ray_tpu.ops.layers import rms_norm, rope_frequencies, swiglu


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    d_model: int = 2048
    n_layers: int = 16
    n_heads: int = 16
    n_kv_heads: int = 8
    d_ff: int = 4096            # per-expert FFN width
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    max_seq: int = 2048
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    attention_impl: str = "auto"
    sp_axis: str = "sp"
    balance_loss_coef: float = 0.01
    z_loss_coef: float = 1e-3

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def capacity(self, seq: int) -> int:
        """Static per-expert token capacity for a (batch-row, seq) shard."""
        cap = self.capacity_factor * self.top_k * seq / self.n_experts
        return max(1, math.ceil(cap))

    @staticmethod
    def mixtral_8x7b(**overrides) -> "MoEConfig":
        base = dict(vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
                    n_kv_heads=8, d_ff=14336, n_experts=8, top_k=2,
                    rope_theta=1e6)
        base.update(overrides)
        return MoEConfig(**base)

    @staticmethod
    def tiny(**overrides) -> "MoEConfig":
        base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=96, n_experts=4, top_k=2, max_seq=128)
        base.update(overrides)
        return MoEConfig(**base)

    def num_params(self) -> int:
        d, f, v, L, E = (self.d_model, self.d_ff, self.vocab_size,
                         self.n_layers, self.n_experts)
        hd = self.head_dim
        attn = (d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                + self.n_heads * hd * d)
        moe = d * E + 3 * E * d * f
        return v * d + L * (attn + moe + 2 * d) + d + d * v

    def active_params(self) -> int:
        """Parameters touched per token (top-k of E experts)."""
        d, f, L = self.d_model, self.d_ff, self.n_layers
        hd = self.head_dim
        attn = (d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                + self.n_heads * hd * d)
        moe = d * self.n_experts + 3 * self.top_k * d * f
        return self.vocab_size * d + L * (attn + moe + 2 * d) + d + d * self.vocab_size

    def flops_per_token(self, seq: int) -> float:
        n = self.active_params() - self.vocab_size * self.d_model
        return 6.0 * n + 12 * self.n_layers * self.d_model * seq


# ---------------------------------------------------------------- parameters

def init_params(config: MoEConfig, key: jax.Array) -> Dict:
    d, f, v = config.d_model, config.d_ff, config.vocab_size
    hd, H, K = config.head_dim, config.n_heads, config.n_kv_heads
    L, E = config.n_layers, config.n_experts
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(config.dtype)

    ks = jax.random.split(k_layers, 9)

    params = {
        "embed": dense(k_embed, (v, d), d),
        "layers": {
            "attn_norm": jnp.ones((L, d), dtype=config.dtype),
            "wq": dense(ks[0], (L, d, H * hd), d),
            "wk": dense(ks[1], (L, d, K * hd), d),
            "wv": dense(ks[2], (L, d, K * hd), d),
            "wo": dense(ks[3], (L, H * hd, d), H * hd),
            "mlp_norm": jnp.ones((L, d), dtype=config.dtype),
            # Router stays float32: routing decisions are precision-sensitive.
            "router": jax.random.normal(ks[4], (L, d, E), dtype=jnp.float32)
                      * (1.0 / math.sqrt(d)),
            "w_gate": dense(ks[5], (L, E, d, f), d),
            "w_up": dense(ks[6], (L, E, d, f), d),
            "w_down": dense(ks[7], (L, E, f, d), f),
        },
        "final_norm": jnp.ones((d,), dtype=config.dtype),
        "lm_head": dense(k_head, (d, v), d),
    }
    return params


def param_logical_axes(config: MoEConfig) -> Dict:
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", None),
            # Router is tiny; replicate so every shard routes locally.
            "router": ("layers", "embed", None),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        },
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


# ---------------------------------------------------------------- MoE block

def moe_block(config: MoEConfig, x: jax.Array, router: jax.Array,
              w_gate: jax.Array, w_up: jax.Array,
              w_down: jax.Array) -> Tuple[jax.Array, Dict]:
    """Top-k routed expert FFN with static capacity.

    x: (b, s, d); router: (d, E); w_gate/w_up: (E, d, f); w_down: (E, f, d).
    Returns (out (b, s, d), aux losses dict). Dropped tokens (expert over
    capacity) contribute zero — the residual connection carries them.
    """
    b, s, d = x.shape
    E, k = config.n_experts, config.top_k
    C = config.capacity(s)

    logits = x.astype(jnp.float32) @ router              # (b, s, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)      # (b, s, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)          # Mixtral renorm

    # (b, s, k, E) one-hot of chosen experts.
    sel = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
    # Position of each (token, choice) in its expert's queue. Queue order is
    # choice-rank-major: all top-1 routes enqueue before any top-2 route, so
    # over-capacity drops hit lower-ranked choices first.
    sel_rank = sel.transpose(0, 2, 1, 3).reshape(b, k * s, E)
    pos = (jnp.cumsum(sel_rank, axis=1) - 1.0).reshape(b, k, s, E)
    pos = pos.transpose(0, 2, 1, 3)
    within_cap = pos < C
    sel = sel * within_cap
    slot = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=jnp.float32)
    # masked_slot[b,s,k,e,c] = 1 iff choice k routes token s to expert e at
    # slot c (sel zeroes the slot collisions of unchosen/overflowed entries).
    masked_slot = slot * sel[..., None]
    # dispatch[b, s, e, c] = 1 iff token s goes to expert e at slot c.
    dispatch = masked_slot.sum(axis=2)
    combine = jnp.einsum("bsk,bskec->bsec", gate_vals, masked_slot)

    from ray_tpu.parallel.sharding import constrain

    # Step the token activations down from batch-over-(dp,fsdp,ep) to
    # batch-over-(dp,fsdp) + ep-replicated BEFORE the dispatch einsum: this
    # is the intended EP collective (an all-gather over ep), and without the
    # explicit hop GSPMD falls back to an involuntary full rematerialization
    # (replicate-everything) to reach the expert layout.
    x = constrain(x, ("moe_batch", "seq", None))
    dispatch = constrain(dispatch, ("moe_batch", "seq", None, None))
    xin = jnp.einsum("bsec,bsd->ebcd", dispatch, x.astype(jnp.float32))
    xin = xin.astype(config.dtype)
    # Expert-parallel layout for the dispatched tokens: experts over ep (the
    # dispatch einsum becomes the all-to-all), batch keeps (dp, fsdp), d
    # replicated so the fsdp-sharded expert weights all-gather (FSDP) rather
    # than forcing a degenerate activation reshard.
    xin = constrain(xin, ("expert", "moe_batch", None, None))
    h = swiglu(jnp.einsum("ebcd,edf->ebcf", xin, w_gate),
               jnp.einsum("ebcd,edf->ebcf", xin, w_up))
    h = constrain(h, ("expert", "moe_batch", None, "mlp"))
    out_e = jnp.einsum("ebcf,efd->ebcd", h, w_down)
    out_e = constrain(out_e, ("expert", "moe_batch", None, None))
    combine = constrain(combine, ("moe_batch", "seq", None, None))
    out = jnp.einsum("bsec,ebcd->bsd", combine,
                     out_e.astype(jnp.float32)).astype(x.dtype)
    # Explicit hop back up: batch-over-(dp,fsdp) -> batch-over-(dp,fsdp,ep)
    # (a slice over ep), mirroring the gather on the way in, so the residual
    # add in _layer sees matching layouts.
    out = constrain(out, ("batch", "seq", None))

    # Switch-transformer load-balancing loss: E * sum_e f_e * P_e, where f_e
    # = fraction of (token, choice) pairs routed to e, P_e = mean router prob.
    f_e = jax.nn.one_hot(expert_idx, E).reshape(b, s * k, E).mean(axis=(0, 1))
    p_e = probs.mean(axis=(0, 1))
    balance = E * jnp.sum(f_e * p_e)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    dropped = 1.0 - dispatch.sum() / (b * s * k)
    aux = {"balance_loss": balance, "router_z_loss": z_loss,
           "dropped_frac": dropped}
    return out, aux


# ---------------------------------------------------------------- forward

def _layer(config: MoEConfig, x, layer_params, cos, sin):
    from ray_tpu.models.llama import _gather_layer_params
    from ray_tpu.parallel.sharding import constrain

    # Same explicit FSDP weight all-gather as llama._layer; expert weights
    # keep their ep sharding and gather only the fsdp (embed) factor.
    p = _gather_layer_params(layer_params, extra_axes={
        "router": (None, None),
        "w_gate": ("expert", None, "mlp"),
        "w_up": ("expert", None, "mlp"),
        "w_down": ("expert", "mlp", None),
    })
    # Pin the scan carry (see llama._layer: an unpinned carry lets GSPMD
    # pick a d-over-fsdp layout and full-rematerialize every layer).
    x = constrain(x, ("batch", "seq", None))
    x = attention_sublayer(config, x, p, cos, sin)
    h = rms_norm(x, p["mlp_norm"], config.norm_eps)
    moe_out, aux = moe_block(config, h, p["router"], p["w_gate"], p["w_up"],
                             p["w_down"])
    return x + moe_out, aux


def forward(params: Dict, tokens: jax.Array,
            config: MoEConfig) -> Tuple[jax.Array, Dict]:
    """tokens: (b, s) int32 -> (logits (b, s, vocab) f32, mean aux losses)."""
    from ray_tpu.parallel.sharding import constrain

    cos, sin = rope_frequencies(config.head_dim, config.max_seq,
                                config.rope_theta)
    # Gather the table's fsdp factor before the lookup (see llama.forward).
    table = constrain(params["embed"], ("vocab", None))
    x = table[tokens].astype(config.dtype)
    x = constrain(x, ("batch", "seq", None))

    layer_fn = partial(_layer, config)
    if config.remat:
        layer_fn = jax.checkpoint(
            layer_fn, policy=jax.checkpoint_policies.nothing_saveable)

    def scan_body(x, layer_params):
        x, aux = layer_fn(x, layer_params, cos, sin)
        return x, aux

    x, aux = jax.lax.scan(scan_body, x, params["layers"])
    aux = jax.tree.map(jnp.mean, aux)  # mean over layers
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    x = constrain(x, ("batch", "seq", None))
    lm_head = constrain(params["lm_head"], (None, "vocab"))
    logits = (x @ lm_head.astype(config.dtype)).astype(jnp.float32)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits, aux


def loss_fn(params: Dict, batch: Dict[str, jax.Array],
            config: MoEConfig) -> Tuple[jax.Array, Dict]:
    """Next-token CE + balance/z aux losses. batch: {"tokens": (b, s+1)}."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits, aux = forward(params, inputs, config)
    mask = batch.get("mask")
    ce = next_token_ce(logits, targets,
                       mask[:, 1:] if mask is not None else None)
    loss = (ce + config.balance_loss_coef * aux["balance_loss"]
            + config.z_loss_coef * aux["router_z_loss"])
    metrics = {"loss": ce, "total_loss": loss,
               "tokens": jnp.array(targets.size, jnp.float32), **aux}
    return loss, metrics
