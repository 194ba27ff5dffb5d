"""Llama-3-family decoder: the flagship model, pure-JAX and mesh-native.

The reference serves this family through external engines (vLLM for serving,
torch for training — SURVEY §2.3 ray.llm/ray.train rows). Here the model is a
first-class citizen: parameters are a pytree with logical-axis annotations
(ray_tpu.parallel.sharding), the layer stack is a `lax.scan` over stacked
weights (one-layer compile, O(1) HLO size in depth), attention dispatches to
XLA-fused reference, Pallas flash (serving), or ring attention (sp>1), and
the same definition drives training (FSDP/TP/SP) and inference (TP + paged
KV) by swapping rule tables.

Architecture: RMSNorm (pre-norm), RoPE, GQA, SwiGLU — Llama-3 conventions.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import normal
from ray_tpu.ops.attention import (attention, flash_attention,
                                   resolve_attention_impl)
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies, swiglu


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 2048
    n_layers: int = 16
    n_heads: int = 16
    n_kv_heads: int = 8
    d_ff: int = 8192
    max_seq: int = 2048
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "full": recompute everything in backward (min memory, ~fwd again of
    # extra FLOPs). "dots": save matmul outputs without batch dims
    # (projections/MLP), recompute elementwise + attention scores — the
    # usual TPU sweet spot when HBM allows (scaling-book remat recipe).
    remat_policy: str = "full"     # full | dots
    attention_impl: str = "auto"   # reference | flash | ring
    sp_axis: str = "sp"

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def llama3_8b(**overrides) -> "LlamaConfig":
        base = dict(vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
                    n_kv_heads=8, d_ff=14336, rope_theta=500000.0)
        base.update(overrides)
        return LlamaConfig(**base)

    @staticmethod
    def tiny(**overrides) -> "LlamaConfig":
        base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, d_ff=128, max_seq=128)
        base.update(overrides)
        return LlamaConfig(**base)

    def num_params(self) -> int:
        d, f, v, L = self.d_model, self.d_ff, self.vocab_size, self.n_layers
        hd = self.head_dim
        attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        mlp = 3 * d * f
        per_layer = attn + mlp + 2 * d
        return v * d + L * per_layer + d + d * v

    def flops_per_token(self, seq: int) -> float:
        """Training FLOPs/token (fwd+bwd ~= 6*N + attention term)."""
        n = self.num_params() - self.vocab_size * self.d_model  # non-embedding
        attn_flops = 12 * self.n_layers * self.d_model * seq  # 2*2*3 * L * d * s
        return 6.0 * n + attn_flops


# ---------------------------------------------------------------- parameters

def init_params(config: LlamaConfig, key: jax.Array) -> Dict:
    d, f, v = config.d_model, config.d_ff, config.vocab_size
    hd, H, K, L = config.head_dim, config.n_heads, config.n_kv_heads, config.n_layers
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def dense(key, shape, fan_in):     # one program a leaf, eagerly too
        return normal(key, shape, 1.0 / math.sqrt(fan_in), 0.0, config.dtype)

    ks = jax.random.split(k_layers, 7)

    def stack(key, shape, fan_in):
        return dense(key, (L,) + shape, fan_in)

    params = {
        "embed": dense(k_embed, (v, d), d),
        "layers": {
            "attn_norm": jnp.ones((L, d), dtype=config.dtype),
            "wq": stack(ks[0], (d, H * hd), d),
            "wk": stack(ks[1], (d, K * hd), d),
            "wv": stack(ks[2], (d, K * hd), d),
            "wo": stack(ks[3], (H * hd, d), H * hd),
            "mlp_norm": jnp.ones((L, d), dtype=config.dtype),
            "w_gate": stack(ks[4], (d, f), d),
            "w_up": stack(ks[5], (d, f), d),
            "w_down": stack(ks[6], (f, d), f),
        },
        "final_norm": jnp.ones((d,), dtype=config.dtype),
        "lm_head": dense(k_head, (d, v), d),
    }
    return params


def param_logical_axes(config: LlamaConfig) -> Dict:
    """Logical axis names per parameter (see parallel/sharding.py rules)."""
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", None),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


# ---------------------------------------------------------------- forward

def _attention_dispatch(config: LlamaConfig, q, k, v):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import current_mesh

    impl = resolve_attention_impl(config.attention_impl, q.shape)
    mesh = current_mesh()
    if impl == "ring":
        from ray_tpu.parallel.ring import ring_attention

        if mesh is None:
            raise RuntimeError(
                "attention_impl='ring' needs an ambient mesh: wrap the step "
                "in ray_tpu.parallel.mesh.use_mesh(mesh)")
        local = partial(ring_attention, axis_name=config.sp_axis, causal=True)
        seq_axis = config.sp_axis
    elif impl == "flash" and mesh is not None and mesh.size > 1:
        # A Mosaic kernel cannot be partitioned by GSPMD: each device runs
        # it on its own batch rows and heads, the whole sequence local.
        local = partial(flash_attention, causal=True)
        seq_axis = None
    else:
        return attention(q, k, v, causal=True, impl=impl)
    spec = P(("dp", "fsdp", "ep"), seq_axis, "tp", None)
    # check_vma=False: the flash kernel's interpret-mode discharge hits
    # a jax vma propagation gap on dynamic_slice indices (jax suggests
    # exactly this workaround); Mosaic lowering is unaffected.
    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec, check_vma=False)
    return fn(q, k, v)


def attention_sublayer(config, x, p, cos, sin):
    """Pre-norm GQA attention block with residual. Shared by every decoder
    family in models/ (config needs head_dim/n_heads/n_kv_heads/norm_eps and
    the attention_impl fields _attention_dispatch reads)."""
    from ray_tpu.parallel.sharding import constrain

    b, s, d = x.shape
    hd, H, K = config.head_dim, config.n_heads, config.n_kv_heads
    h = rms_norm(x, p["attn_norm"], config.norm_eps)
    # Constrain every projection OUTPUT to batch-sharded: with fsdp-sharded
    # weights, GSPMD then all-gathers the weights (the FSDP recipe) instead
    # of resharding the activation embed-over-fsdp, which degenerates into
    # an involuntary full rematerialization per layer.
    q = constrain((h @ p["wq"]).reshape(b, s, H, hd),
                  ("batch", "seq", "heads", None))
    k = constrain((h @ p["wk"]).reshape(b, s, K, hd),
                  ("batch", "seq", "kv_heads", None))
    v = constrain((h @ p["wv"]).reshape(b, s, K, hd),
                  ("batch", "seq", "kv_heads", None))
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    attn_out = _attention_dispatch(config, q, k, v)
    out = attn_out.reshape(b, s, H * hd) @ p["wo"]
    return x + constrain(out, ("batch", "seq", None))


def next_token_ce(logits: jax.Array, targets: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean next-token cross entropy; mask (same shape as targets) optional."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    return -ll.mean()


# Per-layer param layout INSIDE the scan: "embed" gathered (None) so each
# layer's weights are explicitly all-gathered over fsdp right before use —
# the FSDP recipe (gather weights, compute, discard; grads reduce-scatter
# back through the constraint's transpose). Left implicit, GSPMD instead
# reshards the batch-sharded activation embed-over-fsdp, which degenerates
# into an involuntary full rematerialization per layer. tp axes stay.
_LAYER_GATHER_AXES = {
    "attn_norm": (None,),
    "wq": (None, "heads"),
    "wk": (None, "kv_heads"),
    "wv": (None, "kv_heads"),
    "wo": ("heads", None),
    "mlp_norm": (None,),
    "w_gate": (None, "mlp"),
    "w_up": (None, "mlp"),
    "w_down": ("mlp", None),
}


def _gather_layer_params(p, extra_axes=None):
    from ray_tpu.parallel.sharding import constrain

    axes = dict(_LAYER_GATHER_AXES)
    if extra_axes:
        axes.update(extra_axes)
    return {k: (constrain(v, axes[k]) if k in axes else v)
            for k, v in p.items()}


def _layer(config: LlamaConfig, x, layer_params, cos, sin):
    """One decoder layer. x: (b, s, d)."""
    from ray_tpu.parallel.sharding import constrain

    p = _gather_layer_params(layer_params)
    # Keep the loop-carried activation on (batch, seq, None) inside the
    # scan: left to propagation, GSPMD picks a d-over-fsdp carry sharding
    # (resharding activations instead of all-gathering weights) and
    # full-rematerializes every layer.
    x = constrain(x, ("batch", "seq", None))
    x = attention_sublayer(config, x, p, cos, sin)
    h = rms_norm(x, p["mlp_norm"], config.norm_eps)
    gate = constrain(h @ p["w_gate"], ("batch", "seq", "mlp"))
    up = constrain(h @ p["w_up"], ("batch", "seq", "mlp"))
    x = x + constrain(swiglu(gate, up) @ p["w_down"], ("batch", "seq", None))
    return x


def backbone(params: Dict, tokens: jax.Array, config: LlamaConfig) -> jax.Array:
    """tokens: (b, s) int32 -> final-norm hidden states (b, s, d) in
    config.dtype — everything `forward` computes except the lm_head
    projection. Value heads and reward models (rlhf/) hang off this."""
    from ray_tpu.parallel.sharding import constrain

    cos, sin = rope_frequencies(config.head_dim, config.max_seq, config.rope_theta)
    # Deliberately all-gather the table's fsdp (embed) factor before the
    # lookup (rows stay vocab-sharded over tp); the backward reduce-scatters
    # the table grad through the constraint's transpose. Left implicit,
    # GSPMD wants the gather cotangent embed-over-fsdp and falls back to an
    # involuntary full rematerialization.
    table = constrain(params["embed"], ("vocab", None))
    x = table[tokens].astype(config.dtype)
    x = constrain(x, ("batch", "seq", None))

    layer_fn = partial(_layer, config)
    if config.remat:
        if config.remat_policy not in ("full", "dots", "flash"):
            raise ValueError(
                f"remat_policy must be 'full', 'dots' or 'flash', "
                f"got {config.remat_policy!r}")
        # "flash": save ONLY the flash-attention kernel outputs (out +
        # lse, tagged in ops/attention.py) — O(s) extra memory per layer,
        # and the backward skips re-running the O(s^2) forward kernel
        # (its other residuals, q/k/v, are cheap dot recomputes from the
        # saved layer input). The long-context policy: "dots" busts HBM
        # past ~8k, full remat pays the quadratic kernel twice.
        policy = {
            "full": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "flash": jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"),
        }[config.remat_policy]
        layer_fn = jax.checkpoint(layer_fn, policy=policy)

    def scan_body(x, layer_params):
        return layer_fn(x, layer_params, cos, sin), None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], config.norm_eps)
    return constrain(x, ("batch", "seq", None))


def forward(params: Dict, tokens: jax.Array, config: LlamaConfig) -> jax.Array:
    """tokens: (b, s) int32 -> logits (b, s, vocab) float32."""
    from ray_tpu.parallel.sharding import constrain

    x = backbone(params, tokens, config)
    # lm_head: gather the fsdp (embed/contracting) factor, keep vocab on tp.
    lm_head = constrain(params["lm_head"], (None, "vocab"))
    logits = (x @ lm_head.astype(config.dtype)).astype(jnp.float32)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits


def loss_fn(params: Dict, batch: Dict[str, jax.Array],
            config: LlamaConfig) -> Tuple[jax.Array, Dict]:
    """batch: {"tokens": (b, s+1) int32} -> next-token cross entropy."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = forward(params, inputs, config)
    mask = batch.get("mask")
    loss = next_token_ce(logits, targets,
                         mask[:, 1:] if mask is not None else None)
    return loss, {"loss": loss, "tokens": jnp.array(targets.size, jnp.float32)}
