"""Adapter for the MiMo-V2-Flash family (full and window attention layers
mixed, a leading dense layer, sigmoid top-k expert layers with a selection
bias and no shared expert): from a configuration file's keys to the program's
`MimoV2FlashConfig`, and to the plain reference in
`benchmarks/mimo_v2_flash_reference.py`. `README.md` ("The family file") lists
every name the harness reads.

Of `sizes`, beside the published keys: `n_routed_experts` counts the experts
HELD by this chip (the guide's convention for an expert share),
`n_routed_experts_published` is the router's width and `first_held_expert`
the first held published id.

The cache has two layer groups. `cache_bytes_per_token` counts the layers that
hold EVERY token of a sequence (the full layers: what `kv_tokens` of a tick
reads); `window_cache_bytes_per_token` the window layers, which hold a
sequence's last `sliding_window` tokens (what `window_kv_tokens` reads).

The family serves and does not train: it brings no `loss_fn`,
`param_logical_axes` or `init_params` (the expert layer has no gradient path
through a sharded train step: ROADMAP S5).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from ray_tpu.models import mimo_v2_flash

import mimo_v2_flash_reference as reference
import routing

# What `rehearse.py` shrinks a configuration of this family to: every key of
# `sizes` that holds a whole number (and the two per-layer patterns at the
# tiny depth), so that no published width stays beside a tiny one. Window 8:
# a rehearsal's contexts pass it many times.
TINY_SIZES = {"hidden_size": 64, "intermediate_size": 96,
              "moe_intermediate_size": 32, "num_hidden_layers": 5,
              "hybrid_layer_pattern": [0, 1, 1, 0, 1],
              "moe_layer_freq": [0, 1, 1, 1, 1],
              "num_attention_heads": 8, "num_key_value_heads": 2,
              "swa_num_attention_heads": 8, "swa_num_key_value_heads": 4,
              "head_dim": 24, "v_head_dim": 16, "swa_head_dim": 24,
              "swa_v_head_dim": 16, "sliding_window": 8,
              "sliding_window_size": 8, "attention_chunk_size": 8,
              "rope_theta": 5000000, "swa_rope_theta": 10000,
              "n_routed_experts": 8, "n_routed_experts_published": 16,
              "first_held_expert": 0, "num_experts_per_tok": 4,
              "n_group": 1, "topk_group": 1,
              "vocab_size": 256, "max_position_embeddings": 256,
              "torch_dtype": "float32"}
BYTES_OF = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

    if (sizes.get("topk_method") != "noaux_tc"
            or sizes.get("scoring_func") != "sigmoid"
            or not sizes.get("norm_topk_prob") or sizes.get("attention_bias")
            or sizes.get("tie_word_embeddings")
            or sizes.get("n_group") != 1 or sizes.get("topk_group") != 1
            or sizes.get("n_shared_experts")
            or sizes.get("routed_scaling_factor")
            or not sizes.get("add_swa_attention_sink_bias")
            or sizes.get("add_full_attention_sink_bias")
            or sizes["swa_head_dim"] != sizes["head_dim"]
            or sizes["swa_v_head_dim"] != sizes["v_head_dim"]
            or sizes["swa_num_attention_heads"]
            != sizes["num_attention_heads"]
            or sizes["sliding_window_size"] != sizes["sliding_window"]
            or len(sizes["hybrid_layer_pattern"])
            != sizes["num_hidden_layers"]
            or len(sizes["moe_layer_freq"]) != sizes["num_hidden_layers"]):
        raise SystemExit("benchmark: a router, a sink or a layer pattern "
                         "this family does not model")
    first = sizes["first_held_expert"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return mimo_v2_flash.MimoV2FlashConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        swa_num_key_value_heads=sizes["swa_num_key_value_heads"],
        head_dim=sizes["head_dim"], v_head_dim=sizes["v_head_dim"],
        partial_rotary_factor=float(sizes["partial_rotary_factor"]),
        rope_theta=float(sizes["rope_theta"]),
        swa_rope_theta=float(sizes["swa_rope_theta"]),
        sliding_window=sizes["sliding_window"],
        attention_value_scale=float(sizes["attention_value_scale"]),
        hybrid_layer_pattern=tuple(sizes["hybrid_layer_pattern"]),
        moe_layer_freq=tuple(sizes["moe_layer_freq"]),
        n_routed_experts=sizes["n_routed_experts_published"],
        experts_held=(first, first + sizes["n_routed_experts"]),
        num_experts_per_tok=sizes["num_experts_per_tok"],
        layernorm_epsilon=float(sizes["layernorm_epsilon"]),
        max_position_embeddings=sizes["max_position_embeddings"],
        dtype=dtype)


def _layers(sizes: Dict, window: bool) -> int:
    return sum(1 for k in sizes["hybrid_layer_pattern"] if bool(k) == window)


def _kv_heads(sizes: Dict, window: bool) -> int:
    return sizes["swa_num_key_value_heads" if window
                 else "num_key_value_heads"]


def _attention_params(sizes: Dict, window: bool) -> int:
    d, H, K = (sizes["hidden_size"], sizes["num_attention_heads"],
               _kv_heads(sizes, window))
    return (d * H * sizes["head_dim"] + d * K * sizes["head_dim"]
            + d * K * sizes["v_head_dim"] + H * sizes["v_head_dim"] * d)


def train_flops_per_token(sizes: Dict, seq: int) -> float:
    """Operations the forward and backward passes need per token, counting
    the HELD share of the experts: 6 a parameter a token touches (attention,
    the dense feed-forward or the router and top_k x held / published routed
    experts, the head), plus attention at H x (qk + v) x 2 a query-context
    pair, x 3: a full layer's token sees `seq` pairs, a window layer's at
    most the window. (The benchmark's copy of
    `MimoV2FlashConfig.flops_per_token`, so that no PR to the program moves
    it.)"""
    d = sizes["hidden_size"]
    expert = 3 * d * sizes["moe_intermediate_size"]
    picks = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
             / sizes["n_routed_experts_published"])
    n = d * sizes["vocab_size"]
    for window, moe in zip(sizes["hybrid_layer_pattern"],
                           sizes["moe_layer_freq"]):
        n += _attention_params(sizes, bool(window)) + (
            d * sizes["n_routed_experts_published"] + picks * expert
            if moe else 3 * d * sizes["intermediate_size"])
    pair = sizes["num_attention_heads"] * (sizes["head_dim"]
                                           + sizes["v_head_dim"]) * 2
    seen = (_layers(sizes, False) * seq
            + _layers(sizes, True) * min(seq, sizes["sliding_window"]))
    return 6.0 * n + 3.0 * pair * seen


def _cache_bytes(sizes: Dict, window: bool) -> int:
    return (_layers(sizes, window) * _kv_heads(sizes, window)
            * (sizes["head_dim"] + sizes["v_head_dim"])
            * BYTES_OF[sizes["torch_dtype"]])


def cache_bytes_per_token(sizes: Dict) -> int:
    """Useful bytes of cache one context token holds over the layers that
    keep EVERY token (the full layers): K and V of each kv head (a K row's
    padding to whole lane tiles is not counted: a floor)."""
    return _cache_bytes(sizes, False)


def window_cache_bytes_per_token(sizes: Dict) -> int:
    """The same over the window layers, which keep a sequence's last
    `sliding_window` tokens."""
    return _cache_bytes(sizes, True)


def attention_flops_per_pair(sizes: Dict) -> int:
    """Operations one query-context pair costs over the layers that see every
    pair (the full layers) by the equations' own count: a head's score over
    qk dimensions and its value sum over v, 2 operations each."""
    return (_layers(sizes, False) * sizes["num_attention_heads"]
            * (sizes["head_dim"] + sizes["v_head_dim"]) * 2)


def reference_logits_at(params, tokens, positions, sizes: Dict):
    return reference.logits_at(params, tokens, positions, sizes)[0]


def reference_logits_routed(params, tokens, positions, sizes: Dict, kept):
    """The reference following the experts the program kept, `kept` (routed
    layers, b, s, top_k) published ids, and the shortfall (routed layers, b,
    s) of every choice it would not have made itself at that point: over the
    selection scores, score + bias, with one group."""
    kept = np.asarray(kept)
    logits, scores = reference.logits_at(params, tokens, positions, sizes,
                                         kept)
    layers, b, s, k = kept.shape
    short = np.stack([
        routing.shortfall(scores[i].reshape(b * s, -1),
                          kept[i].reshape(b * s, k), k, 1, 1).reshape(b, s)
        for i in range(layers)])
    return logits, short


reference_loss_and_grad_norm = reference.loss_and_grad_norm
