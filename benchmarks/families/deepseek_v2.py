"""Adapter for the DeepSeek-V2 family (latent attention, a leading dense
layer, `group_limited_greedy` expert layers with a shared expert): from a
configuration file's keys to the program's `DeepseekV2Config`, and to the
plain reference in `benchmarks/deepseek_v2_reference.py`. `README.md` ("The
family file") lists every name the harness reads.

Of `sizes`, beside the published keys: `n_routed_experts` counts the experts
HELD by this chip (the guide's convention for an expert share),
`n_routed_experts_published` is the router's width and `first_held_expert`
the first held published id; `n_group` and `topk_group` are over the
published experts.

The family serves and does not train: it brings no `loss_fn`,
`param_logical_axes` or `init_params`. The program's expert layer is the
serving one (sorted pairs through ragged products, no gradient path through a
sharded train step), and training a routed model without drops is ROADMAP S5.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from ray_tpu.models import deepseek_v2

import deepseek_v2_reference as reference
import routing

# What `rehearse.py` shrinks a configuration of this family to: every key of
# `sizes` that holds a whole number, so that no published width stays beside
# a tiny one.
TINY_SIZES = {"hidden_size": 64, "intermediate_size": 96,
              "moe_intermediate_size": 32, "num_hidden_layers": 3,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16,
              "n_routed_experts": 8, "n_routed_experts_published": 16,
              "first_held_expert": 0, "n_shared_experts": 2,
              "num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
              "first_k_dense_replace": 1, "moe_layer_freq": 1,
              "rope_theta": 10000, "routed_scaling_factor": 16,
              "vocab_size": 256, "max_position_embeddings": 256,
              "torch_dtype": "float32"}
BYTES_OF = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

    if (sizes.get("topk_method") != "group_limited_greedy"
            or sizes.get("scoring_func") != "softmax"
            or sizes.get("norm_topk_prob") or sizes.get("attention_bias")
            or sizes.get("tie_word_embeddings")
            or sizes.get("moe_layer_freq") != 1
            or not sizes.get("q_lora_rank")
            or sizes["rope_scaling"].get("type") != "yarn"):
        raise SystemExit("benchmark: a router, rope or projection this "
                         "family does not model")
    rs = sizes["rope_scaling"]
    first = sizes["first_held_expert"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return deepseek_v2.DeepseekV2Config(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        num_attention_heads=sizes["num_attention_heads"],
        q_lora_rank=sizes["q_lora_rank"], kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        n_routed_experts=sizes["n_routed_experts_published"],
        experts_held=(first, first + sizes["n_routed_experts"]),
        n_shared_experts=sizes["n_shared_experts"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        n_group=sizes["n_group"], topk_group=sizes["topk_group"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        first_k_dense_replace=sizes["first_k_dense_replace"],
        rms_norm_eps=float(sizes["rms_norm_eps"]),
        rope_theta=float(sizes["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_original_max_position=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        rope_mscale=float(rs["mscale"]),
        rope_mscale_all_dim=float(rs["mscale_all_dim"]),
        max_position_embeddings=sizes["max_position_embeddings"],
        dtype=dtype)


def _attention_params(sizes: Dict) -> int:
    d, H = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    lat, v = sizes["kv_lora_rank"], sizes["v_head_dim"]
    q = sizes["q_lora_rank"]
    return (d * q + q * H * (nope + rope) + d * (lat + rope)
            + lat * H * (nope + v) + H * v * d)


def train_flops_per_token(sizes: Dict, seq: int) -> float:
    """Operations the forward and backward passes need per token, counting
    the HELD share of the experts: 6 a parameter a token touches (attention,
    the dense feed-forward or the router, the shared experts and top_k x held
    / published routed experts, the head), plus attention at H x (qk + v) x 2
    a query-context pair, x 3. (The benchmark's copy of
    `DeepseekV2Config.flops_per_token`, so that no PR to the program moves
    it.)"""
    d = sizes["hidden_size"]
    expert = 3 * d * sizes["moe_intermediate_size"]
    picks = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
             / sizes["n_routed_experts_published"])
    n_dense = min(sizes["first_k_dense_replace"], sizes["num_hidden_layers"])
    n_moe = sizes["num_hidden_layers"] - n_dense
    dense = _attention_params(sizes) + 3 * d * sizes["intermediate_size"]
    moe = (_attention_params(sizes) + d * sizes["n_routed_experts_published"]
           + (picks + sizes["n_shared_experts"]) * expert)
    n = n_dense * dense + n_moe * moe + d * sizes["vocab_size"]
    return 6.0 * n + 3.0 * attention_flops_per_pair(sizes) * seq


def cache_bytes_per_token(sizes: Dict) -> int:
    """Useful bytes of the latent cache one context token holds over all
    layers: `[c_kv | k_rope]` a layer, with no head axis (the row's padding
    to whole lane tiles is not counted: a floor)."""
    return (sizes["num_hidden_layers"]
            * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
            * BYTES_OF[sizes["torch_dtype"]])


def attention_flops_per_pair(sizes: Dict) -> int:
    """Operations one query-context pair costs over all layers by the
    equations' own count: a head's score over nope + rope dimensions and its
    value sum over v, 2 operations each. A floor: the absorbed form the
    program runs executes H x (W + lat) x 2 a pair, 3.4 x this."""
    return (sizes["num_hidden_layers"] * sizes["num_attention_heads"]
            * (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
               + sizes["v_head_dim"]) * 2)


def reference_logits_at(params, tokens, positions, sizes: Dict):
    return reference.logits_at(params, tokens, positions, sizes)[0]


def reference_logits_routed(params, tokens, positions, sizes: Dict, kept):
    """The reference following the experts the program kept, `kept` (routed
    layers, b, s, top_k) published ids, and the shortfall (routed layers, b,
    s) of every choice it would not have made itself at that point."""
    kept = np.asarray(kept)
    logits, scores = reference.logits_at(params, tokens, positions, sizes,
                                         kept)
    layers, b, s, k = kept.shape
    short = np.stack([
        routing.shortfall(scores[i].reshape(b * s, -1),
                          kept[i].reshape(b * s, k), k, sizes["n_group"],
                          sizes["topk_group"]).reshape(b, s)
        for i in range(layers)])
    return logits, short


reference_loss_and_grad_norm = reference.loss_and_grad_norm
