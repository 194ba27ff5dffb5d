"""Adapter for the LFM2-MoE family (LFM2-24B-A2B: gated short-convolution
layers three to one beside grouped-query attention with QK-norm and
rotate-half RoPE, leading dense layers, sigmoid top-k expert layers with a
selection bias and no shared expert, the head tied to the embedding): from a
configuration file's keys to the program's `Lfm2MoeConfig`, and to the plain
reference in `benchmarks/lfm2_moe_reference.py`. `README.md` ("The family
file") lists every name the harness reads.

Of `sizes`, beside the published keys: `head_dim` (hidden / heads: the
published key is null), `num_experts` counts the experts HELD by this chip
(the guide's convention for an expert share; the cell holds all of them),
`num_experts_published` is the router's width and `first_held_expert` the
first held published id (`n_routed_experts` repeats the held count under the
key the reader `expert_load_skew.mean` and the reference know).

The cache has two layer groups. `cache_bytes_per_token` counts the K and V
rows of the `full_attention` layers (what `kv_tokens` of a tick reads): USEFUL
bytes, the pair form pads nothing. `state_bytes_per_sequence` is a slot of the
state group: every `conv` layer's two-row tail. For `expert_product_hbm.share`:
`expert_bytes(sizes, met, rows)`.

The family serves and does not train: it brings no `loss_fn`,
`param_logical_axes` or `init_params` (`train_cell` says so in one line);
`train_flops_per_token` and `reference_loss_and_grad_norm` are there because
the harness's own tests hold every family's file to them.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from ray_tpu.models import lfm2_moe

import lfm2_moe_reference as reference
import routing

# What `rehearse.py` shrinks a configuration of this family to: every key of
# `sizes` that holds a whole number (and `layer_types` at the tiny depth), so
# that no published width stays beside a tiny one. Five layers: conv (dense),
# attention, conv, conv, conv; 8 query / 4 kv heads of 64: two kv pairs, a
# pool row a whole lane tile; every one of 16 experts held, as in the cell.
TINY_SIZES = {"hidden_size": 64, "intermediate_size": 96,
              "moe_intermediate_size": 32, "num_hidden_layers": 5,
              "layer_types": ["conv", "full_attention", "conv", "conv",
                              "conv"],
              "num_dense_layers": 1, "num_attention_heads": 8,
              "num_key_value_heads": 4, "head_dim": 64, "conv_L_cache": 3,
              "num_experts": 16, "num_experts_published": 16,
              "n_routed_experts": 16, "first_held_expert": 0,
              "num_experts_per_tok": 4, "routed_scaling_factor": 1,
              "vocab_size": 256, "max_position_embeddings": 256,
              "torch_dtype": "float32"}
BYTES_OF = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

    rope = sizes.get("rope_parameters") or {}
    if (sizes.get("conv_bias") or not sizes.get("norm_topk_prob")
            or not sizes.get("use_expert_bias")
            or rope.get("rope_type") != "default"
            or sizes["n_routed_experts"] != sizes["num_experts"]
            or len(sizes["layer_types"]) != sizes["num_hidden_layers"]):
        raise SystemExit("benchmark: a bias, a router, a rope scaling or a "
                         "layer pattern this family does not model")
    first = sizes["first_held_expert"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return lfm2_moe.Lfm2MoeConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], layer_types=tuple(sizes["layer_types"]),
        num_dense_layers=sizes["num_dense_layers"],
        num_experts=sizes["num_experts_published"],
        experts_held=(first, first + sizes["num_experts"]),
        num_experts_per_tok=sizes["num_experts_per_tok"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        conv_L_cache=sizes["conv_L_cache"],
        rope_theta=float(rope["rope_theta"]),
        norm_eps=float(sizes["norm_eps"]),
        max_position_embeddings=sizes["max_position_embeddings"],
        dtype=dtype)


def _layers(sizes: Dict, kind: str) -> int:
    return sum(1 for k in sizes["layer_types"] if k == kind)


def _expert_params(sizes: Dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def train_flops_per_token(sizes: Dict, seq: int) -> float:
    """Operations the forward and backward passes need per token, counting
    the HELD share of the experts: 6 a parameter a token's products touch (a
    conv mixer's two projections; q and o at H heads, k and v at K; the dense
    feed-forward or the router and top_k x held / published routed experts;
    the head), plus attention at H x 2 head_dim x 2 a query-context pair, x
    3, over the attention layers. (The benchmark's copy of
    `Lfm2MoeConfig.flops_per_token`, so that no PR to the program moves it.)"""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    H, K = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    picks = (sizes["num_experts_per_tok"] * sizes["num_experts"]
             / sizes["num_experts_published"])
    dense = min(sizes["num_dense_layers"], sizes["num_hidden_layers"])
    n = (d * sizes["vocab_size"]
         + _layers(sizes, "conv") * 4 * d * d
         + _layers(sizes, "full_attention") * d * hd * 2 * (H + K)
         + dense * 3 * d * sizes["intermediate_size"]
         + (sizes["num_hidden_layers"] - dense) * (
             d * sizes["num_experts_published"]
             + picks * _expert_params(sizes)))
    pair = H * 2 * hd * 2
    return 6.0 * n + 3.0 * _layers(sizes, "full_attention") * pair * seq


def cache_bytes_per_token(sizes: Dict) -> int:
    """Bytes of cache one context token holds over the layers that keep
    tokens (the attention layers): K and V of each kv head, nothing padded."""
    return (_layers(sizes, "full_attention") * sizes["num_key_value_heads"]
            * 2 * sizes["head_dim"] * BYTES_OF[sizes["torch_dtype"]])


def state_bytes_per_sequence(sizes: Dict) -> int:
    """Bytes of one slot of the state group: every conv layer's tail, the
    last `conv_L_cache` - 1 rows of the convolution's input."""
    return (_layers(sizes, "conv") * (sizes["conv_L_cache"] - 1)
            * sizes["hidden_size"] * BYTES_OF[sizes["torch_dtype"]])


def expert_bytes(sizes: Dict, met: int, rows: int) -> int:
    """Bytes the held experts' products of a tick cannot do without: the
    three matrices of every expert that had a row (`met`, summed over the
    routed layers: a tick record's `experts_met`), once, and every computed
    row (`rows`: `expert_rows`) in at the hidden width and out again, in the
    model's dtype. The hidden layer between the products is left out (a
    fused form would not write it): a floor whatever implements them."""
    item = BYTES_OF[sizes["torch_dtype"]]
    return item * (met * _expert_params(sizes)
                   + rows * 2 * sizes["hidden_size"])


def attention_flops_per_pair(sizes: Dict) -> int:
    """Operations one query-context pair costs over the attention layers: a
    head's score and its value sum, 2 operations a dimension each."""
    return (_layers(sizes, "full_attention") * sizes["num_attention_heads"]
            * 2 * sizes["head_dim"] * 2)


def reference_logits_at(params, tokens, positions, sizes: Dict):
    return reference.logits_at(params, tokens, positions, sizes)[0]


def reference_logits_routed(params, tokens, positions, sizes: Dict, kept):
    """The reference following the experts the program kept, `kept` (routed
    layers, b, s, top_k) published ids, and the shortfall (routed layers, b,
    s) of every choice it would not have made itself at that point: over the
    selection scores, score + bias, with one group."""
    kept = np.asarray(kept)
    logits, found = reference.logits_at(params, tokens, positions, sizes,
                                        kept)
    scores = found["scores"]
    layers, b, s, k = kept.shape
    short = np.stack([
        routing.shortfall(scores[i].reshape(b * s, -1),
                          kept[i].reshape(b * s, k), k, 1, 1).reshape(b, s)
        for i in range(layers)])
    return logits, short


reference_loss_and_grad_norm = reference.loss_and_grad_norm
