"""Adapter for the AFMoE family (Trinity-Large-Preview: gated grouped-query
attention with QK-norm under sandwich norms, rotated window layers three to
one beside unrotated full layers, leading dense layers, sigmoid top-k expert
layers with a selection bias, a route scale and one shared expert): from a
configuration file's keys to the program's `AfmoeConfig`, and to the plain
reference in `benchmarks/afmoe_reference.py`. `README.md` ("The family file")
lists every name the harness reads.

Of `sizes`, beside the published keys: `num_experts` counts the experts HELD
by this chip (the guide's convention for an expert share),
`num_experts_published` is the router's width and `first_held_expert` the
first held published id (`n_routed_experts` repeats the held count under the
key the reader `expert_load_skew.mean` and the reference know).

The cache has two layer groups. `cache_bytes_per_token` counts the layers that
hold EVERY token of a sequence (the full layers: what `kv_tokens` of a tick
reads); `window_cache_bytes_per_token` the window layers, which hold a
sequence's last `sliding_window` tokens (what `window_kv_tokens` reads). For
this PR's reader `expert_product_hbm.share`: `expert_bytes(sizes, met, rows)`.

The family serves and does not train: it brings no `loss_fn`,
`param_logical_axes` or `init_params` (the expert layer has no gradient path
through a sharded train step: ROADMAP S5).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from ray_tpu.models import afmoe

import afmoe_reference as reference
import routing

# What `rehearse.py` shrinks a configuration of this family to: every key of
# `sizes` that holds a whole number (and `layer_types` at the tiny depth), so
# that no published width stays beside a tiny one. Window 8: a rehearsal's
# contexts pass it many times; 6 query heads a kv head, as published.
TINY_SIZES = {"hidden_size": 64, "intermediate_size": 96,
              "moe_intermediate_size": 32, "num_hidden_layers": 5,
              "layer_types": ["sliding_attention", "sliding_attention",
                              "sliding_attention", "full_attention",
                              "sliding_attention"],
              "num_dense_layers": 1, "num_attention_heads": 12,
              "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 8,
              "global_attn_every_n_layers": 4, "rope_theta": 10000,
              "num_experts": 8, "num_experts_published": 16,
              "n_routed_experts": 8, "first_held_expert": 0,
              "num_experts_per_tok": 4, "num_shared_experts": 1,
              "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
              "num_limited_groups": 1, "vocab_size": 256,
              "max_position_embeddings": 256, "torch_dtype": "float32"}
BYTES_OF = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

    if (sizes.get("score_func") != "sigmoid" or not sizes.get("route_norm")
            or not sizes.get("mup_enabled") or sizes.get("rope_scaling")
            or sizes.get("tie_word_embeddings")
            or sizes.get("hidden_act") != "silu"
            or any(sizes.get(k) != 1 for k in (
                "n_group", "topk_group", "num_expert_groups",
                "num_limited_groups", "num_shared_experts"))
            or sizes["n_routed_experts"] != sizes["num_experts"]
            or len(sizes["layer_types"]) != sizes["num_hidden_layers"]):
        raise SystemExit("benchmark: a router, a rope scaling or a layer "
                         "pattern this family does not model")
    first = sizes["first_held_expert"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return afmoe.AfmoeConfig(
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
        num_shared_experts=sizes["num_shared_experts"],
        route_scale=float(sizes["route_scale"]),
        sliding_window=sizes["sliding_window"],
        rope_theta=float(sizes["rope_theta"]),
        rms_norm_eps=float(sizes["rms_norm_eps"]),
        max_position_embeddings=sizes["max_position_embeddings"],
        dtype=dtype)


def _layers(sizes: Dict, window: bool) -> int:
    return sum(1 for k in sizes["layer_types"]
               if (k == "sliding_attention") == window)


def _expert_params(sizes: Dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["moe_intermediate_size"]


def train_flops_per_token(sizes: Dict, seq: int) -> float:
    """Operations the forward and backward passes need per token, counting
    the HELD share of the experts: 6 a parameter a token touches (q, the gate
    and o at H heads, k and v at K; the dense feed-forward or the router, the
    shared expert and top_k x held / published routed experts; the head),
    plus attention at H x 2 head_dim x 2 a query-context pair, x 3: a full
    layer's token sees `seq` pairs, a window layer's at most the window. (The
    benchmark's copy of `AfmoeConfig.flops_per_token`, so that no PR to the
    program moves it.)"""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    H, K = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    picks = (sizes["num_experts_per_tok"] * sizes["num_experts"]
             / sizes["num_experts_published"])
    dense = min(sizes["num_dense_layers"], sizes["num_hidden_layers"])
    n = (d * sizes["vocab_size"]
         + sizes["num_hidden_layers"] * d * hd * (3 * H + 2 * K)
         + dense * 3 * d * sizes["intermediate_size"]
         + (sizes["num_hidden_layers"] - dense) * (
             d * sizes["num_experts_published"]
             + (picks + sizes["num_shared_experts"]) * _expert_params(sizes)))
    pair = H * 2 * hd * 2
    seen = (_layers(sizes, False) * seq
            + _layers(sizes, True) * min(seq, sizes["sliding_window"]))
    return 6.0 * n + 3.0 * pair * seen


def _cache_bytes(sizes: Dict, window: bool) -> int:
    return (_layers(sizes, window) * sizes["num_key_value_heads"]
            * 2 * sizes["head_dim"] * BYTES_OF[sizes["torch_dtype"]])


def cache_bytes_per_token(sizes: Dict) -> int:
    """Bytes of cache one context token holds over the layers that keep
    EVERY token (the full layers): K and V of each kv head."""
    return _cache_bytes(sizes, False)


def window_cache_bytes_per_token(sizes: Dict) -> int:
    """The same over the window layers, which keep a sequence's last
    `sliding_window` tokens."""
    return _cache_bytes(sizes, True)


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
    """Operations one query-context pair costs over the layers that see every
    pair (the full layers): a head's score and its value sum, 2 operations a
    dimension each."""
    return (_layers(sizes, False) * sizes["num_attention_heads"]
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
