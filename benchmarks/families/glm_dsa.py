"""Adapter for the GLM-5.2 family (`model_type: glm_moe_dsa`: latent attention
over the `index_topk` context rows a lightning indexer selects, the selection
shared by the layers behind an indexer's, leading dense layers, sigmoid top-k
expert layers with a selection bias, a scaling factor and a shared expert):
from a configuration file's keys to the program's `GlmDsaConfig`, and to the
plain reference in `benchmarks/glm_dsa_reference.py`. `README.md` ("The family
file") lists every name the harness reads.

Of `sizes`, beside the published keys: `n_routed_experts` counts the experts
HELD by this chip (the guide's convention for an expert share; the reader
`expert_load_skew.mean` reads the held count under that key),
`n_routed_experts_published` is the router's width and `first_held_expert`
the first held published id; `rope_theta` repeats `rope_parameters`'.

The cache is ONE layer group with two arrays. `cache_bytes_per_token` counts a
token's latent rows `[c | kr]` over all layers (a floor: the row's padding to
640 lanes is not counted) and `index_bytes_per_row` one "full" layer's index
key; `attention_flops_per_pair` the equations' own count of one query-context
pair over all layers. The readers `dsa_*` multiply them by the program's
SPARSE counts (`dsa_attend_rows`, `dsa_index_rows`, `dsa_pairs`); the dense
counts (`kv_tokens`, `attn_pairs`) times these would read over 100% of a peak
for a kernel that reads a sixteenth of them, which is why this family's cell
is not listed under `paged_kernel_hbm.share` or `latent_kernel_mxu.share`.

The family serves and does not train: it brings no `loss_fn`,
`param_logical_axes` or `init_params` (ops/sparse_latent.py has no backward
pass; `train_cell` says so in one line); `train_flops_per_token` and
`reference_loss_and_grad_norm` are there because the harness's own tests hold
every family's file to them.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from ray_tpu.models import glm_dsa

import glm_dsa_reference as reference
import routing

# What `rehearse.py` shrinks a configuration of this family to: every key of
# `sizes` that holds a whole number (and the two lists at the tiny depth), so
# that no published width stays beside a tiny one. Four layers, every kind;
# a selection of 8 rows, so that the rehearsal's contexts run over it.
TINY_SIZES = {"hidden_size": 64, "intermediate_size": 96,
              "moe_intermediate_size": 32, "num_hidden_layers": 4,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "head_dim": 24, "q_lora_rank": 48, "kv_lora_rank": 32,
              "qk_head_dim": 32, "qk_nope_head_dim": 24,
              "qk_rope_head_dim": 8, "v_head_dim": 16,
              "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8,
              "index_topk_freq": 2, "index_skip_topk_offset": 1,
              "indexer_types": ["full", "shared", "full", "shared"],
              "mlp_layer_types": ["dense", "dense", "sparse", "sparse"],
              "n_routed_experts": 8, "n_routed_experts_published": 16,
              "first_held_expert": 0, "n_shared_experts": 1,
              "num_experts_per_tok": 4, "n_group": 1, "topk_group": 1,
              "first_k_dense_replace": 2, "moe_layer_freq": 1, "ep_size": 1,
              "num_nextn_predict_layers": 0, "rope_theta": 10000,
              "rope_parameters": {"rope_theta": 10000,
                                  "rope_type": "default"},
              "vocab_size": 256, "max_position_embeddings": 256,
              "torch_dtype": "float32"}
BYTES_OF = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

    if (sizes.get("topk_method", "noaux_tc") != "noaux_tc"
            or sizes.get("scoring_func", "sigmoid") != "sigmoid"
            or not sizes.get("norm_topk_prob", True)
            or sizes.get("attention_bias") or sizes.get("tie_word_embeddings")
            or sizes.get("n_group") != 1 or sizes.get("topk_group") != 1
            or sizes.get("moe_layer_freq") != 1
            or sizes.get("num_nextn_predict_layers")
            or not sizes.get("rope_interleave", True)
            or not sizes.get("indexer_rope_interleave", True)
            or sizes.get("rope_parameters", {}).get("rope_type",
                                                    "default") != "default"
            or sizes.get("hidden_act", "silu") != "silu"
            or sizes.get("num_key_value_heads")
            != sizes["num_attention_heads"]):
        raise SystemExit("benchmark: a router, a rope, a projection or a "
                         "layer pattern this family does not model")
    first = sizes["first_held_expert"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return glm_dsa.GlmDsaConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        num_attention_heads=sizes["num_attention_heads"],
        q_lora_rank=sizes["q_lora_rank"], kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        index_n_heads=sizes["index_n_heads"],
        index_head_dim=sizes["index_head_dim"],
        index_topk=sizes["index_topk"],
        indexer_types=tuple(sizes["indexer_types"]),
        mlp_layer_types=tuple(sizes["mlp_layer_types"]),
        n_routed_experts=sizes["n_routed_experts_published"],
        experts_held=(first, first + sizes["n_routed_experts"]),
        n_shared_experts=sizes["n_shared_experts"],
        num_experts_per_tok=sizes["num_experts_per_tok"],
        routed_scaling_factor=float(sizes.get("routed_scaling_factor", 2.5)),
        rms_norm_eps=float(sizes.get("rms_norm_eps", 1e-5)),
        rope_theta=float(sizes["rope_theta"]),
        max_position_embeddings=sizes["max_position_embeddings"],
        dtype=dtype)


def _full_layers(sizes: Dict) -> int:
    return list(sizes["indexer_types"]).count("full")


def attention_params(sizes: Dict) -> int:
    d, H = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    lat, v, q = (sizes["kv_lora_rank"], sizes["v_head_dim"],
                 sizes["q_lora_rank"])
    return (d * q + q * H * (nope + rope) + d * (lat + rope)
            + lat * H * (nope + v) + H * v * d)


def indexer_params(sizes: Dict) -> int:
    HI, dI = sizes["index_n_heads"], sizes["index_head_dim"]
    return (sizes["q_lora_rank"] * HI * dI + sizes["hidden_size"] * dI
            + sizes["hidden_size"] * HI)


def _layer_params(sizes: Dict, picks: float) -> float:
    d = sizes["hidden_size"]
    expert = 3 * d * sizes["moe_intermediate_size"]
    return sum(
        attention_params(sizes)
        + (indexer_params(sizes) if ix == "full" else 0)
        + (3 * d * sizes["intermediate_size"] if ff == "dense"
           else d * sizes["n_routed_experts_published"]
           + (picks + sizes["n_shared_experts"]) * expert)
        for ix, ff in zip(sizes["indexer_types"], sizes["mlp_layer_types"]))


def num_params(sizes: Dict) -> int:
    """Parameters this chip holds (the held experts, not the published
    count); norms and biases left out."""
    return int(2 * sizes["vocab_size"] * sizes["hidden_size"]
               + _layer_params(sizes, sizes["n_routed_experts"]))


def train_flops_per_token(sizes: Dict, seq: int) -> float:
    """Operations a token of a forward and backward pass: 6 a parameter its
    products touch (the HELD share of its experts: top_k x held / published),
    attention at H x (qk + v) x 2 a query-context pair over the min(seq,
    index_topk) rows a token attends to, and the indexer's HI x dI x 2 a pair
    over all of them, x 3. (The benchmark's copy of `GlmDsaConfig.
    flops_per_token`, so that no PR to the program moves it; the family does
    not train.)"""
    picks = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
             / sizes["n_routed_experts_published"])
    n = _layer_params(sizes, picks) + sizes["hidden_size"] * sizes[
        "vocab_size"]
    index = (_full_layers(sizes) * sizes["index_n_heads"]
             * sizes["index_head_dim"] * 2)
    return (6.0 * n
            + 3.0 * attention_flops_per_pair(sizes)
            * min(seq, sizes["index_topk"]) + 3.0 * index * seq)


def cache_bytes_per_token(sizes: Dict) -> int:
    """Useful bytes of latent cache one context token holds over all layers:
    the row `[c | kr]` a layer (its padding to whole lane tiles is not
    counted: a floor). The index keys are `index_bytes_per_row`'s."""
    return (sizes["num_hidden_layers"]
            * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
            * BYTES_OF[sizes["torch_dtype"]])


def index_bytes_per_row(sizes: Dict) -> int:
    """Bytes of index key one context token holds in ONE "full" layer."""
    return sizes["index_head_dim"] * BYTES_OF[sizes["torch_dtype"]]


def index_layers(sizes: Dict) -> int:
    return _full_layers(sizes)


def attention_flops_per_pair(sizes: Dict) -> int:
    """Operations one query-context pair costs over all layers by the
    equations' own count: a head's score over nope + rope dimensions and its
    value sum over v, 2 operations each. A floor: the absorbed form executes
    H x (W + lat) x 2 a pair."""
    return (sizes["num_hidden_layers"] * sizes["num_attention_heads"]
            * (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
               + sizes["v_head_dim"]) * 2)


def reference_logits_at(params, tokens, positions, sizes: Dict):
    return reference.logits_at(params, tokens, positions, sizes)[0]


def reference_logits_routed(params, tokens, positions, sizes: Dict, kept):
    """The reference following the experts the program kept, `kept` (routed
    layers, b, s, top_k) published ids, and the shortfall (routed layers, b,
    s) of every choice it would not have made itself at that point: over the
    selection scores, score + bias, with one group. It SELECTS ITS CONTEXT
    ROWS FOR ITSELF: at the harness's 264 positions every row is selected
    (`index_topk` 2,048), so the cell's `correct` cannot see a selection;
    chip_smoke.py's `glm_dsa_check` runs 8,192 + 8 (PERF.md section 7)."""
    kept = np.asarray(kept)
    logits, scores, _ = reference.logits_at(params, tokens, positions, sizes,
                                            kept)
    layers, b, s, k = kept.shape
    short = np.stack([
        routing.shortfall(scores[i].reshape(b * s, -1),
                          kept[i].reshape(b * s, k), k, 1, 1).reshape(b, s)
        for i in range(layers)])
    return logits, short


reference_loss_and_grad_norm = reference.loss_and_grad_norm
