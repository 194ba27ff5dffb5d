"""Adapter for the Kimi-Linear family (Kimi Delta Attention layers, a gated
delta-rule state a sequence with a gate a key channel, three to one beside
latent attention layers that rotate nothing; a leading dense layer, sigmoid
top-k expert layers with a selection bias, a scaling factor and a shared
expert): from a configuration file's keys to the program's
`KimiLinearConfig`, and to the plain reference in
`benchmarks/kimi_linear_reference.py`. `README.md` ("The family file") lists
every name the harness reads.

Of `sizes`, beside the published keys: `num_experts` counts the experts HELD
by this chip (the guide's convention for an expert share),
`num_experts_published` is the router's width and `first_held_expert` the
first held published id (`n_routed_experts` repeats the held count under the
key the reader `expert_load_skew.mean` knows); `max_position_embeddings` is
`model_max_length` as run; `gate_rank` and `l2_norm_eps` stand under `assumed` in the file.

The cache has two layer groups, both with bytes. `cache_bytes_per_token`
counts the latent rows of the layers in `full_attn_layers` (what `kv_tokens`
of a tick reads); `state_bytes_per_sequence` is a slot of the state group:
every KDA layer's S and its convolution's tail, float32. For this PR's
readers: `kda_bytes(sizes, rows, sequences)`.

The family serves and does not train: it brings no `loss_fn`,
`param_logical_axes` or `init_params` (ops/kda.py has no backward pass in the
program; `train_cell` says so in one line); `train_flops_per_token` and
`reference_loss_and_grad_norm` are there because the harness's own tests hold
every family's file to them.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from ray_tpu.models import kimi_linear

import kimi_linear_reference as reference
import routing

# What `rehearse.py` shrinks a configuration of this family to: every key of
# `sizes` that holds a whole number (and the two lists at the tiny depth), so
# that no published width stays beside a tiny one. Five layers: KDA, KDA, MLA,
# KDA, MLA.
TINY_SIZES = {"hidden_size": 64, "intermediate_size": 96,
              "moe_intermediate_size": 32, "num_hidden_layers": 5,
              "linear_attn_config": {
                  "kda_layers": [1, 2, 4], "full_attn_layers": [3, 5],
                  "num_heads": 4, "head_dim": 16,
                  "short_conv_kernel_size": 4},
              "head_dim": 16, "num_attention_heads": 4,
              "num_key_value_heads": 4, "kv_lora_rank": 32,
              "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
              "num_experts": 8, "num_experts_published": 16,
              "n_routed_experts": 8,
              "first_held_expert": 0, "num_experts_per_token": 4,
              "num_shared_experts": 1, "num_expert_group": 1, "topk_group": 1,
              "first_k_dense_replace": 1, "moe_layer_freq": 1,
              "num_nextn_predict_layers": 0, "rope_theta": 10000,
              "gate_rank": 16, "vocab_size": 256, "model_max_length": 256,
              "max_position_embeddings": 256, "torch_dtype": "float32"}
BYTES_OF = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

    if (sizes.get("moe_router_activation_func") != "sigmoid"
            or not sizes.get("moe_renormalize")
            or not sizes.get("mla_use_nope")
            or sizes.get("q_lora_rank") is not None
            or sizes.get("rope_scaling") is not None
            or sizes.get("tie_word_embeddings")
            or sizes.get("hidden_act") != "silu"
            or sizes.get("num_expert_group") != 1
            or sizes.get("topk_group") != 1
            or sizes.get("moe_layer_freq") != 1
            or sizes.get("num_nextn_predict_layers")
            or sizes.get("num_key_value_heads")
            != sizes["num_attention_heads"]):
        raise SystemExit("benchmark: a router, a rope, a projection or a "
                         "layer pattern this family does not model")
    lin = sizes["linear_attn_config"]
    first = sizes["first_held_expert"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return kimi_linear.KimiLinearConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        moe_intermediate_size=sizes["moe_intermediate_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        kda_layers=tuple(lin["kda_layers"]),
        full_attn_layers=tuple(lin["full_attn_layers"]),
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        num_attention_heads=sizes["num_attention_heads"],
        kv_lora_rank=sizes["kv_lora_rank"],
        qk_nope_head_dim=sizes["qk_nope_head_dim"],
        qk_rope_head_dim=sizes["qk_rope_head_dim"],
        v_head_dim=sizes["v_head_dim"],
        num_experts=sizes["num_experts_published"],
        experts_held=(first, first + sizes["num_experts"]),
        num_experts_per_token=sizes["num_experts_per_token"],
        num_shared_experts=sizes["num_shared_experts"],
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        first_k_dense_replace=sizes["first_k_dense_replace"],
        rms_norm_eps=float(sizes["rms_norm_eps"]),
        max_position_embeddings=sizes["max_position_embeddings"],
        gate_rank=sizes["gate_rank"],
        l2_norm_eps=float(sizes["l2_norm_eps"]), dtype=dtype)


def _kda_layers(sizes: Dict) -> int:
    return len(sizes["linear_attn_config"]["kda_layers"])


def _mla_layers(sizes: Dict) -> int:
    return len(sizes["linear_attn_config"]["full_attn_layers"])


def _kda_width(sizes: Dict) -> int:
    lin = sizes["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"]


def kda_params(sizes: Dict) -> int:
    """A KDA layer outside its feed-forward: q, k, v and o, the two gates'
    rank-`gate_rank` pairs, beta and the convolutions' taps."""
    d, w, r = sizes["hidden_size"], _kda_width(sizes), sizes["gate_rank"]
    lin = sizes["linear_attn_config"]
    return (4 * d * w + 2 * (d * r + r * w) + d * lin["num_heads"]
            + lin["short_conv_kernel_size"] * 3 * w)


def mla_params(sizes: Dict) -> int:
    d, H, lat = (sizes["hidden_size"], sizes["num_attention_heads"],
                 sizes["kv_lora_rank"])
    return (d * H * (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"])
            + d * (lat + sizes["qk_rope_head_dim"])
            + lat * H * (sizes["qk_nope_head_dim"] + sizes["v_head_dim"])
            + H * sizes["v_head_dim"] * d)


def _layer_params(sizes: Dict, picks: float) -> float:
    d = sizes["hidden_size"]
    expert = 3 * d * sizes["moe_intermediate_size"]
    kda = set(sizes["linear_attn_config"]["kda_layers"])
    return sum(
        (kda_params(sizes) if li + 1 in kda else mla_params(sizes))
        + (3 * d * sizes["intermediate_size"]
           if li < sizes["first_k_dense_replace"]
           else d * sizes["num_experts_published"]
           + (picks + sizes["num_shared_experts"]) * expert)
        for li in range(sizes["num_hidden_layers"]))


def num_params(sizes: Dict) -> int:
    """Parameters this chip holds (the held experts, not the published
    count); norms, biases, `A_log` and `dt_bias` left out."""
    return int(2 * sizes["vocab_size"] * sizes["hidden_size"]
               + _layer_params(sizes, sizes["num_experts"]))


def train_flops_per_token(sizes: Dict, seq: int) -> float:
    """Operations a token of a forward and backward pass: 6 a parameter its
    products touch (the HELD share of its experts: top_k x held / published),
    the latent layers' attention at H x (qk + v) x 2 a query-context pair, and
    a KDA layer's recurrence by its own count whatever the context (a state
    element decayed, read for the correction, updated and read for the output,
    2 operations each), x 3. (The benchmark's copy of
    `KimiLinearConfig.flops_per_token`, so that no PR to the program moves it;
    the family does not train.)"""
    picks = (sizes["num_experts_per_token"] * sizes["num_experts"]
             / sizes["num_experts_published"])
    lin = sizes["linear_attn_config"]
    n = _layer_params(sizes, picks) + sizes["hidden_size"] * sizes[
        "vocab_size"]
    state = 8 * lin["num_heads"] * lin["head_dim"] ** 2
    return (6.0 * n + 3.0 * attention_flops_per_pair(sizes) * seq
            + 3.0 * _kda_layers(sizes) * state)


def cache_bytes_per_token(sizes: Dict) -> int:
    """Useful bytes of cache one context token holds: the latent row `[c_kv |
    k_rope]` of each latent layer (its padding to whole lane tiles is not
    counted: a floor). The KDA layers hold nothing a token."""
    return (_mla_layers(sizes)
            * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
            * BYTES_OF[sizes["torch_dtype"]])


def attention_flops_per_pair(sizes: Dict) -> int:
    """Operations one query-context pair costs over the latent layers by the
    equations' own count: a head's score over qk dimensions and its value sum
    over v, 2 operations each."""
    return (_mla_layers(sizes) * sizes["num_attention_heads"]
            * (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
               + sizes["v_head_dim"]) * 2)


def state_bytes_per_sequence(sizes: Dict) -> int:
    """A slot of the state group: every KDA layer's S (a head's keys x
    values) and the last rows of its convolution's input, float32."""
    lin = sizes["linear_attn_config"]
    return 4 * _kda_layers(sizes) * (
        lin["num_heads"] * lin["head_dim"] ** 2
        + (lin["short_conv_kernel_size"] - 1) * 3 * _kda_width(sizes))


def kda_bytes(sizes: Dict, rows: int, sequences: int) -> int:
    """Bytes no form of the KERNEL can avoid for a step of `rows` rows of
    `sequences` sequences, every KDA layer, whatever implements it: a
    sequence's S READ once (the delta rule corrects a row by what the state
    already holds for its key), float32; a row's q, k, v, the gates' logs (a
    value a key channel) and beta in and its output out, float32 as the
    program states them. The write-back is not counted (as families/brumby.py
    `retention_bytes` does not): a form that reads and rewrites S a step, as
    this PR's kernel does, reads at most about half, and a form that held a
    few rows' k and u beside S and wrote it once in r rows would still read
    under 100%. The convolution's tails (7% of a slot) move outside the
    kernel, in time the kernel's events do not hold, and are not counted."""
    lin = sizes["linear_attn_config"]
    H, hd, w = lin["num_heads"], lin["head_dim"], _kda_width(sizes)
    return _kda_layers(sizes) * (rows * 4 * (5 * w + H)
                                 + sequences * 4 * H * hd * hd)


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
