"""Adapter for the Nemotron-H family (Mamba-2 layers, a matrix state a sequence
and head with one decay a head and token; one GQA attention layer in eleven
that rotates nothing; LatentMoE layers, sigmoid top-k over a selection bias
with a scaling factor, non-gated relu^2 experts in a latent the layer enters
and leaves, and a shared expert on the full hidden state; ONE mixer a layer by
`hybrid_override_pattern`): from a configuration file's keys to the program's
`NemotronHConfig`, and to the plain reference in
`benchmarks/nemotron_h_reference.py`. `README.md` ("The family file") lists
every name the harness reads.

Of `sizes`, beside the published keys: `n_routed_experts` counts the experts
HELD by this chip (the guide's convention for an expert share, and the key
the reader `expert_load_skew.mean` knows), `n_routed_experts_published` is the
router's width and `first_held_expert` the first held published id.

The cache has two layer groups, both with bytes. `cache_bytes_per_token`
counts the K and V rows of the `*` layers (what `kv_tokens` of a tick reads);
`state_bytes_per_sequence` is a slot of the state group: every `M` layer's S
(float32) and its convolution's tail (the configuration's dtype). For this
PR's readers: `ssd_bytes(sizes, rows, sequences)`.

The family serves and does not train: it brings no `loss_fn`,
`param_logical_axes` or `init_params` (ops/ssd.py has no backward pass in the
program; `train_cell` says so in one line); `train_flops_per_token` and
`reference_loss_and_grad_norm` are there because the harness's own tests hold
every family's file to them.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from ray_tpu.models import nemotron_h

import nemotron_h_reference as reference
import routing

# What `rehearse.py` shrinks a configuration of this family to: every key of
# `sizes` that holds a whole number (and the pattern at the tiny depth), so
# that no published width stays beside a tiny one. Six layers: M, E, M, *, E,
# M.
TINY_SIZES = {"hidden_size": 64, "num_hidden_layers": 6,
              "hybrid_override_pattern": "MEM*EM", "mamba_num_heads": 8,
              "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2,
              "conv_kernel": 4, "chunk_size": 8, "expand": 2,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "intermediate_size": 32,
              "moe_intermediate_size": 32, "moe_latent_size": 32,
              "moe_shared_expert_intermediate_size": 48,
              "n_routed_experts": 8, "n_routed_experts_published": 16,
              "first_held_expert": 0, "num_experts_per_tok": 4,
              "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
              "routed_scaling_factor": 5, "num_nextn_predict_layers": 0,
              "num_logits_to_keep": 1, "partial_rotary_factor": 1,
              "rope_theta": 10000, "vocab_size": 256,
              "max_position_embeddings": 256, "torch_dtype": "float32"}
BYTES_OF = {"bfloat16": 2, "float16": 2, "float32": 4}
KINDS = {"M": "mamba", "*": "attn", "E": "latent_moe"}


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

    if (sizes.get("mlp_hidden_act") != "relu2"
            or sizes.get("mamba_hidden_act") != "silu"
            or sizes.get("n_group") != 1 or sizes.get("topk_group") != 1
            or not sizes.get("norm_topk_prob")
            or sizes.get("n_shared_experts") != 1
            or sizes.get("attention_bias") or sizes.get("mlp_bias")
            or sizes.get("use_bias") or sizes.get("mamba_proj_bias")
            or not sizes.get("use_conv_bias")
            or sizes.get("tie_word_embeddings")
            or sizes.get("sliding_window") is not None
            or sizes.get("num_nextn_predict_layers")
            or sizes["expand"] * sizes["hidden_size"]
            != sizes["mamba_num_heads"] * sizes["mamba_head_dim"]):
        raise SystemExit("benchmark: a router, an activation, a bias or a "
                         "layer this family does not model")
    first = sizes["first_held_expert"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
            "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts_per_tok", "moe_intermediate_size",
            "moe_latent_size", "moe_shared_expert_intermediate_size",
            "max_position_embeddings")
    return nemotron_h.NemotronHConfig(
        **{key: sizes[key] for key in same},
        n_routed_experts=sizes["n_routed_experts_published"],
        experts_held=(first, first + sizes["n_routed_experts"]),
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        layer_norm_epsilon=float(sizes["layer_norm_epsilon"]),
        time_step_min=float(sizes["time_step_min"]),
        time_step_max=float(sizes["time_step_max"]),
        time_step_floor=float(sizes["time_step_floor"]), dtype=dtype)


def _layers(sizes: Dict, kind: str) -> int:
    return sum(KINDS[c] == kind for c in sizes["hybrid_override_pattern"])


def _d_inner(sizes: Dict) -> int:
    return sizes["mamba_num_heads"] * sizes["mamba_head_dim"]


def _conv_dim(sizes: Dict) -> int:
    return _d_inner(sizes) + 2 * sizes["n_groups"] * sizes["ssm_state_size"]


def mamba_params(sizes: Dict) -> int:
    """A Mamba-2 layer: `in_proj` (z | xBC | dt), the taps and their bias,
    `A_log`, `dt_bias` and `D` a head, the gated norm, `out_proj`, the
    layer's norm."""
    d, di, H = sizes["hidden_size"], _d_inner(sizes), sizes["mamba_num_heads"]
    conv = _conv_dim(sizes)
    return (d * (di + conv + H) + (sizes["conv_kernel"] + 1) * conv + 3 * H
            + di + di * d + d)


def attn_params(sizes: Dict) -> int:
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    H, K = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return d * (H + 2 * K) * hd + H * hd * d + d


def expert_params(sizes: Dict) -> int:
    return 2 * sizes["moe_latent_size"] * sizes["moe_intermediate_size"]


def moe_params(sizes: Dict, picks: float) -> float:
    """An expert layer with `picks` routed experts: the router and its bias,
    the latent's two projections, the shared expert, the norm."""
    d, wide = sizes["hidden_size"], sizes["n_routed_experts_published"]
    return (d * wide + wide + 2 * d * sizes["moe_latent_size"]
            + 2 * d * sizes["moe_shared_expert_intermediate_size"] + d
            + picks * expert_params(sizes))


def _layer_params(sizes: Dict, picks: float) -> float:
    return (_layers(sizes, "mamba") * mamba_params(sizes)
            + _layers(sizes, "attn") * attn_params(sizes)
            + _layers(sizes, "latent_moe") * moe_params(sizes, picks))


def num_params(sizes: Dict) -> int:
    """Parameters this chip holds (the held experts, not the published
    count), every norm, bias, `A_log`, `dt_bias` and `D` counted."""
    d = sizes["hidden_size"]
    return int(2 * sizes["vocab_size"] * d + d
               + _layer_params(sizes, sizes["n_routed_experts"]))


def train_flops_per_token(sizes: Dict, seq: int) -> float:
    """Operations a token of a forward and backward pass: 6 a parameter its
    products touch (the HELD share of its experts: top_k x held / published),
    the attention layers' at H x 2 hd x 2 a query-context pair, and a Mamba-2
    layer's recurrence by its own count whatever the context (a state element
    decayed, updated and read for the output, 2 operations each), x 3. (The
    benchmark's copy of `NemotronHConfig.flops_per_token`, so that no PR to
    the program moves it; the family does not train.)"""
    picks = (sizes["num_experts_per_tok"] * sizes["n_routed_experts"]
             / sizes["n_routed_experts_published"])
    n = _layer_params(sizes, picks) + sizes["hidden_size"] * sizes[
        "vocab_size"]
    state = 6 * _d_inner(sizes) * sizes["ssm_state_size"]
    return (6.0 * n + 3.0 * attention_flops_per_pair(sizes) * seq
            + 3.0 * _layers(sizes, "mamba") * state)


def cache_bytes_per_token(sizes: Dict) -> int:
    """Bytes of cache one context token holds: the K and the V row (its kv
    heads side by side) of each `*` layer. The `M` layers hold nothing a
    token."""
    return (_layers(sizes, "attn") * 2 * sizes["num_key_value_heads"]
            * sizes["head_dim"] * BYTES_OF[sizes["torch_dtype"]])


def attention_flops_per_pair(sizes: Dict) -> int:
    """Operations one query-context pair costs over the `*` layers by the
    equations' own count: a head's score over hd dimensions and its value sum
    over hd, 2 operations each."""
    return (_layers(sizes, "attn") * sizes["num_attention_heads"]
            * 2 * sizes["head_dim"] * 2)


def state_bytes_per_sequence(sizes: Dict) -> int:
    """A slot of the state group: every `M` layer's S (a head's values x
    state, float32) and the last rows of its convolution's input (the
    configuration's dtype)."""
    return _layers(sizes, "mamba") * (
        4 * _d_inner(sizes) * sizes["ssm_state_size"]
        + BYTES_OF[sizes["torch_dtype"]] * (sizes["conv_kernel"] - 1)
        * _conv_dim(sizes))


def ssd_bytes(sizes: Dict, rows: int, sequences: int) -> int:
    """Bytes no form of the KERNEL can avoid for a step of `rows` rows of
    `sequences` sequences, every `M` layer, whatever implements it: a
    sequence's S in ONCE, float32; a row's x, its groups' B and C and its dt
    in and its y out, float32 as the program states them. The write-back is
    NOT counted (as `families/kimi_linear.py`'s `kda_bytes` does not): a form
    that reads and rewrites S a step, as this PR's kernel does, reads at most
    about half, and a form that held a few rows beside S and wrote it once in
    r rows (one decay a head makes that possible: PERF.md section 7) would
    still read under 100%. The convolution's tails (1.5% of a slot) move
    outside the kernel, in time the kernel's events do not hold, and are not
    counted."""
    H, G, N = (sizes["mamba_num_heads"], sizes["n_groups"],
               sizes["ssm_state_size"])
    di = _d_inner(sizes)
    return _layers(sizes, "mamba") * (
        rows * 4 * (2 * di + 2 * G * N + H) + sequences * 4 * di * N)


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
