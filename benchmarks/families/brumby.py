"""Adapter for the Brumby family (a dense decoder whose every layer mixes
tokens by power retention: a matrix-valued gated state a sequence and kv head,
and no page of K/V anywhere): from a configuration file's keys to the
program's `BrumbyConfig`, and to the plain reference in
`benchmarks/brumby_reference.py`. `README.md` ("The family file") lists every
name the harness reads.

Of `sizes`, beside the published keys: `retention_eps` (under `assumed` in the
configuration file) and `torch_dtype`.

The cache has no paged layer: `cache_bytes_per_token` is 0 (a context token
holds nothing; `num_kv_blocks` counts the engine's accounting pages, zero
bytes). `state_bytes_per_sequence` is a slot of the state group AS IT LIES:
every layer's S, a kv head's degree-2 key features by 128 value lanes, and its
normaliser z, float32, the features in 65 chunks of 128 lanes (8,320 for the
8,256 distinct products of two key lanes). For this PR's readers:
`retention_bytes(sizes, rows, sequences)`.

The family serves and does not train: it brings no `loss_fn`,
`param_logical_axes` or `init_params` (the retention has no backward pass in
the program; `train_cell` says so in one line); `train_flops_per_token` and
`reference_loss_and_grad_norm` are there because the harness's own tests hold
every family's file to them.
"""

from __future__ import annotations

from typing import Dict

from ray_tpu.models import brumby

import brumby_reference as reference

# What `rehearse.py` shrinks a configuration of this family to: every key of
# `sizes` that holds a whole number. Two layers, 6 query heads over 2 kv heads
# (3:1), heads of 16 (9 chunks of 16 lanes).
TINY_SIZES = {"hidden_size": 64, "intermediate_size": 96,
              "num_hidden_layers": 2, "num_attention_heads": 6,
              "num_key_value_heads": 2, "head_dim": 16,
              "max_window_layers": 2, "rope_theta": 10000,
              "vocab_size": 256, "max_position_embeddings": 256,
              "torch_dtype": "float32"}
BYTES_OF = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

    if (sizes.get("tie_word_embeddings") or sizes.get("attention_bias")
            or sizes.get("hidden_act") != "silu"
            or sizes.get("rope_scaling") is not None
            or sizes.get("use_sliding_window")
            or sizes.get("sliding_window") is not None):
        raise SystemExit("benchmark: a head, a bias, an activation, a rope "
                         "scaling or a window this family does not model")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return brumby.BrumbyConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"],
        rms_norm_eps=float(sizes["rms_norm_eps"]),
        rope_theta=float(sizes["rope_theta"]),
        max_position_embeddings=sizes["max_position_embeddings"],
        retention_eps=float(sizes["retention_eps"]), dtype=dtype)


def num_params(sizes: Dict) -> int:
    d, ff, hd = (sizes["hidden_size"], sizes["intermediate_size"],
                 sizes["head_dim"])
    H, K = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    layer = (2 * d * H * hd + 2 * d * K * hd + d * K + K + 2 * hd
             + 3 * d * ff + 2 * d)
    return 2 * sizes["vocab_size"] * d + d + sizes["num_hidden_layers"] * layer


def train_flops_per_token(sizes: Dict, seq: int) -> float:
    """Operations a token of a forward and backward pass: 6 a parameter but
    the embedding's rows (looked up), and the recurrence's own count whatever
    the context: a feature of a kv head's state (by 128 values and the
    normaliser) decayed and updated (3 operations) and read by each of its
    query heads (2 each); x 3 for the backward pass. (The benchmark's copy of
    `BrumbyConfig.flops_per_token`, so that no PR to the program moves it;
    the family does not train.)"""
    hd, H, K = (sizes["head_dim"], sizes["num_attention_heads"],
                sizes["num_key_value_heads"])
    retention = (sizes["num_hidden_layers"] * features(sizes) * (hd + 1)
                 * (3 * K + 2 * H))
    return (6.0 * (num_params(sizes)
                   - sizes["vocab_size"] * sizes["hidden_size"])
            + 3.0 * retention)


def cache_bytes_per_token(sizes: Dict) -> int:
    """A context token holds nothing: no layer keeps K or V."""
    return 0


def features(sizes: Dict) -> int:
    """Distinct products of two lanes of a key: hd (hd + 1) / 2."""
    return sizes["head_dim"] * (sizes["head_dim"] + 1) // 2


def state_bytes_per_sequence(sizes: Dict) -> int:
    """A slot of the state group as it lies: every layer's S and z, float32,
    the features in hd / 2 + 1 chunks of hd lanes."""
    hd = sizes["head_dim"]
    return 4 * sizes["num_hidden_layers"] * sizes["num_key_value_heads"] * (
        (hd // 2 + 1) * hd * (hd + 1))


def retention_bytes(sizes: Dict, rows: int, sequences: int) -> int:
    """Bytes no form of the layer can avoid for a step of `rows` rows of
    `sequences` sequences, every layer, whatever implements it: a sequence's
    S and z READ once (float32, the distinct features only), a row's q, k and
    v in and o out (the configuration's dtype) and its gates (float32). The
    write-back is not counted: a form that reads and rewrites the state a
    step reads at most about half, and a form that folds a chunk's keys in
    once cannot read over 100%."""
    hd, H, K = (sizes["head_dim"], sizes["num_attention_heads"],
                sizes["num_key_value_heads"])
    row = BYTES_OF[sizes["torch_dtype"]] * hd * (2 * H + 2 * K) + 4 * K
    state = 4 * K * features(sizes) * (hd + 1)
    return sizes["num_hidden_layers"] * (rows * row + sequences * state)


def reference_logits_at(params, tokens, positions, sizes: Dict):
    return reference.logits_at(params, tokens, positions, sizes)[0]


reference_loss_and_grad_norm = reference.loss_and_grad_norm
