"""Adapter for the Llama-shaped decoder family: from a configuration file's
published (Hugging Face) keys to the program's `LlamaConfig`, and to the plain
reference in `benchmarks/reference.py`. A family the program models otherwise
brings a file like this one, named by the configuration's `family`.
"""

from __future__ import annotations

from typing import Dict

import reference


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

    from ray_tpu.models import llama

    if sizes["hidden_size"] != sizes["num_attention_heads"] * sizes["head_dim"]:
        raise SystemExit("benchmark: LlamaConfig derives head_dim as "
                         "hidden_size / heads; this file's differs")
    if sizes.get("sliding_window") or sizes.get("tie_word_embeddings"):
        raise SystemExit("benchmark: sliding window / tied head not modelled")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return llama.LlamaConfig(
        vocab_size=sizes["vocab_size"], d_model=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        d_ff=sizes["intermediate_size"],
        max_seq=sizes["max_position_embeddings"],
        rope_theta=float(sizes["rope_theta"]),
        norm_eps=float(sizes["rms_norm_eps"]), dtype=dtype)


def train_flops_per_token(sizes: Dict, seq: int) -> float:
    """Operations the forward and backward passes need per token: 6 N over
    the parameters without the embedding table, plus the attention term
    12 L d s. Recomputation is not counted. (Copied from
    `LlamaConfig.flops_per_token`, so that no PR to the program moves it.)"""
    d, f, v = sizes["hidden_size"], sizes["intermediate_size"], \
        sizes["vocab_size"]
    hd, H, K = sizes["head_dim"], sizes["num_attention_heads"], \
        sizes["num_key_value_heads"]
    L = sizes["num_hidden_layers"]
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f + 2 * d
    n = L * per_layer + d + d * v
    return 6.0 * n + 12.0 * L * d * seq


reference_logits_at = reference.logits_at
reference_loss_and_grad_norm = reference.loss_and_grad_norm
