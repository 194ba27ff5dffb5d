"""Adapter for the Llama-shaped decoder family: from a configuration file's
published (Hugging Face) keys to the program's `LlamaConfig` and entry points,
and to the plain reference in `benchmarks/reference.py`. A family the program
models otherwise brings a file like this one, named by the configuration's
`family`; `README.md` ("The family file") lists every name the harness reads.
"""

from __future__ import annotations

from typing import Dict

from ray_tpu.models import llama

import reference

# What `rehearse.py` shrinks a configuration of this family to: every key of
# `sizes` that sets a shape, so that no published width stays beside a tiny one.
TINY_SIZES = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
              "max_position_embeddings": 256, "torch_dtype": "float32"}
BYTES_OF = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

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


def cache_bytes_per_token(sizes: Dict) -> int:
    """Bytes of K and V one context token holds over all layers: what the
    paged kernel must read of the pool for it, once a layer."""
    return (2 * sizes["num_hidden_layers"] * sizes["num_key_value_heads"]
            * sizes["head_dim"] * BYTES_OF[sizes["torch_dtype"]])


# Training, through the program's own entry points (a family that cannot
# train leaves these three out).
loss_fn = llama.loss_fn
param_logical_axes = llama.param_logical_axes
init_params = llama.init_params

reference_logits_at = reference.logits_at
reference_loss_and_grad_norm = reference.loss_and_grad_norm
