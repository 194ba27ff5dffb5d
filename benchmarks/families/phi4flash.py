"""Adapter for the Phi-4-mini-flash family (state-space layers whose state is
a slot a sequence, window attention layers, one full attention layer whose K
and V a cross-decoder reads, gated memory units, differential attention): from
a configuration file's keys to the program's `Phi4FlashConfig`, and to the
plain reference in `benchmarks/phi4flash_reference.py`. `README.md` ("The
family file") lists every name the harness reads.

Of `sizes`, beside the published keys: the four Mamba sizes the published
config does not carry (`mamba_d_state`, `mamba_d_conv`, `mamba_expand`,
`mamba_dt_rank`: the family's defaults, under `assumed` in the configuration
file) and `torch_dtype`.

The cache has three layer groups. `cache_bytes_per_token` counts what a token
HOLDS in the group that keeps every token: ONE layer's K and V (layer L/2 +
1's), which `cross_layers` further layers read and do not write.
`window_cache_bytes_per_token` the window layers, which hold a sequence's
last `sliding_window` tokens. `state_bytes_per_sequence` a slot of the state
group: the scan state and the convolution tail of every Mamba layer, a fixed
size whatever the context. For this PR's readers: `cross_layers(sizes)` and
`ssm_bytes(sizes, rows, sequences)`.

The family serves and does not train: it brings no `loss_fn`,
`param_logical_axes` or `init_params` (the selective scan has no backward pass
in the program; `train_cell` says so in one line).
"""

from __future__ import annotations

from typing import Dict

from ray_tpu.models import phi4flash

import phi4flash_reference as reference

# What `rehearse.py` shrinks a configuration of this family to: every key of
# `sizes` that holds a whole number. Eight layers, so that every role is
# present (0-3 Mamba / window, 4 Mamba + memory, 5 full, 6-7 GMU / cross);
# window 8: a rehearsal's contexts pass it many times.
TINY_SIZES = {"hidden_size": 64, "intermediate_size": 96,
              "num_hidden_layers": 8, "num_attention_heads": 8,
              "num_key_value_heads": 4, "sliding_window": 8,
              "mb_per_layer": 2, "embd_pdrop": 0, "resid_pdrop": 0,
              "mamba_d_state": 4, "mamba_d_conv": 4, "mamba_expand": 2,
              "mamba_dt_rank": 4, "vocab_size": 256,
              "max_position_embeddings": 256, "torch_dtype": "float32"}
BYTES_OF = {"bfloat16": 2, "float16": 2, "float32": 4}


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

    if (not sizes.get("tie_word_embeddings") or sizes.get("mlp_bias")
            or sizes.get("lm_head_bias") or sizes.get("embd_pdrop")
            or sizes.get("resid_pdrop") or sizes.get("hidden_act") != "silu"
            or sizes.get("mb_per_layer") != 2
            or sizes["mamba_dt_rank"] != -(-sizes["hidden_size"] // 16)):
        raise SystemExit("benchmark: a head, a bias, an activation or a layer "
                         "pattern this family does not model")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    return phi4flash.Phi4FlashConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        sliding_window=sizes["sliding_window"],
        mb_per_layer=sizes["mb_per_layer"],
        layer_norm_eps=float(sizes["layer_norm_eps"]),
        max_position_embeddings=sizes["max_position_embeddings"],
        mamba_d_state=sizes["mamba_d_state"],
        mamba_d_conv=sizes["mamba_d_conv"],
        mamba_expand=sizes["mamba_expand"], dtype=dtype)


def _head_dim(sizes: Dict) -> int:
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def _d_inner(sizes: Dict) -> int:
    return sizes["mamba_expand"] * sizes["hidden_size"]


def mamba_layers(sizes: Dict) -> int:
    """Layers 0, 2, .., L/2."""
    return sizes["num_hidden_layers"] // 4 + 1


def window_layers(sizes: Dict) -> int:
    """Layers 1, 3, .., L/2 - 1."""
    return sizes["num_hidden_layers"] // 4


def cross_layers(sizes: Dict) -> int:
    """Layers L/2 + 3, .., L - 1: they read layer L/2 + 1's K and V."""
    return sizes["num_hidden_layers"] // 4 - 1


def num_params(sizes: Dict) -> int:
    d, di, ff = sizes["hidden_size"], _d_inner(sizes), \
        sizes["intermediate_size"]
    H, K, hd = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                _head_dim(sizes))
    N, R, taps = (sizes["mamba_d_state"], sizes["mamba_dt_rank"],
                  sizes["mamba_d_conv"])
    mlp = 3 * d * ff + 4 * d
    mamba = (d * 2 * di + di * (taps + 1) + di * (R + 2 * N) + R * di + di
             + di * N + di + di * d)
    lam = 6 * hd
    attn = d * (H + 2 * K) * hd + (H + 2 * K) * hd + d * d + d + lam
    cross = d * H * hd + H * hd + d * d + d + lam
    return (sizes["vocab_size"] * d + 2 * d
            + mamba_layers(sizes) * (mamba + attn + 2 * mlp)
            + cross_layers(sizes) * (2 * d * di + cross + 2 * mlp))


def train_flops_per_token(sizes: Dict, seq: int) -> float:
    """Operations a token of a forward and backward pass over a context of
    `seq`: 6 a parameter (the tied embedding once, as the head), attention at
    a head's score over hd and value sum over 2 hd, 2 operations each, a
    query-context pair (a window layer's token sees at most the window), and
    9 operations a state element a step of the recurrence; x 3 for the
    backward pass. (The benchmark's copy of `Phi4FlashConfig.flops_per_token`,
    so that no PR to the program moves it; the family does not train.)"""
    pair = sizes["num_attention_heads"] * 3 * _head_dim(sizes) * 2
    seen = ((1 + cross_layers(sizes)) * seq
            + window_layers(sizes) * min(seq, sizes["sliding_window"]))
    scan = 9 * mamba_layers(sizes) * _d_inner(sizes) * sizes["mamba_d_state"]
    return 6.0 * num_params(sizes) + 3.0 * pair * seen + 3.0 * scan


def _kv_bytes(sizes: Dict) -> int:
    """K and V of one layer, one token."""
    return (2 * sizes["num_key_value_heads"] * _head_dim(sizes)
            * BYTES_OF[sizes["torch_dtype"]])


def cache_bytes_per_token(sizes: Dict) -> int:
    """Bytes one context token HOLDS in the group that keeps every token: K
    and V of the one full layer. (What a tick READS of it is more: the rows
    that reach the cross-decoder walk it once a cross layer besides:
    `shared_kv_hbm.share`.)"""
    return _kv_bytes(sizes)


def window_cache_bytes_per_token(sizes: Dict) -> int:
    """The same over the window layers, which keep a sequence's last
    `sliding_window` tokens."""
    return window_layers(sizes) * _kv_bytes(sizes)


def state_bytes_per_sequence(sizes: Dict) -> int:
    """A slot of the state group: every Mamba layer's scan state (float32)
    and convolution tail."""
    return mamba_layers(sizes) * _d_inner(sizes) * (
        4 * sizes["mamba_d_state"]
        + (sizes["mamba_d_conv"] - 1) * BYTES_OF[sizes["torch_dtype"]])


def ssm_bytes(sizes: Dict, rows: int, sequences: int) -> int:
    """Bytes the recurrence of every Mamba layer needs for a step of `rows`
    rows of `sequences` sequences, whatever implements it: a row's x and dt
    in and y out (float32, d_i each) and its B and C (N each); a sequence's
    scan state in and out. A floor: the convolution, its tail and the
    projections around the scan are not the kernel's."""
    di, N = _d_inner(sizes), sizes["mamba_d_state"]
    return mamba_layers(sizes) * 4 * (
        rows * (3 * di + 2 * N) + sequences * 2 * di * N)


def reference_logits_at(params, tokens, positions, sizes: Dict):
    return reference.logits_at(params, tokens, positions, sizes)[0]


reference_loss_and_grad_norm = reference.loss_and_grad_norm
