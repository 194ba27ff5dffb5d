"""Adapter for the MiniCPM-SALA family (`minicpm4` layers: InfLLM-V2
block-sparse attention over a paged K/V row pool, a page-mean cache beside the
pages, every kv head its own 64 blocks; `lightning-attn` layers: a 128 x 128
state a head with one fixed decay, keys and queries a head's own; a SwiGLU MLP
every layer; muP scalings): from a configuration file's keys to the program's
`MiniCPMSALAConfig`, and to the plain reference in
`benchmarks/minicpm_sala_reference.py`. `README.md` ("The family file") lists
every name the harness reads.

Of `sizes`, beside the published keys: `first_published_layer` and
`published_layers` say where the run's first layer stands in the published
model (the lightning decays are a function of the PUBLISHED layer index), and
`kernel_size`, `kernel_stride`, `block_size`, `topk`, `init_blocks`,
`window_size`, `dense_len` are `sparse_config`'s (MiniCPM4's values, under
`assumed` in the configuration's file).

The cache has two layer groups, both with bytes. `cache_bytes_per_token`
counts the K and V rows of the sparse layers AND their page means (one row a
page: a sixteenth of a K row a token); `state_bytes_per_sequence` is a slot
of the state group's S (float32). For this PR's readers: `ssd_bytes`,
`select_bytes`, `attend_bytes`: floors whatever implements a stage.

The family serves and does not train: it brings no `loss_fn`,
`param_logical_axes` or `init_params` (ops/ssd.py has no backward pass in the
program); `train_flops_per_token` and `reference_loss_and_grad_norm` are
there because the harness's own tests hold every family's file to them. It
does not route: `check_logits` takes its first form.
"""

from __future__ import annotations

from typing import Dict

from ray_tpu.models import minicpm_sala

import minicpm_sala_reference as reference

# What `rehearse.py` shrinks a configuration of this family to: every key of
# `sizes` that holds a whole number, and the mixers at the tiny depth. The
# published kernel, stride and block stay (a page is a stride), over a
# `dense_len` the rehearsal's contexts pass.
TINY_SIZES = {"hidden_size": 64, "intermediate_size": 96,
              "num_hidden_layers": 6,
              "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                              "lightning-attn", "minicpm4",
                              "lightning-attn"],
              "first_published_layer": 2, "published_layers": 9,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "lightning_nh": 4, "lightning_nkv": 4,
              "lightning_head_dim": 16, "rope_theta": 10000,
              "scale_emb": 12, "mup_denominator": 6, "dim_model_base": 32,
              "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
              "topk": 4, "init_blocks": 1, "window_size": 64,
              "dense_len": 64, "vocab_size": 256,
              "max_position_embeddings": 1024, "torch_dtype": "float32"}
BYTES_OF = {"bfloat16": 2, "float16": 2, "float32": 4}
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def model_config(sizes: Dict):
    """The program's model configuration at the file's sizes. Only sizes are
    passed: every other field keeps the program's default."""
    import jax.numpy as jnp

    if (sizes.get("hidden_act") != "silu" or sizes.get("attention_bias")
            or sizes.get("attn_use_rope") or not sizes.get("qk_norm")
            or not sizes.get("lightning_use_rope")
            or sizes.get("lightning_scale") != "1/sqrt(d)"
            or not sizes.get("use_output_gate")
            or not sizes.get("use_output_norm")
            or not sizes.get("attn_use_output_gate")
            or sizes.get("tie_word_embeddings")):
        raise SystemExit("benchmark: a norm, a gate, a rotation or a bias "
                         "this family does not model")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        sizes["torch_dtype"]]
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "first_published_layer", "published_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "lightning_nh", "lightning_nkv", "lightning_head_dim",
            "mup_denominator", "dim_model_base", "max_position_embeddings",
            "kernel_size", "kernel_stride", "block_size", "topk",
            "init_blocks", "window_size", "dense_len")
    return minicpm_sala.MiniCPMSALAConfig(
        **{key: sizes[key] for key in same},
        mixer_types=tuple(sizes["mixer_types"]),
        rope_theta=float(sizes["rope_theta"]),
        rms_norm_eps=float(sizes["rms_norm_eps"]),
        scale_emb=float(sizes["scale_emb"]),
        scale_depth=float(sizes["scale_depth"]), dtype=dtype)


def _layers(sizes: Dict, kind: str) -> int:
    return sum(KINDS[m] == kind for m in sizes["mixer_types"])


def mlp_params(sizes: Dict) -> int:
    return 3 * sizes["hidden_size"] * sizes["intermediate_size"]


def lightning_params(sizes: Dict) -> int:
    """q, k, v, the gate and o; the QK-norms and the output norm a head's
    width; the layer's two norms; the MLP."""
    d = sizes["hidden_size"]
    w = sizes["lightning_nh"] * sizes["lightning_head_dim"]
    return (4 * d * w + w * d + 3 * sizes["lightning_head_dim"] + 2 * d
            + mlp_params(sizes))


def sparse_params(sizes: Dict) -> int:
    """q, the gate and o (32 heads), k and v (2), the QK-norms, the layer's
    two norms; the MLP."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    H, K = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    return (d * (2 * H + 2 * K) * hd + H * hd * d + 2 * hd + 2 * d
            + mlp_params(sizes))


def num_params(sizes: Dict) -> int:
    d = sizes["hidden_size"]
    return (2 * sizes["vocab_size"] * d + d
            + _layers(sizes, "lightning") * lightning_params(sizes)
            + _layers(sizes, "sparse") * sparse_params(sizes))


def attention_flops_per_pair(sizes: Dict) -> int:
    """Operations one query-context pair costs over the sparse layers: a
    head's score over hd dimensions and its value sum over hd, 2 each."""
    return (_layers(sizes, "sparse") * sizes["num_attention_heads"]
            * 2 * sizes["head_dim"] * 2)


def train_flops_per_token(sizes: Dict, seq: int) -> float:
    """Operations a token of a forward and backward pass: 6 a parameter its
    products touch, the sparse layers' attention over min(seq, topk blocks)
    of context (all of it up to `dense_len`), a lightning layer's recurrence
    by its own count whatever the context (a state element decayed, updated
    and read, 2 operations each), x 3. (The benchmark's copy of
    `MiniCPMSALAConfig.flops_per_token`; the family does not train.)"""
    n = num_params(sizes) - sizes["vocab_size"] * sizes["hidden_size"]
    seen = seq if seq <= sizes["dense_len"] else min(
        seq, sizes["topk"] * sizes["block_size"])
    state = 6 * sizes["lightning_nh"] * sizes["lightning_head_dim"] ** 2
    return (6.0 * n + 3.0 * attention_flops_per_pair(sizes) * seen
            + 3.0 * _layers(sizes, "lightning") * state)


def _row_bytes(sizes: Dict) -> int:
    """A K (or V) row of one token of one sparse layer."""
    return (sizes["num_key_value_heads"] * sizes["head_dim"]
            * BYTES_OF[sizes["torch_dtype"]])


def cache_bytes_per_token(sizes: Dict) -> int:
    """Bytes of cache one context token holds: the K and the V row (its kv
    heads side by side) of each sparse layer and its share of the page's
    mean row (one K row a page of `kernel_stride` tokens). The lightning
    layers hold nothing a token."""
    row = _row_bytes(sizes)
    return _layers(sizes, "sparse") * (2 * row + row // sizes["kernel_stride"])


def state_bytes_per_sequence(sizes: Dict) -> int:
    """A slot of the state group: every lightning layer's S (a head's values
    x keys, float32). The rows buffered beside it are the kernel's."""
    return (_layers(sizes, "lightning") * 4 * sizes["lightning_nh"]
            * sizes["lightning_head_dim"] ** 2)


def ssd_bytes(sizes: Dict, rows: int, sequences: int) -> int:
    """Bytes no form of the lightning layers' KERNEL can avoid for a step of
    `rows` rows of `sequences` sequences, every lightning layer: a
    sequence's S in ONCE, float32; a row's q, k and v in and its o out,
    float32 as the program states them. No write-back is counted (a decode
    row joins the buffer beside S; a fold's and a slice's write would make
    the floor higher, not lower)."""
    width = sizes["lightning_nh"] * sizes["lightning_head_dim"]
    return _layers(sizes, "lightning") * (
        rows * 4 * 4 * width
        + sequences * 4 * width * sizes["lightning_head_dim"])


def select_bytes(sizes: Dict, pages_scored: int, select_rows: int) -> int:
    """Bytes no form of the FIRST STAGE can avoid for a step, every sparse
    layer: each distinct page's mean row in once (rows that share a
    document's pages could score them in one walk), a selecting row's q in
    (the configuration's dtype) and its table out (`topk` int32 a kv
    head)."""
    item = BYTES_OF[sizes["torch_dtype"]]
    q = sizes["num_attention_heads"] * sizes["head_dim"] * item
    table = sizes["num_key_value_heads"] * sizes["topk"] * 4
    return _layers(sizes, "sparse") * (
        pages_scored * _row_bytes(sizes) + select_rows * (q + table))


def attend_bytes(sizes: Dict, select_seqs: int, select_rows: int) -> int:
    """Bytes no form of the SECOND STAGE can avoid for a step, every sparse
    layer: for every selecting (sequence, kv head) ONE token's kept set,
    `topk` blocks of `block_size` tokens of THAT head's K lanes and V lanes,
    once, however many of the sequence's tokens the step carries (their
    union holds at least one token's set), and a selecting row's q in and o
    out."""
    item = BYTES_OF[sizes["torch_dtype"]]
    head = 2 * sizes["head_dim"] * item             # K and V of one kv head
    kept = sizes["topk"] * sizes["block_size"] * head
    qo = 2 * sizes["num_attention_heads"] * sizes["head_dim"] * item
    return _layers(sizes, "sparse") * (
        select_seqs * sizes["num_key_value_heads"] * kept + select_rows * qo)


def reference_logits_at(params, tokens, positions, sizes: Dict):
    return reference.logits_at(params, tokens, positions, sizes)[0]


reference_loss_and_grad_norm = reference.loss_and_grad_norm
