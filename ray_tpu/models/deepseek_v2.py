"""DeepSeek-V2 for the serving engine: latent attention (MLA) over a paged
latent cache, a leading dense layer, and expert layers that hold a SHARE of
the published experts and route over all of them without drops.

Source: https://huggingface.co/deepseek-ai/DeepSeek-V2 (`config.json`, and
`modeling_deepseek.py` for the equations). Pre-norm residual decoder, eps
1e-6, final norm, untied head. What this file states once and the serving
runner (llm/model_runner.py) consumes through `Block`:

  * Latent attention, every layer. `c_q = rms(x W_qa)`; `q = c_q W_qb`, H
    heads of `[q_nope | q_rope]`; `[c_kv | k_rope] = x W_kva`, `c_kv =
    rms(c_kv)`, `k_rope` rotated, one for all heads. The cache row of a token
    of a layer is `[c_kv | k_rope]` with no head axis. Scores and values in
    the ABSORBED form: `q_lat_h = q_nope_h W_kb_h` (as wide as `c_kv`), `s_h
    = (q_lat_h . c_kv + q_rope_h . k_rope) * scale`, `o_h = (sum p c_kv)
    W_vb_h`: the same numbers as expanding `k_nope_h = c_kv W_kb_h^T`, `v_h =
    c_kv W_vb_h` for every context token (tests/test_llm_deepseek_v2.py holds
    the two equal), with the context read once for all heads.
  * YaRN rotary positions on the rope dimensions (`yarn_inv_freq`), and the
    softmax scale `(nope + rope)^-0.5 * m^2` (`attention_scale`).
  * Feed-forward: the first `first_k_dense_replace` layers a SwiGLU; every
    later layer `softmax_fp32(x W_g)` over ALL published experts, the
    `topk_group` best of `n_group` groups by their best expert, the `top_k`
    best experts inside them (`group_limited_greedy`), gates those
    probabilities (not renormalised) times `routed_scaling_factor`, plus the
    shared expert.

**The expert share.** A deployment splits a layer's experts over chips. This
program holds the experts `experts_held = (first, stop)` (published ids) and
the router at its published width: it routes every token over all experts
exactly as above, computes what ITS experts contribute plus the shared
expert, and leaves out what absent experts would add (their chips' partial
results, summed by an exchange this repo does not have yet: ROADMAP). No token
is dropped and no capacity is set: the token-expert pairs that hit a held
expert are sorted by expert and go through one ragged product a projection
(`jax.lax.ragged_dot`), whose static shape is tokens x top_k. Four programs
holding 0-39, 40-79, 80-119 and 120-159 sum to the uncut layer, the shared
expert counted once (tests/test_llm_deepseek_v2.py).

Departures from the checkpoint's layout, all relabellings of random weights:
`kv_b_proj` is kept split per head as `w_kb (H, nope, lat)` and `w_vb (H, lat,
v)`; the rope dimensions are held de-interleaved (rotate-half pairs i and i +
rope/2, as the published code has them after its own de-interleave).

Training a routed model without drops is ROADMAP S5 (models/moe.py is the
capacity-dispatch training layer); S5 should reuse `route` here and
`held_expert_ffn` (models/expert_share.py, shared with models/
mimo_v2_flash.py) rather than grow a third.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.expert_share import (_dot32, _ffn, _wide,
                                         held_expert_ffn)
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.layers import rms_norm

LANE = 128


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    """The published keys (their Hugging Face names), `vocab_size`,
    `num_hidden_layers` and `max_position_embeddings` as run, and the share
    of the published experts this program holds."""
    vocab_size: int = 102400
    hidden_size: int = 5120
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 60
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 160          # the router's width: as published
    experts_held: Tuple[int, int] = (0, 160)   # published ids [first, stop)
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    first_k_dense_replace: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    max_position_embeddings: int = 163840
    dtype: Any = jnp.bfloat16

    def serving_block(self) -> "Block":
        return Block(self)

    def __post_init__(self):
        first, stop = self.experts_held
        per_group = self.n_routed_experts // self.n_group
        if not 0 <= first < stop <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of 0..{self.n_routed_experts}")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")
        if self.topk_group * per_group < self.num_experts_per_tok:
            raise ValueError("the kept groups hold fewer experts than top_k")

    # What the serving runner and engine read of any model's configuration.
    @property
    def max_seq(self) -> int:
        return self.max_position_embeddings

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_dense_layers(self) -> int:
        return min(self.first_k_dense_replace, self.num_hidden_layers)

    @property
    def n_moe_layers(self) -> int:
        return self.num_hidden_layers - self.n_dense_layers

    @property
    def row_width(self) -> int:
        """A latent cache row as it lies in HBM: `[c_kv | k_rope]` padded
        with zeros to whole lane tiles (576 -> 640 at the published sizes)."""
        used = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-used // LANE) * LANE

    @staticmethod
    def tiny(**overrides) -> "DeepseekV2Config":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_hidden_layers=3,
                    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    n_routed_experts=16, experts_held=(0, 16), n_group=4,
                    topk_group=2, num_experts_per_tok=3,
                    rope_original_max_position=64,
                    max_position_embeddings=256, dtype=jnp.float32)
        base.update(overrides)
        return DeepseekV2Config(**base)

    def attention_params(self) -> int:
        d, H = self.hidden_size, self.num_attention_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        return (d * self.q_lora_rank + self.q_lora_rank * H * qk
                + d * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + H * self.v_head_dim * d)

    def expert_params(self) -> int:
        return 3 * self.hidden_size * self.moe_intermediate_size

    def num_params(self) -> int:
        """Parameters this program holds (the held experts, not the
        published count), norms left out."""
        d = self.hidden_size
        dense = self.attention_params() + 3 * d * self.intermediate_size
        moe = (self.attention_params() + d * self.n_routed_experts
               + (self.n_held + self.n_shared_experts) * self.expert_params())
        return (2 * self.vocab_size * d + self.n_dense_layers * dense
                + self.n_moe_layers * moe)

    def flops_per_token(self, seq: int) -> float:
        """Training operations a token, forward and backward (6 a parameter a
        token touches), counting the HELD share: of its top_k experts a token
        meets top_k * held / published here on average. Attention by the
        equations' own count, H * (qk + v) * 2 a query-context pair."""
        d = self.hidden_size
        picks = (self.num_experts_per_tok * self.n_held
                 / self.n_routed_experts)
        dense = self.attention_params() + 3 * d * self.intermediate_size
        moe = (self.attention_params() + d * self.n_routed_experts
               + (picks + self.n_shared_experts) * self.expert_params())
        n = (self.n_dense_layers * dense + self.n_moe_layers * moe
             + d * self.vocab_size)
        pair = self.num_attention_heads * (
            self.qk_nope_head_dim + self.qk_rope_head_dim + self.v_head_dim)
        return 6.0 * n + 6.0 * self.num_hidden_layers * pair * seq


# ------------------------------------------------------------- YaRN rotary

def yarn_inv_freq(config: DeepseekV2Config):
    """(inv_freq (rope/2,), low, high): below `low` a dimension keeps its
    frequency, above `high` it is divided by the factor, a linear ramp
    between."""
    dim, base = config.qk_rope_head_dim, config.rope_theta
    orig = config.rope_original_max_position

    def correction(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction(config.rope_beta_fast)), 0)
    high = min(math.ceil(correction(config.rope_beta_slow)), dim - 1)
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    freq = base ** (-2.0 * i / dim)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = (freq / config.rope_factor) * ramp + freq * (1.0 - ramp)
    return inv_freq, low, high


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def attention_scale(config: DeepseekV2Config) -> float:
    m = yarn_mscale(config.rope_factor, config.rope_mscale_all_dim)
    qk = config.qk_nope_head_dim + config.qk_rope_head_dim
    return qk ** -0.5 * m * m


def rope_at(config: DeepseekV2Config, positions):
    """cos, sin (..., rope/2) float32 at `positions` (...,), scaled by
    m(mscale) / m(mscale_all_dim) (1 at the published values). Computed from
    the positions in the step program, not looked up: a (max_seq, rope/2)
    table is a constant of the program, and at 16,384 positions its 32
    columns pad to 128 lanes, 8 MiB a table in every serialized step program
    (16 of a program's 63 MiB, where the machine's compile cache holds 192)."""
    inv_freq, _, _ = yarn_inv_freq(config)
    m = (yarn_mscale(config.rope_factor, config.rope_mscale)
         / yarn_mscale(config.rope_factor, config.rope_mscale_all_dim))
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angle) * m, jnp.sin(angle) * m


def rotate_half(x, cos, sin):
    """x (..., heads, rope) rotated by cos, sin (..., rope/2): pairs (i, i +
    rope/2). float32 out."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# -------------------------------------------------------------- parameters

def init_params(config: DeepseekV2Config, key: jax.Array) -> Dict:
    """Random normal, 1/sqrt(fan_in). Every stacked weight is drawn a slice
    (a layer; for the experts, an expert) at a time and cast to the
    configuration's dtype inside one program, so that no float32 copy of a
    stack exists: at the published widths the held experts of four layers
    are 7.5 GB in bf16."""
    c = config
    d, H = c.hidden_size, c.num_attention_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    lat, rope = c.kv_lora_rank, c.qk_rope_head_dim
    keys = iter(jax.random.split(key, 64))

    def stack(lead: Tuple[int, ...], shape: Tuple[int, ...], fan_in: int):
        n = math.prod(lead)

        @jax.jit
        def draw(ks):
            return jax.lax.map(
                lambda k: (jax.random.normal(k, shape, jnp.float32)
                           * (1.0 / math.sqrt(fan_in))).astype(c.dtype), ks)

        return draw(jax.random.split(next(keys), n)).reshape(lead + shape)

    def ones(*shape):
        return jnp.ones(shape, dtype=c.dtype)

    def attention(L):
        return {
            "attn_norm": ones(L, d),
            "wq_a": stack((L,), (d, c.q_lora_rank), d),
            "q_norm": ones(L, c.q_lora_rank),
            "wq_b": stack((L,), (c.q_lora_rank, H * qk), c.q_lora_rank),
            "wkv_a": stack((L,), (d, lat + rope), d),
            "kv_norm": ones(L, lat),
            "w_kb": stack((L, H), (c.qk_nope_head_dim, lat), lat),
            "w_vb": stack((L, H), (lat, c.v_head_dim), lat),
            "wo": stack((L,), (H * c.v_head_dim, d), H * c.v_head_dim),
            "mlp_norm": ones(L, d),
        }

    Ld, Lm = c.n_dense_layers, c.n_moe_layers
    f, fm = c.intermediate_size, c.moe_intermediate_size
    fs = c.n_shared_experts * fm
    params = {
        "embed": stack((), (c.vocab_size, d), d),
        "dense_layers": {
            **attention(Ld),
            "w_gate": stack((Ld,), (d, f), d),
            "w_up": stack((Ld,), (d, f), d),
            "w_down": stack((Ld,), (f, d), f),
        },
        "moe_layers": {
            **attention(Lm),
            "router": stack((Lm,), (d, c.n_routed_experts), d),
            "shared_gate": stack((Lm,), (d, fs), d),
            "shared_up": stack((Lm,), (d, fs), d),
            "shared_down": stack((Lm,), (fs, d), fs),
        },
        # The held experts, one dict an expert layer and not one stack: see
        # Block.segments.
        "experts": [{"w_gate": stack((c.n_held,), (d, fm), d),
                     "w_up": stack((c.n_held,), (d, fm), d),
                     "w_down": stack((c.n_held,), (fm, d), fm)}
                    for _ in range(Lm)],
        "final_norm": ones(d),
        "lm_head": stack((), (d, c.vocab_size), d),
    }
    return params


# ----------------------------------------------------------------- routing

def route(config: DeepseekV2Config, scores: jax.Array):
    """`group_limited_greedy` over `scores` (N, published experts), a
    softmax's: -> (ids (N, top_k) int32, published; gates (N, top_k)). Ties go
    to the lower id, for groups and for experts (`lax.top_k`)."""
    n, e = scores.shape
    per_group = e // config.n_group
    group_score = scores.reshape(n, config.n_group, per_group).max(-1)
    _, groups = jax.lax.top_k(group_score, config.topk_group)
    kept = jnp.zeros((n, config.n_group), bool).at[
        jnp.arange(n)[:, None], groups].set(True)
    inside = jnp.repeat(kept, per_group, axis=1)
    gates, ids = jax.lax.top_k(jnp.where(inside, scores, -1.0),
                               config.num_experts_per_tok)
    return ids.astype(jnp.int32), gates * config.routed_scaling_factor


# --------------------------------------------------------------- precision
#
# In bf16 this layer is noisier than a GQA layer: every rounding that feeds
# the scores is amplified by the softmax (logits of standard deviation 1.6
# under YaRN's scale), and the latent chain has twice as many of them (x ->
# rms -> W_qa -> rms -> W_qb -> rope / W_kb; x -> rms -> W_kva -> rms ->
# cache). One dense layer at the published widths read 1.2% against the
# float32 reference, five layers 3.1-3.5%, over the benchmark's 3e-2 (my chip
# runs, PR 29; Mistral's sixteen layers read 1.3-1.6%). By site (one layer,
# rounding one site at a time): the normed input of the two latent
# down-projections 0.46%, their outputs 0.29% and 0.36%, the cache row 0.37%,
# the residual adds and the products added to them 0.15-0.23% each. So:
#   - the residual stream is float32 (`residual_dtype`), and products added
#     to it accumulate into float32 without a rounding of their own;
#   - the query's chain up to the kernel (W_qa, its norm, W_qb, rope, W_kb)
#     and the latent down-projection W_kva with its norm stay float32: each
#     product takes its float32 input as two bf16 parts (`_wide`: two passes
#     over bf16 weights; these are 40% of a layer's attention weights and
#     under 5% of an expert layer's operations a token), and the query is
#     rounded once, as the kernel's operand;
#   - SwiGLU's two products stay float32 until their one rounding.
# Weights, cache rows, the kernel's operands and every other matmul input
# stay bf16. One layer then reads 0.68% in the same emulation, five layers
# 2.2-2.4% on the chip before the query's chain was widened.

def _absorb(q_nope, w_kb):
    return jnp.einsum("...hn,hnl->...hl", q_nope, w_kb,
                      preferred_element_type=jnp.float32)


def latent_attention(ctx, c, pool, li, q, kv, lp, rotate=None,
                     own_rows: bool = False, **attend_kw):
    """A latent layer's attention from its two projections on, for every
    block whose cache row is `[c_kv | k_rope]` (this one, models/
    kimi_linear.py's, which rotates nothing, and models/glm_dsa.py's, whose
    `attend_kw` carry a selection of the context to its own attention, and
    which takes the step's own rows as written beside them, `own_rows`): q
    (..., H, nope + rope) and kv (..., lat + rope) float32; `rotate` turns the
    rope lanes of both (x (..., heads, rope) -> float32) or is None; `li` the
    layer's index in `pool` (a pair (group, place) where the pool's rows hold
    several layers' side by side: llm/model_runner.py, "The latent pool").
    The latent is normed, the row written, the query absorbed (`_absorb`),
    the paged kernel attends and the values are expanded (`v_head_dim` wide,
    whatever `qk_nope_head_dim` is). -> (what the layer adds to the residual
    stream (..., d) float32, pool). `c` gives the widths, the
    eps and the dtype; `lp` kv_norm, w_kb, w_vb, wo."""
    lead = q.shape[:-2]
    H, lat, rope = c.num_attention_heads, c.kv_lora_rank, c.qk_rope_head_dim
    nope, dt = c.qk_nope_head_dim, c.dtype
    pad = c.row_width - lat - rope
    q_rope, k_rope = q[..., nope:], kv[..., None, lat:]
    if rotate is not None:
        q_rope, k_rope = rotate(q_rope), rotate(k_rope)
    ckv = rms_norm(kv[..., :lat], lp["kv_norm"], c.rms_norm_eps).astype(dt)
    row = jnp.concatenate(
        [ckv, k_rope[..., 0, :].astype(dt),
         jnp.zeros(lead + (pad,), ckv.dtype)], axis=-1)
    pool = ctx.write(pool, li, row)
    if own_rows:
        attend_kw["own"] = row
    q_lat = _wide(_absorb, q[..., :nope], lp["w_kb"])
    q_cat = jnp.concatenate(
        [q_lat, q_rope, jnp.zeros(lead + (H, pad), q_lat.dtype)],
        axis=-1).astype(dt)
    o_lat = ctx.attend(q_cat, pool, li, **attend_kw)
    o = jnp.einsum("...hl,hlv->...hv", o_lat, lp["w_vb"])
    return _dot32(o.reshape(*lead, H * c.v_head_dim), lp["wo"]), pool


# -------------------------------------------------------- the serving block

class Block:
    """DeepSeek-V2 as the serving runner consumes a model (the protocol is
    llm/model_runner.py's, "A block")."""

    def __init__(self, config: DeepseekV2Config):
        self.config = config
        self.q_block = pa.latent_q_block(
            config.num_attention_heads, config.row_width)
        self.routed_layers = config.n_moe_layers
        self.top_k = config.num_experts_per_tok
        self.held_experts = config.n_held
        self.residual_dtype = jnp.float32       # see "precision" above
        self.scale = attention_scale(config)

    def refuse(self, *, tensor_parallel: int, lora: bool) -> None:
        if tensor_parallel > 1:
            raise ValueError(
                "deepseek_v2: tensor_parallel > 1 is not supported (the "
                "latent row has no head axis to shard the pool over)")
        if lora:
            raise ValueError("deepseek_v2: LoRA adapters are not supported")

    def pallas_ok(self) -> bool:
        return self.config.row_width % LANE == 0

    # ---- cache -----------------------------------------------------------

    def cache_arrays(self, pages: Dict[str, int], block_size: int):
        from ray_tpu.llm.model_runner import latent_cache_array

        c = self.config
        return (latent_cache_array(
            "latent", (c.num_hidden_layers, pages["all"], block_size,
                       c.row_width), c.dtype),)

    def init_cache(self, pages: Dict[str, int], block_size: int):
        from ray_tpu.llm.model_runner import init_cache

        return init_cache(self.cache_arrays(pages, block_size))

    def segments(self, params):
        """(kind, stacked layer parameters, first layer's index, parameters
        held apart a layer). XLA's grouped products (`ragged-dot` custom
        calls) take a layer's expert weights as one whole operand: sliced
        out of a (layers, held, d, f) stack, by a scan or statically, they
        are COPIED every step, 1.9 GB a layer at the published widths (23.6
        ms of a 69.7 ms tick: my chip run, PR 29). So every expert layer's
        three expert arrays are parameters of their own
        (`params["experts"][layer]`), and the expert layers run as a Python
        loop."""
        c = self.config
        out = []
        if c.n_dense_layers:
            out.append(("dense", params["dense_layers"], 0, None))
        if c.n_moe_layers:
            out.append(("moe", params["moe_layers"], c.n_dense_layers,
                        params["experts"]))
        return out

    def attention_fns(self, impl: str):
        rect, ragged = (
            (pa.latent_paged_attention, pa.latent_paged_attention_unified)
            if impl == "pallas" else
            (pa.latent_paged_attention_reference,
             pa.latent_paged_attention_unified_reference))
        kw = dict(scale=self.scale, lat=self.config.kv_lora_rank)
        return (lambda *a: rect(*a, **kw)), (lambda *a: ragged(*a, **kw))

    # ---- the layer step, stated once --------------------------------------

    def layer_step(self, ctx, kind: str, x, caches, lp, li, ll):
        """One layer over rows x (..., d). -> (x, caches, aux): aux is None
        for a dense layer, (ids (..., top_k), counts (3,)) for an expert
        layer."""
        c = self.config
        (pool,) = caches
        lead = x.shape[:-1]
        H = c.num_attention_heads
        nope, rope = c.qk_nope_head_dim, c.qk_rope_head_dim
        dt = c.dtype

        h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps)      # float32
        cq = rms_norm(_wide(_dot32, h, lp["wq_a"]), lp["q_norm"],
                      c.rms_norm_eps)
        q = _wide(_dot32, cq, lp["wq_b"]).reshape(*lead, H, nope + rope)
        cos, sin = rope_at(c, ctx.rope_pos)
        out, pool = latent_attention(
            ctx, c, pool, li, q, _wide(_dot32, h, lp["wkv_a"]), lp,
            rotate=lambda a: rotate_half(a, cos, sin))
        x = x + out

        h = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps).astype(dt)
        if kind == "dense":
            x = x + _ffn(_dot32, h, lp["w_gate"], lp["w_up"], lp["w_down"])
            return x, (pool,), None
        flat = h.reshape(-1, c.hidden_size)
        scores = jax.nn.softmax(_dot32(flat, lp["router"]), axis=-1)
        ids, gates = route(c, scores)
        routed, counts = held_expert_ffn(
            c, flat, ids, gates, ctx.valid.reshape(-1), lp)
        y = routed + _ffn(_dot32, flat, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"])
        return (x + y.reshape(x.shape), (pool,),
                (ids.reshape(*lead, self.top_k), counts))
