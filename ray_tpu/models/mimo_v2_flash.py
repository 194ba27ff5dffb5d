"""MiMo-V2-Flash for the serving engine: window and full attention layers
side by side over a paged K/V cache with one block table a LAYER GROUP, a
leading dense layer, and expert layers that hold a SHARE of the published
experts behind a sigmoid top-k router.

Source: https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash (`config.json`; the
equations stand in models/mimo_v2_flash_reference.py's docstring). Pre-norm
residual decoder, RMSNorm eps 1e-5, final norm, untied head. What this file
states once and the serving runner (llm/model_runner.py) consumes through
`Block`:

  * Attention, both kinds: H query heads of width `head_dim` (192), K kv
    heads, values of width `v_head_dim` (128) scaled by
    `attention_value_scale`; rope (rotate-half) on the first
    int(head_dim x partial_rotary_factor) dimensions of every q and k head;
    scores over sqrt(head_dim).
  * A FULL layer (`hybrid_layer_pattern` 0): `num_key_value_heads` kv heads,
    `rope_theta`, causal, plain softmax. Its K and V rows are kept for every
    token of a sequence: the cache spec's group `all`.
  * A WINDOW layer (1): `swa_num_key_value_heads` kv heads, `swa_rope_theta`,
    token i sees j with 0 <= i - j < `sliding_window`, and a learned logit a
    head (`sink`) joins the softmax's denominator. Its rows are kept for the
    last `sliding_window` tokens and the step's own: the group `window`,
    whose pages the engine frees behind the window and whose block table is a
    ring (llm/model_runner.py, "Layer groups").
  * Feed-forward: layer 0 a SwiGLU; every later layer `sigmoid(x W_r)` over
    ALL published experts, the `top_k` best by score + `router_bias`
    (`noaux_tc`, one group), gates the kept scores over their sum, no scaling
    factor, no shared expert. The expert share is models/expert_share.py's.

In the cache a K row lies SPLIT, with no lane of padding
(ops/paged_attention.py, `KRow`, the one place the layout is stated): a
head's 192 lanes are a whole lane tile and a half, so a token's row is its K
heads' first 128 lanes side by side, then their last 64 packed two heads a
lane tile, K x 192 lanes in all (768 a full layer, 1,536 a window layer:
whole lane tiles, which is all Mosaic asks of a page DMA's minor dimension;
a HEAD of 192 it refuses, "Slice shape along dimension 2 must be aligned to
tiling (128), but is 192": compiled for a described v5e, PR 33, which is why
the rows lay padded to 256 lanes a head until PR 64). q rides 256 wide a
head: its first 128 lanes, then its last 64 in the half of a lane tile where
its kv head's lie in the shared tile and zeros in the other half, so the
kernel's two products a head (128 + 128 deep) meet the neighbour's lanes
with zeros. V is 128 a head. A full layer holds K x (192 + 128) x 2 = 2,560
bytes a token, all of them useful. Both groups' pools are ROW POOLS (PR 46),
`(layers, pages, page, K x 192)` and `(layers, pages, page, K x 128)`. What
rows change against `(..., K, width)` is the tile in VMEM: four (or eight)
kv heads fill a quarter (half) of a bfloat16 sublane tile, so a 5-D tile
took 4 x (2 x) its bytes there and the kernel had to compact it before every
product, where a row pool's tile is whole lane tiles and a head's parts
slices of them (ops/paged_attention.py, `_kv_rows_kernel`). The block has
two layer groups, whose pages do not travel, so the pools need no wire view.

Left out: the multi-token-prediction layers of the published model (no key
of `config.json` describes them) and the later vision and audio encoders.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import normal
from ray_tpu.models.expert_share import (ROUTER_BIAS_WIDTH, _dot32, _ffn,
                                         _wide, held_expert_ffn,
                                         kind_segments,
                                         route_one_group as route,
                                         router_bias as _router_bias,
                                         runs_of)
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.layers import rms_norm

LANE = 128
FULL, WINDOW = 0, 1
# A window head's sink logit is drawn N(SINK_MEAN, 1). A full window's 128
# scores of unit variance sum to about e^5.35, so at 3 the median head's sink
# takes a tenth of the softmax's mass, and a program without the sink reads
# 8% in the benchmark's check at the published widths against its 3e-2; at
# N(0, 1) it takes under a hundredth and the fault reads 2% (PERF.md section
# 6, PR 33).
SINK_MEAN = 3.0


@dataclasses.dataclass(frozen=True)
class MimoV2FlashConfig:
    """The published keys (their Hugging Face names), `vocab_size`, the two
    per-layer patterns and `max_position_embeddings` as run, and the share of
    the published experts this program holds."""
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    swa_num_key_value_heads: int = 8
    head_dim: int = 192
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 5e6
    swa_rope_theta: float = 1e4
    sliding_window: int = 128
    attention_value_scale: float = 0.707
    hybrid_layer_pattern: Tuple[int, ...] = (0, 1, 1, 1, 1, 0, 1)
    moe_layer_freq: Tuple[int, ...] = (0, 1, 1, 1, 1, 1, 1)
    n_routed_experts: int = 256          # the router's width: as published
    experts_held: Tuple[int, int] = (0, 256)   # published ids [first, stop)
    num_experts_per_tok: int = 8
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16

    def serving_block(self) -> "Block":
        return Block(self)

    def __post_init__(self):
        first, stop = self.experts_held
        if not 0 <= first < stop <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of 0..{self.n_routed_experts}")
        if len(self.hybrid_layer_pattern) != len(self.moe_layer_freq):
            raise ValueError("hybrid_layer_pattern and moe_layer_freq name "
                             "different numbers of layers")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("top_k over the router's width")

    # What the serving runner and engine read of any model's configuration.
    @property
    def max_seq(self) -> int:
        return self.max_position_embeddings

    @property
    def norm_eps(self) -> float:
        return self.layernorm_epsilon

    @property
    def num_hidden_layers(self) -> int:
        return len(self.hybrid_layer_pattern)

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    def kv_heads(self, kind: int) -> int:
        return (self.swa_num_key_value_heads if kind == WINDOW
                else self.num_key_value_heads)

    def k_row(self, kind: int) -> pa.KRow:
        """A K row of a `kind` layer as it lies in the pool, and q as it
        rides (the module docstring says how, `pa.KRow` states it)."""
        return pa.k_row(self.kv_heads(kind), self.head_dim)

    def layers_of(self, kind: int) -> int:
        return sum(1 for k in self.hybrid_layer_pattern if k == kind)

    @staticmethod
    def tiny(**overrides) -> "MimoV2FlashConfig":
        """Window 8 with pages of 4 passes the window many times in a short
        test; 16 published experts of which a test holds all or a share."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_attention_heads=8,
                    num_key_value_heads=2, swa_num_key_value_heads=4,
                    head_dim=24, v_head_dim=16, sliding_window=8,
                    hybrid_layer_pattern=(0, 1, 1, 0, 1),
                    moe_layer_freq=(0, 1, 1, 1, 1), n_routed_experts=16,
                    experts_held=(0, 16), num_experts_per_tok=4,
                    max_position_embeddings=256, dtype=jnp.float32)
        base.update(overrides)
        return MimoV2FlashConfig(**base)

    def attention_params(self, kind: int) -> int:
        d, H, K = self.hidden_size, self.num_attention_heads, \
            self.kv_heads(kind)
        return (d * H * self.head_dim + d * K * self.head_dim
                + d * K * self.v_head_dim + H * self.v_head_dim * d)

    def expert_params(self) -> int:
        return 3 * self.hidden_size * self.moe_intermediate_size

    def _layer_params(self, picks: float) -> float:
        """Attention and feed-forward parameters over the layers, an expert
        layer counted with `picks` experts."""
        d = self.hidden_size
        return sum(
            self.attention_params(kind)
            + (d * self.n_routed_experts + picks * self.expert_params()
               if moe else 3 * d * self.intermediate_size)
            for kind, moe in zip(self.hybrid_layer_pattern,
                                 self.moe_layer_freq))

    def num_params(self) -> int:
        """Parameters this program holds (the held experts, not the
        published count), norms, sinks and router biases left out."""
        return int(2 * self.vocab_size * self.hidden_size
                   + self._layer_params(self.n_held))

    def flops_per_token(self, seq: int) -> float:
        """Training operations a token, forward and backward (6 a parameter
        a token touches), counting the HELD share: of its top_k experts a
        token meets top_k * held / published here on average. Attention by
        the equations' own count, H * (qk + v) * 2 a query-context pair: a
        full layer's token sees `seq` of them, a window layer's at most the
        window."""
        picks = (self.num_experts_per_tok * self.n_held
                 / self.n_routed_experts)
        n = self._layer_params(picks) + self.hidden_size * self.vocab_size
        pair = self.num_attention_heads * (self.head_dim + self.v_head_dim)
        seen = (self.layers_of(FULL) * seq
                + self.layers_of(WINDOW) * min(seq, self.sliding_window))
        return 6.0 * n + 6.0 * pair * seen


# ------------------------------------------------------------ partial rope

def rope_at(config: MimoV2FlashConfig, theta: float, positions):
    """cos, sin (..., rotary_dim / 2) float32 at `positions` (...,): computed
    in the step program, not looked up (a table of `max_seq` rows is a
    constant of every serialized step program: deepseek_v2.rope_at)."""
    rot = config.rotary_dim
    inv_freq = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angle), jnp.sin(angle)


def partial_rope(x, cos, sin):
    """x (..., heads, head_dim): the first 2 * cos.shape[-1] dimensions
    rotated as rotate-half pairs (i, i + rot / 2), the others as they are.
    float32 out."""
    rot = 2 * cos.shape[-1]
    x = x.astype(jnp.float32)
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


# -------------------------------------------------------------- parameters

def layer_kinds(config: MimoV2FlashConfig):
    """[(segment kind, layers)]: runs of like layers in the published order.
    A kind is "full_dense", "window_moe", "full_moe" (or "window_dense")."""
    names = [("window" if a == WINDOW else "full")
             + ("_moe" if m else "_dense")
             for a, m in zip(config.hybrid_layer_pattern,
                             config.moe_layer_freq)]
    return runs_of(names)


def init_params(config: MimoV2FlashConfig, key: jax.Array) -> Dict:
    """Random normal, 1/sqrt(fan_in), but the embedding's rows N(0, 1) (what
    `torch.nn.Embedding` draws): a window layer's sink logits N(SINK_MEAN, 1)
    and an expert layer's router bias `_router_bias`'s. Why the embedding is
    not 1/sqrt(d): attention over random weights averages its context, so
    what it adds is shared by every token of a context and grows with depth,
    while a row of norm 1 is lost under it; every router then sees much the
    same vector, one expert a layer takes 28-98% of the tokens, and how many
    of the favoured ones a chip's share holds moves its work by a quarter
    from seed to seed. Under rows of norm sqrt(d) a token's own row leads the
    stream, the routers see tokens, and the held experts' load is the same at
    every seed. Under this draw a program without the sink, the bias or the
    value scale fails the benchmark's check at the published widths (PERF.md
    section 6, PR 33, the controls). Every stacked weight is drawn a
    slice at a time and cast inside one program (no float32 copy of a stack:
    deepseek_v2.init_params). `params["layers"]` is one dict a KIND of layer,
    its layers stacked in the published order; `params["experts"]` one dict
    an expert layer (deepseek_v2.Block.segments says why)."""
    c = config
    d, H = c.hidden_size, c.num_attention_heads
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

    layers: Dict[str, Dict] = {}
    n_moe = 0
    for name in sorted({name for name, _ in layer_kinds(c)}):
        L = sum(len(ls) for n_, ls in layer_kinds(c) if n_ == name)
        kind = WINDOW if name.startswith("window") else FULL
        K = c.kv_heads(kind)
        p = {"attn_norm": ones(L, d),
             "wq": stack((L,), (d, H * c.head_dim), d),
             "wk": stack((L,), (d, K * c.head_dim), d),
             "wv": stack((L,), (d, K * c.v_head_dim), d),
             "wo": stack((L,), (H * c.v_head_dim, d), H * c.v_head_dim),
             "mlp_norm": ones(L, d)}
        if kind == WINDOW:
            p["sink"] = normal(next(keys), (L, H), 1.0, SINK_MEAN)
        if name.endswith("_moe"):
            n_moe += L
            p["router"] = stack((L,), (d, c.n_routed_experts), d)
            p["router_bias"] = _router_bias(next(keys), L,
                                            c.n_routed_experts, c.n_held)
        else:
            f = c.intermediate_size
            p.update(w_gate=stack((L,), (d, f), d),
                     w_up=stack((L,), (d, f), d),
                     w_down=stack((L,), (f, d), f))
        layers[name] = p
    fm = c.moe_intermediate_size
    return {
        "embed": stack((), (c.vocab_size, d), 1),
        "layers": layers,
        # The held experts, one dict an expert layer in the published order.
        "experts": [{"w_gate": stack((c.n_held,), (d, fm), d),
                     "w_up": stack((c.n_held,), (d, fm), d),
                     "w_down": stack((c.n_held,), (fm, d), fm)}
                    for _ in range(n_moe)],
        "final_norm": ones(d),
        "lm_head": stack((), (d, c.vocab_size), d),
    }


# -------------------------------------------------------- the serving block

class Block:
    """MiMo-V2-Flash as the serving runner consumes a model (the protocol is
    llm/model_runner.py's, "A block"): two layer groups, four pools."""

    def __init__(self, config: MimoV2FlashConfig):
        from ray_tpu.llm.model_runner import LayerGroup

        self.config = config
        self.routed_layers = sum(1 for m in config.moe_layer_freq if m)
        self.top_k = config.num_experts_per_tok
        self.held_experts = config.n_held
        # float32 for the reason deepseek_v2.py's "precision" gives: the
        # routed check follows the program's experts, and a bf16 stream's
        # rounding moves both the scores and the logits it is held to.
        self.residual_dtype = jnp.float32
        self.scale = config.head_dim ** -0.5
        # (at any page size: the query block does not depend on it)
        self.q_block = self.kv_kernels(16)["all"].q_block
        self.groups = (LayerGroup("all"),
                       LayerGroup("window", config.sliding_window))
        # A layer's index inside its group's pools.
        seen = {FULL: 0, WINDOW: 0}
        self.pool_layer = []
        for kind in config.hybrid_layer_pattern:
            self.pool_layer.append(seen[kind])
            seen[kind] += 1

    def refuse(self, *, tensor_parallel: int, lora: bool) -> None:
        if tensor_parallel > 1:
            raise ValueError("mimo_v2_flash: tensor_parallel > 1 is not "
                             "supported (4 kv heads a full layer, and no "
                             "exchange of the expert shares)")
        if lora:
            raise ValueError("mimo_v2_flash: LoRA adapters are not supported")

    def pallas_ok(self) -> bool:
        c = self.config
        return c.v_head_dim % LANE == 0 and all(
            c.k_row(kind).lanes % LANE == 0 for kind in (FULL, WINDOW))

    # ---- cache -----------------------------------------------------------

    def cache_arrays(self, pages: Dict[str, int], block_size: int):
        """K and V of each group as ROW POOLS: (layers of the group, the
        group's pages, page, the group's K row (`k_row`) | kv heads x 128)."""
        from ray_tpu.llm.model_runner import row_cache_array

        c = self.config
        out = []
        for group, kind in (("all", FULL), ("window", WINDOW)):
            lead = (c.layers_of(kind), pages[group], block_size)
            K = c.kv_heads(kind)
            out += [row_cache_array(f"k_{group}",
                                    lead + (c.k_row(kind).lanes,), c.dtype,
                                    group),
                    row_cache_array(f"v_{group}", lead + (K * c.v_head_dim,),
                                    c.dtype, group)]
        return tuple(out)

    def kv_kernels(self, block_size: int):
        """{page group: the sizes its kernel takes} (`pa.kv_sizes`)."""
        c = self.config
        return {group: pa.kv_sizes(
            c.num_attention_heads, c.kv_heads(kind), c.head_dim,
            c.v_head_dim, block_size, jnp.dtype(c.dtype).itemsize, rows=True,
            window=c.sliding_window if kind == WINDOW else None)
            for group, kind in (("all", FULL), ("window", WINDOW))}

    def init_cache(self, pages: Dict[str, int], block_size: int):
        from ray_tpu.llm.model_runner import init_cache

        return init_cache(self.cache_arrays(pages, block_size))

    def segments(self, params):
        """Runs of like layers in the published order, each a Python loop
        (`expert_share.kind_segments`)."""
        return kind_segments(layer_kinds(self.config), params)

    def attention_fns(self, impl: str):
        rect, ragged = (
            (pa.ragged_paged_attention, pa.ragged_paged_attention_unified)
            if impl == "pallas" else
            (pa.ragged_paged_attention_reference,
             pa.ragged_paged_attention_unified_reference))
        return rect, ragged

    # ---- the layer step, stated once --------------------------------------

    def layer_step(self, ctx, kind: str, x, caches, lp, li, ll):
        """One layer over rows x (..., d); `li` is the layer's published
        index, a Python int. -> (x, caches, aux): aux None for a dense layer,
        (ids (..., top_k), counts (3,)) for an expert layer."""
        c = self.config
        window = kind.startswith("window")
        group = "window" if window else "all"
        at = 2 if window else 0
        pool_li = self.pool_layer[li]
        lead = x.shape[:-1]
        row = c.k_row(WINDOW if window else FULL)
        H, K = c.num_attention_heads, row.heads
        hd, vd, dt = c.head_dim, c.v_head_dim, c.dtype

        h = rms_norm(x, lp["attn_norm"], c.layernorm_epsilon).astype(dt)
        cos, sin = rope_at(c, c.swa_rope_theta if window else c.rope_theta,
                           ctx.rope_pos)
        # q is laid as it rides BEFORE the rotation (which turns lanes under
        # `rotary_dim` only, inside a head's whole tile): slicing the
        # rotation's float32 output at lane 128 cost a copy of it a layer.
        q = partial_rope(row.queries(_dot32(h, lp["wq"]).reshape(
            *lead, H, hd)), cos, sin)
        k = partial_rope(_dot32(h, lp["wk"]).reshape(*lead, K, hd), cos, sin)
        v = (_dot32(h, lp["wv"]) * c.attention_value_scale).reshape(
            *lead, K, vd)
        caches = list(caches)
        # A token's row whole: K as `row` lays it, V's heads side by side.
        caches[at] = ctx.write(
            caches[at], pool_li, row.lay(k.astype(dt)), group)
        caches[at + 1] = ctx.write(
            caches[at + 1], pool_li, v.astype(dt).reshape(*lead, K * vd),
            group)
        attn = ctx.attend(
            q.astype(dt), caches[at], caches[at + 1], pool_li,
            group=group, scale=self.scale, kv_heads=K,
            **({"window": c.sliding_window, "sink": lp["sink"]}
               if window else {}))
        x = x + _dot32(attn.reshape(*lead, H * vd), lp["wo"])

        h = rms_norm(x, lp["mlp_norm"], c.layernorm_epsilon)
        if kind.endswith("_dense"):
            x = x + _ffn(_dot32, h.astype(dt), lp["w_gate"], lp["w_up"],
                         lp["w_down"])
            return x, tuple(caches), None
        flat = h.reshape(-1, c.hidden_size)
        # The router's chain stays float32 (two bf16 passes over its
        # weights): a score's rounding is a choice's.
        scores = jax.nn.sigmoid(_wide(_dot32, flat, lp["router"]))
        ids, gates = route(c, scores, lp["router_bias"])
        routed, counts = held_expert_ffn(
            c, flat.astype(dt), ids, gates, ctx.valid.reshape(-1), lp)
        return (x + routed.reshape(x.shape), tuple(caches),
                (ids.reshape(*lead, self.top_k), counts))
