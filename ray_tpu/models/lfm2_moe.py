"""LFM2-24B-A2B (`model_type: lfm2_moe`) for the serving engine: gated
SHORT-CONVOLUTION layers whose whole state a sequence is the last two rows of
the convolution's input, three to one beside grouped-query attention layers
at a head width of HALF a lane tile, leading dense layers, and expert layers
that hold a share of 64 sigmoid-routed experts: here ALL of them, with no
shared expert.

Source: https://huggingface.co/LiquidAI/LFM2-24B-A2B (`config.json`; the
equations stand in models/lfm2_moe_reference.py's docstring, with what the
config does not carry and is assumed). What this file states once and the
serving runner (llm/model_runner.py) consumes through `Block`:

  * Two LAYER GROUPS. `all`: the K and V ROW POOLS of the `full_attention`
    layers in the PAIR FORM BY RUNS (ops/paged_attention.py, `pair_queries`):
    a pool row is one kv PAIR `[k_2j | k_2j+1]`, 128 lanes, K / 2 of them a
    token's row, nothing padded and no value twice in HBM; the H / K query
    heads of kv head 2j ride in the first half of their 128-lane rows, those
    of 2j + 1 in the second, and each head keeps its OWN half of the value
    sum (`pair_outputs`). `state`: a slot a sequence whose ONLY array is the
    conv layers' tail, `(g_{t-1}, g_t)` of `g = B * z`, 2 x hidden values a
    layer in the configuration's dtype: no matrix state, no buffer, no fill,
    no kernel (ops/state_slots.py says what of the contract still holds). A
    prefix hit needs a page chain AND a parked slot (llm/engine.py).
  * The convolution is `ops/ssm_scan.ragged_conv` at `conv_L_cache` taps and
    a zero bias, over `g` and NOT over z alone; no activation.
  * Attention: q and k normed a head (one gain vector a layer each), then
    rotated over the whole head (rotate-half, computed in the step); K is
    cached after both.
  * Segments: runs of like layers in the published order ("conv_dense",
    "conv_moe", "attn_moe", "attn_dense"), each a Python loop, the experts'
    weights held apart (deepseek_v2.Block.segments says why).
  * The expert layer is models/expert_share.py's: `route_one_group` (sigmoid
    + `expert_bias` in the selection only, the 4 best of 64, renormalised
    over their sum + 1e-6, x `routed_scaling_factor`) and `held_expert_ffn`
    over the held experts' sorted pairs: no row passes an expert it did not
    pick, though every expert is here.

Precision: the residual stream, the router's chain, the softmax, the FIR's
sum and all norms float32 (deepseek_v2.py, "precision"); weights, K/V rows
and the tail the configuration's dtype.

Left out: training (no cell trains a routed model), tensor parallelism (a
slot's tail is not sharded, and no exchange of the expert shares), LoRA.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.afmoe import rope_at
from ray_tpu.models.expert_share import (_dot32, _ffn, _wide, held_expert_ffn,
                                         kind_segments, route_one_group,
                                         router_bias, runs_of)
from ray_tpu.models.mimo_v2_flash import partial_rope
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import ssm_scan as ss
from ray_tpu.ops.layers import rms_norm

LANE = 128
F32 = jnp.float32
CONV, ATTN = "conv", "full_attention"
PUBLISHED_LAYERS = tuple(ATTN if li % 4 == 2 else CONV for li in range(40))
# What the kept scores' sum takes before it divides.
GATE_EPS = 1e-6
# The drawn gain of `q_layernorm` / `k_layernorm`, every lane. With the
# reference alone at the published widths (PERF.md section 6, PR 63: the
# draw's two tests): at gain 1 an attention layer adds 0.21 a lane to the
# stream where a conv layer adds 1.0, and the reference with QK-norm dropped,
# the rotation dropped or the halves of a pair swapped moves the logits by
# 1.2 / 4.2 / 7.8%: a check at 3% is blind to the first and near blind to
# the others. At 1.5 (scores of standard deviation 2.25: attention picks
# tokens) it adds 0.41 and the three read 11-13 / 16-18 / 18-21%; at 2, 0.64
# and 23 / 26 / 32%, but every attention layer multiplies the bf16 stream's
# rounding by about the scores' variance (models/afmoe.py's QK_NORM_GAIN
# says what 2 cost there). 1.5 is the least of the three under which each
# control moves the logits by 3 x the check's tolerance.
QK_NORM_GAIN = 1.5


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published keys (their Hugging Face names), `layer_types`,
    `num_dense_layers` and `max_position_embeddings` as run, `head_dim` and
    `rope_theta` out of the published `rope_parameters`, and the share of the
    published experts this program holds."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64                  # hidden / heads: the key is null
    layer_types: Tuple[str, ...] = PUBLISHED_LAYERS
    num_dense_layers: int = 2
    num_experts: int = 64               # the router's width: as published
    experts_held: Tuple[int, int] = (0, 64)    # published ids [first, stop)
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3               # the FIR's taps: a tail of one fewer
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_position_embeddings: int = 128000
    dtype: Any = jnp.bfloat16

    def serving_block(self) -> "Block":
        return Block(self)

    def __post_init__(self):
        first, stop = self.experts_held
        if not 0 <= first < stop <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of 0..{self.num_experts}")
        if set(self.layer_types) - {CONV, ATTN}:
            raise ValueError(f"layer_types names a kind of layer this block "
                             f"does not have: {set(self.layer_types)}")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("top_k over the router's width")
        if (self.num_key_value_heads % 2
                or self.num_attention_heads % self.num_key_value_heads):
            raise ValueError("the pair form pairs kv heads: an even count "
                             "that divides the query heads")

    # What the serving runner and engine read of any model's configuration,
    # and models/expert_share.py of a routed one.
    @property
    def max_seq(self) -> int:
        return self.max_position_embeddings

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    def layers_of(self, kind: str) -> int:
        return sum(1 for k in self.layer_types if k == kind)

    def layer_kinds(self) -> List[str]:
        """A kind a layer, in the published order: "conv_dense", "conv_moe",
        "attn_moe" (or "attn_dense")."""
        return [("conv" if a == CONV else "attn")
                + ("_dense" if li < self.num_dense_layers else "_moe")
                for li, a in enumerate(self.layer_types)]

    @property
    def state_bytes_per_sequence(self) -> int:
        """A slot of the state group: every conv layer's tail."""
        return (self.layers_of(CONV) * (self.conv_L_cache - 1)
                * self.hidden_size * jnp.dtype(self.dtype).itemsize)

    @staticmethod
    def tiny(**overrides) -> "Lfm2MoeConfig":
        """Six layers of every kind that the published model has and one it
        has not (conv_dense, attn_dense, conv_moe, conv_moe, attn_moe,
        conv_moe: a conv layer on both sides of an attention layer and after
        an expert layer); 8 query / 4 kv heads of 64: two kv PAIRS, runs of
        two query heads, a pool row a whole lane tile; 16 published experts of
        which a test holds all or a share, 4 kept."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_attention_heads=8,
                    num_key_value_heads=4, head_dim=64,
                    layer_types=(CONV, ATTN, CONV, CONV, ATTN, CONV),
                    num_dense_layers=2, num_experts=16, experts_held=(0, 16),
                    num_experts_per_tok=4, rope_theta=1e4,
                    max_position_embeddings=256, dtype=jnp.float32)
        base.update(overrides)
        return Lfm2MoeConfig(**base)

    def reference_sizes(self) -> Dict:
        """The plain reference's `sizes` (a configuration file's keys) of
        this configuration (models/lfm2_moe_reference.py)."""
        return dict(
            hidden_size=self.hidden_size,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            head_dim=self.head_dim,
            rope_parameters={"rope_theta": self.rope_theta},
            layer_types=list(self.layer_types),
            num_dense_layers=self.num_dense_layers,
            conv_L_cache=self.conv_L_cache, norm_eps=self.norm_eps,
            n_routed_experts=self.n_held,
            num_experts_published=self.num_experts,
            first_held_expert=self.experts_held[0],
            num_experts_per_tok=self.num_experts_per_tok,
            routed_scaling_factor=self.routed_scaling_factor)

    def conv_params(self) -> int:
        """`in_proj` (B | C | z), the taps, `out_proj`."""
        d = self.hidden_size
        return 3 * d * d + self.conv_L_cache * d + d * d

    def attention_params(self) -> int:
        """q and o at H heads, k and v at K."""
        return self.hidden_size * self.head_dim * 2 * (
            self.num_attention_heads + self.num_key_value_heads)

    def expert_params(self) -> int:
        return 3 * self.hidden_size * self.moe_intermediate_size

    def _layer_params(self, picks: float) -> float:
        """The mixers' and feed-forwards' parameters over the layers, an
        expert layer counted with `picks` routed experts."""
        d = self.hidden_size
        dense = min(self.num_dense_layers, self.num_hidden_layers)
        moe = d * self.num_experts + picks * self.expert_params()
        return (self.layers_of(CONV) * self.conv_params()
                + self.layers_of(ATTN) * self.attention_params()
                + dense * 3 * d * self.intermediate_size
                + (self.num_hidden_layers - dense) * moe)

    def num_params(self) -> int:
        """Parameters this program holds (the held experts, not the
        published count; the embedding ONCE: the head is tied), norm gains
        and router biases left out."""
        return int(self.vocab_size * self.hidden_size
                   + self._layer_params(self.n_held))

    def flops_per_token(self, seq: int) -> float:
        """Training operations a token, forward and backward (6 a parameter
        a token's products touch, the HELD share of its top_k experts; the
        head's product counted though its matrix is the embedding's), and the
        attention layers' at H x 2 head_dim x 2 a query-context pair x 3.
        The conv mixers' element-wise passes (two gates and three taps a
        channel) are under a thousandth of their projections: left out."""
        picks = self.num_experts_per_tok * self.n_held / self.num_experts
        n = (self._layer_params(picks) - self.layers_of(CONV)
             * self.conv_L_cache * self.hidden_size
             + self.hidden_size * self.vocab_size)
        pair = self.num_attention_heads * 2 * self.head_dim * 2
        return 6.0 * n + 3.0 * self.layers_of(ATTN) * pair * seq


# -------------------------------------------------------------- parameters

def init_params(config: Lfm2MoeConfig, key: jax.Array) -> Dict:
    """Random normal, 1/sqrt(fan_in); the embedding's rows N(0, 1) (it is the
    head too: the stream stays the token's, and the routers see tokens:
    models/mimo_v2_flash.py's init_params says why); the taps 1/sqrt(taps) a
    channel. A conv mixer's output is then 1.0 a lane whatever the context
    (B, C and z are each 1 a lane, their product through 1/sqrt(fan_in)
    weights keeps that: measured), and an attention layer's what its softmax
    leaves of v: `q_layernorm` and `k_layernorm` QK_NORM_GAIN (above: why);
    every other norm 1. An expert layer's `expert_bias` is
    `expert_share.router_bias`'s grid, over all experts where one chip holds
    them all. Every stacked weight is drawn a slice at a time and cast inside
    one program (deepseek_v2.init_params). `params["layers"]` is one dict a
    KIND of layer, its layers stacked in the published order;
    `params["experts"]` one dict an expert layer. No `lm_head`: the head is
    the embedding (`ModelRunner._logits`)."""
    c = config
    d, H, K, hd = (c.hidden_size, c.num_attention_heads,
                   c.num_key_value_heads, c.head_dim)
    keys = iter(jax.random.split(key, 96))

    def stack(lead: Tuple[int, ...], shape: Tuple[int, ...], fan_in: int):
        n = math.prod(lead)

        @jax.jit
        def draw(ks):
            return jax.lax.map(
                lambda k: (jax.random.normal(k, shape, F32)
                           * (1.0 / math.sqrt(fan_in))).astype(c.dtype), ks)

        return draw(jax.random.split(next(keys), n)).reshape(lead + shape)

    def ones(*shape):
        return jnp.ones(shape, dtype=c.dtype)

    kinds = c.layer_kinds()
    layers: Dict[str, Dict] = {}
    for name in sorted(set(kinds)):
        L = kinds.count(name)
        p = {"operator_norm": ones(L, d), "ffn_norm": ones(L, d)}
        if name.startswith("conv"):
            p.update(in_proj=stack((L,), (d, 3 * d), d),
                     conv_w=stack((L,), (c.conv_L_cache, d), c.conv_L_cache),
                     out_proj=stack((L,), (d, d), d))
        else:
            p.update(q_norm=QK_NORM_GAIN * ones(L, hd),
                     k_norm=QK_NORM_GAIN * ones(L, hd),
                     wq=stack((L,), (d, H * hd), d),
                     wk=stack((L,), (d, K * hd), d),
                     wv=stack((L,), (d, K * hd), d),
                     wo=stack((L,), (H * hd, d), H * hd))
        if name.endswith("_moe"):
            p.update(router=stack((L,), (d, c.num_experts), d),
                     router_bias=router_bias(next(keys), L, c.num_experts,
                                             c.n_held))
        else:
            f = c.intermediate_size
            p.update(w_gate=stack((L,), (d, f), d),
                     w_up=stack((L,), (d, f), d),
                     w_down=stack((L,), (f, d), f))
        layers[name] = p
    fm = c.moe_intermediate_size
    blocks = 8 if c.vocab_size % 8 == 0 else 1
    return {
        "embed": stack((blocks,), (c.vocab_size // blocks, d), 1).reshape(
            c.vocab_size, d),
        "layers": layers,
        # The held experts, one dict an expert layer in the published order.
        "experts": [{"w_gate": stack((c.n_held,), (d, fm), d),
                     "w_up": stack((c.n_held,), (d, fm), d),
                     "w_down": stack((c.n_held,), (fm, d), fm)}
                    for name in kinds if name.endswith("_moe")],
        "final_norm": ones(d),
    }


# -------------------------------------------------------- the serving block

class Block:
    """LFM2-MoE as the serving runner consumes a model (the protocol is
    llm/model_runner.py's, "A block"): two layer groups, three arrays."""

    # A tick record's: rows and sequences the conv mixers carried (a
    # sequence is a slot READ).
    state_fields = ("conv_rows", "conv_seqs")

    def __init__(self, config: Lfm2MoeConfig):
        from ray_tpu.llm.model_runner import LayerGroup

        self.config = config
        self.kinds = config.layer_kinds()
        self.routed_layers = sum(k.endswith("_moe") for k in self.kinds)
        self.top_k = config.num_experts_per_tok
        self.held_experts = config.n_held
        self.residual_dtype = F32      # the module docstring, "Precision"
        self.scale = config.head_dim ** -0.5
        # Query heads a kv head: the run of `pa.pair_queries`.
        self.run = config.num_attention_heads // config.num_key_value_heads
        # (at any page size: the query block does not depend on it)
        self.q_block = self.kv_kernels(16)["all"].q_block
        self.groups = (LayerGroup("all"), LayerGroup("state", slots=True))
        # A slot's tail as it lies: its rows as whole lane tiles of the
        # slot's own, so that a step's write of a slot is one contiguous
        # block (kimi_linear.Block says what the other forms cost).
        flat = (config.conv_L_cache - 1) * config.hidden_size
        self.tail_tile = ((flat // LANE, LANE) if flat % LANE == 0
                          else (1, flat))
        # A layer's index inside its group's arrays.
        seen = {CONV: 0, ATTN: 0}
        self.pool_layer = []
        for kind in config.layer_types:
            self.pool_layer.append(seen[kind])
            seen[kind] += 1

    def refuse(self, *, tensor_parallel: int, lora: bool) -> None:
        if tensor_parallel > 1:
            raise ValueError("lfm2_moe: tensor_parallel > 1 is not supported "
                             "(a slot's tail is not sharded, and no exchange "
                             "of the expert shares)")
        if lora:
            raise ValueError("lfm2_moe: LoRA adapters are not supported")

    def pallas_ok(self) -> bool:
        return (2 * self.config.head_dim) % LANE == 0

    # ---- cache -----------------------------------------------------------

    def cache_arrays(self, pages: Dict[str, int], block_size: int):
        """The `all` group's K and V ROW POOLS (the attention layers; a
        token's row its K / 2 kv pairs of 2 hd side by side), and the state
        group's ONE array: the conv layers' tails, `pages["state"]` slots and
        the junk slot behind them."""
        from ray_tpu.llm.model_runner import (row_cache_array,
                                              state_cache_array)

        c = self.config
        row = (c.layers_of(ATTN), pages["all"], block_size,
               c.num_key_value_heads * c.head_dim)
        return (row_cache_array("k_all", row, c.dtype, "all"),
                row_cache_array("v_all", row, c.dtype, "all"),
                state_cache_array("conv_tail", (
                    c.layers_of(CONV), pages["state"] + 1) + self.tail_tile,
                    c.dtype))

    def kv_kernels(self, block_size: int):
        """{page group: the sizes its kernel takes} (`pa.kv_sizes`), in the
        pair form: K / 2 rows of 2 hd lanes."""
        c = self.config
        return {"all": pa.kv_sizes(
            c.num_attention_heads, c.num_key_value_heads // 2,
            2 * c.head_dim, 2 * c.head_dim, block_size,
            jnp.dtype(c.dtype).itemsize, rows=True)}

    def init_cache(self, pages: Dict[str, int], block_size: int):
        from ray_tpu.llm.model_runner import init_cache

        return init_cache(self.cache_arrays(pages, block_size))

    def segments(self, params):
        """Runs of like layers in the published order, each a Python loop
        (`expert_share.kind_segments`)."""
        return kind_segments(runs_of(self.kinds), params)

    def attention_fns(self, impl: str):
        return ((pa.ragged_paged_attention, pa.ragged_paged_attention_unified)
                if impl == "pallas" else
                (pa.ragged_paged_attention_reference,
                 pa.ragged_paged_attention_unified_reference))

    # ---- the mixers and the feed-forward, each stated once ----------------

    def _short_conv(self, ctx, u, tail, lp, pool_li):
        """The gated short convolution over the normed rows u (R, d)
        float32. -> (the mixer's output (R, d) float32, tail)."""
        c = self.config
        rows, d = ctx.rows, c.hidden_size
        bcz = _dot32(u.astype(c.dtype), lp["in_proj"])
        g = (bcz[:, :d] * bcz[:, 2 * d:]).astype(c.dtype)       # B * z
        # `enter`'s rule (ops/state_slots.py): a sequence that starts at
        # position 0 starts from zeros whatever its slot held; a sequence
        # without a row writes the junk slot.
        before = jnp.where(
            (rows.q_positions == 0)[:, None, None], jnp.zeros((), c.dtype),
            tail[pool_li, rows.slots].reshape(-1, c.conv_L_cache - 1, d))
        conv, after = ss.ragged_conv(
            g, before, lp["conv_w"], jnp.zeros((d,), F32), rows.seq,
            rows.local, rows.starts, rows.lens)
        tail = tail.at[pool_li, jnp.where(rows.lens > 0, rows.slots,
                                          tail.shape[1] - 1)].set(
            after.reshape((-1,) + self.tail_tile))
        y = (bcz[:, d:2 * d] * conv).astype(c.dtype)            # C * c
        return _dot32(y, lp["out_proj"]), tail

    def _attention(self, ctx, u, k_pool, v_pool, lp, pool_li):
        """GQA over the normed rows u (..., d) float32 in the pair form by
        runs. -> (the mixer's output, k_pool, v_pool)."""
        c = self.config
        H, K, hd, dt = (c.num_attention_heads, c.num_key_value_heads,
                        c.head_dim, c.dtype)
        lead = u.shape[:-1]
        u = u.astype(dt)
        q = rms_norm(_dot32(u, lp["wq"]).reshape(*lead, H, hd),
                     lp["q_norm"], c.norm_eps)
        k = rms_norm(_dot32(u, lp["wk"]).reshape(*lead, K, hd),
                     lp["k_norm"], c.norm_eps)
        cos, sin = rope_at(c, ctx.rope_pos)
        q, k = partial_rope(q, cos, sin), partial_rope(k, cos, sin)
        # A token's row whole: its K heads side by side, pair by pair.
        k_pool = ctx.write(k_pool, pool_li,
                           k.astype(dt).reshape(*lead, K * hd), "all")
        v_pool = ctx.write(v_pool, pool_li,
                           _dot32(u, lp["wv"]).astype(dt), "all")
        o = pa.pair_outputs(ctx.attend(
            pa.pair_queries(q.astype(dt), self.run), k_pool, v_pool, pool_li,
            group="all", scale=self.scale, kv_heads=K // 2), self.run)
        return (_dot32(o.reshape(*lead, H * hd).astype(dt), lp["wo"]),
                k_pool, v_pool)

    def feed_forward(self, kind: str, w, valid, lp):
        """What a layer's feed-forward makes of the normed rows w (N, d)
        float32. -> (m (N, d) float32, None | (ids (N, top_k) published,
        counts (3,)))."""
        c = self.config
        if kind.endswith("_dense"):
            return _ffn(_dot32, w.astype(c.dtype), lp["w_gate"], lp["w_up"],
                        lp["w_down"]), None
        # The router's chain stays float32 (two bf16 passes over its
        # weights): a score's rounding is a choice's.
        scores = jax.nn.sigmoid(_wide(_dot32, w, lp["router"]))
        ids, gates = route_one_group(c, scores, lp["router_bias"],
                                     scale=c.routed_scaling_factor,
                                     eps=GATE_EPS)
        routed, counts = held_expert_ffn(c, w.astype(c.dtype), ids, gates,
                                         valid, lp)
        return routed, (ids, counts)

    def layer_step(self, ctx, kind: str, x, caches, lp, li, ll):
        """One layer over rows x (..., d); `li` is the layer's index (from 0,
        a Python int). -> (x, caches, aux): aux {"mixed"} (what the layer's
        mixer adds to the stream, which `ModelRunner.last_layer_outputs`
        keeps of the rectangular step), and {"routing", "counts"} of an
        expert layer."""
        c = self.config
        k_pool, v_pool, tail = caches
        lead = x.shape[:-1]
        pool_li = self.pool_layer[li]
        u = rms_norm(x, lp["operator_norm"], c.norm_eps)        # float32
        if kind.startswith("conv"):
            a, tail = self._short_conv(ctx, u.reshape(-1, c.hidden_size),
                                       tail, lp, pool_li)
        else:
            a, k_pool, v_pool = self._attention(ctx, u, k_pool, v_pool, lp,
                                                pool_li)
        a = a.reshape(x.shape)
        x = x + a
        w = rms_norm(x, lp["ffn_norm"], c.norm_eps)             # float32
        m, routed = self.feed_forward(
            kind, w.reshape(-1, c.hidden_size), ctx.valid.reshape(-1), lp)
        aux = {"mixed": a}
        if routed is not None:
            aux.update(routing=routed[0].reshape(*lead, self.top_k),
                       counts=routed[1])
        return x + m.reshape(x.shape), (k_pool, v_pool, tail), aux
