"""Trinity-Large-Preview (`model_type: afmoe`) for the serving engine: gated
grouped-query attention with QK-norm under FOUR RMSNorms a layer, window
layers that rotate three to one beside full layers that rotate nothing, over
a paged K/V cache with one block table a LAYER GROUP, leading dense layers,
and expert layers that hold a SHARE of the published sigmoid-routed experts
beside one shared expert.

Source: https://huggingface.co/arcee-ai/Trinity-Large-Preview (`config.json`;
the equations stand in models/afmoe_reference.py's docstring). What this file
states once and the serving runner (llm/model_runner.py) consumes through
`Block`:

  * The stream starts at sqrt(hidden_size) x the embedding's row
    (`mup_enabled`). A layer is `x += R_post_attn(attention(R_in(x)))`, then
    `x += R_post_mlp(feed_forward(R_pre_mlp(x)))`: a norm before AND after
    each sublayer ("sandwich").
  * Attention, both kinds: H query heads over K kv heads of `head_dim`, q
    and k normed a head (`q_norm`, `k_norm`: one gain vector of `head_dim` a
    layer), the output times `sigmoid(W_g u)` (u the normed input) before
    `W_o`. A `sliding_attention` layer rotates q and k over the whole head
    (rotate-half, `rope_theta`) and token i sees j with 0 <= i - j <
    `sliding_window`; its rows are the group `window`, whose pages the engine
    frees behind the window and whose block table is a ring. A
    `full_attention` layer rotates NOTHING and sees every j <= i: the group
    `all`. K is cached after its norm and rotation.
  * Feed-forward: layers below `num_dense_layers` a SwiGLU; every later layer
    `sigmoid(w W_r)` over ALL published experts in float32, the
    `num_experts_per_tok` best by score + `expert_bias`, gates the kept
    scores over their sum (+ 1e-20) times `route_scale`, beside one shared
    expert that sees every row (a dense product, not a held expert). The
    expert share is models/expert_share.py's: what absent experts would add is
    left out, and `R_post_mlp` of THAT partial sum goes on (in a deployment
    the exchange's combine stands before the norm).

Both groups' pools are ROW POOLS (ops/paged_attention.py, `_kv_rows_kernel`),
`(layers, pages, page, K x head_dim)`: a token's kv heads side by side on the
lanes, nothing padded (128 is a lane tile). Two layer groups, whose pages do
not travel, so the pools need no wire view.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.expert_share import (_dot32, _ffn, _wide, held_expert_ffn,
                                         kind_segments, route_one_group,
                                         router_bias, runs_of)
from ray_tpu.models.mimo_v2_flash import partial_rope
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.layers import rms_norm

LANE = 128
WINDOW, FULL = "sliding_attention", "full_attention"
PERIOD = (WINDOW, WINDOW, WINDOW, FULL)
# What `route_norm` adds to the kept scores' sum before it divides.
ROUTE_NORM_EPS = 1e-20
# The drawn gains of `q_norm` / `k_norm` (every lane) and of `R_post_attn`.
# With both at 1 the scores q . k / sqrt(hd) have unit variance, a softmax
# over hundreds of them is nearly the context's mean of v, which every token
# of a context shares, and `R_post_attn` scales that small vector up to 1 a
# lane: the routers of a context then lean the same way (the held 32 experts
# took 0.65-1.27 x uniform a layer, at any scale of the embedding) and the
# full layer's missing rotation moved the logits by 6.6% where the check's
# tolerance is 3%. Sharper scores make attention pick tokens, but every
# attention layer then multiplies the bf16 stream's rounding by about the
# scores' variance: at gain 2 (variance 16) the program read 4.3-5.1% against
# the float32 reference, at 1.5 2.0%. At QK gain 1.5 with the attention's
# post-norm at 0.5 (the published post-norm gains are INITIALISED by depth,
# "depth-scaled sandwich norm": below 1) the held experts take 0.92-1.01 x
# uniform a layer over 4,096 tokens, the rotation reads 30%, and the program
# 1.1-1.3% (PERF.md section 6, PR 59: the draw's two tests, on the chip).
QK_NORM_GAIN = 1.5
POST_ATTN_NORM_GAIN = 0.5


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """The published keys (their Hugging Face names), `vocab_size`,
    `layer_types`, `num_dense_layers` and `max_position_embeddings` as run,
    and the share of the published experts this program holds."""
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = PERIOD * 15
    num_dense_layers: int = 6
    num_experts: int = 256               # the router's width: as published
    experts_held: Tuple[int, int] = (0, 256)   # published ids [first, stop)
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_scale: float = 2.448
    sliding_window: int = 4096
    rope_theta: float = 1e4
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    dtype: Any = jnp.bfloat16

    def serving_block(self) -> "Block":
        return Block(self)

    def __post_init__(self):
        first, stop = self.experts_held
        if not 0 <= first < stop <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of 0..{self.num_experts}")
        if set(self.layer_types) - {WINDOW, FULL}:
            raise ValueError(f"layer_types names a kind of layer this block "
                             f"does not have: {set(self.layer_types)}")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("top_k over the router's width")
        if self.num_shared_experts != 1:
            raise ValueError("one shared expert, as published")

    # What the serving runner and engine read of any model's configuration.
    @property
    def max_seq(self) -> int:
        return self.max_position_embeddings

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def num_hidden_layers(self) -> int:
        return len(self.layer_types)

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    def layers_of(self, kind: str) -> int:
        return sum(1 for k in self.layer_types if k == kind)

    def layer_kinds(self):
        """A kind a layer, in the published order: "window_dense",
        "window_moe", "full_moe" (or "full_dense")."""
        return [("window" if a == WINDOW else "full")
                + ("_dense" if li < self.num_dense_layers else "_moe")
                for li, a in enumerate(self.layer_types)]

    @staticmethod
    def tiny(**overrides) -> "AfmoeConfig":
        """Window 8 with pages of 4 passes the window many times in a short
        test; every kind of layer; 16 published experts of which a test holds
        all or a share; 6 query heads a kv head, as published."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_attention_heads=12,
                    num_key_value_heads=2, head_dim=16, sliding_window=8,
                    layer_types=(WINDOW, FULL, WINDOW, WINDOW, FULL),
                    num_dense_layers=2, num_experts=16, experts_held=(0, 16),
                    num_experts_per_tok=4, max_position_embeddings=256,
                    dtype=jnp.float32)
        base.update(overrides)
        return AfmoeConfig(**base)

    def reference_sizes(self) -> Dict:
        """The plain reference's `sizes` (a configuration file's keys) of
        this configuration (models/afmoe_reference.py)."""
        return dict(
            hidden_size=self.hidden_size,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            head_dim=self.head_dim, rope_theta=self.rope_theta,
            sliding_window=self.sliding_window,
            layer_types=list(self.layer_types),
            num_dense_layers=self.num_dense_layers,
            n_routed_experts=self.n_held,
            num_experts_published=self.num_experts,
            first_held_expert=self.experts_held[0],
            num_experts_per_tok=self.num_experts_per_tok,
            route_scale=self.route_scale, rms_norm_eps=self.rms_norm_eps,
            mup_enabled=True)

    def attention_params(self) -> int:
        """q, the gate and o at H heads, k and v at K."""
        d, hd = self.hidden_size, self.head_dim
        return d * hd * (3 * self.num_attention_heads
                         + 2 * self.num_key_value_heads)

    def expert_params(self) -> int:
        return 3 * self.hidden_size * self.moe_intermediate_size

    def _layer_params(self, picks: float) -> float:
        """Attention and feed-forward parameters over the layers, an expert
        layer counted with `picks` routed experts beside its shared one."""
        d = self.hidden_size
        moe = (d * self.num_experts
               + (picks + self.num_shared_experts) * self.expert_params())
        dense = min(self.num_dense_layers, self.num_hidden_layers)
        return (self.num_hidden_layers * self.attention_params()
                + dense * 3 * d * self.intermediate_size
                + (self.num_hidden_layers - dense) * moe)

    def num_params(self) -> int:
        """Parameters this program holds (the held experts, not the
        published count), norm gains and router biases left out."""
        return int(2 * self.vocab_size * self.hidden_size
                   + self._layer_params(self.n_held))

    def flops_per_token(self, seq: int) -> float:
        """Training operations a token, forward and backward (6 a parameter
        a token touches), counting the HELD share: of its top_k experts a
        token meets top_k * held / published here on average. Attention at
        H * 2 head_dim * 2 a query-context pair: a full layer's token sees
        `seq` of them, a window layer's at most the window."""
        picks = self.num_experts_per_tok * self.n_held / self.num_experts
        n = self._layer_params(picks) + self.hidden_size * self.vocab_size
        pair = self.num_attention_heads * 2 * self.head_dim
        seen = (self.layers_of(FULL) * seq
                + self.layers_of(WINDOW) * min(seq, self.sliding_window))
        return 6.0 * n + 6.0 * pair * seen


def rope_at(config: AfmoeConfig, positions):
    """cos, sin (..., head_dim / 2) float32 at `positions` (...,): computed
    in the step program, not looked up (deepseek_v2.rope_at says why)."""
    hd = config.head_dim
    inv_freq = config.rope_theta ** (
        -jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(angle), jnp.sin(angle)


# -------------------------------------------------------------- parameters

def init_params(config: AfmoeConfig, key: jax.Array) -> Dict:
    """Random normal, 1/sqrt(fan_in), the embedding's rows too: N(0, 1 /
    hidden_size), so that the stream starts at 1 a lane under the embedding's
    factor. Every sublayer adds a POST-NORMED vector of 1 a lane: under
    `torch.nn.Embedding`'s N(0, 1) rows the stream would start at 55 a lane,
    ten sublayers would be 6% of it and a check of the logits blind to all of
    them; at 1 a lane a token's own row still leads what the routers see and
    a sublayer weighs what the embedding does (PERF.md section 6, PR 59: the
    draw's two tests). Norm gains ones, but `q_norm` and `k_norm`
    QK_NORM_GAIN and `post_attn_norm` POST_ATTN_NORM_GAIN (above: why); an
    expert layer's `expert_bias`
    `expert_share.router_bias`'s grid, dealt to every share alike. Every
    stacked weight is drawn a slice at a time and cast inside one program
    (deepseek_v2.init_params). `params["layers"]` is one dict a KIND of
    layer, its layers stacked in the published order; `params["experts"]`
    one dict an expert layer (deepseek_v2.Block.segments says why)."""
    c = config
    d, H, K, hd = (c.hidden_size, c.num_attention_heads,
                   c.num_key_value_heads, c.head_dim)
    keys = iter(jax.random.split(key, 96))

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

    kinds = c.layer_kinds()
    layers: Dict[str, Dict] = {}
    for name in sorted(set(kinds)):
        L = kinds.count(name)
        p = {"attn_norm": ones(L, d),
             "post_attn_norm": POST_ATTN_NORM_GAIN * ones(L, d),
             "mlp_norm": ones(L, d), "post_mlp_norm": ones(L, d),
             "q_norm": QK_NORM_GAIN * ones(L, hd),
             "k_norm": QK_NORM_GAIN * ones(L, hd),
             "wq": stack((L,), (d, H * hd), d),
             "wk": stack((L,), (d, K * hd), d),
             "wv": stack((L,), (d, K * hd), d),
             "wg": stack((L,), (d, H * hd), d),
             "wo": stack((L,), (H * hd, d), H * hd)}
        if name.endswith("_moe"):
            fs = c.moe_intermediate_size * c.num_shared_experts
            p.update(router=stack((L,), (d, c.num_experts), d),
                     router_bias=router_bias(next(keys), L, c.num_experts,
                                             c.n_held),
                     shared_gate=stack((L,), (d, fs), d),
                     shared_up=stack((L,), (d, fs), d),
                     shared_down=stack((L,), (fs, d), fs))
        else:
            f = c.intermediate_size
            p.update(w_gate=stack((L,), (d, f), d),
                     w_up=stack((L,), (d, f), d),
                     w_down=stack((L,), (f, d), f))
        layers[name] = p
    fm = c.moe_intermediate_size
    return {
        "embed": stack((), (c.vocab_size, d), d),
        "layers": layers,
        # The held experts, one dict an expert layer in the published order.
        "experts": [{"w_gate": stack((c.n_held,), (d, fm), d),
                     "w_up": stack((c.n_held,), (d, fm), d),
                     "w_down": stack((c.n_held,), (fm, d), fm)}
                    for name in kinds if name.endswith("_moe")],
        "final_norm": ones(d),
        "lm_head": stack((), (d, c.vocab_size), d),
    }


# -------------------------------------------------------- the serving block

class Block:
    """Trinity-Large-Preview as the serving runner consumes a model (the
    protocol is llm/model_runner.py's, "A block"): two layer groups, four
    pools."""

    def __init__(self, config: AfmoeConfig):
        from ray_tpu.llm.model_runner import LayerGroup

        self.config = config
        self.kinds = config.layer_kinds()
        self.routed_layers = sum(k.endswith("_moe") for k in self.kinds)
        self.top_k = config.num_experts_per_tok
        self.held_experts = config.n_held
        # float32 for the reason deepseek_v2.py's "precision" gives.
        self.residual_dtype = jnp.float32
        self.scale = config.head_dim ** -0.5
        # (at any page size: the query block does not depend on it)
        self.q_block = self.kv_kernels(16)["all"].q_block
        self.groups = (LayerGroup("all"),
                       LayerGroup("window", config.sliding_window))
        # A layer's index inside its group's pools.
        seen = {FULL: 0, WINDOW: 0}
        self.pool_layer = []
        for kind in config.layer_types:
            self.pool_layer.append(seen[kind])
            seen[kind] += 1

    def refuse(self, *, tensor_parallel: int, lora: bool) -> None:
        if tensor_parallel > 1:
            raise ValueError("afmoe: tensor_parallel > 1 is not supported "
                             "(no exchange of the expert shares)")
        if lora:
            raise ValueError("afmoe: LoRA adapters are not supported")

    def pallas_ok(self) -> bool:
        return self.config.head_dim % LANE == 0

    # ---- cache -----------------------------------------------------------

    def cache_arrays(self, pages: Dict[str, int], block_size: int):
        """K and V of each group as ROW POOLS: (layers of the group, the
        group's pages, page, kv heads x head_dim)."""
        from ray_tpu.llm.model_runner import row_cache_array

        c = self.config
        row = c.num_key_value_heads * c.head_dim
        return tuple(
            row_cache_array(f"{kv}_{group}", (c.layers_of(kind), pages[group],
                                              block_size, row), c.dtype, group)
            for group, kind in (("all", FULL), ("window", WINDOW))
            for kv in "kv")

    def kv_kernels(self, block_size: int):
        """{page group: the sizes its kernel takes} (`pa.kv_sizes`)."""
        c = self.config
        return {group: pa.kv_sizes(
            c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.head_dim, block_size, jnp.dtype(c.dtype).itemsize, rows=True,
            window=window)
            for group, window in (("all", None),
                                  ("window", c.sliding_window))}

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

    # ---- the layer step, stated once --------------------------------------

    def feed_forward(self, kind: str, w, valid, lp):
        """What a layer's feed-forward makes of the normed rows w (N, d)
        float32, BEFORE `R_post_mlp`. -> (m (N, d) float32, None | (ids (N,
        top_k) published, counts (3,)))."""
        c = self.config
        if kind.endswith("_dense"):
            return _ffn(_dot32, w.astype(c.dtype), lp["w_gate"], lp["w_up"],
                        lp["w_down"]), None
        # The router's chain stays float32 (two bf16 passes over its
        # weights): a score's rounding is a choice's.
        scores = jax.nn.sigmoid(_wide(_dot32, w, lp["router"]))
        ids, gates = route_one_group(c, scores, lp["router_bias"],
                                     scale=c.route_scale, eps=ROUTE_NORM_EPS)
        w = w.astype(c.dtype)
        routed, counts = held_expert_ffn(c, w, ids, gates, valid, lp)
        # The shared expert sees every row: a dense product.
        shared = _ffn(_dot32, w, lp["shared_gate"], lp["shared_up"],
                      lp["shared_down"])
        return routed + shared, (ids, counts)

    def layer_step(self, ctx, kind: str, x, caches, lp, li, ll):
        """One layer over rows x (..., d); `li` is the layer's published
        index, a Python int. -> (x, caches, aux): aux {"attended"} (the
        attention's output before its gate, which `ModelRunner.
        last_layer_outputs` keeps of the rectangular step: a mask shows there
        and hardly in the logits, chip_smoke.py `afmoe_check`), and
        {"routing", "counts"} of an expert layer."""
        c = self.config
        window = kind.startswith("window")
        group = "window" if window else "all"
        at = 2 if window else 0
        pool_li = self.pool_layer[li]
        lead = x.shape[:-1]
        H, K, hd, dt = (c.num_attention_heads, c.num_key_value_heads,
                        c.head_dim, c.dtype)
        eps = c.rms_norm_eps
        if li == 0:         # `mup_enabled`, as published
            x = x * math.sqrt(c.hidden_size)

        u = rms_norm(x, lp["attn_norm"], eps).astype(dt)
        q = rms_norm(_dot32(u, lp["wq"]).reshape(*lead, H, hd),
                     lp["q_norm"], eps)
        k = rms_norm(_dot32(u, lp["wk"]).reshape(*lead, K, hd),
                     lp["k_norm"], eps)
        if window:      # a full layer rotates nothing
            cos, sin = rope_at(c, ctx.rope_pos)
            q, k = partial_rope(q, cos, sin), partial_rope(k, cos, sin)
        caches = list(caches)
        # A token's row whole: its K heads side by side, 128 lanes each.
        caches[at] = ctx.write(caches[at], pool_li,
                               k.astype(dt).reshape(*lead, K * hd), group)
        caches[at + 1] = ctx.write(caches[at + 1], pool_li,
                                   _dot32(u, lp["wv"]).astype(dt), group)
        o = ctx.attend(
            q.astype(dt), caches[at], caches[at + 1], pool_li, group=group,
            scale=self.scale, kv_heads=K,
            **({"window": c.sliding_window} if window else {})
        ).reshape(*lead, H * hd)
        gated = o.astype(jnp.float32) * jax.nn.sigmoid(_dot32(u, lp["wg"]))
        x = x + rms_norm(_dot32(gated.astype(dt), lp["wo"]),
                         lp["post_attn_norm"], eps)

        w = rms_norm(x, lp["mlp_norm"], eps)                    # float32
        m, routed = self.feed_forward(
            kind, w.reshape(-1, c.hidden_size), ctx.valid.reshape(-1), lp)
        x = x + rms_norm(m.reshape(x.shape), lp["post_mlp_norm"], eps)
        aux = {"attended": o}
        if routed is not None:
            aux.update(routing=routed[0].reshape(*lead, self.top_k),
                       counts=routed[1])
        return x, tuple(caches), aux
