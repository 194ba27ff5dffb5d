"""GLM-5.2 (`model_type: glm_moe_dsa`) for the serving engine: latent
attention (MLA) that attends only to the `index_topk` context rows a learned
indexer selects (DeepSeek Sparse Attention), the selection shared by the
layers that follow an indexer's (IndexShare), an index-key pool beside the
latent pool, leading dense layers and sigmoid-routed expert layers of which
this program holds a share.

Source: https://huggingface.co/zai-org/GLM-5.2 (`config.json`); the attention
is DeepSeek-V3.2-Exp's ("Boosting Long-Context Efficiency with DeepSeek
Sparse Attention") and its released inference code's `Indexer`, which the
`index_*` keys size. Pre-norm residual decoder, eps 1e-5, final norm, untied
head. For the normed input x_t of token t at position t:

  * Latent projections, every layer (models/deepseek_v2.py's, other widths):
    `cq = rms(W_qa x)`; `q = W_qb cq`, H heads of `[q_nope | q_rope]`; `[c |
    kr] = W_kva x`, `c = rms(c)`; the rope lanes of q and kr rotated at t,
    INTERLEAVED pairs (`rotate_interleaved`: lanes (2i, 2i + 1) turn by t
    theta^(-2i / rope); not deepseek_v2's `rotate_half`), no scaling; scale
    `qk_head_dim^-0.5`. Cached a token a layer: the row `[c | kr]`.
  * Indexer, a layer whose `indexer_types` entry is "full": `qI = W_qI cq`,
    HI heads of dI, from the same normed query latent; `kI = LayerNorm(W_kI
    x)` (weight and bias, eps 1e-6), ONE key a token for all heads; the first
    `qk_rope_head_dim` lanes of every qI head and of kI rotated as above; `w =
    W_w x`; `I[t, s] = HI^-0.5 dI^-0.5 sum_j w[t, j] relu(qI[t, j] . kI[s])`
    for s <= t. The SELECTION of t: the positions of the min(t + 1,
    index_topk) largest I[t, s], ties to the lower position. Cached a token a
    "full" layer: kI. (The published code passes qI and kI through a Hadamard
    transform, which leaves every product as it is, and quantises both to
    FP8, which is not this configuration's dtype: both left out.)
  * A "shared" layer has no indexer and writes no index key: its selection is
    the nearest "full" layer's before it.
  * Attention over the selection only, in the absorbed form (`deepseek_v2.
    latent_attention`). Where no context of a step holds more than
    `index_topk` rows every row is selected and the step takes the dense
    latent kernel; else `ops/sparse_latent.py`: scores, selection, attention
    over the gathered rows. A "shared" layer never scores.
  * Feed-forward: a "dense" layer SwiGLU; a "sparse" layer `sigmoid(W_r h)`
    over all published experts, the top_k best by score + bias in one group,
    gates the kept scores over their sum times `routed_scaling_factor`, plus
    the shared expert (`expert_share.route_one_group`, `held_expert_ffn`:
    this program holds `experts_held` and leaves out what absent experts
    would add).

The block's `x` is the pair (rows, selection): a "full" layer replaces the
selection, a "shared" layer reads it. A SELECTION GROUP is a "full" layer and
the "shared" layers that follow it: they attend to the same positions, so
their latent rows of one token lie SIDE BY SIDE, and the selection carries
its rows of the whole group, gathered ONCE (ops/sparse_latent.py,
`gather_rows`: four layers' rows at once cost 41 ns where four gathers
cost 67). Two
cache arrays in ONE group "all", so that a sequence's pages hold both and
whatever moves pages (the prefix cache, spills, adoption) moves both:

  latent  (groups, pages, page, S x W)  a row pool by selection group: S the
          largest group's size, W = `row_width` (640); layer `s` of group `g`
          (`Block.place`) owns lanes [s W, (s + 1) W) of the group's row, a
          whole number of lane tiles, so a lane block is a legal window for
          XLA's scatter, a Mosaic DMA and a BlockSpec. (2, pages, 16, 2560)
          for `glm-5.2-l8-e8`: the bytes of (8, pages, 16, 640). A group
          smaller than S leaves lanes unused: the published 78 layers are two
          groups of one (layers 0 and 1; layer 2 leads the first group of
          four) and nineteen of four, 84 slots for 78 layers.
  index   (groups, pages, page, dI)     the "full" layers' index keys.
 Precision as deepseek_v2.py says
("precision"): the residual stream, the query's chain and the indexer's
scores float32; the latent row, the index key and the weights the model's
dtype.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import normal
from ray_tpu.models.deepseek_v2 import latent_attention
from ray_tpu.models.expert_share import (_dot32, _ffn, _wide,
                                         held_expert_ffn, kind_segments,
                                         route_one_group, router_bias,
                                         runs_of)
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import sparse_latent as sl
from ray_tpu.ops.layers import rms_norm

LANE = 128
F32 = jnp.float32
INDEX_NORM_EPS = 1e-6


def _published_types(n: int, dense: int = 3, freq: int = 4):
    """(indexer_types, mlp_layer_types) of the first n published layers."""
    return (tuple("full" if li < dense or (li - dense) % freq == freq - 1
                  else "shared" for li in range(n)),
            tuple("dense" if li < dense else "sparse" for li in range(n)))


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig:
    """The published keys (their Hugging Face names; `rope_theta` out of
    `rope_parameters`), `vocab_size`, `num_hidden_layers`, the two lists and
    `max_position_embeddings` as run, and the share of the published experts
    this program holds."""
    vocab_size: int = 154880
    hidden_size: int = 6144
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 78
    num_attention_heads: int = 64
    q_lora_rank: int = 2048
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    indexer_types: Tuple[str, ...] = _published_types(78)[0]
    mlp_layer_types: Tuple[str, ...] = _published_types(78)[1]
    n_routed_experts: int = 256          # the router's width: as published
    experts_held: Tuple[int, int] = (0, 256)   # published ids [first, stop)
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-5
    rope_theta: float = 8e6
    max_position_embeddings: int = 1048576
    dtype: Any = jnp.bfloat16

    def serving_block(self) -> "Block":
        return Block(self)

    def __post_init__(self):
        first, stop = self.experts_held
        L = self.num_hidden_layers
        if not 0 <= first < stop <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of 0..{self.n_routed_experts}")
        if len(self.indexer_types) != L or len(self.mlp_layer_types) != L:
            raise ValueError("indexer_types and mlp_layer_types name every "
                             "layer")
        if (set(self.indexer_types) - {"full", "shared"}
                or set(self.mlp_layer_types) - {"dense", "sparse"}):
            raise ValueError("indexer_types: full | shared; mlp_layer_types: "
                             "dense | sparse")
        if self.indexer_types[0] != "full":
            raise ValueError("the first layer has nobody's selection to "
                             "share: it must be a full one")
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError("an index head holds the rotated lanes")

    # What the serving runner and engine read of any model's configuration.
    @property
    def max_seq(self) -> int:
        return self.max_position_embeddings

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def num_experts_per_token(self) -> int:     # expert_share's name for it
        return self.num_experts_per_tok

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def n_moe_layers(self) -> int:
        return self.mlp_layer_types.count("sparse")

    @property
    def n_full_layers(self) -> int:
        return self.indexer_types.count("full")

    @property
    def row_width(self) -> int:
        """A latent cache row as it lies: `[c | kr]` padded with zeros to
        whole lane tiles (576 -> 640 at the published widths)."""
        used = self.kv_lora_rank + self.qk_rope_head_dim
        return -(-used // LANE) * LANE

    def layer_kinds(self) -> Tuple[str, ...]:
        """A layer's kind: its indexer crossed with its feed-forward."""
        return tuple(f"{ix}_{'moe' if ff == 'sparse' else 'dense'}"
                     for ix, ff in zip(self.indexer_types,
                                       self.mlp_layer_types))

    def reference_sizes(self) -> Dict:
        """The keys the plain reference (glm_dsa_reference.py) reads of a
        configuration file's `sizes`."""
        out = {k: getattr(self, k) for k in (
            "hidden_size", "num_hidden_layers", "num_attention_heads",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
            "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps",
            "rope_theta")}
        out.update(indexer_types=list(self.indexer_types),
                   mlp_layer_types=list(self.mlp_layer_types),
                   n_routed_experts=self.n_held,
                   n_routed_experts_published=self.n_routed_experts,
                   first_held_expert=self.experts_held[0])
        return out

    @staticmethod
    def tiny(**overrides) -> "GlmDsaConfig":
        """Four layers, every kind: full_dense, shared_dense, full_moe,
        shared_moe; a selection of 8 rows."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    moe_intermediate_size=32, num_hidden_layers=4,
                    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=16,
                    index_n_heads=4, index_head_dim=16, index_topk=8,
                    indexer_types=("full", "shared", "full", "shared"),
                    mlp_layer_types=("dense", "dense", "sparse", "sparse"),
                    n_routed_experts=16, experts_held=(0, 16),
                    num_experts_per_tok=4, rope_theta=10000.0,
                    max_position_embeddings=256, dtype=F32)
        base.update(overrides)
        return GlmDsaConfig(**base)

    def attention_params(self) -> int:
        d, H = self.hidden_size, self.num_attention_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        return (d * self.q_lora_rank + self.q_lora_rank * H * qk
                + d * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank * H * (self.qk_nope_head_dim
                                           + self.v_head_dim)
                + H * self.v_head_dim * d)

    def indexer_params(self) -> int:
        HI, dI = self.index_n_heads, self.index_head_dim
        return (self.q_lora_rank * HI * dI + self.hidden_size * dI
                + self.hidden_size * HI)

    def expert_params(self) -> int:
        return 3 * self.hidden_size * self.moe_intermediate_size

    def _layer_params(self, picks: float) -> float:
        d = self.hidden_size
        return sum(
            self.attention_params()
            + (self.indexer_params() if ix == "full" else 0)
            + (3 * d * self.intermediate_size if ff == "dense"
               else d * self.n_routed_experts
               + (picks + self.n_shared_experts) * self.expert_params())
            for ix, ff in zip(self.indexer_types, self.mlp_layer_types))

    def num_params(self) -> int:
        """Parameters this program holds (the held experts, not the
        published count); norms and biases left out."""
        return int(2 * self.vocab_size * self.hidden_size
                   + self._layer_params(self.n_held))

    def flops_per_token(self, seq: int) -> float:
        """Training operations a token, forward and backward (6 a parameter a
        token's products touch, the HELD share of its experts), attention at
        H (qk + v) 2 a query-context pair over the min(seq, index_topk) rows
        a token attends to, and the indexer's HI dI 2 a pair over all."""
        picks = (self.num_experts_per_tok * self.n_held
                 / self.n_routed_experts)
        n = self._layer_params(picks) + self.hidden_size * self.vocab_size
        pair = self.num_attention_heads * (
            self.qk_nope_head_dim + self.qk_rope_head_dim + self.v_head_dim)
        index = self.index_n_heads * self.index_head_dim
        return (6.0 * n
                + 6.0 * self.num_hidden_layers * pair
                * min(seq, self.index_topk)
                + 6.0 * self.n_full_layers * index * seq)


# ------------------------------------------------------------------ rotary

def rope_at(config: GlmDsaConfig, positions):
    """cos, sin (..., rope / 2) float32 at `positions`: theta^(-2i / rope),
    no scaling. Computed in the step program (deepseek_v2.rope_at says why
    no table)."""
    dim = config.qk_rope_head_dim
    inv_freq = config.rope_theta ** (
        -2.0 * jnp.arange(dim // 2, dtype=F32) / dim)
    angle = positions[..., None].astype(F32) * inv_freq
    return jnp.cos(angle), jnp.sin(angle)


def rotate_interleaved(x, cos, sin):
    """x (..., heads, rope) rotated by cos, sin (..., rope / 2): pairs (2i,
    2i + 1), as `rope_interleave` lays them. float32 out."""
    pairs = x.astype(F32).reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                     axis=-1).reshape(x.shape)


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


# -------------------------------------------------------------- parameters

def init_params(config: GlmDsaConfig, key: jax.Array) -> Dict:
    """Random normal, 1 / sqrt(fan_in); the embedding's rows N(0, 1) and the
    router's bias dealt as models/mimo_v2_flash.py's init_params and
    `expert_share.router_bias` say and why; norms 1, the index key's
    LayerNorm bias 0.1 N(0, 1) (so that a program without it differs). Every
    stacked weight is drawn a slice at a time and cast inside one program (no
    float32 copy of a stack: deepseek_v2.init_params). `params["layers"]` is
    one dict a KIND of layer, its layers stacked in the published order;
    `params["experts"]` one dict an expert layer."""
    c = config
    d, H = c.hidden_size, c.num_attention_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    lat, rope = c.kv_lora_rank, c.qk_rope_head_dim
    HI, dI = c.index_n_heads, c.index_head_dim
    keys = iter(jax.random.split(key, 128))

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

    def indexer(L):
        return {
            "wq_i": stack((L,), (c.q_lora_rank, HI * dI), c.q_lora_rank),
            "wk_i": stack((L,), (d, dI), d),
            "k_norm_w": jnp.ones((L, dI), F32),
            "k_norm_b": normal(next(keys), (L, dI), 0.1),
            "w_w": stack((L,), (d, HI), d),
        }

    f, fm = c.intermediate_size, c.moe_intermediate_size
    fs = c.n_shared_experts * fm
    kinds = c.layer_kinds()
    layers = {}
    for name in sorted(set(kinds)):
        L = kinds.count(name)
        p = attention(L)
        if name.startswith("full"):
            p.update(indexer(L))
        if name.endswith("_moe"):
            p.update(router=stack((L,), (d, c.n_routed_experts), d),
                     router_bias=router_bias(next(keys), L,
                                             c.n_routed_experts, c.n_held),
                     shared_gate=stack((L,), (d, fs), d),
                     shared_up=stack((L,), (d, fs), d),
                     shared_down=stack((L,), (fs, d), fs))
        else:
            p.update(w_gate=stack((L,), (d, f), d),
                     w_up=stack((L,), (d, f), d),
                     w_down=stack((L,), (f, d), f))
        layers[name] = p
    return {
        "embed": stack((), (c.vocab_size, d), 1),
        "layers": layers,
        # The held experts, one dict an expert layer in the published order.
        "experts": [{"w_gate": stack((c.n_held,), (d, fm), d),
                     "w_up": stack((c.n_held,), (d, fm), d),
                     "w_down": stack((c.n_held,), (fm, d), fm)}
                    for _ in range(c.n_moe_layers)],
        "final_norm": ones(d),
        "lm_head": stack((), (d, c.vocab_size), d),
    }


# -------------------------------------------------------- the serving block

class Block:
    """GLM-5.2 as the serving runner consumes a model (the protocol is
    llm/model_runner.py's, "A block"): one layer group, two arrays."""

    # A tick record's counts of what the selection spares, by `tick_counts`.
    tick_fields = ("dsa_pairs", "dsa_index_rows", "dsa_attend_rows",
                   "dsa_selected_rows", "dsa_gathered_rows",
                   "dsa_index_walked_rows")

    def __init__(self, config: GlmDsaConfig):
        self.config = config
        self.q_block = pa.latent_q_block(config.num_attention_heads,
                                         config.row_width)
        self.routed_layers = config.n_moe_layers
        self.top_k = config.num_experts_per_tok
        self.held_experts = config.n_held
        self.residual_dtype = F32       # deepseek_v2.py, "precision"
        self.scale = (config.qk_nope_head_dim
                      + config.qk_rope_head_dim) ** -0.5
        self.impl = "reference"         # attention_fns sets it
        # A layer's selection group (its "full" layer's index in both pools)
        # and its place among the group's layers.
        self.place = []
        for ix in config.indexer_types:
            g, s = self.place[-1] if self.place else (-1, 0)
            self.place.append((g + 1, 0) if ix == "full" else (g, s + 1))
        self.group_size = 1 + max(s for _, s in self.place)

    def refuse(self, *, tensor_parallel: int, lora: bool) -> None:
        if tensor_parallel > 1:
            raise ValueError(
                "glm_dsa: tensor_parallel > 1 is not supported (neither the "
                "latent row nor the index key has a head axis to shard)")
        if lora:
            raise ValueError("glm_dsa: LoRA adapters are not supported")

    def pallas_ok(self) -> bool:
        c = self.config
        return c.row_width % LANE == 0 and c.index_head_dim % LANE == 0

    def tick_counts(self, rows, tables=None, page: int = 1) -> Dict[str, int]:
        """Of a tick's rows [(tokens, first position, context after them)]
        and the step's block table (a row's pages of `page` tokens, padded
        with zeros; None: no two rows share a page), by this block's own
        arithmetic: `dsa_pairs`, the query-context pairs
        a latent layer must cover (min(position + 1, index_topk) a token:
        `attn_pairs` is the dense count); `dsa_index_rows`, the index keys a
        "full" layer must read at least once a row; `dsa_attend_rows`, the
        latent rows a layer must read at least once a row, whatever its
        kernel does; `dsa_selected_rows`, the rows that took the selection
        (a context over index_topk); `dsa_gathered_rows`, the pool rows the
        step's gathers fetch: min(position + 1, index_topk) a token ONCE A
        SELECTION GROUP where the step selects (a gather a layer would fetch
        `dsa_pairs` x layers), 0 where it takes the dense kernel;
        `dsa_index_walked_rows`, the index keys a "full" layer's walks FETCH
        where the step selects: a page run several rows share is walked once
        for all of them (`sl.index_walked_rows`, the index kernel's own
        plan), so under `dsa_index_rows` where rows share a document and
        over it where a slice's blocks each walk their context again."""
        k = self.config.index_topk
        out = dict.fromkeys(self.tick_fields, 0)
        for n, first, kv_len in rows:
            dense = max(0, min(n, k - first))    # tokens that see all
            out["dsa_pairs"] += (dense * first + dense * (dense + 1) // 2
                                 + (n - dense) * k)
            out["dsa_index_rows"] += kv_len
            out["dsa_attend_rows"] += min(kv_len, k)
            out["dsa_selected_rows"] += kv_len > k
        if out["dsa_selected_rows"]:
            out["dsa_gathered_rows"] = (self.config.n_full_layers
                                        * out["dsa_pairs"])
            out["dsa_index_walked_rows"] = sl.index_walked_rows(
                rows, tables, page)
        return out

    # ---- cache -----------------------------------------------------------

    def cache_arrays(self, pages: Dict[str, int], block_size: int):
        """Both of the "all" group, so a sequence's page holds a token's
        latent row of every layer AND its index key of every "full" layer:
        the second array follows the first through every path that moves
        pages (each is one entry of the spec's tuple, as K and V are). The
        latent pool is a row pool by selection group (the module's docstring);
        `latent_cache_array`'s wire view indexes pages only, so it holds for
        any leading and minor dimension."""
        from ray_tpu.llm.model_runner import latent_cache_array

        c = self.config
        return (latent_cache_array(
                    "latent", (c.n_full_layers, pages["all"], block_size,
                               self.group_size * c.row_width), c.dtype),
                latent_cache_array(
                    "index", (c.n_full_layers, pages["all"], block_size,
                              c.index_head_dim), c.dtype))

    def init_cache(self, pages: Dict[str, int], block_size: int):
        from ray_tpu.llm.model_runner import init_cache

        return init_cache(self.cache_arrays(pages, block_size))

    def segments(self, params):
        """Runs of like layers in the published order, each a Python loop
        (`expert_share.kind_segments`)."""
        return kind_segments(runs_of(self.config.layer_kinds()), params)

    def finish(self, x, params):
        rows = x[0] if isinstance(x, tuple) else x
        return rms_norm(rows, params["final_norm"],
                        self.config.rms_norm_eps).astype(self.config.dtype)

    # ---- attention -------------------------------------------------------

    def attention_fns(self, impl: str):
        """(rectangular, ragged), each two functions in one by `mode`:
        "select" (q = (index queries, head weights) over (the index-key pool,
        the latent pool) of a selection group -> the selection and its
        gathered rows) and "attend" (the absorbed query over the latent pool
        under `sel`, `own` the step's rows of this layer as it wrote them).
        The rectangle is the ragged form with every sequence's Bq tokens in a
        row."""
        self.impl = impl

        def ragged(q, pool, li, tables, kv_lens, q_positions, cu_q_lens, *,
                   mode: str, sel=None, own=None):
            if mode == "select":
                return self._select(q, pool, li, tables, kv_lens,
                                    q_positions, cu_q_lens)
            return self._attend(q, pool, li, tables, kv_lens, q_positions,
                                cu_q_lens, sel, own)

        def rect(q, pool, li, tables, kv_lens, q_positions, *, mode: str,
                 sel=None, own=None):
            S, Bq = jax.tree.leaves(q)[0].shape[:2]
            flat = lambda a: a.reshape((S * Bq,) + a.shape[2:])
            flat_all = lambda x: None if x is None else jax.tree.map(flat, x)
            out = ragged(jax.tree.map(flat, q), pool, li, tables, kv_lens,
                         q_positions, jnp.arange(S + 1, dtype=jnp.int32) * Bq,
                         mode=mode, sel=flat_all(sel), own=flat_all(own))
            return jax.tree.map(
                lambda a: a.reshape((S, Bq) + a.shape[1:]), out)

        return rect, ragged

    def _sparse(self, kv_lens):
        """Whether some context of the step holds more than index_topk rows:
        only then is anything selected. The entries of ops/sparse_latent.py
        take it as `live` (they say why no `lax.cond` stands around them)."""
        return jnp.max(kv_lens) > self.config.index_topk

    def _select(self, q, pools, group, tables, kv_lens, q_positions,
                cu_q_lens):
        """-> (positions (T, index_topk) int32, count (T,), cached (T,), mask
        (T, T), picked (T, index_topk, S x W)): the selection, which of its
        rows the pool holds already and which the step's own tokens bring
        (`sl.step_rows`), and the group's rows of the selected positions,
        gathered once for every layer that attends under it. Zeros where the
        step takes the dense kernel (nobody reads them there)."""
        qi, w = q
        index_pool, pool = pools
        live = self._sparse(kv_lens)
        scores = sl.dsa_index(qi, w, index_pool, group, tables, kv_lens,
                              q_positions, cu_q_lens, impl=self.impl,
                              live=live)
        seq, at, n, valid = sl.flat_rows(cu_q_lens, q_positions, kv_lens,
                                         qi.shape[0])
        positions, count = sl.dsa_select(
            scores, n, topk=self.config.index_topk, impl=self.impl, live=live)
        rows = sl.pool_rows(positions, tables, seq, pool.shape[2],
                            impl=self.impl, live=live)
        cached, mask = sl.step_rows(positions, count, seq, at,
                                    q_positions[seq], valid, live=live)
        return (positions, count, cached, mask,
                sl.gather_selection(pool, group, rows, live=live))

    def _attend(self, q, pool, li, tables, kv_lens, q_positions, cu_q_lens,
                sel, own):
        """Over the selected rows where the step selects, else the dense
        latent attention: ONE of the two does work (the other's grid steps
        are empty: a step that selects gives the dense kernel no row, `live`
        False gives the sparse one none). li = (group, place): the layer's
        lanes of the pool's row and of the gathered operand."""
        kw = dict(scale=self.scale, lat=self.config.kv_lora_rank)
        live = self._sparse(kv_lens)
        _, count, cached, mask, rows = sel
        picked = sl.dsa_attend(q, rows, count, cached, own, mask,
                               place=li[1], impl=self.impl, live=live, **kw)
        if self.impl == "pallas":
            whole = pa.latent_paged_attention_unified(
                q, pool, li, tables, kv_lens, q_positions,
                jnp.where(live, 0, cu_q_lens), **kw)
        else:
            whole = jax.lax.cond(
                live, lambda: jnp.zeros_like(picked),
                lambda: pa.latent_paged_attention_unified_reference(
                    q, pool, li, tables, kv_lens, q_positions, cu_q_lens,
                    **kw))
        return jnp.where(live, picked, whole)

    # ---- the layer step, stated once --------------------------------------

    def layer_step(self, ctx, kind: str, x, caches, lp, li, ll):
        """One layer over rows (..., d); `li` is the layer's index (a Python
        int: its place in the pools is static). x is (rows, selection) from
        the first layer on. -> (x, caches,
        aux): aux {"routing", "counts"} of an expert layer, {"selection"} of
        a "full" layer (what `ModelRunner.last_layer_outputs` keeps of the
        rectangular step), or both."""
        c = self.config
        rows, sel = x if isinstance(x, tuple) else (x, None)
        pool, index_pool = caches
        lead = rows.shape[:-1]
        H, dt = c.num_attention_heads, c.dtype
        rope = c.qk_rope_head_dim
        group = self.place[li][0]

        h = rms_norm(rows, lp["attn_norm"], c.rms_norm_eps)     # float32
        cq = rms_norm(_wide(_dot32, h, lp["wq_a"]), lp["q_norm"],
                      c.rms_norm_eps)
        q = _wide(_dot32, cq, lp["wq_b"]).reshape(
            *lead, H, c.qk_nope_head_dim + rope)
        cos, sin = rope_at(c, ctx.rope_pos)
        rotate = lambda a: rotate_interleaved(a, cos, sin)
        aux = {}
        if kind.startswith("full"):
            front = lambda a: jnp.concatenate(
                [rotate(a[..., :rope]), a[..., rope:]], axis=-1)
            qi = front(_wide(_dot32, cq, lp["wq_i"]).reshape(
                *lead, c.index_n_heads, c.index_head_dim))
            ki = front(layer_norm(
                _wide(_dot32, h, lp["wk_i"]), lp["k_norm_w"], lp["k_norm_b"],
                INDEX_NORM_EPS)[..., None, :])[..., 0, :]
            index_pool = ctx.write(index_pool, group, ki.astype(dt))
            w = _wide(_dot32, h, lp["w_w"]) * (
                c.index_n_heads ** -0.5 * c.index_head_dim ** -0.5)
            # The group's rows as the pool holds them BEFORE any of its layers
            # writes this step's: those each layer brings itself (`own`).
            sel = ctx.attend((qi.astype(dt), w), (index_pool, pool), group,
                             mode="select")
            aux["selection"] = sel[:2]
        out, pool = latent_attention(
            ctx, c, pool, self.place[li], q, _wide(_dot32, h, lp["wkv_a"]),
            lp, rotate=rotate, own_rows=True, mode="attend", sel=sel)
        rows = rows + out
        caches = (pool, index_pool)

        h = rms_norm(rows, lp["mlp_norm"], c.rms_norm_eps)
        if kind.endswith("_dense"):
            rows = rows + _ffn(_dot32, h.astype(dt), lp["w_gate"],
                               lp["w_up"], lp["w_down"])
            return (rows, sel), caches, aux or None
        flat = h.reshape(-1, c.hidden_size)
        # The router's chain stays float32 (mimo_v2_flash.Block.layer_step).
        scores = jax.nn.sigmoid(_wide(_dot32, flat, lp["router"]))
        ids, gates = route_one_group(c, scores, lp["router_bias"])
        flat = flat.astype(dt)
        routed, counts = held_expert_ffn(
            c, flat, ids, gates * c.routed_scaling_factor,
            ctx.valid.reshape(-1), lp)
        y = routed + _ffn(_dot32, flat, lp["shared_gate"], lp["shared_up"],
                          lp["shared_down"])
        aux.update(routing=ids.reshape(*lead, self.top_k),
                   counts=counts)
        return (rows + y.reshape(rows.shape), sel), caches, aux
