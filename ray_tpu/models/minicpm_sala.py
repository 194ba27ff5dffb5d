"""MiniCPM-SALA (`model_type: minicpm_sala`) for the serving engine: one layer
in four is `minicpm4`, InfLLM-V2's block-sparse attention (32 query / 2 kv
heads of 128, nothing rotated, QK-norm, an output gate: a token whose context
is longer than `dense_len` attends to the `topk` blocks of 64 tokens that its
own queries score highest against pooled keys, every kv head its own set), and
three in four are `lightning-attn`, Lightning Attention-2 (32 heads of 128
with keys and values a HEAD's, rotated, QK-norm, ONE fixed decay a head, a
128 x 128 state a head, an output norm and gate); every layer a SwiGLU MLP;
muP scalings on the embedding, the residual branches and the head.

Source: https://huggingface.co/openbmb/MiniCPM-SALA (`config.json`; OpenBMB's
MiniCPM-SALA report, 2026-02; the sparse mixer is the MiniCPM4 report's,
arXiv:2506.07900; the linear one arXiv:2401.04658). The equations stand in
models/minicpm_sala_reference.py's docstring, with what the config does not
carry and is assumed. What this file states once and the serving runner
(llm/model_runner.py) consumes through `Block`:

  * Two LAYER GROUPS, BOTH WITH BYTES. `all`: the K and V ROW POOLS of the
    `minicpm4` layers (2 kv heads x 128 = 256 lanes a token a layer,
    ops/paged_attention.py's row form; K AFTER its norm) and, beside them,
    the PAGE MEANS (one 256-lane row a page a layer: the mean of the page's
    keys, which is the whole cache of the selection's first stage: a kernel
    of 32 tokens at stride 16 is two pages, ops/block_sparse.py). All three
    are indexed by the page, so whatever shares or recycles a page (the
    prefix cache) takes its mean with it; the layer that writes a page's keys
    makes the mean again from the pool, and it is complete when the page is.
    `state`: a slot a sequence, every `lightning-attn` layer's S (32 heads of
    128 x 128 float32: 2.1 MB), the rows buffered beside it and their count
    (ops/ssd.py, which holds exactly this recurrence: one decay a head and
    token, here constant, dt 1, x = v, B = k, C = q, every head a group of
    its own); no convolution. A prefix hit needs a page chain AND a parked
    slot (llm/engine.py).
  * Segments: runs of like layers in the published order ("sparse",
    "lightning"), each a Python loop (a layer's place in its group's arrays
    and its decays are then static).
  * The rows x are float32 and pass the first layer times `scale_emb`; a
    mixer's and an MLP's output joins them times `scale_depth /
    sqrt(mup_denominator)`; `finish` divides the normed rows by `hidden_size
    / dim_model_base` before the head.

Precision: the residual stream, S, the first stage's scores and both
softmaxes float32; weights, K/V rows and page means the configuration's dtype.

Left out: training (ops/ssd.py has no backward pass), tensor parallelism (a
slot's state is not sharded over the heads, and 2 kv heads split no further),
LoRA.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.expert_share import _dot32, _ffn, kind_segments, runs_of
from ray_tpu.ops import block_sparse as bs
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import sparse_latent as sl
from ray_tpu.ops import ssd as sd
from ray_tpu.ops.layers import rms_norm

LANE = 128
F32 = jnp.float32
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}
PUBLISHED_MIXERS = tuple(
    "minicpm4" if li in (0, 9, 16, 17, 22, 29, 30, 31) else "lightning-attn"
    for li in range(32))


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig:
    """The published keys under their Hugging Face names; `vocab_size`,
    `num_hidden_layers`, `mixer_types` and `max_position_embeddings` as run,
    with where the run's first layer stands in the published model (the
    decays are a function of the PUBLISHED layer index); `sparse_config`'s
    keys (MiniCPM4's values); the chunk of ops/ssd.py's chunked form."""
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    mixer_types: Tuple[str, ...] = PUBLISHED_MIXERS
    first_published_layer: int = 0
    published_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_denominator: int = 32
    dim_model_base: int = 256
    max_position_embeddings: int = 524288
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192
    chunk_size: int = 128
    dtype: Any = jnp.bfloat16

    def serving_block(self) -> "Block":
        return Block(self)

    def __post_init__(self):
        if (len(self.mixer_types) != self.num_hidden_layers
                or set(self.mixer_types) - set(KINDS)):
            raise ValueError(f"mixer_types {self.mixer_types!r} does not "
                             f"name {self.num_hidden_layers} layers of "
                             f"{sorted(KINDS)}")
        if (self.num_attention_heads % self.num_key_value_heads
                or self.lightning_nkv != self.lightning_nh):
            raise ValueError("heads that no kv head divides, or lightning "
                             "keys that are not a head's own")
        if self.kernel_size != 2 * self.kernel_stride:
            raise ValueError("a first-stage kernel is two strides (a page "
                             "each: ops/block_sparse.py)")
        self.geometry(self.kernel_stride).check()

    # What the serving runner and engine read of any model's configuration.
    @property
    def max_seq(self) -> int:
        return self.max_position_embeddings

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.mup_denominator)

    def geometry(self, page: int) -> bs.Geometry:
        return bs.Geometry(page=page, block=self.block_size, topk=self.topk,
                           init_blocks=self.init_blocks,
                           window=self.window_size, dense_len=self.dense_len)

    def layer_kinds(self) -> List[str]:
        return [KINDS[m] for m in self.mixer_types]

    def layers_of(self, kind: str) -> int:
        return self.layer_kinds().count(kind)

    def decays(self):
        """s (lightning layers, heads) float32, lambda = exp(-s): Lightning
        Attention-2's slopes 2^(-8 h / H), h = 1..H, times 1 - l / (L - 1) +
        1e-5 at PUBLISHED layer l of L (as MiniMax-01 scales them by depth)."""
        H = self.lightning_nh
        slopes = 2.0 ** (-8.0 * jnp.arange(1, H + 1, dtype=F32) / H)
        at = jnp.asarray([self.first_published_layer + li for li, kind
                          in enumerate(self.layer_kinds())
                          if kind == "lightning"], F32)
        return slopes[None, :] * (
            1.0 - at / (self.published_layers - 1) + 1e-5)[:, None]

    @property
    def state_bytes_per_sequence(self) -> int:
        """A slot of the state group: every lightning layer's S, float32 (the
        buffered rows beside it are `ssd.buffer_shape`'s)."""
        return (self.layers_of("lightning") * 4 * self.lightning_nh
                * self.lightning_head_dim ** 2)

    def reference_sizes(self) -> Dict:
        """The keys the plain reference (minicpm_sala_reference.py) reads of
        a configuration file's `sizes`."""
        return {k: (list(v) if isinstance(v, tuple) else v)
                for k, v in dataclasses.asdict(self).items()
                if k not in ("dtype", "chunk_size", "vocab_size",
                             "max_position_embeddings", "intermediate_size")}

    @staticmethod
    def tiny(**overrides) -> "MiniCPMSALAConfig":
        """Six layers (sparse, three lightning, sparse, lightning: a state
        layer on both sides of a sparse one), 4 query / 2 kv heads of 16 and
        4 lightning heads of 16; the published kernel, stride and block over
        a `dense_len` of 128, 4 kept blocks of which the first and the
        token's own are forced: a token selects from 129 tokens on and drops
        blocks from 257."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    num_hidden_layers=6,
                    mixer_types=("minicpm4",) + ("lightning-attn",) * 3
                    + ("minicpm4", "lightning-attn"),
                    first_published_layer=2, published_layers=9,
                    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
                    dim_model_base=32, mup_denominator=6, topk=4,
                    window_size=64, dense_len=128, chunk_size=8,
                    max_position_embeddings=1024, dtype=jnp.float32)
        base.update(overrides)
        return MiniCPMSALAConfig(**base)

    def mlp_params(self) -> int:
        return 3 * self.hidden_size * self.intermediate_size

    def lightning_params(self) -> int:
        """q, k, v, the gate and o; the two QK-norms and the output norm a
        head's width; the layer's two norms; the MLP."""
        d, w = self.hidden_size, self.lightning_nh * self.lightning_head_dim
        return (4 * d * w + w * d + 3 * self.lightning_head_dim + 2 * d
                + self.mlp_params())

    def sparse_params(self) -> int:
        d, hd = self.hidden_size, self.head_dim
        H, K = self.num_attention_heads, self.num_key_value_heads
        return (d * (2 * H + 2 * K) * hd + H * hd * d + 2 * hd + 2 * d
                + self.mlp_params())

    def num_params(self) -> int:
        d = self.hidden_size
        return (2 * self.vocab_size * d + d
                + self.layers_of("lightning") * self.lightning_params()
                + self.layers_of("sparse") * self.sparse_params())

    def flops_per_token(self, seq: int) -> float:
        """Operations a token of a forward and backward pass: 6 a parameter
        its products touch, the sparse layers' attention at H x 2 hd x 2 a
        query-context pair over min(seq, topk blocks) of context, a lightning
        layer's recurrence by its own count (a state element decayed, updated
        and read, 2 operations each), x 3 for the backward pass."""
        n = (self.num_params() - self.vocab_size * self.hidden_size)
        pair = self.num_attention_heads * 2 * self.head_dim * 2
        seen = seq if seq <= self.dense_len else min(
            seq, self.topk * self.block_size)
        state = 6 * self.lightning_nh * self.lightning_head_dim ** 2
        return (6.0 * n + 3.0 * self.layers_of("sparse") * pair * seen
                + 3.0 * self.layers_of("lightning") * state)


# -------------------------------------------------------------- parameters

def init_params(config: MiniCPMSALAConfig, key: jax.Array) -> Dict:
    """Random normal, 1/sqrt(fan_in); the embedding's rows N(0, 1) (times
    `scale_emb` in the model, then a norm: the scale is the model's, not the
    draw's); every norm 1. What decides a KEPT BLOCK is content: the sparse
    layers rotate nothing, so a score is `q . kbar` of a normed query against
    the mean of 32 normed keys of tokens drawn at random, about N(0, 0.18^2)
    a kernel over ~2,000 kernels: no block wins by recency or by a constant,
    the 31 free blocks of a row are those whose tokens' keys happen to lie
    along its queries, they differ between the two kv heads and from row to
    row, and ranks near the 31st lie within bf16 rounding of each other (the
    check follows the program's choice and reports the shortfall: chip_smoke.py
    `minicpm_sala_check`). What keeps S alive is the decays themselves, which
    are no parameters (`MiniCPMSALAConfig.decays`): at the published depth a
    layer's slowest heads keep lambda over 0.99 (s = 2^-8 x 0.23 to 0.71), so
    a state carries hundreds of tokens, and its fastest forget in a few (a
    program that dropped the state at a chunk's edge, or folded the buffer
    wrongly, must not agree with the reference). Every stacked weight is drawn
    a slice at a time and cast inside one program (no float32 copy of a
    stack). `params["layers"]` is one dict a KIND of layer, its layers
    stacked in the published order."""
    c = config
    d, f = c.hidden_size, c.intermediate_size
    H, K, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    wl, ld = c.lightning_nh * c.lightning_head_dim, c.lightning_head_dim
    keys = iter(jax.random.split(key, 64))

    def stack(lead: Tuple[int, ...], shape: Tuple[int, ...], fan_in: int):
        n = math.prod(lead)

        def one(k):
            return (jax.random.normal(k, shape, F32)
                    * (1.0 / math.sqrt(fan_in))).astype(c.dtype)

        draw = jax.jit(lambda ks: jax.lax.map(one, ks))
        return draw(jax.random.split(next(keys), n)).reshape(lead + shape)

    def ones(*shape):
        return jnp.ones(shape, dtype=c.dtype)

    def mlp(L):
        return {"mlp_norm": ones(L, d), "w_gate": stack((L,), (d, f), d),
                "w_up": stack((L,), (d, f), d),
                "w_down": stack((L,), (f, d), f)}

    def lightning(L):
        return {"norm": ones(L, d), "wq": stack((L,), (d, wl), d),
                "wk": stack((L,), (d, wl), d), "wv": stack((L,), (d, wl), d),
                "wz": stack((L,), (d, wl), d), "wo": stack((L,), (wl, d), wl),
                "q_norm": ones(L, ld), "k_norm": ones(L, ld),
                "o_norm": ones(L, ld), **mlp(L)}

    def sparse(L):
        return {"norm": ones(L, d), "wq": stack((L,), (d, H * hd), d),
                "wk": stack((L,), (d, K * hd), d),
                "wv": stack((L,), (d, K * hd), d),
                "wz": stack((L,), (d, H * hd), d),
                "wo": stack((L,), (H * hd, d), H * hd),
                "q_norm": ones(L, hd), "k_norm": ones(L, hd), **mlp(L)}

    draw = {"lightning": lightning, "sparse": sparse}
    blocks = 8 if c.vocab_size % 8 == 0 else 1
    return {
        "embed": stack((blocks,), (c.vocab_size // blocks, d), 1).reshape(
            c.vocab_size, d),
        "layers": {kind: draw[kind](c.layers_of(kind))
                   for kind in sorted(set(c.layer_kinds()))},
        "final_norm": ones(d),
        "lm_head": stack((), (d, c.vocab_size), d),
    }


def rotate_half(x, positions, theta: float):
    """x (..., heads, hd) float32 rotated at positions (...): the whole head,
    lane i with lane i + hd / 2 by position x theta^(-2 i / hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    angle = positions.astype(F32)[..., None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# -------------------------------------------------------- the serving block

class Block:
    """MiniCPM-SALA as the serving runner consumes a model (the protocol is
    llm/model_runner.py's, "A block"): two layer groups, six arrays."""

    routed_layers = 0
    top_k = None
    held_experts = 0
    # A tick record's: rows and sequences the lightning layers' calls
    # carried,
    state_fields = ("ssd_rows", "ssd_seqs")
    # and what the selection spares and scores, by `tick_counts`.
    tick_fields = ("block_pairs", "select_rows", "select_seqs",
                   "pages_scored")

    def __init__(self, config: MiniCPMSALAConfig):
        from ray_tpu.llm.model_runner import LayerGroup

        self.config = config
        self.residual_dtype = F32      # the module docstring, "Precision"
        self.scale = config.head_dim ** -0.5
        self.q_block = self.kv_kernels(16)["all"].q_block
        self.groups = (LayerGroup("all"), LayerGroup("state", slots=True))
        self.impl = "reference"        # attention_fns sets it
        self.log_decay = -config.decays()                    # (layers, H)
        # A layer's index inside its group's arrays.
        seen: Dict[str, int] = {}
        self.pool_layer = []
        for kind in config.layer_kinds():
            self.pool_layer.append(seen.get(kind, 0))
            seen[kind] = seen.get(kind, 0) + 1

    def refuse(self, *, tensor_parallel: int, lora: bool) -> None:
        if tensor_parallel > 1:
            raise ValueError("minicpm_sala: tensor_parallel > 1 is not "
                             "supported (a slot's state is not sharded)")
        if lora:
            raise ValueError("minicpm_sala: LoRA adapters are not supported")

    def pallas_ok(self) -> bool:
        c = self.config
        return (c.head_dim % LANE == 0 and c.lightning_head_dim % LANE == 0)

    def tick_counts(self, rows, tables=None, page: int = 1) -> Dict[str, int]:
        """Of a tick's rows [(tokens, first position, context after them)]
        and the step's block table (a row's pages of `page` tokens; None: no
        two rows share a page), host arithmetic (the device's picks are not
        read back): `block_pairs`, the query-context pairs a sparse layer
        must cover (a token that sees n > dense_len tokens: its own block's
        part and min(blocks, topk) - 1 whole blocks; else n: `attn_pairs` is
        the dense count); `select_rows`, the query tokens that select;
        `select_seqs`, the sequences with one; `pages_scored`, the DISTINCT
        pages whose means the step's selecting contexts hold (a shared
        document's count once)."""
        c = self.config
        B = c.block_size
        out = dict.fromkeys(self.tick_fields, 0)
        # (compose time, every tick: whole-array arithmetic, no loop a token
        # or a page)
        scored = None if tables is None else np.zeros(
            int(np.max(tables, initial=0)) + 1, bool)
        for row, (n, first, kv_len) in enumerate(rows):
            # tokens first .. first + n - 1; token p sees p + 1
            dense = max(0, min(n, c.dense_len - first))
            out["block_pairs"] += dense * first + dense * (dense + 1) // 2
            if n == dense:
                continue
            p = np.arange(first + dense, first + n)
            out["block_pairs"] += int(np.sum(
                p % B + 1 + B * (np.minimum(p // B + 1, c.topk) - 1)))
            out["select_rows"] += n - dense
            out["select_seqs"] += 1
            pages = -(-kv_len // page)
            if scored is None:
                out["pages_scored"] += pages
            else:
                scored[np.asarray(tables[row][:pages])] = True
        if scored is not None:
            out["pages_scored"] = int(scored.sum())
        return out

    # ---- cache -----------------------------------------------------------

    def cache_arrays(self, pages: Dict[str, int], block_size: int):
        """The `all` group's K and V ROW POOLS and page means (the sparse
        layers); the state group's S, the rows buffered beside it and their
        count (the lightning layers), `pages["state"]` slots and the junk
        slot behind them."""
        from ray_tpu.llm.model_runner import (row_cache_array,
                                              state_cache_array)

        c = self.config
        if block_size != c.kernel_stride:
            raise ValueError(
                f"minicpm_sala: pages of {block_size} tokens; the page means "
                f"are the first stage's cache only where a page is a kernel's "
                f"stride ({c.kernel_stride})")
        width = c.num_key_value_heads * c.head_dim
        L, P = c.layers_of("sparse"), pages["all"]
        M, slots = c.layers_of("lightning"), pages["state"]
        H, hd = c.lightning_nh, c.lightning_head_dim
        return (
            row_cache_array("k_all", (L, P, block_size, width), c.dtype,
                            "all"),
            row_cache_array("v_all", (L, P, block_size, width), c.dtype,
                            "all"),
            # (a row a page: what indexes pages indexes it)
            row_cache_array("k_mean", (L, P, width), c.dtype, "all"),
            state_cache_array("ssd_state",
                              sd.state_shape(M, slots, H, hd, hd), F32),
            state_cache_array("ssd_rows",
                              sd.buffer_shape(M, slots, H, H, hd, hd), F32),
            state_cache_array("ssd_fill", sd.fill_shape(M, slots),
                              jnp.int32))

    def kv_kernels(self, block_size: int):
        c = self.config
        return {"all": pa.kv_sizes(
            c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.head_dim, block_size, jnp.dtype(c.dtype).itemsize, rows=True)}

    def init_cache(self, pages: Dict[str, int], block_size: int):
        from ray_tpu.llm.model_runner import init_cache

        return init_cache(self.cache_arrays(pages, block_size))

    def segments(self, params):
        """Runs of like layers in the published order, each a Python loop
        (`expert_share.kind_segments`)."""
        return kind_segments(runs_of(self.config.layer_kinds()), params)

    def finish(self, x, params):
        c = self.config
        rows = rms_norm(x, params["final_norm"], c.rms_norm_eps)
        return (rows / (c.hidden_size / c.dim_model_base)).astype(c.dtype)

    # ---- attention -------------------------------------------------------

    def attention_fns(self, impl: str):
        """(rectangular, ragged), each three functions in one by `mode`,
        all over (the page means, the K pool) or (the K pool, the V pool) of
        a layer: "means" (q the step's K rows as the layer wrote them: their
        shape says which tokens the step holds -> the page means), "select"
        (q over the page means -> the kept blocks and their count, a token
        and kv head) and "attend" (q over the K and V pools under `sel`). The
        rectangle is the ragged form with every sequence's Bq tokens in a
        row."""
        self.impl = impl

        def ragged(q, a_pool, b_pool, li, tables, kv_lens, q_positions,
                   cu_q_lens, *, mode: str, sel=None):
            rows = (tables, kv_lens, q_positions, cu_q_lens)
            if mode == "means":
                return self._means(q, a_pool, b_pool, li, *rows)
            if mode == "select":
                return self._select(q, a_pool, b_pool, li, *rows)
            return self._attend(q, a_pool, b_pool, li, *rows, sel)

        def rect(q, a_pool, b_pool, li, tables, kv_lens, q_positions, *,
                 mode: str, sel=None):
            S, Bq = q.shape[:2]
            flat = lambda a: a.reshape((S * Bq,) + a.shape[2:])
            out = ragged(flat(q), a_pool, b_pool, li, tables, kv_lens,
                         q_positions, jnp.arange(S + 1, dtype=jnp.int32) * Bq,
                         mode=mode,
                         sel=None if sel is None else jax.tree.map(flat, sel))
            if mode == "means":
                return out
            return jax.tree.map(
                lambda a: a.reshape((S, Bq) + a.shape[1:]), out)

        return rect, ragged

    def _means(self, k_rows, means, k_pool, li, tables, kv_lens, q_positions,
               cu_q_lens):
        """The means of the pages this step's tokens wrote to, made again
        from the K pool as the step left it."""
        ps = k_pool.shape[2]
        seq, positions, _, valid = sl.flat_rows(
            cu_q_lens, q_positions, kv_lens, k_rows.shape[0])
        written = valid & (positions < kv_lens[seq])
        pages = tables[seq, jnp.clip(positions // ps, 0, tables.shape[1] - 1)]
        return bs.page_means(means, k_pool, li,
                             jnp.where(written, pages, k_pool.shape[1]))

    def _select(self, q, means, k_pool, li, tables, kv_lens, q_positions,
                cu_q_lens):
        c = self.config
        g = c.geometry(k_pool.shape[2])
        R = bs.block_scores(q, means, li, tables, kv_lens, q_positions,
                            cu_q_lens, kv_heads=c.num_key_value_heads,
                            scale=self.scale, geometry=g, impl=self.impl)
        _, positions, _, selects = bs.token_rows(
            cu_q_lens, q_positions, kv_lens, q.shape[0], g.dense_len)
        return bs.block_select(R, positions, selects, geometry=g,
                               impl=self.impl)

    def _attend(self, q, k_pool, v_pool, li, tables, kv_lens, q_positions,
                cu_q_lens, sel):
        """A sequence whose context is longer than `dense_len` attends under
        its tokens' kept blocks; the others take the dense row kernel as it
        is. Each entry is given zero lengths for the sequences that are not
        its own and walks nothing of them."""
        c = self.config
        g = c.geometry(k_pool.shape[2])
        K = c.num_key_value_heads
        sparse = kv_lens > g.dense_len
        picked = bs.block_attend(
            q, k_pool, v_pool, li, tables, kv_lens, q_positions, cu_q_lens,
            *sel, kv_heads=K, scale=self.scale, geometry=g, impl=self.impl)
        dense = (pa.ragged_paged_attention_unified if self.impl == "pallas"
                 else pa.ragged_paged_attention_unified_reference)
        whole = dense(q, k_pool, v_pool, li, tables,
                      jnp.where(sparse, 0, kv_lens), q_positions, cu_q_lens,
                      scale=self.scale, kv_heads=K)
        seq = pa.token_seq_ids(cu_q_lens, q.shape[0], kv_lens.shape[0])
        return jnp.where(sparse[seq][:, None, None], picked, whole)

    # ---- the mixers, each stated once -------------------------------------

    def _lightning(self, ctx, h, held, lp, pool_li):
        """Lightning attention over the normed rows h (R, d); held = (state,
        buffer, fill). -> (the mixer's output (R, d) float32, held)."""
        c = self.config
        rows = ctx.rows
        H, hd = c.lightning_nh, c.lightning_head_dim
        hb = h.astype(c.dtype)
        heads = lambda name: _dot32(hb, lp[name]).reshape(-1, H, hd)
        at = ctx.rope_pos.reshape(-1)
        q = rotate_half(rms_norm(heads("wq"), lp["q_norm"], c.rms_norm_eps),
                        at, c.rope_theta)
        k = rotate_half(rms_norm(heads("wk"), lp["k_norm"], c.rms_norm_eps),
                        at, c.rope_theta)
        y, *held = sd.ssd(
            heads("wv"), jnp.ones(q.shape[:2], F32), self.log_decay[pool_li],
            k, q * hd ** -0.5, *held, pool_li, rows.slots, rows.starts,
            rows.lens, rows.q_positions == 0, impl=self.impl,
            chunk=c.chunk_size)
        y = rms_norm(y, lp["o_norm"], c.rms_norm_eps).reshape(-1, H * hd)
        y = y * jax.nn.sigmoid(_dot32(hb, lp["wz"]))
        return _dot32(y.astype(c.dtype), lp["wo"]), tuple(held)

    def _sparse(self, ctx, h, k_pool, v_pool, means, lp, pool_li):
        """InfLLM-V2 attention over the normed rows h (..., d): nothing is
        rotated. -> (the mixer's output, k_pool, v_pool, means, what the
        layer hands out by name: the kept blocks with their count, and the
        attention's output before its gate)."""
        c = self.config
        H, K, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        lead = h.shape[:-1]
        hb = h.astype(c.dtype)
        q = rms_norm(_dot32(hb, lp["wq"]).reshape(*lead, H, hd),
                     lp["q_norm"], c.rms_norm_eps).astype(c.dtype)
        k = rms_norm(_dot32(hb, lp["wk"]).reshape(*lead, K, hd),
                     lp["k_norm"], c.rms_norm_eps).astype(c.dtype)
        # A token's row whole: its kv heads side by side.
        k = k.reshape(*lead, K * hd)
        k_pool = ctx.write(k_pool, pool_li, k, "all")
        v_pool = ctx.write(v_pool, pool_li,
                           _dot32(hb, lp["wv"]).astype(c.dtype), "all")
        means = ctx.attend(k, means, k_pool, pool_li, group="all",
                           mode="means")
        sel = ctx.attend(q, means, k_pool, pool_li, group="all",
                         mode="select")
        o = ctx.attend(q, k_pool, v_pool, pool_li, group="all",
                       mode="attend", sel=sel).reshape(*lead, H * hd)
        gated = o.astype(F32) * jax.nn.sigmoid(_dot32(hb, lp["wz"]))
        return (_dot32(gated.astype(c.dtype), lp["wo"]), k_pool, v_pool,
                means, {"selection": sel, "attended": o})

    def layer_step(self, ctx, kind: str, x, caches, lp, li, ll):
        """One layer, a mixer and an MLP, over rows x (..., d); `li` is the
        layer's index (from 0, a Python int). -> (x, caches, aux): aux
        {"selection", "attended"} of a sparse layer (what
        `ModelRunner.last_layer_outputs` keeps of the rectangular step: the
        check of chip_smoke.py follows the first and compares the second,
        which the logits of random weights hardly see: a softmax over
        thousands of random values is a fortieth of a lightning layer's
        output)."""
        c = self.config
        k_pool, v_pool, means, *held = caches
        pool_li = self.pool_layer[li]
        scale = c.residual_scale
        if li == 0:
            x = x * c.scale_emb
        h = rms_norm(x, lp["norm"], c.rms_norm_eps)              # float32
        aux = None
        if kind == "lightning":
            out, held = self._lightning(
                ctx, h.reshape(-1, c.hidden_size), held, lp, pool_li)
        else:
            out, k_pool, v_pool, means, aux = self._sparse(
                ctx, h, k_pool, v_pool, means, lp, pool_li)
        x = x + scale * out.reshape(x.shape)
        h = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps)
        x = x + scale * _ffn(_dot32, h.astype(c.dtype), lp["w_gate"],
                             lp["w_up"], lp["w_down"])
        return x, (k_pool, v_pool, means, *held), aux
