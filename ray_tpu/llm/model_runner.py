"""Model execution for serving: one bucketed step for prefill + decode.

Reference analog: the vLLM engine internals the reference only *places*
(vllm_engine.py:222, vllm_models.py:117-168). TPU-native design:

  * The KV cache is a paged pool `(layers, num_blocks, block_size, kv_heads,
    head_dim)` (page-major: see "The KV pool's layout" below); block tables
    map each sequence's logical positions onto pool pages.
  * ONE jitted step (`step_mixed`) serves an engine tick: decode rows,
    draft-verify rows and prefill slices lie token-major in one flat batch;
    new-token KV is scattered into the pool, then the unified paged
    attention (ops/paged_attention.py — Pallas on TPU, O(actual context))
    attends over each sequence's pages. `step_mixed_logits` is the same
    backbone with a head that returns logits for the host to sample.
  * Shapes are bucketed on the tick's TOTAL token count at one pinned batch
    bucket: the engine runs a small fixed set of compiled programs — no
    recompiles in the hot loop.
  * `step` is the rectangular (batch, Bq) program with a logits head: no
    engine path runs it; the benchmark's check against the plain reference
    does (benchmarks/serve_cell.py; ROADMAP D2b).
  * Tensor parallelism: pass a mesh — params/cache shard per SERVE_RULES
    (heads/kv_heads/mlp/vocab over tp), attention runs under shard_map with
    per-shard heads.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

from ray_tpu.models import llama as llama_mod
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies, swiglu


# ---- The KV pool's layout, said once -------------------------------------
#
#   pool         (L, P, page, K, hd)   what init_kv_cache builds, the layer
#                                      scan carries and every step program
#                                      takes (donated) and returns
#   kernel view  the pool as it lies + the layer's index: the paged kernel
#                (ops/paged_attention.py) takes both pools whole, in HBM, and
#                reads a page of all K heads a DMA; its jnp references index
#                the same operands. Nothing is sliced or transposed on the way
#                in
#   wire view    (L, K, n, page, hd)   n pages on their way out or in
#                                      (gather_pages / scatter_pages): what
#                                      llm/disagg.py, session migration,
#                                      adopt_request / adopt_prefix and the
#                                      prefix_store.py codec read and write
#
# (L layers, P pool pages, K kv heads.) Everything that indexes the pool goes
# through the five functions below and ops/paged_attention.py; nothing else
# knows which axis is which.
#
# Why (K, hd) is minor. XLA writes a step's new rows with a scatter whose
# update window is one token's (K, hd), and its layout assignment wants the
# window's dimensions minor. With the pool declared (L, K, P, page, hd), the
# layout this file had, it therefore carried the pool through the layer scan
# as {4,1,3,2,0} (physically this layout) and re-laid the WHOLE pool out on
# the way into the scan and again on the way out: four pool-sized copies in
# every step program (24 ms a tick at Mistral-7B widths and 3584 pages) and
# one K + V pool of temporaries (PERF.md, PR 27; compiled for a described v5e
# by tests/test_tpu_compile.py). Declared as it is written, entry parameter,
# carry and result share one layout and nothing is copied. (L, P, K, page,
# hd), a head's page contiguous, does not work: XLA re-lays the carry to
# {4,2,3,1,0} and the copies are back. While XLA's scatter writes the pool,
# (K, hd) minor is the one layout that is not copied, so the kernel reads that
# layout (until PR 32 each layer sliced its pages out and transposed them to
# (K, P, page, hd): 7.5 GB of copies a tick at Mistral-7B widths and 3584
# pages, proportional to the pool and not to the context).


def pool_shape(config: llama_mod.LlamaConfig, num_blocks: int,
               block_size: int) -> Tuple[int, int, int, int, int]:
    return (config.n_layers, num_blocks, block_size, config.n_kv_heads,
            config.head_dim)


def pool_partition_spec():
    """Tensor parallelism shards the pool over its kv heads."""
    from jax.sharding import PartitionSpec as P

    return P(None, None, None, "tp", None)


def pool_write_rows(pool, layer, block_ids, offsets, rows):
    """Write new tokens' K or V, `rows` (..., K, hd) as computed, to slot
    offsets[...] of page block_ids[...] of `layer`. A row whose page id is
    out of bounds HIGH (padding: id == num_blocks) is dropped. `layer` a pair
    (group, place): a row pool whose rows hold several layers' side by side
    ("The latent pool" below); `rows` (..., W) land in lanes [place W, (place
    + 1) W) of the group's row. The tokens' rows are read whole, their lanes
    replaced and the rows written back whole: XLA's scatter takes a window
    that is a whole row as one operation, and turns a window of SOME lanes
    into a loop of one `dynamic-update-slice` a token (0.5 ms a layer for 160
    tokens on the v5e: PERF.md section 6, PR 50)."""
    if isinstance(layer, tuple):
        group, place = layer
        W = rows.shape[-1]
        whole = pool[group, block_ids, offsets]
        whole = whole.at[..., place * W:(place + 1) * W].set(rows)
        return pool.at[group, block_ids, offsets].set(whole, mode="drop")
    return pool.at[layer, block_ids, offsets].set(rows, mode="drop")


def pool_pages_to_wire(pool, ids):
    """Pages `ids` of every layer in the wire view (L, K, n, page, hd)."""
    return pool[:, ids].transpose(0, 3, 1, 2, 4)


def pool_pages_from_wire(pool, ids, pages):
    """Write `pages`, in the wire view, over pages `ids` of every layer."""
    return pool.at[:, ids].set(
        jnp.asarray(pages, dtype=pool.dtype).transpose(0, 2, 3, 1, 4))


def init_kv_cache(config: llama_mod.LlamaConfig, num_blocks: int,
                  block_size: int) -> Dict[str, jax.Array]:
    """The K and the V pool, (L, P, page, K, hd) each: page-major with one
    token's (K, hd) minor, the layout the step's scatter writes without a
    copy (see "The KV pool's layout" above). Pages leave and enter through
    gather_pages / scatter_pages in the wire view (L, K, n, page, hd)."""
    shape = pool_shape(config, num_blocks, block_size)
    return {"k": jnp.zeros(shape, dtype=config.dtype),
            "v": jnp.zeros(shape, dtype=config.dtype)}


# ---- A block and its cache spec (ROADMAP D3, D4) ---------------------------
#
# The runner is typed on a BLOCK, not on a model's configuration. A block
# supplies:
#
#   config                      vocab_size, max_seq, dtype, norm_eps
#   residual_dtype              of the rows x the layers carry (the model's
#                               dtype, or float32 where a block says why)
#   groups                      its LAYER GROUPS (below), the first "all";
#                               absent: that one group. A group with `slots`
#                               is a STATE group: a slot a sequence, no pages
#   cache_arrays(pages, page)   its CACHE SPEC: a tuple of CacheArray, the
#                               named pools a layer step reads and writes,
#                               each of one group; `pages` {group: its pages}
#   init_cache(pages, page)     {name: zeros} in the spec's device layout
#   segments(params)            [(kind, stacked layer parameters, first layer's
#                               index, apart)]: the layers outside the main
#                               stack (a leading dense layer) and the stack.
#                               What a segment's step calls a layer is the
#                               block's (phi4flash: a PAIR of layers).
#                               `apart` None: the segment is one scan over the
#                               stacked parameters. Else a sequence, one dict
#                               a layer, of parameters held APART from the
#                               stack (weights a custom call takes whole, which
#                               a scan would copy out of their stack every
#                               step): the segment runs as a Python loop
#   layer_step(ctx, kind, x, caches, lp, li, ll) -> (x, caches, aux)
#                               ONE statement of a layer over rows x (..., d):
#                               both backbones below call it, each with its
#                               own StepContext (rows are (S, Bq) or (T,)).
#                               x is the block's own pytree (phi4flash hands
#                               (rows, memory) on from its middle segment,
#                               glm_dsa (rows, the selection of context rows
#                               its next layers share)); aux may be a dict:
#                               "routing" and "counts" of a layer that routes,
#                               and whatever else a layer hands out by name,
#                               which the rectangular `step` keeps stacked
#                               over the layers in `last_layer_outputs`;
#                               `ctx.rows` (RowSegments) says, for a state
#                               group, which rows are which sequence's segment
#                               and which slot is theirs. A layer writes and
#                               reads the pools by (group, the layer's index
#                               IN THE POOL): a pool may be written by one
#                               layer and read by many that write nothing
#   narrow_at                   absent or None: every row passes every segment.
#                               Else the index of the segment before which the
#                               rows NARROW to one a sequence (its last real
#                               row; mixed: `out_rows[:, 0]`), for layers that
#                               hold no cache: those segments get a context of
#                               S one-token rows and cannot write
#   finish(x, params)           absent: RMSNorm `params["final_norm"]`. Else
#                               the block's own last step -> (rows, d) (of a
#                               pytree x: its rows)
#   params["lm_head"]           absent: the head is the embedding (`_logits`)
#   attention_fns(impl)         (rectangular, ragged) paged attention over
#                               a group's pools as they lie and a layer's
#                               index in them
#   q_block                     query tokens a block of its Pallas kernel's
#                               grid (the tick's `q_blocks`, `kv_pages_walked`);
#                               None: no paged layer, nothing walks pages
#   kv_kernels(block_size)      absent: no K/V kernel. Else {page group:
#                               `pa.KVSizes`}: the layout, block and tile
#                               sizes that group's kernel takes
#   tick_fields, tick_counts(rows, tables, page)   absent: nothing. Else the
#                               names of counts the block keeps of a tick by
#                               arithmetic of its own, and the counts of a
#                               tick's rows [(tokens, first position, context
#                               after them)] and the step's block table of
#                               the "all" group (pages of `page` tokens: what
#                               rows share; None: nothing composed): the
#                               engine puts them in the tick's record and sums
#                               them in `stats()` (models/glm_dsa.py: what a
#                               selection of the context spares)
#   state_fields                absent: ("ssm_rows", "ssm_seqs"). The names a
#                               tick record gives the rows and the sequences
#                               its state group's layers carried (kimi_linear:
#                               ("kda_rows", "kda_seqs"))
#   pallas_ok()                 whether its Pallas kernels take its widths
#   refuse(tensor_parallel=, lora=)   raise, in one line, what it cannot do
#   param_logical_axes()        for tensor parallelism, where it has it
#   routed_layers, top_k,       0 / None / 0 unless layers route tokens to
#   held_experts                experts: then `aux` is (ids, counts) and the
#                               runner keeps `last_routing`
#
# `LlamaBlock` below is the K/V block this file always had; the latent block
# is models/deepseek_v2.py's; models/glm_dsa.py's holds TWO arrays in its one
# group (the latent rows and the index keys its selection is scored from):
# both are entries of the spec's tuple, so both travel with a page. A cache
# array has two views: the DEVICE layout
# (what the scan carries and the block's attention reads where it lies; layers
# lead, pages second) and the WIRE view (n pages on their way out or in; None for
# an array that does not travel yet: a state group's, a row pool's). Every
# wire array is 5-D with its pages on axis 2, so whoever
# carries pages (engine.py's spill, adoption, export and host tier; disagg.py;
# serving.py; the prefix_store.py codec) handles the spec's arrays as one
# opaque tuple through `wire_*` below and names none of them.

#
# Layer groups (ROADMAP D4: "two instances in, two kinds out"). Layers that
# keep the same span of a sequence share a GROUP: its pools have the group's
# page count, a sequence has one block table a group, and the engine's
# allocator hands out and takes back pages by group (engine.py, BlockManager).
#
#   LayerGroup("all")           every token of the sequence. The table is
#                               (S, max_blocks_per_seq): logical page p at
#                               column p. The one group of LlamaBlock and of
#                               the latent block. It may hold NO ARRAY (a
#                               block whose every layer is recurrent:
#                               models/brumby.py): its pages are then the
#                               engine's token accounting alone (admission,
#                               the length cap, the prefix chain's digests),
#                               zero bytes, and no program reads its table.
#                               It may stand BESIDE a state group with bytes
#                               of its own (models/kimi_linear.py: a latent
#                               row pool of some layers, slots of the others;
#                               models/phi4flash.py: one K/V layer): a prefix
#                               hit then needs the page chain AND a parked
#                               slot, and a recycled page frees its snapshot
#   LayerGroup("window", w)     the last w tokens and the step's own. Pages
#                               behind every window are freed, so the table is
#                               a RING (S, ring_width): logical page p at
#                               column p % ring_width, wide enough for the
#                               pages a step's first token can see and the
#                               pages it writes
#   LayerGroup("state", slots=True)   recurrent layers: ONE fixed-size slot a
#                               sequence whatever its length, read AND written
#                               by every step. Arrays (layers, slots + 1,
#                               ...), the last slot nobody's (padding rows);
#                               the "table" is (S, 1): the sequence's slot. A
#                               row whose first position is 0 starts from
#                               zeros in the program, so no slot is ever
#                               cleared; `copy_state` snapshots and restores
#                               one for the prefix cache (engine.py). A slot
#                               may be a matrix state with a buffer and a
#                               fill (ops/state_slots.py) or a convolution's
#                               tail ALONE, with no kernel behind it
#                               (models/lfm2_moe.py: two rows a layer)
#
# `num_blocks` and `max_blocks_per_seq` are the "all" group's. A window
# group's page count is derived (`window_group_pages`), a state group's
# slots too (`state_group_slots`). The mixed step takes
# one table a group (the engine's). The rectangular `step` is the entry of a
# caller that OWNS THE POOL while it steps (the benchmark's check,
# benchmarks/serve_cell.py, under the server's lock with the engine idle) and
# gives ONE table, the "all" group's; there, and nowhere else, the runner lays
# a window group's pages itself, a ring at the top of that pool that is a
# pure function of row and column, and a state group's slots, row s slot s
# (`ModelRunner._tables`).

WIRE_PAGE_AXIS = 2


@dataclasses.dataclass(frozen=True)
class LayerGroup:
    name: str
    window: Optional[int] = None    # None: every token of the sequence
    slots: bool = False             # a slot a sequence, not pages

    def ring_width(self, block_size: int, chunk: int) -> int:
        """Columns of a window group's table: the pages that hold the
        window, a step's `chunk` tokens and the next token at any alignment,
        and one to spare."""
        return -(-(self.window + chunk) // block_size) + 2


ONE_GROUP = (LayerGroup("all"),)


def window_group_pages(group: LayerGroup, block_size: int, chunk: int,
                       max_batch: int, all_pages: int) -> int:
    """Pages of a window group's pools: what `max_batch` sequences hold live
    (a ring each), as much again for the window tails that cached prefixes
    park, and never more than the "all" group has (a live window page lies
    beside a live page of the same tokens there)."""
    ring = group.ring_width(block_size, chunk)
    return max(min(2 * max_batch * ring, all_pages), ring)


def state_group_slots(max_batch: int) -> int:
    """Slots of a state group: one a live sequence, and as many again for the
    snapshots that cached prefixes park (`window_group_pages`' rule)."""
    return 2 * max_batch


@dataclasses.dataclass(frozen=True)
class CacheArray:
    name: str
    shape: Tuple[int, ...]          # device layout
    dtype: Any
    to_wire: Callable               # (pool, ids) -> n pages, wire view
    from_wire: Callable             # (pool, ids, pages) -> pool
    partition: Any = None           # PartitionSpec under tensor parallelism
    group: str = "all"              # the layer group whose pages it holds


def kv_cache_array(name: str, shape, dtype, group: str = "all") -> CacheArray:
    """A K or a V pool (L, P, page, K, width) of `group`."""
    return CacheArray(name, tuple(shape), dtype, pool_pages_to_wire,
                      pool_pages_from_wire, pool_partition_spec(), group)


def row_cache_array(name: str, shape, dtype, group: str) -> CacheArray:
    """A K or a V ROW POOL (L, P, page, K x width) of `group`: a token's row
    whole on the lanes (ops/paged_attention.py says when). No wire view yet:
    the blocks that use it have more than one group, whose pages do not
    travel (`require_one_group`)."""
    return CacheArray(name, tuple(shape), dtype, None, None, None, group)


def state_cache_array(name: str, shape, dtype) -> CacheArray:
    """An array of the "state" group (layers, slots + 1, ...): a slot a
    sequence and the junk slot behind them. It has no wire view (a slot does
    not travel: `require_one_group`)."""
    return CacheArray(name, tuple(shape), dtype, None, None, None, "state")


def init_cache(arrays: Sequence[CacheArray]) -> Dict[str, jax.Array]:
    return {a.name: jnp.zeros(a.shape, dtype=a.dtype) for a in arrays}


# The latent pool (one row a token a layer, shared by every head; MLA):
#
#   pool         (L, P, page, W)      W = [c_kv | k_rope] padded with zeros to
#                                     whole 128-lane tiles (576 -> 640 at
#                                     DeepSeek-V2's widths): a row's tail is
#                                     real HBM, 1,280 B a token a layer, and a
#                                     page is one aligned (page, W) DMA
#   kernel view  the pool as it lies + the layer's index (scalar prefetch):
#                nothing is sliced or transposed on the way in
#   wire view    (L, 1, n, page, W)
#
# One token's W is minor, so the step's scatter writes it without a copy, for
# the reason given above for (K, hd).
#
# A model whose consecutive layers read the SAME rows of their contexts
# (models/glm_dsa.py: the layers that share a selection) lays those layers'
# rows of one token side by side, a ROW POOL by group:
#
#   pool         (G, P, page, S x W)  layer `place` of group `group` owns
#                                     lanes [place W, (place + 1) W): whole
#                                     lane tiles; the step's scatter writes
#                                     the tokens' whole rows back with that
#                                     window replaced, in place
#                                     (`pool_write_rows` with the pair), and
#                                     the latent kernel's page DMA reads it
#                                     (ops/paged_attention.py); one gather
#                                     of a row fetches all S layers'
#   wire view    (G, 1, n, page, S x W)   the same function: it indexes pages
#                                     only

def latent_cache_array(name: str, shape, dtype) -> CacheArray:
    return CacheArray(
        name, tuple(shape), dtype,
        to_wire=lambda pool, ids: pool[:, ids][:, None],
        from_wire=lambda pool, ids, pages: pool.at[:, ids].set(
            jnp.asarray(pages, dtype=pool.dtype)[:, 0]))


def wire_page_count(pages: Sequence) -> int:
    return int(np.shape(pages[0])[WIRE_PAGE_AXIS])


def wire_pages(pages: Sequence, start: int, stop: int) -> tuple:
    """Pages [start, stop) of every array of a wire tuple."""
    return tuple(np.asarray(p)[:, :, start:stop] for p in pages)


def wire_concat(parts: Sequence[Sequence]) -> tuple:
    """Wire tuples joined along their pages."""
    if len(parts) == 1:
        return tuple(np.asarray(p) for p in parts[0])
    return tuple(np.concatenate([np.asarray(p) for p in arrs],
                                axis=WIRE_PAGE_AXIS)
                 for arrs in zip(*parts))


def wire_nbytes(pages: Sequence) -> int:
    return int(sum(np.asarray(p).nbytes for p in pages))


@dataclasses.dataclass
class StepContext:
    """What a layer step needs of the step program it runs in. The
    rectangular and the token-major backbone differ in these and in nothing
    else: rows are (S, Bq, ...) or (T, ...)."""
    rope_pos: jax.Array     # the rows' absolute positions, clipped
    valid: jax.Array        # real rows (not padding)
    write: Callable         # (pool, layer, rows, group="all") -> pool
    attend: Callable        # (q, *pools, layer, group="all", **kw of the
    #                         block's attention) -> attention output
    proj: Callable          # (h, layer params, layer lora, name) -> h @ W
    rows: Optional["RowSegments"] = None    # for a block with a state group


@dataclasses.dataclass
class RowSegments:
    """A step's rows, flat (R = S x Bq or T), as segments of its sequences:
    what a recurrent layer walks. Sequence s owns rows [starts[s], starts[s]
    + lens[s]) from position q_positions[s] on, and slot slots[s] of the
    state group."""
    seq: jax.Array          # (R,) a row's sequence
    local: jax.Array        # (R,) its index in the segment
    starts: jax.Array       # (S,)
    lens: jax.Array         # (S,)
    q_positions: jax.Array  # (S,)
    slots: jax.Array        # (S,)


class LlamaBlock:
    """Dense GQA decoder (models/llama.py) over a K and a V pool."""

    routed_layers = 0
    top_k = None
    held_experts = 0
    groups = ONE_GROUP

    def __init__(self, config: llama_mod.LlamaConfig):
        self.config = config
        # (at any page size: the query block does not depend on it)
        self.q_block = self.kv_kernels(16)["all"].q_block
        self.residual_dtype = config.dtype
        self.cos, self.sin = rope_frequencies(
            config.head_dim, config.max_seq, config.rope_theta)

    def refuse(self, *, tensor_parallel: int, lora: bool) -> None:
        pass

    def pallas_ok(self) -> bool:
        # The Pallas kernel's page DMA needs a 128-aligned trailing dim.
        return self.config.head_dim % 128 == 0

    def param_logical_axes(self):
        return llama_mod.param_logical_axes(self.config)

    def cache_arrays(self, pages: Dict[str, int], block_size: int):
        shape = pool_shape(self.config, pages["all"], block_size)
        return tuple(kv_cache_array(name, shape, self.config.dtype)
                     for name in ("k", "v"))

    def kv_kernels(self, block_size: int):
        c = self.config
        return {"all": pa.kv_sizes(c.n_heads, c.n_kv_heads, c.head_dim,
                                   c.head_dim, block_size,
                                   jnp.dtype(c.dtype).itemsize)}

    def init_cache(self, pages: Dict[str, int], block_size: int):
        return init_kv_cache(self.config, pages["all"], block_size)

    def segments(self, params):
        return [("layer", params["layers"], 0, None)]

    def attention_fns(self, impl: str):
        if impl == "pallas":
            return (pa.ragged_paged_attention,
                    pa.ragged_paged_attention_unified)
        return (pa.ragged_paged_attention_reference,
                pa.ragged_paged_attention_unified_reference)

    def layer_step(self, ctx: StepContext, kind: str, x, caches, lp, li, ll):
        config = self.config
        ck, cv = caches
        lead = x.shape[:-1]
        H, K, hd = config.n_heads, config.n_kv_heads, config.head_dim
        proj = ctx.proj
        h = rms_norm(x, lp["attn_norm"], config.norm_eps)
        q = proj(h, lp, ll, "wq").reshape(*lead, H, hd)
        k = proj(h, lp, ll, "wk").reshape(*lead, K, hd)
        v = proj(h, lp, ll, "wv").reshape(*lead, K, hd)
        q = apply_rope(q, self.cos, self.sin, ctx.rope_pos)
        k = apply_rope(k, self.cos, self.sin, ctx.rope_pos)
        # Scatter this step's kv into the pool: layer li, each row's page and
        # slot, every kv head: the value is (..., K, hd), k/v as computed.
        ck = ctx.write(ck, li, k)
        cv = ctx.write(cv, li, v)
        attn = ctx.attend(q, ck, cv, li)
        x = x + proj(attn.reshape(*lead, H * hd), lp, ll, "wo")
        h = rms_norm(x, lp["mlp_norm"], config.norm_eps)
        x = x + proj(swiglu(proj(h, lp, ll, "w_gate"),
                            proj(h, lp, ll, "w_up")), lp, ll, "w_down")
        return x, (ck, cv), None


def block_of(config):
    """The serving block of a model configuration: the configuration's own
    (`serving_block()`), else the K/V block of a LlamaConfig."""
    make = getattr(config, "serving_block", None)
    return make() if make is not None else LlamaBlock(config)


def _bucket(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    # Beyond the precomputed set: next power of two (a new compile, never a
    # silent cap — capping would overflow the engine's padded arrays).
    # ModelRunner._note_shapes makes that compile visible (metric + log)
    # instead of a silent multi-second hot-loop stall.
    return 1 << (n - 1).bit_length()


def token_buckets(budget: int) -> list:
    """Static token-budget ladder for the unified mixed step: powers of two
    from 8 up to (and always including) `budget`. Single source of truth for
    runtime bucketing AND warmup precompilation, mirroring chunk_buckets().
    Every bucket is a multiple of 8 (a sublane tile of the flat rows)."""
    buckets, b = [], 8
    while b < budget:
        buckets.append(b)
        b *= 2
    buckets.append(budget)
    return buckets


# ---- order statistics by selection (the sampling head) ----------------------
#
# A threshold of the sampler's filter is an order statistic of a row of the
# vocabulary. Its 32 bits are settled from the top, one a pass: a pass compares
# the row as it lies against the candidate with that bit set and reduces along
# the row, so a threshold costs 32 reads of the row whatever k, p or the ties.
# (Two and four bits a pass, 3 and 15 candidates, read 0.27 and 0.42 ms against
# 0.17 at 16 x 200,064, where the sorts took 9.2, and within 0.005 ms of one
# bit's 0.038-0.045 at 8 x 19,072 to 32,768; my chip run, PR 39. XLA keeps the
# rows in VMEM across the passes.)


def _select(row_stat, want):
    """The largest uint32 `t` with `row_stat(t) >= want`, row by row, for a
    `row_stat` (candidates (rows,) -> a statistic (rows,)) that does not rise
    with `t`; 0 where even `t = 0` falls short of `want` (rows,)."""

    def settle(i, t):
        cand = t | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(row_stat(cand) >= want, cand, t)

    return jax.lax.fori_loop(0, 32, settle, jnp.zeros(want.shape, jnp.uint32))


def _kth_largest(x, k):
    """The k-th largest value of each row of float32 `x` (rows, V), `k`
    (rows,) in [1, V]: exact, ties and infinities included. A float's bits,
    with the sign flipped (and the rest too below zero), order as the floats
    do; a pass counts the keys at or above each candidate."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    sign = jnp.uint32(1 << 31)
    key = jnp.where(bits >= sign, ~bits, bits | sign)
    t = _select(lambda cand: jnp.sum(key >= cand[:, None], axis=-1,
                                     dtype=jnp.int32), k.astype(jnp.int32))
    return jax.lax.bitcast_convert_type(
        jnp.where(t >= sign, t ^ sign, ~t), jnp.float32)


def _top_p_floor(kept, top_ps):
    """The lowest logit of each row's nucleus: over `probs = softmax(kept)`
    the cutoff is the smallest probability whose mass strictly above it is
    `< top_p`, and the floor is the least `kept` value whose probability
    reaches it (+inf where `top_p <= 0` keeps nothing). Probabilities are not
    negative, so their bits order as they do; a pass sums the mass strictly
    above each candidate, which does not rise with the candidate: the nucleus
    is what lies above the largest candidate with `top_p` of mass above it
    (above 0 where a row's whole mass rounds short of `top_p`)."""
    probs = jax.nn.softmax(kept, axis=-1)
    pbits = jax.lax.bitcast_convert_type(probs, jnp.uint32)
    t = _select(lambda cand: jnp.sum(
        jnp.where(pbits > cand[:, None], probs, 0.0), axis=-1), top_ps)
    return jnp.min(jnp.where(pbits > t[:, None], kept, jnp.inf), axis=-1)


class ModelRunner:
    """Bucketed, jit-compiled unified step over a paged cache."""

    BATCH_BUCKETS = (1, 2, 4, 8, 16, 32)

    def __init__(self, config, params,
                 num_blocks: int, block_size: int = 16,
                 mesh=None, attention_impl: str = "auto",
                 chunk_size: int = 128,
                 max_blocks_per_seq: Optional[int] = None,
                 lora_manager=None, max_batch: Optional[int] = None):
        self.config = config
        self.block = block_of(config)
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.chunk_size = chunk_size
        self.max_blocks_per_seq = max_blocks_per_seq or (
            (config.max_seq + block_size - 1) // block_size)
        # Layer groups: pages and table columns of each ("Layer groups"
        # above). `max_batch` sequences at most are live at once.
        self.groups = tuple(getattr(self.block, "groups", ONE_GROUP))
        self.max_batch = max_batch or self.BATCH_BUCKETS[-1]
        self.group_pages = {"all": num_blocks}
        self.table_widths = {"all": self.max_blocks_per_seq}
        for g in self.groups[1:]:
            if g.slots:     # a slot a sequence: the "table" is its one column
                self.group_pages[g.name] = state_group_slots(self.max_batch)
                self.table_widths[g.name] = 1
                continue
            self.group_pages[g.name] = window_group_pages(
                g, block_size, chunk_size, self.max_batch, num_blocks)
            self.table_widths[g.name] = g.ring_width(block_size, chunk_size)
        self.state_group = next((g.name for g in self.groups if g.slots),
                                None)
        self._narrows = getattr(self.block, "narrow_at", None) is not None
        self.mesh = mesh
        self.tp = int(mesh.shape.get("tp", 1)) if mesh is not None else 1
        self.block.refuse(tensor_parallel=self.tp,
                          lora=lora_manager is not None)
        if attention_impl == "auto":
            from ray_tpu.ops import is_tpu_backend

            attention_impl = ("pallas" if is_tpu_backend()
                              and self.block.pallas_ok() else "reference")
        self.attention_impl = attention_impl
        self._attention = self.block.attention_fns(attention_impl)
        # What each page group's K/V kernel takes at these shapes: a static
        # fact, said once (`engine.stats()["kv_kernels"]`).
        kernels = getattr(self.block, "kv_kernels", None)
        self.kv_kernels = {} if kernels is None else {
            g: sizes.describe() for g, sizes in kernels(block_size).items()}
        # Multi-LoRA (llm/lora.py): when a manager is attached, the step
        # takes the slot stacks + a per-sequence slot index and adds batched
        # low-rank deltas; without one the step compiles with no LoRA code.
        self.lora = lora_manager
        self.params = self._place_params(params)
        self.cache_arrays = self.block.cache_arrays(self.group_pages,
                                                    block_size)
        self.cache = self._place_cache(
            self.block.init_cache(self.group_pages, block_size))
        # A block that routes: the published ids of the experts the last
        # step(...) kept, int32 (routed layers, S, Bq, top_k), and of the
        # last step_mixed(...) the rows its held experts computed and the
        # busiest expert's, summed over the routed layers (device arrays).
        self.last_routing = None
        self.last_expert_counts = None
        # What else the layers of the last step(...) handed out by name (a
        # block that selects context rows: "selection"), or None.
        self.last_layer_outputs = None
        self._step_jit = jax.jit(self._step, donate_argnums=(1,))
        self._step_mixed_jit = jax.jit(self._step_mixed, donate_argnums=(1,))
        self._step_mixed_logits_jit = jax.jit(self._step_mixed_logits,
                                              donate_argnums=(1,))
        # Reads pages and writes nothing: the pool is NOT donated. (Pages
        # travel for a block of one group only: `require_one_group`.)
        def gather(cache, ids):
            return tuple(a.to_wire(cache[a.name], ids)
                         for a in self.cache_arrays if a.to_wire is not None)

        self._gather_jit = jax.jit(gather)
        # Bytes of one page over every array of the spec, in the wire view.
        self.page_nbytes = sum(
            math.prod(w.shape) * w.dtype.itemsize for w in jax.eval_shape(
                gather, {a.name: jax.ShapeDtypeStruct(a.shape, a.dtype)
                         for a in self.cache_arrays},
                jax.ShapeDtypeStruct((1,), jnp.int32)))
        if self.state_group is not None:
            # Slot `src` of every array of the state group copied over slot
            # `dst`, in place: a snapshot taken or restored (engine.py).
            def copy_state(cache, src, dst):
                return {a.name: (cache[a.name].at[:, dst].set(
                    cache[a.name][:, src]) if a.group == self.state_group
                    else cache[a.name]) for a in self.cache_arrays}

            self._copy_state_jit = jax.jit(copy_state, donate_argnums=(0,))
        # Shape signatures already dispatched: a new one means XLA compiles
        # a fresh program on this call (satellite of ISSUE 17 — silent
        # hot-loop recompiles become a counted, logged event).
        self._seen_shapes: set = set()
        self.step_compiles = 0
        self._noted: Optional[tuple] = None     # see _note_shapes

    def _note_shapes(self, kind: str, *arrs) -> None:
        """Record the padded shape signature entering a jitted entry point.
        A new one (this dispatch pays a compile) bumps
        ray_tpu_llm_step_compiles_total, logs once, and leaves in
        `self._noted` what `_note_compiled` takes after that dispatch (an
        attribute and not the caller's local: the caller's frame lies under
        the trace, `serving._StartupAccount`)."""
        key = (kind,) + tuple(tuple(getattr(a, "shape", ())) for a in arrs)
        if key in self._seen_shapes:
            return
        self._seen_shapes.add(key)
        self.step_compiles += 1
        from ray_tpu.runtime import metric_defs
        from ray_tpu.util import tracing

        metric_defs.LLM_STEP_COMPILES.inc()
        logger.info("llm step compile #%d: %s", self.step_compiles, key)
        self._noted = (key, self.step_compiles, time.time(),
                       tracing.compile_totals())

    def _note_compiled(self) -> None:
        """After the dispatch that `_note_shapes` said pays: the compile as a
        span with that dispatch's extent (a jitted call returns once its
        program is compiled and enqueued: nothing waits here) and the compile
        ledger's stages inside it. In the request timeline it attributes the
        one slow inter-token gap to XLA rather than to scheduling, and says
        to which stage: a trace and a lowering alone where the persistent
        cache hit."""
        from ray_tpu.util import tracing

        key, index, start, before = self._noted
        self._noted = None
        tracing.record_span("llm:step_compile", "llm", start, time.time(),
                            entry_point=key[0],
                            shapes=[list(shape) for shape in key[1:]],
                            compile_index=index,
                            **tracing.stage_args(tracing.compile_since(before)))

    # ---- placement (TP over the mesh, SERVE_RULES) -----------------------

    def _place_params(self, params):
        if self.mesh is None:
            return params
        from ray_tpu.parallel.sharding import SERVE_RULES, shard_tree

        return shard_tree(params, self.block.param_logical_axes(),
                          SERVE_RULES, self.mesh)

    def _place_cache(self, cache):
        if self.mesh is None:
            return cache
        from jax.sharding import NamedSharding

        return {a.name: jax.device_put(
            cache[a.name], NamedSharding(self.mesh, a.partition))
            for a in self.cache_arrays}

    # ---- attention dispatch ---------------------------------------------

    def _attend(self, fn, q, views, group, tables, *scalars, **kw):
        """Paged attention `fn` of q, (..., H, hd) with its heads on axis
        -2, over `views`: the pools of `group` as they lie and the layer's
        index in them, under the group's block table; `kw` is the block's
        own (a window, a sink, a scale). Under tensor parallelism each chip
        takes its own heads of q and of the pools."""
        if kw:
            fn = functools.partial(fn, **kw)
        scalars = (tables[group],) + scalars
        if self.tp > 1:
            from jax import shard_map
            from jax.sharding import PartitionSpec as P

            heads = P(*([None] * (q.ndim - 2)), "tp", None)
            fn = shard_map(
                fn, mesh=self.mesh,
                in_specs=(heads, *(a.partition for a in self.cache_arrays
                                   if a.group == group),
                          *([P()] * (1 + len(scalars)))),
                out_specs=heads)
        return fn(q, *views, *scalars)

    def _page_slots(self, tables, positions, valid, rows=None):
        """{group: (page ids, offsets)} of the tokens at `positions`: where a
        step writes each new row. `rows` None: positions are (S, Bq) and
        tables' rows the sequences; else (T,) with each token's table row.
        Padding tokens get the group's page count: out of bounds HIGH, which
        mode="drop" discards. (-1 would NOT be dropped: JAX wraps negative
        indices before the bounds check, so padded rows would silently
        corrupt the pool's last page.)"""
        logical = positions // self.block_size
        out = {}
        for g in self.groups:
            if g.slots:
                continue
            table = tables[g.name]
            width = table.shape[1]
            column = (logical % width if g.window is not None
                      else jnp.clip(logical, 0, width - 1))
            ids = (jnp.take_along_axis(table, column, axis=1) if rows is None
                   else table[rows, column])
            out[g.name] = (jnp.where(valid, ids, self.group_pages[g.name]),
                           positions % self.block_size)
        return out

    def _run_layers(self, ctx: StepContext, params, cache, x, lora,
                    narrow=None):
        """The block's segments, each one scan of its layer step over the
        stacked parameters; the cache's pools ride in the carry. Before
        segment `block.narrow_at`, where the block has one, the rows narrow
        to one a sequence: `narrow(x) -> (x, ctx)`. Returns (x, cache, aux):
        aux None, or for a block that routes {"routing": (routed layers, ...,
        top_k), "counts": (3,)}, and whatever else its layers hand out by
        name (a layer's aux may be a dict: "routing" and "counts" as above,
        any other entry stacked over the layers that give it)."""
        names = [a.name for a in self.cache_arrays]
        pools = tuple(cache[n] for n in names)
        routing, counts, outputs = [], 0, {}
        narrow_at = getattr(self.block, "narrow_at", None)
        for at, (kind, stacked, first, apart) in enumerate(
                self.block.segments(params)):
            n = jax.tree.leaves(stacked)[0].shape[0]
            if at == narrow_at:
                x, ctx = narrow(x)

            def layer_step(carry, scanned, kind=kind):
                lp, li, ll = scanned
                x, pools, aux = self.block.layer_step(
                    ctx, kind, carry[0], carry[1:], lp, li, ll)
                return (x,) + tuple(pools), aux

            if apart is None:
                (x, *pools), aux = jax.lax.scan(
                    layer_step, (x,) + tuple(pools),
                    (stacked, jnp.arange(first, first + n), lora))
            else:   # a Python loop: layer j also takes its own apart[j]
                auxes = []
                for j, own in enumerate(apart):
                    lp = {**jax.tree.map(lambda a: a[j], stacked), **own}
                    (x, *pools), aux = layer_step(
                        (x,) + tuple(pools), (lp, first + j, lora))
                    auxes.append(aux)
                aux = jax.tree.map(lambda *a: jnp.stack(a), *auxes)
            if isinstance(aux, dict):
                for name in aux.keys() - {"routing", "counts"}:
                    outputs.setdefault(name, []).append(aux[name])
                aux = ((aux["routing"], aux["counts"]) if "routing" in aux
                       else None)
            if aux is not None:
                routing.append(aux[0])
                counts = counts + aux[1].sum(axis=0)
        finish = getattr(self.block, "finish", None)
        x = (finish(x, params) if finish is not None else rms_norm(
            x, params["final_norm"],
            self.config.norm_eps).astype(self.config.dtype))
        aux = ({"routing": jnp.concatenate(routing), "counts": counts}
               if routing else {})
        for name, parts in outputs.items():
            aux[name] = jax.tree.map(lambda *a: jnp.concatenate(a), *parts)
        return x, dict(zip(names, pools)), aux or None

    # ---- the unified step ------------------------------------------------

    def _backbone(self, params, cache, tokens, q_positions, kv_lens, q_lens,
                  block_tables, lora=None, lora_idx=None):
        """tokens: (S, Bq) new tokens (padded); q_positions: (S,) absolute
        position of tokens[s, 0]; kv_lens: (S,) context length AFTER this
        step's tokens; q_lens: (S,) real token count per row (0 for padding
        sequences); block_tables: {group: (S, columns)} (`_tables`);
        lora/lora_idx: slot stacks + per-sequence adapter slot
        (llm/lora.py) when multi-LoRA is active. Returns (final hidden
        states (S, Bq, d), cache, aux); the heads below pay the vocab matmul
        only where they need it."""
        config = self.config
        S, Bq = tokens.shape
        block_tables = self._tables(block_tables)
        x = params["embed"][tokens].astype(
            self.block.residual_dtype)                          # (S, Bq, d)
        positions = q_positions[:, None] + jnp.arange(Bq)[None, :]
        valid = jnp.arange(Bq)[None, :] < q_lens[:, None]
        slots = self._page_slots(block_tables, positions, valid)
        use_lora = bool(lora)   # static: {}/None compiles the base program

        def proj(h, lp, ll, name):
            out = h @ lp[name]
            if use_lora and name in ll:
                from ray_tpu.llm.lora import apply_lora

                out = out + apply_lora(h, ll[name]["a"], ll[name]["b"],
                                       lora_idx).astype(out.dtype)
            return out

        ctx = StepContext(
            rope_pos=jnp.clip(positions, 0, config.max_seq - 1), valid=valid,
            write=lambda pool, li, rows, group="all": pool_write_rows(
                pool, li, *slots[group], rows),
            attend=lambda q, *views, group="all", **kw: self._attend(
                self._attention[0], q, views, group, block_tables, kv_lens,
                q_positions, **kw),
            proj=proj)
        if self.state_group is not None:
            ctx.rows = RowSegments(
                seq=jnp.repeat(jnp.arange(S), Bq),
                local=jnp.tile(jnp.arange(Bq), S),
                starts=jnp.arange(S) * Bq, lens=q_lens,
                q_positions=q_positions,
                slots=block_tables[self.state_group][:, 0])

        def narrow(x):
            """Each sequence's last real row, (S, 1, ...), and its context."""
            last = jnp.maximum(q_lens - 1, 0)
            x = jax.tree.map(lambda a: jnp.take_along_axis(
                a, last[:, None, None], axis=1), x)
            at = q_positions + last
            return x, StepContext(
                rope_pos=jnp.clip(at, 0, config.max_seq - 1)[:, None],
                valid=(q_lens > 0)[:, None], write=None,
                attend=lambda q, *views, group="all", **kw: self._attend(
                    self._attention[0], q, views, group, block_tables,
                    kv_lens, at, **kw),
                proj=proj)

        return self._run_layers(ctx, params, cache, x,
                                lora if use_lora else {}, narrow)

    def _step(self, params, cache, tokens, q_positions, kv_lens, q_lens,
              block_tables, lora=None, lora_idx=None):
        """Standard head: only the last REAL position per sequence pays the
        vocab matmul. Returns (logits (S, vocab), cache, routing, outputs):
        the routing (routed layers, S, Bq, top_k) of a block that routes, else
        None; what else its layers hand out by name, else None."""
        x, cache, aux = self._backbone(params, cache, tokens, q_positions,
                                       kv_lens, q_lens, block_tables, lora,
                                       lora_idx)
        # A block that narrows hands back each sequence's last row alone.
        last = x[:, 0] if self._narrows else jnp.take_along_axis(
            x, jnp.maximum(q_lens - 1, 0)[:, None, None], axis=1)[:, 0]
        aux = aux or {}
        return (self._logits(params, last), cache, aux.get("routing"),
                {k: v for k, v in aux.items()
                 if k not in ("routing", "counts")} or None)

    def _logits(self, params, rows):
        """The head over `rows` (n, d): fp32 accumulation out of the matmul
        (not a post-hoc cast, which would keep bf16 rounding), since logits
        feed sampling/argmax decisions. A model without `lm_head` ties it to
        the embedding (rows . E^T, contracted where E lies: no transpose of
        the embedding is made)."""
        if "lm_head" in params:
            return jnp.matmul(rows,
                              params["lm_head"].astype(self.config.dtype),
                              preferred_element_type=jnp.float32)
        return jax.lax.dot_general(
            rows, params["embed"].astype(self.config.dtype),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    # ---- the unified RAGGED step (one launch per engine tick) ------------

    def _backbone_mixed(self, params, cache, tokens, q_positions, kv_lens,
                        cu_q_lens, block_tables, lora=None, lora_idx=None,
                        last_rows=None):
        """Token-major unified backbone: `tokens` is flat (T,) — sequence s
        owns rows [cu_q_lens[s], cu_q_lens[s+1]) and rows past cu_q_lens[S]
        are padding. q_positions[s] is the absolute position of s's FIRST
        query token; kv_lens[s] the context length AFTER this step's
        tokens. Embed / RoPE / KV-scatter run per token on (T, ...) shapes;
        attention is the ragged unified kernel — decode rows, spec-verify
        rows, and prefill chunk slices share ONE launch instead of one
        rectangular (S, Bq) launch per phase. Returns (hidden (T, d),
        cache, aux); for a block that narrows, hidden (S, d): of the flat
        rows `last_rows` (S,), the one row a sequence that passes the
        segments from `block.narrow_at` on."""
        config = self.config
        T = tokens.shape[0]
        S = kv_lens.shape[0]
        block_tables = self._tables(block_tables)
        seq = pa.token_seq_ids(cu_q_lens, T, S)              # (T,)
        local = jnp.arange(T) - cu_q_lens[seq]
        valid = jnp.arange(T) < cu_q_lens[S]
        positions = q_positions[seq] + local                 # (T,)
        x = params["embed"][tokens].astype(
            self.block.residual_dtype)                       # (T, d)
        slots = self._page_slots(block_tables, positions, valid, seq)
        use_lora = bool(lora)
        tok_lora = (lora_idx[seq] if use_lora and lora_idx is not None
                    else None)

        def proj(h, lp, ll, name):
            out = h @ lp[name]
            if use_lora and name in ll:
                from ray_tpu.llm.lora import apply_lora

                # apply_lora is (S, Bq, d)-shaped; flat rows ride as Bq=1
                # with a per-TOKEN slot index (sequences may differ).
                out = out + apply_lora(
                    h[:, None], ll[name]["a"], ll[name]["b"],
                    tok_lora)[:, 0].astype(out.dtype)
            return out

        ctx = StepContext(
            rope_pos=jnp.clip(positions, 0, config.max_seq - 1), valid=valid,
            write=lambda pool, li, rows, group="all": pool_write_rows(
                pool, li, *slots[group], rows),
            attend=lambda q, *views, group="all", **kw: self._attend(
                self._attention[1], q, views, group, block_tables, kv_lens,
                q_positions, cu_q_lens, **kw),
            proj=proj)
        lens = cu_q_lens[1:] - cu_q_lens[:-1]
        if self.state_group is not None:
            ctx.rows = RowSegments(
                seq=seq, local=local, starts=cu_q_lens[:-1], lens=lens,
                q_positions=q_positions,
                slots=block_tables[self.state_group][:, 0])

        def narrow(x):
            """Rows `last_rows`, one a sequence: a launch of S one-token
            rows, each at its own position over the same contexts."""
            x = jax.tree.map(lambda a: a[last_rows], x)
            at = positions[last_rows]
            return x, StepContext(
                rope_pos=jnp.clip(at, 0, config.max_seq - 1),
                valid=lens > 0, write=None,
                attend=lambda q, *views, group="all", **kw: self._attend(
                    self._attention[1], q, views, group, block_tables,
                    kv_lens, at, jnp.arange(S + 1, dtype=cu_q_lens.dtype),
                    **kw),
                proj=proj)

        return self._run_layers(ctx, params, cache, x,
                                lora if use_lora else {}, narrow)

    def _step_mixed(self, params, cache, tokens, prev_samples, token_src,
                    q_positions, kv_lens, cu_q_lens, block_tables, out_rows,
                    proposals, prop_lens, temps, top_ks, top_ps, seeds,
                    counters, lora=None, lora_idx=None):
        """Unified mixed step + on-device seeded acceptance sampling.

        prev_samples (S, W) / token_src (T,): the `samples` of the step
        dispatched before this one, as they lie on the device, and for every
        flat token the row of them it is taken from (slot 0: a row without a
        draft), or -1 for "take `tokens`". The engine composes a step while
        the one before it runs (engine.py, "One step of lookahead"): a decode
        row's input token is then not on the host yet.

        out_rows (S, W): flat hidden-state rows whose logits sequence s
        reads (decode: its single row, W times; spec verify: the rows
        after proposal positions 0..k; prefill finals: the chunk's last
        row). proposals (S, W) / prop_lens (S,): the deterministic draft
        under test (length 0 for plain rows). Row (s, j) carries generation
        counter counters[s] + j — the SAME absolute-index keying as the
        plain sampler, so a row with no proposal draws exactly what a
        speculation-off engine draws.

        Returns (accept (S, W) bool, samples (S, W) int32, cache, counts),
        counts the (3,) expert-row counts of a block that routes, else None:
          accept[s, j]  — proposal j passes (greedy rows: argmax matches;
                          temp>0 rows: u < p(proposal), the rejection test
                          against the FILTERED target distribution — the
                          draft is a point mass, so q(proposal) = 1)
          samples[s, j] — the token to commit when j is the first rejected
                          slot (temp>0: a residual sample with the proposal
                          masked out) or the bonus slot (the full filtered
                          distribution under the plain sampler's key).
        The host commits proposals[s, :n_acc] + [samples[s, n_acc]]."""
        tokens = jnp.where(token_src >= 0,
                           prev_samples[jnp.maximum(token_src, 0), 0], tokens)
        S, W = out_rows.shape
        x, cache, aux = self._backbone_mixed(
            params, cache, tokens, q_positions, kv_lens, cu_q_lens,
            block_tables, lora, lora_idx, out_rows[:, 0])
        # (S*W, d); a block that narrows hands back row out_rows[s, 0] of
        # every sequence (W is 1 there: `refuse_drafts`).
        rows = x if self._narrows else x[out_rows.reshape(-1)]
        logits = self._logits(params, rows)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

        def rep(a):
            return jnp.repeat(a, W)

        scaled = self._filter_sampled(logits, rep(temps), rep(top_ks),
                                      rep(top_ps))
        j_idx = jnp.tile(jnp.arange(W), S)
        n = rep(counters) + j_idx                            # (S*W,)
        is_bonus = j_idx >= rep(prop_lens)
        prop_flat = proposals.reshape(-1)

        def one_row(seed, counter, lg, prop):
            base = jax.random.fold_in(jax.random.key(seed), counter)
            # `full` is keyed on (seed, absolute index) alone: bonus slots
            # and spec-off rows reproduce the non-speculative stream bit
            # for bit. u / resid fold in fixed subkeys so a replayed request
            # re-derives the identical accept/reject trajectory (failover +
            # migration determinism).
            full = jax.random.categorical(base, lg)
            u = jax.random.uniform(jax.random.fold_in(base, 101))
            resid = jax.random.categorical(
                jax.random.fold_in(base, 102),
                lg.at[prop].set(self.NEG_INF))
            return full, u, resid, jax.nn.softmax(lg)[prop]

        full, u, resid, p_prop = jax.vmap(one_row)(
            rep(seeds), n, scaled, prop_flat)
        grow = rep(temps) <= 0.0
        accept = jnp.where(grow, greedy == prop_flat, u < p_prop)
        samples = jnp.where(
            grow, greedy,
            jnp.where(is_bonus, full.astype(jnp.int32),
                      resid.astype(jnp.int32)))
        return (accept.reshape(S, W), samples.reshape(S, W), cache,
                aux.get("counts") if aux else None)

    def _step_mixed_logits(self, params, cache, tokens, q_positions, kv_lens,
                           cu_q_lens, block_tables, out_rows, lora=None,
                           lora_idx=None):
        """The mixed step with a logits head, for ticks the host samples (a
        request with a repetition penalty): out_rows (S,) names ONE flat
        hidden-state row a sequence. Returns (logits (S, vocab) float32,
        cache, counts)."""
        x, cache, aux = self._backbone_mixed(
            params, cache, tokens, q_positions, kv_lens, cu_q_lens,
            block_tables, lora, lora_idx, out_rows)
        logits = self._logits(params, x if self._narrows else x[out_rows])
        return logits, cache, aux.get("counts") if aux else None

    def _tables(self, block_tables,
                owns_pool: bool = False) -> Dict[str, Any]:
        """{group: table} from what a caller gave: that dict (the engine's,
        one table a group), or ONE array, the "all" group's. For a block with
        a window group one array is taken only from a caller that owns the
        pool (`step`): the group's table is then laid here, row s holding the
        ring's columns at the top of that pool, page `pages - 1 - (s * columns
        + column)`, a pure function of row and column, so a caller that steps
        the same rows again (the benchmark's check) finds what it wrote.
        Anywhere else those pages may be a live sequence's."""
        if isinstance(block_tables, dict):
            return block_tables
        tables = {"all": block_tables}
        if len(self.groups) > 1 and not owns_pool:
            raise ValueError(
                "a block with more than one layer group takes one block "
                "table a group ({group: table}); only step(), whose caller "
                "owns the pool, lays the others itself")
        S = np.shape(block_tables)[0]
        for g in self.groups[1:]:
            if g.slots:     # row s: slot s
                tables[g.name] = np.arange(S, dtype=np.int32)[:, None]
                continue
            width = self.table_widths[g.name]
            if S * width > self.group_pages[g.name]:
                raise ValueError(
                    f"{S} rows of {width} pages do not fit the {g.name} "
                    f"group's {self.group_pages[g.name]} pages")
            tables[g.name] = (self.group_pages[g.name] - 1 - np.arange(
                S * width, dtype=np.int32)).reshape(S, width)
        return tables

    def step_mixed(self, tokens, q_positions, kv_lens, cu_q_lens,
                   block_tables, out_rows, proposals, prop_lens, temps,
                   top_ks, top_ps, seeds, counters, lora_idx=None,
                   prev_samples=None, token_src=None):
        """One unified ragged launch for a mixed decode / spec-verify /
        prefill batch, bucketed on total token count T rather than the
        (batch, Bq) product. `block_tables`: see `_tables`. `prev_samples` /
        `token_src`: an earlier call's `samples` (a device array, not
        fetched) and the flat tokens to take from it (`_step_mixed`); left
        out, every token is `tokens`'. Returns (accept (S, W) bool, samples
        (S, W) int32) as host numpy-convertible arrays."""
        block_tables = self._tables(block_tables)
        self._note_shapes("mixed", tokens, out_rows, block_tables["all"])
        lora, idx = self._lora_args(lora_idx, len(kv_lens))
        if token_src is None:
            token_src = np.full(np.shape(tokens), -1, np.int32)
        if prev_samples is None:
            prev_samples = self._no_samples(np.shape(out_rows))
        accept, samples, self.cache, self.last_expert_counts = \
            self._step_mixed_jit(
            self.params, self.cache, tokens, prev_samples, token_src,
            q_positions, kv_lens, cu_q_lens, block_tables, out_rows,
            proposals, prop_lens, temps, top_ks, top_ps, seeds, counters,
            lora, idx)
        if self._noted is not None:
            self._note_compiled()
        return accept, samples

    def _no_samples(self, shape):
        """What `step_mixed` gives the program for `prev_samples` when no
        step ran before: zeros placed as a step's own `samples` come back
        (replicated over the mesh), so that both are one program's input."""
        if self.mesh is None:
            return np.zeros(shape, np.int32)
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(np.zeros(shape, np.int32),
                              NamedSharding(self.mesh, PartitionSpec()))

    def step_mixed_logits(self, tokens, q_positions, kv_lens, cu_q_lens,
                          block_tables, out_rows, lora_idx=None):
        """step_mixed's launch with the logits head: returns float32 logits
        (S, vocab) of rows `out_rows` (S,) for the host's sampler."""
        block_tables = self._tables(block_tables)
        self._note_shapes("mixed_logits", tokens, block_tables["all"])
        lora, idx = self._lora_args(lora_idx, len(kv_lens))
        logits, self.cache, self.last_expert_counts = \
            self._step_mixed_logits_jit(
            self.params, self.cache, tokens, q_positions, kv_lens,
            cu_q_lens, block_tables, out_rows, lora, idx)
        if self._noted is not None:
            self._note_compiled()
        return logits

    def warm_mixed(self, T: int, S: int, W: int):
        """Precompile the mixed-step program for token bucket T without
        touching cache state: cu_q_lens all zero makes every row padding,
        so every KV write drops and the outputs are ignored."""
        import numpy as np

        z = lambda *s: np.zeros(s, np.int32)
        self.step_mixed(
            z(T), z(S), z(S), z(S + 1), self.zero_tables(S),
            z(S, W), z(S, W), z(S), np.zeros(S, np.float32), z(S),
            np.ones(S, np.float32), z(S), z(S))

    def warm_mixed_logits(self, T: int, S: int):
        """warm_mixed for the logits head."""
        z = lambda *s: np.zeros(s, np.int32)
        self.step_mixed_logits(z(T), z(S), z(S), z(S + 1),
                               self.zero_tables(S), z(S))

    def zero_tables(self, S: int) -> Dict[str, np.ndarray]:
        """One zeroed block table a layer group, for `S` rows: what the
        engine fills in a tick."""
        return {name: np.zeros((S, width), dtype=np.int32)
                for name, width in self.table_widths.items()}

    def _lora_args(self, lora_idx, batch: int):
        if self.lora is None:
            return {}, None
        idx = (jnp.zeros(batch, dtype=jnp.int32) if lora_idx is None
               else jnp.asarray(lora_idx, dtype=jnp.int32))
        return self.lora.lora_pytree(), idx

    def step(self, tokens, q_positions, kv_lens, q_lens, block_tables,
             lora_idx=None):
        """Run one bucketed step; inputs are host arrays already padded to a
        (batch, Bq) bucket by the caller, who OWNS THE POOL meanwhile (no
        engine tick runs between its steps): given one table, a window
        group's is laid here (`_tables`). Returns logits (S, vocab)."""
        block_tables = self._tables(block_tables, owns_pool=True)
        self._note_shapes("step", tokens, block_tables["all"])
        lora, idx = self._lora_args(lora_idx, len(tokens))
        (logits, self.cache, self.last_routing,
         self.last_layer_outputs) = self._step_jit(
            self.params, self.cache, tokens, q_positions, kv_lens, q_lens,
            block_tables, lora, idx)
        if self._noted is not None:
            self._note_compiled()
        return logits

    # ---- on-device sampling ---------------------------------------------

    NEG_INF = -1e30

    def _filter_logits(self, logits, temps, top_ks, top_ps):
        """Temperature / top-k / top-p filtering of the mixed step's sampler.
        Returns filtered scaled logits; sampling from softmax of them is the
        target distribution. Row by row:

        1. `scaled = logits / max(T, 1e-6)`.
        2. top-k: `kth` is the k-th largest of the row's `scaled` (k =
           `top_k`, the whole row where it is 0 or past the vocabulary);
           `scaled >= kth` stays, ties at the threshold all of them, the rest
           becomes `NEG_INF`.
        3. top-p: over the softmax of the survivors, `cutoff` is the smallest
           probability whose mass STRICTLY ABOVE it is `< top_p` (the crossing
           token included, vLLM semantics; every tie with a kept token stays;
           `top_p >= 1` keeps all); `probs >= cutoff` stays.

        A kept value is `scaled`'s own float. Both thresholds are order
        statistics and are found by SELECTION (`_kth_largest`, `_top_p_floor`:
        compare-and-reduce passes over the values as they lie, no sort), and
        only where a row asks: no top-k passes in a call whose rows all have
        `top_k == 0`, no softmax and no top-p passes where all have
        `top_p >= 1`."""
        V = logits.shape[-1]
        scaled = logits / jnp.maximum(temps[:, None], 1e-6)
        asks_k, asks_p = top_ks > 0, top_ps < 1.0
        no_floor = jnp.full(scaled.shape[:1], -jnp.inf, scaled.dtype)
        kth = jax.lax.cond(
            jnp.any(asks_k),
            lambda: jnp.where(asks_k, _kth_largest(
                scaled, jnp.clip(top_ks, 1, V)), -jnp.inf),
            lambda: no_floor)

        def nucleus():
            kept = jnp.where(scaled >= kth[:, None], scaled, self.NEG_INF)
            return jnp.where(asks_p, _top_p_floor(kept, top_ps), -jnp.inf)

        # The nucleus' lowest logit: at or above `kth` where a row has one.
        floor = jax.lax.cond(jnp.any(asks_p), nucleus, lambda: no_floor)
        return jnp.where(scaled >= jnp.maximum(kth, floor)[:, None], scaled,
                         self.NEG_INF)

    def _filter_sampled(self, logits, temps, top_ks, top_ps):
        """`_filter_logits` for the rows that sample (temperature > 0), which
        are the only ones whose filtered logits are read (a greedy row commits
        its argmax). Most rows of most ticks are greedy, and the filter's
        passes read every row they are given: up to a quarter of the rows (at
        least 8) are gathered, filtered by the same function and put back; a
        step with more sampling rows than that filters every row. A row's
        result is the same either way: the filter is row-wise. What a greedy
        row holds in `top_k` / `top_p` asks for no pass."""
        n = logits.shape[0]
        cap = min(n, max(8, n // 4))
        need = temps > 0.0
        count = jnp.sum(need)
        top_ks = jnp.where(need, top_ks, 0)
        top_ps = jnp.where(need, top_ps, 1.0)

        def few(_):
            idx = jnp.nonzero(need, size=cap, fill_value=0)[0]
            sub = self._filter_logits(logits[idx], temps[idx], top_ks[idx],
                                      top_ps[idx])
            to = jnp.where(jnp.arange(cap) < count, idx, n)     # fill: drop
            return logits.at[to].set(sub, mode="drop")

        def every(_):
            return self._filter_logits(logits, temps, top_ks, top_ps)

        return jax.lax.cond(count <= cap, few, every, None)

    # ---- disaggregated KV handoff (llm/disagg.py) -----------------------

    def gather_pages(self, block_ids: Sequence[int]) -> tuple:
        """Fetch the pages backing `block_ids` as host arrays in the wire
        view: one array for each of the cache spec's, in its order (K and V,
        each (n_layers, n_kv_heads, n_pages, block_size, head_dim), for the
        K/V block) — the export side of the prefill->decode handoff. One
        device-side gather (and a transpose of the n pages it moved) per
        array; the host copies are the raw buffers the zero-pickle framing
        streams. Callers carry the tuple whole (`wire_*` above)."""
        self.require_one_group("gather_pages")
        ids = jnp.asarray(list(block_ids), dtype=jnp.int32)
        return tuple(np.asarray(a.to_wire(self.cache[a.name], ids))
                     for a in self.cache_arrays)

    def require_one_group(self, what: str) -> None:
        """Pages travel (the wire view: spills, adoption, export, the host
        and cluster tiers, disaggregation) for a block of one layer group
        only: one list of page ids names a sequence's cache there."""
        if len(self.groups) > 1:
            refused = ", ".join(
                f"the {g.name!r} group's {'slots' if g.slots else 'pages'}"
                for g in self.groups[1:])
            raise ValueError(
                f"{what}: not supported for a block with layer groups "
                f"{[g.name for g in self.groups]}: {refused} do not travel "
                "(ROADMAP Queue 2)")

    def gather_pages_async(self, block_ids: Sequence[int]) -> tuple:
        """gather_pages without the wait, for the engine's eviction spills:
        ONE program reads pages `block_ids` of every array of the cache spec
        into a staging result in the wire view, the copies to the host are
        started, and the device arrays are returned at once (np.asarray of
        each, on whatever thread, completes the copy). The device runs
        programs in dispatch order, so a step program dispatched AFTER this
        call may overwrite the pages: they are read intact, with no
        synchronisation. One program for each len(block_ids): callers pad
        to a short ladder and warm it (LLMEngine.warmup)."""
        self.require_one_group("gather_pages_async")
        ids = np.asarray(list(block_ids), dtype=np.int32)
        self._note_shapes("gather", ids)
        staged = self._gather_jit(self.cache, ids)
        if self._noted is not None:
            self._note_compiled()
        for arr in staged:
            arr.copy_to_host_async()
        return staged

    def scatter_pages(self, block_ids: Sequence[int], *pages):
        """Write adopted pages (gather_pages' tuple) into this runner's
        pools at `block_ids` — the import side of the handoff."""
        self.require_one_group("scatter_pages")
        if len(pages) != len(self.cache_arrays):
            raise ValueError(
                f"{len(pages)} page arrays for a cache of "
                f"{[a.name for a in self.cache_arrays]}")
        ids = jnp.asarray(list(block_ids), dtype=jnp.int32)
        for a, arr in zip(self.cache_arrays, pages):
            self.cache[a.name] = a.from_wire(self.cache[a.name], ids, arr)

    def copy_state(self, src: int, dst: int) -> None:
        """Slot `src` of the state group over slot `dst`, on the device and
        in dispatch order: after the steps dispatched before this call,
        before those dispatched after it. One program whatever the slots."""
        self.cache = self._copy_state_jit(self.cache, np.int32(src),
                                          np.int32(dst))

    def batch_bucket(self, n: int) -> int:
        return _bucket(n, self.BATCH_BUCKETS)

    def chunk_buckets(self) -> list:
        """The static prefill-chunk bucket ladder: powers of two from 8 up
        to (and always including) chunk_size. Single source of truth for
        runtime bucketing AND warmup precompilation — a diverging copy
        means some runtime bucket never gets warmed."""
        buckets, b = [], 8
        while b < self.chunk_size:
            buckets.append(b)
            b *= 2
        buckets.append(self.chunk_size)
        return buckets

    def chunk_bucket(self, n: int) -> int:
        return _bucket(n, self.chunk_buckets())
