"""The grouped product of a routed layer's held experts as a WEIGHT STREAM:
`jax.lax.ragged_dot`'s contract by a Pallas kernel, for groups of a few rows
(a serving tick's: 1-40 pairs an expert) over experts of any size.

  a      (P, K)     the pairs' rows, sorted by held expert, group g's rows
                    the `sizes[g]` behind group g - 1's
  w      (E, K, N)  the held experts' matrices, in a's dtype
  sizes  (E,)       int32, DATA (scalar prefetch), never a shape
  ->     (P, N)     float32: row r of group g is a[r] @ w[g], the products
                    accumulated in float32; rows behind the last group ZERO

A serving tick is bound by the weights it reads, so the kernel walks VISITS:
one (expert, row tile) pair for every tile of `row_tile` rows that a
NON-EMPTY group touches, in the order of the rows. An expert without a row
has no visit and costs no DMA. The grid is (visits, K / k_tile); a step's
blocks are `a[tile, k]` (row_tile, k_tile), `w[expert, k]` (k_tile, N: rows
of a matrix that lie side by side, one contiguous piece), and the output
tile (row_tile, N), all indexed through the plan's scalar-prefetch arrays, so
Pallas asks for step i + 1's blocks (the next expert's first piece among
them) before it multiplies step i's, and fetches nothing anew where the
index stays (a group over two tiles reads its expert once). Where one
expert's matrix fits the budget twice, `k_tile` is K and a visit is one step.

Group offsets fall on any row and Mosaic reads a tile whole, so a visit
multiplies the ALIGNED tile its group reaches into and keeps the group's rows
by a mask; the tile's other rows keep what earlier visits left (the output
block stays in VMEM while consecutive visits name it) or zero on its first
visit. Tiles behind the last group get a visit of their own that multiplies
nothing and writes zeros, and what is left of the static grid repeats the
last step's indices and does nothing.

`product` is what `models/expert_share.py::held_expert_ffn` takes its `dot`
from: this kernel on a TPU, `jax.lax.ragged_dot` off it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_tag

# What the kernel's blocks may take of VMEM (`grouped_vmem_bytes` is the
# reckoning, and what Mosaic allocates: at DeepSeek-V2's down-projection it
# refused 17.75 MiB, the reckoning's figure, against the v5e's scoped default
# of 16 MiB; PERF.md section 6, PR 53).
GROUPED_VMEM_BUDGET = 14 << 20
LANES = 128
# Rows a visit. Swept on the v5e at the five routed configurations' shapes
# over {16, 32, 64, 80, 128} (PERF.md section 6, PR 53): a visit's product is
# bound by the weights the MXU latches, not by its rows, so more rows cost
# nothing up to here; fewer make a group cross a tile border more often, and
# where K is walked in pieces the second visit reads its expert AGAIN
# (DeepSeek-V2's 5120 x 1536 on a decode tick: 0.551 / 0.529 / 0.508 ms at
# 16 / 32 / 64); 128 gained nothing over 64 and takes twice the output's VMEM.
ROW_TILE = 64


class GroupedSizes(NamedTuple):
    row_tile: int       # rows of `a` and of the output a visit
    k_tile: int         # rows of an expert's matrix a step; divides K


def _sublanes(itemsize: int) -> int:
    """Rows of a whole tile of the operand's dtype (float32 8, bfloat16 16)."""
    return 8 * 4 // itemsize


def grouped_vmem_bytes(n: int, itemsize: int, row_tile: int, k_tile: int,
                       k: int) -> int:
    """VMEM the kernel's blocks take: `w`, `a` and the output double-buffered
    by the pipeline, the float32 accumulator where K is walked in pieces."""
    acc = row_tile * n * 4 if k_tile < k else 0
    return (2 * k_tile * n * itemsize + 2 * row_tile * k_tile * itemsize
            + 2 * row_tile * n * 4 + acc)


def grouped_sizes(rows: int, k: int, n: int,
                  itemsize: int = 2) -> GroupedSizes:
    """The kernel's tile sizes for `rows` pairs over experts of (k, n), under
    GROUPED_VMEM_BUDGET: static facts of the shapes.

    `row_tile`: ROW_TILE, or all the rows in whole sublane tiles of the dtype
    where there are fewer. `k_tile`: K where an expert's matrix fits twice
    (Nemotron-3-Super's 5.5 MB, Kimi-Linear's 4.7 MB: a visit is one step),
    else the largest multiple of 128 lanes of `a` that divides K and fits."""
    sub = _sublanes(itemsize)
    row_tile = min(ROW_TILE, sub * -(-rows // sub))
    pieces = [k] + [LANES * d for d in range(k // LANES - 1, 0, -1)
                    if k % LANES == 0 and (k // LANES) % d == 0]
    for k_tile in pieces:
        if grouped_vmem_bytes(n, itemsize, row_tile, k_tile,
                              k) <= GROUPED_VMEM_BUDGET:
            break
    return GroupedSizes(row_tile, k_tile)


def visit_plan(sizes: jax.Array, rows: int, row_tile: int):
    """The kernel's walk over `sizes` (E,): six int32 arrays of one entry a
    grid step, `min(E, rows) + tiles` of them (a static bound: every
    non-empty group adds a visit, every tile border inside the rows one more).

      expert, lo, hi   the visit's expert and its group's rows [lo, hi)
      tile             the output tile it writes
      a_tile           the tile of `a` it reads
      first            1 where it is the first to write its output tile

    Steps behind the visits: one for every tile that no group reaches (lo ==
    hi, first 1: zeros), then repeats of the last step (lo == hi, first 0:
    nothing). Both keep the last visit's expert and `a` tile, so neither
    fetches."""
    E = sizes.shape[0]
    tiles = -(-rows // row_tile)
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    total = ends[-1]
    first_tile = starts // row_tile
    reach = jnp.where(sizes > 0, (ends - 1) // row_tile - first_tile + 1, 0)
    visit_ends = jnp.cumsum(reach)
    visits = visit_ends[-1]
    v = jnp.arange(min(E, rows) + tiles, dtype=jnp.int32)
    # The group of visit v: empty groups share their successor's end and are
    # stepped over.
    g = jnp.minimum(jnp.sum(visit_ends[None, :] <= v[:, None], axis=1), E - 1)
    at = first_tile[g] + v - (visit_ends[g] - reach[g])
    real = v < visits
    reached = -(-total // row_tile)                 # tiles some group touches
    zeros = ~real & (v - visits < tiles - reached)
    tile = jnp.where(real, at, jnp.where(zeros, reached + v - visits,
                                         tiles - 1))
    last_expert = jnp.max(jnp.where(sizes > 0, jnp.arange(E), 0))
    last_tile = jnp.maximum(total - 1, 0) // row_tile
    fresh = jnp.concatenate([jnp.ones((1,), bool), at[1:] != at[:-1]])
    i32 = lambda x: x.astype(jnp.int32)
    return (i32(jnp.where(real, g, last_expert)),
            i32(jnp.where(real, starts[g], 0)),
            i32(jnp.where(real, ends[g], 0)),
            i32(tile), i32(jnp.where(real, at, last_tile)),
            i32(jnp.where(real, fresh, zeros)))


def _grouped_kernel(expert_ref, lo_ref, hi_ref, tile_ref, a_tile_ref,
                    first_ref, a_ref, w_ref, o_ref, *acc, row_tile: int,
                    steps: int):
    from jax.experimental import pallas as pl

    v, kk = pl.program_id(0), pl.program_id(1)
    lo, hi, first = lo_ref[v], hi_ref[v], first_ref[v]

    def keep(product):
        """The group's rows of `product`; the tile's others as they were."""
        row = tile_ref[v] * row_tile + jax.lax.broadcasted_iota(
            jnp.int32, o_ref.shape, 0)
        before = jnp.where(first == 1, 0.0, o_ref[...])
        o_ref[...] = jnp.where((row >= lo) & (row < hi), product, before)

    @pl.when(hi > lo)
    def _visit():
        product = jnp.dot(a_ref[...], w_ref[...],
                          preferred_element_type=jnp.float32)
        if steps == 1:
            keep(product)
            return
        acc_ref, = acc

        @pl.when(kk == 0)
        def _():
            acc_ref[...] = product

        @pl.when(kk > 0)
        def _():
            acc_ref[...] += product

        @pl.when(kk == steps - 1)
        def _():
            keep(acc_ref[...])

    @pl.when((hi == lo) & (first == 1) & (kk == steps - 1))
    def _zeros():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("row_tile", "k_tile",
                                             "interpret"))
def grouped_dot_call(a, w, expert, lo, hi, tile, a_tile, first, *,
                     row_tile: int, k_tile: int, interpret: bool):
    """The kernel's launch over a `visit_plan`. Jitted under a name of its
    own so that a profile's events read `grouped_dot_call.<n>` (as `ssd_call`,
    `kda_call`): not `tpu_custom_call*`, which the readers of the paged
    kernels sum."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    P, K = a.shape
    N = w.shape[2]
    steps = K // k_tile
    assert steps * k_tile == K, (K, k_tile)

    # A step that multiplies nothing names the blocks the last one left.
    def piece(kk, lo, hi, v):
        return jnp.where(hi[v] > lo[v], kk, steps - 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(expert.shape[0], steps),
        in_specs=[
            pl.BlockSpec((row_tile, k_tile),
                         lambda v, kk, e, lo, hi, t, at, f: (
                             at[v], piece(kk, lo, hi, v))),
            pl.BlockSpec((None, k_tile, N),
                         lambda v, kk, e, lo, hi, t, at, f: (
                             e[v], piece(kk, lo, hi, v), 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, N),
                               lambda v, kk, e, lo, hi, t, at, f: (t[v], 0)),
        scratch_shapes=([pltpu.VMEM((row_tile, N), jnp.float32)]
                        if steps > 1 else []),
    )
    return pl.pallas_call(
        functools.partial(_grouped_kernel, row_tile=row_tile, steps=steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((P, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        **kernel_tag("grouped_dot"),
    )(expert, lo, hi, tile, a_tile, first, a, w)


def grouped_dot(a, w, sizes, *, plan=None, tiles: Optional[GroupedSizes] = None,
                interpret: Optional[bool] = None):
    """`jax.lax.ragged_dot(a, w, sizes, preferred_element_type=float32)` by
    the kernel. `plan` is `visit_plan(sizes, rows, row_tile)` where a caller
    has it already (a layer's products share one); `tiles` overrides
    `grouped_sizes` (tests, sweeps)."""
    if interpret is None:
        from ray_tpu.ops import is_tpu_backend

        interpret = not is_tpu_backend()
    P, K = a.shape
    if tiles is None:
        tiles = grouped_sizes(P, K, w.shape[2], jnp.dtype(a.dtype).itemsize)
    if plan is None:
        plan = visit_plan(sizes, P, tiles.row_tile)
    return grouped_dot_call(a, w, *plan,
                            row_tile=tiles.row_tile, k_tile=tiles.k_tile,
                            interpret=interpret)


def product(sizes: jax.Array):
    """`dot(a, w)` -> float32 for `models/expert_share.py::held_expert_ffn`:
    the grouped product of the sorted pairs over group `sizes`; a layer's
    products share one plan.

    ONE path on a TPU, this kernel, whatever the shapes: alone on the v5e
    (`chip_smoke.py --phase grouped_dot`, PERF.md section 6, PR 53) it read
    the met experts' weights at 690-745 GB/s at all five routed
    configurations' shapes, XLA's `ragged_dot` at 270-310 where an expert is
    4.7-5.5 MB (Nemotron-3-Super 1.92 -> 0.75 ms a product on a decode tick,
    Kimi-Linear 0.40 -> 0.15) and at 500-690 where it is 15-25 MB
    (DeepSeek-V2 0.548 -> 0.508, MiMo-V2-Flash 0.236 -> 0.162, GLM-5.2 0.203
    -> 0.139): XLA's own kernel is a Mosaic grouped product too, tiled 128 x
    512 x 128 at Nemotron's shapes (4,500 grid steps of 128 KB a product) and
    64 x 512 x 512 at DeepSeek-V2's. No shape was found where `ragged_dot`
    is the faster, so there is no rule to keep. Off a TPU the product stays
    `jax.lax.ragged_dot` (the interpreted kernel is the tests' to run)."""
    from ray_tpu.ops import is_tpu_backend

    if not is_tpu_backend():
        return lambda a, w: jax.lax.ragged_dot(
            a, w, sizes, preferred_element_type=jnp.float32)
    plans = {}

    def dot(a, w):
        tiles = grouped_sizes(a.shape[0], *w.shape[1:],
                              jnp.dtype(a.dtype).itemsize)
        if tiles.row_tile not in plans:
            plans[tiles.row_tile] = visit_plan(sizes, a.shape[0],
                                               tiles.row_tile)
        return grouped_dot(a, w, sizes, plan=plans[tiles.row_tile],
                           tiles=tiles)

    return dot
