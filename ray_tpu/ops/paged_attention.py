"""Ragged paged attention: the serving-decode hot op.

Reference analog: the paged-attention CUDA kernels inside vLLM, which the
reference repo only places (python/ray/llm/_internal/serve/deployments/llm/
vllm/vllm_engine.py:222). TPU-native design: one kernel serves BOTH decode
(one query token per sequence) and chunked prefill (a block of query tokens
per sequence) — "ragged" means each sequence in the batch has its own query
count and context length; shapes stay static (bucketed) and per-sequence
lengths arrive as scalar-prefetch operands.

Layouts:
  q:            (S, Bq, H, hd)  — rectangular: Bq query tokens per sequence
                                  (1 for decode, chunk size for prefill)
                (T, H, hd)      — token-major: sequence s owns rows
                                  [cu_q_lens[s], cu_q_lens[s+1])
  k/v pool:     (L, P, ps, K, hd) — the WHOLE pool as it lies on the device
                                  (llm/model_runner.py, "The KV pool's
                                  layout"): page-major, one token's (K, hd)
                                  minor, K = kv heads. The V pool may be
                                  narrower, (L, P, ps, K, vd): q and k share
                                  hd, the output is vd wide
                (L, P, ps, K * hd) — a ROW POOL: a token's row whole on
                                  the lanes (`kv_heads=K` says how many heads
                                  lie in it). A family declares rows when its
                                  kv heads fill no sublane tile of the dtype
                                  (bf16: 16 rows): XLA lays (10, 128) bf16
                                  out as 16 rows, 1.6 x the bytes in HBM and
                                  in every page DMA (compiled for a described
                                  v5e, PR 35), and a 5-D tile of (4, 256)
                                  takes 4 x its bytes in VMEM (PR 46); and
                                  when its pages do not travel (no wire view
                                  yet: llm/model_runner.py, `row_cache_array`).
                                  A kv head's part of a tile is then a static
                                  slice of whole lane tiles. A K row may be
                                  NARROWER than K x q's width: heads a whole
                                  number of lane tiles and a part of one wide
                                  lie SPLIT, the whole tiles side by side, then
                                  the rests packed several heads a lane tile,
                                  and q rides with zeros where its neighbours'
                                  rests lie (`KRow`, the one place that layout
                                  is stated; both kernels' callers and the jnp
                                  references take it from the operands' widths
                                  alone, `k_row_of`)
  layer:        () int32        — which layer's pages to read
  block_tables: (S, max_pages)  int32, logical page i of seq s -> pool page
  kv_lens:      (S,) int32      — context length INCLUDING this step's tokens
  q_positions:  (S,) int32      — absolute position of a sequence's first
                                  query token
  window:       static int|None — token i sees j with 0 <= i - j < window
                                  (None: every j <= i). With a window the
                                  block table is a RING: logical page p of
                                  seq s is block_tables[s, p % width], and a
                                  page behind every window is never looked up
  sink:         (H,) float32|None — a logit a head that joins the softmax's
                                  denominator and carries no value

`(K, hd)` minor is the layout of the WRITE (XLA's scatter of a step's new rows
has a `(K, hd)` update window and wants it minor; any other declaration is
re-laid out whole in every step program: PERF.md, PR 27), and the kernel
takes it as it lies: the pools are `memory_space=ANY` operands, the layer a
scalar-prefetch operand, so no layer's pages are sliced out, transposed or
copied on the way in. One DMA is one page of ALL kv heads, `(ps, K, hd)`
contiguous in HBM (32 KB at 16 x 8 x 128 bf16). Off the device, pages travel
in the wire view `(L, K, n, ps, hd)` (`ModelRunner.gather_pages` /
`scatter_pages`).

Two Pallas kernels, one a layout, behind both entry points: `_kv_kernel` over
5-D pools and `_kv_rows_kernel` over row pools. Their grid walks QUERY BLOCKS
of ONE sequence (`query_blocks`): a decode row is a block of one token (its H
rows), a prefill slice or a draft-verify row is cut into ceil(n / q_block)
blocks, and a block walks its sequence's pages up to its own last token
(`min(kv_len, q_pos + n)`: the causal exit), a tile of pages a loop step. The
block and tile sizes are `kv_sizes`' (a function of the shapes, under a stated
VMEM budget), and live nowhere else. A block reads its own tokens' rows out
of the flat q (one DMA, behind which the first tile's pages are started); a
page is one DMA a pool; two tile slots. Both products take the pool's dtype
with float32 accumulation; the scale is applied to the float32 scores; the
softmax state is float32. Cost is O(actual context), never O(max context);
with a window it is O(window): a block starts its walk at the page that holds
its first token's oldest visible position, and no DMA is started for a page
behind it. A sink logit is the softmax state's first column: the state starts
at (m, l, acc) = (sink, 1, 0) and not at (-inf, 0, 0).

`_kv_kernel` (5-D): all of a step's page DMAs are in flight before the first
wait and the next tile's are started before this tile's products. A tile
lands as `(tile, K, hd)`. A block of one token reads it as `(tile x K, hd)`:
column `j * K + kh` of the scores is context token j under kv head kh, its H
rows are multiplied against every column and the other heads' columns masked
(the same eight MXU weight tiles as eight per-head products of G rows each,
no strided read: 1.9 x faster than the per-head form over a 5-D tile on the
v5e). A block of many tokens turns the tile to `(K, tile, hd)` and multiplies
every kv head's q_block x G rows against that head's `(tile, hd)` in one
product batched over the heads (against a strided read a head: 7-24% faster
on a 128-token slice, and an eighth of the kernel's trace). Measured alone
(16 layers a call, Mistral-7B widths, PERF.md section 6, PR 32): 62% of the
v5e's 819 GB/s on 28 decode rows x ~700 tokens.

`_kv_rows_kernel` (rows, PR 46): a tile lands as `(tile, K x hd)`, whole lane
tiles, nothing padded; a kv head's part is read where it lies and multiplied
against that head's rows alone, so nothing of the tile is copied and no score
of another head's columns is computed (over split K rows, PR 64: a head's
whole lane tiles, plus the lane tile its rest shares, which a block of one
token multiplies ONCE for the heads that share it). A block of one token keeps its small
state in registers and folds the heads' scores together; a block of many keeps
the state in scratch and takes a head's rows a pass at a time. Its docstring
has the rest, PERF.md section 6 (PR 46) the numbers.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_tag
from ray_tpu.ops.attention import vma_of

NEG_INF = -1e30

# What a K/V kernel may take of the 16 MB of VMEM the compiler scopes to a
# kernel on the v5e, by `kv_vmem_bytes`' reckoning, which is never under the
# compiler's own count where that was looked for (tests/test_tpu_compile.py
# compiles the row kernel with this limit; an overrun of the 5-D kernel shows
# on the chip only: PR 33).
KV_VMEM_BUDGET = 15 * 2 ** 20
LANE = 128
# Row pools: the bytes of K and V a tile of a block of one token holds at
# least, and the context tokens a tile of a block of many holds at most
# (`kv_sizes` says where each was measured).
ROW_TILE_BYTES = 2 ** 20
ROW_TILE_TOKENS = 1024


# With a window: a block of many's tile is this part of the window's pages at
# most (`kv_sizes` says where it was swept).
WINDOW_TILES = 5


class KRow(NamedTuple):
    """How a token's K lies in a ROW POOL, for `heads` kv heads `whole + rest`
    lanes wide each; the only place this layout is stated (`k_row` chooses
    it; the write, `_kv_rows_kernel`, the jnp references and `kv_vmem_bytes`
    read it here).

    `rest` 0, the layout of every head of whole lane tiles (and of any head
    the split below does not fit): the heads side by side, `heads x whole`
    lanes, and q rides as wide as a head.

    `rest` > 0, a head a whole number of lane tiles AND a part of one (192 =
    128 + 64): first the heads' whole tiles side by side, `heads x whole`
    lanes, then their rests packed `LANE // rest` heads a lane tile, head kh's
    in part `kh % per` of tile `kh // per`. No lane of the row is padding. q
    rides `whole + LANE` wide: a head's first `whole` lanes against its kv
    head's whole tiles, its rest in the part of the last LANE lanes where its
    kv head's rest lies in the shared tile, zeros in the other parts, so the
    other heads' lanes of that tile meet zeros (exact). The zeros that a row
    padded to whole lane tiles a head held in HBM and in every page DMA ride
    in q instead, which a block fetches once. MiMo-V2-Flash's full layers: 4
    x 128 + 4 x 64 = 768 lanes (padded: 1,024)."""
    heads: int
    whole: int
    rest: int

    @property
    def per(self) -> int:
        """Heads whose rests share a lane tile."""
        return LANE // self.rest if self.rest else 1

    @property
    def lanes(self) -> int:
        return self.heads * (self.whole + self.rest)

    @property
    def q_width(self) -> int:
        return self.whole + (LANE if self.rest else 0)

    @property
    def parts(self):
        """[(q's first lane, lanes, heads)]: a head's scores are the sum over
        these of q's lanes against as many of the row's from `first_lane` on,
        which `heads` neighbouring kv heads have in common."""
        return [(0, self.whole, 1)] + (
            [(self.whole, LANE, self.per)] if self.rest else [])

    def first_lane(self, part: int, kh):
        """Where in the row kv head kh's lanes of `parts[part]` begin (`kh`
        may be traced): its whole tiles, or the tile its rest lies in."""
        if part == 0:
            return kh * self.whole
        return self.heads * self.whole + kh // self.per * LANE

    def lay(self, k):
        """k (..., heads, whole + rest) -> a token's row (..., lanes)."""
        *lead, K, _ = k.shape
        if not self.rest:
            return k.reshape(*lead, self.lanes)
        return jnp.concatenate(
            [k[..., :self.whole].reshape(*lead, K * self.whole),
             k[..., self.whole:].reshape(*lead, K * self.rest)], axis=-1)

    def heads_of(self, rows):
        """`lay`'s inverse: rows (..., lanes) -> (..., heads, whole + rest)."""
        *lead, _ = rows.shape
        if not self.rest:
            return rows.reshape(*lead, self.heads, self.whole)
        split = self.heads * self.whole
        return jnp.concatenate(
            [rows[..., :split].reshape(*lead, self.heads, self.whole),
             rows[..., split:].reshape(*lead, self.heads, self.rest)],
            axis=-1)

    def queries(self, q):
        """q (..., H, whole + rest) as it rides, (..., H, q_width): query
        head h is of kv head h // (H / heads)."""
        if not self.rest:
            return q
        H = q.shape[-2]
        part = (jnp.arange(H)[:, None] // (H // self.heads)) % self.per
        rest = q[..., self.whole:]
        return jnp.concatenate(
            [q[..., :self.whole]] + [jnp.where(part == p, rest, 0).astype(
                q.dtype) for p in range(self.per)], axis=-1)

    def as_queries_see(self, rows):
        """rows (..., lanes) -> (..., heads, q_width): what each kv head's
        queries are multiplied against, the shared tile whole (the jnp
        references' view of a row pool)."""
        *lead, _ = rows.shape
        if not self.rest:
            return rows.reshape(*lead, self.heads, -1)
        split = self.heads * self.whole
        shared = rows[..., split:].reshape(*lead, self.heads // self.per,
                                           LANE)
        return jnp.concatenate(
            [rows[..., :split].reshape(*lead, self.heads, self.whole),
             jnp.repeat(shared, self.per, axis=-2)], axis=-1)


def k_row(K: int, hd: int) -> KRow:
    """The layout of a K row of K kv heads `hd` lanes wide: split where a
    head is whole lane tiles and a rest that divides LANE, with the K rests
    filling whole lane tiles (`KRow`); else the heads side by side as they
    are (a head narrower than a lane tile rides the PAIR FORM,
    `pair_queries`, or the jnp references alone)."""
    whole, rest = hd // LANE * LANE, hd % LANE
    if whole and rest and LANE % rest == 0 and (K * rest) % LANE == 0:
        return KRow(K, whole, rest)
    return KRow(K, hd, 0)


def k_row_of(q_width: int, lanes: int, K: int) -> KRow:
    """The layout that operands of these widths state: rows of K heads as
    wide as q lie side by side; narrower ones lie split."""
    row = KRow(K, q_width, 0) if lanes == K * q_width else k_row(
        K, lanes // K)
    if (row.lanes, row.q_width) != (lanes, q_width):
        raise ValueError(f"a K row of {lanes} lanes for {K} kv heads under "
                         f"queries {q_width} wide is no layout of `k_row`")
    return row


class KVSizes(NamedTuple):
    """What `kv_sizes` chose for one shape; the only place these live."""
    q_block: int        # query tokens a block of many (a 128-token slice
    #                     reads its context 128 / q_block times)
    pages_one: int      # pool pages a loop step, a block of one token
    pages_many: int     # and a block of many (5-D pools: the same)
    rows: bool          # row pools (`_kv_rows_kernel`) or 5-D (`_kv_kernel`)
    k_lanes: Optional[Tuple[int, int]] = None   # row pools: a head's K as it
    #                     lies, lanes (whole, rest) (`KRow`; rest 0: side by
    #                     side, else the split layout)

    def describe(self) -> dict:
        """For whoever reads `engine.stats()["kv_kernels"]`."""
        return {"layout": "rows" if self.rows else "5d",
                "decode": "per_head" if self.rows else "masked_all_heads",
                "q_block": self.q_block,
                "pages": [self.pages_one, self.pages_many],
                **({} if self.k_lanes is None
                   else {"k_lanes": list(self.k_lanes)})}


# Rows of a kv head a pass of a step of `_kv_rows_kernel` takes (there: why).
PASS_ROWS = 256


def _pass_tokens(tokens: int, G: int) -> int:
    """Tokens of a block of `tokens` whose rows (x G a kv head) one pass of a
    step takes: the most whole tokens that divide the block and come to
    PASS_ROWS rows or fewer."""
    return max(d for d in range(1, tokens + 1)
               if tokens % d == 0 and (d == 1 or d * G <= PASS_ROWS))


def kv_vmem_bytes(H: int, K: int, hd: int, vd: int, ps: int, itemsize: int,
                  rows: bool, TQ: int, pages_one: int,
                  pages_many: int) -> int:
    """Bytes of VMEM a K/V kernel takes at these sizes, reckoned by hand: the
    two tile slots, the q scratch, the double-buffered output block, the
    float32 state (acc; m and l), the float32 scores and what the compiler
    takes of its own (half a MiB; a MiB beside row pools, where q is re-laid
    at any G).

    5-D pools (`_kv_kernel`): a tile's two minor dimensions are (K, width)
    and K pads to the dtype's sublane tile (bf16: 16), so four kv heads take
    4 x their bytes; m and l are a lane tile wide each; one copy of every
    head's scores at once. Held against the chip at 64 / 4 heads, 256 / 128
    lanes, bf16, 32 query tokens (PR 33): 16 pages run, 32 asked for 17.2
    MB; this reckoning 13.5 and 21.5 MiB.

    Row pools (`_kv_rows_kernel`): a tile is whole lane tiles; q lies twice
    (as fetched, and a kv head's rows together); m and l share one lane
    tile; three copies of the scores of ONE kv head's pass (PASS_ROWS rows).
    Held against the least `vmem_limit_bytes` the kernel compiles under for
    a described v5e, at 64 / 4 heads, 256 / 128 lanes, bf16 (query tokens,
    pages of one, of many; MiB): (32, 16, 16) 6.25, (32, 64, 16) 10.75, (32,
    64, 64) 13.25, (64, 64, 32) 15.5; at 8 kv heads (32, 16, 16) 8.25; in the
    pair form at 40 / 10 heads of 128 lanes (48, 16, 16) 6.75; this reckoning
    7.75, 12.25, 14.5, 17.5, 9.25 and 7.0: over the compiler's count by up to
    2 MiB, never under it. A K tile counts at the lanes it lies in (`k_row`)
    and q at the width it rides: at 64 / 4 heads of 192 / 128 lanes laid
    SPLIT (768-lane K rows under q of 256; PR 64) the compiler takes (32, 64,
    64) and (32, 32, 64) 12.5, (32, 16, 32) 8.0, at 8 kv heads with a window
    (32, 16, 16) 7.75; this reckoning 13.5, 13.5, 9.5 and 8.75."""
    G = H // K
    if rows:
        row = k_row(K, hd)
        tiles = (2 * max(pages_one, pages_many) * ps * (row.lanes + K * vd)
                 * itemsize)
        q = 2 * TQ * H * row.q_width * itemsize
        state = TQ * H * (vd + LANE) * 4
        scores = 3 * 4 * ps * max(
            G * pages_one, _pass_tokens(TQ, G) * G * pages_many)
    else:
        sublanes = 32 // itemsize
        heads = -(-K // sublanes) * sublanes
        tiles = 2 * pages_one * ps * heads * (hd + vd) * itemsize
        q = TQ * H * hd * itemsize
        state = TQ * H * (vd + 2 * LANE) * 4
        scores = 4 * ps * pages_one * H * max(K, TQ)
    out = (1 if rows else 2) * TQ * H * vd * itemsize
    return tiles + q + out + state + scores + 2 ** (20 if rows else 19)


def kv_sizes(H: int, K: int, hd: int, vd: int, ps: int, itemsize: int = 2,
             rows: bool = False, window: Optional[int] = None) -> KVSizes:
    """The K/V kernels' block and tile sizes for H query heads over K kv
    heads (a pair form: K pairs), q and K rows `hd` lanes a head and V rows
    `vd`, pages of `ps` tokens, under KV_VMEM_BUDGET. Everything here is a
    static fact of the shapes the kernel is given, and the query block does
    not depend on the page size (a block states it before it has pages).

    The query block, both layouts: 64 tokens up to 32 heads (swept on the
    v5e at Mistral-7B's widths over {16, 32, 64, 128}, PERF.md section 6,
    PR 32), fewer beyond so that a block's rows (tokens x heads) stay the
    size, a whole number of sublane tiles (40 heads: 48, 64 heads: 32). At
    64 / 4 heads over row pools 64 tokens a block gain a 128-token slice at
    33k 7% over 32 (PR 46) and cost 4 MiB more: not taken.

    5-D pools (pages that travel: llm/model_runner.py's wire view): 16 pages
    a step for every block (best for decode rows at ~700 tokens; 32 gain on
    slices at 2.4k and run out of VMEM at four kv heads), halved while the
    reckoning is over the budget.

    Row pools (swept at MiMo-V2-Flash's and Phi-4-mini-flash's shapes, PERF.md
    section 6, PR 46). A block of one token: whole multiples of 16 pages
    until a tile holds ROW_TILE_BYTES of K and V: under that the walk is
    bound by its steps, not its bytes (64 / 4 heads, 48 KB a page: 5.09 /
    4.60 / 4.60 ms a layer at 16 / 32 / 64 pages; the pair form's 80 KB
    pages: 2.81 / 2.82 at 16 / 32). A block of many, without a window: a tile
    of ROW_TILE_TOKENS context tokens, which amortise a step's passes (a
    128-token slice at 33k: +4.74 / 2.77 / 2.32 / 1.97 ms a layer at 16 / 32
    / 48 / 64 pages), halved while over the budget. With a window the walk
    is the window's pages and a block's own: where that is a tile or two of
    a block of one token's (MiMo-V2-Flash's 128 tokens, Phi-4-mini-flash's
    512) a larger tile is zeros to multiply (+6-9% at 32 pages) and the tile
    stays a block of one token's; a wider window takes whole multiples of 16
    pages up to a fifth of its own (`WINDOW_TILES`) and never more than the
    full form. Swept at Trinity-Large-Preview's shapes (48 / 8 heads of 128,
    window 4,096: a slice's block walks 264 pages; `chip_smoke.py --phase
    afmoe_kernels`, PERF.md section 6, PR 59): a 128-token slice beside 31
    decode rows, a window layer, +0.390 / 0.333-0.347 / 0.266 / 0.259-0.264
    ms at 16 / 32 / 48 / 64 pages, and 64 reckons over the budget (15.09
    MiB): 48. A decode row's 256-page walk there reads 0.797-0.803 / 0.807-
    0.816 / 0.857 ms a layer at 16 / 32 / 64 pages a step of a block of one
    token: the ROW_TILE_BYTES rule's 16 stands. (The same slice through the
    FULL layer: +0.57 / 0.44 / 0.46 ms at 32 / 48 / 64 pages; the halving
    under the budget takes 32 there, as it does at Phi's shape.) Swept at
    LFM2-24B-A2B's shape in the pair form by runs (32 query heads over 4 kv
    PAIRS of 128 + 128 lanes, a page 32 KB; `chip_smoke.py --phase
    lfm2_kernels`, PERF.md section 6, PR 63): the rules give 32 pages a step
    for a block of one token (1 MiB) and 64 for a block of many, and 64
    decode rows a layer read 0.367 / 1.015 / 1.897 ms at 1k / 4k / 8k (42 /
    61 / 66% of 819 GB/s in useful bytes) where 16 pages read 0.388 / 1.162 /
    2.172 and 64 pages 0.351 / 0.963 / 1.797; a tick's 63 decode rows beside
    a 128-token slice 0.790 at (32, 64), 0.918 at (16, 32), 0.822 at (32,
    32), 0.788 at (64, 64). 64 pages of one token would gain a twentieth of
    a kernel that is a fourteenth of its cell's tick, and a ROW_TILE_BYTES
    of 2 MiB would move MiMo's, Phi's and Trinity's tiles, whose sweeps read
    a larger tile no better or worse: the rule stands. Swept at
    MiMo-V2-Flash's shape again with its K rows laid SPLIT (`KRow`: 64 / 4
    heads, a page 40 KB where the padded rows made it 48; `chip_smoke.py`'s
    `mimo_kernel_timing`, PERF.md section 6, PR 64): 32 decode rows over
    ~33.9k tokens, a full layer, 5.07 / 4.40 / 4.31 / 4.15 ms at 16 / 32 / 48
    / 64 pages a step of a block of one token, where the padded rows read
    4.62 at 32 and 4.61 at 64. With a sixth of the bytes gone the walk is not
    bound by its bytes any more (its DMAs alone, no product: 3.84 ms, 724
    GB/s) but by what a step does in ONE instruction stream beside them: the
    page DMAs' starts, ~23 ns each from a rolled loop, then the products
    (alone 1.33 ms at 32 pages, 1.07 at 64), so a step of 32 pages costs 2.08
    us for 1.81 us of bytes, and a larger tile only spreads a step's fixed
    0.3 us. So over split rows a block of one token takes the block of many's
    tile where there is no window (64 pages; VMEM is the larger tile's
    either way). The ROW_TILE_BYTES rule stands for the others: LFM2's 32 KB
    pages read the same way (above: 64 pages 5% faster at 8k) and were left
    to the issue that made the starts cheaper (PR 65, `start_counted`: at 64
    pages a step the same rows read 3.91 ms, the DMAs alone 3.81-3.84; no
    size was swept again). A 128-token slice: +2.00 / 2.36 ms at 64 / 48 pages of many (the
    padded rows: 1.98 at 64); the window form 0.124 (0.131)."""
    tokens = min(64, max(8, 64 * 32 // H // 8 * 8))
    one = many = 16
    row = k_row(K, hd) if rows else None
    if rows:
        page = ps * (row.lanes + K * vd) * itemsize
        one = many = min(16 * -(-ROW_TILE_BYTES // (16 * page)),
                         max(16, ROW_TILE_TOKENS // ps))
        many = max(one, ROW_TILE_TOKENS // ps if window is None else
                   min(ROW_TILE_TOKENS // ps,
                       window // ps // WINDOW_TILES // 16 * 16))
        if row.rest and window is None:     # (the docstring's last sweep)
            one = many

    def over(one, many):
        return kv_vmem_bytes(H, K, hd, vd, ps, itemsize, rows, tokens, one,
                             many) > KV_VMEM_BUDGET

    while over(one, many) and max(one, many) > 1:
        if rows and many > one:
            many = max(one, many // 2)
        else:
            one = many = max(1, one // 2)
    return KVSizes(tokens, one, many, rows,
                   (row.whole, row.rest) if rows else None)


def pair_queries(q, run: int = 1):
    """The PAIR FORM of the K/V kernel's operands, for attention at a head
    width of half a lane tile: a pool row is
    one kv pair, K `[k_2j | k_2j+1]` and V `[v_2j | v_2j+1]`, 2 hd wide, K /
    2 of them a token's row, and q (..., H, hd) rides as (..., H, 2 hd): query
    heads alternate between the halves of their rows in RUNS of `run` heads,
    the first run of a pair of runs in the first half, the second in the
    second, zeros in the other. The kernel's product of such a row with a K
    row is the head's product with ONE k of the pair, its softmax the head's,
    its value sum over the whole V row. `run` 1 is differential attention's
    pairing (models/phi4flash.py): heads 2p and 2p + 1 come back as the two
    softmax sums of query pair p over kv pair p // (H / K), which the caller
    subtracts. `run` H / K is a plain GQA's (models/lfm2_moe.py): the H / K
    query heads of kv head 2j lie in the first half, those of 2j + 1 in the
    second, and each head's output is its OWN half of the value sum
    (`pair_outputs`). No K or V value lies in HBM twice and the kernel is
    `_kv_rows_kernel`, full and window form, over row pools (`kv_heads` = K /
    2; pass `scale` = 1 / sqrt(hd): the row is 2 hd wide)."""
    *lead, H, hd = q.shape
    pairs = q.reshape(*lead, H // (2 * run), 2, run, hd)
    zeros = jnp.zeros_like(pairs[..., 0, :, :])
    return jnp.stack(
        [jnp.concatenate([pairs[..., 0, :, :], zeros], axis=-1),
         jnp.concatenate([zeros, pairs[..., 1, :, :]], axis=-1)],
        axis=-3).reshape(*lead, H, 2 * hd)


def pair_outputs(o, run: int):
    """`pair_queries`' inverse for the output of a plain GQA: of o (..., H, 2
    vd), the value sums over whole V rows `[v_2j | v_2j+1]`, each head's own
    half, (..., H, vd): the first for a head of a first run, the second for
    one of a second."""
    *lead, H, wide = o.shape
    halves = o.reshape(*lead, H // (2 * run), 2, run, 2, wide // 2)
    return jnp.stack([halves[..., 0, :, 0, :], halves[..., 1, :, 1, :]],
                     axis=-3).reshape(*lead, H, wide // 2)


def _gather_context(pool, layer, pages):
    """(S, n * ps, K, w): pages `pages` (S, n) of `layer`."""
    S, n = pages.shape
    _, _, ps, K, w = pool.shape
    return pool[layer][pages].reshape(S, n * ps, K, w)


def ragged_paged_attention_reference(
        q, k_pool, v_pool, layer, block_tables, kv_lens, q_positions, *,
        scale: Optional[float] = None, window: Optional[int] = None,
        sink=None, kv_heads: Optional[int] = None):
    """jnp reference (CPU tests + fallback). Gathers the full padded context
    (with a window: the ring's pages from the first query token's oldest
    visible page on); the Pallas kernel below is the O(actual-context)
    implementation."""
    S, Bq, H, hd = q.shape
    if kv_heads is not None:    # row pools: the same rows, (K, w) apart
        k_pool = k_row_of(hd, k_pool.shape[-1], kv_heads).as_queries_see(
            k_pool)
        v_pool = v_pool.reshape(*v_pool.shape[:3], kv_heads, -1)
    ps, K = k_pool.shape[2], k_pool.shape[3]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    width = block_tables.shape[1]
    if window is None:
        first, pages = jnp.zeros((S,), jnp.int32), block_tables
    else:
        first = jnp.maximum(q_positions - (window - 1), 0) // ps
        pages = jnp.take_along_axis(
            block_tables, (first[:, None] + jnp.arange(width)) % width,
            axis=1)
    k = _gather_context(k_pool, layer, pages)
    v = _gather_context(v_pool, layer, pages)
    max_ctx = k.shape[1]
    if K != H:
        rep = H // K
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("sqhd,skhd->shqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    k_pos = ((first * ps)[:, None] + jnp.arange(max_ctx))[:, None, None, :]
    q_abs = (q_positions[:, None] + jnp.arange(Bq)[None, :])[:, None, :, None]
    mask = (k_pos < kv_lens[:, None, None, None]) & (q_abs >= k_pos)
    if window is not None:
        mask &= q_abs - k_pos < window
    logits = jnp.where(mask, logits, NEG_INF)
    if sink is not None:    # one more column, of no value
        column = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None], (S, H, Bq, 1))
        probs = jax.nn.softmax(jnp.concatenate([logits, column], axis=-1),
                               axis=-1)[..., :-1].astype(v.dtype)
    else:
        probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("shqk,skhd->sqhd", probs, v)


def token_seq_ids(cu_q_lens, T: int, S: int):
    """Sequence id per flat token (count of cu boundaries at or below it),
    clamped into [0, S-1] so padding tokens index real scalar rows; the
    caller masks them out separately (tok >= cu_q_lens[S])."""
    tok = jnp.arange(T)
    seq = jnp.sum(tok[:, None] >= cu_q_lens[None, 1:], axis=1).astype(
        jnp.int32)
    return jnp.minimum(seq, S - 1)


def ragged_paged_attention_unified_reference(
        q, k_pool, v_pool, layer, block_tables, kv_lens, q_positions,
        cu_q_lens, *, scale: Optional[float] = None,
        window: Optional[int] = None, sink=None,
        kv_heads: Optional[int] = None):
    """Token-major unified reference: q is flat (T, H, hd), sequences own
    contiguous row spans delimited by cu_q_lens (S+1 cumulative starts).

    Implemented by scattering the flat rows back into the rectangular
    (S, T, H, hd) layout and calling ragged_paged_attention_reference —
    per-row math is THE SAME FUNCTION, so a row's output does not depend on
    which rows share its launch (the CPU-CI anchor of the engine's tests
    against the plain forward pass)."""
    T, H, hd = q.shape
    S = kv_lens.shape[0]
    seq = token_seq_ids(cu_q_lens, T, S)
    local = jnp.arange(T) - cu_q_lens[seq]
    valid = jnp.arange(T) < cu_q_lens[S]
    # Padding tokens scatter to column T (out of bounds -> dropped): never
    # a wrapped negative index, which would silently overwrite real rows.
    qr = jnp.zeros((S, T, H, hd), q.dtype).at[
        seq, jnp.where(valid, local, T)].set(q, mode="drop")
    out_r = ragged_paged_attention_reference(
        qr, k_pool, v_pool, layer, block_tables, kv_lens, q_positions,
        scale=scale, window=window, sink=sink, kv_heads=kv_heads)
    out = out_r[seq, jnp.minimum(local, T - 1)]
    return jnp.where(valid[:, None, None], out, jnp.zeros_like(out))


# ---------------------------------------------------------------------------
# Query blocks of one sequence (both Pallas kernels' grids)
# ---------------------------------------------------------------------------

def query_blocks(cu_q_lens, T: int, S: int, TQ: int):
    """Cut a flat mixed batch into blocks of up to TQ query tokens of ONE
    sequence, at most NB = S + T // TQ of them. Returns (seq, local, blk_n,
    slot_tok, first): block b holds blk_n[b] tokens (0: a padding block) of
    sequence seq[b], of which it is block local[b]; slot_tok (NB, TQ) are the
    flat tokens of its slots; sequence s's first block is first[s]."""
    NB = S + T // TQ
    n_s = cu_q_lens[1:] - cu_q_lens[:-1]                      # (S,)
    blocks_s = (n_s + TQ - 1) // TQ                           # blocks a seq
    end = jnp.cumsum(blocks_s)
    first = end - blocks_s                                    # its first
    b = jnp.arange(NB)
    seq = jnp.minimum(jnp.sum(b[:, None] >= end[None, :], axis=1), S - 1)
    local = b - first[seq]                                    # block of seq
    blk_n = jnp.where(b < end[S - 1],
                      jnp.clip(n_s[seq] - local * TQ, 0, TQ), 0)
    slot_tok = (cu_q_lens[seq] + local * TQ)[:, None] + jnp.arange(TQ)
    return seq, local, blk_n, slot_tok, first


def blocks_to_tokens(out, cu_q_lens, first, T: int, S: int, TQ: int, H: int):
    """The blocks' outputs (NB, TQ * H, w) gathered back into the flat token
    order (T, H, w), padding tokens zero."""
    NB, _, w = out.shape
    tok_seq = token_seq_ids(cu_q_lens, T, S)
    tok_local = jnp.arange(T) - cu_q_lens[tok_seq]
    tok_slot = (first[tok_seq] + tok_local // TQ) * TQ + tok_local % TQ
    flat = out.reshape(NB * TQ, H, w)[jnp.clip(tok_slot, 0, NB * TQ - 1)]
    valid = jnp.arange(T) < cu_q_lens[S]
    return jnp.where(valid[:, None, None], flat, jnp.zeros_like(flat))


# ---------------------------------------------------------------------------
# How a tile's page DMAs are started (the row kernel's and the latent's)
# ---------------------------------------------------------------------------
#
# A start is a few scalar instructions in the kernel's ONE instruction stream,
# serial with the products. Read on the v5e in the row kernel (PERF.md section
# 6, PR 65; ns a start, its products taken off): a page a turn of a rolled
# loop 22-23; PAGE_RUN a loop step, unrolled, 18 (runs of 16 and 32 the same);
# a whole tile's unrolled with every place in the tile static 15. The last
# costs a page's lowering a start at every step program's warm start (the
# Mosaic module of MiMo-V2-Flash's full form 749 -> 3,666 lines at 64 pages a
# step, `.lower()` +0.18 s a kernel a program where a run of eight adds 0.035):
# it is kept for tiles whose pages are counted when the kernel is TRACED (the
# latent kernel's fast part), and a tile counted at run time takes runs.

# Page DMAs started a step of the loop that counts a tile's pages.
PAGE_RUN = 8


def start_pages(start, lo, hi, first=0):
    """`start(first + j)` for j in [lo, hi): unrolled (and traced once) where
    the bounds are static, else one a loop step."""
    def one(j, _):
        start(first + j)
        return _

    jax.lax.fori_loop(lo, hi, one, 0, unroll=isinstance(hi, int))


def start_counted(start, count):
    """`start(j)` for a tile's first `count` pages (traced): PAGE_RUN of them
    a loop step, then the rest one by one."""
    runs = count // PAGE_RUN

    def run(r, _):
        start_pages(start, 0, PAGE_RUN, r * PAGE_RUN)
        return _

    jax.lax.fori_loop(0, runs, run, 0)
    start_pages(start, runs * PAGE_RUN, count)


def pages_in_runs(n_pages: int, tile: int) -> int:
    """Of the `n_pages` a block of one token walks through the row kernel,
    `tile` pages a step, those `start_counted` starts unrolled in runs of
    PAGE_RUN (host arithmetic, for whoever counts a tick's walk)."""
    whole, rest = divmod(n_pages, tile)
    return (whole * (tile // PAGE_RUN) + rest // PAGE_RUN) * PAGE_RUN


# ---------------------------------------------------------------------------
# K/V paged attention: the Pallas kernel
# ---------------------------------------------------------------------------

def _kv_kernel(blk_seq_ref, blk_pos_ref, blk_n_ref, blk_tok_ref, meta_ref,
               block_tables_ref, kv_lens_ref,               # scalar prefetch
               q_hbm, kpool_hbm, vpool_hbm,                 # tensor inputs
               *rest,                                       # [sink], out, scratch
               ps: int, KB: int, scale: float, TQ: int, H: int, K: int,
               window: Optional[int], has_sink: bool):
    """The kernel of 5-D pools. Grid: (NB,). Block b is up to TQ query tokens
    of sequence blk_seq[b]: blk_n[b] of them are real (0: a padding block,
    which does nothing), the first is flat token blk_tok[b] of q_hbm (tokens,
    H, hd) at absolute position blk_pos[b]. meta = (layer, real blocks).
    o_ref: (1, TQ * H, vd), rows token-major (t * H + h). q and the pools
    stay in HBM: a block reads its own tokens' rows, and k_scr / v_scr hold
    two tiles of KB pages, (tile, K, hd) and (tile, K, vd). sink_ref, where
    the layer has one: (H, 1) float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if has_sink:
        sink_ref, *rest = rest
    o_ref, q_scr, k_scr, v_scr, sems, q_sem = rest
    b = pl.program_id(0)
    s = blk_seq_ref[b]
    n = blk_n_ref[b]
    q_pos = blk_pos_ref[b]
    tok0 = blk_tok_ref[b]
    layer = meta_ref[0]
    G = H // K
    hd = q_scr.shape[-1]
    vd = v_scr.shape[-1]
    width = block_tables_ref.shape[1]
    # No row of the block sees past its last real token, and with a window
    # none sees a page before the one that holds q_pos - (window - 1).
    kv_len = jnp.minimum(kv_lens_ref[s], q_pos + n)
    page0 = 0 if window is None else jnp.maximum(
        q_pos - (window - 1), 0) // ps
    n_pages = pl.cdiv(kv_len, ps) - page0
    n_tiles = pl.cdiv(n_pages, KB)
    tile = KB * ps

    @pl.when(b == 0)
    def _():
        # A tile's slots past the context's last page are not DMA'd: what
        # they hold is masked out of the scores but multiplied (by zero) in
        # the second product, so it has to be finite from the first block on.
        k_scr[...] = jnp.zeros_like(k_scr)
        v_scr[...] = jnp.zeros_like(v_scr)

    def tile_dma(slot, i, go):
        """Start (or wait for) the real pages of tile i: one DMA a page of
        all kv heads, each to its place in the slot."""
        def page_dma(j, _):
            logical = page0 + i * KB + j
            page = block_tables_ref[
                s, logical if window is None else logical % width]
            rows = pl.ds(pl.multiple_of(j * ps, ps), ps)
            for pool, scr, sem in ((kpool_hbm, k_scr, sems.at[0, slot]),
                                   (vpool_hbm, v_scr, sems.at[1, slot])):
                go(pltpu.make_async_copy(
                    pool.at[layer, page], scr.at[slot, rows], sem))
            return _

        jax.lax.fori_loop(0, jnp.minimum(KB, n_pages - i * KB), page_dma, 0)

    def fetch_q(nq: int):
        """The block's first nq tokens' rows into q_scr, the first tile's
        pages started behind them."""
        copy = pltpu.make_async_copy(
            q_hbm.at[pl.ds(tok0, nq)], q_scr.at[pl.ds(0, nq)], q_sem)
        copy.start()
        tile_dma(0, 0, lambda c: c.start())
        copy.wait()

    def scores(q, k):
        """q (..., rows, hd) . k (..., cols, hd) -> (..., rows, cols), float32,
        scaled; a leading axis is a batch of kv heads."""
        heads = tuple(range(q.ndim - 2))
        return jax.lax.dot_general(
            q, k, (((q.ndim - 1,), (k.ndim - 1,)), (heads, heads)),
            preferred_element_type=jnp.float32) * scale

    def fold(state, sc, ok, v):
        """One online-softmax step of masked float32 scores sc (..., rows,
        cols) against values v (..., cols, vd)."""
        m, l, acc = state
        sc = jnp.where(ok, sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        # Explicit zero where masked: a row whose tile is all masked would
        # otherwise add exp(NEG_INF - NEG_INF) == 1 a column.
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1, keepdims=True)
        heads = tuple(range(v.ndim - 2))
        acc_new = alpha * acc + jax.lax.dot_general(
            p.astype(v.dtype), v,
            (((p.ndim - 1,), (v.ndim - 2,)), (heads, heads)),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    def init(rows, sink):
        """The softmax state before the first tile; `sink` rows + (1,): the
        rows' sink logits, a first column of no value."""
        acc = jnp.zeros(rows + (vd,), dtype=jnp.float32)
        if sink is not None:
            return sink, jnp.ones(rows + (1,), dtype=jnp.float32), acc
        return (jnp.full(rows + (1,), NEG_INF, dtype=jnp.float32),
                jnp.zeros(rows + (1,), dtype=jnp.float32), acc)

    def pipelined(step, state):
        """state after step(i, slot, state) over the block's tiles (the
        first already started), tile i + 1 in flight while tile i is
        computed."""
        def body(i, state):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_tiles)
            def _():
                tile_dma(1 - slot, i + 1, lambda c: c.start())

            tile_dma(slot, i, lambda c: c.wait())
            return step(i, slot, state)

        return jax.lax.fori_loop(0, n_tiles, body, state)

    def walk_one():
        """A block of one token: its H rows against the tile read as
        (tile x K, hd), the other kv heads' columns masked."""
        cols = tile * K
        fetch_q(1)
        q = q_scr[0]                                         # (H, hd)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
        row_kh = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // G
        mine = (col % K) == row_kh                           # (H, cols)
        k_off = page0 * ps + col // K                        # (1, cols)

        def step(i, slot, state):
            k = k_scr[slot].reshape(cols, hd)
            v = v_scr[slot].reshape(cols, vd)
            k_pos = i * tile + k_off
            ok = mine & (k_pos < kv_len)
            if window is not None:
                ok &= q_pos - k_pos < window
            return fold(state, scores(q, k), ok, v)

        m, l, acc = pipelined(
            step, init((H,), sink_ref[...] if has_sink else None))
        o_ref[0, :H] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)

    def by_head(scr, slot):
        """The tile in `slot` as (K, tile, w)."""
        return jnp.swapaxes(scr[slot], 0, 1)

    def walk_heads():
        """A block of up to TQ tokens: the tile turned to (K, tile, hd), and
        every kv head's TQ * G rows against that head's (tile, hd) in one
        product batched over the heads."""
        nq, rows = TQ, TQ * G
        fetch_q(nq)
        q = jnp.swapaxes(q_scr[:nq].reshape(nq, K, G, hd), 0, 1).reshape(
            K, rows, hd)
        q_abs = q_pos + jax.lax.broadcasted_iota(
            jnp.int32, (1, rows, 1), 1) // G
        k_off = page0 * ps + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, tile), 2)

        def step(i, slot, state):
            k_pos = i * tile + k_off
            ok = (k_pos < kv_len) & (q_abs >= k_pos)         # (1, rows, tile)
            if window is not None:
                ok &= q_abs - k_pos < window
            k = by_head(k_scr, slot)                         # (K, tile, hd)
            return fold(state, scores(q, k), ok, by_head(v_scr, slot))

        sink = None
        if has_sink:    # row t * G + g of kv head kh is head kh * G + g
            sink = jnp.tile(sink_ref[...].reshape(K, G, 1), (1, nq, 1))
        m, l, acc = pipelined(step, init((K, rows), sink))
        out = (acc / jnp.maximum(l, 1e-30)).reshape(K, nq, G, vd)
        o_ref[0, :nq * H] = jnp.swapaxes(out, 0, 1).reshape(
            nq * H, vd).astype(o_ref.dtype)

    @pl.when((n > 0) & (n_tiles == 0))
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when((n_tiles > 0) & (n == 1))
    def _():
        walk_one()

    if TQ > 1:
        @pl.when((n_tiles > 0) & (n > 1))
        def _():
            walk_heads()


def _kv_rows_kernel(blk_seq_ref, blk_pos_ref, blk_n_ref, blk_tok_ref,
                    meta_ref, block_tables_ref, kv_lens_ref,  # scalar prefetch
                    q_hbm, kpool_hbm, vpool_hbm,              # tensor inputs
                    *rest,                        # [sink], out, scratch
                    ps: int, KB1: int, KBN: int, scale: float, TQ: int,
                    H: int, K: int, window: Optional[int], has_sink: bool,
                    block_tokens: Optional[int] = None):
    """The kernel of ROW POOLS, (L, P, ps, K * hd) and (L, P, ps, K * vd).
    Grid, blocks, meta and o_ref as `_kv_kernel`'s. k_scr / v_scr hold two
    tiles of context rows, (tile, K * hd) and (tile, K * vd), whole lane
    tiles with nothing padded: KB1 pages each for a block of one token, the
    first KBN of them for a block of many. A step takes the kv heads ONE AT A
    TIME: head kh's part of the tile is a slice of whole lane tiles, read
    where it lies, and its G (x tokens) query rows are multiplied against it
    alone, so nothing of the tile is copied and no score of another head's
    columns is computed. Where a K row lies SPLIT (`KRow`: the operands'
    widths say so, `k_row_of`), a head's scores are two such products added
    in float32, its whole lane tiles' and the lane tile its rest shares with
    its neighbour's, whose lanes meet the zeros q rides with. The softmax
    state (m, l, acc; float32, a kv head
    leading) rides the loop in registers for a block of one token (K x G
    rows) and lies in scratch, updated in place, for a block of many.

    A step asks for the NEXT tile's pages before it waits for its own (one
    DMA a page a pool; a whole tile is waited for at once, the semaphore
    counts bytes), so the DMA queue never runs empty. How they are asked for
    follows from the block (`start_tile`): a block of one token starts
    PAGE_RUN pages a loop step, 2 PAGE_RUN starts unrolled, and the rest of a
    ragged tile one by one; a block of many a page a turn of a rolled loop.

    `block_tokens` (ops/block_sparse.py: a slice whose tokens each attend to
    BLOCKS of their context of their own choosing, a kv head): one more
    operand keep_hbm (NB, K x R, TQ, LANE), 0 / 1 in the pool's dtype, a
    query block's own: with `per` blocks a tile of the walk and LANE // per
    tiles a lane row (R rows a kv head), lane `(i % (LANE // per)) per + c`
    of row `kh R + i // (LANE // per)` of token t says whether t's kv head kh
    keeps block c of tile i of its context. A block of many fetches its rows
    once and a pass masks its scores with them beside the causal mask: a
    pass's tokens' lane rows for (kh, i), repeated down a token's G rows and
    across a block's columns by two small products with 0 / 1 matrices
    (exact), so the walk reads every page once whatever the tokens keep."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if block_tokens is not None:
        keep_hbm, *rest = rest
        *rest, keep_scr, keep_sem = rest
    if has_sink:
        sink_ref, *rest = rest
    o_ref, q_scr, qh_scr, k_scr, v_scr, ml_scr, acc_scr, sems, q_sem = rest
    b = pl.program_id(0)
    s = blk_seq_ref[b]
    n = blk_n_ref[b]
    q_pos = blk_pos_ref[b]
    tok0 = blk_tok_ref[b]
    layer = meta_ref[0]
    G = H // K
    row = k_row_of(q_scr.shape[-1], k_scr.shape[-1], K)
    vd = v_scr.shape[-1] // K
    OUT = 8             # tokens of a block written out at a time
    width = block_tables_ref.shape[1]
    # No row of the block sees past its last real token, and with a window
    # none sees a page before the one that holds q_pos - (window - 1).
    kv_len = jnp.minimum(kv_lens_ref[s], q_pos + n)
    page0 = 0 if window is None else jnp.maximum(
        q_pos - (window - 1), 0) // ps
    n_pages = pl.cdiv(kv_len, ps) - page0
    pools = ((kpool_hbm, k_scr, 0), (vpool_hbm, v_scr, 1))

    @pl.when(b == 0)
    def _():
        # A tile's rows past the context's last page are not DMA'd: what
        # they hold is masked out of the scores but multiplied (by zero) in
        # the second product, so it has to be finite from the first block on.
        k_scr[...] = jnp.zeros_like(k_scr)
        v_scr[...] = jnp.zeros_like(v_scr)

    def page_dmas(KB: int, slot, i, j):
        """Page j of tile i (KB pages a tile) into `slot`: a DMA a pool."""
        logical = page0 + i * KB + j
        page = block_tables_ref[
            s, logical if window is None else logical % width]
        at = pl.ds(pl.multiple_of(j * ps, ps), ps)
        return [pltpu.make_async_copy(pool.at[layer, page],
                                      scr.at[slot, at], sems.at[p, slot])
                for pool, scr, p in pools]

    def real_pages(KB: int, slot, i, go):
        """Start (or wait for) the real pages of tile i, however many."""
        def one(j, _):
            for copy in page_dmas(KB, slot, i, j):
                go(copy)
            return _

        jax.lax.fori_loop(0, jnp.minimum(KB, n_pages - i * KB), one, 0)

    def start_tile(KB: int, slot, i, counted: bool):
        """Start the real pages of tile i (it has some). `counted`, a block
        of one token, whose walk is its DMAs and their starts: PAGE_RUN pages
        a loop step, their starts unrolled, then the rest one by one
        (`start_counted`), whole tile or ragged. Else a page a turn of a
        rolled loop: a block of many hides its DMAs behind its products as it
        is."""
        if not counted:
            real_pages(KB, slot, i, lambda c: c.start())
            return

        def start(j):
            for copy in page_dmas(KB, slot, i, j):
                copy.start()

        start_counted(start, jnp.minimum(KB, n_pages - i * KB))

    def fetch(nq: int, KB: int, counted: bool):
        """The block's first nq tokens' rows out of the flat q into q_scr,
        the first tile's pages started behind them (`start_tile`)."""
        copy = pltpu.make_async_copy(
            q_hbm.at[pl.ds(tok0, nq)], q_scr.at[pl.ds(0, nq)], q_sem)
        copy.start()
        start_tile(KB, 0, 0, counted)
        copy.wait()

    def steps(KB: int, fold, state, counted: bool):
        """state after `fold(i, slot, state)` over the block's tiles of KB
        pages (the first already started; `counted`: `start_tile`)."""
        n_tiles = pl.cdiv(n_pages, KB)

        def step(i, state):
            # The next tile's pages are asked for BEFORE this tile is waited
            # for: the DMA queue never runs empty (waiting first cost a full
            # layer's decode rows 12-47% more at 64 / 4 heads: PERF.md
            # section 6, PR 46; a tile's starts unrolled AFTER the wait 14-26%
            # more than before it: PR 64).
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_tiles)
            def _():
                start_tile(KB, 1 - slot, i + 1, counted)

            @pl.when(n_pages - i * KB >= KB)
            def _():
                # A whole tile: one wait a pool (the semaphore counts bytes).
                for _, scr, p in pools:
                    whole = scr.at[slot, pl.ds(0, KB * ps)]
                    pltpu.make_async_copy(whole, whole,
                                          sems.at[p, slot]).wait()

            @pl.when(n_pages - i * KB < KB)
            def _():
                real_pages(KB, slot, i, lambda c: c.wait())

            return fold(i, slot, state)

        return jax.lax.fori_loop(0, n_tiles, step, state)

    def head_major(nq: int, lanes=slice(None)):
        """Lanes `lanes` of q_scr's first nq tokens with a kv head's rows
        together, (K, nq * G, lanes): row t * G + g of head kh is query head
        kh * G + g of token t."""
        rows = q_scr[:nq, :, lanes]
        return jnp.swapaxes(rows.reshape(nq, K, G, -1), 0, 1).reshape(
            K, nq * G, -1)

    def token_major(out, nq: int):
        """(K, nq * G, vd) back to o_ref's rows, (nq * H, vd)."""
        return jnp.swapaxes(out.reshape(K, nq, G, vd), 0, 1).reshape(
            nq * H, vd).astype(o_ref.dtype)

    def tile_of(scr, slot, tile: int, first, w: int):
        """Lanes [first, first + w) of the tile in `slot`, where they lie (a
        traced `first` is a multiple of LANE)."""
        lanes = (pl.ds(first, w) if isinstance(first, int)
                 else pl.ds(pl.multiple_of(first, LANE), w))
        return scr[slot, :tile, lanes]

    def k_scores(q_lanes, slot, tile: int, kh):
        """kv head kh's rows against its K in the tile in `slot`, float32
        (rows, tile), unscaled: a product a part of the head's K as it lies
        (`KRow.parts`); q_lanes(first, w) are the rows' lanes."""
        return functools.reduce(operator.add, [
            product(q_lanes(at, w), tile_of(
                k_scr, slot, tile, row.first_lane(part, kh), w), ((1,), (1,)))
            for part, (at, w, _) in enumerate(row.parts)])

    def visible(i, tile: int, q_abs, causal: bool):
        """Which of tile i's columns the rows at positions q_abs see."""
        k_pos = i * tile + page0 * ps + jax.lax.broadcasted_iota(
            jnp.int32, (1, tile), 1)
        ok = k_pos < kv_len
        if causal:
            ok &= q_abs >= k_pos
        if window is not None:
            ok &= q_abs - k_pos < window
        return ok

    def online(m, l, acc, sc, ok, pv):
        """One online-softmax step: float32 scores sc, seen where `ok`, into
        (m, l, acc); pv(p) is the second product."""
        sc = jnp.where(ok, sc, NEG_INF)
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        # Explicit zero where masked: a row whose tile is all masked would
        # otherwise add exp(NEG_INF - NEG_INF) == 1 a column.
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        return (m_new, alpha * l + p.sum(axis=-1, keepdims=True),
                alpha * acc + pv(p))

    def product(a, b, contract):
        return jax.lax.dot_general(a, b, ((contract, ((), ()))),
                                   preferred_element_type=jnp.float32)

    def walk_one():
        """A block of one token: the state is small (K x G rows) and rides
        the loop in registers; the kv heads' scores are computed a head at a
        time from the tile where it lies and folded together."""
        tile = KB1 * ps
        fetch(1, KB1, True)
        # A lane tile of K is multiplied ONCE a step: the n heads whose
        # rests share one ride it together, (K / n, n x G, lanes).
        q = [head_major(1, pl.ds(at, w)).reshape(K // n, n * G, w)
             for at, w, n in row.parts]
        shape = (K, G, 1)
        if has_sink:    # a first column of no value: (m, l) = (sink, 1)
            ml = (sink_ref[...].reshape(shape),
                  jnp.ones(shape, dtype=jnp.float32))
        else:
            ml = (jnp.full(shape, NEG_INF, dtype=jnp.float32),
                  jnp.zeros(shape, dtype=jnp.float32))

        def fold(i, slot, state):
            sc = functools.reduce(operator.add, [jnp.stack([product(
                rows[j], tile_of(k_scr, slot, tile,
                                 row.first_lane(part, j * n), w),
                ((1,), (1,))) for j in range(K // n)]).reshape(K, G, tile)
                for part, ((_, w, n), rows) in enumerate(zip(row.parts, q))
            ]) * scale                                      # (K, G, tile)
            ok = visible(i, tile, q_pos, False)
            return online(*state, sc, ok, lambda p: jnp.stack([
                product(p[kh].astype(v_scr.dtype),
                        tile_of(v_scr, slot, tile, kh * vd, vd), ((1,), (0,)))
                for kh in range(K)]))

        _, l, acc = steps(KB1, fold, ml + (
            jnp.zeros((K, G, vd), dtype=jnp.float32),), True)
        o_ref[0, :H] = token_major(acc / jnp.maximum(l, 1e-30), 1)

    def walk_many():
        """A block of up to TQ tokens: the state lies in scratch, updated in
        place, and a step takes a head's rows a PASS at a time (whole tokens,
        about PASS_ROWS rows) in a rolled loop, so that one pass's float32
        scores are all that is live."""
        nq, rows, tile = TQ, TQ * G, KBN * ps
        pass_rows = _pass_tokens(nq, G) * G
        fetch(nq, KBN, False)
        qh_scr[...] = head_major(nq)
        if block_tokens is not None:
            kept = pltpu.make_async_copy(keep_hbm.at[b], keep_scr, keep_sem)
            kept.start()
            kept.wait()
            iota = jax.lax.broadcasted_iota
            pass_tokens = pass_rows // G
            per = tile // block_tokens          # blocks a tile
            lane_tiles = LANE // per            # tiles a lane row
            # a token's row down its G rows
            down = (iota(jnp.int32, (pass_rows, pass_tokens), 0) // G
                    == iota(jnp.int32, (pass_rows, pass_tokens), 1)
                    ).astype(keep_scr.dtype)
        # (m, l) side by side, lanes 0 and 1 of one array (a column of its
        # own each would pad to a lane tile twice).
        shape = (K, rows, 1)
        if has_sink:    # a first column of no value: (m, l) = (sink, 1)
            ml_scr[:, :, 0:1] = jnp.tile(
                sink_ref[...].reshape(K, G, 1), (1, nq, 1))
            ml_scr[:, :, 1:2] = jnp.ones(shape, dtype=jnp.float32)
        else:
            ml_scr[:, :, 0:1] = jnp.full(shape, NEG_INF, dtype=jnp.float32)
            ml_scr[:, :, 1:2] = jnp.zeros(shape, dtype=jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, dtype=jnp.float32)

        def fold_pass(c, i, slot):
            """Rows [c pass_rows, (c + 1) pass_rows) of every kv head."""
            r0 = c * pass_rows if isinstance(c, int) else pl.multiple_of(
                c * pass_rows, pass_rows)
            at = pl.ds(r0, pass_rows)
            ok = visible(i, tile, q_pos + (r0 + jax.lax.broadcasted_iota(
                jnp.int32, (pass_rows, 1), 0)) // G, True)
            if block_tokens is not None:
                # tile i's lanes of a row across its blocks' columns
                across = (iota(jnp.int32, (LANE, tile), 0)
                          - jax.lax.rem(i, lane_tiles) * per
                          == iota(jnp.int32, (LANE, tile), 1) // block_tokens
                          ).astype(keep_scr.dtype)
                tokens = pl.ds(c * pass_tokens if isinstance(c, int) else
                               pl.multiple_of(c * pass_tokens, pass_tokens),
                               pass_tokens)

            def head(kh, _):
                v = tile_of(v_scr, slot, tile, kh * vd, vd)
                seen = ok
                if block_tokens is not None:
                    mine = keep_scr[
                        kh * (keep_scr.shape[0] // K) + i // lane_tiles,
                        tokens, :]
                    seen = ok & (product(
                        product(down, mine, ((1,), (0,))).astype(
                            keep_scr.dtype), across, ((1,), (0,))) > 0.5)
                m, l, acc = online(
                    ml_scr[kh, at, 0:1], ml_scr[kh, at, 1:2], acc_scr[kh, at],
                    k_scores(lambda q0, w: qh_scr[kh, at, pl.ds(q0, w)],
                             slot, tile, kh) * scale,
                    seen, lambda p: product(p.astype(v.dtype), v,
                                            ((1,), (0,))))
                ml_scr[kh, at, 0:1] = m
                ml_scr[kh, at, 1:2] = l
                acc_scr[kh, at] = acc
                return _

            # Unrolled: rolled, a 128-token slice at 33k takes 7% longer.
            jax.lax.fori_loop(0, K, head, 0, unroll=True)

        def fold(i, slot, state):
            if pass_rows == rows:
                fold_pass(0, i, slot)
            else:
                def one(c, _):
                    fold_pass(c, i, slot)
                    return _

                jax.lax.fori_loop(0, rows // pass_rows, one, 0)
            return state

        steps(KBN, fold, 0, False)

        def write_out(t0, nt):
            """Tokens [t0, t0 + nt) of the block: acc / l, token-major."""
            at = pl.ds(t0 * G, nt * G)
            o_ref[0, pl.ds(t0 * H, nt * H)] = token_major(
                acc_scr[:, at] / jnp.maximum(ml_scr[:, at, 1:2], 1e-30), nt)

        if nq <= OUT:
            write_out(0, nq)
        else:       # a few tokens at a time: the turn's copies stay small
            def some(c, _):
                write_out(pl.multiple_of(c * OUT, OUT), OUT)
                return _

            jax.lax.fori_loop(0, nq // OUT, some, 0)

    @pl.when((n > 0) & (n_pages <= 0))
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when((n_pages > 0) & (n == 1))
    def _():
        walk_one()

    if TQ > 1:
        @pl.when((n_pages > 0) & (n > 1))
        def _():
            walk_many()


def _interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        from ray_tpu.ops import is_tpu_backend

        interpret = not is_tpu_backend()
    return interpret


# Jitted so that JAX traces the kernel once a process for each set of
# shapes (and NAMED for whoever reads a profile: inlined, the kernel's HLO
# instruction takes the jitted function's name, `paged_attention_kv_call.<n>`
# or, with a window, `paged_attention_window_call.<n>`, and the benchmark's
# reduction finds its kernels by `paged_attention_`): the token-major entry
# pads q to a multiple of Q_PAD tokens, so every token bucket of an engine's
# ladder up to Q_PAD - q_block brings the same shapes and a step program's
# start pays the kernel's lowering alone (the trace is a third of what this
# kernel adds to a warm start: PERF.md, PR 32).
Q_PAD = 256


def _kv_call(q, blk_seq, blk_pos, blk_n, blk_tok, nb_real, k_pool, v_pool,
             layer, block_tables, kv_lens, sink, *, scale, TQ, kv_pages,
             window, interpret, kv_heads=None, keep=None, block_tokens=None,
             tag=None):
    """q (tokens, H, hd), every block's TQ tokens from blk_tok[b] in bounds
    -> the blocks' outputs (NB, TQ * H, vd). Of a padding block (b >=
    nb_real) nothing is written. `kv_pages`: pool pages a loop step (of a
    block of one token, of a block of many). `kv_heads`: the pools are row
    pools of that many kv heads. `keep`, `block_tokens`: the row kernel's
    block mask (`_kv_rows_kernel` says what it holds); `tag`: the kernel's
    name where it serves another entry (ops/block_sparse.py)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, H, hd = q.shape
    NB = blk_seq.shape[0]
    rows = kv_heads is not None
    one, many = kv_pages
    if rows:
        ps, K = k_pool.shape[2], kv_heads
        vd = v_pool.shape[-1] // K
    else:
        _, _, ps, K, _ = k_pool.shape
        vd = v_pool.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    def out_block(b, seq, pos, n, tok, meta, *_):
        # A padding block keeps the last real block's buffer (and leaves it
        # alone), so nothing of it is written back.
        return jnp.minimum(b, jnp.maximum(meta[1] - 1, 0)), 0, 0

    in_specs = [
        pl.BlockSpec(memory_space=pl.ANY),   # q: a block reads its rows
        pl.BlockSpec(memory_space=pl.ANY),   # the K pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),   # the V pool stays in HBM
    ]
    operands = [q, k_pool, v_pool]
    if keep is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        operands.append(keep)
    if sink is not None:
        in_specs.append(pl.BlockSpec((H, 1), lambda b, *_: (0, 0)))
        operands.append(sink.astype(jnp.float32).reshape(H, 1))
    tile = max(one, many) * ps
    scratch = [
        pltpu.VMEM((TQ, H, hd), q.dtype),
        *([pltpu.VMEM((K, TQ * (H // K), hd), q.dtype)] if rows else []),
        pltpu.VMEM((2, tile) + k_pool.shape[3:], k_pool.dtype),
        pltpu.VMEM((2, tile) + v_pool.shape[3:], v_pool.dtype)]
    common = dict(ps=ps, scale=scale, TQ=TQ, H=H, K=K, window=window,
                  has_sink=sink is not None)
    if rows:    # the softmax state, a kv head leading
        G = H // K
        scratch += [pltpu.VMEM((K, TQ * G, 2), jnp.float32),
                    pltpu.VMEM((K, TQ * G, vd), jnp.float32)]
        if keep is not None:
            common["block_tokens"] = block_tokens
        kernel = functools.partial(_kv_rows_kernel, KB1=one, KBN=many,
                                   **common)
    else:
        kernel = functools.partial(_kv_kernel, KB=one, **common)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(NB,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, TQ * H, vd), out_block),
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2, 2)),
                                  pltpu.SemaphoreType.DMA(())] + (
            [] if keep is None else [
                pltpu.VMEM(keep.shape[1:], keep.dtype),
                pltpu.SemaphoreType.DMA(())]),
    )
    meta = jnp.stack([jnp.asarray(layer, jnp.int32),
                      jnp.asarray(nb_real, jnp.int32)])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (NB, TQ * H, vd), q.dtype, vma=vma_of(q, k_pool, v_pool)),
        interpret=interpret,
        **kernel_tag(tag or ("paged_attention_unified" if window is None
                             else "paged_attention_window")),
    )(blk_seq, blk_pos, blk_n, blk_tok, meta, block_tables, kv_lens,
      *operands)


_KV_STATIC = ("scale", "TQ", "kv_pages", "window", "interpret", "kv_heads")


@functools.partial(jax.jit, static_argnames=_KV_STATIC)
def paged_attention_kv_call(*args, **static):
    return _kv_call(*args, **static)


@functools.partial(jax.jit, static_argnames=_KV_STATIC)
def paged_attention_window_call(*args, **static):
    """The same kernel in its window form, under a name of its own so that
    a profile tells a window layer's kernel from a full layer's."""
    return _kv_call(*args, **static)


def _sizes_of(q, k_pool, v_pool, kv_heads: Optional[int],
              window: Optional[int]) -> KVSizes:
    """`kv_sizes` of the operands as they come (a row pool's K head as wide
    as it lies: `k_row_of`)."""
    K = kv_heads or k_pool.shape[3]
    return kv_sizes(q.shape[-2], K, k_pool.shape[-1] // (kv_heads or 1),
                    v_pool.shape[-1] // (kv_heads or 1), k_pool.shape[2],
                    k_pool.dtype.itemsize, rows=kv_heads is not None,
                    window=window)


def _kv_entry(window: Optional[int]):
    return (paged_attention_kv_call if window is None
            else paged_attention_window_call)


def ragged_paged_attention_unified(q, k_pool, v_pool, layer, block_tables,
                                   kv_lens, q_positions, cu_q_lens, *,
                                   scale: Optional[float] = None,
                                   window: Optional[int] = None, sink=None,
                                   kv_heads: Optional[int] = None,
                                   interpret: Optional[bool] = None):
    """Pallas unified ragged paged attention: ONE launch for a mixed batch
    where each sequence contributes its own query-token count (decode = 1,
    spec verify = k+1, prefill chunk = up to chunk tokens). Layouts in the
    module docstring; rows past cu_q_lens[S] are padding and come back zero.
    The kernel reads each query block's rows out of the flat q and writes
    the blocks' outputs, which are gathered back into the flat order."""
    T, H, hd = q.shape
    S = kv_lens.shape[0]
    sizes = _sizes_of(q, k_pool, v_pool, kv_heads, window)
    TQ = sizes.q_block
    padded = -(-(T + TQ) // Q_PAD) * Q_PAD       # a last block's TQ tokens
    seq, local, blk_n, slot_tok, first = query_blocks(
        cu_q_lens, padded, S, TQ)
    out = _kv_entry(window)(
        jnp.pad(q, ((0, padded - T), (0, 0), (0, 0))),
        seq.astype(jnp.int32),
        (q_positions[seq] + local * TQ).astype(jnp.int32),
        blk_n.astype(jnp.int32), slot_tok[:, 0].astype(jnp.int32),
        jnp.sum(blk_n > 0), k_pool, v_pool, layer, block_tables, kv_lens,
        sink, scale=scale, TQ=TQ,
        kv_pages=(sizes.pages_one, sizes.pages_many), window=window,
        interpret=_interpret(interpret),
        **({"kv_heads": kv_heads} if kv_heads else {}))
    return blocks_to_tokens(out, cu_q_lens, first, T, S, TQ, H)


def ragged_paged_attention(q, k_pool, v_pool, layer, block_tables, kv_lens,
                           q_positions, *, scale: Optional[float] = None,
                           window: Optional[int] = None, sink=None,
                           kv_heads: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Pallas ragged paged attention, rectangular: every sequence brings Bq
    query tokens (1: decode). The same kernel; the blocks are the
    rectangle's own rows, ceil(Bq / q_block) a sequence."""
    S, Bq, H, hd = q.shape
    sizes = _sizes_of(q, k_pool, v_pool, kv_heads, window)
    TQ = min(sizes.q_block, Bq)
    per_seq = -(-Bq // TQ)
    pad = per_seq * TQ - Bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    NB = S * per_seq
    local = jnp.tile(jnp.arange(per_seq, dtype=jnp.int32), S)
    seq = jnp.repeat(jnp.arange(S, dtype=jnp.int32), per_seq)
    out = _kv_entry(window)(
        q.reshape(NB * TQ, H, hd), seq, q_positions[seq] + local * TQ,
        jnp.clip(Bq - local * TQ, 0, TQ),
        jnp.arange(NB, dtype=jnp.int32) * TQ, NB, k_pool, v_pool, layer,
        block_tables, kv_lens, sink, scale=scale, TQ=TQ,
        kv_pages=(sizes.pages_one, sizes.pages_many), window=window,
        interpret=_interpret(interpret),
        **({"kv_heads": kv_heads} if kv_heads else {}))
    return out.reshape(S, per_seq * TQ, H, -1)[:, :Bq]


# ---------------------------------------------------------------------------
# Latent (MLA) paged attention
# ---------------------------------------------------------------------------
#
# A latent cache holds ONE row a token a layer, shared by every head:
# `[c_kv | k_rope | 0...]`, `W` wide (llm/model_runner.py, "The latent pool",
# says how a row lies in HBM). In the absorbed form a head's query is as wide
# as the row, `[q_nope W_kb^T | q_rope | 0...]`, its scores are one product
# with the row and its values are the row's first `lat` columns; W_kb and W_vb
# are applied to the query and to the output OUTSIDE these functions, by the
# model's layer step.
#
#   q:      (S, Bq, H, W) rectangular | (T, H, W) flat, as above
#   pool:   (L, P, ps, W): the WHOLE pool as it lies; `layer` picks the
#           layer by scalar prefetch, so no layer's pages are sliced out or
#           transposed on the way in (what ROADMAP S2 asks of the K/V kernels).
#           Or (G, P, ps, S x W), rows wider than q's: several layers' rows
#           side by side a token, `layer` a pair (group, place): the layer's
#           rows are lanes [place W, (place + 1) W) (`latent_lanes`), a
#           window of the page's DMA; `place` rides with `layer` in the
#           scalar prefetch, so a group's layers share ONE trace and one
#           lowering of the kernel
#   out:    (S, Bq, H, lat) | (T, H, lat)
#
# One Pallas kernel, `_latent_kernel`, serves both entry points, on the K/V
# kernel's data path (PR 36). Its grid walks QUERY BLOCKS of ONE sequence
# (`query_blocks` above): a decode row is a block of one token, a prefill
# slice is cut into ceil(n / 8) blocks of 8 tokens, and a block walks its
# sequence's pages up to its own last token. q (flat, padded to LATENT_Q_PAD
# tokens) and the pool are `memory_space=ANY` operands: a block reads its own
# tokens' rows by one DMA (164 KB a token) with the first tile's pages started
# behind it; a page is one 20 KB DMA and only REAL pages are read (the scratch
# is zeroed once); a padding block writes nothing. A tile is 64 pages (1,024
# context tokens) for a block of one token and 24 (384) for a block of many,
# two slots: the two products of a step are (H, W) x (W, 1024) and (H, 1024)
# x (1024, lat), or (8 H, W) x (W, 384) and (8 H, 384) x (384, lat), bf16
# with float32 accumulation; the scale is applied to the float32 scores and
# the softmax state (float32) lies in scratch, updated in place.
#
# A step ASKS for the next tile's pages, then waits for its own tile (the DMA
# semaphore counts bytes: one wait a whole tile), then multiplies. The walk
# has a fast part and a ragged end. Fast: every tile that is whole, seen
# whole by every row and followed by a whole tile: no count, no branch, no
# mask, the next tile's page DMAs unrolled into the products' own instruction
# stream; a block of one token lays them OVER the step, the first 24 before
# the wait and the rest a few behind every lane tile of columns of the two
# products (LATENT_ASK). The ragged end (at most two tiles of a decode row):
# pages counted, PAGE_RUN a loop step, scores masked.
#
# Swept on the v5e, five layers a call at 128 heads x 640 lanes, ms (PERF.md
# section 6, PR 36 and PR 58): 31 decode rows at 8.3k-8.9k tokens 8.27 before
# PR 36 -> 5.07 / 4.60 / 4.69 at 32 / 64 / 128 pages a step; a 128-token
# slice at 8.3k beside them +16.2 before -> +11.4 / +10.7 / +10.8 at 16 / 24 /
# 32 pages; 16 or 32 query tokens a block gain nothing on the slice (its
# products are whole MXU passes at 8) and do not fit 16 MB of VMEM. With q as
# the stationary operand (scores^T) decode rows lose (7.4 against 6.2 in the
# same form): the (tile, H) probabilities have to be transposed for the
# second product. PR 58, the walk's DMA order (kernel events alone, the same
# decode rows: 4.38 as PR 36 left it, which waited first): every start from a
# ROLLED loop that asks first 5.52, eight a loop step 4.58; unrolled, all 64
# before the wait 4.35, 8 / 16 / 32 of them before it 4.36; a third slot and
# two tiles ahead 4.57; the next block's first tile started from this
# block's last step (with the rolled forms) -0.0; the starts laid between
# the products' chunks 3.96-4.19 by where they lie (none may be left behind
# the last chunk: 4.19-4.35), as they lie now 3.96. The walk alone with no
# product takes 3.02 (565 GB/s: 2.91 with pages twice the size, so the
# chip's rate and not the descriptors'), the products alone with no DMA 2.87:
# the order decides how much of the two overlaps, and it is about half.
# 64 rows of 32 heads at 1.3k-5k (Kimi-Linear's tick, three layers): 1.73 ->
# 1.50 (asking first 1.66; the ragged end's pages eight a loop step the rest).
# Operations a context byte (H = 128, W = 640, lat = 512, bf16): a decode row
# 128 x (640 + 512) x 2 / 1280 = 230, the v5e's ridge (240); a block of 8
# tokens 8 x that: bound by the MXU, which is why prefill keeps the absorbed
# form too: expanding K and V from a tile costs 2 x 512 x 128 x 256 operations
# a context token a block before any score, more than the absorbed form's 8 x
# 128 x 1152 x 2 until a block holds ~160 tokens, and a slice holds 128.

# Query tokens a block of many, and context tokens a loop step (a tile) for
# a block of one token and for a block of many, AT 128 heads of 640 lanes
# (swept on the v5e, above); `latent_q_block` / `latent_kv_pages` scale them
# to other widths.
LATENT_Q_BLOCK = 8
LATENT_TILE_ONE = 1024
LATENT_TILE_MANY = 384
# Of a whole tile of 64 pages, how many a fast step starts after each lane
# tile of columns of its scores (8 of them at 1,024 context tokens) and of its
# values (4 at 512); the rest, 24 here, it starts before its wait.
LATENT_ASK = (3, 4)
# What the kernel may take of the 16 MB of VMEM the compiler scopes to a
# kernel on the v5e (tests/test_tpu_compile.py compiles it with this limit).
LATENT_VMEM_BUDGET = 14 * 2 ** 20
# The flat q is padded to a multiple of this many tokens (a last block's TQ
# tokens in bounds, and token buckets that share a trace): a token's q is
# 164 KB here, 20 x a K/V model's, so not Q_PAD's 256 (42 MB written a
# layer for a tick of 32 tokens).
LATENT_Q_PAD = 64


def latent_vmem_bytes(H: int, W: int, lat: int, ps: int, TQ: int,
                      pages_one: int, pages_many: int,
                      itemsize: int = 2) -> int:
    """Bytes of VMEM the latent kernel takes at these sizes, reckoned by
    hand: the two tile slots, the q scratch, the double-buffered output
    block, the float32 state (acc; m and l a lane tile wide each) with a
    successor of m and of the rescaling factor, one float32 copy of the
    larger block kind's scores, half a MiB of the compiler's own, and the 2
    MiB more that it takes past 64 query blocks. Held against the compiler's
    count at 128 x 640 (the least `vmem_limit_bytes` the kernel compiles
    under for a described v5e, at 56 and at 72 query blocks): 10.0 / 12.1,
    11.2 / 13.3, 11.8 / 13.7 MiB at pages (32, 16), (64, 16), (64, 24); this
    reckoning 12.0, 13.25, 13.75."""
    rows = TQ * H
    tiles = 2 * max(pages_one, pages_many) * ps * W * itemsize
    q = rows * W * itemsize
    out = 2 * rows * lat * itemsize
    state = rows * (lat + 4 * 128) * 4
    scores = 4 * max(H * pages_one, rows * pages_many) * ps
    compilers = 2 ** 19 + out           # its own, and past 64 query blocks
    return tiles + q + out + state + scores + compilers


def latent_q_block(heads: int, width: int) -> int:
    """Query tokens a block of many for a latent model of `heads` heads and
    `width`-lane rows: LATENT_Q_BLOCK at 128 x 640 (where it was swept), at
    other widths as many as keep a block's q (tokens x heads x width) and
    with it the float32 state no larger; from 8 on a whole number of sublane
    tiles."""
    tokens = LATENT_Q_BLOCK * 128 * 640 // (heads * width)
    return min(64, tokens // 8 * 8) if tokens >= 8 else max(1, tokens)


def latent_kv_pages(H: int, W: int, lat: int, ps: int):
    """(pages a step of a block of one token, pages a step of a block of
    many): the swept tiles at 128 heads, at other head counts tiles that
    keep the float32 scores no larger (1,024 tokens at most), halved down to
    one lane tile of context tokens until the buffers fit the budget."""
    TQ = latent_q_block(H, W)
    least = max(1, 128 // ps)        # pages of one lane tile of tokens

    def pages(tokens):
        return max(least, min(1024, tokens) // ps // least * least)

    one = pages(LATENT_TILE_ONE * 128 // H)
    many = pages(LATENT_TILE_MANY * LATENT_Q_BLOCK * 128 // (TQ * H))
    while (latent_vmem_bytes(H, W, lat, ps, TQ, one, many)
           > LATENT_VMEM_BUDGET and max(one, many) > least):
        if one >= many:
            one = pages(one * ps // 2)
        else:
            many = pages(many * ps // 2)
    return one, many


def latent_lanes(pool, layer, W: int):
    """(the layer's index in the pool's first axis, its lane block or None):
    by the SHAPES handed in, a pool whose rows are wider than the query's W
    holds several layers' rows side by side and `layer` is (group, place)."""
    return (layer, None) if pool.shape[-1] == W else layer


def latent_paged_attention_reference(q, pool, layer, block_tables, kv_lens,
                                     q_positions, *, scale: float, lat: int):
    """jnp reference of the absorbed form over the full padded context."""
    S, Bq, H, W = q.shape
    ps = pool.shape[2]
    max_ctx = block_tables.shape[1] * ps
    layer, place = latent_lanes(pool, layer, W)
    rows = pool[layer][block_tables]
    if place is not None:
        rows = rows[..., place * W:(place + 1) * W]
    rows = rows.reshape(S, max_ctx, W)
    logits = jnp.einsum("sqhw,skw->shqk", q, rows,
                        preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(max_ctx)[None, None, None, :]
    q_abs = (q_positions[:, None] + jnp.arange(Bq)[None, :])[:, None, :, None]
    mask = (k_pos < kv_lens[:, None, None, None]) & (q_abs >= k_pos)
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(rows.dtype)
    return jnp.einsum("shqk,skl->sqhl", probs, rows[..., :lat],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def latent_paged_attention_unified_reference(
        q, pool, layer, block_tables, kv_lens, q_positions, cu_q_lens, *,
        scale: float, lat: int):
    """Token-major reference: the flat rows scattered into the rectangle,
    then the SAME function as the rectangular reference (as
    ragged_paged_attention_unified_reference does for K/V pages)."""
    T, H, W = q.shape
    S = kv_lens.shape[0]
    seq = token_seq_ids(cu_q_lens, T, S)
    local = jnp.arange(T) - cu_q_lens[seq]
    valid = jnp.arange(T) < cu_q_lens[S]
    qr = jnp.zeros((S, T, H, W), q.dtype).at[
        seq, jnp.where(valid, local, T)].set(q, mode="drop")
    out_r = latent_paged_attention_reference(
        qr, pool, layer, block_tables, kv_lens, q_positions, scale=scale,
        lat=lat)
    out = out_r[seq, jnp.minimum(local, T - 1)]
    return jnp.where(valid[:, None, None], out, jnp.zeros_like(out))


def _latent_kernel(blk_seq_ref, blk_pos_ref, blk_n_ref, blk_tok_ref, meta_ref,
                   block_tables_ref, kv_lens_ref,            # scalar prefetch
                   q_hbm, pool_hbm,                          # tensor inputs
                   o_ref,                                    # output
                   q_scr, kv_scr, m_scr, l_scr, acc_scr, sems, q_sem,
                   *, ps: int, KB1: int, KBN: int, scale: float, TQ: int,
                   H: int, lat: int, windowed: bool):
    """Grid: (NB,). Block b is up to TQ query tokens of sequence blk_seq[b]:
    blk_n[b] of them are real (0: a padding block, which does nothing), the
    first is flat token blk_tok[b] of q_hbm (tokens, H, W) at absolute
    position blk_pos[b]. meta = (layer, real blocks). o_ref: (1, TQ * H, lat),
    rows token-major (t * H + h). q and the pool stay in HBM: a block reads
    its own tokens' rows, and kv_scr holds two tiles of context rows, KB1
    pages each for a block of one token and the first KBN of them for a block
    of many. The softmax state (m, l, acc; float32) lies in scratch and is
    updated in place. `windowed`: the pool's rows hold several layers', this
    one's in lane block meta[2] (a window of every page's DMA); else a row
    is this layer's alone.

    A step asks for the NEXT tile's pages, then waits for its own, then
    multiplies (`step` below; the comment above LATENT_Q_BLOCK has what was
    measured). Every block starts its own first tile: started from the block
    before it, it gained nothing."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b = pl.program_id(0)
    s = blk_seq_ref[b]
    n = blk_n_ref[b]
    q_pos = blk_pos_ref[b]
    tok0 = blk_tok_ref[b]
    layer = meta_ref[0]
    W = q_scr.shape[-1]
    # No row of the block sees past its last real token.
    kv_len = jnp.minimum(kv_lens_ref[s], q_pos + n)
    n_pages = pl.cdiv(kv_len, ps)

    @pl.when(b == 0)
    def _():
        # A tile's rows past the context's last page are not DMA'd: what
        # they hold is masked out of the scores but multiplied (by zero) in
        # the second product, so it has to be finite from the first block on.
        kv_scr[...] = jnp.zeros_like(kv_scr)

    def walk(nq: int, KB: int):
        """The block's first nq tokens' H rows each against the context, KB
        pages a step."""
        rows, tile = nq * H, KB * ps
        n_tiles = pl.cdiv(n_pages, KB)

        def page_dma(slot, i, j):
            page = pool_hbm.at[layer, block_tables_ref[s, i * KB + j]]
            if windowed:
                page = page.at[:, pl.ds(pl.multiple_of(meta_ref[2] * W, 128),
                                        W)]
            return pltpu.make_async_copy(
                page,
                kv_scr.at[slot, pl.ds(pl.multiple_of(j * ps, ps), ps)],
                sems.at[slot])

        def start(slot, i, lo, hi):
            """Start pages [lo, hi) of tile i (`start_pages`)."""
            start_pages(lambda j: page_dma(slot, i, j).start(), lo, hi)

        def start_real(slot, i):
            """Start the real pages of tile i, however many (none behind the
            last tile; `start_counted`)."""
            start_counted(lambda j: page_dma(slot, i, j).start(),
                          jnp.clip(n_pages - i * KB, 0, KB))

        def wait(slot, pages):
            """Wait for `pages` pages of the tile in `slot` at once (the
            semaphore counts bytes)."""
            got = kv_scr.at[slot, pl.ds(0, pages * ps)]
            pltpu.make_async_copy(got, got, sems.at[slot]).wait()

        # The block's rows out of the flat q, the first tile's pages started
        # behind them.
        copy = pltpu.make_async_copy(
            q_hbm.at[pl.ds(tok0, nq)], q_scr.at[pl.ds(0, nq)], q_sem)
        copy.start()
        start_real(0, 0)
        copy.wait()
        q_abs = q_pos + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // H
        k_off = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        m_scr[:rows] = jnp.full((rows, 1), NEG_INF, dtype=jnp.float32)
        l_scr[:rows] = jnp.zeros((rows, 1), dtype=jnp.float32)
        acc_scr[:rows] = jnp.zeros((rows, lat), dtype=jnp.float32)

        # A block of one token takes its two products a lane tile of columns
        # at a time, so that a step can start pages between them (`step`).
        score_chunks = max(1, tile // LANE) if nq == 1 else 1
        value_chunks = max(1, lat // LANE) if nq == 1 else 1

        def fold(i, slot, masked: bool, ask=lambda pages: None):
            """One online-softmax step over the tile in `slot`; `ask(k)`
            after every chunk of the scores (k = 0) and of the values (1)."""
            q = q_scr[:nq].reshape(rows, W)
            sc = []
            for c in range(score_chunks):
                at = pl.ds(c * (tile // score_chunks), tile // score_chunks)
                sc.append(jax.lax.dot_general(
                    q, kv_scr[slot, at], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale)
                ask(0)
            sc = jnp.concatenate(sc, axis=-1)                # (rows, tile)
            m = m_scr[:rows]
            if masked:
                k_pos = i * tile + k_off
                ok = k_pos < kv_len
                if nq > 1:      # one token sees its whole context
                    ok &= q_abs >= k_pos
                sc = jnp.where(ok, sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
            p = jnp.exp(sc - m_new)
            if masked:
                # Explicit zero where masked: a row whose tile is all masked
                # would otherwise add exp(NEG_INF - NEG_INF) == 1 a column.
                p = jnp.where(ok, p, 0.0)
            alpha = jnp.exp(m - m_new)
            m_scr[:rows] = m_new
            l_scr[:rows] = alpha * l_scr[:rows] + p.sum(
                axis=-1, keepdims=True)
            p = p.astype(kv_scr.dtype)
            for c in range(value_chunks):
                at = pl.ds(c * (lat // value_chunks), lat // value_chunks)
                acc_scr[:rows, at] = alpha * acc_scr[:rows, at] + jnp.dot(
                    p, kv_scr[slot, :tile, at],
                    preferred_element_type=jnp.float32)
                ask(1)

        def step(ragged: bool, i, carry):
            """Ask for tile i + 1, wait for tile i, fold it in. `ragged`
            False: tile i is whole, every row sees it whole and a whole tile
            follows it, so nothing is counted and nothing masked, and the
            next tile's page DMAs are laid out over the step (LATENT_ASK):
            the first before the wait, the rest between the products."""
            slot = jax.lax.rem(i, 2)
            if ragged:
                start_real(1 - slot, i + 1)
                here = jnp.minimum(KB, n_pages - i * KB)
                pl.when(here == KB)(lambda: wait(slot, KB))

                @pl.when(here < KB)
                def _():
                    def one(j, _):
                        wait(slot, 1)
                        return _

                    jax.lax.fori_loop(0, here, one, 0)

                fold(i, slot, masked=True)
                return carry
            between = [k * KB // 64 for k in LATENT_ASK]
            asked = [max(0, KB - between[0] * score_chunks
                         - between[1] * value_chunks)]
            start(1 - slot, i + 1, 0, asked[0])
            wait(slot, KB)

            def ask(k):
                upto = min(asked[0] + between[k], KB)
                start(1 - slot, i + 1, asked[0], upto)
                asked[0] = upto

            fold(i, slot, masked=False, ask=ask)
            return carry

        n_fast = jnp.maximum(
            jnp.minimum(n_pages // KB, jnp.minimum(q_pos + 1, kv_len) // tile)
            - 1, 0)
        jax.lax.fori_loop(0, n_fast, functools.partial(step, False), 0)
        jax.lax.fori_loop(n_fast, n_tiles, functools.partial(step, True), 0)
        o_ref[0, :rows] = (acc_scr[:rows] / jnp.maximum(
            l_scr[:rows], 1e-30)).astype(o_ref.dtype)

    @pl.when((n > 0) & (n_pages == 0))
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

    @pl.when((n_pages > 0) & (n == 1))
    def _():
        walk(1, KB1)

    if TQ > 1:
        @pl.when((n_pages > 0) & (n > 1))
        def _():
            walk(TQ, KBN)


def _latent_call(q, blk_seq, blk_pos, blk_n, blk_tok, nb_real, pool, layer,
                 block_tables, kv_lens, place=None, *, scale, lat, TQ,
                 kv_pages, interpret):
    """q (tokens, H, W), every block's TQ tokens from blk_tok[b] in bounds ->
    the blocks' outputs (NB, TQ * H, lat). Of a padding block (b >= nb_real)
    nothing is written. kv_pages: (pages a step of a block of one token, of a
    block of many). `place`: the layer's lane block of a pool whose rows hold
    several layers' (`latent_lanes`), else None."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, H, W = q.shape
    NB = blk_seq.shape[0]
    ps = pool.shape[2]
    one, many = kv_pages

    def out_block(b, seq, pos, n, tok, meta, *_):
        # A padding block keeps the last real block's buffer (and leaves it
        # alone), so nothing of it is written back.
        return jnp.minimum(b, jnp.maximum(meta[1] - 1, 0)), 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(NB,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),   # q: a block reads its rows
            pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, TQ * H, lat), out_block),
        scratch_shapes=[
            pltpu.VMEM((TQ, H, W), q.dtype),
            pltpu.VMEM((2, max(one, many) * ps, W), pool.dtype),
            pltpu.VMEM((TQ * H, 1), jnp.float32),
            pltpu.VMEM((TQ * H, 1), jnp.float32),
            pltpu.VMEM((TQ * H, lat), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA(()),
        ],
    )
    kernel = functools.partial(
        _latent_kernel, ps=ps, KB1=one, KBN=many, scale=scale, TQ=TQ, H=H,
        lat=lat, windowed=place is not None)
    meta = jnp.stack([jnp.asarray(v, jnp.int32) for v in (
        (layer, nb_real) if place is None else (layer, nb_real, place))])
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (NB, TQ * H, lat), q.dtype, vma=vma_of(q, pool)),
        interpret=interpret,
        **kernel_tag("paged_attention_latent_unified"),
    )(blk_seq, blk_pos, blk_n, blk_tok, meta, block_tables, kv_lens, q, pool)


# Jitted and named as the K/V kernel's entries are, and for the same reasons
# (one trace a process for each set of shapes; the HLO instruction is
# `paged_attention_latent_call.<n>`, which the benchmark's readers find by
# `paged_attention_`).
@functools.partial(jax.jit, static_argnames=(
    "scale", "lat", "TQ", "kv_pages", "interpret"))
def paged_attention_latent_call(*args, **static):
    return _latent_call(*args, **static)


def latent_paged_attention_unified(q, pool, layer, block_tables, kv_lens,
                                   q_positions, cu_q_lens, *, scale: float,
                                   lat: int,
                                   interpret: Optional[bool] = None):
    """Pallas latent paged attention over a flat mixed batch (layouts as
    ragged_paged_attention_unified; pool and `layer` as above). The kernel
    reads each query block's rows out of the flat q (padded to LATENT_Q_PAD
    tokens, so an engine's token buckets share three traces) and writes the
    blocks' outputs, which are gathered back into the flat order."""
    T, H, W = q.shape
    S = kv_lens.shape[0]
    TQ = latent_q_block(H, W)
    layer, place = latent_lanes(pool, layer, W)
    padded = -(-(T + TQ) // LATENT_Q_PAD) * LATENT_Q_PAD
    seq, local, blk_n, slot_tok, first = query_blocks(
        cu_q_lens, padded, S, TQ)
    out = paged_attention_latent_call(
        jnp.pad(q, ((0, padded - T), (0, 0), (0, 0))),
        seq.astype(jnp.int32),
        (q_positions[seq] + local * TQ).astype(jnp.int32),
        blk_n.astype(jnp.int32), slot_tok[:, 0].astype(jnp.int32),
        jnp.sum(blk_n > 0), pool, layer, block_tables, kv_lens, place,
        scale=scale, lat=lat, TQ=TQ,
        kv_pages=latent_kv_pages(H, W, lat, pool.shape[2]),
        interpret=_interpret(interpret))
    return blocks_to_tokens(out, cu_q_lens, first, T, S, TQ, H)


def latent_paged_attention(q, pool, layer, block_tables, kv_lens,
                           q_positions, *, scale: float, lat: int,
                           interpret: Optional[bool] = None):
    """Pallas latent paged attention, rectangular: every sequence brings Bq
    query tokens (1: decode). The same kernel; the blocks are the rectangle's
    own rows, ceil(Bq / q_block) a sequence."""
    S, Bq, H, W = q.shape
    TQ = min(latent_q_block(H, W), Bq)
    layer, place = latent_lanes(pool, layer, W)
    per_seq = -(-Bq // TQ)
    pad = per_seq * TQ - Bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    NB = S * per_seq
    local = jnp.tile(jnp.arange(per_seq, dtype=jnp.int32), S)
    seq = jnp.repeat(jnp.arange(S, dtype=jnp.int32), per_seq)
    out = paged_attention_latent_call(
        q.reshape(NB * TQ, H, W), seq, q_positions[seq] + local * TQ,
        jnp.clip(Bq - local * TQ, 0, TQ),
        jnp.arange(NB, dtype=jnp.int32) * TQ, NB, pool, layer, block_tables,
        kv_lens, place, scale=scale, lat=lat, TQ=TQ,
        kv_pages=latent_kv_pages(H, W, lat, pool.shape[2]),
        interpret=_interpret(interpret))
    return out.reshape(S, per_seq * TQ, H, lat)[:, :Bq]
