"""The Mamba-2 recurrence (state-space duality, arXiv:2405.21060) over ragged
token-major rows: the recurrent step for a decode row and the chunked form,
which is matrix products, for a prompt slice, in one call a layer.

For head h of a sequence, with x_t (P values), dt_t > 0 (after the softplus),
A_h < 0, and B_t, C_t (N each) shared by the H / G heads of a GROUP,

  a_t = exp(dt_t A_h);  S_t = a_t S_(t-1) + (dt_t x_t) B_t^T;  y_t = S_t C_t.

The state S (P x N, float32) is a MATRIX a head with ONE decay a head and
token (ops/ssm_scan.py's Mamba-1 state is diagonal: a decay a channel and
state dimension, B and C scalars a token). One decay a head means a decode
row need NOT rewrite it (ops/power_retention.py's scheme; a delta rule,
ops/kda.py's, cannot take it): the last rows' dt x, B and decays lie in a
BUFFER beside the state, a row is answered from the state as the last fold
left it (S0) and the buffered rows s, c_s the logs of a summed from the fold
through row s (every exponent <= 0),

  y_t = e^(c_t) S0 C_t + sum_(s <= t) e^(c_t - c_s) (C_t . B_s) (dt x)_s,

and the buffer is FOLDED into the state once in FOLD rows:
S <- e^(c_last) S0 + sum_s e^(c_last - c_s) (dt x)_s B_s^T. So (state, buffer,
fill) together are the recurrence's S_t (`folded`), and a decode row READS its
state once and writes one row. All three are a SLOT a sequence
(llm/model_runner.py, "Layer groups": a state group):

  state   (layers, slots + 1, H, P, N) float32; the last slot is nobody's
          (padding sequences read it)
  buffer  (layers, slots + 1, H / HB, T, LW) float32: a TILE for the HB heads
          a grid step holds (`heads_a_step`: one group's 16 at the published
          widths), read whole and written whole by that step and no other.
          With hp = HB / 2 its rows are: [s hp, (s + 1) hp) buffered row s's
          dt x, head k of the block in lanes [0, P) of row k and head hp + k
          in lanes [P, 2 P) (whole 128-lane rows at P 64; one (8, 128) tile a
          buffered row at 16 heads); [FOLD hp, FOLD hp + FOLD) the rows' B
          (the block's GROUP's, N lanes, never broadcast to the heads); then
          HB rows of logs, head h's c_s in lane s. Rows and lanes from the
          fill on are stale and never read. 44 KB at the published widths
          beside the block's 512 KB of S. Where every head is a group of its
          own (G = H: a linear-attention layer whose keys and queries are a
          HEAD's, models/minicpm_sala.py) a step's HB heads each bring their
          B, laid as dt x is: FOLD x hp rows, head k's in lanes [0, N) of
          row s hp + k and head hp + k's in lanes [N, 2 N), then the logs
          (FOLD 8, 16 heads of 128 x 128: 144 rows of 256 lanes, 147 KB
          beside 1 MB of S); nothing else of the scheme differs
  fill    (layers, slots + 1) int32: rows the buffer holds, 0 .. FOLD - 1

The slots' contract (`slots`, `starts`, `lens`, `zero`, the junk slot, the
fill's rule) is ops/state_slots.py's. The skip `D x_t`, the gate and the
grouped norm behind it are the layer's (models/nemotron_h.py), as is the
convolution before it (`ops/ssm_scan.ragged_conv`).

  `ssd_reference`   the recurrence as a `lax.scan` over time from `folded`,
                    the sequences side by side: the tests' oracle and the
                    path off the chip; it hands back state, buffer and fill
                    by the same rule
  `ssd`             the Pallas kernel where `impl == "pallas"`

The kernel reads the step's rows WHERE THEY LIE, token-major as ops/kda.py's
(the row is the untiled leading axis, so a block or a DMA may start at any
row): x (rows, H, 2 P) = [dt x | log a in every lane] a head, and bc (rows, G,
2 N) = [B | C] a GROUP. The grid is (sequences, H / HB) in order. A step's
state block and buffer tile are blocked INPUTS indexed by scalar prefetch
(Pallas fetches the next step's while this one computes); the tile is a
blocked output too, but the state leaves only by the kernel's own DMA, where
a row folds and where a slice ends (a blocked output would be written back at
every step, touched or not), waited for at the next such write or at the
grid's last step. State and buffer are aliased in and out.

  one row (a decode row): the state's term on the VPU, a multiply and a lane
      reduce a tile; the heads' sums reach their rows by ONE transpose a grid
      step. The buffered rows' term: C_t . B_s for all of them as one (1,
      N)(N, FOLD) product, the weights e^(c_t - c_s) (C_t . B_s) a head and
      row, and a multiply-add of one (hp, 2 P) tile a row the buffer holds.
      A fold is a transpose a PAIR of heads and the slice path's `into`
      product a head, (P, rows)(rows, N) at `HIGHEST`, in one rolled loop
      that a decode row and a slice share.
  more rows (a slice): chunks of `chunk` rows. With l_i the logs of a summed
      from the chunk's first row through row i (a product with a triangle of
      ones) and G_ij = C_i . B_j, computed once a chunk for the group's heads,

        Y = (G * exp(l_i - l_j) for j <= i) (dt X) + exp(l_i) C S_0^T,
        S <- exp(l_Q) S_0 + (dt X exp(l_Q - l_j))^T B.

      Every exponent is <= 0: no running product is inverted. Products are
      float32 at `HIGHEST`. Rows past the segment decay nothing and add
      nothing, so the last row holds the chunk's whole decay.

A slice's chunk is one DMA of (chunk, HB, 2 P) from row `starts[s] + t
chunk` on; its output goes back the same way, whole, so its last rows may
overhang the segment: they land on rows of LATER sequences, which the grid
writes afterwards (a decode row's in an array of their own), or on the
`chunk` spare rows behind the last (as ops/kda.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_tag
from ray_tpu.ops.state_slots import (answers, enter, fill_shape, filled,
                                     first_fill, interpreted, joins,
                                     state_block, tile_block)

# Rows a step of the chunked form takes (the published `chunk_size`), the
# most heads a grid step holds (one group's 16 at the published widths), and
# the rows a slot's buffer holds before it is folded (PERF.md section 6 has
# the sweep: 16 is 5% faster alone, and in the step program its 0.19 GB more
# sent XLA to recompute `in_proj` four times a slice tick).
CHUNK = 128
HEADS = 16
FOLD = 8
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def state_shape(layers: int, slots: int, heads: int, head_dim: int,
                d_state: int):
    """S of `slots` sequences and the junk slot behind them."""
    return fill_shape(layers, slots) + (heads, head_dim, d_state)


def heads_a_step(heads: int, groups: int, head_dim: int) -> int:
    """Heads a grid step holds, and a buffer's tile serves: of ONE group, or,
    where every head is a group of its own, any."""
    per = heads // groups if groups < heads else heads
    return next(b for b in range(min(HEADS, per, 2 * head_dim), 0, -1)
                if per % b == 0)


def _key_rows(fold: int, hb: int, own: bool) -> int:
    """Rows of a tile that hold the buffered rows' B: one a row for a group's
    heads, hb / 2 where every head brings its own (`own`)."""
    return fold * (hb // 2) if own else fold


def buffer_shape(layers: int, slots: int, heads: int, groups: int,
                 head_dim: int, d_state: int, fold: int = FOLD):
    """The buffered rows beside `state_shape`'s S: a tile a block of
    `heads_a_step` heads (the module docstring lays it out)."""
    hb = heads_a_step(heads, groups, head_dim)
    own = groups == heads
    lanes = max(2 * head_dim, (2 if own else 1) * d_state)
    if hb % 2 or fold > lanes:
        raise ValueError(f"a buffer tile pairs the {hb} heads of a step and "
                         f"holds row s's log in lane s of {lanes}: {fold}")
    return fill_shape(layers, slots) + (
        heads // hb, fold * (hb // 2) + _key_rows(fold, hb, own) + hb, lanes)


def _fold_rows(tile_rows: int, hb: int, own: bool = False) -> int:
    """FOLD of a tile of `tile_rows` rows for `hb` heads."""
    return (tile_rows - hb) // (hb if own else hb // 2 + 1)


def _pack(rows, hb: int):
    """(..., H, P) -> (..., H / hb, hb / 2, 2 P): a block's head k beside its
    head hb / 2 + k."""
    *lead, H, P = rows.shape
    paired = rows.reshape(*lead, H // hb, 2, hb // 2, P)
    return jnp.moveaxis(paired, -3, -2).reshape(*lead, H // hb, hb // 2,
                                                2 * P)


def _unpack(rows, r: int, hp: int, width: int):
    """`_pack`'s inverse over a tile's rows (..., r hp, >= 2 width) ->
    (..., r, 2 hp, width)."""
    lead = rows.shape[:-2]
    paired = rows[..., :2 * width].reshape(lead + (r, hp, 2, width))
    return jnp.moveaxis(paired, -2, -3).reshape(lead + (r, 2 * hp, width))


def folded(state, buf, fill, own: bool = False):
    """The recurrence's S_t of slots whose parts are given as they lie:
    state (..., H, P, N), buf (..., J, T, LW), fill (...) -> state with the
    buffer's first `fill` rows folded in. `own`: every head a group of its
    own (the tile holds a B a head)."""
    H, P, N = state.shape[-3:]
    J, T = buf.shape[-3:-1]
    hb = H // J
    hp = hb // 2
    r = _fold_rows(T, hb, own)
    kr = _key_rows(r, hb, own)
    lead = buf.shape[:-2]                                        # (..., J)
    dtx = _unpack(buf[..., :r * hp, :], r, hp, P)           # (.., r, hb, P)
    if own:
        B = _unpack(buf[..., r * hp:r * hp + kr, :], r, hp, N)
    else:
        B = buf[..., r * hp:r * hp + r, None, :N]         # (.., r, 1, N)
    c = buf[..., r * hp + kr:, :r]                               # (.., hb, r)
    f = fill[..., None, None, None]
    at = jnp.arange(r)
    c_last = jnp.sum(jnp.where(at == f - 1, c, 0.0), -1, keepdims=True)
    keep = jnp.where(at < f, jnp.exp(jnp.minimum(c_last - c, 0.0)), 0.0)
    live = (at < f)[..., 0, :, None, None]                    # (.., r, 1, 1)
    add = jnp.einsum("...hs,...shp,...shn->...hpn", keep,
                     jnp.where(live, dtx, 0.0),
                     jnp.where(live, jnp.broadcast_to(
                         B, B.shape[:-2] + (hb, N)), 0.0), precision=HIGHEST)
    held = jnp.exp(c_last)[..., None]            # (.., J, hb, 1, 1); f 0: 1
    return (held * state.reshape(lead + (hb, P, N)) + add).reshape(
        state.shape)


def ssd_reference(x, dt, A, B, C, state, buf, fill, layer, slots, starts,
                  lens, zero):
    """The recurrence, a row at a time: x (R, H, P), dt (R, H) after the
    softplus, A (H,) negative, B / C (R, G, N), float32; state / buf / fill
    `state_shape`'s / `buffer_shape`'s / `fill_shape`'s; slots / starts /
    lens / zero (S,). -> (y (R, H, P) float32 without the skip, rows outside
    every segment zero; state; buf; fill, the sequences' slots written by the
    fill's rule: the recurrence runs from `folded` and a sequence of one row,
    where its buffer has room, is handed back as it came with the row in its
    buffer)."""
    R, H, P = x.shape
    G, N = B.shape[1:]
    J, T = buf.shape[2:4]
    hb = H // J
    hp = hb // 2
    own = G == H
    r = _fold_rows(T, hb, own)
    kr = _key_rows(r, hb, own)
    x, dt, A, B, C = (a.astype(F32) for a in (x, dt, A, B, C))
    keep = lambda z, a: jnp.where(
        z.reshape((-1,) + (1,) * (a.ndim - 1)), 0, a)
    f0 = first_fill(fill, layer, slots, zero)                     # (S,)
    held = keep(zero, state[layer, slots])
    tiles = buf[layer, slots]                                 # (S, J, T, LW)
    s0 = folded(held, tiles, f0, own)
    rows = jnp.clip(starts[:, None] + jnp.arange(R)[None, :], 0, R - 1)
    live = jnp.arange(R)[None, :] < lens[:, None]                 # (S, R)
    per = H // G

    def step(s, xs):
        x_t, dt_t, B_t, C_t, live_t = xs
        sg = s.reshape(-1, G, per, P, N)         # a group's heads side by side
        new = (jnp.exp(dt_t * A).reshape(-1, G, per, 1, 1) * sg
               + (dt_t[..., None] * x_t).reshape(-1, G, per, P, 1)
               * B_t[:, :, None, None, :])
        sg = jnp.where(live_t[:, None, None, None, None], new, sg)
        y = jnp.einsum("sghpn,sgn->sghp", sg, C_t, precision=HIGHEST)
        return sg.reshape(s.shape), y.reshape(-1, H, P)

    move = lambda a: jnp.moveaxis(a[rows], 1, 0)
    s1, y = jax.lax.scan(step, s0, (move(x), move(dt), move(B), move(C),
                                    live.T))
    y = jnp.moveaxis(y, 0, 1)                                 # (S, R, H, P)
    flat = jnp.zeros(x.shape, F32).at[jnp.where(live, rows, R)].set(
        y, mode="drop")
    # The fill's rule: one row that leaves room joins the buffer and the
    # state stays as it was held; everything else hands back S_t.
    stay = joins(lens, zero, f0, r)
    at = rows[:, 0]
    logs = tiles[:, :, r * hp + kr:, :r]                      # (S, J, hb, r)
    c_t = (jnp.sum(jnp.where(jnp.arange(r) == f0[:, None, None, None] - 1,
                             logs, 0.0), -1)
           + (dt * A)[at].reshape(-1, J, hb))                     # (S, J, hb)
    # The row's B as the tile holds it: a group's (S, J, 1, N), or every
    # head's own, packed as dt x is (S, J, hp, 2 N).
    b_t = (_pack(B[at], hb) if own
           else B[at][:, jnp.arange(J) * hb // per][:, :, None, :])

    def join(tile, f, dtx_t, b_rows, c_row):
        put = jax.lax.dynamic_update_slice
        tile = put(tile, dtx_t, (0, f * hp, 0))
        tile = put(tile, b_rows, (0, r * hp + f * (kr // r), 0))
        return put(tile, c_row[:, :, None], (0, r * hp + kr, f))

    joined = jax.vmap(join)(tiles, f0, _pack((dt[..., None] * x)[at], hb),
                            b_t, c_t)
    pick = lambda a, b: jnp.where(
        stay.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
    put = lambda whole, part: whole.at[layer, slots].set(part, mode="drop")
    return (flat, put(state, pick(held, s1)), put(buf, pick(joined, tiles)),
            filled(fill, layer, slots, stay, f0))


def _ssd_kernel(meta_ref, slots_ref, starts_ref, lens_ref, zero_ref, fill_ref,
                x_ref, bc_ref, s_in_ref, b_in_ref, x_hbm, bc_hbm, od_ref,
                os_hbm, s_hbm, b_ref, x_scr, bc_scr, o_scr, t_scr, s_scr,
                d_scr, p_scr, bw_scr, k_scr, *rest, HB: int, P: int,
                N: int, TC: int, R: int, per_group: int):
    """Grid (S, H / HB): sequence s, heads [j HB, (j + 1) HB), all of group
    j HB // per_group, or, where per_group is 1 (`own`), each a group of its
    own: bc_ref is then (HB, 2 N), these heads' [B | C], bc_scr a chunk's of
    these heads, and two more scratch arrays hold a fold's B a head (the
    halves of the tile's packed rows). s_in_ref (HB, P, N): their state as
    the last fold left
    it; s_hbm the whole state in HBM (the same memory: aliased), written from
    s_scr where a row folds and where a slice ends. b_in_ref / b_ref (T, LW):
    their buffer tile, aliased. x_ref (HB, 2 P): these heads of
    the step's row `starts[s]`, where it lies, [dt x | log a in every lane];
    bc_ref (G, 2 N): that row's [B | C], every group's; x_hbm / bc_hbm the
    same rows in HBM, for a slice's chunks. od_ref (HB, 2 P): a decode row's
    y in its first P lanes, at the same row of o; os_hbm (rows, H, 2 P): a
    slice's. fill_ref: rows the slot's buffer holds (0 where the sequence
    starts)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    *kb_scr, flag, sems = rest
    own = per_group == 1
    s = pl.program_id(0)
    j = pl.program_id(1)
    first = (s == 0) & (j == 0)
    last = (s == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1)
    layer = meta_ref[0]
    slot = slots_ref[s]
    n = lens_ref[s]
    row0 = starts_ref[s]
    fresh = zero_ref[s] != 0
    f = fill_ref[s]
    g = (j * HB) // per_group
    W = 2 * P
    hp = HB // 2
    B0 = R * hp
    C0 = B0 + _key_rows(R, HB, own)
    LW = b_ref.shape[1]
    heads = pl.ds(pl.multiple_of(j * HB, HB), HB)
    dot = functools.partial(jax.lax.dot_general, precision=HIGHEST,
                            preferred_element_type=F32)
    nn = (((1,), (0,)), ((), ()))
    nt = (((1,), (1,)), ((), ()))
    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape,
                                                        axis)

    def leave():
        return pltpu.make_async_copy(s_scr, s_hbm.at[layer, slot, heads],
                                     sems.at[3])

    def left():
        """The last write of s_scr has left it."""
        @pl.when(flag[0] == 1)
        def _():
            leave().wait()
            flag[0] = 0

    def buffered(rows, keys, logs, c_last, held):
        """What a fold reads, laid in scratch: the tile's first `rows` rows
        of dt x in d_scr (zeros behind), their B `keys` (R, N) in bw_scr
        (zeros behind), a head's decays e^(c_last - c_s) in lane s of k_scr
        and beneath them what it keeps of S0: logs (HB, LW) holds c_s in lane
        s, c_last and held (HB, 1) a head."""
        def lay(r, carry):
            d_scr[r] = jnp.where(r < rows, b_ref[pl.ds(r * hp, hp), 0:W],
                                 0.0)
            return carry

        jax.lax.fori_loop(0, R, lay, 0, unroll=True)
        if own:     # a head's B: the two halves of the tile's packed rows
            def lay_keys(r, carry):
                at = pl.ds(pl.multiple_of(B0 + r * hp, hp), hp)
                for half, scr in enumerate(kb_scr):
                    scr[r] = jnp.where(
                        r < rows, b_ref[at, half * N:(half + 1) * N], 0.0)
                return carry

            jax.lax.fori_loop(0, R, lay_keys, 0, unroll=True)
        else:
            bw_scr[0:R, :] = jnp.where(iota((R, N), 0) < rows, keys, 0.0)
        k_scr[0:HB, :] = jnp.where(iota((HB, LW), 1) < rows, jnp.exp(
            jnp.minimum(c_last - logs, 0.0)), 0.0)
        k_scr[HB:2 * HB, :] = jnp.broadcast_to(held, (HB, LW))

    @pl.when(first)
    def _():
        flag[0] = 0
        p_scr[...] = jnp.zeros_like(p_scr)
        bw_scr[...] = jnp.zeros_like(bw_scr)

    # The tile leaves as it came, but for the row that joins it.
    b_ref[...] = b_in_ref[...]
    folds = ((n == 1) & (fresh | (f + 1 >= R))) | ((n > 1) & (f > 0))

    @pl.when(n == 1)
    def _one_row():
        tile = x_ref[...]                                        # (HB, W)
        low = iota((hp, W), 1) < P
        new = jnp.where(low, tile[0:hp], pltpu.roll(tile[hp:HB], P, 1))
        # The row joins the buffer at its fill: dt x, B and c_t = c of the
        # row before it + its log a.
        lane = iota((HB, LW), 1)
        logs = b_in_ref[C0:C0 + HB, :]
        c_t = jnp.sum(jnp.where(lane == f - 1, logs, 0.0), axis=1,
                      keepdims=True) + tile[:, P:P + 1]          # (HB, 1)
        logs = jnp.where(lane == f, c_t, logs)
        b_ref[pl.ds(pl.multiple_of(f * hp, hp), hp), 0:W] = new
        if own:
            # Every head's own B and C, packed as dt x is: head k's in the
            # first N lanes of row k, head hp + k's in the next N.
            both = bc_ref[...]                                   # (HB, 2 N)
            low_n = iota((hp, 2 * N), 1) < N
            b_new = jnp.where(low_n, both[0:hp],
                              pltpu.roll(both[hp:HB], N, 1))
            c_pair = jnp.where(low_n, pltpu.roll(both[0:hp], N, 1),
                               both[hp:HB])
            c_rows = bc_ref[:, N:2 * N]                          # (HB, N)
            keys = None
            b_ref[pl.ds(pl.multiple_of(B0 + f * hp, hp), hp),
                  0:2 * N] = b_new
            b_ref[C0:C0 + HB, :] = logs

            # C_t . B_s a head, in lane s: a multiply and a lane reduce a
            # buffered row (the heads' keys differ: no product serves two).
            def column(r, cb):
                pair = c_pair * jnp.where(
                    r == f, b_new, b_in_ref[
                        pl.ds(pl.multiple_of(B0 + r * hp, hp), hp), 0:2 * N])
                col = jnp.concatenate(
                    [jnp.sum(jnp.where(low_n, pair, 0.0), axis=1,
                             keepdims=True),
                     jnp.sum(jnp.where(low_n, 0.0, pair), axis=1,
                             keepdims=True)], axis=0)            # (HB, 1)
                return jnp.where(lane == r, col, cb)

            cb = jax.lax.fori_loop(0, R, column, jnp.zeros((HB, LW), F32),
                                   unroll=True)
        else:
            # This group's B and C out of the row's G (a masked sum: Mosaic
            # loads no sublane at a traced index).
            mine = iota((bc_ref.shape[0], N), 0) == g
            b_row = jnp.sum(jnp.where(mine, bc_ref[:, 0:N], 0.0), axis=0,
                            keepdims=True)                       # (1, N)
            c_row = jnp.sum(jnp.where(mine, bc_ref[:, N:2 * N], 0.0), axis=0,
                            keepdims=True)
            keys = jnp.where(iota((R, N), 0) == f, b_row,
                             b_in_ref[B0:B0 + R, 0:N])           # (R, N)
            b_ref[B0:B0 + R, 0:N] = keys
            b_ref[C0:C0 + HB, :] = logs
            # The buffered rows, this one among them: C_t . B_s in lane s
            # (against whole lane tiles of zeros: a product R lanes wide has
            # a layout whose columns Mosaic cannot slice), then a weight a
            # head and row, its column picked by a mask.
            cb = dot(jnp.broadcast_to(c_row, (8, N)), jnp.concatenate(
                [keys, jnp.zeros((LW - R, N), F32)], 0), nt)[0:1]  # (1, LW)
        w = jnp.where(lane <= f, cb * jnp.exp(
            jnp.minimum(c_t - logs, 0.0)), 0.0)                  # (HB, LW)

        def row(r, acc):
            col = jnp.sum(jnp.where(lane == r, w, 0.0), axis=1,
                          keepdims=True)                         # (HB, 1)
            return acc + jnp.where(
                r <= f, b_ref[pl.ds(r * hp, hp), 0:W], 0.0) * jnp.where(
                low, jnp.broadcast_to(col[0:hp], (hp, W)),
                jnp.broadcast_to(col[hp:HB], (hp, W)))

        # (rows behind the fill weigh nothing, but what lies there is stale)
        acc = jax.lax.fori_loop(0, R, row, jnp.zeros((hp, W), F32),
                                unroll=True)
        # The state as the last fold left it: a head's sums over the lanes
        # stand down the sublanes; side by side (head h in lane h) and
        # transposed they are rows.
        at = iota((P, W), 1)

        def head(h, ys):
            mine = c_row if not own else jnp.sum(jnp.where(
                iota((HB, N), 0) == h, c_rows, 0.0), axis=0, keepdims=True)
            return jnp.where(at == h, jnp.sum(s_in_ref[h] * mine, axis=1,
                                              keepdims=True), ys)

        t_scr[0:P, :] = jax.lax.fori_loop(0, HB, head,
                                          jnp.zeros((P, W), F32),
                                          unroll=True)
        since = jnp.where(fresh, 0.0, jnp.exp(c_t))              # (HB, 1)
        held = jnp.where(fresh, 0.0, since * t_scr[...].T[0:HB, :])
        od_ref[0:hp, :] = held[0:hp] + acc
        od_ref[hp:HB, :] = held[hp:HB] + pltpu.roll(acc, P, 1)

        @pl.when(folds)
        def _():
            buffered(f + 1, keys, logs, c_t, since)

    @pl.when((n > 1) & folds)   # a sequence that was parked among its rows
    def _():
        logs = b_in_ref[C0:C0 + HB, :]
        c_last = jnp.sum(jnp.where(iota((HB, LW), 1) == f - 1, logs, 0.0),
                         axis=1, keepdims=True)
        buffered(f, None if own else b_in_ref[B0:B0 + R, 0:N], logs, c_last,
                 jnp.exp(c_last))

    @pl.when(folds)
    def _fold():
        """s_scr <- what S0 keeps + the buffered rows, row s decayed by
        e^(c_last - c_s), as `buffered` laid them. A pair of heads to an
        iteration: their rows lie side by side."""
        left()

        def pair(k, carry):
            p_scr[0:R, :] = d_scr[:, k, :]
            both = p_scr[...].T              # (2 P, 2 P): [p of k ; of hp + k]
            for half in range(2):
                h = half * hp + k
                if own:
                    bw_scr[0:R, :] = kb_scr[half][:, k, :]
                into = (both[half * P:(half + 1) * P, :]
                        * k_scr[pl.ds(h, 1), 0:W])
                # (a row at a traced index is loaded at its full width)
                kept = (k_scr[pl.ds(HB + h, 1), 0:N] if LW == N
                        else k_scr[pl.ds(HB + h, 1), :][:, 0:N])
                s_scr[h] = (jnp.where(fresh, 0.0, kept * s_in_ref[h])
                            + dot(into, bw_scr[...], nn))
            return carry

        jax.lax.fori_loop(0, hp, pair, 0)

        @pl.when(n == 1)
        def _():
            leave().start()
            flag[0] = 1

    @pl.when(n > 1)
    def _slice():
        left()

        @pl.when(f == 0)
        def _():
            s_scr[...] = jnp.where(fresh, 0.0, s_in_ref[...])

        r_i = iota((TC, TC), 0)
        c_i = iota((TC, TC), 1)
        ones = jnp.where(c_i <= r_i, 1.0, 0.0)

        def chunk(t, carry):
            base = row0 + t * TC
            real = jnp.minimum(TC, n - t * TC)
            loads = [
                pltpu.make_async_copy(x_hbm.at[pl.ds(base, TC), heads],
                                      x_scr, sems.at[0]),
                # (every group's: one group's rows are no whole tiles; these
                # heads' where each is a group)
                pltpu.make_async_copy(
                    bc_hbm.at[pl.ds(base, TC), heads] if own
                    else bc_hbm.at[pl.ds(base, TC)], bc_scr, sems.at[1])]
            for copy in loads:
                copy.start()
            for copy in loads:
                copy.wait()
            valid = iota((TC, 1), 0) < real

            def keyed(at):
                """B (rows past the segment zero), C and C_i . B_j (TC, TC)
                of group (or head) `at` of the chunk."""
                bc = bc_scr[:, at, :]                            # (TC, 2 N)
                bb = jnp.where(valid, bc[:, 0:N], 0.0)
                cc = bc[:, N:2 * N]
                return bb, cc, dot(cc, bb, nt)

            # a group's: once a chunk for all its heads
            shared = None if own else keyed(g)

            def head(h, carry):
                bb, cc, cb = keyed(h) if own else shared
                x = jnp.where(valid, x_scr[:, h, :], 0.0)        # (TC, W)
                # l_i in every lane of row i; its transpose holds l_j.
                l_c = dot(ones, jnp.broadcast_to(x[:, P:P + 1], (TC, TC)),
                          nn)
                m = jnp.where(c_i <= r_i, cb * jnp.exp(
                    jnp.minimum(l_c - l_c.T, 0.0)), 0.0)
                l_w = jnp.broadcast_to(l_c[:, 0:1], (TC, W))
                # l_Q as a row of the product itself (a slice of a broadcast
                # is a broadcast of one element to sublanes AND lanes, which
                # Mosaic does not take).
                last = lambda width: (
                    l_c[TC - 1:TC] if width == TC else
                    jnp.concatenate([l_c[TC - 1:TC]] * (width // TC), 1)
                    if width % TC == 0 else
                    jnp.broadcast_to(l_c[TC - 1:TC, 0:1], (1, width)))
                state = s_scr[h]                                 # (P, N)
                # (the lanes past P carry the logs along: nobody reads them)
                o_scr[:, h, :] = dot(m, x, nn) + jnp.exp(l_w) * dot(
                    cc, jnp.concatenate([state, state], 0), nt)
                into = (x * jnp.exp(last(W) - l_w)).T
                s_scr[h] = (jnp.exp(last(N)) * state
                            + dot(into[0:P], bb, nn))
                return carry

            jax.lax.fori_loop(0, HB, head, 0)
            store = pltpu.make_async_copy(
                o_scr, os_hbm.at[pl.ds(base, TC), heads], sems.at[2])
            store.start()
            store.wait()
            return carry

        jax.lax.fori_loop(0, pl.cdiv(n, TC), chunk, 0)
        leave().start()
        flag[0] = 1

    @pl.when(last)
    def _():
        left()


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_call(x, bc, state, buf, layer, slots, starts, lens, zero, fill, *,
             chunk: int, interpret: bool):
    """The kernel's launch: x (rows, H, 2 P) = [dt x | log a in every lane],
    bc (rows, G, 2 N) = [B | C], the step's rows as they lie, a sequence's
    from `starts[s]` on, and `chunk` rows to spare behind the last; fill (S,)
    the rows each sequence's buffer holds. -> (y of the sequences of one row;
    y of the others; state; buf), y (rows, H, 2 P) with the values in the
    first P lanes, the rows where x's are. Jitted under a name of its own so
    that a profile's events read `ssd_call.<n>` (as `kda_call` does)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, H, W = x.shape
    G, N = bc.shape[1], bc.shape[2] // 2
    P = W // 2
    S = slots.shape[0]
    HB = heads_a_step(H, G, P)
    own = G == H
    T, LW = buf.shape[3:]
    R = _fold_rows(T, HB, own)
    if (buf.shape[2] != H // HB
            or T != R * (HB // 2) + _key_rows(R, HB, own) + HB):
        raise ValueError(f"a buffer {buf.shape} for {H} heads in blocks of "
                         f"{HB}: `buffer_shape` lays it")
    # (this module's `state_block`, looked up now: a timing patches it)
    slot_spec = pl.BlockSpec((None, None, HB, P, N), state_block)
    tile_spec = pl.BlockSpec((None, None, None, T, LW), tile_block)
    # A decode row where it lies; every other sequence's output block is a
    # spare row's, so that it lands on nobody's.
    at_row = lambda s, j, meta, slots, starts, *_: (starts[s], j, 0)
    row_out = pl.BlockSpec(
        (None, HB, W),
        lambda s, j, meta, slots, starts, lens, *_: (
            jnp.where(lens[s] == 1, starts[s], rows - 1), j, 0))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(S, H // HB),
        in_specs=[pl.BlockSpec((None, HB, W), at_row),
                  # the row's [B | C]: every group's, or these heads' own
                  pl.BlockSpec((None, HB, 2 * N), at_row) if own else
                  pl.BlockSpec((None, G, 2 * N),
                               lambda s, j, meta, slots, starts, *_: (
                                   starts[s], 0, 0)),
                  slot_spec, tile_spec, anywhere, anywhere],
        out_specs=[row_out, anywhere, anywhere, tile_spec],
        scratch_shapes=[
            pltpu.VMEM((chunk, HB, W), F32),            # a chunk's rows
            pltpu.VMEM((chunk, HB if own else G, 2 * N), F32),  # their B | C
            pltpu.VMEM((chunk, HB, W), F32),            # its output
            pltpu.VMEM((W, W), F32),                    # sums to transpose
            pltpu.VMEM((HB, P, N), F32),                # the state to write
            pltpu.VMEM((R, HB // 2, W), F32),           # a fold's rows
            pltpu.VMEM((W, W), F32),                    # a pair, zeros on
            pltpu.VMEM((W, N), F32),                    # their B, zeros on
            pltpu.VMEM((2 * HB, LW), F32),              # decays; what S0 keeps
            # a fold's B a head, where each brings its own
            *([pltpu.VMEM((R, HB // 2, N), F32)] * (2 if own else 0)),
            pltpu.SMEM((1,), jnp.int32),                # a write in flight
            pltpu.SemaphoreType.DMA((4,)),
        ],
    )
    out = jax.ShapeDtypeStruct((rows, H, W), F32)
    return pl.pallas_call(
        functools.partial(_ssd_kernel, HB=HB, P=P, N=N, TC=chunk, R=R,
                          per_group=H // G),
        grid_spec=grid_spec,
        out_shape=[out, out, jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(buf.shape, buf.dtype)],
        # the state (its blocks in, the whole out) and the buffer, in place
        input_output_aliases={8: 2, 9: 3},
        interpret=interpret,
        **kernel_tag("ssd"),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, starts, lens, zero,
      fill, x, bc, state, buf, x, bc)


def ssd(x, dt, A, B, C, state, buf, fill, layer, slots, starts, lens, zero,
        *, impl: str = "pallas", interpret: Optional[bool] = None,
        chunk: Optional[int] = None):
    """`ssd_reference`'s contract, by the Pallas kernel where `impl` is
    "pallas"."""
    slots, starts, lens, zero = enter(state, slots, starts, lens, zero)
    if impl != "pallas":
        return ssd_reference(x, dt, A, B, C, state, buf, fill, layer, slots,
                             starts, lens, zero)
    chunk = chunk or CHUNK
    R, H, P = x.shape
    dt = dt.astype(F32)
    # The step's rows as the kernel reads them, and `chunk` rows of zeros for
    # the last chunk to overhang onto.
    spare = lambda a: jnp.pad(a, ((0, chunk), (0, 0), (0, 0)))
    packed = spare(jnp.concatenate(
        [dt[..., None] * x.astype(F32),
         jnp.broadcast_to((dt * A.astype(F32))[..., None], (R, H, P))], -1))
    bc = spare(jnp.concatenate([B.astype(F32), C.astype(F32)], -1))
    i32 = lambda a: a.astype(jnp.int32)
    f0 = first_fill(fill, layer, slots, zero)
    # (a sequence without a row may start anywhere: its block is read, and
    # dropped, so it is read inside the rows)
    y_row, y_rows, state, buf = ssd_call(
        packed, bc, state, buf, layer, i32(slots),
        i32(jnp.clip(starts, 0, R - 1)), i32(lens), i32(zero), i32(f0),
        chunk=chunk, interpret=interpreted(interpret))
    fold = _fold_rows(buf.shape[3], H // buf.shape[2], B.shape[1] == H)
    fill = filled(fill, layer, slots, joins(lens, zero, f0, fold), f0)
    return answers(y_row, y_rows, starts, lens, x.shape), state, buf, fill
