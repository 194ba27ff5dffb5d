"""Kimi Delta Attention (arXiv:2510.26692): a gated DELTA RULE over ragged
token-major rows, the buffered step for a decode row and the chunked (WY)
form for a prompt slice, in one call a layer.

For head h of a sequence, with q and k already normalised, log a_t <= 0 the
forget gate's log A KEY CHANNEL and 0 < b_t < 1 the write strength a head,

  S' = diag(a_t) S_(t-1);  u_t = b_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T;
  o_t = S_t^T q_t.

The state S (dk keys x dv values, float32) is a SLOT a sequence
(llm/model_runner.py, "Layer groups": a state group). The update READS the
state (`S'^T k_t`), but it need not read the REWRITTEN state: as the chunked
form answers a whole chunk from the S the chunk started with, a row is
answered from the state as the last fold left it (S0) and the rows buffered
since, by forward substitution. With c_j the gates' logs summed A KEY CHANNEL
from the fold through row j (every exponent below is a DIFFERENCE of such
sums, <= 0),

  m(x, j) = sum_d x_d k_jd e^(c_td - c_jd)                 (a number a row)
  u_t = b_t (v_t - S0^T (k_t * e^(c_t)) - sum_(j<t) m(k_t, j) u_j)
  o_t =        S0^T (q_t * e^(c_t)) + sum_(j<=t) m(q_t, j) u_j,

and the buffer is FOLDED into the state once in FOLD rows:
S <- diag(e^(c_r)) S0 + sum_j (k_j * e^(c_r - c_j)) u_j^T. So (state, buffer,
fill) together are the recurrence's S_t (`folded`), exactly: no term is
dropped and nothing is rounded anew; a decode row READS its state once and
writes one row (ops/ssd.py's scheme, ops/power_retention.py's before it).
All three are a slot a sequence:

  state   (layers, slots + 1, H, dk, dv) float32; the last slot is nobody's
          (padding sequences read it)
  buffer  (layers, slots + 1, H / HB, FOLD HB, 2 dk + dv) float32: a TILE for
          the HB heads a grid step holds (`heads_a_step`), read whole by
          that step and no other, which writes ONE row group of it. Rows
          [j HB, (j + 1) HB) are buffered row j's, a head a row: [k_j | c_j |
          u_j] on the lanes (a gate a key channel costs the c_j: 12 KB a row
          of 8 heads, 96 KB a tile at FOLD 8 beside the block's 512 KB of S).
          Rows from the fill on are stale and never read.
  fill    (layers, slots + 1) int32: rows the buffer holds, 0 .. FOLD - 1

The slots' contract (`slots`, `starts`, `lens`, `zero`, the junk slot, the
fill's rule) is ops/state_slots.py's.

  `kda_reference`   the recurrence as a `lax.scan` over time from `folded`,
                    the sequences side by side: the tests' oracle and the
                    path off the chip; it hands back state, buffer and fill
                    by the same rule
  `kda`             the Pallas kernel where `impl == "pallas"`

The kernel reads the step's rows WHERE THEY LIE: x (rows, H, W) token-major as
the layer's projections leave them, W = [q | k | log a | v | beta in every
lane]. The row is the untiled leading axis and (H, W) are whole (8, 128)
tiles, so a block or a DMA may start at ANY row (with the heads in front the
rows are the sublanes, every sequence has to start on a multiple of 8, and a
wrapper has to gather and transpose a plane a layer to make it so). Its
outputs are token-major too. The grid is (sequences, heads / HB) in order. A
step's state block and buffer tile are blocked INPUTS indexed by scalar
prefetch (Pallas fetches the next step's while this one computes). Of the
tile only the row group at the fill goes back, a blocked output of (HB, 2 dk
+ dv) indexed by the fill (the whole tile back read a joining step 1.30 us
for 1.12); the state leaves only by the kernel's own DMA, where a row folds
and where a slice ends (a blocked output would be written back at every step,
touched or not), waited for at the next such write or at the grid's last
step. State and buffer are aliased in and out. For a sequence of
one row the step also holds these heads of that row as a block, `(None, HB,
W)` at `starts[s]`.

  one row (a decode row): the heads stand on the SUBLANES, as the row's block
      and the tile's rows have them. S0's two terms on the VPU, a multiply and
      a sublane reduce a tile: the vectors that scale S0's rows (k_t e^(c_t),
      q_t e^(c_t) down the key channels) come from ONE 128 x 128 transpose a
      grid step, all heads'. The buffered rows' terms: m(k_t, j) and m(q_t, j)
      of all heads are two lane reduces of one (HB, dk) expression a buffered
      row, then a multiply-add of its (HB, dv) u_j. A fold lays the rows k_j
      e^(c_r - c_j) and e^(c_r) beneath each other, transposes them once, and
      takes a head's columns of that against the rows' u as ONE product
      (depth FOLD HB, the other heads' columns masked), `HIGHEST`.
  more rows (a slice): chunks of CHUNK rows, with c_r the gates' log summed
      from the chunk's first row through row r (a matrix product with a
      triangle of ones), M_rj = sum_d k_rd k_jd e^(c_rd - c_jd) for j < r:

        T = (I + diag(b) strict_lower(M))^-1,  U = T diag(b) (V - (K e^c) S),
        O = (Q e^c) S + lower((Q, K)-form of M) U,
        S <- diag(e^(c_C)) S + (K e^(c_C - c))^T U.

      `K / e^c` is never formed (a channel whose gate is small over a chunk
      overflows it): M is built a SUB x SUB block at a time from DIFFERENCES
      of the logs, all <= 0. A block below the diagonal takes the row block's
      first row as its reference, (K_a e^(c_a - ref)) (K_b e^(ref - c_b))^T,
      a matrix product; a block on the diagonal is built a column at a time,
      elementwise. T comes from the nilpotent series (I + P)(I + P^2)(I +
      P^4) ... with P = -diag(b) strict_lower(M), which ends at P^(CHUNK/2).
      Products are float32 at `HIGHEST` (fewer passes: ROADMAP, "State beside
      pages").

A slice's chunk is one DMA of (CHUNK, HB, W) from row `starts[s] + t CHUNK`
on, a head's rows read out of it; its output goes back the same way, whole, so
its last rows may overhang the segment: they land on rows of LATER sequences,
which the grid writes afterwards (a decode row's in an array of their own), or
on the CHUNK spare rows behind the last (as ops/ssm_scan.py).
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

# Rows a step of the chunked form takes, rows of a block of M (the
# publication's kernel: 64 and 16), the most heads a grid step holds, and the
# rows a slot's buffer holds before it is folded (PERF.md section 6, PR 60,
# has the sweep).
CHUNK = 64
SUB = 16
HEADS = 8
FOLD = 8
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def state_shape(layers: int, slots: int, heads: int, dk: int, dv: int):
    """S of `slots` sequences and the junk slot behind them."""
    return fill_shape(layers, slots) + (heads, dk, dv)


def heads_a_step(heads: int, dk: int) -> int:
    """Heads a grid step holds, and a buffer's tile serves (their k_t and
    q_t are 2 HB rows of ONE (dk, dk) transpose)."""
    return next(b for b in range(min(HEADS, heads, dk // 2), 0, -1)
                if heads % b == 0)


def buffer_shape(layers: int, slots: int, heads: int, dk: int, dv: int,
                 fold: int = FOLD):
    """The buffered rows beside `state_shape`'s S: a tile a block of
    `heads_a_step` heads (the module docstring lays it out)."""
    hb = heads_a_step(heads, dk)
    return fill_shape(layers, slots) + (heads // hb, fold * hb, 2 * dk + dv)


def _tile_parts(buf, hb: int, dk: int):
    """A tile's rows (..., r hb, 2 dk + dv) -> k, c (..., r, hb, dk) and u
    (..., r, hb, dv)."""
    rows = buf.reshape(buf.shape[:-2] + (-1, hb, buf.shape[-1]))
    return rows[..., :dk], rows[..., dk:2 * dk], rows[..., 2 * dk:]


def _last_log(c, fill):
    """c (..., J, r, hb, dk), fill (...) -> the logs of the last row held,
    c[fill - 1] (..., J, 1, hb, dk); zeros where the buffer is empty."""
    at = jnp.arange(c.shape[-3])[:, None, None]
    return jnp.sum(jnp.where(
        at == fill[..., None, None, None, None] - 1, c, 0.0), -3,
        keepdims=True)


def folded(state, buf, fill):
    """The recurrence's S_t of slots whose parts are given as they lie:
    state (..., H, dk, dv), buf (..., J, T, LW), fill (...) -> state with the
    buffer's first `fill` rows folded in."""
    H, dk, dv = state.shape[-3:]
    hb = H // buf.shape[-3]
    k, c, u = _tile_parts(buf, hb, dk)                   # (..., J, r, hb, .)
    held = (jnp.arange(k.shape[-3])[:, None, None]
            < fill[..., None, None, None, None])
    c_last = _last_log(c, fill)
    keys = jnp.where(held, k * jnp.exp(jnp.minimum(c_last - c, 0.0)), 0.0)
    add = jnp.einsum("...rhk,...rhv->...hkv", keys, jnp.where(held, u, 0.0),
                     precision=HIGHEST)
    kept = jnp.exp(c_last[..., 0, :, :, None])       # (..., J, hb, dk, 1)
    return (kept * state.reshape(add.shape) + add).reshape(state.shape)


def kda_reference(q, k, v, log_a, beta, state, buf, fill, layer, slots,
                  starts, lens, zero):
    """The recurrence, a row at a time: q / k / log_a (R, H, dk), v (R, H,
    dv), beta (R, H), float32; state / buf / fill `state_shape`'s /
    `buffer_shape`'s / `fill_shape`'s; slots / starts / lens / zero (S,).
    -> (o (R, H, dv) float32, rows outside every segment zero; state; buf;
    fill, the sequences' slots written by the fill's rule: the recurrence
    runs from `folded` and a sequence of one row, where its buffer has room,
    is handed back as it came with the row in its buffer)."""
    R, H, dk = q.shape
    dv = v.shape[-1]
    J, T = buf.shape[2:4]
    hb = H // J
    r = T // hb
    q, k, v, log_a, beta = (a.astype(F32) for a in (q, k, v, log_a, beta))
    keep = lambda z, a: jnp.where(
        z.reshape((-1,) + (1,) * (a.ndim - 1)), 0, a)
    f0 = first_fill(fill, layer, slots, zero)                     # (S,)
    held = keep(zero, state[layer, slots])
    tiles = buf[layer, slots]                                 # (S, J, T, LW)
    s0 = folded(held, tiles, f0)
    rows = jnp.clip(starts[:, None] + jnp.arange(R)[None, :], 0, R - 1)
    live = jnp.arange(R)[None, :] < lens[:, None]                 # (S, R)

    def correction(s, k_t, v_t, la_t, b_t):
        after = jnp.exp(la_t)[..., None] * s                  # (S, H, dk, dv)
        return after, b_t[..., None] * (v_t - jnp.einsum(
            "shkv,shk->shv", after, k_t, precision=HIGHEST))

    def step(s, xs):
        q_t, k_t, v_t, la_t, b_t, live_t = xs
        after, u = correction(s, k_t, v_t, la_t, b_t)
        new = after + k_t[..., None] * u[..., None, :]
        s = jnp.where(live_t[:, None, None, None], new, s)
        return s, jnp.einsum("shkv,shk->shv", s, q_t, precision=HIGHEST)

    move = lambda a: jnp.moveaxis(a[rows], 1, 0)
    s1, o = jax.lax.scan(step, s0, (move(q), move(k), move(v), move(log_a),
                                    move(beta), live.T))
    o = jnp.moveaxis(o, 0, 1)                                 # (S, R, H, dv)
    flat = jnp.zeros(v.shape, F32).at[jnp.where(live, rows, R)].set(
        o, mode="drop")
    # The fill's rule: one row that leaves room joins the buffer, [k_t | c_t
    # = c of the row before it + its log a | u_t], and the state stays as it
    # was held; everything else hands back S_t.
    stay = joins(lens, zero, f0, r)
    at = rows[:, 0]
    c_t = (_last_log(_tile_parts(tiles, hb, dk)[1], f0)[:, :, 0]
           + log_a[at].reshape(-1, J, hb, dk))
    u_t = correction(s0, k[at], v[at], log_a[at], beta[at])[1]
    row = jnp.concatenate([k[at].reshape(-1, J, hb, dk), c_t,
                           u_t.reshape(-1, J, hb, dv)], -1)
    joined = jax.vmap(lambda tile, f, new: jax.lax.dynamic_update_slice(
        tile, new, (0, f * hb, 0)))(tiles, f0, row)
    pick = lambda a, b: jnp.where(
        stay.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
    put = lambda whole, part: whole.at[layer, slots].set(part, mode="drop")
    return (flat, put(state, pick(held, s1)), put(buf, pick(joined, tiles)),
            filled(fill, layer, slots, stay, f0))


def _kda_kernel(meta_ref, slots_ref, starts_ref, lens_ref, zero_ref, fill_ref,
                x_ref, s_in_ref, b_in_ref, x_hbm, od_ref, os_hbm, s_hbm,
                b_ref, x_scr, o_scr, k_scr, c_scr, t_scr, s_scr, a_scr,
                u_scr, w_scr, flag, sems, *, HB: int, dk: int, dv: int,
                TC: int, TS: int, R: int):
    """Grid (S, H / HB): sequence s, heads [j HB, (j + 1) HB). s_in_ref (HB,
    dk, dv): their state as the last fold left it; s_hbm the whole state in
    HBM (the same memory: aliased), written from s_scr where a row folds and
    where a slice ends. b_in_ref (R HB, 2 dk + dv): their buffer tile;
    b_ref (HB, 2 dk + dv): its row group at the fill, the same memory
    (aliased), where the step's row joins. x_ref (HB, W): these heads of
    the step's row `starts[s]`, where it lies, W = [q | k | log a | v | beta
    in every lane]; x_hbm the same rows (rows, H, W) in HBM, for a slice's
    chunks. od_ref (HB, dv): a decode row's output, at the same row of o;
    os_hbm (rows, H, dv): a slice's. fill_ref: rows the slot's buffer holds
    (0 where the sequence starts)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

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
    heads = pl.ds(pl.multiple_of(j * HB, HB), HB)
    dot = functools.partial(jax.lax.dot_general, precision=HIGHEST,
                            preferred_element_type=F32)
    nn = (((1,), (0,)), ((), ()))
    nt = (((1,), (1,)), ((), ()))
    iota = lambda shape, axis: jax.lax.broadcasted_iota(jnp.int32, shape,
                                                        axis)
    V0, B0 = 3 * dk, 3 * dk + dv
    C0, U0 = dk, 2 * dk                      # a tile's lanes: [k | c | u]
    E0 = R * HB                              # a_scr's rows of e^(c_last)
    tile_rows = lambda r: pl.ds(pl.multiple_of(r * HB, HB), HB)

    def leave():
        return pltpu.make_async_copy(s_scr, s_hbm.at[layer, slot, heads],
                                     sems.at[2])

    def left():
        """The last write of s_scr has left it."""
        @pl.when(flag[0] == 1)
        def _():
            leave().wait()
            flag[0] = 0

    @pl.when(first)
    def _():
        flag[0] = 0
        u_scr[...] = jnp.zeros_like(u_scr)

    # The joining row's place in the tile leaves as it came (b_ref is that
    # row group alone), but where a row joins.
    b_ref[...] = b_in_ref[tile_rows(f), :]
    folds = ((n == 1) & (fresh | (f + 1 >= R))) | ((n > 1) & (f > 0))

    @pl.when(n == 1)
    def _one_row():
        qh, kh = x_ref[:, 0:dk], x_ref[:, dk:2 * dk]            # (HB, dk)
        # c_t = c of the row before it + its log a.
        c_t = x_ref[:, 2 * dk:V0] + jnp.where(
            f > 0, b_in_ref[tile_rows(jnp.maximum(f - 1, 0)), C0:U0], 0.0)
        e_t = jnp.exp(c_t)
        # S0's terms: k_t e^(c_t) and q_t e^(c_t) of the HB heads as rows h
        # and HB + h, then down the key channels by one transpose.
        t_scr[0:HB, :] = kh * e_t
        t_scr[HB:2 * HB, :] = qh * e_t
        cols = t_scr[...].T                                     # (dk, dk)
        for h in range(HB):
            s0 = s_in_ref[h]
            for i in range(2):
                w_scr[i * HB + h:i * HB + h + 1, :] = jnp.sum(
                    s0 * cols[:, i * HB + h:i * HB + h + 1], axis=0,
                    keepdims=True)

        # The buffered rows: m(k_t, j) and m(q_t, j) a head, times u_j.
        def row(r, carry):
            corr, seen = carry
            at = tile_rows(r)
            e = b_in_ref[at, 0:dk] * jnp.exp(jnp.minimum(
                c_t - b_in_ref[at, C0:U0], 0.0))
            u_r = b_in_ref[at, U0:U0 + dv]
            m = lambda x: jnp.where(
                r < f, jnp.sum(x * e, axis=1, keepdims=True), 0.0)
            return corr + m(kh) * u_r, seen + m(qh) * u_r

        # (rows behind the fill weigh nothing, but what lies there is stale)
        zeros = jnp.zeros((HB, dv), F32)
        corr, seen = jax.lax.fori_loop(0, R, row, (zeros, zeros),
                                       unroll=True)
        held = lambda a: jnp.where(fresh, 0.0, a)
        u = x_ref[:, B0:B0 + dv] * (
            x_ref[:, V0:B0] - held(w_scr[0:HB, :]) - corr)
        od_ref[...] = (held(w_scr[HB:2 * HB, :]) + seen
                       + jnp.sum(qh * kh, axis=1, keepdims=True) * u)
        b_ref[:, 0:dk] = kh
        b_ref[:, C0:U0] = c_t
        b_ref[:, U0:U0 + dv] = u

    @pl.when(folds)
    def _fold():
        """s_scr <- what S0 keeps + the rows the tile holds and the one that
        just joined, row r decayed by e^(c_last - c_r): their k_r e^(c_last -
        c_r) laid beneath each other (row r HB + h head h's; zeros behind the
        last), e^(c_last) a head beneath them, ONE transpose, and a head's
        columns of it (the other heads' masked) against every row's u. The
        heads are a ROLLED loop, e^(c_last)'s column picked by a masked sum:
        2.5 us a fold where a Python loop (static column slices, the right
        operand's three parts made once) read 1.6, for 102 equations fewer
        a kernel, which every step program's start pays (PERF.md section 6,
        PR 60: the Python loop was +5.3% tokens/s and +5.5% `setup_s`)."""
        left()
        c_last = jnp.where(
            n == 1, b_ref[:, C0:U0],
            b_in_ref[tile_rows(jnp.maximum(f - 1, 0)), C0:U0])

        def lay(r, carry):
            at = tile_rows(r)
            a_scr[at, :] = jnp.where(
                r < f, b_in_ref[at, 0:dk] * jnp.exp(jnp.minimum(
                    c_last - b_in_ref[at, C0:U0], 0.0)), 0.0)
            return carry

        jax.lax.fori_loop(0, R, lay, 0, unroll=True)
        a_scr[E0:E0 + HB, :] = jnp.exp(c_last)
        u_scr[0:E0, :] = b_in_ref[:, U0:U0 + dv]

        @pl.when(n == 1)
        def _():        # the row that just joined: e^(c_last - c_last) = 1
            a_scr[tile_rows(f), :] = b_ref[:, 0:dk]
            u_scr[tile_rows(f), :] = b_ref[:, U0:U0 + dv]

        cols = a_scr[...].T                                 # (dk, laid)
        col = iota(cols.shape, 1)
        own = jnp.where(col < E0, jax.lax.rem(col, HB), -1)

        def head(h, carry):
            kept = jnp.sum(jnp.where(col == E0 + h, cols, 0.0), axis=1,
                           keepdims=True)                       # (dk, 1)
            s_scr[h] = (jnp.where(fresh, 0.0, kept * s_in_ref[h])
                        + dot(jnp.where(own == h, cols, 0.0), u_scr[...],
                              nn))
            return carry

        jax.lax.fori_loop(0, HB, head, 0)

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
        eye = jnp.where(c_i == r_i, 1.0, 0.0)
        cols_s = iota((TS, TC), 1)

        def chunk(t, carry):
            base = row0 + t * TC
            real = jnp.minimum(TC, n - t * TC)
            load = pltpu.make_async_copy(
                x_hbm.at[pl.ds(base, TC), heads], x_scr, sems.at[0])
            load.start()
            load.wait()
            valid = iota((TC, 1), 0) < real

            def head(h, carry):
                x = x_scr[:, h, :]                                # (TC, W)
                qq = x[:, 0:dk]
                kk = jnp.where(valid, x[:, dk:2 * dk], 0.0)
                vv = jnp.where(valid, x[:, V0:B0], 0.0)
                bb = jnp.where(valid, x[:, B0:B0 + 1], 0.0)       # (TC, 1)
                # c_r: rows past the segment decay nothing, so the last row
                # holds the chunk's whole decay.
                c = dot(ones, jnp.where(valid, x[:, 2 * dk:V0], 0.0), nn)
                k_scr[...] = kk
                c_scr[...] = c
                blocks_k, blocks_q = [], []
                for a in range(TC // TS):
                    lo = a * TS
                    c_a = c[lo:lo + TS]
                    ref = c[lo:lo + 1]
                    # Below the diagonal: columns of earlier blocks, whose c
                    # is at or above `ref`.
                    into = jnp.exp(c_a - ref)
                    rows2 = jnp.concatenate(
                        [kk[lo:lo + TS] * into, qq[lo:lo + TS] * into], 0)
                    off = dot(rows2, kk * jnp.exp(jnp.minimum(ref - c, 0.0)),
                              nt)                               # (2 TS, TC)
                    k_a, q_a = kk[lo:lo + TS], qq[lo:lo + TS]

                    def column(i, dq, lo=lo, c_a=c_a, k_a=k_a, q_a=q_a):
                        d_k, d_q = dq
                        at = lo + i
                        e = (jnp.exp(jnp.minimum(
                            c_a - c_scr[pl.ds(at, 1), :], 0.0))
                            * k_scr[pl.ds(at, 1), :])           # (TS, dk)
                        col = cols_s == at
                        return (jnp.where(col, jnp.sum(
                            k_a * e, axis=1, keepdims=True), d_k),
                            jnp.where(col, jnp.sum(
                                q_a * e, axis=1, keepdims=True), d_q))

                    zeros = jnp.zeros((TS, TC), F32)
                    d_k, d_q = jax.lax.fori_loop(0, TS, column,
                                                 (zeros, zeros))
                    blocks_k.append(jnp.where(cols_s < lo, off[:TS], d_k))
                    blocks_q.append(jnp.where(cols_s < lo, off[TS:], d_q))
                m_k = jnp.concatenate(blocks_k, 0)
                m_q = jnp.concatenate(blocks_q, 0)
                # T = (I + L)^-1, L strictly lower: the series ends.
                p = jnp.where(c_i < r_i, -bb * m_k, 0.0)
                inv = eye + p
                size = 2
                while size < TC:
                    p = dot(p, p, nn)
                    inv = inv + dot(inv, p, nn)
                    size *= 2
                state = s_scr[h]
                e_c = jnp.exp(c)
                u = dot(inv, bb * (vv - dot(kk * e_c, state, nn)), nn)
                o_scr[:, h, :] = dot(qq * e_c, state, nn) + dot(
                    jnp.where(c_i <= r_i, m_q, 0.0), u, nn)
                last_c = c[TC - 1:TC]
                t_scr[0:1, :] = jnp.exp(last_c)
                s_scr[h] = (t_scr[...].T[:, 0:1] * state
                            + dot((kk * jnp.exp(last_c - c)).T, u, nn))
                return carry

            jax.lax.fori_loop(0, HB, head, 0)
            store = pltpu.make_async_copy(
                o_scr, os_hbm.at[pl.ds(base, TC), heads], sems.at[1])
            store.start()
            store.wait()
            return carry

        jax.lax.fori_loop(0, pl.cdiv(n, TC), chunk, 0)
        leave().start()
        flag[0] = 1

    @pl.when(last)
    def _():
        left()


@functools.partial(jax.jit, static_argnames=("dk", "chunk", "sub",
                                             "interpret"))
def kda_call(x, state, buf, layer, slots, starts, lens, zero, fill, *,
             dk: int, chunk: int, sub: int, interpret: bool):
    """The kernel's launch: x (rows, H, 3 dk + 2 dv) = [q | k | log a | v |
    beta], the step's rows as they lie, a sequence's from `starts[s]` on, and
    `chunk` rows to spare behind the last; fill (S,) the rows each sequence's
    buffer holds. -> (o of the sequences of one row; o of the others; state;
    buf), o (rows, H, dv), the rows where x's are. Jitted under a name of its
    own so that a profile's events read `kda_call.<n>` (as `ssm_scan_call`
    does)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, H, width = x.shape
    dv = (width - 3 * dk) // 2
    S = slots.shape[0]
    HB = heads_a_step(H, dk)
    T, LW = buf.shape[3:]
    R = T // HB
    if buf.shape[2] != H // HB or T != R * HB or LW != 2 * dk + dv:
        raise ValueError(f"a buffer {buf.shape} for {H} heads in blocks of "
                         f"{HB}: `buffer_shape` lays it")
    if chunk % sub or chunk & (chunk - 1) or sub % 8:
        raise ValueError(f"chunk {chunk}: a power of two, in blocks of "
                         f"{sub} rows, themselves a multiple of 8")
    # (this module's `state_block`, looked up now: a timing patches it)
    slot_spec = pl.BlockSpec((None, None, HB, dk, dv), state_block)
    tile_spec = pl.BlockSpec((None, None, None, T, LW), tile_block)
    # (the joining row's place alone goes back: 12 KB of the tile's 96)
    tile_out = pl.BlockSpec(
        (None, None, None, HB, LW),
        lambda s, j, *scalars: tile_block(s, j, *scalars)[:3] + (
            scalars[-1][s], 0))
    # A decode row where it lies; every other sequence's output block is a
    # spare row's, so that it lands on nobody's.
    row_in = pl.BlockSpec(
        (None, HB, width),
        lambda s, j, meta, slots, starts, *_: (starts[s], j, 0))
    row_out = pl.BlockSpec(
        (None, HB, dv),
        lambda s, j, meta, slots, starts, lens, *_: (
            jnp.where(lens[s] == 1, starts[s], rows - 1), j, 0))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    # A fold's rows beneath each other, R HB of the buffer's and HB of
    # e^(c_last), in whole (dk, dk) tiles for the transpose.
    laid = -(-(T + HB) // dk) * dk
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(S, H // HB),
        in_specs=[row_in, slot_spec, tile_spec, anywhere],
        out_specs=[row_out, anywhere, anywhere, tile_out],
        scratch_shapes=[
            pltpu.VMEM((chunk, HB, width), F32),        # a chunk's rows
            pltpu.VMEM((chunk, HB, dv), F32),           # its output
            pltpu.VMEM((chunk, dk), F32),               # a head's k
            pltpu.VMEM((chunk, dk), F32),               # its c
            pltpu.VMEM((dk, dk), F32),                  # rows to transpose
            pltpu.VMEM((HB, dk, dv), F32),              # the state to write
            pltpu.VMEM((laid, dk), F32),                # a fold's keys
            pltpu.VMEM((laid, dv), F32),                # their u, zeros on
            pltpu.VMEM((2 * HB, dv), F32),              # S0's terms a head
            pltpu.SMEM((1,), jnp.int32),                # a write in flight
            pltpu.SemaphoreType.DMA((3,)),
        ],
    )
    out = jax.ShapeDtypeStruct((rows, H, dv), F32)
    return pl.pallas_call(
        functools.partial(_kda_kernel, HB=HB, dk=dk, dv=dv, TC=chunk,
                          TS=sub, R=R),
        grid_spec=grid_spec,
        out_shape=[out, out, jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(buf.shape, buf.dtype)],
        # the state (its blocks in, the whole out) and the buffer, in place
        input_output_aliases={7: 2, 8: 3},
        interpret=interpret,
        **kernel_tag("kda"),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, starts, lens, zero,
      fill, x, state, buf, x)


def kda(q, k, v, log_a, beta, state, buf, fill, layer, slots, starts, lens,
        zero, *, impl: str = "pallas", interpret: Optional[bool] = None,
        chunk: Optional[int] = None, sub: Optional[int] = None):
    """`kda_reference`'s contract, by the Pallas kernel where `impl` is
    "pallas"."""
    slots, starts, lens, zero = enter(state, slots, starts, lens, zero)
    if impl != "pallas":
        return kda_reference(q, k, v, log_a, beta, state, buf, fill, layer,
                             slots, starts, lens, zero)
    chunk, sub = chunk or CHUNK, sub or SUB
    R, H, dk = q.shape
    dv = v.shape[-1]
    # The step's rows as the layer made them, side by side on the lanes, and
    # `chunk` rows of zeros for the last chunk to overhang onto.
    x = jnp.concatenate(
        [a.astype(F32) for a in (q, k, log_a, v)]
        + [jnp.broadcast_to(beta.astype(F32)[..., None], (R, H, dv))], -1)
    x = jnp.pad(x, ((0, chunk), (0, 0), (0, 0)))
    i32 = lambda a: a.astype(jnp.int32)
    f0 = first_fill(fill, layer, slots, zero)
    # (a sequence without a row may start anywhere: its block is read, and
    # dropped, so it is read inside the rows)
    o_row, o_rows, state, buf = kda_call(
        x, state, buf, layer, i32(slots), i32(jnp.clip(starts, 0, R - 1)),
        i32(lens), i32(zero), i32(f0), dk=dk, chunk=chunk, sub=sub,
        interpret=interpreted(interpret))
    fold = buf.shape[3] // heads_a_step(H, dk)
    fill = filled(fill, layer, slots, joins(lens, zero, f0, fold), f0)
    return answers(o_row, o_rows, starts, lens, v.shape), state, buf, fill
