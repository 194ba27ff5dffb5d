"""Power retention (power attention of degree 2 with a gate; arXiv:2507.04239)
over ragged token-major rows: the recurrent step for a decode row and the
chunked form for a prompt slice, in one call a layer.

For query head h of kv head j = h // G and every s <= t of a sequence,

  a_ts = exp(sum_{r=s+1..t} log g_rj) * (scale * q_th . k_sj) ** 2,
  o_th = sum_s a_ts v_sj / (sum_s a_ts + eps).

The square is an inner product of degree-2 features, so the sum over s is a
STATE of fixed size a sequence and kv head (llm/model_runner.py, "Layer
groups": a state group):

  S_t = g_t S_(t-1) + phi(k_t) v_t^T,  z_t = g_t z_(t-1) + phi(k_t),
  o_th = S_t^T phi(q_th) / (z_t . phi(q_th) + eps).

A decode row does NOT rewrite it (the publication's inference form): the last
rows' k, v and gates lie in a BUFFER beside the state, a row is answered from
the state as it stood at the last fold (S0, z0) and the buffered rows s in the
attention form, c_s the gates' log summed from the fold through row s,

  o_th = [e^(c_t) S0^T phi(q) + sum_s e^(c_t - c_s) (q . k_s)^2 v_s]
         / [e^(c_t) z0 . phi(q) + sum_s e^(c_t - c_s) (q . k_s)^2 + eps],

and the buffer is FOLDED into the state once in FOLD rows:
S <- e^(c_last) S0 + sum_s e^(c_last - c_s) phi(k_s) v_s^T, z likewise. So
(state, norm, buffer, fill) together are the recurrence's S_t and z_t
(`folded`), and a decode row READS its state once and writes one row.

`phi`, as it lies (hd = head width, C = hd / 2 + 1 chunks of hd lanes):
chunk r holds w_r * x * roll(x, r), the products of every pair of lanes at
circular distance r, with w_0 = 1 (the squares), w_r = sqrt 2 for 0 < r <
hd / 2 (each unordered pair once) and w_(hd/2) = 1 (each pair twice): phi(q) .
phi(k) = (q . k) ** 2. That is hd (hd + 1) / 2 = 8,256 distinct products at hd
128 in 65 x 128 = 8,320 lanes (the last chunk's 64 duplicates are the padding),
made by lane rotations and no gather.

  state   (layers, slots + 1, K, C, hd, hd) float32: [r, c, l] = sum_t decay *
          v_t[c] * phi(k_t)[r, l]: a chunk is S^T's (hd values, hd lanes) tile,
          so that a chunk's readout contracts lanes with lanes and its update
          is (V^T)(phi K). The last slot is nobody's (padding sequences)
  norm    (layers, slots + 1, C, K, hd) float32: z, a slot's K heads down
          the sublanes (one whole tile a chunk)
  buffer  (layers, slots + 1, K, 2 FOLD + 8, hd) float32: rows [0, FOLD) the
          buffered k (scaled as the state's features are), [FOLD, 2 FOLD)
          their v, and one tile of gates: its row 0 holds c_s in lane s, its
          row 1 c of the last buffered row in every lane. Rows and lanes
          from the fill on are stale and never read
  fill    (layers, slots + 1) int32: rows the buffer holds, 0 .. FOLD - 1

The slots' contract (`slots`, `starts`, `lens`, `zero`, the junk slot, the
fill's rule) is ops/state_slots.py's.

  `power_retention_reference`   the recurrence as a `lax.scan` over time, the
                                sequences side by side, phi built whole: the
                                oracle of the tests and the path off the chip
  `power_retention`             the Pallas kernel where `impl == "pallas"`

The kernel's grid is (sequences, kv heads) in order. The state and the buffer
stay in HBM (aliased in and out: nothing copies the arrays) and move by the
kernel's own DMAs: a step's state block, its buffer and, for one row, its q /
k / v rows are started a step AHEAD into the other of two sets of scratch, and
the state is written only by a fold and by a slice (a blocked output would be
written back at every step, touched or not). z, 1 / hd of the state, is a
block a sequence. A sequence without rows moves nothing.

  one row (a decode row): the state's term on the VPU, float32: each (8
      values, hd lanes) tile of S0 is read and multiplied into the G query
      heads' accumulators, WALK_TILES tiles to a load of the G feature rows,
      UNROLL chunks of phi to an iteration (a chunk at a time, its rotation,
      loads and products wait for one another and the step takes 11.4 us;
      side by side 6.3-6.7 us, the state block's DMA at ~80% of the chip's
      bandwidth: PERF.md section 5); the buffered rows' term by the chunked
      form's score arithmetic, a (G, FOLD) product. The row's k, v and gate
      leave as three tiles, its output as one, waited for a step later. A
      fold is the slice path's `features` loop without a query, over the
      buffer.
  more rows (a slice): chunks of CHUNK rows. Inside a chunk the attention form
      ((Q K^T) ** 2 with the gates' decay, causal, times V); against the
      incoming state and for its update, matrix products a phi chunk at a
      time: (rows x hd)(hd x hd) on the MXU, float32 at `HIGHEST`.

A chunk's output is written whole, so its last rows may overhang the segment:
they land on rows of LATER sequences, which the grid writes afterwards, or on
padding (as ops/ssm_scan.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_tag
from ray_tpu.ops.state_slots import (enter, fill_shape, filled, first_fill,
                                     interpreted, joins)

# Rows a step of the chunked form takes, rows a decode row's DMA moves, the
# rows of the q / k / v planes a multiple of which the wrapper lays, rows
# the buffer holds before it is folded (a multiple of 8, at most the head's
# width: c_s lies in lane s), (8, hd) tiles of the state a step of the one-row
# walk multiplies by one load of the G feature rows, and steps of a loop over
# phi's chunks to one iteration (65 = 5 x 13). PERF.md section 5 has the
# sweeps that chose the last three.
CHUNK = 128
DEC_ROWS = 8
PLANE = 128
FOLD = 64
WALK_TILES = 4
UNROLL = 5
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def chunks(head_dim: int) -> int:
    """Chunks of `head_dim` lanes phi has."""
    return head_dim // 2 + 1


def state_shape(layers: int, slots: int, kv_heads: int, head_dim: int):
    """S of `slots` sequences and the junk slot behind them."""
    return fill_shape(layers, slots) + (kv_heads, chunks(head_dim), head_dim,
                                        head_dim)


def norm_shape(layers: int, slots: int, kv_heads: int, head_dim: int):
    """z beside `state_shape`'s S."""
    return fill_shape(layers, slots) + (chunks(head_dim), kv_heads, head_dim)


def fold_rows(head_dim: int) -> int:
    """Rows a buffer holds before it is folded: FOLD, and no more than the
    head has lanes (a tiny head's buffer is smaller)."""
    return min(FOLD, head_dim // 8 * 8)


def buffer_shape(layers: int, slots: int, kv_heads: int, head_dim: int):
    """The buffered rows beside `state_shape`'s S: k, v and a tile of gates."""
    return fill_shape(layers, slots) + (kv_heads, 2 * fold_rows(head_dim) + 8,
                                        head_dim)


def _weights(head_dim: int):
    """w_r (C, 1)."""
    C = chunks(head_dim)
    r = jnp.arange(C)
    return jnp.where((r == 0) | (r == C - 1), 1.0, math.sqrt(2.0)).astype(
        F32)[:, None]


def phi(x):
    """x (..., hd) -> (..., C, hd), the degree-2 features as the state holds
    them: phi(q) . phi(k) = (q . k) ** 2."""
    hd = x.shape[-1]
    x = x.astype(F32)
    # Lane l of chunk r: x[l] * x[l - r]. One gather (a `jnp.roll` by hd / 2
    # alone in a program aborts the TPU compiler of this installation).
    back = (jnp.arange(hd)[None, :] - jnp.arange(chunks(hd))[:, None]) % hd
    return _weights(hd) * x[..., None, :] * x[..., back]


def folded(state, norm, buf, fill):
    """The recurrence's S_t and z_t of slots whose parts are given as they
    lie: state (..., K, C, hd, hd), norm (..., C, K, hd), buf (..., K, 2 FOLD
    + 8, hd), fill (...) -> (state, norm) with the buffer's first `fill` rows
    folded in."""
    F = buf.shape[-2] // 2 - 4
    live = (jnp.arange(F) < fill[..., None, None])[..., None]   # (.., 1, F, 1)
    kk = jnp.where(live, buf[..., :F, :], 0.0)
    vv = jnp.where(live, buf[..., F:2 * F, :], 0.0)
    c, c_last = buf[..., 2 * F, :F], buf[..., 2 * F + 1, :1]    # (.., K, F|1)
    keep = jnp.where(live[..., 0], jnp.exp(jnp.minimum(c_last - c, 0.0)), 0.0)
    held = jnp.where(fill[..., None, None] > 0, jnp.exp(c_last), 1.0)
    pk = phi(kk)                                            # (.., K, F, C, hd)
    s = held[..., None, None] * state + jnp.einsum(
        "...kfc,...kfrl->...krcl", keep[..., None] * vv, pk,
        precision=HIGHEST)
    z = held[..., None, :, :] * norm + jnp.einsum(
        "...kf,...kfrl->...rkl", keep, pk, precision=HIGHEST)
    return s, z


def power_retention_reference(q, k, v, log_g, state, norm, buf, fill, layer,
                              slots, starts, lens, zero, *, scale: float,
                              eps: float):
    """The recurrence, a row at a time: q (R, H, hd), k / v (R, K, hd), log_g
    (R, K) float32 (log of the gate); state / norm / buf / fill
    `state_shape`'s / `norm_shape`'s / `buffer_shape`'s / `fill_shape`'s;
    slots / starts / lens / zero (S,). -> (o (R, H, hd) float32, rows outside
    every segment zero; state; norm; buf; fill, the sequences' slots written
    by the fill's rule: the recurrence runs from `folded` and a sequence of
    one row, where its buffer has room, is handed back as it came with the
    row in its buffer)."""
    R, H, hd = q.shape
    K = k.shape[1]
    G = H // K
    F = fold_rows(hd)
    root = math.sqrt(scale)
    q, k, v = (a.astype(F32) for a in (q * root, k * root, v))
    log_g = log_g.astype(F32)
    keep = lambda z, a: jnp.where(
        z.reshape((-1,) + (1,) * (a.ndim - 1)), 0.0, a)
    f0 = first_fill(fill, layer, slots, zero)                     # (S,)
    held_s = keep(zero, state[layer, slots])        # (S, K, C, hd, hd)
    held_z = keep(zero, norm[layer, slots])         # (S, C, K, hd)
    rows_b = buf[layer, slots]                      # (S, K, 2 F + 8, hd)
    s0, z0 = folded(held_s, held_z, rows_b, f0)
    z0 = z0.swapaxes(1, 2)                          # (S, K, C, hd)
    rows = jnp.clip(starts[:, None] + jnp.arange(R)[None, :], 0, R - 1)
    live = jnp.arange(R)[None, :] < lens[:, None]                 # (S, R)

    def step(carry, xs):
        s, z = carry
        q_t, k_t, v_t, lg_t, live_t = xs
        g = jnp.exp(lg_t)                                          # (S, K)
        pk = phi(k_t)                                              # (S,K,C,hd)
        s_new = (g[..., None, None, None] * s
                 + v_t[:, :, None, :, None] * pk[:, :, :, None, :])
        z_new = g[..., None, None] * z + pk
        s = jnp.where(live_t[:, None, None, None, None], s_new, s)
        z = jnp.where(live_t[:, None, None, None], z_new, z)
        pq = phi(q_t).reshape(-1, K, G, chunks(hd), hd)
        num = jnp.einsum("skgrl,skrcl->skgc", pq, s, precision=HIGHEST)
        den = jnp.einsum("skgrl,skrl->skg", pq, z, precision=HIGHEST)
        return (s, z), (num / (den[..., None] + eps)).reshape(-1, H, hd)

    move = lambda a: jnp.moveaxis(a[rows], 1, 0)
    (s1, z1), o = jax.lax.scan(
        step, (s0, z0), (move(q), move(k), move(v), move(log_g), live.T))
    o = jnp.moveaxis(o, 0, 1)                                     # (S,R,H,hd)
    flat = jnp.zeros((R, H, hd), F32).at[jnp.where(live, rows, R)].set(
        o, mode="drop")
    # The fill's rule: one row that leaves room joins the buffer and the
    # state stays as it was held; everything else hands back S_t, z_t.
    stay = joins(lens, zero, f0, F)
    seq, at = jnp.arange(slots.shape[0]), rows[:, 0]
    c_t = jnp.where(f0[:, None] == 0, 0.0,
                    rows_b[:, :, 2 * F + 1, 0]) + log_g[at]        # (S, K)
    joined = (rows_b.at[seq, :, f0].set(k[at]).at[seq, :, F + f0].set(v[at])
              .at[seq, :, 2 * F, f0].set(c_t)
              .at[seq, :, 2 * F + 1].set(
                  jnp.broadcast_to(c_t[..., None], c_t.shape + (hd,))))
    pick = lambda a, b: jnp.where(
        stay.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
    put = lambda whole, part: whole.at[layer, slots].set(part, mode="drop")
    return (flat, put(state, pick(held_s, s1)),
            put(norm, pick(held_z, z1.swapaxes(1, 2))),
            put(buf, pick(joined, rows_b)),
            filled(fill, layer, slots, stay, f0))


def _retention_kernel(meta_ref, slots_ref, starts_ref, lens_ref, zero_ref,
                      fill_ref, z_in_ref, q_hbm, kv_hbm, s_in_hbm, b_in_hbm,
                      o_hbm, s_hbm, z_ref, b_hbm, q_scr, kv_scr, o_scr,
                      x_scr, pb_scr, acc_scr, num_scr, den_scr, s_buf, b_buf,
                      qd_scr, kvd_scr, w_scr, flag, sems, *, G: int, hd: int,
                      TC: int, F: int, TW: int, eps: float):
    """Grid (S, K): sequence s, kv head j. s_hbm (the state) and b_hbm (the
    buffer) are the arrays in HBM, read and written where they lie (s_in_hbm
    / b_in_hbm are the same memory: aliased); z_in_ref / z_ref (C, K, hd) all
    heads of the slot. q_hbm / o_hbm (K, rows, G hd), kv_hbm (K, rows, 4 hd)
    = [k | v | the gates' running log, this row counted | the same, not
    counted] in HBM; q and k come scaled. fill_ref: rows the slot's buffer
    holds (0 where the sequence starts). s_buf / b_buf / qd_scr / kvd_scr:
    two sets, this step's and the next one's on its way in."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del s_in_hbm, b_in_hbm
    s = pl.program_id(0)
    j = pl.program_id(1)
    S = pl.num_programs(0)
    K = pl.num_programs(1)
    i = s * K + j
    cur = jax.lax.rem(i, 2)
    layer = meta_ref[0]
    n = lens_ref[s]
    slot = slots_ref[s]
    row0 = pl.multiple_of(starts_ref[s], 8)
    fresh = zero_ref[s] != 0
    f = fill_ref[s]
    C = hd // 2 + 1
    root2 = math.sqrt(2.0)
    sb = s_buf.at[cur]
    bb = b_buf.at[cur]
    dot = functools.partial(jax.lax.dot_general, precision=HIGHEST,
                            preferred_element_type=F32)
    nn = (((1,), (0,)), ((), ()))
    nt = (((1,), (1,)), ((), ()))
    start = lambda copy: copy.start()
    wait = lambda copy: copy.wait()

    def weight(r):
        return jnp.where((r == 0) | (r == C - 1), 1.0, root2).astype(F32)

    U = math.gcd(UNROLL, C)

    def unrolled(body, init):
        """`fori_loop(0, C, body, init)`, U steps to an iteration: a step's
        rotation, load and products wait for one another, and only side by
        side do the steps fill the vector unit."""
        def some(i, carry):
            for u in range(U):
                carry = body(i * U + u, carry)
            return carry

        return jax.lax.fori_loop(0, C // U, some, init)

    def fetch(s_, j_, set_, go):
        """Start (or wait for) what step (s_, j_) reads, into set `set_`: its
        state block and buffer and, for one row, its q / k / v rows."""
        n_ = lens_ref[s_]
        slot_ = slots_ref[s_]

        @pl.when(n_ > 0)
        def _():
            go(pltpu.make_async_copy(s_hbm.at[layer, slot_, j_],
                                     s_buf.at[set_], sems.at[3 + set_]))
            go(pltpu.make_async_copy(b_hbm.at[layer, slot_, j_],
                                     b_buf.at[set_], sems.at[5 + set_]))

        @pl.when(n_ == 1)
        def _():
            at = pl.ds(pl.multiple_of(starts_ref[s_], 8), DEC_ROWS)
            go(pltpu.make_async_copy(q_hbm.at[j_, at], qd_scr.at[set_],
                                     sems.at[7 + set_]))
            go(pltpu.make_async_copy(kv_hbm.at[j_, at], kvd_scr.at[set_],
                                     sems.at[9 + set_]))

    def leave(go, row, slot_, tile):
        """Start (or wait for) what a one-row step writes: its output's tile
        and the buffer's three tiles that hold its k, v and gate."""
        for src, dst in (
                (o_scr.at[pl.ds(0, DEC_ROWS)],
                 o_hbm.at[j, pl.ds(row, DEC_ROWS)]),
                (w_scr.at[pl.ds(0, 8)],
                 b_hbm.at[layer, slot_, j, pl.ds(tile, 8)]),
                (w_scr.at[pl.ds(8, 8)],
                 b_hbm.at[layer, slot_, j, pl.ds(F + tile, 8)]),
                (w_scr.at[pl.ds(16, 8)],
                 b_hbm.at[layer, slot_, j, pl.ds(2 * F, 8)])):
            go(pltpu.make_async_copy(src, dst, sems.at[12]))

    def left():
        """The last one-row step's writes have left o_scr and w_scr."""
        @pl.when(flag[0] == 1)
        def _():
            leave(wait, 0, 0, 0)
            flag[0] = 0

    def write_state():
        copy = pltpu.make_async_copy(sb, s_hbm.at[layer, slot, j],
                                     sems.at[11])
        copy.start()
        copy.wait()

    def fold(rows, crow, c_last):
        """S, z <- e^(c_last) S, z + the buffer's first `rows` rows, row s
        decayed by e^(c_last - c_s): crow (1, hd) holds c_s in lane s, c_last
        (1, hd) the last row's in every lane."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, F), 1)
        keep = jnp.where(lane < rows, jnp.exp(jnp.minimum(
            c_last[:, 0:F] - crow[:, 0:F], 0.0)), 0.0)              # (1, F)
        held = jnp.exp(c_last)
        real = jax.lax.broadcasted_iota(jnp.int32, (F, hd), 0) < rows
        kk = jnp.where(real, bb[0:F, :], 0.0)
        v_keep = jnp.where(real, bb[F:2 * F, :], 0.0).T * keep      # (hd, F)
        keep8 = jnp.broadcast_to(keep, (8, F))

        def features(r, carry):
            pk = kk * pltpu.roll(kk, r, 1) * weight(r)
            sb[r] = held * sb[r] + dot(v_keep, pk, nn)
            z_ref[r, pl.ds(j, 1), :] = (
                held * z_ref[r, pl.ds(j, 1), :] + dot(keep8, pk, nn)[0:1, :])
            return carry

        jax.lax.fori_loop(0, C, features, 0)

    @pl.when(i == 0)
    def _():
        flag[0] = 0
        fetch(s, j, 0, start)

    @pl.when(i + 1 < S * K)
    def _():
        wrap = j == K - 1
        fetch(jnp.where(wrap, jnp.minimum(s + 1, S - 1), s),
              jnp.where(wrap, 0, j + 1), 1 - cur, start)

    fetch(s, j, cur, wait)

    @pl.when(j == 0)
    def _():
        z_ref[...] = jnp.where(fresh, 0.0, z_in_ref[...])

    @pl.when(fresh & (n > 0))
    def _():
        def clear(r, carry):
            sb[r] = jnp.zeros((hd, hd), F32)
            return carry

        jax.lax.fori_loop(0, C, clear, 0)

    @pl.when(n == 1)
    def _one_row():
        qd = qd_scr.at[cur]
        kvd = kvd_scr.at[cur]
        # The row joins the buffer at its fill: k, v and c_t = c of the row
        # before it + its gate's log.
        c_t = (jnp.where(f == 0, 0.0, bb[2 * F + 1:2 * F + 2, :])
               + kvd[0:1, 2 * hd:3 * hd] - kvd[0:1, 3 * hd:4 * hd])
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, hd), 1)
        crow = jnp.where(lane == f, c_t, bb[2 * F:2 * F + 1, :])
        bb[pl.ds(f, 1), :] = kvd[0:1, 0:hd]
        bb[pl.ds(F + f, 1), :] = kvd[0:1, hd:2 * hd]
        bb[2 * F:2 * F + 1, :] = crow
        bb[2 * F + 1:2 * F + 2, :] = c_t
        # The G query heads' rows, one tile: phi of all in one pass.
        x_scr[...] = jnp.zeros_like(x_scr)
        for h in range(G):
            x_scr[h:h + 1, :] = qd[0:1, h * hd:(h + 1) * hd]
        x = x_scr[...]

        def features(r, zacc):
            p = x * pltpu.roll(x, r, 1) * weight(r)
            pb_scr[r] = p                   # head h's row in sublane h
            return zacc + p * z_ref[r, pl.ds(j, 1), :]

        zacc = unrolled(features, jnp.zeros(x_scr.shape, F32))
        den = jnp.sum(zacc, axis=1, keepdims=True)              # (XR, 1)

        # The state as the last fold left it: U chunks of TW tiles of 8
        # values to ONE product a head (not U x TW traced copies: a traced
        # operation is paid at every program's start).
        def tiles(t, carry):
            rows = pl.ds(pl.multiple_of(t * (8 * TW), 8 * TW), 8 * TW)

            def walk(i, accs):
                some = pl.ds(i * U, U)
                tile = sb[some, rows, :]                    # (U, 8 TW, hd)
                return tuple(
                    a + jnp.sum(pb_scr[some, h:h + 1, :] * tile, axis=0)
                    for h, a in enumerate(accs))

            accs = jax.lax.fori_loop(
                0, C // U, walk,
                tuple(jnp.zeros((8 * TW, hd), F32) for _ in range(G)))
            for h in range(G):
                acc_scr[h, rows, :] = accs[h]
            return carry

        jax.lax.fori_loop(0, hd // (8 * TW), tiles, 0)
        # A head's sums over the lanes stand down the sublanes; side by side
        # (head h in lane h) and transposed they are rows.
        cols = jnp.zeros((hd, hd), F32)
        for h in range(G):
            cols = jnp.where(lane == h, jnp.sum(acc_scr[h], axis=1,
                                                keepdims=True), cols)
        num = cols.T[0:x_scr.shape[0], :]                       # (XR, hd)
        # The buffered rows, this one among them, in the attention form.
        upto = jax.lax.broadcasted_iota(jnp.int32, (1, F), 1) <= f
        real = jax.lax.broadcasted_iota(jnp.int32, (F, hd), 0) <= f
        a = dot(x, bb[0:F, :], nt)                              # (XR, F)
        p = jnp.where(upto, a * a * jnp.exp(jnp.minimum(
            c_t[:, 0:F] - crow[:, 0:F], 0.0)), 0.0)
        since = jnp.exp(c_t)
        num = since * num + dot(p, jnp.where(real, bb[F:2 * F, :], 0.0), nn)
        den = since * den + jnp.sum(p, axis=1, keepdims=True)   # (XR, hd)
        out = num / (den + eps)
        left()
        for h in range(G):
            o_scr[0:1, h * hd:(h + 1) * hd] = out[h:h + 1, :]
        tile = pl.multiple_of(f // 8 * 8, 8)
        w_scr[0:8, :] = bb[pl.ds(tile, 8), :]
        w_scr[8:16, :] = bb[pl.ds(F + tile, 8), :]
        w_scr[16:24, :] = bb[2 * F:2 * F + 8, :]
        leave(start, row0, slot, tile)
        flag[0] = 1

        @pl.when(fresh | (f + 1 >= F))
        def _():
            fold(f + 1, crow, c_t)
            write_state()

    def move(base, rows):
        """Rows [base, base + rows) of this head's planes into scratch."""
        loads = [pltpu.make_async_copy(hbm.at[j, pl.ds(base, rows)],
                                       scr.at[pl.ds(0, rows)], sem)
                 for hbm, scr, sem in ((q_hbm, q_scr, sems.at[0]),
                                       (kv_hbm, kv_scr, sems.at[1]))]
        for copy in loads:
            copy.start()
        for copy in loads:
            copy.wait()

    def put(base, rows):
        store = pltpu.make_async_copy(o_scr.at[pl.ds(0, rows)],
                                      o_hbm.at[j, pl.ds(base, rows)],
                                      sems.at[2])
        store.start()
        store.wait()

    @pl.when(n > 1)
    def _slice():
        left()

        @pl.when(f > 0)         # a sequence that was parked among its rows
        def _():
            fold(f, bb[2 * F:2 * F + 1, :], bb[2 * F + 1:2 * F + 2, :])

        def chunk(t, carry):
            base = pl.multiple_of(row0 + t * TC, 8)
            real = jnp.minimum(TC, n - t * TC)
            move(base, TC)
            row = jax.lax.broadcasted_iota(jnp.int32, (TC, hd), 0)
            valid = row < real
            kk = jnp.where(valid, kv_scr[:, 0:hd], 0.0)
            vv = jnp.where(valid, kv_scr[:, hd:2 * hd], 0.0)
            # c_i: the gates' log from the chunk's first row through row i.
            c = kv_scr[:, 2 * hd:3 * hd] - kv_scr[0:1, 3 * hd:4 * hd]
            c_last = jnp.sum(jnp.where(row == real - 1, c, 0.0), axis=0,
                             keepdims=True)
            c = jnp.where(valid, c, c_last)
            cb = c if hd == TC else jnp.broadcast_to(c[:, 0:1], (TC, TC))
            i_s = jax.lax.broadcasted_iota(jnp.int32, (TC, TC), 0)
            s_s = jax.lax.broadcasted_iota(jnp.int32, (TC, TC), 1)
            decay = jnp.where((s_s <= i_s) & (s_s < real),
                              jnp.exp(jnp.minimum(cb - cb.T, 0.0)), 0.0)
            for h in range(G):
                qh = q_scr[:, h * hd:(h + 1) * hd]
                a = dot(qh, kk, nt)
                p = a * a * decay
                num_scr[h] = dot(p, vv, nn)
                den_scr[h] = jnp.broadcast_to(
                    jnp.sum(p, axis=1, keepdims=True), (TC, hd))
            into = jnp.exp(c)                   # the state as row i sees it
            keep = jnp.where(valid, jnp.exp(c_last - c), 0.0)
            e_last = jnp.exp(c_last)
            v_keep = (vv * keep).T                              # (hd, TC)

            def features(r, carry):
                w = weight(r)
                pk = kk * pltpu.roll(kk, r, 1) * w
                tile = sb[r]
                z_old = z_ref[r, pl.ds(j, 1), :]
                for h in range(G):
                    qh = q_scr[:, h * hd:(h + 1) * hd]
                    pq = qh * pltpu.roll(qh, r, 1) * w
                    num_scr[h] = num_scr[h] + into * dot(pq, tile, nt)
                    den_scr[h] = den_scr[h] + into * jnp.sum(
                        pq * z_old, axis=1, keepdims=True)
                sb[r] = e_last * tile + dot(v_keep, pk, nn)
                z_ref[r, pl.ds(j, 1), :] = e_last * z_old + jnp.sum(
                    pk * keep, axis=0, keepdims=True)
                return carry

            jax.lax.fori_loop(0, C, features, 0)
            for h in range(G):
                o_scr[:, h * hd:(h + 1) * hd] = num_scr[h] / (den_scr[h]
                                                              + eps)
            put(base, TC)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(n, TC), chunk, 0)
        write_state()

    @pl.when(i == S * K - 1)
    def _():
        left()


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def power_retention_call(q, kv, state, norm, buf, layer, slots, starts, lens,
                         zero, fill, *, eps: float, interpret: bool):
    """The kernel's launch: q (K, rows, G hd), kv (K, rows, 4 hd), a
    sequence's rows from `starts[s]`, a multiple of 8, on, and CHUNK rows to
    spare behind the last; fill (S,) the rows each sequence's buffer holds.
    -> (o, state, norm, buf). Jitted under a name of its own so that a
    profile's events read `power_retention_call.<n>` (as `ssm_scan_call`
    does)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, rows, width = q.shape
    hd = kv.shape[2] // 4
    G = width // hd
    C = chunks(hd)
    S = slots.shape[0]
    TC, F = CHUNK, fold_rows(hd)
    XR = -(-G // 8) * 8
    if F % 8 or buf.shape[3] != 2 * F + 8:
        raise ValueError(f"FOLD {FOLD}: a multiple of 8, and the buffer's "
                         f"{buf.shape[3]} rows 2 x {F} + 8")

    z_block = pl.BlockSpec(
        (None, None, C, K, hd),
        lambda s, j, meta, slots, *_: (meta[0], slots[s], 0, 0, 0))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(S, K),
        in_specs=[z_block, anywhere, anywhere, anywhere, anywhere],
        out_specs=[anywhere, anywhere, z_block, anywhere],
        scratch_shapes=[
            pltpu.VMEM((TC, G * hd), F32),              # a chunk's q rows
            pltpu.VMEM((TC, 4 * hd), F32),              # k, v, gates
            pltpu.VMEM((TC, G * hd), F32),              # o rows
            pltpu.VMEM((XR, hd), F32),                  # a decode row's q
            pltpu.VMEM((C, XR, hd), F32),               # its phi, a head a row
            pltpu.VMEM((G, hd, hd), F32),               # its sums
            pltpu.VMEM((G, TC, hd), F32),               # a chunk's numerators
            pltpu.VMEM((G, TC, hd), F32),               # and denominators
            pltpu.VMEM((2, C, hd, hd), F32),            # the state, two sets
            pltpu.VMEM((2, 2 * F + 8, hd), F32),        # the buffer
            pltpu.VMEM((2, DEC_ROWS, G * hd), F32),     # a decode row's q
            pltpu.VMEM((2, DEC_ROWS, 4 * hd), F32),     # k, v, gates
            pltpu.VMEM((24, hd), F32),                  # the buffer's tiles out
            pltpu.SMEM((1,), jnp.int32),                # writes in flight
            pltpu.SemaphoreType.DMA((13,)),
        ],
    )
    block = 4 * C * hd * hd
    return pl.pallas_call(
        functools.partial(_retention_kernel, G=G, hd=hd, TC=TC, F=F,
                          TW=math.gcd(WALK_TILES, hd // 8), eps=eps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype),
                   jax.ShapeDtypeStruct(buf.shape, buf.dtype)],
        # norm, state and the buffer, in place
        input_output_aliases={6: 2, 9: 1, 10: 3},
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # The state's two sets, and the scratch.
            vmem_limit_bytes=2 * block + (24 << 20)),
        **kernel_tag("power_retention"),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, starts, lens, zero,
      fill, norm, q, kv, state, buf)


def power_retention(q, k, v, log_g, state, norm, buf, fill, layer, slots,
                    starts, lens, zero, *, scale: float, eps: float,
                    impl: str = "pallas", interpret: Optional[bool] = None):
    """`power_retention_reference`'s contract, by the Pallas kernel where
    `impl` is "pallas"."""
    slots, starts, lens, zero = enter(state, slots, starts, lens, zero)
    if impl != "pallas":
        return power_retention_reference(
            q, k, v, log_g, state, norm, buf, fill, layer, slots, starts,
            lens, zero, scale=scale, eps=eps)
    R, H, hd = q.shape
    K = k.shape[1]
    S = slots.shape[0]
    root = math.sqrt(scale)
    # The planes the kernel reads: a sequence's rows from a multiple of 8 on
    # (a DMA starts on a whole tile), in the sequences' order.
    room = -(-lens // 8) * 8
    first = jnp.cumsum(room) - room                               # (S,)
    r = jnp.arange(R)[:, None]
    mine = (r >= starts[None, :]) & (r < (starts + lens)[None, :])  # (R, S)
    live = jnp.any(mine, axis=1)
    # (to a multiple of PLANE rows, so that a ladder of token buckets shares
    # a few traces of the kernel: a trace is paid at every program's start)
    P = -(-(-(-R // 8) * 8 + 8 * S + CHUNK) // PLANE) * PLANE
    at = jnp.where(live, jnp.sum(jnp.where(
        mine, first[None, :] + r - starts[None, :], 0), axis=1), P)
    through = jnp.cumsum(log_g.astype(F32), axis=0)               # (R, K)
    lanes = lambda a: jnp.broadcast_to(a[..., None], (R, K, hd))
    plane = lambda a: jnp.moveaxis(
        jnp.zeros((P,) + a.shape[1:], F32).at[at].set(a, mode="drop"), 1, 0)
    i32 = lambda a: a.astype(jnp.int32)
    f0 = first_fill(fill, layer, slots, zero)
    o, state, norm, buf = power_retention_call(
        plane((q.astype(F32) * root).reshape(R, K, -1)),
        plane(jnp.concatenate(
            [k.astype(F32) * root, v.astype(F32), lanes(through),
             lanes(through - log_g.astype(F32))], axis=-1)),
        state, norm, buf, layer, i32(slots), i32(first), i32(lens), i32(zero),
        i32(f0), eps=eps, interpret=interpreted(interpret))
    fill = filled(fill, layer, slots, joins(lens, zero, f0, fold_rows(hd)), f0)
    o = jnp.moveaxis(o, 0, 1)[jnp.minimum(at, P - 1)].reshape(R, H, hd)
    return jnp.where(live[:, None, None], o, 0.0), state, norm, buf, fill
