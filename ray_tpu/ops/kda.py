"""Kimi Delta Attention (arXiv:2510.26692): a gated DELTA RULE over ragged
token-major rows, the recurrent step for a decode row and the chunked (WY)
form for a prompt slice, in one call a layer.

For head h of a sequence, with q and k already normalised, log a_t <= 0 the
forget gate's log A KEY CHANNEL and 0 < b_t < 1 the write strength a head,

  S' = diag(a_t) S_(t-1);  u_t = b_t (v_t - S'^T k_t);  S_t = S' + k_t u_t^T;
  o_t = S_t^T q_t.

The state S (dk keys x dv values, float32) is a SLOT a sequence
(llm/model_runner.py, "Layer groups": a state group) and, unlike
ops/power_retention.py's accumulation `S <- g S + phi(k) v^T`, the update
READS it (`S'^T k_t`): a row cannot be buffered beside the state and folded in
later, so every decode row reads and rewrites its sequence's S.

  state   (layers, slots + 1, H, dk, dv) float32; the last slot is nobody's
          (padding sequences read and write it)

A sequence whose segment starts at position 0 starts from zeros (`zero`), so
no program ever clears a slot.

  `kda_reference`   the recurrence as a `lax.scan` over time, the sequences
                    side by side: the tests' oracle and the path off the chip
  `kda`             the Pallas kernel where `impl == "pallas"`

The kernel reads the step's rows WHERE THEY LIE: x (rows, H, W) token-major as
the layer's projections leave them, W = [q | k | log a | v | beta in every
lane]. The row is the untiled leading axis and (H, W) are whole (8, 128)
tiles, so a block or a DMA may start at ANY row (with the heads in front the
rows are the sublanes, every sequence has to start on a multiple of 8, and a
wrapper has to gather and transpose a plane a layer to make it so). Its
outputs are token-major too. The grid is (sequences, heads / HEADS) in order; a
step holds HEADS heads' state as one block (indexed by scalar prefetch: Pallas
fetches the next block while this one is computed and writes it back where it
came from, the state aliased in and out) and, for a sequence of one row, these
heads of that row as a second block, `(None, HEADS, W)` at `starts[s]`.

  one row (a decode row): the step above on the VPU, float32, exactly as
      written: the three vectors that scale S's rows (a, k, q down the key
      channels) come from ONE 128 x 128 transpose a grid step, all heads'.
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

A slice's chunk is one DMA of (CHUNK, HEADS, W) from row `starts[s] + t CHUNK`
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

# Rows a step of the chunked form takes, rows of a block of M (the
# publication's kernel: 64 and 16) and heads a grid step holds.
CHUNK = 64
SUB = 16
HEADS = 8
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def state_shape(layers: int, slots: int, heads: int, dk: int, dv: int):
    """S of `slots` sequences and the junk slot behind them."""
    return (layers, slots + 1, heads, dk, dv)


def kda_reference(q, k, v, log_a, beta, state, layer, slots, starts, lens,
                  zero):
    """The recurrence, a row at a time: q / k / log_a (R, H, dk), v (R, H,
    dv), beta (R, H), float32; state `state_shape`'s; slots / starts / lens /
    zero (S,). -> (o (R, H, dv) float32, rows outside every segment zero;
    state with the sequences' slots written)."""
    R = q.shape[0]
    q, k, v, log_a, beta = (a.astype(F32) for a in (q, k, v, log_a, beta))
    s0 = jnp.where(zero[:, None, None, None], 0.0, state[layer, slots])
    rows = jnp.clip(starts[:, None] + jnp.arange(R)[None, :], 0, R - 1)
    live = jnp.arange(R)[None, :] < lens[:, None]                 # (S, R)

    def step(s, xs):
        q_t, k_t, v_t, la_t, b_t, live_t = xs
        held = jnp.exp(la_t)[..., None] * s                   # (S, H, dk, dv)
        u = b_t[..., None] * (v_t - jnp.einsum(
            "shkv,shk->shv", held, k_t, precision=HIGHEST))
        new = held + k_t[..., None] * u[..., None, :]
        s = jnp.where(live_t[:, None, None, None], new, s)
        return s, jnp.einsum("shkv,shk->shv", s, q_t, precision=HIGHEST)

    move = lambda a: jnp.moveaxis(a[rows], 1, 0)
    s1, o = jax.lax.scan(step, s0, (move(q), move(k), move(v), move(log_a),
                                    move(beta), live.T))
    o = jnp.moveaxis(o, 0, 1)                                 # (S, R, H, dv)
    flat = jnp.zeros(v.shape, F32).at[jnp.where(live, rows, R)].set(
        o, mode="drop")
    return flat, state.at[layer, slots].set(s1, mode="drop")


def _kda_kernel(meta_ref, slots_ref, starts_ref, lens_ref, zero_ref, x_ref,
                s_in_ref, x_hbm, od_ref, os_hbm, s_ref, x_scr, o_scr, k_scr,
                c_scr, t_scr, sems, *, HB: int, dk: int, dv: int, TC: int,
                TS: int):
    """Grid (S, H / HB): sequence s, heads [j HB, (j + 1) HB). s_in_ref /
    s_ref (HB, dk, dv): their state, aliased. x_ref (HB, W): these heads of
    the step's row `starts[s]`, where it lies, W = [q | k | log a | v | beta
    in every lane]; x_hbm the same rows (rows, H, W) in HBM, for a slice's
    chunks. od_ref (HB, dv): a decode row's output, at the same row of o;
    os_hbm (rows, H, dv): a slice's."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del meta_ref, slots_ref
    s = pl.program_id(0)
    j = pl.program_id(1)
    n = lens_ref[s]
    row0 = starts_ref[s]
    fresh = zero_ref[s] != 0
    dot = functools.partial(jax.lax.dot_general, precision=HIGHEST,
                            preferred_element_type=F32)
    nn = (((1,), (0,)), ((), ()))
    nt = (((1,), (1,)), ((), ()))
    V0, B0 = 3 * dk, 3 * dk + dv

    def held(h):
        return jnp.where(fresh, 0.0, s_in_ref[h])

    @pl.when(n <= 0)
    def _():
        s_ref[...] = s_in_ref[...]

    @pl.when(n == 1)
    def _one_row():
        # a, k, q of the HB heads as rows h, HB + h, 2 HB + h, then down the
        # key channels by one transpose.
        t_scr[0:HB, :] = jnp.exp(x_ref[:, 2 * dk:V0])
        t_scr[HB:2 * HB, :] = x_ref[:, dk:2 * dk]
        t_scr[2 * HB:3 * HB, :] = x_ref[:, 0:dk]
        cols = t_scr[...].T                                     # (dk, dk)
        for h in range(HB):
            a, kc, qc = (cols[:, i * HB + h:i * HB + h + 1] for i in range(3))
            after = held(h) * a
            u = x_ref[h:h + 1, B0:B0 + dv] * (
                x_ref[h:h + 1, V0:B0]
                - jnp.sum(after * kc, axis=0, keepdims=True))
            new = after + kc * u
            s_ref[h] = new
            od_ref[h:h + 1, :] = jnp.sum(new * qc, axis=0, keepdims=True)

    @pl.when(n > 1)
    def _slice():
        r_i = jax.lax.broadcasted_iota(jnp.int32, (TC, TC), 0)
        c_i = jax.lax.broadcasted_iota(jnp.int32, (TC, TC), 1)
        ones = jnp.where(c_i <= r_i, 1.0, 0.0)
        eye = jnp.where(c_i == r_i, 1.0, 0.0)
        cols_s = jax.lax.broadcasted_iota(jnp.int32, (TS, TC), 1)
        s_ref[...] = jnp.where(fresh, 0.0, s_in_ref[...])
        heads = pl.ds(pl.multiple_of(j * HB, HB), HB)

        def chunk(t, carry):
            base = row0 + t * TC
            real = jnp.minimum(TC, n - t * TC)
            load = pltpu.make_async_copy(
                x_hbm.at[pl.ds(base, TC), heads], x_scr, sems.at[0])
            load.start()
            load.wait()
            valid = jax.lax.broadcasted_iota(jnp.int32, (TC, 1), 0) < real

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
                state = s_ref[h]
                e_c = jnp.exp(c)
                u = dot(inv, bb * (vv - dot(kk * e_c, state, nn)), nn)
                o_scr[:, h, :] = dot(qq * e_c, state, nn) + dot(
                    jnp.where(c_i <= r_i, m_q, 0.0), u, nn)
                last = c[TC - 1:TC]
                t_scr[0:1, :] = jnp.exp(last)
                s_ref[h] = (t_scr[...].T[:, 0:1] * state
                            + dot((kk * jnp.exp(last - c)).T, u, nn))
                return carry

            jax.lax.fori_loop(0, HB, head, 0)
            store = pltpu.make_async_copy(
                o_scr, os_hbm.at[pl.ds(base, TC), heads], sems.at[1])
            store.start()
            store.wait()
            return carry

        jax.lax.fori_loop(0, pl.cdiv(n, TC), chunk, 0)


@functools.partial(jax.jit, static_argnames=("dk", "chunk", "sub",
                                             "interpret"))
def kda_call(x, state, layer, slots, starts, lens, zero, *, dk: int,
             chunk: int, sub: int, interpret: bool):
    """The kernel's launch: x (rows, H, 3 dk + 2 dv) = [q | k | log a | v |
    beta], the step's rows as they lie, a sequence's from `starts[s]` on, and
    `chunk` rows to spare behind the last. -> (o of the sequences of one row;
    o of the others; state), o (rows, H, dv), the rows where x's are. Jitted
    under a name of its own so that a profile's events read `kda_call.<n>`
    (as `ssm_scan_call` does)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, H, width = x.shape
    dv = (width - 3 * dk) // 2
    S = slots.shape[0]
    HB = next(b for b in range(min(HEADS, H, dk // 3), 0, -1) if H % b == 0)
    if chunk % sub or chunk & (chunk - 1) or sub % 8:
        raise ValueError(f"chunk {chunk}: a power of two, in blocks of "
                         f"{sub} rows, themselves a multiple of 8")
    slot_block = pl.BlockSpec(
        (None, None, HB, dk, dv),
        lambda s, j, meta, slots, *_: (meta[0], slots[s], j, 0, 0))
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
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(S, H // HB),
        in_specs=[row_in, slot_block, anywhere],
        out_specs=[row_out, anywhere, slot_block],
        scratch_shapes=[
            pltpu.VMEM((chunk, HB, width), F32),        # a chunk's rows
            pltpu.VMEM((chunk, HB, dv), F32),           # its output
            pltpu.VMEM((chunk, dk), F32),               # a head's k
            pltpu.VMEM((chunk, dk), F32),               # its c
            pltpu.VMEM((dk, dk), F32),                  # rows to transpose
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = jax.ShapeDtypeStruct((rows, H, dv), F32)
    return pl.pallas_call(
        functools.partial(_kda_kernel, HB=HB, dk=dk, dv=dv, TC=chunk,
                          TS=sub),
        grid_spec=grid_spec,
        out_shape=[out, out, jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 2},        # the state, in place
        interpret=interpret,
        **kernel_tag("kda"),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, starts, lens, zero,
      x, state, x)


def kda(q, k, v, log_a, beta, state, layer, slots, starts, lens, zero, *,
        impl: str = "pallas", interpret: Optional[bool] = None,
        chunk: Optional[int] = None, sub: Optional[int] = None):
    """`kda_reference`'s contract, by the Pallas kernel where `impl` is
    "pallas". Sequences must lie in the order of their rows (`starts`
    ascending, as a mixed tick and a rectangle lay them)."""
    slots, starts, lens = (jnp.asarray(a) for a in (slots, starts, lens))
    # A sequence without a row leaves its slot alone: it takes the junk one.
    slots = jnp.where(lens > 0, slots, state.shape[1] - 1)
    zero = jnp.asarray(zero).astype(bool)
    if impl != "pallas":
        return kda_reference(q, k, v, log_a, beta, state, layer, slots,
                             starts, lens, zero)
    if interpret is None:
        from ray_tpu.ops import is_tpu_backend

        interpret = not is_tpu_backend()
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
    # (a sequence without a row may start anywhere: its block is read, and
    # dropped, so it is read inside the rows)
    o_row, o_rows, state = kda_call(
        x, state, layer, i32(slots), i32(jnp.clip(starts, 0, R - 1)),
        i32(lens), i32(zero), dk=dk, chunk=chunk, sub=sub,
        interpret=interpret)
    r = jnp.arange(R)[:, None]
    mine = (r >= starts[None, :]) & (r < (starts + lens)[None, :])  # (R, S)
    one = jnp.any(mine & (lens == 1)[None, :], axis=1)[:, None, None]
    live = jnp.any(mine, axis=1)[:, None, None]
    return jnp.where(live, jnp.where(one, o_row[:R], o_rows[:R]), 0.0), state
