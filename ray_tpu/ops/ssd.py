"""The Mamba-2 recurrence (state-space duality, arXiv:2405.21060) over ragged
token-major rows: the recurrent step for a decode row and the chunked form,
which is matrix products, for a prompt slice, in one call a layer.

For head h of a sequence, with x_t (P values), dt_t > 0 (after the softplus),
A_h < 0, and B_t, C_t (N each) shared by the H / G heads of a GROUP,

  a_t = exp(dt_t A_h);  S_t = a_t S_(t-1) + (dt_t x_t) B_t^T;  y_t = S_t C_t.

The state S (P x N, float32) is a MATRIX a head with ONE decay a head and
token (ops/ssm_scan.py's Mamba-1 state is diagonal: a decay a channel and
state dimension, B and C scalars a token). It is a SLOT a sequence
(llm/model_runner.py, "Layer groups": a state group):

  state   (layers, slots + 1, H, P, N) float32; the last slot is nobody's
          (padding sequences read and write it)

A sequence whose segment starts at position 0 starts from zeros (`zero`), so
no program ever clears a slot. The skip `D x_t`, the gate and the grouped
norm behind it are the layer's (models/nemotron_h.py), as is the convolution
before it (`ops/ssm_scan.ragged_conv`).

  `ssd_reference`   the recurrence as a `lax.scan` over time, the sequences
                    side by side: the tests' oracle and the path off the chip
  `ssd`             the Pallas kernel where `impl == "pallas"`

The kernel reads the step's rows WHERE THEY LIE, token-major as ops/kda.py's
(the row is the untiled leading axis, so a block or a DMA may start at any
row): x (rows, H, 2 P) = [dt x | log a in every lane] a head, and bc (rows, G,
2 N) = [B | C] a GROUP: B and C are never broadcast to the heads. The grid is
(sequences, H / HEADS) in order, HEADS heads of one group a step; a step
holds their state as one block (indexed by scalar prefetch: Pallas fetches
the next block while this one is computed and writes it back where it came
from, the state aliased in and out).

  one row (a decode row): the step above on the VPU, exactly as written. The
      heads' dt x and a reach the state's sublanes by ONE transpose a grid
      step, and y leaves by one more.
  more rows (a slice): chunks of `chunk` rows. With l_i the logs of a summed
      from the chunk's first row through row i (a product with a triangle of
      ones) and G_ij = C_i . B_j, computed once a chunk for the group's heads,

        Y = (G * exp(l_i - l_j) for j <= i) (dt X) + exp(l_i) C S_0^T,
        S <- exp(l_Q) S_0 + (dt X exp(l_Q - l_j))^T B.

      Every exponent is <= 0: no running product is inverted. Products are
      float32 at `HIGHEST`. Rows past the segment decay nothing and add
      nothing, so the last row holds the chunk's whole decay.

A slice's chunk is one DMA of (chunk, HEADS, 2 P) from row `starts[s] + t
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

# Rows a step of the chunked form takes (the published `chunk_size`) and the
# most heads a grid step holds (one group's 16 at the published widths).
CHUNK = 128
HEADS = 16
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def state_shape(layers: int, slots: int, heads: int, head_dim: int,
                d_state: int):
    """S of `slots` sequences and the junk slot behind them."""
    return (layers, slots + 1, heads, head_dim, d_state)


def ssd_reference(x, dt, A, B, C, state, layer, slots, starts, lens, zero):
    """The recurrence, a row at a time: x (R, H, P), dt (R, H) after the
    softplus, A (H,) negative, B / C (R, G, N), float32; state
    `state_shape`'s; slots / starts / lens / zero (S,). -> (y (R, H, P)
    float32 without the skip, rows outside every segment zero; state with
    the sequences' slots written)."""
    R, H, P = x.shape
    G, N = B.shape[1:]
    x, dt, A, B, C = (a.astype(F32) for a in (x, dt, A, B, C))
    s0 = jnp.where(zero[:, None, None, None], 0.0, state[layer, slots])
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
    return flat, state.at[layer, slots].set(s1, mode="drop")


def _ssd_kernel(meta_ref, slots_ref, starts_ref, lens_ref, zero_ref, x_ref,
                bc_ref, s_in_ref, x_hbm, bc_hbm, od_ref, os_hbm, s_ref, x_scr,
                bc_scr, o_scr, t_scr, sems, *, HB: int, P: int, N: int,
                TC: int, per_group: int):
    """Grid (S, H / HB): sequence s, heads [j HB, (j + 1) HB), all of group
    j HB // per_group. s_in_ref / s_ref (HB, P, N): their state, aliased.
    x_ref (HB, 2 P): these heads of the step's row `starts[s]`, where it
    lies, [dt x | log a in every lane]; bc_ref (G, 2 N): that row's [B | C],
    every group's; x_hbm / bc_hbm the same rows in HBM, for a slice's chunks.
    od_ref (HB, 2 P): a decode row's y in its first P lanes, at the same row
    of o; os_hbm (rows, H, 2 P): a slice's."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del meta_ref, slots_ref
    s = pl.program_id(0)
    j = pl.program_id(1)
    n = lens_ref[s]
    row0 = starts_ref[s]
    fresh = zero_ref[s] != 0
    g = (j * HB) // per_group
    W = 2 * P
    dot = functools.partial(jax.lax.dot_general, precision=HIGHEST,
                            preferred_element_type=F32)
    nn = (((1,), (0,)), ((), ()))
    nt = (((1,), (1,)), ((), ()))

    @pl.when(n <= 0)
    def _():
        s_ref[...] = s_in_ref[...]

    @pl.when(n == 1)
    def _one_row():
        # dt x and a of the HB heads as rows, then down the state's sublanes
        # by one transpose: column h is [dt x (P) ; a (P)] of head h.
        lane = jax.lax.broadcasted_iota(jnp.int32, (HB, W), 1)
        tile = x_ref[...]
        t_scr[0:HB, :] = jnp.where(lane >= P, jnp.exp(tile), tile)
        cols = t_scr[...].T                                      # (W, W)
        # This group's B and C out of the row's G (a masked sum: Mosaic
        # loads no sublane at a traced index).
        mine = jax.lax.broadcasted_iota(jnp.int32, (bc_ref.shape[0], N),
                                        0) == g
        b_row = jnp.sum(jnp.where(mine, bc_ref[:, 0:N], 0.0), axis=0,
                        keepdims=True)                           # (1, N)
        c_row = jnp.sum(jnp.where(mine, bc_ref[:, N:2 * N], 0.0), axis=0,
                        keepdims=True)
        at = jax.lax.broadcasted_iota(jnp.int32, (P, W), 1)
        ys = jnp.zeros((P, W), F32)
        for h in range(HB):
            held = jnp.where(fresh, 0.0, s_in_ref[h])            # (P, N)
            new = cols[P:W, h:h + 1] * held + cols[0:P, h:h + 1] * b_row
            s_ref[h] = new
            ys = jnp.where(at == h, jnp.sum(new * c_row, axis=1,
                                            keepdims=True), ys)
        t_scr[0:P, :] = ys
        od_ref[...] = t_scr[...].T[0:HB, :]

    @pl.when(n > 1)
    def _slice():
        r_i = jax.lax.broadcasted_iota(jnp.int32, (TC, TC), 0)
        c_i = jax.lax.broadcasted_iota(jnp.int32, (TC, TC), 1)
        ones = jnp.where(c_i <= r_i, 1.0, 0.0)
        s_ref[...] = jnp.where(fresh, 0.0, s_in_ref[...])
        heads = pl.ds(pl.multiple_of(j * HB, HB), HB)

        def chunk(t, carry):
            base = row0 + t * TC
            real = jnp.minimum(TC, n - t * TC)
            loads = [
                pltpu.make_async_copy(x_hbm.at[pl.ds(base, TC), heads],
                                      x_scr, sems.at[0]),
                # (every group's: one group's rows are no whole tiles)
                pltpu.make_async_copy(bc_hbm.at[pl.ds(base, TC)], bc_scr,
                                      sems.at[1])]
            for copy in loads:
                copy.start()
            for copy in loads:
                copy.wait()
            valid = jax.lax.broadcasted_iota(jnp.int32, (TC, 1), 0) < real
            bc = bc_scr[:, g, :]                                 # (TC, 2 N)
            bb = jnp.where(valid, bc[:, 0:N], 0.0)
            cc = bc[:, N:2 * N]
            cb = dot(cc, bb, nt)                 # (TC, TC): C_i . B_j

            def head(h, carry):
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
                    jnp.broadcast_to(l_c[TC - 1:TC, 0:1], (1, width)))
                state = s_ref[h]                                 # (P, N)
                # (the lanes past P carry the logs along: nobody reads them)
                o_scr[:, h, :] = dot(m, x, nn) + jnp.exp(l_w) * dot(
                    cc, jnp.concatenate([state, state], 0), nt)
                into = (x * jnp.exp(last(W) - l_w)).T
                s_ref[h] = (jnp.exp(last(N)) * state
                            + dot(into[0:P], bb, nn))
                return carry

            jax.lax.fori_loop(0, HB, head, 0)
            store = pltpu.make_async_copy(
                o_scr, os_hbm.at[pl.ds(base, TC), heads], sems.at[2])
            store.start()
            store.wait()
            return carry

        jax.lax.fori_loop(0, pl.cdiv(n, TC), chunk, 0)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_call(x, bc, state, layer, slots, starts, lens, zero, *, chunk: int,
             interpret: bool):
    """The kernel's launch: x (rows, H, 2 P) = [dt x | log a in every lane],
    bc (rows, G, 2 N) = [B | C], the step's rows as they lie, a sequence's
    from `starts[s]` on, and `chunk` rows to spare behind the last. -> (y of
    the sequences of one row; y of the others; state), y (rows, H, 2 P) with
    the values in the first P lanes, the rows where x's are. Jitted under a
    name of its own so that a profile's events read `ssd_call.<n>` (as
    `kda_call` does)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, H, W = x.shape
    G, N = bc.shape[1], bc.shape[2] // 2
    P = W // 2
    S = slots.shape[0]
    per_group = H // G
    HB = next(b for b in range(min(HEADS, per_group, W), 0, -1)
              if per_group % b == 0)
    slot_block = pl.BlockSpec(
        (None, None, HB, P, N),
        lambda s, j, meta, slots, *_: (meta[0], slots[s], j, 0, 0))
    # A decode row where it lies; every other sequence's output block is a
    # spare row's, so that it lands on nobody's.
    at_row = lambda s, j, meta, slots, starts, *_: (starts[s], j, 0)
    row_out = pl.BlockSpec(
        (None, HB, W),
        lambda s, j, meta, slots, starts, lens, *_: (
            jnp.where(lens[s] == 1, starts[s], rows - 1), j, 0))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(S, H // HB),
        in_specs=[pl.BlockSpec((None, HB, W), at_row),
                  pl.BlockSpec((None, G, 2 * N),
                               lambda s, j, meta, slots, starts, *_: (
                                   starts[s], 0, 0)),
                  slot_block, anywhere, anywhere],
        out_specs=[row_out, anywhere, slot_block],
        scratch_shapes=[
            pltpu.VMEM((chunk, HB, W), F32),            # a chunk's rows
            pltpu.VMEM((chunk, G, 2 * N), F32),         # their B | C
            pltpu.VMEM((chunk, HB, W), F32),            # its output
            pltpu.VMEM((W, W), F32),                    # rows to transpose
            pltpu.SemaphoreType.DMA((3,)),
        ],
    )
    out = jax.ShapeDtypeStruct((rows, H, W), F32)
    return pl.pallas_call(
        functools.partial(_ssd_kernel, HB=HB, P=P, N=N, TC=chunk,
                          per_group=per_group),
        grid_spec=grid_spec,
        out_shape=[out, out, jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 2},        # the state, in place
        interpret=interpret,
        **kernel_tag("ssd"),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, starts, lens, zero,
      x, bc, state, x, bc)


def ssd(x, dt, A, B, C, state, layer, slots, starts, lens, zero, *,
        impl: str = "pallas", interpret: Optional[bool] = None,
        chunk: Optional[int] = None):
    """`ssd_reference`'s contract, by the Pallas kernel where `impl` is
    "pallas". Sequences must lie in the order of their rows (`starts`
    ascending, as a mixed tick and a rectangle lay them)."""
    slots, starts, lens = (jnp.asarray(a) for a in (slots, starts, lens))
    # A sequence without a row leaves its slot alone: it takes the junk one.
    slots = jnp.where(lens > 0, slots, state.shape[1] - 1)
    zero = jnp.asarray(zero).astype(bool)
    if impl != "pallas":
        return ssd_reference(x, dt, A, B, C, state, layer, slots, starts,
                             lens, zero)
    if interpret is None:
        from ray_tpu.ops import is_tpu_backend

        interpret = not is_tpu_backend()
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
    # (a sequence without a row may start anywhere: its block is read, and
    # dropped, so it is read inside the rows)
    y_row, y_rows, state = ssd_call(
        packed, bc, state, layer, i32(slots),
        i32(jnp.clip(starts, 0, R - 1)), i32(lens), i32(zero), chunk=chunk,
        interpret=interpret)
    r = jnp.arange(R)[:, None]
    mine = (r >= starts[None, :]) & (r < (starts + lens)[None, :])  # (R, S)
    one = jnp.any(mine & (lens == 1)[None, :], axis=1)[:, None, None]
    live = jnp.any(mine, axis=1)[:, None, None]
    return jnp.where(live, jnp.where(one, y_row[:R, :, :P],
                                     y_rows[:R, :, :P]), 0.0), state
