"""The selective scan of a state-space (Mamba-1) layer over ragged
token-major rows, and the causal convolution before it.

A step's R rows are segments of S sequences (`starts`, `lens`: one row for a
decode row, up to a prefill chunk for a slice), each continuing from its
sequence's SLOT of a state that lives beside the paged cache
(llm/model_runner.py, "Layer groups": a state group):

  scan state   (layers, slots + 1, N, C, 128) float32: a slot's (d_i, N)
               state with the channels on the lanes, d_i = C x 128, and the N
               state dimensions leading, so that every per-channel operand of
               the recurrence is a whole (C, 128) tile and B_t, C_t are
               scalars. The last slot is nobody's: padding sequences read and
               write it
  conv tail    (layers, slots + 1, K_c - 1, d_i): the rows before a segment

The slots' contract (`slots`, `starts`, `lens`, `zero`, the junk slot) is
ops/state_slots.py's; this state is rewritten by every row and has no buffer.

  `ragged_conv`   plain jnp: c_t = b + sum_j w[j] * u_{t - (K_c - 1) + j},
                  rows before the segment from the tail; -> (c before the
                  activation, float32; the segment's new tail). It is not in
                  the kernel because the equations put two matrix products
                  over ALL channels (x_proj, dt_proj) between the convolution
                  and the recurrence
  `ssm_scan`      s_t = exp(dt_t A) * s_{t-1} + (dt_t * x_t) B_t^T,
                  y_t = s_t C_t, dt_t = softplus(dt_raw_t): the Pallas kernel
                  (`impl="pallas"`) or a `lax.scan` a sequence (the CPU
                  tests' oracle and the reference path)

The kernel's grid walks the sequences in order. A step loads the sequence's
slot (a BlockSpec indexed by scalar prefetch: Pallas fetches the next slot
while this one is computed, and writes a slot back where it came from: the
state is aliased in and out), DMAs the segment's rows in chunks of ROW_CHUNK
out of HBM, runs the recurrence a row at a time on all channels at once
(float32 state, `exp` and `softplus` inside) and DMAs the chunk's y back. A
chunk is written whole, so its last rows may overhang the segment: they land
on rows of LATER sequences, which the grid writes afterwards, or on padding.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.ops import kernel_tag
from ray_tpu.ops.state_slots import enter, interpreted

LANE = 128
# Rows a DMA: a decode row pays a chunk's DMA for one row, a slice one
# latency a chunk.
ROW_CHUNK = 8


def state_shape(layers: int, slots: int, d_state: int, d_inner: int):
    """The scan state of `slots` sequences and the junk slot behind them."""
    return (layers, slots + 1, d_state, d_inner // LANE, LANE)


def ragged_conv(u, tail, w, b, seq, local, starts, lens):
    """u (R, d_i) rows; tail (S, K_c - 1, d_i) the rows before each
    sequence's segment (zeros where it starts at position 0); w (K_c, d_i),
    b (d_i,); seq / local (R,): a row's sequence and its index in the
    segment. -> (b + conv (R, d_i) float32, new tail (S, K_c - 1, d_i))."""
    taps = w.shape[0]
    u32, w32 = u.astype(jnp.float32), w.astype(jnp.float32)
    acc = b.astype(jnp.float32) + w32[taps - 1] * u32
    for j in range(taps - 1):
        shift = taps - 1 - j
        before = tail[seq, jnp.clip(local + j, 0, taps - 2)]
        tap = jnp.where((local >= shift)[:, None],
                        jnp.roll(u32, shift, axis=0),
                        before.astype(jnp.float32))
        acc = acc + w32[j] * tap
    # The last K_c - 1 rows of [tail; segment].
    at = lens[:, None] - (taps - 1) + jnp.arange(taps - 1)[None, :]
    rows = u[jnp.clip(starts[:, None] + at, 0, u.shape[0] - 1)]
    old = jnp.take_along_axis(
        tail, jnp.clip(at + taps - 1, 0, taps - 2)[..., None], axis=1)
    return acc, jnp.where((at >= 0)[..., None], rows, old.astype(u.dtype))


def softplus(x):
    """log(1 + exp(x)), the linear branch above 20 (as torch's)."""
    return jnp.where(x > 20.0, x,
                     jnp.log1p(jnp.exp(jnp.minimum(x, 20.0))))


def ssm_scan_reference(dt, x, B, C, A, state, layer, slots, starts, lens,
                       zero):
    """The recurrence as a `lax.scan` over time, the sequences side by side:
    dt (R, d_i) before the softplus, x (R, d_i), B / C (R, N), A (N, d_i)
    negative, all float32; state `state_shape`'s; slots / starts / lens /
    zero (S,). -> (y (R, d_i) float32, rows outside every segment zero;
    state with the sequences' slots written)."""
    R, d_i = dt.shape
    N = A.shape[0]
    s0 = jnp.where(zero[:, None, None], 0.0,
                   state[layer, slots].reshape(-1, N, d_i))
    rows = jnp.clip(starts[:, None] + jnp.arange(R)[None, :], 0, R - 1)
    live = jnp.arange(R)[None, :] < lens[:, None]                 # (S, R)

    def step(s, xs):
        dt_t, x_t, B_t, C_t, live_t = xs
        dt_t = softplus(dt_t)
        new = (jnp.exp(dt_t[:, None, :] * A) * s
               + (dt_t * x_t)[:, None, :] * B_t[:, :, None])
        s = jnp.where(live_t[:, None, None], new, s)
        return s, jnp.einsum("snc,sn->sc", s, C_t)

    move = lambda a: jnp.moveaxis(a[rows], 1, 0)
    s1, y = jax.lax.scan(step, s0, (move(dt), move(x), move(B), move(C),
                                    live.T))
    y = jnp.moveaxis(y, 0, 1)                                     # (S, R, d_i)
    flat = jnp.zeros((R, d_i), jnp.float32).at[
        jnp.where(live, rows, R)].set(y, mode="drop")
    return flat, state.at[layer, slots].set(
        s1.reshape((-1,) + state.shape[2:]), mode="drop")


def _scan_kernel(meta_ref, slots_ref, starts_ref, lens_ref, zero_ref,
                 b_ref, c_ref, a_ref, s_in_ref, dt_hbm, x_hbm,
                 y_hbm, s_ref, dt_scr, x_scr, y_scr, sems, *, N: int,
                 TC: int):
    """Grid (S,): sequence s, its slot's state in s_in_ref / s_ref (N, C,
    128). b_ref / c_ref: B and C of every row, flat (rows * N,) in SMEM;
    a_ref (N, C, 128); dt_hbm / x_hbm / y_hbm (rows + TC, C, 128) in HBM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    n = lens_ref[s]
    row0 = starts_ref[s]
    s_ref[...] = jnp.where(zero_ref[s] != 0, 0.0, s_in_ref[...])

    def chunk(i, carry):
        base = row0 + i * TC
        loads = [pltpu.make_async_copy(hbm.at[pl.ds(base, TC)], scr, sem)
                 for hbm, scr, sem in ((dt_hbm, dt_scr, sems.at[0]),
                                       (x_hbm, x_scr, sems.at[1]))]
        for copy in loads:
            copy.start()
        for copy in loads:
            copy.wait()

        def row(r, carry):
            dt = softplus(dt_scr[r])                            # (C, 128)
            dtx = dt * x_scr[r]
            at = (base + r) * N
            y = jnp.zeros_like(dt)
            for k in range(N):
                sk = jnp.exp(dt * a_ref[k]) * s_ref[k] + dtx * b_ref[at + k]
                s_ref[k] = sk
                y = y + sk * c_ref[at + k]
            y_scr[r] = y
            return carry

        jax.lax.fori_loop(0, jnp.minimum(TC, n - i * TC), row, 0)
        store = pltpu.make_async_copy(y_scr, y_hbm.at[pl.ds(base, TC)],
                                      sems.at[2])
        store.start()
        store.wait()
        return carry

    jax.lax.fori_loop(0, pl.cdiv(n, TC), chunk, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_scan_call(dt, x, B, C, A, state, layer, slots, starts, lens, zero, *,
                  interpret: bool):
    """The kernel's launch: dt / x (rows + ROW_CHUNK, C, 128), B / C (rows *
    N,), A (N, C, 128). Jitted under a name of its own so that a profile's
    events read `ssm_scan_call.<n>` (as `paged_attention_kv_call` does)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, CB, _ = dt.shape
    N = A.shape[0]
    S = slots.shape[0]
    TC = ROW_CHUNK
    slot_block = pl.BlockSpec(
        (None, None, N, CB, LANE),
        lambda s, meta, slots, *_: (meta[0], slots[s], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(S,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),               # B
            pl.BlockSpec(memory_space=pltpu.SMEM),               # C
            pl.BlockSpec((N, CB, LANE), lambda s, *_: (0, 0, 0)),  # A
            slot_block,
            pl.BlockSpec(memory_space=pl.ANY),                   # dt rows
            pl.BlockSpec(memory_space=pl.ANY),                   # x rows
        ],
        out_specs=[pl.BlockSpec(memory_space=pl.ANY), slot_block],
        scratch_shapes=[pltpu.VMEM((TC, CB, LANE), jnp.float32)] * 3
        + [pltpu.SemaphoreType.DMA((3,))],
    )
    return pl.pallas_call(
        functools.partial(_scan_kernel, N=N, TC=TC),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, CB, LANE), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={8: 1},        # the state, in place
        interpret=interpret,
        **kernel_tag("ssm_scan"),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, starts, lens, zero,
      B, C, A, state, dt, x)


def ssm_scan(dt, x, B, C, A, state, layer, slots, starts, lens, zero, *,
             impl: str = "pallas", interpret: Optional[bool] = None):
    """`ssm_scan_reference`'s contract, by the Pallas kernel where `impl` is
    "pallas"."""
    slots, starts, lens, zero = enter(state, slots, starts, lens, zero)
    if impl != "pallas":
        return ssm_scan_reference(dt, x, B, C, A, state, layer, slots,
                                  starts, lens, zero)
    R, d_i = dt.shape
    N = A.shape[0]
    tiles = lambda a: jnp.pad(a.astype(jnp.float32).reshape(R, -1, LANE),
                              ((0, ROW_CHUNK), (0, 0), (0, 0)))
    i32 = lambda a: a.astype(jnp.int32)
    y, state = ssm_scan_call(
        tiles(dt), tiles(x), B.astype(jnp.float32).reshape(-1),
        C.astype(jnp.float32).reshape(-1),
        A.astype(jnp.float32).reshape(N, -1, LANE), state, layer,
        i32(slots), i32(starts), i32(lens),
        i32(zero), interpret=interpreted(interpret))
    r = jnp.arange(R)[:, None]
    live = jnp.any((r >= starts[None, :]) & (r < (starts + lens)[None, :]),
                   axis=1)
    return (jnp.where(live[:, None], y[:R].reshape(R, d_i), 0.0), state)
