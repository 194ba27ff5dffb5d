"""Power retention (power attention of degree 2 with a gate; arXiv:2507.04239)
over ragged token-major rows: the recurrent step for a decode row and the
chunked form for a prompt slice, in one call a layer.

For query head h of kv head j = h // G and every s <= t of a sequence,

  a_ts = exp(sum_{r=s+1..t} log g_rj) * (scale * q_th . k_sj) ** 2,
  o_th = sum_s a_ts v_sj / (sum_s a_ts + eps).

The square is an inner product of degree-2 features, so the sum over s is a
STATE of fixed size a sequence and kv head (llm/model_runner.py, "Layer
groups": a state group), read and rewritten by every step:

  S_t = g_t S_(t-1) + phi(k_t) v_t^T,  z_t = g_t z_(t-1) + phi(k_t),
  o_th = S_t^T phi(q_th) / (z_t . phi(q_th) + eps).

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

A sequence whose segment starts at position 0 starts from zeros (`zero`), so
no program ever clears a slot.

  `power_retention_reference`   the recurrence as a `lax.scan` over time, the
                                sequences side by side, phi built whole: the
                                oracle of the tests and the path off the chip
  `power_retention`             the Pallas kernel where `impl == "pallas"`

The kernel's grid is (sequences, kv heads) in order; a step's state block is
fetched by BlockSpec (scalar prefetch names the slot; the next step's block
comes in while this one computes) and written back where it came from (the
state is aliased in and out: nothing copies the array). A sequence without
rows names the junk slot's head 0 for every step, and consecutive equal block
indices move nothing. Rows come in by DMA from planes (K, rows, ...) in HBM.

  one row (a decode row): the recurrence itself on the VPU, float32: each
      (8 values, hd lanes) tile of the state is read, decayed, given its
      rank-one update, written, and multiplied into the G query heads'
      accumulators while it is in registers. Bound by the state's bytes.
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

# Rows a step of the chunked form takes, and rows a decode row's DMA moves.
CHUNK = 128
DEC_ROWS = 8
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def chunks(head_dim: int) -> int:
    """Chunks of `head_dim` lanes phi has."""
    return head_dim // 2 + 1


def state_shape(layers: int, slots: int, kv_heads: int, head_dim: int):
    """S of `slots` sequences and the junk slot behind them."""
    return (layers, slots + 1, kv_heads, chunks(head_dim), head_dim, head_dim)


def norm_shape(layers: int, slots: int, kv_heads: int, head_dim: int):
    """z beside `state_shape`'s S."""
    return (layers, slots + 1, chunks(head_dim), kv_heads, head_dim)


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


def power_retention_reference(q, k, v, log_g, state, norm, layer, slots,
                              starts, lens, zero, *, scale: float,
                              eps: float):
    """The recurrence, a row at a time: q (R, H, hd), k / v (R, K, hd), log_g
    (R, K) float32 (log of the gate); state / norm `state_shape`'s /
    `norm_shape`'s; slots / starts / lens / zero (S,). -> (o (R, H, hd)
    float32, rows outside every segment zero; state; norm, the sequences'
    slots written)."""
    R, H, hd = q.shape
    K = k.shape[1]
    G = H // K
    root = math.sqrt(scale)
    q, k, v = (a.astype(F32) for a in (q * root, k * root, v))
    keep = lambda z, a: jnp.where(
        z.reshape((-1,) + (1,) * (a.ndim - 1)), 0.0, a)
    s0 = keep(zero, state[layer, slots])            # (S, K, C, hd, hd)
    z0 = keep(zero, norm[layer, slots]).swapaxes(1, 2)  # (S, K, C, hd)
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
        step, (s0, z0), (move(q), move(k), move(v), move(log_g.astype(F32)),
                         live.T))
    o = jnp.moveaxis(o, 0, 1)                                     # (S,R,H,hd)
    flat = jnp.zeros((R, H, hd), F32).at[jnp.where(live, rows, R)].set(
        o, mode="drop")
    return (flat, state.at[layer, slots].set(s1, mode="drop"),
            norm.at[layer, slots].set(z1.swapaxes(1, 2), mode="drop"))


def _retention_kernel(meta_ref, slots_ref, starts_ref, lens_ref, zero_ref,
                      s_in_ref, z_in_ref, q_hbm, kv_hbm, o_hbm, s_ref, z_ref,
                      q_scr, kv_scr, o_scr, x_scr, pb_scr, acc_scr, num_scr,
                      den_scr, sems, *, G: int, hd: int, TC: int, eps: float):
    """Grid (S, K): sequence s, kv head j; its state in s_in_ref / s_ref (C,
    hd, hd) and, all heads of the slot, z_in_ref / z_ref (C, K, hd). q_hbm /
    o_hbm (K, rows, G hd), kv_hbm (K, rows, 4 hd) = [k | v | the gates'
    running log, this row counted | the same, not counted] in HBM; q and k
    come scaled."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s = pl.program_id(0)
    j = pl.program_id(1)
    n = lens_ref[s]
    row0 = pl.multiple_of(starts_ref[s], 8)
    fresh = zero_ref[s] != 0
    C = hd // 2 + 1
    root2 = math.sqrt(2.0)

    def weight(r):
        return jnp.where((r == 0) | (r == C - 1), 1.0, root2).astype(F32)

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

    @pl.when(n == 1)
    def _one_row():
        move(row0, DEC_ROWS)
        g = jnp.exp(kv_scr[0:1, 2 * hd:3 * hd] - kv_scr[0:1, 3 * hd:4 * hd])
        g8 = jnp.broadcast_to(g, (8, hd))
        # The G query heads' rows and k's, one tile: phi of all in one pass.
        x_scr[...] = jnp.zeros_like(x_scr)
        for h in range(G):
            x_scr[h:h + 1, :] = q_scr[0:1, h * hd:(h + 1) * hd]
        x_scr[G:G + 1, :] = kv_scr[0:1, 0:hd]
        x = x_scr[...]

        def features(r, zacc):
            p = x * pltpu.roll(x, r, 1) * weight(r)
            z_old = jnp.where(fresh, 0.0, z_in_ref[r, pl.ds(j, 1), :])
            z_new = g * z_old + p[G:G + 1, :]
            z_ref[r, pl.ds(j, 1), :] = z_new
            for i in range(G + 1):      # a row a tile: what the walk reads
                pb_scr[i, r] = jnp.broadcast_to(p[i:i + 1, :], (8, hd))
            return zacc + p * z_new

        zacc = jax.lax.fori_loop(0, C, features,
                                 jnp.zeros(x_scr.shape, F32))
        den = jnp.sum(zacc, axis=1, keepdims=True)              # (XR, 1)
        # v down the sublanes: [c, l] = v[c].
        v_t = jnp.broadcast_to(kv_scr[0:1, hd:2 * hd], (hd, hd)).T

        acc_scr[G] = v_t                     # (a ref: sliced where it lies)

        def tiles(t, carry):
            c0 = pl.multiple_of(t * 8, 8)
            vb = acc_scr[G, pl.ds(c0, 8), :]

            def walk(r, accs):
                tile = jnp.where(fresh, 0.0, s_in_ref[r, pl.ds(c0, 8), :])
                tile = g8 * tile + vb * pb_scr[G, r]
                s_ref[r, pl.ds(c0, 8), :] = tile
                return tuple(a + pb_scr[h, r] * tile
                             for h, a in enumerate(accs))

            accs = jax.lax.fori_loop(
                0, C, walk, tuple(jnp.zeros((8, hd), F32) for _ in range(G)))
            for h in range(G):
                acc_scr[h, pl.ds(c0, 8), :] = accs[h]
            return carry

        jax.lax.fori_loop(0, hd // 8, tiles, 0)
        # A head's sums over the lanes stand down the sublanes; side by side
        # (head h in lane h) and transposed they are rows.
        lane = jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
        cols = jnp.zeros((hd, hd), F32)
        for h in range(G):
            cols = jnp.where(lane == h, jnp.sum(acc_scr[h], axis=1,
                                                keepdims=True), cols)
        out = cols.T[0:x_scr.shape[0], :] / (den + eps)
        for h in range(G):
            o_scr[0:1, h * hd:(h + 1) * hd] = out[h:h + 1, :]
        put(row0, DEC_ROWS)

    @pl.when(n > 1)
    def _slice():
        def enter(r, carry):
            s_ref[r] = jnp.where(fresh, 0.0, s_in_ref[r])
            z_ref[r, pl.ds(j, 1), :] = jnp.where(
                fresh, 0.0, z_in_ref[r, pl.ds(j, 1), :])
            return carry

        jax.lax.fori_loop(0, C, enter, 0)
        dot = functools.partial(jax.lax.dot_general, precision=HIGHEST,
                                preferred_element_type=F32)
        nn = (((1,), (0,)), ((), ()))
        nt = (((1,), (1,)), ((), ()))

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
                tile = s_ref[r]
                z_old = z_ref[r, pl.ds(j, 1), :]
                for h in range(G):
                    qh = q_scr[:, h * hd:(h + 1) * hd]
                    pq = qh * pltpu.roll(qh, r, 1) * w
                    num_scr[h] = num_scr[h] + into * dot(pq, tile, nt)
                    den_scr[h] = den_scr[h] + into * jnp.sum(
                        pq * z_old, axis=1, keepdims=True)
                s_ref[r] = e_last * tile + dot(v_keep, pk, nn)
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


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def power_retention_call(q, kv, state, norm, layer, slots, starts, lens,
                         zero, *, eps: float, interpret: bool):
    """The kernel's launch: q (K, rows, G hd), kv (K, rows, 4 hd), a
    sequence's rows from `starts[s]`, a multiple of 8, on, and CHUNK rows to
    spare behind the last. Jitted under a name of its own so that a profile's
    events read `power_retention_call.<n>` (as `ssm_scan_call` does)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, rows, width = q.shape
    hd = kv.shape[2] // 4
    G = width // hd
    C = chunks(hd)
    S = slots.shape[0]
    TC = CHUNK
    XR = -(-(G + 1) // 8) * 8

    def slot(s, j, meta, slots, starts, lens, zero):
        # A sequence without rows: the junk slot's head 0 at every step, so
        # that no block moves between them.
        return meta[0], slots[s], jnp.where(lens[s] > 0, j, 0)

    s_block = pl.BlockSpec((None, None, None, C, hd, hd),
                           lambda *a: slot(*a) + (0, 0, 0))
    z_block = pl.BlockSpec((None, None, C, K, hd),
                           lambda *a: slot(*a)[:2] + (0, 0, 0))
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(S, K),
        in_specs=[s_block, z_block, anywhere, anywhere],
        out_specs=[anywhere, s_block, z_block],
        scratch_shapes=[
            pltpu.VMEM((TC, G * hd), F32),              # q rows
            pltpu.VMEM((TC, 4 * hd), F32),              # k, v, gates
            pltpu.VMEM((TC, G * hd), F32),              # o rows
            pltpu.VMEM((XR, hd), F32),                  # a decode row's q, k
            pltpu.VMEM((G + 1, C, 8, hd), F32),         # its phi, a row a tile
            pltpu.VMEM((G + 1, hd, hd), F32),           # its sums; v's tile
            pltpu.VMEM((G, TC, hd), F32),               # a chunk's numerators
            pltpu.VMEM((G, TC, hd), F32),               # and denominators
            pltpu.SemaphoreType.DMA((3,)),
        ],
    )
    block = 4 * C * hd * hd
    return pl.pallas_call(
        functools.partial(_retention_kernel, G=G, hd=hd, TC=TC, eps=eps),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct(norm.shape, norm.dtype)],
        input_output_aliases={5: 1, 6: 2},      # state and norm, in place
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            # The state block twice in and twice out, and the scratch.
            vmem_limit_bytes=4 * block + (24 << 20)),
        **kernel_tag("power_retention"),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, starts, lens, zero,
      state, norm, q, kv)


def power_retention(q, k, v, log_g, state, norm, layer, slots, starts, lens,
                    zero, *, scale: float, eps: float, impl: str = "pallas",
                    interpret: Optional[bool] = None):
    """`power_retention_reference`'s contract, by the Pallas kernel where
    `impl` is "pallas". Sequences must lie in the order of their rows
    (`starts` ascending, as a mixed tick and a rectangle lay them)."""
    # A sequence without a row leaves its slot alone: it takes the junk one.
    slots = jnp.where(lens > 0, slots, state.shape[1] - 1)
    if impl != "pallas":
        return power_retention_reference(
            q, k, v, log_g, state, norm, layer, slots, starts, lens, zero,
            scale=scale, eps=eps)
    if interpret is None:
        from ray_tpu.ops import is_tpu_backend

        interpret = not is_tpu_backend()
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
    P = -(-R // 8) * 8 + 8 * S + CHUNK
    at = jnp.where(live, jnp.sum(jnp.where(
        mine, first[None, :] + r - starts[None, :], 0), axis=1), P)
    through = jnp.cumsum(log_g.astype(F32), axis=0)               # (R, K)
    lanes = lambda a: jnp.broadcast_to(a[..., None], (R, K, hd))
    plane = lambda a: jnp.moveaxis(
        jnp.zeros((P,) + a.shape[1:], F32).at[at].set(a, mode="drop"), 1, 0)
    i32 = lambda a: a.astype(jnp.int32)
    o, state, norm = power_retention_call(
        plane((q.astype(F32) * root).reshape(R, K, -1)),
        plane(jnp.concatenate(
            [k.astype(F32) * root, v.astype(F32), lanes(through),
             lanes(through - log_g.astype(F32))], axis=-1)),
        state, norm, layer, i32(slots), i32(first), i32(lens), i32(zero),
        eps=eps, interpret=interpret)
    o = jnp.moveaxis(o, 0, 1)[jnp.minimum(at, P - 1)].reshape(R, H, hd)
    return jnp.where(live[:, None, None], o, 0.0), state, norm
