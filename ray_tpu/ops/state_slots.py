"""State beside pages: the contract of the kernels whose layer keeps a STATE
of fixed size a sequence (ops/ssm_scan.py, ops/power_retention.py,
ops/ssd.py, ops/kda.py; llm/model_runner.py, "Layer groups": a state group),
and the parts of it that are the same code in all of them.

A state group has one member with NO kernel at all: models/lfm2_moe.py, whose
slot holds a convolution's TAIL and nothing else (the last two rows of a gated
short convolution's input, 57 KB a slot at seven layers where a matrix state
is megabytes): no S, no buffer, no fill, the convolution itself
`ops/ssm_scan.ragged_conv` in plain `jax.numpy`. Of what follows, `slots` /
`starts` / `lens` / `zero` and the junk slot hold for it as written (the block
states them in its own lines: a sequence that starts at position 0 starts
from zeros, a sequence without a row writes the junk slot), and `copy_state`
snapshots and restores its slot as any other's (llm/model_runner.py).

A step's R rows are token-major segments of S sequences, one call a layer:

  slots   (S,) the slot of the state's arrays each sequence continues from
          and is written back to. Every array of a state group is (layers,
          slots + 1, ...): the LAST slot is nobody's, the junk slot. A
          sequence without a row (`lens == 0`: a padding sequence of a
          bucketed step) is sent there by the wrapper (`enter`), so that it
          leaves its own slot and fill alone; whatever the kernel reads or
          writes for it lands on the junk slot
  starts  (S,) a sequence's first row; ASCENDING, as a mixed tick and a
          rectangle lay them (a chunk written whole may overhang onto LATER
          sequences' rows, which the grid writes afterwards). Where the
          kernel reads planes with the heads in front
          (ops/power_retention.py) a sequence's rows lie from a multiple of
          8 on: a DMA starts on a whole tile
  lens    (S,) its rows: 1 for a decode row, up to a prefill chunk for a
          slice, 0 for none
  zero    (S,) the segment starts at position 0: the sequence starts from
          zeros AND an empty buffer whatever the slot held, so no program
          ever clears a slot

`impl != "pallas"` is the oracle: the recurrence as a `lax.scan` over time,
the sequences side by side (`*_reference`), under the same contract and
handing back the same arrays; the tests hold the kernels to it, and it is the
path off the chip. `interpret` left None is "not on a TPU" (`interpreted`).

Three of the four do not rewrite S for a decode row (ops/ssm_scan.py does: it
takes `enter` and `interpreted` and nothing else here). Beside `state`, S as
the last FOLD left it, a slot holds a BUFFER, the rows since in a tile of the
kernel's own layout, and a FILL, (layers, slots + 1) int32, the rows the
buffer holds, 0 .. fold - 1 (`fold`: the rows a buffer holds before it is
folded, the kernel's `FOLD` or what the buffer's shape says). (state, buffer,
fill) together are the recurrence's S_t (each kernel's `folded`). The fill's
rule, the kernels' and the oracles' alike, `fill_after` as host arithmetic
and `joins` as its array form:

  one row    joins the buffer at the fill; where the buffer is then full, or
             the row was the sequence's first (the zeros must reach the
             slot), the buffer is folded into S, S is written and the fill is
             0 again
  more rows  what the buffer holds is folded first, the slice takes the
             chunked form, S is written and the buffer is left empty
  no row     nothing moves

A buffered kernel answers a sequence of one row and the others in arrays of
their own (`y_row`, `y_rows`: a slice's chunk is written whole and may
overhang a later decode row's place); `answers` picks.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def fill_shape(layers: int, slots: int):
    """Rows each slot's buffer holds (int32): the two leading axes of every
    array of a state group, `slots` sequences' and the junk slot behind."""
    return (layers, slots + 1)


def fill_after(fill: int, rows: int, fresh: bool, fold: int):
    """The fill's rule as host arithmetic: a slot's buffer of `fold` rows
    holds `fill` and a call carries `rows` (> 0) of its sequence, `fresh`
    where they start at position 0 -> (the fill the call leaves, whether it
    folded the buffer into the state)."""
    fill = 0 if fresh else fill
    if rows > 1:
        return 0, fill > 0
    full = fresh or fill + 1 >= fold
    return (0 if full else fill + 1), full


def joins(lens, zero, fill, fold: int):
    """`fill_after` over a call's sequences: where the one row a sequence
    brings joins its buffer and the state stays as it is held (elsewhere the
    call leaves the buffer empty)."""
    return (lens == 1) & ~zero & (fill + 1 < fold)


def enter(state, slots, starts, lens, zero):
    """A wrapper's first lines -> (slots, starts, lens, zero): arrays, a
    sequence without a row on the junk slot of `state`, `zero` bool."""
    slots, starts, lens = (jnp.asarray(a) for a in (slots, starts, lens))
    slots = jnp.where(lens > 0, slots, state.shape[1] - 1)
    return slots, starts, lens, jnp.asarray(zero).astype(bool)


def interpreted(interpret: Optional[bool]) -> bool:
    """`interpret` as given, or, left None, whether this is not a TPU."""
    if interpret is None:
        from ray_tpu.ops import is_tpu_backend

        interpret = not is_tpu_backend()
    return interpret


def first_fill(fill, layer, slots, zero):
    """(S,) the rows each sequence's buffer holds as the call finds it."""
    return jnp.where(zero, 0, fill[layer, slots])


def filled(fill, layer, slots, stay, f0):
    """The fill written back: `f0` (`first_fill`) + 1 where the row joined
    (`stay`: `joins`), else 0. The junk slot takes the padding's."""
    return fill.at[layer, slots].set(
        jnp.where(stay, f0 + 1, 0).astype(jnp.int32), mode="drop")


def answers(y_row, y_rows, starts, lens, shape):
    """The step's rows (`shape`, rows leading) out of a buffered kernel's two
    outputs: a sequence of one row's from `y_row`, a slice's from `y_rows`,
    zeros outside every segment."""
    r = jnp.arange(shape[0])[:, None]
    mine = (r >= starts[None, :]) & (r < (starts + lens)[None, :])  # (R, S)
    one = jnp.any(mine & (lens == 1)[None, :], axis=1)[:, None, None]
    live = jnp.any(mine, axis=1)[:, None, None]
    cut = tuple(slice(n) for n in shape)
    return jnp.where(live, jnp.where(one, y_row[cut], y_rows[cut]), 0.0)


def state_block(s, j, meta, slots, starts, lens, *_):
    """The index map of a step's block of S (a BlockSpec by scalar prefetch:
    grid (sequences, head blocks)). A sequence without a row reads ONE block
    of the junk slot, whatever j: consecutive steps on one block fetch
    nothing. A kernel file imports it by name and looks it up when it is
    traced, so that a timing can hold ONE kernel's block still
    (chip_smoke.py's `decode_sweep`)."""
    return (meta[0], slots[s], jnp.where(lens[s] > 0, j, 0), 0, 0)


def tile_block(s, j, meta, slots, starts, lens, *_):
    """A step's tile of the buffer, by `state_block`'s rule (a function of
    its own so that a timing can hold the state's block still alone)."""
    return (meta[0], slots[s], jnp.where(lens[s] > 0, j, 0), 0, 0)
