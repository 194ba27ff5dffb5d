"""What blocks that hold a SHARE of a layer's experts have in common
(models/deepseek_v2.py, models/mimo_v2_flash.py, models/kimi_linear.py,
models/glm_dsa.py, models/nemotron_h.py, models/afmoe.py,
models/lfm2_moe.py): the held experts' part of a routed
feed-forward (whatever an expert's form is, on the hidden state or in a latent
the layer enters and leaves), the sigmoid router and its drawn bias that the
`noaux_tc` families share, and the products that keep a float32 operand whole.

A deployment splits a layer's experts over chips. A program holds the experts
`config.experts_held = (first, stop)` (published ids) and the router at its
published width: it routes every token over all experts, computes what ITS
experts contribute, and leaves out what absent experts would add (their
chips' partial results, summed by an exchange this repo does not have yet:
ROADMAP). No token is dropped and no capacity is set. One share may be the
WHOLE (`experts_held` = (0, the router's width): models/lfm2_moe.py's cell):
every pair then hits a held expert and is computed, still sorted by expert
and one ragged product a projection, never every row through every expert.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import grouped_dot
from ray_tpu.ops.layers import swiglu


def runs_of(kinds):
    """[(kind, its layers)]: runs of like layers in the published order."""
    runs = []
    for li, name in enumerate(kinds):
        if runs and runs[-1][0] == name:
            runs[-1][1].append(li)
        else:
            runs.append((name, [li]))
    return runs


def kind_segments(runs, params):
    """A block's `segments` where `params["layers"]` is one dict a KIND of
    layer, its layers stacked in the published order, and `params["experts"]`
    one dict an expert layer (a kind that ends in "_moe"): every run of
    `runs` (`runs_of`) a Python loop (`apart` is given for every segment: an
    expert layer's three expert arrays are parameters of their own,
    deepseek_v2.Block.segments says why, and a layer's place in its group's
    pools is then static)."""
    out, taken, moe = [], {}, 0
    for name, ls in runs:
        lo = taken.get(name, 0)
        taken[name] = lo + len(ls)
        stacked = jax.tree.map(lambda a, lo=lo, n=len(ls): a[lo:lo + n],
                               params["layers"][name])
        if name.endswith("_moe"):
            apart = params["experts"][moe:moe + len(ls)]
            moe += len(ls)
        else:
            apart = [{}] * len(ls)
        out.append((name, stacked, ls[0], apart))
    return out


def swiglu_expert(dot, xs, lp):
    """An expert as a gated SwiGLU of three arrays, `lp["w_gate"]`,
    `lp["w_up"]`, `lp["w_down"]` (held, in, out)."""
    return _ffn(dot, xs, lp["w_gate"], lp["w_up"], lp["w_down"])


def relu2_expert(dot, xs, lp):
    """An expert with NO gate projection: `w2 relu(w1 x)^2`, `lp["w1"]`,
    `lp["w2"]` (held, in, out)."""
    return dot(_relu2(dot(xs, lp["w1"])).astype(xs.dtype), lp["w2"])


def _relu2(a):
    return jnp.square(jax.nn.relu(a))


def held_expert_ffn(config, x, ids, gates, valid, lp, *,
                    expert=swiglu_expert, enter=None, leave=None):
    """What the HELD experts (`config.experts_held`, `config.n_held`)
    contribute to rows `x` (N, d) routed to `ids` with `gates`: the pairs
    that hit a held expert sorted by expert, one ragged product a
    projection; pairs of absent experts (and of padding rows, `valid` False)
    ride behind the last group with gate 0. The expert's form is the
    caller's: `expert(dot, xs, lp)` over the sorted pairs with `dot(a, w)`
    the ragged product (`ops/grouped_dot.product`: the Pallas weight stream
    on a TPU, XLA's `ragged_dot` off it). Experts that work in a LATENT
    (`enter` (d, latent), `leave` (latent, d): models/nemotron_h.py) are
    entered ONCE A ROW, before the pairs are gathered, and left once a row,
    after their gated sum: both projections are linear, so that is the
    published sum. Returns (y (N, d) float32, counts (3,) int32: the rows
    computed, the busiest held expert's rows, the held experts that had a
    row: a tick record's `expert_rows`, `expert_rows_max`, `experts_met`)."""
    if enter is not None:
        x = _dot32(x, enter).astype(x.dtype)
    n, k = ids.shape
    first, n_held = config.experts_held[0], config.n_held
    local = ids.reshape(-1) - first
    held = (local >= 0) & (local < n_held) & jnp.repeat(valid, k)
    local = jnp.where(held, local, n_held)
    order = jnp.argsort(local, stable=True)
    sizes = jnp.bincount(local, length=n_held + 1)[:n_held].astype(jnp.int32)
    xs = x[order // k]                                          # (N k, d)
    y = expert(grouped_dot.product(sizes), xs, lp)
    gate = jnp.where(held, gates.reshape(-1), 0.0)[order]
    y = jnp.where(gate[:, None] != 0.0, y * gate[:, None], 0.0)
    y = y[jnp.argsort(order)].reshape(n, k, -1).sum(axis=1)
    if leave is not None:
        y = _dot32(y.astype(x.dtype), leave)
    return y, jnp.stack([sizes.sum(), sizes.max(),
                         jnp.count_nonzero(sizes).astype(jnp.int32)])


# ---- the sigmoid router both `noaux_tc` families share (models/
# mimo_v2_flash.py, models/kimi_linear.py) ------------------------------------

# Wide enough that a router without the bias fails the benchmark's routed
# check at the published widths (kept scores crowd near 0.9 and spread over
# ~0.1: at 0.2 the fault falls short by 15-16%, at 0.02 by 2% against a margin
# of 10%; PERF.md section 6, PR 33), small enough that score + bias stays
# positive.
ROUTER_BIAS_WIDTH = 0.2


def router_bias(key: jax.Array, layers: int, experts: int,
                held: int) -> jax.Array:
    """(layers, experts) float32 in [0, ROUTER_BIAS_WIDTH): positive, so that
    score + bias is. The values are a grid of `held` levels over the width,
    and the seed deals them to every share of `held` consecutive experts of
    every layer in an order of its own. The published bias is what balances
    the experts' load; a drawn one cannot, but dealt this way it favours
    every chip's share alike and as unevenly inside a share at every seed, so
    that neither the held experts' load nor how it lies over them moves with
    the seed (drawn an expert at a time it moved a layer's load by a third).
    Where the shares are not whole, or one chip holds every expert, the grid
    is over all of them."""
    shares = experts // held if experts % held == 0 else 1
    per = experts // shares
    levels = (jnp.arange(per, dtype=jnp.float32) + 0.5) * (
        ROUTER_BIAS_WIDTH / per)
    dealt = jax.vmap(jax.random.permutation)(
        jax.random.split(key, layers * shares),
        jnp.broadcast_to(levels, (layers * shares, per)))
    return dealt.reshape(layers, experts)


def route_one_group(config, scores: jax.Array, bias: jax.Array, *,
                    scale=None, eps=None):
    """`noaux_tc` with one group over `scores` (N, published experts), a
    sigmoid's: the `config.num_experts_per_tok` best by score + bias (ties to
    the lower id, `lax.top_k`), gates the kept SCORES (the bias moves the
    selection and not the gates) over their sum (+ `eps`, where a family's
    code adds one: models/afmoe.py), times `scale` where given. -> (ids (N,
    top_k) int32, published; gates (N, top_k))."""
    _, ids = jax.lax.top_k(scores + bias, config.num_experts_per_tok)
    kept = jnp.take_along_axis(scores, ids, axis=-1)
    total = kept.sum(axis=-1, keepdims=True)
    gates = kept / (total if eps is None else total + eps)
    return ids.astype(jnp.int32), gates if scale is None else gates * scale


# ---- products that keep a float32 operand whole (why: deepseek_v2.py,
# "precision") ---------------------------------------------------------------

def _wide(dot, h, w):
    """dot(h, w) -> float32 with float32 h kept whole: as its bf16 rounding
    plus the bf16 rounding of what that lost, two passes over bf16 weights
    (which have no low part of their own)."""
    if w.dtype != jnp.bfloat16:
        return dot(h, w)
    hi = h.astype(jnp.bfloat16)
    lo = (h - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return dot(hi, w) + dot(lo, w)


def _ffn(dot, h, gate, up, down):
    """SwiGLU with `dot(a, w) -> float32`: -> float32."""
    hidden = swiglu(dot(h, gate), dot(h, up)).astype(h.dtype)
    return dot(hidden, down)


def _dot32(a, w):
    return jnp.matmul(a, w, preferred_element_type=jnp.float32)
