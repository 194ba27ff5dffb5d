"""NVIDIA-Nemotron-3-Super-120B-A12B for the serving engine: Mamba-2 layers
(state-space duality: a MATRIX state a sequence and head with one decay a head
and token), one GQA attention layer in eleven that rotates NOTHING, and
LatentMoE layers that hold a SHARE of 512 sigmoid-routed non-gated relu^2
experts, which work in a latent a quarter of the hidden state wide, beside one
shared expert on the full hidden state. Every layer is ONE mixer behind one
RMSNorm and one residual add, by `hybrid_override_pattern` (`M`, `*`, `E`).

Source: https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
(`config.json`, `model_type: nemotron_h`; the Mamba-2 layer is arXiv:2405.21060
as Nemotron-H, arXiv:2504.03624, lays it; the equations stand in
models/nemotron_h_reference.py's docstring, with what the config does not
carry and is assumed). What this file states once and the serving runner
(llm/model_runner.py) consumes through `Block`:

  * Two LAYER GROUPS, BOTH WITH BYTES. `all`: the K and V ROW POOLS of the
    `*` layers (2 kv heads x 128 = 256 lanes a token a layer: whole lane
    tiles, ops/paged_attention.py's row form), written and read by one layer
    in eleven. `state`: a slot a sequence, every `M` layer's S (128 heads of
    64 x 128, float32: 4.19 MB), the rows buffered beside it (`ssd_rows`,
    0.36 MB: a decode row READS S and joins them, and they are folded into S
    once in `ops/ssd.FOLD` rows) with their count (`ssd_fill`), and the last
    three rows of its convolution's input (`xBC`, 10,240 channels, as whole
    tiles of the slot's own); a sequence whose rows start at position 0
    starts from zeros and an empty buffer. A prefix hit therefore needs a
    page chain AND a parked slot, and an eviction frees both (llm/engine.py).
  * Segments: runs of like layers in the published order ("mamba", "attn",
    "latent_moe"), each a Python loop, the experts' weights held apart
    (deepseek_v2.Block.segments says why).
  * The expert layer is models/expert_share.py's: `route_one_group` (sigmoid
    + correction bias, the 22 best of 512, renormalised) times
    `routed_scaling_factor`, `held_expert_ffn` with the relu^2 form, entered
    by `fc1_latent` and left by `fc2_latent` ONCE A ROW, plus the shared
    expert.

Precision: the residual stream, S, the decays' logs, the router's scores and
the softmax are float32; weights, K/V rows and the convolution's tail are the
configuration's dtype (NVIDIA's own serving note asks for a float32 SSM
cache).

Left out: the MTP layer (`num_nextn_predict_layers`: no cell speculates),
training (ops/ssd.py has no backward pass), tensor parallelism (a slot's state
is not sharded over the Mamba heads, and 2 kv heads split no further), LoRA.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.expert_share import (_dot32, _relu2, _wide,
                                         held_expert_ffn, kind_segments,
                                         relu2_expert, route_one_group,
                                         router_bias, runs_of)
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import ssd as sd
from ray_tpu.ops import ssm_scan as ss
from ray_tpu.ops.layers import rms_norm

LANE = 128
F32 = jnp.float32
KINDS = {"M": "mamba", "*": "attn", "E": "latent_moe"}
PUBLISHED_PATTERN = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
                     "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The published keys under their Hugging Face names, `vocab_size`,
    `num_hidden_layers`, `hybrid_override_pattern` and
    `max_position_embeddings` as run, and the share of the published experts
    this program holds."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = PUBLISHED_PATTERN
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128               # of the chunked form (ops/ssd.py)
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 512          # the router's width: as published
    experts_held: Tuple[int, int] = (0, 512)   # published ids [first, stop)
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    routed_scaling_factor: float = 5.0
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    dtype: Any = jnp.bfloat16

    def serving_block(self) -> "Block":
        return Block(self)

    def __post_init__(self):
        first, stop = self.experts_held
        if not 0 <= first < stop <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of 0..{self.n_routed_experts}")
        pattern = self.hybrid_override_pattern
        if (len(pattern) != self.num_hidden_layers
                or set(pattern) - set(KINDS)):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} does not name "
                f"{self.num_hidden_layers} layers of M, * and E")
        if self.num_experts_per_tok > self.n_routed_experts:
            raise ValueError("top_k over the router's width")
        if (self.mamba_num_heads % self.n_groups
                or self.num_attention_heads % self.num_key_value_heads):
            raise ValueError("heads that no group or kv head divides")

    # What the serving runner and engine read of any model's configuration,
    # and models/expert_share.py of a routed one.
    @property
    def max_seq(self) -> int:
        return self.max_position_embeddings

    @property
    def norm_eps(self) -> float:
        return self.layer_norm_epsilon

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """`xBC`: x and the groups' B and C, what the convolution passes."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def layer_kinds(self) -> List[str]:
        """A layer's kind, in the published order."""
        return [KINDS[c] for c in self.hybrid_override_pattern]

    def layers_of(self, kind: str) -> int:
        return self.layer_kinds().count(kind)

    @property
    def state_bytes_per_sequence(self) -> int:
        """A slot of the state group: every `M` layer's S (float32) and its
        convolution's tail (the configuration's dtype)."""
        return self.layers_of("mamba") * (
            4 * self.d_inner * self.ssm_state_size
            + jnp.dtype(self.dtype).itemsize * (self.conv_kernel - 1)
            * self.conv_dim)

    def reference_sizes(self) -> Dict:
        """The keys the plain reference (nemotron_h_reference.py) reads of a
        configuration file's `sizes`."""
        out = {k: getattr(self, k) for k in (
            "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
            "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
            "conv_kernel", "num_attention_heads", "num_key_value_heads",
            "head_dim", "num_experts_per_tok", "routed_scaling_factor",
            "layer_norm_epsilon")}
        out.update(n_routed_experts=self.n_held,
                   n_routed_experts_published=self.n_routed_experts,
                   first_held_expert=self.experts_held[0])
        return out

    @staticmethod
    def tiny(**overrides) -> "NemotronHConfig":
        """Six layers (M, E, M, *, E, M: a state layer on both sides of the
        attention layer and after an expert layer), 8 Mamba heads of 16 in 2
        groups over a state of 16, chunks of 8; 4 query / 2 kv heads of 16; 16
        published experts of which a test holds all or a share, 4 kept."""
        base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=6,
                    hybrid_override_pattern="MEM*EM", mamba_num_heads=8,
                    mamba_head_dim=16, ssm_state_size=16, n_groups=2,
                    chunk_size=8, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, n_routed_experts=16,
                    experts_held=(0, 16), num_experts_per_tok=4,
                    moe_intermediate_size=32, moe_latent_size=32,
                    moe_shared_expert_intermediate_size=48,
                    max_position_embeddings=256, dtype=jnp.float32)
        base.update(overrides)
        return NemotronHConfig(**base)

    def mamba_params(self) -> int:
        """`in_proj` (z | xBC | dt), the taps and their bias, `A_log`,
        `dt_bias` and `D` a head, the gated norm, `out_proj`, the layer's
        norm."""
        d, di, H = self.hidden_size, self.d_inner, self.mamba_num_heads
        return (d * (di + self.conv_dim + H)
                + (self.conv_kernel + 1) * self.conv_dim + 3 * H + di
                + di * d + d)

    def attn_params(self) -> int:
        d, hd = self.hidden_size, self.head_dim
        H, K = self.num_attention_heads, self.num_key_value_heads
        return d * (H + 2 * K) * hd + H * hd * d + d

    def expert_params(self) -> int:
        return 2 * self.moe_latent_size * self.moe_intermediate_size

    def moe_params(self, picks: float) -> float:
        """An expert layer with `picks` routed experts: the router and its
        bias, the latent's two projections, the shared expert, the norm."""
        d = self.hidden_size
        return (d * self.n_routed_experts + self.n_routed_experts
                + 2 * d * self.moe_latent_size
                + 2 * d * self.moe_shared_expert_intermediate_size + d
                + picks * self.expert_params())

    def _layer_params(self, picks: float) -> float:
        return (self.layers_of("mamba") * self.mamba_params()
                + self.layers_of("attn") * self.attn_params()
                + self.layers_of("latent_moe") * self.moe_params(picks))

    def num_params(self) -> int:
        """Parameters this program holds (the held experts, not the published
        count), every norm, bias, `A_log`, `dt_bias` and `D` counted."""
        d = self.hidden_size
        return int(2 * self.vocab_size * d + d
                   + self._layer_params(self.n_held))

    def flops_per_token(self, seq: int) -> float:
        """Operations a token of a forward and backward pass (6 a parameter a
        token's products touch, the HELD share of its experts), the attention
        layers' at H x 2 hd x 2 a query-context pair, and a Mamba-2 layer's
        recurrence by its own count whatever the context: a state element is
        decayed, updated and read for the output (2 operations each); x 3 for
        the backward pass."""
        picks = self.num_experts_per_tok * self.n_held / self.n_routed_experts
        n = self._layer_params(picks) + self.hidden_size * self.vocab_size
        pair = self.num_attention_heads * 2 * self.head_dim * 2
        state = 6 * self.d_inner * self.ssm_state_size
        return (6.0 * n + 3.0 * self.layers_of("attn") * pair * seq
                + 3.0 * self.layers_of("mamba") * state)


# -------------------------------------------------------------- parameters

def init_params(config: NemotronHConfig, key: jax.Array) -> Dict:
    """Random normal, 1/sqrt(fan_in); the embedding's rows N(0, 1) and the
    router's bias dealt to the shares alike, as models/mimo_v2_flash.py's
    init_params and `expert_share.router_bias` say and why; every norm 1, the
    gated norm too. relu^2 squares what it is given: an expert is given a
    NORMED row through 1/sqrt(fan_in) weights, so a latent lane is N(0, 1) at
    every seed and a hidden lane has mean square 3/2; but every hidden lane is
    >= 0 with MEAN 1/2, and a down-projection drawn plainly maps that mean to
    one fixed vector which EVERY token gets from the shared expert (a sixth
    of its output's power) and from each routed one. At depth every router
    then sees those vectors on top of its token, the load gathers on
    whichever experts they favour, and WHICH moves with the seed (a tick of
    64 rows left 26-32% of a layer's experts without a row, by layer and
    seed, at a mid size on the CPU, and the cell's tokens/s ranged 5% over
    three seeds: PERF.md section 6, PR 52). So `w2` and `shared_down` are
    drawn CENTRED over their fan-in (every output lane's weights sum to 0: the
    hidden state's mean maps to nothing, 1/2688 of the variance is given up):
    23-26% at every layer and seed, which is what the bias grid alone leaves.
    The gated norm's weight at 1/2 to 1/8 changed none of this (tried: the
    state layers are not its cause) and stays 1. Mamba-2's
    own initialisation for what decides whether a state lives: `A_log = log
    U(1, 16)` a head, `dt_bias` the inverse softplus of a step log-uniform in
    [`time_step_min`, `time_step_max`] floored at `time_step_floor`, `D` = 1,
    the taps 1/sqrt(taps) with a zero bias: a head's decay a token exp(-dt A)
    runs from 0.2 to 0.999 before the token's own term, a third of the heads
    above 0.97 and a tenth above 0.99, so that their state lives over tens to
    hundreds of tokens (a program that dropped the state at a chunk's edge
    must not agree with the reference). Every stacked weight is drawn a slice at a time and cast
    inside one program (no float32 copy of a stack: deepseek_v2.init_params).
    `params["layers"]` is one dict a KIND of layer, its layers stacked in the
    published order; `params["experts"]` one dict an expert layer."""
    c = config
    d, di, H = c.hidden_size, c.d_inner, c.mamba_num_heads
    Ha, K, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    lat, fm = c.moe_latent_size, c.moe_intermediate_size
    fs = c.moe_shared_expert_intermediate_size
    keys = iter(jax.random.split(key, 64))

    def stack(lead: Tuple[int, ...], shape: Tuple[int, ...], fan_in: int,
              centred: bool = False):
        n = math.prod(lead)

        def one(k):
            w = jax.random.normal(k, shape, F32) * (1.0 / math.sqrt(fan_in))
            if centred:     # every output lane's weights sum to 0
                w = w - w.mean(axis=-2, keepdims=True)
            return w.astype(c.dtype)

        draw = jax.jit(lambda ks: jax.lax.map(one, ks))
        return draw(jax.random.split(next(keys), n)).reshape(lead + shape)

    def ones(*shape):
        return jnp.ones(shape, dtype=c.dtype)

    def mamba(L):
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            next(keys), (L, H), F32, math.log(c.time_step_min),
            math.log(c.time_step_max))), c.time_step_floor)
        return {
            "norm": ones(L, d),
            "in_proj": stack((L,), (d, di + c.conv_dim + H), d),
            "conv_w": stack((L,), (c.conv_kernel, c.conv_dim),
                            c.conv_kernel),
            "conv_b": jnp.zeros((L, c.conv_dim), c.dtype),
            "A_log": jnp.log(jax.random.uniform(next(keys), (L, H), F32,
                                                1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "D": jnp.ones((L, H), F32),
            "gate_norm": ones(L, di),
            "out_proj": stack((L,), (di, d), di),
        }

    def attn(L):
        return {
            "norm": ones(L, d),
            "wq": stack((L,), (d, Ha * hd), d),
            "wk": stack((L,), (d, K * hd), d),
            "wv": stack((L,), (d, K * hd), d),
            "wo": stack((L,), (Ha * hd, d), Ha * hd),
        }

    def latent_moe(L):
        return {
            "norm": ones(L, d),
            "router": stack((L,), (d, c.n_routed_experts), d),
            "router_bias": router_bias(next(keys), L, c.n_routed_experts,
                                       c.n_held),
            "fc1_latent": stack((L,), (d, lat), d),
            "fc2_latent": stack((L,), (lat, d), lat),
            "shared_up": stack((L,), (d, fs), d),
            "shared_down": stack((L,), (fs, d), fs, centred=True),
        }

    draw = {"mamba": mamba, "attn": attn, "latent_moe": latent_moe}
    blocks = 8 if c.vocab_size % 8 == 0 else 1
    return {
        "embed": stack((blocks,), (c.vocab_size // blocks, d), 1).reshape(
            c.vocab_size, d),
        "layers": {kind: draw[kind](c.layers_of(kind))
                   for kind in sorted(set(c.layer_kinds()))},
        # The held experts, one dict an expert layer in the published order.
        "experts": [{"w1": stack((c.n_held,), (lat, fm), lat),
                     "w2": stack((c.n_held,), (fm, lat), fm, centred=True)}
                    for _ in range(c.layers_of("latent_moe"))],
        "final_norm": ones(d),
        "lm_head": stack((), (d, c.vocab_size), d),
    }


# -------------------------------------------------------- the serving block

class Block:
    """Nemotron-H as the serving runner consumes a model (the protocol is
    llm/model_runner.py's, "A block"): two layer groups, six arrays."""

    # A tick record's: rows and sequences the SSD calls carried (a sequence
    # is a slot READ).
    state_fields = ("ssd_rows", "ssd_seqs")

    def __init__(self, config: NemotronHConfig):
        from ray_tpu.llm.model_runner import LayerGroup

        self.config = config
        self.routed_layers = config.layers_of("latent_moe")
        self.top_k = config.num_experts_per_tok
        self.held_experts = config.n_held
        self.residual_dtype = F32      # the module docstring, "Precision"
        self.scale = config.head_dim ** -0.5
        # (at any page size: the query block does not depend on it)
        self.q_block = self.kv_kernels(16)["all"].q_block
        self.groups = (LayerGroup("all"), LayerGroup("state", slots=True))
        self.impl = "reference"        # attention_fns sets it
        # A slot's convolution tail as it lies: its three rows of `xBC` as
        # whole tiles of the slot's own, so that a step's write of a slot is
        # one contiguous block (kimi_linear.Block says what the other forms
        # cost).
        flat = (config.conv_kernel - 1) * config.conv_dim
        self.tail_tile = ((flat // LANE, LANE) if flat % LANE == 0
                          else (1, flat))
        # A layer's index inside its group's arrays.
        seen: Dict[str, int] = {}
        self.pool_layer = []
        for kind in config.layer_kinds():
            self.pool_layer.append(seen.get(kind, 0))
            seen[kind] = seen.get(kind, 0) + 1

    def refuse(self, *, tensor_parallel: int, lora: bool) -> None:
        if tensor_parallel > 1:
            raise ValueError(
                "nemotron_h: tensor_parallel > 1 is not supported (a slot's "
                "state is not sharded over the Mamba heads, and no exchange "
                "of the expert shares)")
        if lora:
            raise ValueError("nemotron_h: LoRA adapters are not supported")

    def pallas_ok(self) -> bool:
        c = self.config
        return ((c.num_key_value_heads * c.head_dim) % LANE == 0
                and c.head_dim % LANE == 0
                and (2 * c.mamba_head_dim) % LANE == 0
                and c.ssm_state_size % LANE == 0)

    # ---- cache -----------------------------------------------------------

    def cache_arrays(self, pages: Dict[str, int], block_size: int):
        """The `all` group's K and V ROW POOLS (the `*` layers; a token's row
        its kv heads side by side); the state group's S, the rows buffered
        beside it and their count, and the convolution tails (the `M`
        layers), `pages["state"]` slots and the junk slot behind them."""
        from ray_tpu.llm.model_runner import (row_cache_array,
                                              state_cache_array)

        c = self.config
        row = (c.layers_of("attn"), pages["all"], block_size,
               c.num_key_value_heads * c.head_dim)
        M, slots = c.layers_of("mamba"), pages["state"]
        return (
            row_cache_array("k_all", row, c.dtype, "all"),
            row_cache_array("v_all", row, c.dtype, "all"),
            state_cache_array("ssd_state", sd.state_shape(
                M, slots, c.mamba_num_heads, c.mamba_head_dim,
                c.ssm_state_size), F32),
            state_cache_array("ssd_rows", sd.buffer_shape(
                M, slots, c.mamba_num_heads, c.n_groups, c.mamba_head_dim,
                c.ssm_state_size), F32),
            state_cache_array("ssd_fill", sd.fill_shape(M, slots),
                              jnp.int32),
            state_cache_array("conv_tail", (
                M, slots + 1) + self.tail_tile, c.dtype))

    def kv_kernels(self, block_size: int):
        """{page group: the sizes its kernel takes} (`pa.kv_sizes`): 16 query
        heads a kv head."""
        c = self.config
        return {"all": pa.kv_sizes(
            c.num_attention_heads, c.num_key_value_heads, c.head_dim,
            c.head_dim, block_size, jnp.dtype(c.dtype).itemsize, rows=True)}

    def init_cache(self, pages: Dict[str, int], block_size: int):
        from ray_tpu.llm.model_runner import init_cache

        return init_cache(self.cache_arrays(pages, block_size))

    def segments(self, params):
        """Runs of like layers in the published order, each a Python loop
        (`expert_share.kind_segments`)."""
        return kind_segments(runs_of(self.config.layer_kinds()), params)

    def attention_fns(self, impl: str):
        """The `*` layers' paged attention; the `M` layers' kernel is called
        by name, by `impl`."""
        self.impl = impl
        if impl == "pallas":
            return (pa.ragged_paged_attention,
                    pa.ragged_paged_attention_unified)
        return (pa.ragged_paged_attention_reference,
                pa.ragged_paged_attention_unified_reference)

    # ---- the mixers, each stated once -------------------------------------

    def _mamba(self, ctx, h, held, tail, lp, pool_li):
        """Mamba-2 over the normed rows h (R, d); held = (state, buffer,
        fill). -> (the mixer's output (R, d) float32, held, tail)."""
        c = self.config
        rows = ctx.rows
        H, P, G, N = (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                      c.ssm_state_size)
        di, taps = c.d_inner, c.conv_kernel
        zxbcdt = _dot32(h.astype(c.dtype), lp["in_proj"])
        z = zxbcdt[:, :di]
        xbc = zxbcdt[:, di:di + c.conv_dim].astype(c.dtype)
        dt = ss.softplus(zxbcdt[:, di + c.conv_dim:] + lp["dt_bias"])
        zero = rows.q_positions == 0
        before = jnp.where(zero[:, None, None], jnp.zeros((), c.dtype),
                           tail[pool_li, rows.slots].reshape(
                               -1, taps - 1, c.conv_dim))
        conv, after = ss.ragged_conv(xbc, before, lp["conv_w"], lp["conv_b"],
                                     rows.seq, rows.local, rows.starts,
                                     rows.lens)
        tail = tail.at[pool_li, jnp.where(rows.lens > 0, rows.slots,
                                          tail.shape[1] - 1)].set(
            after.reshape((-1,) + self.tail_tile))
        conv = jax.nn.silu(conv)
        x = conv[:, :di].reshape(-1, H, P)
        y, *held = sd.ssd(
            x, dt, -jnp.exp(lp["A_log"]),
            conv[:, di:di + G * N].reshape(-1, G, N),
            conv[:, di + G * N:].reshape(-1, G, N), *held, pool_li,
            rows.slots, rows.starts, rows.lens, zero, impl=self.impl,
            chunk=c.chunk_size)
        y = (y + lp["D"][:, None] * x).reshape(-1, di) * jax.nn.silu(z)
        # The gated norm: each GROUP's lanes apart, the gate before it.
        y = rms_norm(y.reshape(-1, G, di // G),
                     lp["gate_norm"].reshape(G, di // G),
                     c.layer_norm_epsilon).reshape(-1, di)
        return _dot32(y.astype(c.dtype), lp["out_proj"]), tuple(held), tail

    def _attention(self, ctx, h, k_pool, v_pool, lp, pool_li):
        """GQA over the normed rows h (..., d): nothing is rotated. -> (the
        mixer's output, k_pool, v_pool)."""
        c = self.config
        H, K, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        lead = h.shape[:-1]
        h = h.astype(c.dtype)
        q = _dot32(h, lp["wq"]).astype(c.dtype).reshape(*lead, H, hd)
        # A token's row whole: its kv heads side by side.
        k_pool = ctx.write(k_pool, pool_li,
                           _dot32(h, lp["wk"]).astype(c.dtype), "all")
        v_pool = ctx.write(v_pool, pool_li,
                           _dot32(h, lp["wv"]).astype(c.dtype), "all")
        o = ctx.attend(q, k_pool, v_pool, pool_li, group="all",
                       scale=self.scale, kv_heads=K)
        return (_dot32(o.reshape(*lead, H * hd).astype(c.dtype), lp["wo"]),
                k_pool, v_pool)

    def _latent_moe(self, ctx, h, lp):
        """LatentMoE over the normed rows h (N, d) float32. -> (the mixer's
        output, ids (N, top_k) published, counts (3,))."""
        c = self.config
        # The router's chain stays float32 (mimo_v2_flash.Block.layer_step).
        scores = jax.nn.sigmoid(_wide(_dot32, h, lp["router"]))
        ids, gates = route_one_group(c, scores, lp["router_bias"])
        h = h.astype(c.dtype)
        routed, counts = held_expert_ffn(
            c, h, ids, gates * c.routed_scaling_factor,
            ctx.valid.reshape(-1), lp, expert=relu2_expert,
            enter=lp["fc1_latent"], leave=lp["fc2_latent"])
        shared = _dot32(_relu2(_dot32(h, lp["shared_up"])).astype(c.dtype),
                        lp["shared_down"])
        return routed + shared, ids, counts

    def layer_step(self, ctx, kind: str, x, caches, lp, li, ll):
        """One layer, ONE mixer, over rows x (..., d); `li` is the layer's
        index (from 0, a Python int). -> (x, caches, aux): aux None but for
        an expert layer, (ids (..., top_k), counts (3,))."""
        c = self.config
        k_pool, v_pool, *held, tail = caches
        lead = x.shape[:-1]
        pool_li = self.pool_layer[li]
        h = rms_norm(x, lp["norm"], c.layer_norm_epsilon)        # float32
        aux = None
        if kind == "mamba":
            out, held, tail = self._mamba(
                ctx, h.reshape(-1, c.hidden_size), held, tail, lp, pool_li)
        elif kind == "attn":
            out, k_pool, v_pool = self._attention(ctx, h, k_pool, v_pool, lp,
                                                  pool_li)
        else:
            out, ids, counts = self._latent_moe(
                ctx, h.reshape(-1, c.hidden_size), lp)
            aux = (ids.reshape(*lead, self.top_k), counts)
        return (x + out.reshape(x.shape), (k_pool, v_pool, *held, tail), aux)
