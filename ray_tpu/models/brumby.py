"""Brumby-14B-Base for the serving engine: a dense decoder whose every layer
mixes tokens by POWER RETENTION (power attention of degree 2 with a gate;
arXiv:2507.04239) in place of softmax attention: a matrix-valued gated state a
sequence and kv head, of one size whatever the context, and no page of K/V
anywhere.

Source: https://huggingface.co/manifestai/Brumby-14B-Base (`config.json`:
every key is the Qwen3-14B decoder's, whose weights the model was retrained
from; the equations stand in models/brumby_reference.py's docstring, with what
the config does not carry and is assumed). What this file states once and the
serving runner (llm/model_runner.py) consumes through `Block`:

  * Two LAYER GROUPS and NO paged layer. `all` holds no array: its pages are
    the engine's token accounting (admission, the length cap, the prefix
    chain's digests), zero bytes on the device. `state`: a slot a sequence,
    every layer's S (a kv head's 8,256 products of two key lanes by 128 value
    lanes, float32), its normaliser z, and the last rows' k, v and gates
    buffered beside them with their count (ops/power_retention.py says how a
    slot lies): a decode row READS the state and writes its own row, and the
    buffer is folded into the state once in `FOLD` rows; a sequence whose
    rows start at position 0 starts from zeros and an empty buffer.
  * One segment, a scan over the layers; a layer is RMSNorm, q / k / v and
    the gate, a 128-wide RMSNorm a head on q and k, rope, the retention over
    the step's ragged rows (a decode row takes the recurrent step, a prompt
    slice the chunked form, ONE call), the output projection; RMSNorm,
    SwiGLU.

Precision: the residual stream, q, k, v, the gate's log and the state are
float32 (the state accumulates thousands of rank-one updates each a
thousandth of it: bfloat16 would drop them; q . k is SQUARED, which doubles a
rounding's share); weights are the configuration's dtype.

Left out: training (the retention has no backward pass here), tensor
parallelism (a slot's state is not sharded), LoRA.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models.expert_share import _dot32
from ray_tpu.ops import power_retention as pr
from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies, swiglu

LANE = 128
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """The published keys (their Hugging Face names) and what the config does
    not carry: the gates' range of the random draw and the retention's eps."""
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 32768
    retention_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    def serving_block(self) -> "Block":
        return Block(self)

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads: a multiple of the kv heads")
        if self.head_dim % 2:
            raise ValueError("head_dim: even (rope, and phi's chunks)")

    # What the serving runner and engine read of any model's configuration.
    @property
    def max_seq(self) -> int:
        return self.max_position_embeddings

    @property
    def norm_eps(self) -> float:
        return self.rms_norm_eps

    @property
    def state_bytes_per_sequence(self) -> int:
        """The recurrence's state as a slot holds it: every layer's S and z,
        float32 (the rows buffered beside them are 1.6% more)."""
        one = (self.num_hidden_layers, 0, self.num_key_value_heads,
               self.head_dim)          # no slot but the junk one
        return 4 * (math.prod(pr.state_shape(*one))
                    + math.prod(pr.norm_shape(*one)))

    def reference_sizes(self) -> Dict:
        """The keys the plain reference (brumby_reference.py) reads of a
        configuration file's `sizes`."""
        return {k: getattr(self, k) for k in (
            "hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
            "retention_eps")}

    @staticmethod
    def tiny(**overrides) -> "BrumbyConfig":
        """Two layers, 6 query heads over 2 kv heads (3:1), heads of 16: a
        slot is 2 x 2 x 9 x 16 x 17 floats."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    num_hidden_layers=2, num_attention_heads=6,
                    num_key_value_heads=2, head_dim=16,
                    max_position_embeddings=256, dtype=jnp.float32)
        base.update(overrides)
        return BrumbyConfig(**base)

    def num_params(self) -> int:
        d, ff, hd = self.hidden_size, self.intermediate_size, self.head_dim
        H, K = self.num_attention_heads, self.num_key_value_heads
        layer = (2 * d * H * hd + 2 * d * K * hd + d * K + K + 2 * hd
                 + 3 * d * ff + 2 * d)
        return int(2 * self.vocab_size * d + d
                   + self.num_hidden_layers * layer)

    def flops_per_token(self, seq: int) -> float:
        """Operations a token of a forward and backward pass (6 a parameter a
        token's products touch: every one but the embedding's rows, which are
        looked up), and the retention by the recurrence's own count, whatever
        the context `seq`: a feature of a kv head's state (hd (hd + 1) / 2 of
        them, by hd values and the normaliser) is decayed and updated (3
        operations) and read by each of its G query heads (2 each); x 3 for
        the backward pass."""
        hd, H, K = (self.head_dim, self.num_attention_heads,
                    self.num_key_value_heads)
        state = hd * (hd + 1) // 2 * (hd + 1)
        retention = self.num_hidden_layers * state * (3 * K + 2 * H)
        return (6.0 * (self.num_params()
                       - self.vocab_size * self.hidden_size)
                + 3.0 * retention)


# -------------------------------------------------------------- parameters

def init_params(config: BrumbyConfig, key: jax.Array) -> Dict:
    """Random normal, 1/sqrt(fan_in); the embedding's rows N(0, 1) (what
    `torch.nn.Embedding` draws), the head 1/sqrt(d); norms 1; the gate's
    weight as every matrix and its bias the logit of g = 1 - 10^-u, u uniform
    in [1, 3] a head and layer: gates from 0.9 to 0.999 before the token's
    own term, so that a state lives over hundreds to thousands of tokens (a
    gate of 0.5 forgets in thirty, and a program that dropped the state at a
    chunk's edge would still agree with the reference). Every stacked weight
    is drawn a slice at a time and cast inside one program, the embedding and
    the head in eight blocks (no float32 copy of a stack:
    deepseek_v2.init_params)."""
    c = config
    d, ff, hd = c.hidden_size, c.intermediate_size, c.head_dim
    H, K, L = c.num_attention_heads, c.num_key_value_heads, \
        c.num_hidden_layers
    keys = iter(jax.random.split(key, 32))

    def stack(lead: Tuple[int, ...], shape: Tuple[int, ...], fan_in: int):
        n = math.prod(lead)

        @jax.jit
        def draw(ks):
            return jax.lax.map(
                lambda k: (jax.random.normal(k, shape, F32)
                           * (1.0 / math.sqrt(fan_in))).astype(c.dtype), ks)

        return draw(jax.random.split(next(keys), n)).reshape(lead + shape)

    blocks = 8 if c.vocab_size % 8 == 0 else 1
    held = 1.0 - 10.0 ** -jax.random.uniform(next(keys), (L, K), F32, 1.0, 3.0)
    return {
        "embed": stack((blocks,), (c.vocab_size // blocks, d), 1).reshape(
            c.vocab_size, d),
        "lm_head": jnp.moveaxis(
            stack((blocks,), (d, c.vocab_size // blocks), d), 0, 1).reshape(
                d, c.vocab_size),
        "final_norm": jnp.ones((d,), c.dtype),
        "layers": {
            "attn_norm": jnp.ones((L, d), c.dtype),
            "wq": stack((L,), (d, H * hd), d),
            "wk": stack((L,), (d, K * hd), d),
            "wv": stack((L,), (d, K * hd), d),
            "wg": stack((L,), (d, K), d),
            "bg": jnp.log(held) - jnp.log1p(-held),
            "q_norm": jnp.ones((L, hd), c.dtype),
            "k_norm": jnp.ones((L, hd), c.dtype),
            "wo": stack((L,), (H * hd, d), H * hd),
            "mlp_norm": jnp.ones((L, d), c.dtype),
            "w_gate": stack((L,), (d, ff), d),
            "w_up": stack((L,), (d, ff), d),
            "w_down": stack((L,), (ff, d), ff),
        },
    }


# -------------------------------------------------------- the serving block

class Block:
    """Brumby as the serving runner consumes a model (the protocol is
    llm/model_runner.py's, "A block"): two layer groups, four arrays, all the
    state group's."""

    routed_layers = 0
    top_k = None
    held_experts = 0
    q_block = None          # no paged kernel walks anything
    # A tick record's: rows and sequences the calls carried.
    state_fields = ("retention_rows", "retention_seqs")

    def __init__(self, config: BrumbyConfig):
        from ray_tpu.llm.model_runner import LayerGroup

        self.config = config
        self.residual_dtype = F32      # the module docstring, "Precision"
        self.scale = config.head_dim ** -0.5
        self.groups = (LayerGroup("all"), LayerGroup("state", slots=True))
        self.cos, self.sin = rope_frequencies(
            config.head_dim, config.max_seq, config.rope_theta)
        self.impl = "reference"        # attention_fns sets it

    def refuse(self, *, tensor_parallel: int, lora: bool) -> None:
        if tensor_parallel > 1:
            raise ValueError("brumby: tensor_parallel > 1 is not supported "
                             "(a slot's state is not sharded)")
        if lora:
            raise ValueError("brumby: LoRA adapters are not supported")

    def pallas_ok(self) -> bool:
        c = self.config
        return (c.head_dim % LANE == 0
                and c.num_attention_heads // c.num_key_value_heads < 8)

    # ---- cache -----------------------------------------------------------

    def cache_arrays(self, pages: Dict[str, int], block_size: int):
        """The state group's S, z, buffered rows and their count,
        `pages["state"]` slots and the junk slot behind them. The `all` group
        holds nothing."""
        from ray_tpu.llm.model_runner import state_cache_array

        c = self.config
        sizes = (c.num_hidden_layers, pages["state"], c.num_key_value_heads,
                 c.head_dim)
        return (state_cache_array("ret_state", pr.state_shape(*sizes), F32),
                state_cache_array("ret_norm", pr.norm_shape(*sizes), F32),
                state_cache_array("ret_rows", pr.buffer_shape(*sizes), F32),
                state_cache_array("ret_fill", pr.fill_shape(*sizes[:2]),
                                  jnp.int32))

    def init_cache(self, pages: Dict[str, int], block_size: int):
        from ray_tpu.llm.model_runner import init_cache

        return init_cache(self.cache_arrays(pages, block_size))

    def segments(self, params):
        return [("layer", params["layers"], 0, None)]

    def attention_fns(self, impl: str):
        """No paged attention: the retention is called by name, by `impl`."""
        self.impl = impl
        return None, None

    # ---- the layer ---------------------------------------------------------

    def layer_step(self, ctx, kind: str, x, caches, lp, li, ll):
        c = self.config
        state, norm, buf, fill = caches
        rows = ctx.rows
        lead = x.shape[:-1]
        H, K, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        h = rms_norm(x, lp["attn_norm"], c.rms_norm_eps).astype(c.dtype)
        q = _dot32(h, lp["wq"]).reshape(*lead, H, hd)
        k = _dot32(h, lp["wk"]).reshape(*lead, K, hd)
        v = _dot32(h, lp["wv"]).reshape(*lead, K, hd)
        log_g = jax.nn.log_sigmoid(_dot32(h, lp["wg"]) + lp["bg"])
        q = apply_rope(rms_norm(q, lp["q_norm"], c.rms_norm_eps), self.cos,
                       self.sin, ctx.rope_pos)
        k = apply_rope(rms_norm(k, lp["k_norm"], c.rms_norm_eps), self.cos,
                       self.sin, ctx.rope_pos)
        o, state, norm, buf, fill = pr.power_retention(
            q.reshape(-1, H, hd), k.reshape(-1, K, hd), v.reshape(-1, K, hd),
            log_g.reshape(-1, K), state, norm, buf, fill, li, rows.slots,
            rows.starts, rows.lens, rows.q_positions == 0, scale=self.scale,
            eps=c.retention_eps, impl=self.impl)
        x = x + _dot32(o.reshape(*lead, H * hd).astype(c.dtype), lp["wo"])
        h = rms_norm(x, lp["mlp_norm"], c.rms_norm_eps).astype(c.dtype)
        hidden = swiglu(_dot32(h, lp["w_gate"]), _dot32(h, lp["w_up"]))
        return (x + _dot32(hidden.astype(c.dtype), lp["w_down"]),
                (state, norm, buf, fill), None)
