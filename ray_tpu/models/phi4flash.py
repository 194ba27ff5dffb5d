"""Phi-4-mini-flash-reasoning for the serving engine: state-space (Mamba)
layers whose state is a fixed-size SLOT a sequence beside the pages, window
attention layers, ONE full attention layer whose K and V eight layers read (a
cross-decoder), gated memory units, differential attention.

Source: https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning
(`config.json`; the equations stand in models/phi4flash_reference.py's
docstring, with what the config does not carry and is assumed). What this file
states once and the serving runner (llm/model_runner.py) consumes through
`Block`:

  * Three LAYER GROUPS. `all`: layer L/2 + 1's K and V, every token, ONE pool
    layer, written by that layer and read by it and by every cross layer.
    `window`: the L/4 window layers, a ring of pages a sequence. `state`: the
    L/4 + 1 Mamba layers, a slot a sequence (ops/ssm_scan.py says how a slot
    lies), read AND written by every step; a sequence whose rows start at
    position 0 starts from zeros.
  * Segments. The self-decoder is a scan over L/4 (Mamba, window) pairs and
    the pair (L/2, L/2 + 1); the cross-decoder a scan over L/4 - 1 (GMU,
    cross) pairs. A row that is not its sequence's last in the step needs the
    self-decoder only (it leaves its state, its window K/V and its layer-L/2+1
    K/V behind): the rows NARROW to one a sequence before the cross-decoder
    (`narrow_at`), each with its memory M_t. That is the model's linear-time
    prefill.
  * Differential attention on the K/V paged kernel as it is (ops/
    paged_attention.py, "the pair form"): a page's row is one kv PAIR, K
    `[k_2j | k_2j+1]` and V `[v_2j | v_2j+1]`, 2 hd = 128 lanes wide, every
    value in HBM once; a query head rides as a 128-lane row with its 64 values
    in its own half and zeros in the other, so that the kernel's one product
    with the K row is the head's product with its own k, its softmax is the
    head's, and its value sum is over the whole V row. Heads 2p and 2p + 1
    come back as the two softmax sums of pair p; the subtraction, the RMSNorm
    and (1 - lambda_init) are float32, here.

Precision: the residual stream, the subtraction and what follows it, dt,
exp(dt A) and the scan state are float32 (32 residual adds, and a difference
of two near-equal attention sums that is then normalised: bf16 there is read
by every later layer); weights, K/V and the convolution tail are the
configuration's dtype.

Left out: dropout (`embd_pdrop`, `resid_pdrop` 0) and training (the scan has
no backward pass here).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import normal
from ray_tpu.models.expert_share import _dot32
from ray_tpu.ops import paged_attention as pa
from ray_tpu.ops import ssm_scan as ss
from ray_tpu.ops.layers import layer_norm, rms_norm, swiglu

LANE = 128
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The published keys (their Hugging Face names) and the Mamba sizes the
    config does not carry (the family's defaults)."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    dtype: Any = jnp.bfloat16

    def serving_block(self) -> "Block":
        return Block(self)

    def __post_init__(self):
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 8:
            raise ValueError("num_hidden_layers: a multiple of 4, at least 8 "
                             "(pairs of layers, half of them the "
                             "cross-decoder)")
        if self.mb_per_layer != 2:
            raise ValueError("mb_per_layer: only 2 (Mamba, attention "
                             "alternate) is modelled")
        if (self.num_attention_heads % 2 or self.num_key_value_heads % 2
                or self.num_attention_heads % self.num_key_value_heads):
            raise ValueError("differential attention pairs heads: even "
                             "counts, query pairs a multiple of kv pairs")

    # What the serving runner and engine read of any model's configuration.
    @property
    def max_seq(self) -> int:
        return self.max_position_embeddings

    @property
    def norm_eps(self) -> float:
        return self.layer_norm_eps

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return -(-self.hidden_size // 16)

    @property
    def self_pairs(self) -> int:
        """(Mamba, window) pairs: layers 0 .. L/2 - 1."""
        return self.num_hidden_layers // 4

    @property
    def cross_pairs(self) -> int:
        """(GMU, cross) pairs: layers L/2 + 2 .. L - 1."""
        return self.num_hidden_layers // 4 - 1

    @property
    def state_bytes_per_sequence(self) -> int:
        """A slot: the scan state (float32) and the convolution tail of every
        Mamba layer."""
        return (self.self_pairs + 1) * self.d_inner * (
            4 * self.mamba_d_state
            + (self.mamba_d_conv - 1) * jnp.dtype(self.dtype).itemsize)

    def reference_sizes(self) -> Dict:
        """The keys the plain reference (phi4flash_reference.py) reads of a
        configuration file's `sizes`."""
        return {k: getattr(self, k) for k in (
            "hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "sliding_window", "layer_norm_eps",
            "mamba_d_state", "mamba_d_conv", "mamba_expand")}

    @staticmethod
    def tiny(**overrides) -> "Phi4FlashConfig":
        """Eight layers, every role present: 0-3 Mamba / window, 4 Mamba +
        memory, 5 full, 6-7 GMU / cross. Window 8 with pages of 4 passes the
        window many times in a short test; d_inner 128 is one lane tile."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                    num_hidden_layers=8, num_attention_heads=8,
                    num_key_value_heads=4, sliding_window=8,
                    max_position_embeddings=256, mamba_d_state=4,
                    dtype=jnp.float32)
        base.update(overrides)
        return Phi4FlashConfig(**base)

    def num_params(self) -> int:
        d, di, ff = self.hidden_size, self.d_inner, self.intermediate_size
        H, K, hd = (self.num_attention_heads, self.num_key_value_heads,
                    self.head_dim)
        mlp = 3 * d * ff + 4 * d
        mamba = (d * 2 * di + di * (self.mamba_d_conv + 1)
                 + di * (self.dt_rank + 2 * self.mamba_d_state)
                 + self.dt_rank * di + di + di * self.mamba_d_state + di
                 + di * d)
        lam = 4 * hd + 2 * hd
        attn = d * (H + 2 * K) * hd + (H + 2 * K) * hd + d * d + d + lam
        cross = d * H * hd + H * hd + d * d + d + lam
        gmu = 2 * d * di
        return int(self.vocab_size * d + 2 * d
                   + (self.self_pairs + 1) * (mamba + attn + 2 * mlp)
                   + self.cross_pairs * (gmu + cross + 2 * mlp))

    def flops_per_token(self, seq: int) -> float:
        """Operations a token of a forward and backward pass over a context
        of `seq` (6 a parameter a token touches, the tied embedding counted
        once, as the head), attention by the equations' own count (a head's
        score over hd and its value sum over 2 hd, 2 operations each, a
        query-context pair: a window layer's token sees at most the window,
        the full layer's and the cross layers' `seq`), and the recurrence (9
        operations a state element a step: exp, two products and a sum for
        s_t, a product and a sum for y_t, dt's part), each x 3 for the
        backward pass."""
        pair = self.num_attention_heads * 3 * self.head_dim * 2
        seen = ((1 + self.cross_pairs) * seq
                + self.self_pairs * min(seq, self.sliding_window))
        scan = 9 * (self.self_pairs + 1) * self.d_inner * self.mamba_d_state
        return 6.0 * self.num_params() + 3.0 * pair * seen + 3.0 * scan


def lambda_init(layer):
    """0.8 - 0.6 exp(-0.3 i), of a layer's published index (may be traced)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, F32))


# -------------------------------------------------------------- parameters

def init_params(config: Phi4FlashConfig, key: jax.Array) -> Dict:
    """Random normal, 1/sqrt(fan_in); the embedding's rows N(0, 1) (what
    `torch.nn.Embedding` draws; it is the head too); Mamba's own
    initialisation for what decides whether a state lives over thousands of
    steps: `A_log = log(1..N)` a channel, `dt_bias` the inverse softplus of
    dt log-uniform in [1e-3, 1e-1], `D` = 1; the four lambda vectors of an
    attention layer N(0, 0.1); LayerNorm (1, 0), the 2 hd-wide RMSNorm 1.
    Every stacked weight is drawn a slice at a time and cast inside one
    program, the embedding in eight blocks of rows (no float32 copy of a
    stack: deepseek_v2.init_params). `params["layers"]` holds "self", "mid"
    and "cross", each {"mamba" | "gmu", "attn"} stacked over its pairs."""
    c = config
    d, di, ff = c.hidden_size, c.d_inner, c.intermediate_size
    H, K, hd, N = (c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                   c.mamba_d_state)
    keys = iter(jax.random.split(key, 128))

    def stack(lead: Tuple[int, ...], shape: Tuple[int, ...], fan_in: int):
        n = math.prod(lead)

        @jax.jit
        def draw(ks):
            return jax.lax.map(
                lambda k: (jax.random.normal(k, shape, F32)
                           * (1.0 / math.sqrt(fan_in))).astype(c.dtype), ks)

        return draw(jax.random.split(next(keys), n)).reshape(lead + shape)

    def norm(L, prefix="norm"):
        return {f"{prefix}_w": jnp.ones((L, d), c.dtype),
                f"{prefix}_b": jnp.zeros((L, d), c.dtype)}

    def mlp(L):
        return {**norm(L, "mlp_norm"), "fc1": stack((L,), (d, 2 * ff), d),
                "fc2": stack((L,), (ff, d), ff)}

    def mamba(L):
        dt = jnp.exp(jax.random.uniform(
            next(keys), (L, di), F32, math.log(1e-3), math.log(1e-1)))
        return {**norm(L), **mlp(L),
                "in_proj": stack((L,), (d, 2 * di), d),
                "conv_w": stack((L,), (c.mamba_d_conv, di), c.mamba_d_conv),
                "conv_b": jnp.zeros((L, di), c.dtype),
                "x_proj": stack((L,), (di, c.dt_rank + 2 * N), di),
                "dt_proj": stack((L,), (c.dt_rank, di), c.dt_rank),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=F32)), (L, di, N)),
                "D": jnp.ones((L, di), F32),
                "out_proj": stack((L,), (di, d), di)}

    def attention(L, width):
        lam = lambda: normal(next(keys), (L, hd), 0.1)
        return {**norm(L), **mlp(L),
                "wqkv": stack((L,), (d, width), d),
                "bqkv": jnp.zeros((L, width), c.dtype),
                "lambda_q1": lam(), "lambda_k1": lam(),
                "lambda_q2": lam(), "lambda_k2": lam(),
                "subln": jnp.ones((L, 2 * hd), F32),
                "wo": stack((L,), (d, d), d),
                "bo": jnp.zeros((L, d), c.dtype)}

    def gmu(L):
        return {**norm(L), **mlp(L), "w1": stack((L,), (d, di), d),
                "w2": stack((L,), (di, d), di)}

    blocks = 8 if c.vocab_size % 8 == 0 else 1
    kv = (H + 2 * K) * hd
    return {
        "embed": stack((blocks,), (c.vocab_size // blocks, d), 1).reshape(
            c.vocab_size, d),
        "layers": {
            "self": {"mamba": mamba(c.self_pairs),
                     "attn": attention(c.self_pairs, kv)},
            "mid": {"mamba": mamba(1), "attn": attention(1, kv)},
            "cross": {"gmu": gmu(c.cross_pairs),
                      "attn": attention(c.cross_pairs, H * hd)},
        },
        "final_norm_w": jnp.ones((d,), c.dtype),
        "final_norm_b": jnp.zeros((d,), c.dtype),
    }


# -------------------------------------------------------- the serving block

class Block:
    """Phi-4-mini-flash as the serving runner consumes a model (the protocol
    is llm/model_runner.py's, "A block"): three layer groups, six arrays."""

    routed_layers = 0
    top_k = None
    held_experts = 0
    narrow_at = 2           # rows narrow before segment 2, the cross-decoder

    def __init__(self, config: Phi4FlashConfig):
        from ray_tpu.llm.model_runner import LayerGroup

        self.config = config
        self.residual_dtype = F32      # the module docstring, "Precision"
        self.scale = config.head_dim ** -0.5
        # (at any page size: the query block does not depend on it)
        self.q_block = self.kv_kernels(16)["all"].q_block
        self.groups = (LayerGroup("all"),
                       LayerGroup("window", config.sliding_window),
                       LayerGroup("state", slots=True))
        self.impl = "reference"        # attention_fns sets it

    def refuse(self, *, tensor_parallel: int, lora: bool) -> None:
        if tensor_parallel > 1:
            raise ValueError("phi4flash: tensor_parallel > 1 is not "
                             "supported (a slot's state is not sharded)")
        if lora:
            raise ValueError("phi4flash: LoRA adapters are not supported")

    def pallas_ok(self) -> bool:
        c = self.config
        return (2 * c.head_dim) % LANE == 0 and c.d_inner % LANE == 0

    # ---- cache -----------------------------------------------------------

    def cache_arrays(self, pages: Dict[str, int], block_size: int):
        """K and V of the two page groups as ROW POOLS (pages, page, K hd): a
        token's row is its K / 2 kv pairs of 2 hd side by side; the state
        group's scan state and convolution tail, `pages["state"]` slots and
        the junk slot behind them."""
        from ray_tpu.llm.model_runner import row_cache_array, state_cache_array

        c = self.config
        row = (block_size, c.num_key_value_heads * c.head_dim)
        mamba_layers = c.self_pairs + 1
        out = []
        for group, layers in (("all", 1), ("window", c.self_pairs)):
            for name in ("k", "v"):
                out.append(row_cache_array(
                    f"{name}_{group}", (layers, pages[group]) + row, c.dtype,
                    group))
        out.append(state_cache_array(
            "ssm_state", ss.state_shape(mamba_layers, pages["state"],
                                        c.mamba_d_state, c.d_inner), F32))
        out.append(state_cache_array(
            "conv_tail", (mamba_layers, pages["state"] + 1,
                          c.mamba_d_conv - 1, c.d_inner), c.dtype))
        return tuple(out)

    def kv_kernels(self, block_size: int):
        """{page group: the sizes its kernel takes} (`pa.kv_sizes`), in the
        pair form: K / 2 rows of 2 hd lanes."""
        c = self.config
        return {group: pa.kv_sizes(
            c.num_attention_heads, c.num_key_value_heads // 2,
            2 * c.head_dim, 2 * c.head_dim, block_size,
            jnp.dtype(c.dtype).itemsize, rows=True, window=window)
            for group, window in (("all", None),
                                  ("window", c.sliding_window))}

    def init_cache(self, pages: Dict[str, int], block_size: int):
        from ray_tpu.llm.model_runner import init_cache

        return init_cache(self.cache_arrays(pages, block_size))

    def segments(self, params):
        """The self-decoder's pairs as one scan, the pair (L/2, L/2 + 1) on
        its own (it hands the memory on, so what the rows carry changes
        there: a Python loop of one), the cross-decoder's pairs as one scan.
        A segment's layer index counts PAIRS: pair j is layers 2j, 2j + 1."""
        c = self.config
        layers = params["layers"]
        return [("self", layers["self"], 0, None),
                ("mid", layers["mid"], c.self_pairs, [{}]),
                ("cross", layers["cross"], c.self_pairs + 1, None)]

    def finish(self, x, params):
        """The final LayerNorm of what the last segment hands on."""
        h, _ = x
        c = self.config
        return layer_norm(h, params["final_norm_w"], params["final_norm_b"],
                          c.layer_norm_eps).astype(c.dtype)

    def attention_fns(self, impl: str):
        self.impl = impl
        if impl == "pallas":
            return (pa.ragged_paged_attention,
                    pa.ragged_paged_attention_unified)
        return (pa.ragged_paged_attention_reference,
                pa.ragged_paged_attention_unified_reference)

    # ---- the layers, each stated once -------------------------------------

    def _mlp(self, x, lp):
        c = self.config
        h = layer_norm(x, lp["mlp_norm_w"], lp["mlp_norm_b"],
                       c.layer_norm_eps).astype(c.dtype)
        g, u = jnp.split(_dot32(h, lp["fc1"]), 2, axis=-1)
        return x + _dot32(swiglu(g, u).astype(c.dtype), lp["fc2"])

    def _mamba(self, ctx, x, state, tail, lp, pool_li):
        """-> (x + the mixer's output, y before the gate (..., d_i) float32,
        state, tail)."""
        c = self.config
        rows = ctx.rows
        lead, di, N, R = x.shape[:-1], c.d_inner, c.mamba_d_state, c.dt_rank
        h = layer_norm(x, lp["norm_w"], lp["norm_b"],
                       c.layer_norm_eps).astype(c.dtype)
        uz = _dot32(h, lp["in_proj"]).reshape(-1, 2 * di)
        u, z = uz[:, :di].astype(c.dtype), uz[:, di:]
        zero = rows.q_positions == 0
        before = jnp.where(zero[:, None, None], jnp.zeros((), c.dtype),
                           tail[pool_li, rows.slots])
        conv, after = ss.ragged_conv(u, before, lp["conv_w"], lp["conv_b"],
                                     rows.seq, rows.local, rows.starts,
                                     rows.lens)
        tail = tail.at[pool_li, jnp.where(rows.lens > 0, rows.slots,
                                          tail.shape[1] - 1)].set(after)
        xc = jax.nn.silu(conv).astype(c.dtype)
        rbc = _dot32(xc, lp["x_proj"])
        dt = _dot32(rbc[:, :R].astype(c.dtype), lp["dt_proj"]) \
            + lp["dt_bias"]
        y, state = ss.ssm_scan(
            dt, xc.astype(F32), rbc[:, R:R + N], rbc[:, R + N:],
            -jnp.exp(lp["A_log"].astype(F32)).T, state, pool_li, rows.slots,
            rows.starts, rows.lens, zero, impl=self.impl)
        y = y + lp["D"] * xc.astype(F32)
        out = _dot32((y * jax.nn.silu(z)).astype(c.dtype), lp["out_proj"])
        return (x + out.reshape(*lead, -1), y.reshape(*lead, di), state,
                tail)

    def _attention(self, ctx, x, k_pool, v_pool, lp, layer, pool_li, group,
                   *, cross: bool = False):
        """Differential attention of layer `layer` (its published index) over
        pool layer `pool_li` of `group`; a cross layer writes nothing."""
        c = self.config
        H, K, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        lead = x.shape[:-1]
        h = layer_norm(x, lp["norm_w"], lp["norm_b"],
                       c.layer_norm_eps).astype(c.dtype)
        qkv = _dot32(h, lp["wqkv"]) + lp["bqkv"]
        if not cross:
            k = qkv[..., H * hd:(H + K) * hd]
            v = qkv[..., (H + K) * hd:]
            k_pool = ctx.write(k_pool, pool_li, k.astype(c.dtype), group)
            v_pool = ctx.write(v_pool, pool_li, v.astype(c.dtype), group)
        q = pa.pair_queries(
            qkv[..., :H * hd].reshape(*lead, H, hd).astype(c.dtype))
        sums = ctx.attend(
            q, k_pool, v_pool, pool_li, group=group, scale=self.scale,
            kv_heads=K // 2,
            **({"window": c.sliding_window} if group == "window" else {}))
        sums = sums.astype(F32).reshape(*lead, H // 2, 2, 2 * hd)
        lam0 = lambda_init(layer)
        lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
               - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + lam0)
        o = sums[..., 0, :] - lam * sums[..., 1, :]
        o = rms_norm(o, lp["subln"], c.layer_norm_eps) * (1.0 - lam0)
        out = _dot32(o.reshape(*lead, H * hd).astype(c.dtype),
                     lp["wo"]) + lp["bo"]
        return x + out, k_pool, v_pool

    def layer_step(self, ctx, kind: str, x, caches, lp, li, ll):
        """One PAIR of layers over rows x (..., d); `li` counts pairs (layers
        2 li and 2 li + 1). From the pair (L/2, L/2 + 1) on, x is (rows,
        memory)."""
        k_all, v_all, k_win, v_win, state, tail = caches
        if kind == "cross":
            x, memory = x
            c = self.config
            g = lp["gmu"]
            h = layer_norm(x, g["norm_w"], g["norm_b"],
                           c.layer_norm_eps).astype(c.dtype)
            gate = jax.nn.silu(_dot32(h, g["w1"]))
            x = x + _dot32((memory * gate).astype(c.dtype), g["w2"])
            x = self._mlp(x, g)
            x, _, _ = self._attention(ctx, x, k_all, v_all, lp["attn"],
                                      2 * li + 1, 0, "all", cross=True)
            return (self._mlp(x, lp["attn"]), memory), caches, None
        x, memory, state, tail = self._mamba(ctx, x, state, tail,
                                             lp["mamba"], li)
        x = self._mlp(x, lp["mamba"])
        if kind == "self":
            x, k_win, v_win = self._attention(
                ctx, x, k_win, v_win, lp["attn"], 2 * li + 1, li, "window")
        else:
            x, k_all, v_all = self._attention(
                ctx, x, k_all, v_all, lp["attn"], 2 * li + 1, 0, "all")
        x = self._mlp(x, lp["attn"])
        return ((x, memory) if kind == "mid" else x,
                (k_all, v_all, k_win, v_win, state, tail), None)
