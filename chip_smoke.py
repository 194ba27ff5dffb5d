"""Proof that the system starts on the chip: serve, train and the cluster path.

    python chip_smoke.py            # one chip: train, kernels, serve,
                                    # long_context, cluster
    python chip_smoke.py --chips 4  # one four-chip host: the sharded paths only

Every phase runs at Llama-3-8B published widths (`LlamaConfig.llama3_8b`), cut
by depth only, with weights made from `--seed`. Each phase prints one JSON
object on a line of its own; the LAST line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as the JAX process that used it reported it. Any failed phase,
or no TPU, is a non-zero exit and no such line. There is no CPU mode: the
phase bodies below are importable and run at a tiny size on the CPU from
`tests/test_chip_smoke.py`, but the device and kernel assertions live in the
`_child_*` entry points and in `main()`.

One process for each chip. A chip belongs to one process at a time, so this
parent never initializes JAX: `train`, `kernels` and `serve` each run in a
child, and in `cluster` the only process that touches JAX is the replica's
worker.

The compile cache is placed from outside: where `JAX_COMPILATION_CACHE_DIR`
is set it is used as it is, otherwise it is `<checkout>/.jax_cache` for this
script, its children and the cluster's workers. All of them run with
`JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS=0` (see `main`), or no two entry
points would share a compiled serving program.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))

# Kernel-vs-reference tolerance in bf16: max |kernel - reference| over
# max |reference|. bf16 keeps 8 bits of mantissa (2**-8 = 0.4% a rounding);
# kernel and reference round at different points (the reference casts the
# probabilities to bf16 before the PV matmul, the kernels keep them in f32).
BF16_REL_TOL = 2e-2
# Training loss, four-device mesh against one device: same seed, batch and
# depth, different reduction order in bf16.
LOSS_REL_TOL = 2e-2

SERVE_LAYERS = 8
TRAIN_LAYERS = 2
KV_BLOCKS = 4096
PROMPT_LENS = (1024, 896, 768, 640, 512)
MAX_TOKENS = 32
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR = 2, 2048, 3, 1e-4


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


# --------------------------------------------------------------------------
# Phase bodies: importable, take the model configuration, run anywhere.
# --------------------------------------------------------------------------

def llm_config(model_config, *, seed: int, num_kv_blocks: int,
               tensor_parallel: int = 1, num_tpus_per_replica: float = 0.0):
    """The LLMConfig the smoke serves with: the defaults users get
    (prefix caching, batch 8, chunk 128), a real KV pool, and the "light"
    warm-up — a cold "full" one compiles twice the programs."""
    from ray_tpu.llm.serving import LLMConfig

    return LLMConfig(model_config=model_config, seed=seed,
                     num_kv_blocks=num_kv_blocks, warmup_buckets="light",
                     tensor_parallel=tensor_parallel,
                     num_tpus_per_replica=num_tpus_per_replica)


def make_requests(vocab_size: int, prompt_lens, max_tokens: int, seed: int):
    """Seeded requests: the first samples (temperature, top-k, its own seed),
    the rest are greedy."""
    import numpy as np

    rng = np.random.RandomState(seed)
    reqs = []
    for i, n in enumerate(prompt_lens):
        req = {"prompt": rng.randint(1, vocab_size, n).tolist(),
               "max_tokens": max_tokens, "request_id": f"smoke-{seed}-{i}"}
        if i == 0:
            req.update(temperature=0.8, top_k=50, seed=seed + 1)
        reqs.append(req)
    return reqs


def device_memory() -> dict:
    """Bytes in use and at peak on every device (empty on the CPU, which
    keeps no such count)."""
    import jax

    return {str(d): {k: stats[k]
                     for k in ("bytes_in_use", "peak_bytes_in_use")}
            for d in jax.devices() if (stats := d.memory_stats())}


def _tokens(response) -> list:
    return response["choices"][0]["token_ids"]


def _check_tokens(tokens, max_tokens: int, vocab_size: int) -> None:
    if len(tokens) != max_tokens or not all(
            isinstance(t, int) and 0 <= t < vocab_size for t in tokens):
        raise AssertionError(f"bad completion: {tokens!r}")


def serve_phase(model_config, *, seed: int, num_kv_blocks: int,
                prompt_lens, max_tokens: int, tensor_parallel: int = 1):
    """Build an LLMServer, answer concurrent requests, then repeat the first
    one twice. Returns (result dict, the server's responses by request)."""
    from ray_tpu.llm.serving import LLMServer

    server = LLMServer(llm_config(model_config, seed=seed,
                                  num_kv_blocks=num_kv_blocks,
                                  tensor_parallel=tensor_parallel))
    warm = server.engine_stats()
    reqs = make_requests(model_config.vocab_size, prompt_lens, max_tokens,
                         seed)
    t0 = time.time()
    with ThreadPoolExecutor(len(reqs)) as pool:
        responses = list(pool.map(server.completions, reqs))
    batch_s = time.time() - t0
    for r in responses:
        _check_tokens(_tokens(r), max_tokens, model_config.vocab_size)
    mid = server.engine_stats()
    # The first request again, alone, twice: both find the prompt's pages in
    # the prefix cache and run the same programs on the same inputs.
    again = [dict(reqs[0], request_id=f"{reqs[0]['request_id']}-again{i}")
             for i in range(2)]
    rep1, rep2 = (_tokens(server.completions(r)) for r in again)
    end = server.engine_stats()
    kinds = sorted({r.get("kind") for r in server.flight_records()})
    saved = end["prefix_tokens_saved"] - mid["prefix_tokens_saved"]
    if rep1 != rep2:
        raise AssertionError(f"same seeded request, different tokens: "
                             f"{rep1} != {rep2}")
    if end["prefix_hits"] <= mid["prefix_hits"] or saved <= 0:
        raise AssertionError(f"repeated prompt missed the prefix cache: "
                             f"{mid} -> {end}")
    if "mixed" not in kinds:
        raise AssertionError(f"the mixed tick never ran: kinds {kinds}")
    if end["step_compiles"] != warm["step_compiles"]:
        raise AssertionError(
            f"compiles after warm-up: {warm['step_compiles']} -> "
            f"{end['step_compiles']}")
    result = {
        "attention_impl": server.engine.runner.attention_impl,
        "tick_kinds": kinds,
        "requests": len(reqs) + 2, "max_tokens": max_tokens,
        "prompt_lens": list(prompt_lens), "batch_s": round(batch_s, 3),
        "prefix_tokens_saved_by_repeat": saved,
        "repeat_equals_repeat": True,
        "repeat_equals_batched": rep1 == _tokens(responses[0]),
        "warmup_shapes": warm["warmup_shapes"], "warmup_s": warm["warmup_s"],
        "step_compiles_after_warmup": end["step_compiles"]
        - warm["step_compiles"],
        "param_devices": end["devices"], "memory": device_memory(),
    }
    return result, {r["id"]: _tokens(r) for r in responses}


def train_phase(model_config, *, mesh_config, seed: int, batch: int,
                seq: int, steps: int, lr: float, devices=None):
    """`steps` optimizer steps of build_train_step + llama.loss_fn on one
    repeated seeded batch. Returns losses, grad norms and what the compiled
    step holds."""
    import jax
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel.fsdp import build_train_step
    from ray_tpu.parallel.mesh import build_mesh

    devices = devices if devices is not None else jax.devices()
    mesh = build_mesh(mesh_config, devices=devices[:mesh_config.num_devices])
    init_fn, make_step = build_train_step(
        lambda p, b: llama.loss_fn(p, b, model_config), optax.adamw(lr),
        mesh, llama.param_logical_axes(model_config),
        {"tokens": ("batch", None)})
    params = llama.init_params(model_config, jax.random.key(seed))
    state, shardings = init_fn(params)
    del params  # the state holds its own copy; the step donates that one
    tokens = jax.random.randint(jax.random.key(seed + 1), (batch, seq + 1),
                                0, model_config.vocab_size)
    t0 = time.time()
    compiled = make_step(shardings).lower(state, {"tokens": tokens}).compile()
    compile_s = time.time() - t0
    batch = jax.device_put({"tokens": tokens}, compiled.input_shardings[0][1])
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    param_devices = sorted({str(d) for leaf in jax.tree.leaves(
        state["params"]) for d in leaf.devices()})
    losses, grad_norms = [], []
    for _ in range(steps):
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))
        grad_norms.append(float(metrics["grad_norm"]))
    import math

    if not all(math.isfinite(x) for x in losses + grad_norms):
        raise AssertionError(f"not finite: {losses} {grad_norms}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    return {
        "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
        "losses": losses, "grad_norms": grad_norms,
        "compile_s": round(compile_s, 3),
        "pallas_kernels_in_step": text.count(
            'custom_call_target="tpu_custom_call"'),
        "collectives": {op: text.count(f" {op}(") + text.count(f" {op}-start(")
                        for op in ("all-gather", "reduce-scatter",
                                   "all-reduce")},
        "argument_bytes": mem.argument_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "param_devices": param_devices, "memory": device_memory(),
    }


def _session_pids(session_dir: str) -> set:
    """The processes of THIS cluster: what its session started (GCS, raylet)
    and the raylet's children, its workers."""
    from ray_tpu.runtime import node

    started = dict(node.session_pids(session_dir))
    return set(started.values()) | set(node.child_pids(started["raylet"]))


def _alive(pids) -> set:
    return {pid for pid in pids if os.path.exists(f"/proc/{pid}")}


def cluster_phase(model_config, *, seed: int, num_kv_blocks: int,
                  prompt_lens, max_tokens: int):
    """The framework's own entry: ray_tpu.init() with DETECTED resources, one
    replica of build_llm_deployment holding one TPU, two requests through the
    handle, shutdown, nothing left running and no arena left in /dev/shm.
    The caller never touches JAX."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm.serving import build_llm_deployment

    os.environ.setdefault(
        "RAY_TPU_TMPDIR", os.path.join(tempfile.gettempdir(), "ray_tpu"))
    ray_tpu.init()
    session_dir = ray_tpu.get_runtime_context().session_dir
    store_path = ray_tpu.nodes()[0]["object_store_path"]
    try:
        resources = ray_tpu.cluster_resources()
        if resources.get("TPU") != 1.0:
            raise AssertionError(f"node does not advertise TPU: 1: "
                                 f"{resources}")
        cfg = llm_config(model_config, seed=seed,
                         num_kv_blocks=num_kv_blocks,
                         num_tpus_per_replica=1)
        t0 = time.time()
        handle = serve.run(build_llm_deployment(cfg, name="smoke-llm"))
        deploy_s = time.time() - t0
        reqs = make_requests(model_config.vocab_size, prompt_lens,
                             max_tokens, seed)
        pending = [handle.remote(r) for r in reqs]
        responses = [p.result(timeout_s=300) for p in pending]
        for r in responses:
            _check_tokens(_tokens(r), max_tokens, model_config.vocab_size)
        stats = handle.options("engine_stats").remote().result(timeout_s=60)
        held = ray_tpu.cluster_resources()["TPU"] \
            - ray_tpu.available_resources().get("TPU", 0.0)
        serve.shutdown()
    finally:
        started = _session_pids(session_dir)
        ray_tpu.shutdown()
    deadline = time.time() + 30
    while (left := _alive(started)) and time.time() < deadline:
        time.sleep(0.5)
    if left:
        raise AssertionError(f"ray_tpu processes left running: {left}")
    if os.path.exists(store_path):
        raise AssertionError(f"the raylet's arena was left: {store_path}")
    if held != 1.0:
        raise AssertionError(f"the replica held {held} TPU, not 1")
    return {
        "cluster_resources": {k: v for k, v in resources.items()
                              if k.startswith(("TPU", "CPU"))},
        "tpus_held_by_replica": held,
        "replica_devices": stats["devices"],
        "deploy_s": round(deploy_s, 3),
        "warmup_shapes": stats["warmup_shapes"],
        "warmup_s": stats["warmup_s"],
        "step_compiles": stats["step_compiles"],
        "requests": len(reqs),
        "processes_left": 0,
    }, {r["id"]: _tokens(r) for r in responses}


# --------------------------------------------------------------------------
# Chip-only checks and the children's entry points.
# --------------------------------------------------------------------------

def require_tpu(count: int) -> dict:
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or device["count"] != count:
        raise SystemExit(f"chip_smoke: need {count} TPU chip(s), JAX found "
                         f"{device}")
    return device


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, dtype=np.float32)
    want = np.asarray(want, dtype=np.float32)
    if not np.isfinite(got).all():
        raise AssertionError("kernel output is not finite")
    return float(np.abs(got - want).max() / np.abs(want).max())


def kernel_checks(model_config, *, seed: int, num_kv_blocks: int) -> dict:
    """The K/V paged kernel through both entry points and the flash forward,
    compiled by Mosaic (interpret=False), against their jnp references at the
    shapes the server runs, the pools as they lie on the device (two layers,
    the second read): a decode-only tick; a full mixed tick (decode rows and
    one prefill chunk, several query blocks, in the chunk+batch token
    bucket); the rectangular entry's decode step and prefill chunk (the
    benchmark's logits check runs those); and a prompt-length causal
    forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import attention as att
    from ray_tpu.ops import paged_attention as pa

    served = llm_config(model_config, seed=seed, num_kv_blocks=num_kv_blocks)
    block_size, max_batch, prefill_chunk = (
        served.block_size, served.max_batch_size, served.prefill_chunk)
    H, K, hd = (model_config.n_heads, model_config.n_kv_heads,
                model_config.head_dim)
    S, max_pages = max_batch, model_config.max_seq // block_size
    rng = np.random.RandomState(seed)
    keys = jax.random.split(jax.random.key(seed), 8)

    def normal(key, shape):
        return jax.random.normal(key, shape, dtype=jnp.bfloat16)

    layer = jnp.int32(1)
    k_pool = normal(keys[0], (2, num_kv_blocks, block_size, K, hd))
    v_pool = normal(keys[1], (2, num_kv_blocks, block_size, K, hd))
    tables = jnp.asarray(rng.permutation(num_kv_blocks)[:S * max_pages]
                         .reshape(S, max_pages), dtype=jnp.int32)
    out = {}

    # Unified ticks: every row decodes one token at a context of several
    # hundred tokens; then rows 0..S-2 decode and row S-1 prefills a chunk
    # (prefill_chunk / q_block query blocks) on top of 512 cached tokens.
    for T, q_lens, last in (
            (max_batch, np.array([1] * S), rng.randint(300, 1000)),
            (prefill_chunk + max_batch,
             np.array([1] * (S - 1) + [prefill_chunk]), 512 + prefill_chunk)):
        kv_lens = np.append(rng.randint(300, 1000, S - 1), last)
        cu = jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)
        q_pos = jnp.asarray(kv_lens - q_lens, jnp.int32)
        args = (normal(keys[2], (T, H, hd)), k_pool, v_pool, layer, tables,
                jnp.asarray(kv_lens, jnp.int32), q_pos, cu)
        out["unified_T%d" % T] = _rel_err(
            jax.jit(lambda *a: pa.ragged_paged_attention_unified(
                *a, interpret=False))(*args),
            jax.jit(pa.ragged_paged_attention_unified_reference)(*args))

    for name, (rows, Bq) in {"decode": (S, 1),
                             "prefill": (1, prefill_chunk)}.items():
        lens = rng.randint(300, 1000, rows).astype(np.int32) + Bq
        args = (normal(keys[3], (rows, Bq, H, hd)), k_pool, v_pool, layer,
                tables[:rows], jnp.asarray(lens), jnp.asarray(lens - Bq))
        out[f"rectangular_{name}"] = _rel_err(
            jax.jit(lambda *a: pa.ragged_paged_attention(
                *a, interpret=False))(*args),
            jax.jit(pa.ragged_paged_attention_reference)(*args))

    out.update(two_width_kernel_checks(rng, keys[7], S, block_size,
                                       prefill_chunk))

    q, k, v = (normal(keys[4], (1, 1024, H, hd)),
               normal(keys[5], (1, 1024, K, hd)),
               normal(keys[6], (1, 1024, K, hd)))
    out["flash_fwd_1024"] = _rel_err(
        jax.jit(lambda *a: att.flash_attention_fwd(*a, interpret=False))(
            q, k, v),
        jax.jit(att.mha_reference)(q, k, v))
    bad = {k: e for k, e in out.items() if not e <= BF16_REL_TOL}
    if bad:
        raise AssertionError(f"kernel != reference beyond {BF16_REL_TOL}: "
                             f"{bad} (all: {out})")
    return out


def _mimo_pools(key, pages: dict, block_size: int, **cut):
    """(config, {name: pool}): MiMo-V2-Flash's K and V pools as its serving
    block declares them (models/mimo_v2_flash.py, `cache_arrays`: row pools,
    a K row laid by `config.k_row`, no lane of padding), normal values."""
    import jax

    from ray_tpu.models.mimo_v2_flash import MimoV2FlashConfig

    config = MimoV2FlashConfig(**cut)
    pools = {}
    for i, a in enumerate(config.serving_block().cache_arrays(pages,
                                                              block_size)):
        kind = 1 if a.group == "window" else 0
        K = config.kv_heads(kind)
        x = jax.random.normal(
            jax.random.fold_in(key, i),
            a.shape[:-1] + (K, a.shape[-1] // K), a.dtype)
        pools[a.name] = (config.k_row(kind).lay(x)
                         if a.name.startswith("k_") else x.reshape(a.shape))
    return config, pools


def two_width_kernel_checks(rng, key, S: int, block_size: int,
                            chunk: int) -> dict:
    """The K/V kernel at models/mimo_v2_flash.py's widths over the pools as
    the model declares them, against the jnp references: 64 query heads, q
    and K 192 wide, laid as `config.k_row` lays them (a K row split with no
    padding, q 256 lanes wide), V 128; a full layer's 4 kv heads under a
    plain table, and the WINDOW form (8 kv heads, window 128, a sink logit a
    head) under a ring table of 18 pages a row. A mixed tick (decode rows
    and one chunk) each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import paged_attention as pa

    ring, pages = 18, 2048
    keys = jax.random.split(key, 4)
    c, pools = _mimo_pools(keys[0], {"all": pages, "window": pages},
                           block_size)
    H, hd = c.num_attention_heads, c.head_dim
    q_lens = np.array([1] * (S - 1) + [chunk])
    kv_lens = np.append(rng.randint(300, 1000, S - 1), 512 + chunk)
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]), jnp.int32)
    scalars = (jnp.asarray(kv_lens, jnp.int32),
               jnp.asarray(kv_lens - q_lens, jnp.int32), cu)
    q = jax.random.normal(keys[1], (int(q_lens.sum()), H, hd), c.dtype)
    out = {}
    for name, group, kind, width, kw in (
            ("two_widths_full", "all", 0, -(-1024 // block_size), {}),
            ("two_widths_window", "window", 1, ring,
             {"window": c.sliding_window,
              "sink": jax.random.normal(keys[2], (H,), jnp.float32)})):
        tables = jnp.asarray(rng.permutation(pages)[:S * width]
                             .reshape(S, width), dtype=jnp.int32)
        args = (c.k_row(kind).queries(q), pools[f"k_{group}"],
                pools[f"v_{group}"], jnp.int32(1), tables) + scalars
        kw = dict(kw, scale=hd ** -0.5, kv_heads=c.kv_heads(kind))
        out[name] = _rel_err(
            jax.jit(lambda *a, kw=kw: pa.ragged_paged_attention_unified(
                *a, interpret=False, **kw))(*args),
            jax.jit(lambda *a, kw=kw:
                    pa.ragged_paged_attention_unified_reference(
                        *a, **kw))(*args))
    return out


def mimo_kernel_timing(*, seed: int, rows: int = 32, context: int = 33900,
                       piece: int = 128, pages: int = 49152,
                       block_size: int = 16, calls: int = 4, **cut) -> dict:
    """Time MiMo-V2-Flash's K/V layers ALONE at the shapes the cell
    `mimov2flash-longdoc-closed32` gives them, the pools as the model
    declares them and passed as arguments, q moving with the layer (or XLA
    hoists the kernel out of the loop; scaled, so that the zeros it rides
    with stay zeros): `rows` decode rows over ~`context`
    tokens through a FULL layer; the same beside one `piece`-token slice at
    the end of such a context; the decode rows through a WINDOW layer. ->
    {"full_decode" | "full_decode+slice" | "window_decode": {"ms" a layer,
    "gb_s_useful", "share_useful", "gb_s_as_rows_lie", "share_as_rows_lie" of
    819 GB/s}}; "full_decode+slice" also "slice_ms", what the slice added."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import paged_attention as pa

    rng = np.random.RandomState(seed)
    ring = -(-(128 + piece) // block_size) + 2
    c, pools = _mimo_pools(jax.random.key(seed),
                           {"all": pages, "window": 2 * (rows + 1) * ring},
                           block_size, **cut)
    H, hd = c.num_attention_heads, c.head_dim
    ctx = rng.randint(context - 500, context + 500, rows)
    sink = jax.random.normal(jax.random.key(seed + 1), (H,), jnp.float32)

    def timed(group, kind, q_lens, kv_lens, width, **kw):
        S, layers = len(q_lens), c.layers_of(kind)
        tables = jnp.asarray(rng.randint(
            0, pools[f"k_{group}"].shape[1], (S, width)), jnp.int32)
        q = c.k_row(kind).queries(jax.random.normal(
            jax.random.key(seed + 2), (int(sum(q_lens)), H, hd), c.dtype))
        scalars = (jnp.asarray(kv_lens, jnp.int32),
                   jnp.asarray(np.asarray(kv_lens) - np.asarray(q_lens),
                               jnp.int32),
                   jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]),
                               jnp.int32))

        @jax.jit
        def loop(q, k_pool, v_pool, tables, *scalars):
            def layer(i, total):
                li = i % layers
                return total + jnp.sum(pa.ragged_paged_attention_unified(
                    q * (1 + li.astype(q.dtype) / 16), k_pool, v_pool, li,
                    tables, *scalars, scale=hd ** -0.5,
                    kv_heads=c.kv_heads(kind), **kw).astype(jnp.float32))

            return jax.lax.fori_loop(0, calls * layers, layer,
                                     jnp.float32(0))

        args = (q, pools[f"k_{group}"], pools[f"v_{group}"], tables,
                *scalars)
        loop(*args).block_until_ready()                     # compiles
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            loop(*args).block_until_ready()
            best = min(best, time.time() - t0)
        return best / (calls * layers) * 1e3

    def cell(ms, tokens, kind):
        K = c.kv_heads(kind)
        out = {"ms": round(ms, 4)}
        for name, lanes in (("useful", K * hd),
                            ("as_rows_lie", c.k_row(kind).lanes)):
            gb_s = tokens * (lanes + K * c.v_head_dim) * 2 / ms / 1e6
            out[f"gb_s_{name}"] = round(gb_s, 3)
            out[f"share_{name}"] = round(gb_s / 819.0, 4)
        return out

    full_width = -(-(context + 500 + piece) // block_size)
    out = {"full_decode": cell(
        timed("all", 0, [1] * rows, ctx, full_width), float(ctx.sum()), 0)}
    # The slice's block j walks the context up to its own last token: count
    # what a block of `q_block` tokens reads, as the kernel cuts it.
    blocks = -(-piece // c.serving_block().q_block)
    both = timed("all", 0, [1] * rows + [piece],
                 list(ctx) + [context + piece], full_width)
    out["full_decode+slice"] = dict(
        cell(both, float(ctx.sum()) + blocks * (context + piece / 2), 0),
        slice_ms=round(both - out["full_decode"]["ms"], 4),
        slice_blocks=blocks)
    out["window_decode"] = cell(
        timed("window", 1, [1] * rows, ctx, ring, window=c.sliding_window,
              sink=sink),
        float(rows * c.sliding_window), 1)
    return out


def _layers_alone(call, q, operands, layers: int, calls: int):
    """-> run(): `calls` passes over `layers` layers of `call(q, li,
    *operands)` in one jitted loop, q moving with the layer (or XLA hoists
    the kernel out; scaled, so that zeros stay zeros), the operands passed as
    arguments (closed over, a jit captures the pools as constants); run()
    waits for the result."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loop(q, *operands):
        def layer(i, total):
            li = i % layers
            return total + jnp.sum(call(
                q * (1 + li.astype(q.dtype) / 16), li,
                *operands).astype(jnp.float32))

        return jax.lax.fori_loop(0, calls * layers, layer, jnp.float32(0))

    return lambda: loop(q, *operands).block_until_ready()


def table_decode_timing(*, seed: int, rows: int = 32, heads: int = 32,
                        kv_heads: int = 2, head_dim: int = 128,
                        layers: int = 4, pages: int = 24576,
                        block_size: int = 16, block: int = 64,
                        topk: int = 64, context: int = 33900,
                        calls: int = 4, interpret=None) -> dict:
    """Time MiniCPM-SALA's decode stage ALONE at the tick the cell
    `minicpmsala-longdoc-closed32` gives it: `rows` decode rows over
    ~`context` tokens, each kv head keeping `topk` blocks of `block` tokens
    (its own the last), so `block_sparse.block_attend_call` hands `rows x
    kv_heads` walks of `topk x block / block_size` pages a layer to the row
    kernel's table form (one kv head of `kv_heads x head_dim` lanes); the
    pools of the cell's size and passed as arguments, q moving with the
    layer. -> {"ms_a_tick" (the `layers` layers, WITH the wrapper's gathers
    around the kernel), "kernel_ms_a_tick" (the `block_attend_call` events
    alone under the profiler; None off the chip), "page_dmas_a_tick",
    "ns_a_page_dma" and "gb_s_as_rows_lie" (a page's bytes: every kv head's
    lanes) over the kernel's time where there is one}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import block_sparse as bs
    from ray_tpu.ops.paged_attention import _interpret

    rng = np.random.default_rng([seed, 65])
    K, lanes = kv_heads, kv_heads * head_dim
    keys = jax.random.split(jax.random.key(seed), 3)
    k_pool, v_pool = (jax.random.normal(
        key, (layers, pages, block_size, lanes), jnp.bfloat16)
        for key in keys[:2])
    q = jax.random.normal(keys[2], (rows, heads, head_dim), jnp.bfloat16)
    positions = rng.integers(context - 500, context + 500, rows)
    own = positions // block
    blocks = np.stack([np.stack([np.append(np.sort(rng.choice(
        own[s], topk - 1, replace=False)), own[s]) for _ in range(K)])
        for s in range(rows)])
    tables = rng.integers(0, pages, (rows, -(-(context + 500) // block_size)))
    args = (jnp.asarray(tables, jnp.int32),
            jnp.arange(rows, dtype=jnp.int32), jnp.ones((rows,), bool),
            jnp.asarray(positions, jnp.int32), jnp.asarray(blocks, jnp.int32),
            jnp.full((rows, K), topk, jnp.int32))

    run = _layers_alone(
        lambda q, li, k_pool, v_pool, *args: bs.block_attend_call(
            q, k_pool, v_pool, li, *args, kv_heads=K, scale=head_dim ** -0.5,
            block=block, interpret=_interpret(interpret)),
        q, (k_pool, v_pool) + args, layers, calls)
    ms = _best_ms(run, 3) / calls
    kernel = traced_ms(run, "block_attend")
    dmas = 2 * layers * int(np.sum(
        -(-((topk - 1) * block + positions % block + 1) // block_size))) * K
    out = {"ms_a_tick": round(ms, 4), "page_dmas_a_tick": dmas,
           "kernel_ms_a_tick": kernel and round(kernel / calls, 4)}
    spent = out["kernel_ms_a_tick"] or ms
    out["ns_a_page_dma"] = round(spent * 1e6 / dmas, 2)
    out["gb_s_as_rows_lie"] = round(
        dmas * block_size * lanes * 2 / spent / 1e6, 3)
    return out


def kv5d_decode_timing(*, seed: int, rows: int = 28, heads: int = 32,
                       kv_heads: int = 8, head_dim: int = 128,
                       layers: int = 16, pages: int = 3584,
                       block_size: int = 16, context=(200, 1500),
                       calls: int = 4, interpret=None) -> dict:
    """Time the kernel of 5-D pools (`pa._kv_kernel`) ALONE at the decode
    rows of a tick of `mistral7b-chat-closed32`: `rows` rows over contexts
    drawn from `context`, Mistral-7B's heads, the pool of the cell's size
    and passed as arguments, q moving with the layer. Measurement only (no
    cell runs this): whether that kernel's walk, which starts AND waits a
    page a turn of a rolled loop, is bound as the row kernel's was (ROADMAP
    S15 (h)); a scratch copy of the kernel with its products or its DMAs
    left out, swapped in as `pa._kv_kernel`, is timed by the same call. ->
    {"ms" a layer, "kernel_ms" (the `paged_attention` events alone; None off
    the chip), "pages_a_layer", "ns_a_page" and "gb_s" over the
    kernel's time where there is one, "pages_a_step"}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import paged_attention as pa

    rng = np.random.default_rng([seed, 66])
    keys = jax.random.split(jax.random.key(seed), 3)
    k_pool, v_pool = (jax.random.normal(
        key, (layers, pages, block_size, kv_heads, head_dim), jnp.bfloat16)
        for key in keys[:2])
    q = jax.random.normal(keys[2], (rows, heads, head_dim), jnp.bfloat16)
    ctx = rng.integers(*context, rows)
    tables = jnp.asarray(rng.integers(
        0, pages, (rows, -(-context[1] // block_size))), jnp.int32)
    scalars = (jnp.asarray(ctx, jnp.int32), jnp.asarray(ctx - 1, jnp.int32),
               jnp.arange(rows + 1, dtype=jnp.int32))

    run = _layers_alone(
        lambda q, li, k_pool, v_pool, *rest:
            pa.ragged_paged_attention_unified(q, k_pool, v_pool, li, *rest,
                                              interpret=interpret),
        q, (k_pool, v_pool, tables) + scalars, layers, calls)
    ms = _best_ms(run, 3) / (calls * layers)
    kernel = traced_ms(run, "paged_attention")
    walked = int(np.sum(-(-ctx // block_size)))
    out = {"ms": round(ms, 4), "pages_a_layer": walked,
           "kernel_ms": kernel and round(kernel / (calls * layers), 4),
           "pages_a_step": pa.kv_sizes(heads, kv_heads, head_dim, head_dim,
                                       block_size).pages_one}
    spent = out["kernel_ms"] or ms
    out["ns_a_page"] = round(spent * 1e6 / walked, 2)
    out["gb_s"] = round(
        walked * block_size * kv_heads * head_dim * 2 * 2 / spent / 1e6, 3)
    return out


# The latent kernel alone, at the shapes of a tick of the cells that run it
# (128 x 640 is DeepSeek-V2's, 32 heads Kimi-Linear's): heads, layers a call,
# decode rows, their contexts' range, the rows of one prompt slice at the end
# of the shortest such context.
LATENT_SHAPES = {
    "docqa_decode": dict(heads=128, layers=5, rows=31, context=(8300, 8900)),
    "docqa_decode+slice": dict(heads=128, layers=5, rows=31,
                               context=(8300, 8900), piece=128),
    "kimi_decode": dict(heads=32, layers=3, rows=64, context=(1300, 5000)),
}


def latent_kernel_timing(shapes, *, seed: int, width: int = 640,
                         lat: int = 512, pages: int = 16384,
                         block_size: int = 16, calls: int = 4,
                         dtype=None) -> dict:
    """Time `ops.paged_attention.latent_paged_attention_unified` ALONE: for
    every shape a pool of `layers` layers passed as an argument, q moving
    with the layer (or XLA hoists the kernel out of the loop), `calls` passes
    over the layers in one jitted loop, best of three, and once more under
    the profiler. One call on layer 1 is held against
    `latent_paged_attention_unified_reference`, a sequence at a time (the
    reference's rectangle over a whole tick's rows is 23 GB of scores). ->
    {name: {"ms" a pass over the layers (a tick's calls) WITH what the
    wrapper lays around the kernel, "kernel_ms" the
    `paged_attention_latent_call` events alone (None off the chip), "err",
    "gb_s" (the context rows a block walks, once a block, over "kernel_ms" or
    "ms"), "lower_s" (trace and lowering of one call)}}; a
    shape with a slice also "slice_ms", what the slice added to the shape of
    the same name without it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import paged_attention as pa

    dtype = dtype or jnp.bfloat16
    out = {}
    for name, shape in shapes.items():
        H, layers, rows = shape["heads"], shape["layers"], shape["rows"]
        low, high = shape["context"]
        piece = shape.get("piece", 0)
        rng = np.random.RandomState(seed)
        q_lens = np.array([1] * rows + ([piece] if piece else []), np.int32)
        kv_lens = np.append(rng.randint(low, high, rows),
                            [low + piece] if piece else []).astype(np.int32)
        S, T = len(q_lens), int(q_lens.sum())
        keys = jax.random.split(jax.random.key(seed), 2)
        pool = jax.random.normal(
            keys[0], (layers, pages, block_size, width), dtype)
        q = jax.random.normal(keys[1], (T, H, width), dtype)
        tables = jnp.asarray(rng.randint(
            0, pages, (S, -(-(high + piece) // block_size))), jnp.int32)
        cu = np.concatenate([[0], np.cumsum(q_lens)]).astype(np.int32)
        scalars = (jnp.asarray(kv_lens), jnp.asarray(kv_lens - q_lens),
                   jnp.asarray(cu))
        kw = dict(scale=192 ** -0.5, lat=lat)

        one = jax.jit(lambda *a: pa.latent_paged_attention_unified(*a, **kw))
        t0 = time.time()
        one.lower(q, pool, jnp.int32(1), tables, *scalars)
        cell = {"lower_s": round(time.time() - t0, 3)}
        got = one(q, pool, jnp.int32(1), tables, *scalars)
        ref = jax.jit(lambda *a: pa.latent_paged_attention_unified_reference(
            *a, **kw))
        cell["err"] = max(_rel_err(got[cu[s]:cu[s + 1]], ref(
            q[cu[s]:cu[s + 1]], pool, jnp.int32(1), tables[s:s + 1],
            scalars[0][s:s + 1], scalars[1][s:s + 1],
            jnp.asarray([0, q_lens[s]], jnp.int32))) for s in range(S))
        del got

        @jax.jit
        def loop(q, pool, tables, *scalars):
            def layer(i, total):
                li = i % layers
                return total + jnp.sum(pa.latent_paged_attention_unified(
                    q + li.astype(q.dtype) * 1e-3, pool, li, tables,
                    *scalars, **kw).astype(jnp.float32))

            return jax.lax.fori_loop(0, calls * layers, layer,
                                     jnp.float32(0))

        run = lambda: loop(q, pool, tables, *scalars).block_until_ready()
        run()                                               # compiles
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            run()
            best = min(best, time.time() - t0)
        cell["ms"] = round(best / calls * 1e3, 4)
        kernel = traced_ms(run, "paged_attention_latent")
        cell["kernel_ms"] = kernel and round(kernel / calls, 4)
        # A slice's block j walks the context up to its own last token.
        TQ = pa.latent_q_block(H, width)
        walked = float(kv_lens[:rows].sum()) + sum(
            low + min((j + 1) * TQ, piece) for j in range(-(-piece // TQ)))
        cell["gb_s"] = round(
            layers * walked * width * jnp.dtype(dtype).itemsize
            / (cell["kernel_ms"] or cell["ms"]) / 1e6, 1)
        alone = out.get(name.split("+")[0])
        if piece and alone:
            cell["slice_ms"] = round(
                (cell["kernel_ms"] or cell["ms"])
                - (alone["kernel_ms"] or alone["ms"]), 4)
        out[name] = cell
        del pool, q
    return out


# The benchmark's logits check stops at 256 + 8 positions, short of a window
# of 512: this is the same comparison (its formula, its 3e-2) past the window.
LONG_PROMPT, LONG_DECODE, LOGITS_REL_TOL = 1024, 8, 3e-2
LONG_CONTROLS = ("state_not_carried", "tail_not_carried", "memory_after_gate",
                 "no_lambda", "no_window", "bf16_state")


def long_context_check(model_config, *, seed: int, n_prompt: int,
                       n_decode: int, chunk: int, block_size: int,
                       num_blocks: int, attention_impl: str = "auto",
                       controls=LONG_CONTROLS,
                       state_mantissa_bits: int = 23) -> dict:
    """A model with recurrent and window layers (models/phi4flash.py) past
    its window: two seeded prompts of `n_prompt` tokens prefilled through
    `ModelRunner.step` in chunks, then `n_decode` teacher-forced positions,
    the last-position logits against the plain reference's full forward pass
    (max |difference| over max |reference|, the benchmark's formula), and
    against the reference with ONE term dropped, for every control: the sound
    pair must agree and every control must not. -> {"rel_err", "controls"}.
    `state_mantissa_bits` below 23 is a control of the PROGRAM: its state
    group's arrays are rounded to that many bits after every step (7: a
    program that kept its state in bfloat16). A block that routes is held to
    the reference FOLLOWING the program's experts (`runner.last_routing`:
    benchmarks/README.md, "A family that routes")."""
    import importlib

    import jax
    import numpy as np

    from ray_tpu.llm.model_runner import ModelRunner

    module = importlib.import_module(type(model_config).__module__)
    ref = importlib.import_module(type(model_config).__module__
                                  + "_reference")
    params = module.init_params(model_config, jax.random.key(seed))
    runner = ModelRunner(model_config, params, num_blocks=num_blocks,
                         block_size=block_size, chunk_size=chunk,
                         attention_impl=attention_impl, max_batch=2)
    total = n_prompt + n_decode
    tokens = np.random.default_rng([seed, 7]).integers(
        1, model_config.vocab_size, (2, total)).astype(np.int32)
    pages = -(-total // block_size)
    tables = np.zeros((2, runner.max_blocks_per_seq), dtype=np.int32)
    for i in range(2):
        tables[i, :pages] = i * pages + np.arange(pages)
    got, starts, kept = [], [], []
    # Not a cast pair: XLA elides those on a TPU.
    rounded = jax.jit(lambda cache: {
        a.name: (jax.lax.reduce_precision(
            cache[a.name], exponent_bits=8,
            mantissa_bits=state_mantissa_bits)
            if a.group == "state" and np.issubdtype(a.dtype, np.floating)
            else cache[a.name]) for a in runner.cache_arrays},
        donate_argnums=(0,))

    def step(tok, start, bq):
        n = tok.shape[1]
        padded = np.zeros((2, bq), dtype=np.int32)
        padded[:, :n] = tok
        starts.append(start)
        logits = np.asarray(runner.step(
            padded, np.full(2, start, np.int32),
            np.full(2, start + n, np.int32), np.full(2, n, np.int32),
            tables), dtype=np.float32)
        if runner.block.routed_layers:
            kept.append(np.asarray(runner.last_routing)[:, :, :n])
        if state_mantissa_bits < 23:
            runner.cache = rounded(runner.cache)
        return logits

    t0 = time.time()
    for start in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - start)
        logits = step(tokens[:, start:start + n], start, chunk)
    got.append(logits)
    for pos in range(n_prompt, total):
        got.append(step(tokens[:, pos:pos + 1], pos, 1))
    got = np.stack(got[:-1], axis=1)
    t1 = time.time()
    positions = list(range(n_prompt - 1, total - 1))
    sizes = model_config.reference_sizes()

    routed = (np.concatenate(kept, axis=2),) if kept else ()

    def compare(fault=None):
        want, scores = ref.logits_at(params, tokens, positions, sizes,
                                     *routed, fault)
        want = np.asarray(want)
        return float(np.abs(got - want).max() / np.abs(want).max()), scores

    if not np.isfinite(got).all():
        raise AssertionError("logits are not finite")
    sound, scores = compare()
    out = {"rel_err": sound,
           **(one_group_shortfall(scores, routed[0]) if routed else {}),
           "positions": total, "program_s": round(t1 - t0, 3),
           "attention_impl": runner.attention_impl, "controls": {
               name: compare((name, starts[:-1]) if name.endswith("_carried")
                             else name)[0] for name in controls}}
    out["reference_s"] = round(time.time() - t1, 3)
    return out


def one_group_shortfall(scores, kept) -> dict:
    """A routed block's choices against the reference's own at that point,
    for a router with ONE group: `scores` (routed layers, b, s, experts) the
    reference's selection scores, `kept` (routed layers, b, s, top_k) the
    program's experts. A token-layer whose set differs falls short by 1 - its
    worst kept score over the reference's k-th (benchmarks/routing.py's
    `shortfall` at one group)."""
    import numpy as np

    scores = np.asarray(scores, np.float64)
    kept = np.asarray(kept)
    k = kept.shape[-1]
    kth = -np.sort(-scores, axis=-1)[..., k - 1]
    worst = np.take_along_axis(scores, kept, axis=-1).min(-1)
    differ = worst < kth
    short = np.where(differ, 1.0 - worst / kth, 0.0)
    return {"routed_choices": int(differ.size),
            "routed_differ": int(differ.sum()),
            "shortfall_max": float(short.max(initial=0.0))}


def _child_long_context(args) -> None:
    """Phi-4-mini-flash at its published widths, 32 layers, the whole
    vocabulary: 1,024 + 8 positions against a window of 512."""
    from ray_tpu.models.phi4flash import Phi4FlashConfig

    device = require_tpu(1)
    result = long_context_check(
        Phi4FlashConfig(max_position_embeddings=2048), seed=args.seed,
        n_prompt=LONG_PROMPT, n_decode=LONG_DECODE, chunk=128, block_size=16,
        num_blocks=256)
    if result["attention_impl"] != "pallas":
        raise AssertionError(f"not the Pallas kernels: {result}")
    passed = [n for n, e in result["controls"].items()
              if e <= LOGITS_REL_TOL]
    emit("long_context", ok=result["rel_err"] <= LOGITS_REL_TOL,
         device=device, tolerance=LOGITS_REL_TOL, controls_that_pass=passed,
         **result)
    if result["rel_err"] > LOGITS_REL_TOL:
        raise SystemExit(f"chip_smoke: the program is not the reference's "
                         f"past the window: {result}")


# The sampling head's filter alone, at the shapes the serving configurations
# gather it to (rows that sample x vocabulary: Phi-4-mini-flash, Mistral-7B,
# DeepSeek-V2's share, MiMo-V2-Flash's share).
SAMPLER_SHAPES = ((16, 200064), (8, 32768), (8, 25600), (8, 19072))
SAMPLER_ASKS = {"neither": (0, 1.0), "top_k": (50, 1.0), "top_p": (0, 0.9),
                "both": (50, 0.9)}


def sampler_filter_timing(shapes, *, seed: int, calls: int = 50,
                          filter_logits=None) -> dict:
    """Time `ModelRunner._filter_logits` (or `filter_logits`, a function of
    its signature: a sweep's other form, the sort it replaced) alone: for
    every shape and every mix of what the rows ask for, `calls` calls in one
    jitted loop whose input moves with the call (or XLA hoists the filter
    out), the whole waited for, best of three; the result's kept entries are
    counted, so nothing is elided. -> {"<rows>x<vocab>": {ask: ms a call,
    ask + "_kept": entries kept a call}}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.model_runner import ModelRunner

    fn = filter_logits or object.__new__(ModelRunner)._filter_logits

    @jax.jit
    def loop(logits, temps, top_ks, top_ps):
        def call(i, kept):
            out = fn(logits + i.astype(jnp.float32) * 1e-7, temps, top_ks,
                     top_ps)
            return kept + jnp.sum(out > -1e29)

        return jax.lax.fori_loop(0, calls, call, jnp.int32(0))

    out = {}
    for rows, vocab in shapes:
        rng = np.random.default_rng([seed, rows, vocab])
        logits = jnp.asarray(rng.normal(size=(rows, vocab)) * 4, jnp.float32)
        temps = jnp.full(rows, 0.8, jnp.float32)
        cell = out[f"{rows}x{vocab}"] = {}
        for ask, (top_k, top_p) in SAMPLER_ASKS.items():
            args = (logits, temps, jnp.full(rows, top_k, jnp.int32),
                    jnp.full(rows, top_p, jnp.float32))
            kept = int(loop(*args))             # compiles; the same shapes
            best = float("inf")
            for _ in range(3):
                t0 = time.time()
                loop(*args).block_until_ready()
                best = min(best, time.time() - t0)
            cell[ask] = round(best / calls * 1e3, 4)
            cell[ask + "_kept"] = kept // calls
    return out


def _child_sampler_filter(args) -> None:
    """Not one of `main`'s phases: `--phase sampler_filter` alone."""
    device = require_tpu(1)
    emit("sampler_filter", ok=True, device=device, unit="ms a call",
         **sampler_filter_timing(SAMPLER_SHAPES, seed=args.seed))


# The power-retention kernel alone, at the shapes of a tick of the cell
# `brumby14b-longgen-closed16`: (decode rows, rows of one prompt slice).
RETENTION_SHAPES = ((16, 0), (16, 128))
RETENTION_CONTROLS = ("state_not_carried", "no_gate", "no_qk_norm")


def power_retention_timing(shapes, *, seed: int, heads: int = 40,
                           kv_heads: int = 8, head_dim: int = 128,
                           layers: int = 6, calls: int = 5,
                           impl: str = "pallas", run_rows: int = 16) -> dict:
    """Time `ops.power_retention.power_retention` alone: for every shape
    (decode rows, rows of one slice) a state of `layers` layers and a slot a
    sequence, every slot holding a hundred tokens' state and a buffer half
    full; one call against the `lax.scan` oracle on layer 0 (max |difference|
    over max |oracle| of the outputs and, the buffer folded in on both sides,
    of the slots written), then `calls` passes over the layers in one jitted
    loop whose q moves with the layer (or XLA hoists the call out) and whose
    fills stay as they came (no call of the loop folds), the whole waited
    for, best of three. -> {"<rows>+<slice>": {"ms" a call, "gb_s" (a
    sequence's S and z read once, the rows in and out: the benchmark family's
    `retention_bytes`, one layer, over the call), "us_step" (a call over its
    (sequence, kv head) steps), "o_err", "state_err"}; "<rows>+0.fold":
    {"ms", "us_step"} of the decode rows' shape with every buffer one row
    short of full, so that every sequence of every call folds; "run": 2 FOLD
    + 3 consecutive steps of `run_rows` decode rows, kernel and oracle each
    from its own state: {"steps", "folds" (a sequence's), "o_err" (the worst
    step's), "state_err" (after the last, the buffer folded in)}}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import power_retention as pr

    scale, eps = head_dim ** -0.5, 1e-6
    fold = pr.fold_rows(head_dim)
    rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))

    def unit(key, shape):           # rows of mean square 1, as after a norm
        x = jax.random.normal(key, shape, jnp.float32)
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))

    def drawn(key, R):
        """R rows of q, k, v and the gates' log."""
        keys = jax.random.split(key, 4)
        return (unit(keys[0], (R, heads, head_dim)),
                unit(keys[1], (R, kv_heads, head_dim)),
                jax.random.normal(keys[2], (R, kv_heads, head_dim),
                                  jnp.float32),
                jnp.log(1.0 - 10.0 ** -jax.random.uniform(
                    keys[3], (R, kv_heads), jnp.float32, 1.0, 3.0)))

    def filled(key, layers, seqs, fill):
        """Every slot: a hundred tokens' state (S = sum phi(k) v^T) and
        `fill` rows in its buffer."""
        keys = jax.random.split(key, 4)
        fill_k = pr.phi(unit(keys[0], (100, kv_heads, head_dim))
                        * scale ** 0.5)                     # (100, K, C, hd)
        fill_v = jax.random.normal(keys[1], (100, kv_heads, head_dim))
        sizes = (layers, seqs, kv_heads, head_dim)
        c = jnp.cumsum(jnp.full((head_dim,), -0.01, jnp.float32))
        gates = jnp.zeros((8, head_dim), jnp.float32).at[0].set(c).at[1].set(
            c[max(fill, 1) - 1])
        one = jnp.concatenate([
            unit(keys[2], (kv_heads, fold, head_dim)) * scale ** 0.5,
            jax.random.normal(keys[3], (kv_heads, fold, head_dim)),
            jnp.broadcast_to(gates, (kv_heads, 8, head_dim))], axis=1)
        return (jnp.broadcast_to(
            jnp.einsum("tkrl,tkc->krcl", fill_k, fill_v),
            pr.state_shape(*sizes)) + 0.0,
            jnp.broadcast_to(jnp.moveaxis(fill_k.sum(0), 0, 1),
                             pr.norm_shape(*sizes)) + 0.0,
            jnp.broadcast_to(one, pr.buffer_shape(*sizes)) + 0.0,
            jnp.full(pr.fill_shape(layers, seqs), fill, jnp.int32))

    def slots_of(cache, seqs):
        """Layer 0's slots as the recurrence has them: the buffer folded."""
        return pr.folded(*(a[0, :seqs] for a in cache))

    kw = dict(scale=scale, eps=eps)
    out = {}
    for rows, piece in shapes:
        seqs = rows + (1 if piece else 0)
        R = -(-(rows + piece) // 8) * 8
        keys = jax.random.split(jax.random.key(seed + rows + piece), 2)
        q, k, v, log_g = drawn(keys[0], R)
        lens = np.array([1] * rows + ([piece] if piece else []), np.int32)
        starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
        args = (np.arange(seqs, dtype=np.int32), starts, lens,
                np.zeros(seqs, bool))
        once = lambda how: jax.jit(lambda *a: pr.power_retention(
            *a, 0, *args, impl=how, **kw))(
                q, k, v, log_g, *filled(keys[1], 1, seqs, fold // 2))
        want, got = once("reference"), once(impl)
        cell = out[f"{rows}+{piece}"] = {
            "o_err": rel(got[0], want[0]),
            "state_err": max(map(rel, slots_of(got[1:], seqs),
                                 slots_of(want[1:], seqs)))}
        del want, got

        @functools.partial(jax.jit, donate_argnums=(4, 5, 6))
        def loop(q, k, v, log_g, state, norm, buf, fill):
            def layer(i, carry):
                total, state, norm, buf = carry
                o, state, norm, buf, _ = pr.power_retention(
                    q + (i % layers).astype(jnp.float32) * 1e-3, k, v, log_g,
                    state, norm, buf, fill, i % layers, *args, impl=impl,
                    **kw)
                return total + jnp.sum(o), state, norm, buf

            return jax.lax.fori_loop(0, calls * layers, layer,
                                     (jnp.float32(0), state, norm, buf))

        def timed(fill):
            *cache, fills = filled(keys[1], layers, seqs, fill)
            _, *cache = loop(q, k, v, log_g, *cache, fills)     # compiles
            best = float("inf")
            for _ in range(3):
                t0 = time.time()
                total, *cache = loop(q, k, v, log_g, *cache, fills)
                total.block_until_ready()
                best = min(best, time.time() - t0)
            ms = best / (calls * layers) * 1e3
            return {"ms": round(ms, 4),
                    "us_step": round(ms * 1e3 / (seqs * kv_heads), 3)}

        cell.update(timed(fold // 2))
        # What the benchmark's family counts (`retention_bytes`, a layer):
        # S and z of the distinct features read once, float32; a row's q, o,
        # k, v at two bytes and its gates at four.
        moved = (seqs * 4 * kv_heads * (head_dim * (head_dim + 1) // 2)
                 * (head_dim + 1)
                 + (rows + piece) * (2 * head_dim * (2 * heads + 2 * kv_heads)
                                     + 4 * kv_heads))
        cell["gb_s"] = round(moved / cell["ms"] / 1e6, 1)
        if not piece:
            out[f"{rows}+0.fold"] = timed(fold - 1)
    # Decode rows on and on, across two folds: kernel and oracle each carry
    # their own state, buffer and fill.
    steps = 2 * fold + 3
    args = (np.arange(run_rows, dtype=np.int32),
            np.arange(run_rows, dtype=np.int32), np.ones(run_rows, np.int32),
            np.zeros(run_rows, bool))
    key = jax.random.key(seed + 1)
    caches = {how: filled(key, 1, run_rows, 0) for how in ("reference", impl)}
    step = {how: jax.jit(lambda *a, how=how: pr.power_retention(
        *a, 0, *args, impl=how, **kw), donate_argnums=(4, 5, 6, 7))
        for how in caches}
    worst = 0.0
    for t in range(steps):
        x = drawn(jax.random.fold_in(key, t), run_rows)
        outs = {}
        for how in caches:
            outs[how], *caches[how] = step[how](*x, *caches[how])
        worst = max(worst, rel(outs[impl], outs["reference"]))
    out["run"] = {
        "steps": steps, "o_err": worst, "folds": steps // fold,
        "fill": int(caches[impl][3][0, 0]),
        "state_err": max(map(rel, slots_of(caches[impl], run_rows),
                             slots_of(caches["reference"], run_rows)))}
    return out


def _child_power_retention(args) -> None:
    """Not one of `main`'s phases: `--phase power_retention` alone."""
    import jax

    from ray_tpu.ops import power_retention as pr

    device = require_tpu(1)
    # `--sweep 32x4x5,128x2x13`: the same at other FOLD x WALK_TILES x UNROLL,
    # after the module's own (how the constants were chosen: PERF.md section
    # 5).
    tried = [(pr.FOLD, pr.WALK_TILES, pr.UNROLL)] + [
        tuple(int(n) for n in one.split("x"))
        for one in filter(None, args.sweep.split(","))]
    for pr.FOLD, pr.WALK_TILES, pr.UNROLL in tried:
        jax.clear_caches()
        result = power_retention_timing(RETENTION_SHAPES, seed=args.seed)
        ok = all(c["o_err"] < 1e-3 and c["state_err"] < 1e-4
                 for c in result.values() if "o_err" in c)
        emit("power_retention", ok=ok, device=device, fold=pr.FOLD,
             walk_tiles=pr.WALK_TILES, unroll=pr.UNROLL,
             unit="ms a call, a layer; us a (sequence, kv head) step",
             **result)
        if not ok:
            raise SystemExit(f"chip_smoke: the kernel is not the oracle's: "
                             f"{result}")


def _child_retention_check(args) -> None:
    """Not one of `main`'s phases: Brumby at its published widths, 6 layers,
    the whole vocabulary: the benchmark's check (256 + 8 positions) with the
    reference's controls, and once more with the PROGRAM's state rounded to
    bfloat16 after every step."""
    from ray_tpu.models.brumby import BrumbyConfig

    device = require_tpu(1)
    kw = dict(seed=args.seed, n_prompt=256, n_decode=8, chunk=128,
              block_size=16, num_blocks=64)
    config = BrumbyConfig(num_hidden_layers=6, max_position_embeddings=2048)
    sound = long_context_check(config, controls=RETENTION_CONTROLS, **kw)
    # The first run's runner and its jitted methods hold each other (and
    # 8 GB): collected here, not when the second run is out of memory.
    gc.collect()
    bf16 = long_context_check(config, controls=(), state_mantissa_bits=7,
                              **kw)
    emit("retention_check", ok=sound["rel_err"] <= LOGITS_REL_TOL,
         device=device, tolerance=LOGITS_REL_TOL,
         bf16_state_rel_err=bf16["rel_err"], **sound)


# The KDA kernel alone, at the shapes of a tick of the cell
# `kimilinear-longout-closed64`: (decode rows, rows of one prompt slice).
KDA_SHAPES = ((64, 0), (64, 128), (0, 128))


def traced_ms(run, holds: str):
    """`run()` once under the profiler: the ms the first chip spent in the
    operations whose name holds `holds` (its `XLA Ops` line, as
    benchmarks/trace_reduce.py reads it), or None where the trace has no
    such event (off the chip there is no device plane)."""
    import glob

    import jax
    from jax.profiler import ProfileData

    ns = 0
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            run()
        for path in glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                              recursive=True):
            data = ProfileData.from_file(path)    # held while its planes are
            for plane in data.planes:
                if plane.name != "/device:TPU:0":
                    continue
                ns += sum(e.duration_ns for line in plane.lines
                          if line.name == "XLA Ops" for e in line.events
                          if holds in e.name.split(" = ", 1)[0])
    return ns * 1e-6 if ns else None


def state_kernel_timing(shapes, *, seed: int, inputs, state_shape, call,
                        event: str, err_key: str, layers: int, calls: int,
                        beside=lambda layers, seqs: (), settled=None,
                        check: bool = True):
    """Time a recurrent layer's kernel over ragged rows alone (ops/kda.py,
    ops/ssd.py): for every shape (decode rows, rows of one slice) a state of
    `layers` layers and a slot a sequence; one call against the `lax.scan`
    oracle on layer 0 (max |difference| over max |oracle| of the outputs and
    of the slots written), then `calls` passes over the layers in one jitted
    loop whose first operand moves with the layer (or XLA hoists the call
    out), the whole waited for, best of three, and once more under the
    profiler. `inputs(keys, R)` -> the operands before the state;
    `state_shape(layers, seqs)`; `beside(layers, seqs)` the arrays a slot
    holds beside its state (rows buffered and their count: zeros; every
    timed pass starts from them, so `calls` says how many rows join and how
    many folds a sequence pays); `call(how)(*operands, state, *beside, layer,
    slots, starts, lens, zero)` -> (outputs, state, *beside) by the oracle
    (`how` "reference") or the kernel under test; `settled(state, *beside)`
    the state the recurrence holds (the state itself where nothing lies
    beside it). `check` False: no oracle (a timing of a kernel made wrong on
    purpose). -> {"<rows>+<slice>": (seqs,
    {err_key, "state_err", "ms" a call WITH what the wrapper lays around the
    kernel, "kernel_ms" a call of the `event` events alone (None off the
    chip)})}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    out = {}
    for rows, piece in shapes:
        seqs = rows + (1 if piece else 0)
        R = -(-(rows + piece) // 8) * 8
        keys = jax.random.split(jax.random.key(seed + rows + piece), 6)
        x = inputs(keys, R)
        lens = np.array([1] * rows + ([piece] if piece else []), np.int32)
        starts = np.concatenate([[0], np.cumsum(lens)[:-1]]).astype(np.int32)
        args = (np.arange(seqs, dtype=np.int32), starts, lens,
                np.zeros(seqs, bool))
        state = lambda n: jax.random.normal(keys[5], state_shape(n, seqs),
                                            jnp.float32)
        fresh = lambda n: tuple(jnp.zeros(shape, dtype)
                                for shape, dtype in beside(n, seqs))
        cell = {}
        if check:
            once = lambda how: jax.jit(
                lambda *a: call(how)(*a, 0, *args))(*x, state(1), *fresh(1))
            want, got = once("reference"), once("kernel")
            whole = settled or (lambda state, *_: state)
            cell = {err_key: rel(got[0], want[0]),
                    "state_err": rel(whole(*got[1:])[0, :seqs],
                                     whole(*want[1:])[0, :seqs])}
            del want, got
        out[f"{rows}+{piece}"] = (seqs, cell)

        @functools.partial(jax.jit, donate_argnames=("state",))
        def loop(first, *rest, state, others):
            def layer(i, carry):
                total, held = carry
                o, *held = call("kernel")(
                    first + (i % layers).astype(jnp.float32) * 1e-3, *rest,
                    *held, i % layers, *args)
                return total + jnp.sum(o), tuple(held)

            total, held = jax.lax.fori_loop(
                0, calls * layers, layer,
                (jnp.float32(0), (state,) + others))
            return total, held[0]

        run = lambda held: loop(*x, state=held, others=fresh(layers))
        _, held = run(state(layers))                        # compiles
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            total, held = run(held)
            total.block_until_ready()
            best = min(best, time.time() - t0)
        cell["ms"] = round(best / (calls * layers) * 1e3, 4)
        kernel = traced_ms(lambda: run(held)[0].block_until_ready(), event)
        del held
        cell["kernel_ms"] = kernel and round(kernel / (calls * layers), 4)
    return out


def kda_rows(keys, R: int, heads: int, head_dim: int):
    """q (scaled), k (unit), v, the gates' logs (10^-u a channel, u uniform
    in [1, 3]: gates of 0.9 .. 0.999, as the model draws them) and beta in
    (0.1, 0.9) for R rows, from five keys."""
    import jax
    import jax.numpy as jnp

    def unit(key):
        x = jax.random.normal(key, (R, heads, head_dim), jnp.float32)
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True))

    return (unit(keys[0]) * head_dim ** -0.5, unit(keys[1]),
            jax.random.normal(keys[2], (R, heads, head_dim), jnp.float32),
            -10.0 ** -jax.random.uniform(keys[3], (R, heads, head_dim),
                                         jnp.float32, 1.0, 3.0),
            jax.random.uniform(keys[4], (R, heads), jnp.float32, 0.1, 0.9))


def kda_timing(shapes, *, seed: int, heads: int = 32, head_dim: int = 128,
               layers: int = 9, calls: int = 16, impl: str = "pallas",
               fold: int = None, check: bool = True, **chunks) -> dict:
    """Time `ops.kda.kda` alone (`state_kernel_timing`), every pass from
    EMPTY buffers of `fold` rows (`kda.FOLD`): `calls` 16 at a fold of 8 is
    two folds a sequence and layer, the cell's one in eight; `calls` under
    the fold is the row that joins, alone. -> {"<rows>+<slice>": {"ms",
    "kernel_ms" (what `kda_kernel_ms.tick` sums), "hbm_share" (the benchmark
    family's `kda_bytes` floor, one layer: a sequence's S read ONCE, the rows
    in and out, over "kernel_ms" or "ms" over 819 GB/s), "o_err",
    "state_err" (of `kda.folded`)}}."""
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    fold = fold or kda.FOLD
    sizes = (heads, head_dim, head_dim)
    timed = state_kernel_timing(
        shapes, seed=seed, event="kda_call", err_key="o_err",
        inputs=lambda keys, R: kda_rows(keys, R, heads, head_dim),
        state_shape=lambda n, seqs: kda.state_shape(n, seqs, *sizes),
        beside=lambda n, seqs: (
            (kda.buffer_shape(n, seqs, *sizes, fold), jnp.float32),
            (kda.fill_shape(n, seqs), jnp.int32)),
        settled=kda.folded, check=check,
        call=lambda how: functools.partial(
            kda.kda, impl="reference" if how == "reference" else impl,
            **chunks),
        layers=layers, calls=calls)
    out = {}
    for shape, (seqs, cell) in timed.items():
        rows = sum(map(int, shape.split("+")))
        floor = (seqs * 4 * heads * head_dim ** 2
                 + rows * 4 * (5 * heads * head_dim + heads))
        out[shape] = dict(cell, hbm_share=round(
            floor / ((cell["kernel_ms"] or cell["ms"]) * 1e-3) / 819e9, 4))
    return out


def kda_fold_check(fold: int, *, seed: int, rows: int = 64, heads: int = 32,
                   head_dim: int = 128, **chunks) -> dict:
    """`fold_check` of `ops.kda.kda`: its rows drawn anew a call."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import kda

    sizes = (heads, head_dim, head_dim)
    return fold_check(
        fold, seed=seed, rows=rows, err_key="o_err",
        op=functools.partial(kda.kda, **chunks), settled=kda.folded,
        draw=lambda keys, t: kda_rows(
            jax.random.split(jax.random.fold_in(keys[2], t), 5), rows, heads,
            head_dim),
        held=lambda key: (
            jax.random.normal(key, kda.state_shape(1, rows, *sizes),
                              jnp.float32),
            jnp.zeros(kda.buffer_shape(1, rows, *sizes, fold), jnp.float32),
            jnp.zeros(kda.fill_shape(1, rows), jnp.int32)))


def kda_decode_sweep(folds, *, seed: int, rows: int = 64, blocks: int = 4,
                     piece: int = 128, **sizes) -> dict:
    """`decode_sweep` of `ops.kda.kda` (a grid step is a sequence's 8 heads:
    4 a row at 32), and at each fold the mix beside one `piece`-row slice."""
    from ray_tpu.ops import kda

    time_it = lambda fold, calls, shape=(rows, 0), **kw: kda_timing(
        (shape,), seed=seed, fold=fold, calls=calls, **sizes,
        **kw)["%d+%d" % shape]
    out = decode_sweep(
        folds, module=kda, time_it=time_it, rows=rows, blocks=blocks,
        check=lambda fold: kda_fold_check(
            fold, seed=seed, rows=rows,
            **{k: v for k, v in sizes.items() if k != "layers"}))
    for fold in folds:
        beside = time_it(fold, 2 * fold, (rows, piece))
        out[str(fold)]["beside_a_slice_ms"] = (beside["kernel_ms"]
                                               or beside["ms"])
    return out


def _child_kda(args) -> None:
    """Not one of `main`'s phases: `--phase kda` alone; `--sweep 8,16` other
    folds for the decode rows."""
    from ray_tpu.ops import kda

    device = require_tpu(1)
    result = kda_timing(KDA_SHAPES, seed=args.seed)
    folds = [int(f) for f in (args.sweep or str(kda.FOLD)).split(",")]
    decode = kda_decode_sweep(folds, seed=args.seed)
    ok = all(c["o_err"] < 1e-4 and c["state_err"] < 1e-4
             for c in (*result.values(), *decode.values()))
    emit("kda", ok=ok, device=device, unit="ms a call, a layer", **result,
         decode_rows_by_fold=decode)
    if not ok:
        raise SystemExit(f"chip_smoke: the kernel is not the oracle's: "
                         f"{result} {decode}")


# Kimi-Linear as the cell `kimilinear-longout-closed64` runs it (benchmarks/
# configs/kimi-linear-48b-l12-e32.json): 12 layers, 32 held experts, an eighth
# of the vocabulary.
KIMI_CUT = dict(num_hidden_layers=12,
                kda_layers=(1, 2, 3, 5, 6, 7, 9, 10, 11),
                full_attn_layers=(4, 8, 12), experts_held=(0, 32),
                vocab_size=20480)
# GLM-5.2 as the cell `glm52-longdoc-closed32` runs it (benchmarks/configs/
# glm-5.2-l8-e8.json): published layers 2-9, 8 held experts, an eighth of the
# vocabulary, a block table of 36,864 positions.
GLM_CUT = dict(num_hidden_layers=8,
               indexer_types=("full", "shared", "shared", "shared") * 2,
               mlp_layer_types=("dense",) + ("sparse",) * 7,
               experts_held=(0, 8), vocab_size=19360,
               max_position_embeddings=36864)
KDA_CONTROLS = ("state_not_carried", "beta_one", "gate_a_head", "no_delta",
                "no_nope_lanes")


def kda_check(config, *, seed: int, long_prompt: int, long_decode: int,
              n_prompt: int = 256, **kw) -> dict:
    """The benchmark's check (256 + 8 positions) with the reference's
    controls, then two longer runs, each twice: the sound program, and the
    PROGRAM's state group rounded to bfloat16 after every step. `long_prompt`
    + 8 positions round it once a slice; `n_prompt` + `long_decode` once a
    TOKEN from the prompt on, which is what a served sequence's state takes."""
    sound = long_context_check(config, seed=seed, n_prompt=n_prompt,
                               n_decode=8, controls=KDA_CONTROLS, **kw)
    out = dict(sound)
    for name, lengths in (
            ("long", dict(n_prompt=long_prompt, n_decode=8)),
            ("decode", dict(n_prompt=n_prompt, n_decode=long_decode))):
        for bits, key in ((23, f"{name}_rel_err"),
                          (7, f"bf16_state_{name}_rel_err")):
            # A run's runner and its jitted methods hold each other (and
            # 11 GB): collected here, not when the next run is out of memory.
            gc.collect()
            run = long_context_check(config, seed=seed, controls=(),
                                     state_mantissa_bits=bits, **lengths,
                                     **kw)
            out[key] = run["rel_err"]
        out[f"{name}_positions"] = run["positions"]
    gc.collect()
    return out


def _child_kda_check(args) -> None:
    """Not one of `main`'s phases: Kimi-Linear at its published widths as the
    cell cuts it."""
    from ray_tpu.models.kimi_linear import KimiLinearConfig

    device = require_tpu(1)
    result = kda_check(
        KimiLinearConfig(max_position_embeddings=2048, **KIMI_CUT),
        seed=args.seed, long_prompt=1024, long_decode=768, chunk=128,
        block_size=16, num_blocks=256)
    passed = [n for n, e in result["controls"].items()
              if e <= LOGITS_REL_TOL]
    emit("kda_check", ok=result["rel_err"] <= LOGITS_REL_TOL, device=device,
         tolerance=LOGITS_REL_TOL, controls_that_pass=passed,
         bf16_state_passes=max(
             result["bf16_state_long_rel_err"],
             result["bf16_state_decode_rel_err"]) <= LOGITS_REL_TOL,
         **result)


# Nemotron-3-Super as the cell `nemotron3super-longout-closed64` runs it
# (benchmarks/configs/nemotron-3-super-l11-e128.json): published layers 0-10,
# 128 held experts, a quarter of the vocabulary.
NEMOTRON_CUT = dict(num_hidden_layers=11,
                    hybrid_override_pattern="MEMEMEM*EME",
                    experts_held=(0, 128), vocab_size=32768)
# The SSD kernel alone, at the shapes of a tick of that cell: (decode rows,
# rows of one prompt slice).
SSD_SHAPES = ((64, 0), (0, 128), (64, 128))
# And where every head is a group of its own, at MiniCPM-SALA's lightning
# widths and the shapes of a tick of `minicpmsala-longdoc-closed32`.
SSD_OWN_KEYS = dict(heads=32, head_dim=128, groups=32, d_state=128, layers=12)
SSD_OWN_SHAPES = ((32, 0), (0, 128), (32, 128))
NEMOTRON_CONTROLS = ("norm_all_lanes", "group_zero", "no_routed_factor",
                     "state_not_carried")
# The benchmark's margin for a routed choice (benchmarks/serve_cell.py,
# `ROUTING_TIE_MARGIN`): a choice the reference would not have made falls
# short of its k-th score by a tie's rounding, not by a tenth.
ROUTING_TIE_MARGIN = 0.1


def ssd_rows(keys, R: int, heads: int, head_dim: int, groups: int,
             d_state: int):
    """x, dt (log-uniform in [1e-3, 1e-1], as the model draws its steps), A
    (-U(1, 16) a head), B and C a GROUP for R rows, from five keys."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    return (jax.random.normal(keys[0], (R, heads, head_dim), jnp.float32),
            jnp.exp(jax.random.uniform(keys[1], (R, heads), jnp.float32,
                                       np.log(1e-3), np.log(1e-1))),
            -jax.random.uniform(keys[2], (heads,), jnp.float32, 1.0, 16.0),
            jax.random.normal(keys[3], (R, groups, d_state), jnp.float32),
            jax.random.normal(keys[4], (R, groups, d_state), jnp.float32))


def ssd_timing(shapes, *, seed: int, heads: int = 128, head_dim: int = 64,
               groups: int = 8, d_state: int = 128, layers: int = 5,
               calls: int = 16, impl: str = "pallas", chunk: int = None,
               fold: int = None, check: bool = True) -> dict:
    """Time `ops.ssd.ssd` alone (`state_kernel_timing`), every pass from
    EMPTY buffers of `fold` rows (`ssd.FOLD`): `calls` 16 at a fold of 8 is
    two folds a sequence and layer, the cell's one in eight; `calls` under
    the fold is the row that joins, alone. -> {"<rows>+<slice>": {"ms",
    "kernel_ms" (what `ssd_kernel_ms.tick` sums), "hbm_share" (the benchmark
    family's `ssd_bytes` floor, one layer: a sequence's S read ONCE, the rows
    in and out, over "kernel_ms" or "ms" over 819 GB/s), "y_err",
    "state_err" (of `ssd.folded`)}}."""
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    fold = fold or ssd.FOLD
    timed = state_kernel_timing(
        shapes, seed=seed, event="ssd_call", err_key="y_err",
        inputs=lambda keys, R: ssd_rows(keys, R, heads, head_dim, groups,
                                        d_state),
        state_shape=lambda n, seqs: ssd.state_shape(n, seqs, heads, head_dim,
                                                    d_state),
        beside=lambda n, seqs: (
            (ssd.buffer_shape(n, seqs, heads, groups, head_dim, d_state,
                              fold), jnp.float32),
            (ssd.fill_shape(n, seqs), jnp.int32)),
        settled=functools.partial(ssd.folded, own=groups == heads),
        check=check,
        call=lambda how: functools.partial(
            ssd.ssd, impl="reference" if how == "reference" else impl,
            chunk=chunk),
        layers=layers, calls=calls)
    out = {}
    for shape, (seqs, cell) in timed.items():
        rows = sum(map(int, shape.split("+")))
        floor = (seqs * 4 * heads * head_dim * d_state
                 + rows * 4 * (2 * heads * head_dim + 2 * groups * d_state
                               + heads))
        out[shape] = dict(cell, hbm_share=round(
            floor / ((cell["kernel_ms"] or cell["ms"]) * 1e-3) / 819e9, 4))
    return out


def fold_check(fold: int, *, seed: int, rows: int, op, held, draw, settled,
               err_key: str) -> dict:
    """`rows` sequences decode 2 x fold + 3 rows each, a call a row, by the
    kernel and by the `lax.scan` oracle (`op(..., impl=)`), each carrying its
    own state, buffer and fill (`held(key)`: one random state, empty
    buffers) over the rows `draw(keys, t)` gives call t: the largest error
    of a call's output (over the oracle's largest) and of `settled` after the
    last call. The rows cross two folds, so the second fold's S0 is the
    first fold's result."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    keys = jax.random.split(jax.random.key(seed + fold), 3)
    args = (np.arange(rows, dtype=np.int32), np.arange(rows, dtype=np.int32),
            np.ones(rows, np.int32), np.zeros(rows, bool))
    carried = {how: held(keys[1]) for how in ("reference", "pallas")}
    step = {how: jax.jit(functools.partial(op, impl=how)) for how in carried}
    rel = lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
    err = 0.0
    for t in range(2 * fold + 3):
        x = draw(keys, t)
        y = {}
        for how in carried:
            y[how], *carried[how] = step[how](*x, *carried[how], 0, *args)
        err = max(err, rel(y["pallas"], y["reference"]))
    return {err_key: err, "state_err": rel(settled(*carried["pallas"]),
                                           settled(*carried["reference"])),
            "fill": int(carried["pallas"][2][0, 0])}


def ssd_fold_check(fold: int, *, seed: int, rows: int = 64, heads: int = 128,
                   head_dim: int = 64, groups: int = 8, d_state: int = 128,
                   **_) -> dict:
    """`fold_check` of `ops.ssd.ssd`: one A for every call's rows."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    def draw(keys, t):
        k = jax.random.split(jax.random.fold_in(keys[2], t), 5)
        return ssd_rows([k[0], k[1], keys[0], k[3], k[4]], rows, heads,
                        head_dim, groups, d_state)

    return fold_check(
        fold, seed=seed, rows=rows, err_key="y_err", op=ssd.ssd, draw=draw,
        settled=functools.partial(ssd.folded, own=groups == heads),
        held=lambda key: (
            jax.random.normal(key, ssd.state_shape(
                1, rows, heads, head_dim, d_state), jnp.float32),
            jnp.zeros(ssd.buffer_shape(1, rows, heads, groups, head_dim,
                                       d_state, fold), jnp.float32),
            jnp.zeros(ssd.fill_shape(1, rows), jnp.int32)))


def decode_sweep(folds, *, module, time_it, check, rows: int,
                 blocks: int) -> dict:
    """`rows` decode rows alone at each fold of `folds`, of a kernel that
    buffers rows beside its state (`module`: ops/ssd.py, ops/kda.py):
    the row that joins (`time_it(fold, calls)` with no pass reaching a
    fold), the cell's mix (two folds a sequence in 2 x fold calls), what a
    fold costs a (sequence, head block) by their difference, a grid step of
    each, the kernel against the oracle over rows that cross two folds
    (`check(fold)`), and the joining row with the STATE'S BLOCK HELD STILL
    (`module.state_block` patched to one block, so that Pallas fetches it
    once: the step without its 512 KB DMA; its outputs are wrong and not
    checked). -> {fold: {...}}, us a grid step of (sequence, head block),
    `blocks` of them a row."""
    import jax

    steps = rows * blocks
    out = {}
    for fold in folds:
        joins = time_it(fold, fold - 1)
        mixed = time_it(fold, 2 * fold)
        block = module.state_block
        module.state_block = lambda s, j, meta, *_: (meta[0], 0, 0, 0, 0)
        jax.clear_caches()
        try:
            still = time_it(fold, fold - 1, check=False)
        finally:
            module.state_block = block
            jax.clear_caches()
        ms = lambda cell: cell["kernel_ms"] or cell["ms"]
        out[str(fold)] = dict(
            check(fold), hbm_share=mixed["hbm_share"], joins_ms=ms(joins),
            mixed_ms=ms(mixed),
            step_us=round(ms(mixed) * 1e3 / steps, 3),
            join_step_us=round(ms(joins) * 1e3 / steps, 3),
            join_step_no_state_dma_us=round(ms(still) * 1e3 / steps, 3),
            fold_us=round((ms(mixed) - ms(joins)) * fold * 1e3 / steps, 3))
    return out


def ssd_decode_sweep(folds, *, seed: int, rows: int = 64, blocks: int = 8,
                     **sizes) -> dict:
    """`decode_sweep` of `ops.ssd.ssd` (a grid step is a sequence's 16 heads:
    8 a row at 128)."""
    from ray_tpu.ops import ssd

    shape, key = ((rows, 0),), f"{rows}+0"
    return decode_sweep(
        folds, module=ssd, rows=rows, blocks=blocks,
        time_it=lambda fold, calls, **kw: ssd_timing(
            shape, seed=seed, fold=fold, calls=calls, **sizes, **kw)[key],
        check=lambda fold: ssd_fold_check(fold, seed=seed, rows=rows,
                                          **sizes))


def _child_ssd(args) -> None:
    """Not one of `main`'s phases: `--phase ssd` alone; `--sweep 8,16,32`
    other folds for the decode rows."""
    from ray_tpu.ops import ssd

    device = require_tpu(1)
    result = ssd_timing(SSD_SHAPES, seed=args.seed)
    folds = [int(f) for f in (args.sweep or str(ssd.FOLD)).split(",")]
    decode = ssd_decode_sweep(folds, seed=args.seed)
    own = ssd_timing(SSD_OWN_SHAPES, seed=args.seed, **SSD_OWN_KEYS)
    own_folds = ssd_fold_check(
        ssd.FOLD, seed=args.seed, rows=32,
        **{k: v for k, v in SSD_OWN_KEYS.items() if k != "layers"})
    # A slice's chunked form sums in another order than the oracle's scan
    # (5e-5 class since PR 52); decode rows across folds are held closer.
    ok = (all(c["y_err"] < 1e-4 and c["state_err"] < 1e-4
              for c in (*result.values(), *own.values()))
          and all(c["y_err"] < 1e-5 and c["state_err"] < 1e-5
                  for c in (*decode.values(), own_folds)))
    emit("ssd", ok=ok, device=device, unit="ms a call, a layer", **result,
         decode_rows_by_fold=decode, own_keys=own,
         own_keys_across_folds=own_folds)
    if not ok:
        raise SystemExit(f"chip_smoke: the kernel is not the oracle's: "
                         f"{result} {decode} {own} {own_folds}")


# The held experts' grouped product alone, at the five routed cells' shapes:
# cell -> (configuration file, the traffic's clients = a decode tick's rows).
# A slice tick adds one 128-token prompt slice.
GROUPED_CELLS = {
    "nemotron3super": ("nemotron-3-super-l11-e128", 64),
    "kimilinear": ("kimi-linear-48b-l12-e32", 64),
    "deepseekv2": ("deepseek-v2-l5-e40", 32),
    "mimov2flash": ("mimo-v2-flash-l7-e16", 32),
    "glm52": ("glm-5.2-l8-e8", 32),
}
# How unevenly the picks fall on a held share: expert e's chance goes as
# exp(GROUPED_SKEW x its place in (0, 1)). At 3 a Nemotron decode tick (341
# held pairs over 128 experts) leaves ~25% of them without a row and the
# busiest with ~4.5 x the mean, as the cell's flight records read (PERF.md
# section 6, PR 52).
GROUPED_SKEW = 3.0


def grouped_shapes(cell: str, sizes: dict = None) -> dict:
    """Of a routed cell's configuration file (or `sizes` in its place): the
    products (K, N) an expert layer makes, the held and published experts,
    the picks a row, the dtype, and the rows of a decode tick and of a tick
    with a slice (in their buckets)."""
    name, clients = GROUPED_CELLS[cell]
    if sizes is None:
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               name + ".json")) as f:
            sizes = json.load(f)["sizes"]
    get = lambda *keys: next(sizes[k] for k in keys if k in sizes)
    d = sizes.get("moe_latent_size") or sizes["hidden_size"]
    ff = sizes["moe_intermediate_size"]
    gated = sizes.get("mlp_hidden_act") != "relu2"
    return {"products": [(d, ff)] * (2 if gated else 1) + [(ff, d)],
            "held": get("num_experts", "n_routed_experts"),
            "published": get("num_experts_published",
                             "n_routed_experts_published"),
            "picks": get("num_experts_per_token", "num_experts_per_tok"),
            "dtype": sizes["torch_dtype"],
            "rows": (clients, clients + 128)}


def grouped_group_sizes(rng, rows: int, shape: dict):
    """Group sizes a tick of `rows` rows would hand the held experts: the
    held share of rows x picks pairs, dealt by GROUPED_SKEW."""
    import numpy as np

    held = shape["held"]
    pairs = round(rows * shape["picks"] * held / shape["published"])
    chance = np.exp(GROUPED_SKEW * rng.permutation(
        (np.arange(held) + 0.5) / held))
    return rng.multinomial(pairs, chance / chance.sum()).astype(np.int32)


def grouped_dot_oracle(a, w, sizes, widest: int):
    """The grouped product a group at a time in float32 at `highest`: the
    oracle both implementations are held to. `widest` >= the largest group."""
    import jax
    import jax.numpy as jnp

    P, N = a.shape[0], w.shape[2]
    starts = jnp.cumsum(sizes) - sizes
    padded = jnp.pad(a, ((0, widest), (0, 0))).astype(jnp.float32)

    def group(g, out):
        rows = jax.lax.dynamic_slice_in_dim(padded, starts[g], widest)
        y = jnp.dot(rows, w[g].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        mine = jnp.arange(widest)[:, None] < sizes[g]
        was = jax.lax.dynamic_slice_in_dim(out, starts[g], widest)
        return jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(mine, y, was), starts[g], 0)

    out = jax.lax.fori_loop(0, sizes.shape[0], group,
                            jnp.zeros((P + widest, N), jnp.float32))
    return out[:P]


def grouped_dot_timing(cells, *, seed: int, tiles=None, calls: int = 20,
                       shapes=grouped_shapes):
    """Time the held experts' grouped product alone, by the kernel
    (`ops/grouped_dot.py`) and by `jax.lax.ragged_dot`, at every product of
    every cell in `cells` and at a decode tick's and a slice tick's pairs. ->
    {"<cell> <K>x<N> <rows>": {"pairs", "met" (experts with a row), "tiles",
    and for each of "kernel" / "ragged": "ms" a call by the host's clock in a
    jitted loop (WITH the plan XLA computes around the kernel), "op_ms" the
    profile's time of the operation alone (what `expert_product_ms.tick`
    sums; None off the chip), "gbps" the met experts' weights over "op_ms"
    (or "ms"), "err" max |difference| over max |oracle|, "behind" the largest
    |value| behind the last group}}. `tiles` (row_tile, k_tile) overrides
    `grouped_sizes` (k_tile 0 keeps its choice) and times the kernel alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import grouped_dot as gd

    out = {}
    for cell in cells:
        shape = shapes(cell)
        dtype = jnp.dtype(shape["dtype"])
        E, k = shape["held"], shape["picks"]
        for K, N in dict.fromkeys(shape["products"]):
            key = jax.random.key(seed + K + N)
            w = jax.random.normal(key, (E, K, N), dtype) * K ** -0.5
            for rows in shape["rows"]:
                rng = np.random.default_rng(seed + rows)
                sizes_np = grouped_group_sizes(rng, rows, shape)
                sizes = jnp.asarray(sizes_np)
                P = rows * k
                a = jax.random.normal(jax.random.fold_in(key, rows), (P, K),
                                      dtype)
                sized = gd.grouped_sizes(P, K, N, dtype.itemsize)
                if tiles:
                    sized = gd.GroupedSizes(tiles[0], tiles[1] or sized.k_tile)
                ways = {"kernel": ("grouped_dot", lambda a, w, s: gd.grouped_dot(
                    a, w, s, tiles=sized))}
                if not tiles:
                    ways["ragged"] = ("ragged-dot", lambda a, w, s: (
                        jax.lax.ragged_dot(
                            a, w, s, preferred_element_type=jnp.float32)))
                want = jax.jit(grouped_dot_oracle, static_argnums=3)(
                    a, w, sizes, int(-(-max(sizes_np.max(), 1) // 8) * 8))
                met = int((sizes_np > 0).sum())
                line = {"pairs": int(sizes_np.sum()), "met": met,
                        "tiles": list(sized)}
                for how, (event, fn) in ways.items():
                    got = jax.jit(fn)(a, w, sizes)
                    # over the groups' rows; what lies behind them apart
                    # (`ragged_dot` leaves those rows as it found them)
                    n = line["pairs"]
                    err = float(jnp.max(jnp.abs(got[:n] - want[:n]))
                                / jnp.max(jnp.abs(want)))
                    tail = float(jnp.max(jnp.abs(jnp.nan_to_num(
                        got[n:], nan=jnp.inf)))) if n < P else 0.0
                    del got

                    @jax.jit
                    def loop(a, w, sizes, fn=fn):
                        def call(i, total):
                            y = fn(a + (i % 2).astype(a.dtype), w, sizes)
                            return total + y[0, 0] + y[-1, -1]
                        return jax.lax.fori_loop(0, calls, call,
                                                 jnp.float32(0))

                    run = lambda: loop(a, w, sizes).block_until_ready()
                    ms = _best_ms(run, 3) / calls
                    op = traced_ms(run, event)
                    op = op and op / calls
                    line[how] = {
                        "ms": round(ms, 4), "op_ms": op and round(op, 4),
                        "gbps": round(met * K * N * dtype.itemsize
                                      / ((op or ms) * 1e-3) / 1e9, 1),
                        "err": err, "behind": tail}
                out[f"{cell} {K}x{N} {rows}"] = line
            del w
    return out


def _child_grouped_dot(args) -> None:
    """Not one of `main`'s phases: `--phase grouped_dot` alone. `--sweep
    16x0,32x0,64x512`: the same at other ROW_TILE x K_TILE (0: the kernel's
    own choice), and `--sweep` may name cells first (`kimilinear:32x0`)."""
    device = require_tpu(1)
    cells, sweeps = list(GROUPED_CELLS), [None]
    if args.sweep:
        head, _, rest = args.sweep.rpartition(":")
        cells = head.split("+") if head else cells
        sweeps += [tuple(map(int, s.split("x"))) for s in rest.split(",") if s]
    ok = True
    for tiles in sweeps:
        result = grouped_dot_timing(cells, seed=args.seed, tiles=tiles)
        # the kernel's error no larger than ragged_dot's own (both round
        # bfloat16 products into float32 sums; the order differs), and zeros
        # behind the last group
        ok &= all(c["kernel"]["err"] <= max(
                      2 * c.get("ragged", c["kernel"])["err"], 1e-5)
                  and c["kernel"]["behind"] == 0 for c in result.values())
        emit("grouped_dot", ok=ok, device=device, sweep=tiles,
             unit="ms a product; GB/s of the met experts' weights", **result)
    if not ok:
        raise SystemExit("chip_smoke: the kernel is not the oracle's")


def _child_nemotron_h_check(args) -> None:
    """Not one of `main`'s phases: Nemotron-3-Super at its published widths
    as the cell cuts it, 2,048 positions in the engine's slices and 12 decode
    positions (the eighth decode row folds the slot's buffer, ops/ssd.py,
    and four rows more read the folded state) through both caches against the
    plain reference following the program's experts, and the reference's
    four controls. The limits are the benchmark's own: `LOGITS_REL_TOL` on
    the logits (bfloat16 weights and rows against float32 at `highest`) and
    `ROUTING_TIE_MARGIN` on a choice the reference would not have made."""
    from ray_tpu.models.nemotron_h import NemotronHConfig

    device = require_tpu(1)
    result = long_context_check(
        NemotronHConfig(max_position_embeddings=4096, **NEMOTRON_CUT),
        seed=args.seed, n_prompt=2048, n_decode=12, chunk=128, block_size=16,
        num_blocks=512, controls=NEMOTRON_CONTROLS)
    if result["attention_impl"] != "pallas":
        raise AssertionError(f"not the Pallas kernels: {result}")
    passed = [n for n, e in result["controls"].items()
              if e <= LOGITS_REL_TOL]
    ok = (result["rel_err"] <= LOGITS_REL_TOL
          and result["shortfall_max"] <= ROUTING_TIE_MARGIN and not passed)
    emit("nemotron_h_check", ok=ok, device=device, tolerance=LOGITS_REL_TOL,
         margin=ROUTING_TIE_MARGIN, controls_that_pass=passed, **result)
    if not ok:
        raise SystemExit(f"chip_smoke: the program is not the reference's, "
                         f"or a control is: {result}")


# MiniCPM-SALA as `minicpmsala-longdoc-closed32` cuts it (benchmarks/configs/
# minicpm-sala-l16.json: published layers 9-24, whole width and vocabulary).
MINICPM_SALA_CUT = dict(
    num_hidden_layers=16, first_published_layer=9,
    mixer_types=tuple("minicpm4" if li in (9, 16, 17, 22) else
                      "lightning-attn" for li in range(9, 25)))
# What `--phase minicpm_sala_check` holds a run to, and why. The logits: the
# benchmark's own tolerance. But the logits of random weights hardly see the
# sparse layers (a softmax over thousands of random values is a fortieth of a
# lightning layer's output), so the sparse layers' ATTENTION OUTPUTS are
# compared too, at the decode rows and EVERY row of the prompt's last eight
# slices (each token of a slice attends under its own mask: the last row
# alone would let a wrong mask of the other 127 pass), max |difference| over
# max |reference|: bfloat16 probabilities and values against float32
# read 1-2% (PERF.md section 6, PR 57), dense attention where the program
# selected reads ~1. And a kept block the reference would not have kept has
# to be a tie: the program's page means and queries are bfloat16, so scores
# within that rounding of the 64th change sides (a third of the (row, kv
# head) sets differ by a block or two), each short of the reference's 64th
# score by under a percent of it; page means that did not follow their pages
# (zeros) keep the lowest-numbered blocks, short by ~10%.
ATTENDED_REL_TOL = 0.1
BLOCK_TIE_MARGIN = 0.03


def minicpm_sala_check(config, *, seed: int, n_prompt: int, n_decode: int,
                       chunk: int, num_blocks: int, watch_slices: int,
                       attention_impl: str = "auto") -> dict:
    """ONE seeded prompt of `n_prompt` tokens served in the engine's slices
    through `ModelRunner.step` and `n_decode` rows decoded through the cache
    (the timed path's own programs), against the plain float32 reference
    computed in blocks, which FOLLOWS the program's kept blocks (its own
    scores, its own everything else) and reports the shortfall of every
    choice it would not have made. -> {"rel_err" of the logits at the last
    prompt row and the decode rows, "attended_err" of the sparse layers'
    attention outputs at EVERY row of the last `watch_slices` slices and the
    decode rows ("attended_rows" of them: a slice's tokens each attend under
    a mask of their own, so one row a slice would let a wrong (token, kv
    head, block) mask of the others pass), "sets",
    "sets_differ", "shortfall_max", and two controls that must not agree:
    "dense_above", both errors of the reference that attends densely whatever
    the context, and "means_left_behind", the same prompt served again by the
    same runner with the page means of its pages wiped before the first
    decode row, as a prefix hit whose means did not follow its pages would
    find them (the reference follows that program too: what tells is the
    shortfall of its choices)}."""
    import importlib

    import jax
    import numpy as np

    from ray_tpu.llm.model_runner import ModelRunner

    module = importlib.import_module(type(config).__module__)
    ref = importlib.import_module(type(config).__module__ + "_reference")
    params = module.init_params(config, jax.random.key(seed))
    runner = ModelRunner(config, params, num_blocks=num_blocks,
                         block_size=config.kernel_stride, chunk_size=chunk,
                         attention_impl=attention_impl, max_batch=2)
    total = n_prompt + n_decode
    tokens = np.random.default_rng([seed, 7]).integers(
        1, config.vocab_size, (1, total)).astype(np.int32)
    tables = np.zeros((1, runner.max_blocks_per_seq), dtype=np.int32)
    pages = -(-total // runner.block_size)
    tables[0, :pages] = 1 + np.arange(pages)
    positions = list(range(n_prompt - 1, total - 1))
    slices = -(-n_prompt // chunk)
    first = max(0, slices - watch_slices)           # the first slice watched
    watch = list(range(first * chunk, total - 1))
    sizes = config.reference_sizes()
    rel = lambda a, b: float(np.abs(a - b).max() / np.abs(b).max())

    def serve(means_follow: bool):
        """-> (logits at `positions`, attention outputs at `watch`, the kept
        blocks and their count of every position)."""
        got, attended, kept = [], [], []

        def step(tok, start, bq):
            n = tok.shape[1]
            padded = np.zeros((1, bq), dtype=np.int32)
            padded[:, :n] = tok
            logits = np.asarray(runner.step(
                padded, np.full(1, start, np.int32),
                np.full(1, start + n, np.int32), np.full(1, n, np.int32),
                tables), dtype=np.float32)
            out = runner.last_layer_outputs
            kept.append([np.asarray(a)[:, :, :n] for a in out["selection"]])
            if start >= first * chunk:
                attended.append(np.asarray(out["attended"][:, :, :n],
                                           dtype=np.float32))
            return logits

        for start in range(0, n_prompt, chunk):
            n = min(chunk, n_prompt - start)
            logits = step(tokens[:, start:start + n], start, chunk)
        got.append(logits)
        if not means_follow:
            runner.cache["k_mean"] = runner.cache["k_mean"] * 0
        for pos in range(n_prompt, total):
            got.append(step(tokens[:, pos:pos + 1], pos, 1))
        got = np.stack(got[:-1], axis=1)
        if not np.isfinite(got).all():
            raise AssertionError("logits are not finite")
        return (got, np.concatenate(attended, axis=2)[:, :, :len(watch)],
                tuple(np.concatenate(parts, axis=2)[:, :, :total - 1]
                      for parts in zip(*kept)))

    def compare(served, fault=None, follow=True):
        got, mixed, kept = served
        want, found = ref.logits_at(
            params, tokens[:, :total - 1], positions, sizes,
            kept=kept if follow else None, fault=fault, watch=watch)
        return {"rel_err": rel(got, np.asarray(want)),
                "attended_err": rel(mixed, found["attended"]),
                "attended_rows": len(watch),
                "sets": int(found["selects"].sum())
                * config.num_key_value_heads,
                "sets_differ": int(found["differ"].sum()),
                "shortfall_max": float(found["shortfall"].max()),
                "watched_rows_select": bool(
                    found["selects"][:, :, positions].all())}

    t0 = time.time()
    served = serve(True)
    t1 = time.time()
    out = dict(compare(served), positions=total,
               program_s=round(t1 - t0, 3),
               attention_impl=runner.attention_impl)
    out["reference_s"] = round(time.time() - t1, 3)
    dense = compare(served, "dense_above", follow=False)
    out["dense_above"] = {k: dense[k] for k in ("rel_err", "attended_err")}
    behind = compare(serve(False))
    out["means_left_behind"] = {k: behind[k] for k in (
        "rel_err", "attended_err", "sets_differ", "shortfall_max")}
    return out


def _child_minicpm_sala_check(args) -> None:
    """Not one of `main`'s phases: MiniCPM-SALA at its published widths as the
    cell cuts it, a prompt of 16,384 tokens (2 x `dense_len`: 256 blocks, 64
    kept) in the engine's slices of 128 and 8 decode rows through both
    caches, with the selection followed and the attention outputs compared
    at all 1,024 rows of the last 8 slices; then the two controls that must
    fail: the reference attending densely above `dense_len` (by the sparse
    layers' attention outputs) and the program with page means left behind
    (by the shortfall of its choices)."""
    from ray_tpu.models.minicpm_sala import MiniCPMSALAConfig

    device = require_tpu(1)
    result = minicpm_sala_check(
        MiniCPMSALAConfig(max_position_embeddings=16384 + 256,
                          **MINICPM_SALA_CUT),
        seed=args.seed, n_prompt=16384, n_decode=8, chunk=128,
        num_blocks=1280, watch_slices=8)
    if result["attention_impl"] != "pallas":
        raise AssertionError(f"not the Pallas kernels: {result}")
    sound = (result["rel_err"] <= LOGITS_REL_TOL
             and result["attended_err"] <= ATTENDED_REL_TOL
             and result["shortfall_max"] <= BLOCK_TIE_MARGIN
             and result["watched_rows_select"])
    controls = {
        "dense_above": result["dense_above"]["attended_err"]
        <= ATTENDED_REL_TOL,
        "means_left_behind": result["means_left_behind"]["shortfall_max"]
        <= BLOCK_TIE_MARGIN}
    passed = [name for name, ok in controls.items() if ok]
    ok = sound and not passed
    emit("minicpm_sala_check", ok=ok, device=device,
         tolerance=LOGITS_REL_TOL, attended_tolerance=ATTENDED_REL_TOL,
         margin=BLOCK_TIE_MARGIN, controls_that_pass=passed, **result)
    if not ok:
        raise SystemExit(f"chip_smoke: the program is not the reference's, "
                         f"or a control is: {result}")


# What `--phase glm_dsa_check` holds a run to, and why. (a), (b): the
# benchmark's own tolerance for last-position logits, bf16 program against
# the float32 reference. (c): the program's index keys and queries are bf16
# and its hidden state differs from the reference's by rounding, so scores
# within that rounding of the index_topk-th change sides, as a router's ties
# do: of a token's selected rows nearly all are the reference's own, and a
# row it would not have kept falls short of its index_topk-th score by a
# small part of that token's score spread (the standard deviation of its
# visible scores). A program that kept other rows (the most recent ones, or
# what zeroed keys score) keeps about topk / context of the reference's rows
# and falls short by a spread or more. PERF.md section 6 (PR 49) has the
# readings both limits stand between.
DSA_OVERLAP_MIN = 0.9
DSA_GAP_MAX = 0.25
DSA_CONTROLS = ("recent_rows", "index_keys_lost")


def _selection_agreement(index, positions, counts, topk: int):
    """Of one "full" layer: index (s, s) the reference's scores, positions (s,
    topk) / counts (s,) the program's rows. Over the tokens that see more
    than topk rows: (the share of the program's rows among the reference's
    own topk, the largest shortfall of a kept row under the reference's
    topk-th score in units of the token's score spread, the share of the
    program's rows that the most recent topk hold)."""
    import numpy as np

    share, gap, recent, tokens = 0.0, 0.0, 0.0, 0
    for t in range(topk, index.shape[0]):
        if counts[t] < topk:        # a step that took the dense kernel
            continue
        seen = index[t, :t + 1]
        kth = np.partition(seen, t + 1 - topk)[t + 1 - topk]
        mine = seen[positions[t]]
        share += float(np.mean(mine >= kth))
        gap = max(gap, float((kth - mine.min()) / (seen.std() + 1e-30)))
        recent += float(np.mean(positions[t] > t - topk))
        tokens += 1
    return share / max(tokens, 1), gap, recent / max(tokens, 1), tokens


def _recent_rows(scores, n, *, topk: int, **_):
    """`ops.sparse_latent.dsa_select`'s signature: the most recent rows."""
    import jax.numpy as jnp

    count = jnp.minimum(n, topk).astype(jnp.int32)
    slot = jnp.arange(topk)[None, :]
    return (jnp.where(slot < count[:, None], (n - count)[:, None] + slot,
                      0).astype(jnp.int32), count)


def glm_dsa_check(config, *, seed: int, n_prompt: int, n_decode: int,
                  chunk: int, block_size: int, num_blocks: int,
                  attention_impl: str = "auto",
                  controls=DSA_CONTROLS) -> dict:
    """GLM-5.2's block past its selection size: ONE seeded prompt of
    `n_prompt` tokens prefilled through `ModelRunner.step` in slices of
    `chunk`, then `n_decode` teacher-forced positions, against the plain
    reference's full forward pass following the program's experts. Reported:
    (a) `rel_err`, the reference selecting its context rows FOR ITSELF; (b)
    `rel_err_following`, the reference attending to the rows the program kept
    (`runner.last_layer_outputs["selection"]`); (c) a "full" layer at a time,
    `selection_overlap` and `selection_gap` (`_selection_agreement`), and
    `recent_share`, how many of the kept rows a "most recent index_topk" rule
    would keep too (the selection is ALIVE where that is small). Then the
    same for every control of the PROGRAM: "recent_rows" (`dsa_select`
    replaced by the most recent rows) and "index_keys_lost" (the index-key
    pool zeroed between the prompt and the decode rows: a prefix hit that
    brought the latent rows alone)."""
    import importlib

    import jax
    import numpy as np

    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.ops import sparse_latent as sl

    module = importlib.import_module(type(config).__module__)
    ref = importlib.import_module(type(config).__module__ + "_reference")
    params = module.init_params(config, jax.random.key(seed))
    total, topk = n_prompt + n_decode, config.index_topk
    tokens = np.random.default_rng([seed, 7]).integers(
        1, config.vocab_size, (1, total)).astype(np.int32)
    positions = list(range(n_prompt - 1, total - 1))
    sizes = config.reference_sizes()

    def program(control):
        runner = ModelRunner(config, params, num_blocks=num_blocks,
                             block_size=block_size, chunk_size=chunk,
                             attention_impl=attention_impl, max_batch=2)
        table = np.zeros((1, runner.max_blocks_per_seq), dtype=np.int32)
        table[0, :-(-total // block_size)] = np.arange(-(-total // block_size))
        got, kept, rows, counts = [], [], [], []

        def step(tok, start, bq):
            n = tok.shape[1]
            padded = np.zeros((1, bq), dtype=np.int32)
            padded[:, :n] = tok
            one = lambda v: np.full(1, v, np.int32)
            logits = np.asarray(runner.step(
                padded, one(start), one(start + n), one(n), table),
                dtype=np.float32)
            kept.append(np.asarray(runner.last_routing)[:, :, :n])
            pos, count = runner.last_layer_outputs["selection"]
            rows.append(np.asarray(pos)[:, :, :n])
            counts.append(np.asarray(count)[:, :, :n])
            return logits

        for start in range(0, n_prompt, chunk):
            n = min(chunk, n_prompt - start)
            logits = step(tokens[:, start:start + n], start, chunk)
        got.append(logits)
        if control == "index_keys_lost":
            runner.cache["index"] = jax.jit(
                lambda a: a * 0, donate_argnums=(0,))(runner.cache["index"])
        for pos in range(n_prompt, total):
            got.append(step(tokens[:, pos:pos + 1], pos, 1))
        return (np.stack(got[:-1], axis=1), np.concatenate(kept, axis=2),
                np.concatenate(rows, axis=2), np.concatenate(counts, axis=2),
                runner.attention_impl)

    def rel(got, want):
        want = np.asarray(want, dtype=np.float32)
        return float(np.abs(got - want).max() / np.abs(want).max())

    def judged(control):
        t0 = time.time()
        select = sl.dsa_select
        if control == "recent_rows":
            sl.dsa_select = _recent_rows
        try:
            got, kept, rows, counts, impl = program(control)
        finally:
            sl.dsa_select = select
        t1 = time.time()
        own, _, index = ref.logits_at(params, tokens, positions, sizes, kept)
        following = ref.logits_at(
            params, tokens, positions, sizes, kept,
            selection=list(zip(rows, counts)))[0]
        layers = [_selection_agreement(i[0], r[0], c[0], topk)
                  for i, r, c in zip(index, rows, counts)]
        gc.collect()
        return dict(
            rel_err=rel(got, own), rel_err_following=rel(got, following),
            selection_overlap=[round(x[0], 5) for x in layers],
            selection_gap=[round(x[1], 5) for x in layers],
            recent_share=[round(x[2], 5) for x in layers],
            selected_tokens=layers[0][3], attention_impl=impl,
            program_s=round(t1 - t0, 1), reference_s=round(
                time.time() - t1, 1))

    def passes(r):
        # `not >`: a NaN passes nothing.
        return bool(r["rel_err"] <= LOGITS_REL_TOL
                    and r["rel_err_following"] <= LOGITS_REL_TOL
                    and min(r["selection_overlap"]) >= DSA_OVERLAP_MIN
                    and max(r["selection_gap"]) <= DSA_GAP_MAX)

    out = judged(None)
    out.update(positions=total, passes=passes(out), controls={})
    for control in controls:
        result = judged(control)
        out["controls"][control] = dict(result, passes=passes(result))
    return out


# The selection's gather and the attention kernel alone, at the shapes of a
# tick of `glm52-longdoc-closed32`: tokens of a step (a prompt slice beside
# the decode rows; decode rows alone).
GLM_DSA_TOKENS = (160, 32)


def _best_ms(run, calls: int = 5) -> float:
    """`run()` (which waits for its result) `calls` times after a first that
    compiles: the best, in ms by the host's clock."""
    run()
    best = float("inf")
    for _ in range(calls):
        t0 = time.time()
        run()
        best = min(best, time.time() - t0)
    return best * 1e3


def glm_dsa_gather_timing(tokens, *, seed: int, pages: int = 20480,
                          page: int = 16, topk: int = 2048, width: int = 640,
                          layers: int = 8, context: int = 34000,
                          group_sizes=(1, 2, 4)) -> dict:
    """XLA's gather of a selection's rows alone (`sparse_latent.
    gather_rows`), for every step of `tokens` tokens and every count S of
    layers whose rows lie side by side a token: a pool (layers / S, pages,
    page, S x width) of the model's dtype (the SAME bytes at every S), a
    page table a token of `context` positions over random pages, `topk`
    ascending positions a token. -> {"<tokens>x<row bytes>": {"ms" a gather
    by the host's clock (best of five, the result waited for), "device_ms"
    what the chip spent under the profiler (None off the chip), "ns_row"
    and "gb_s" (the rows' bytes written once) over the chip's time}}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import sparse_latent as sl

    rng = np.random.default_rng([seed, 50])
    gather = jax.jit(lambda pool, rows: sl.gather_rows(pool, 0, rows))
    out = {}
    for S in group_sizes:
        pool = jnp.ones((layers // S, pages, page, S * width), jnp.bfloat16)
        for T in tokens:
            table = rng.integers(0, pages, (T, -(-context // page)))
            positions = np.sort(np.stack([
                rng.choice(context, topk, replace=False) for _ in range(T)]),
                axis=1)
            rows = jnp.asarray(
                np.take_along_axis(table, positions // page, axis=1) * page
                + positions % page, jnp.int32)
            run = lambda: gather(pool, rows).block_until_ready()
            ms, device_ms = _best_ms(run), traced_ms(run, "")
            spent = device_ms or ms     # off the chip: the host's clock
            out[f"{T}x{S * width * 2}"] = {
                "ms": round(ms, 4), "device_ms": device_ms,
                "ns_row": round(spent * 1e6 / (T * topk), 2),
                "gb_s": round(T * topk * S * width * 2 / spent / 1e6, 1)}
        del pool
    return out


def glm_dsa_attend_timing(tokens, *, seed: int, heads: int = 64,
                          width: int = 640, lat: int = 512, topk: int = 2048,
                          group_size: int = 4, impl: str = "pallas",
                          interpret=None) -> dict:
    """`sparse_latent.dsa_attend` alone, for every step of `tokens` tokens:
    over a gathered operand of ONE layer's rows (T, topk, width) and over
    lane block `group_size - 1` of a group's (T, topk, group_size x width),
    every token with `topk - 1` cached rows and its own of the step. ->
    {"<tokens>": {"narrow_ms", "wide_ms" a call by the host's clock,
    "narrow_kernel_ms", "wide_kernel_ms" the `dsa_attend_call` events alone
    (None off the chip), "gb_s" (a token's topk rows of `width` read once,
    over "wide_ms"), "err" (the kernel on the lane block against the jnp form
    on that block sliced out: max |difference| over max |oracle|)}}."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import sparse_latent as sl

    out = {}
    for T in tokens:
        ks = jax.random.split(jax.random.key(seed + T), 3)
        q = jax.random.normal(ks[0], (T, heads, width), jnp.bfloat16)
        wide = jax.random.normal(
            ks[1], (T, topk, group_size * width), jnp.bfloat16)
        own = jax.random.normal(ks[2], (T, width), jnp.bfloat16)
        place = group_size - 1
        narrow = wide[..., place * width:]
        count = jnp.full((T,), topk, jnp.int32)
        mask = jnp.eye(T, dtype=bool)
        kw = dict(scale=width ** -0.5, lat=lat)
        attend = jax.jit(lambda picked, place, impl: sl.dsa_attend(
            q, picked, count, count - 1, own, mask, place=place, impl=impl,
            interpret=interpret, **kw), static_argnums=(1, 2))
        want = attend(narrow, 0, "reference").astype(jnp.float32)
        got = attend(wide, place, impl).astype(jnp.float32)
        cell = out[str(T)] = {"err": float(
            jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))}
        del want, got
        for name, picked, at in (("narrow", narrow, 0),
                                 ("wide", wide, place)):
            run = lambda: attend(picked, at, impl).block_until_ready()
            cell[name + "_ms"] = round(_best_ms(run), 4)
            cell[name + "_kernel_ms"] = traced_ms(run, "dsa_attend_call")
        cell["gb_s"] = round(T * topk * width * 2 / cell["wide_ms"] / 1e6, 1)
    return out


def glm_dsa_index_timing(*, seed: int, rows: int = 32,
                         split=(15, 8, 5, 4), run_pages: int = 2048,
                         tail=(15, 140), slice_tokens: int = 128,
                         heads: int = 32, dim: int = 128, page: int = 16,
                         pages: int = 20480, width: int = 2304,
                         impl: str = "pallas", interpret=None) -> dict:
    """`sparse_latent.dsa_index` alone at a tick of `glm52-longdoc-closed32`:
    `rows` decode rows whose tables begin with one of len(split) shared runs
    of `run_pages` pages (`split` rows on each) and go on with `tail` pages
    of their own; the same with the first row a slice of `slice_tokens`
    tokens; and the same rows on tables that share NOTHING (every row's
    pages its own places of the pool). -> {"decode" | "decode+slice" |
    "unshared" | "unshared+slice": {"ms" the entry by the host's clock (best
    of five, the result waited for, WITH what XLA lays around the kernel),
    "kernel_ms" the `dsa_index_call` events alone under the profiler (None
    off the chip), "gb_s" (every row's context read once, `dsa_index_hbm.
    share`'s count, over the kernel's time), "err" (against the oracle, max
    |difference| over max |oracle| of the scores both see; the decode cases
    only: the oracle gathers a context a token)}}. It calls nothing but the
    entry, so this file laid over a parent's checkout times the parent."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import sparse_latent as sl

    rng = np.random.default_rng([seed, 51])
    ks = jax.random.split(jax.random.key(seed), 3)
    pool = jax.random.normal(ks[0], (2, pages, page, dim), jnp.bfloat16)
    tails = rng.integers(tail[0], tail[1], rows)
    kv_lens = (run_pages + tails) * page - rng.integers(0, page, rows)
    shared = np.zeros((rows, width), np.int32)
    alone = np.zeros((rows, width), np.int32)
    free = len(split) * run_pages
    for s, doc in enumerate(np.repeat(np.arange(len(split)), split)):
        own = free + np.arange(tails[s])
        free += tails[s]
        shared[s, :run_pages + tails[s]] = np.concatenate(
            [doc * run_pages + np.arange(run_pages), own])
        # the same count of pages, no place in common with another row's
        alone[s, :run_pages + tails[s]] = (
            s * 577 + np.arange(run_pages + tails[s])) % pages
    # the pool an ARGUMENT: closed over, every jit would hold its 168 MB
    index = jax.jit(lambda pool, q, w, tables, kv, pos, cu, impl:
                    sl.dsa_index(q, w, pool, 1, tables, kv, pos, cu,
                                 impl=impl, interpret=interpret),
                    static_argnums=(7,))
    out = {}
    for name, tables, sliced in (("decode", shared, False),
                                 ("decode+slice", shared, True),
                                 ("unshared", alone, False),
                                 ("unshared+slice", alone, True)):
        q_lens = np.ones(rows, np.int64)
        if sliced:
            q_lens[0] = min(slice_tokens, kv_lens[0] - run_pages * page)
        T = int(q_lens.sum())
        q = jax.random.normal(ks[1], (T, heads, dim), jnp.bfloat16)
        w = jax.random.normal(ks[2], (T, heads), jnp.float32)
        args = (pool, q, w, jnp.asarray(tables),
                jnp.asarray(kv_lens, jnp.int32),
                jnp.asarray(kv_lens - q_lens, jnp.int32),
                jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]),
                            jnp.int32))
        run = lambda: index(*args, impl).block_until_ready()
        cell = out[name] = {"ms": round(_best_ms(run), 4),
                            "kernel_ms": traced_ms(run, "dsa_index_call")}
        cell["gb_s"] = round(int(kv_lens.sum()) * dim * 2
                             / (cell["kernel_ms"] or cell["ms"]) / 1e6, 1)
        if not sliced:
            got, want = index(*args, impl), index(*args, "reference")
            seen = jnp.isfinite(want)
            cell["err"] = float(
                jnp.max(jnp.abs(jnp.where(seen, got - want, 0.0)))
                / jnp.max(jnp.where(seen, jnp.abs(want), 0.0))
                + jnp.sum(jnp.isfinite(got) != seen))
            del got, want
    return out


def _child_glm_dsa(args) -> None:
    """Not one of `main`'s phases: `--phase glm_dsa` alone. `--sweep 8,32`:
    the index entry's leg alone, at those tokens a walk (how `sparse_latent.
    INDEX_Q_BLOCK` was chosen: PERF.md section 6, PR 51)."""
    import jax

    from ray_tpu.ops import sparse_latent as sl

    device = require_tpu(1)
    for block in map(int, filter(None, args.sweep.split(","))):
        sl.INDEX_Q_BLOCK = block
        jax.clear_caches()
        emit("glm_dsa", ok=True, device=device, q_block=block,
             index=glm_dsa_index_timing(seed=args.seed))
    if args.sweep:
        return
    gather = glm_dsa_gather_timing(GLM_DSA_TOKENS, seed=args.seed)
    attend = glm_dsa_attend_timing(GLM_DSA_TOKENS, seed=args.seed)
    index = glm_dsa_index_timing(seed=args.seed)
    ok = (all(c["err"] < BF16_REL_TOL for c in attend.values())
          and all(c.get("err", 0.0) < 1e-3 for c in index.values()))
    emit("glm_dsa", ok=ok, device=device, gather=gather, attend=attend,
         index=index)
    if not ok:
        raise SystemExit(f"chip_smoke: a kernel is not its oracle's: "
                         f"{attend} {index}")


def _child_glm_dsa_check(args) -> None:
    """Not one of `main`'s phases: GLM-5.2 at its published widths as the
    cell cuts it, 8,192 + 8 positions: four times its selection size."""
    from ray_tpu.models.glm_dsa import GlmDsaConfig

    device = require_tpu(1)
    result = glm_dsa_check(GlmDsaConfig(**GLM_CUT), seed=args.seed,
                           n_prompt=8192, n_decode=8, chunk=128,
                           block_size=16, num_blocks=1024)
    failing = [n for n, r in result["controls"].items() if not r["passes"]]
    emit("glm_dsa_check",
         ok=result["passes"] and len(failing) == len(DSA_CONTROLS),
         device=device, tolerance=LOGITS_REL_TOL,
         overlap_min=DSA_OVERLAP_MIN, gap_max=DSA_GAP_MAX,
         controls_that_fail=failing, **result)


# Trinity-Large-Preview as `trinitylarge-docqa-closed32` cuts it
# (benchmarks/configs/trinity-large-l5-e32.json).
AFMOE_CUT = dict(
    vocab_size=25024, num_dense_layers=1, experts_held=(0, 32),
    layer_types=("sliding_attention",) * 3 + ("full_attention",
                                              "sliding_attention"))


def afmoe_kernel_timing(*, seed: int, rows: int = 31,
                        context=(8300, 8900), piece: int = 128,
                        pages: int = 12288, block_size: int = 16,
                        calls: int = 4, tiles=None, config=None) -> dict:
    """Time Trinity-Large-Preview's K/V layers ALONE at the shapes the cell
    `trinitylarge-docqa-closed32` gives them (48 query heads over 8 kv heads
    of 128, row pools of 1,024 lanes as the model declares them, passed as
    arguments; q moving with the layer, or XLA hoists the kernel out of the
    loop): `rows` decode rows at contexts in `context` through the FULL layer
    and through a WINDOW layer (4,096 tokens, a ring of 266 pages), each
    alone and beside one `piece`-token slice at the end of such a context,
    every form against the jnp reference on its first two rows and its last.
    `tiles` (pages a step of a block
    of one token, of many): in place of `kv_sizes`' (how its window rule was
    chosen). -> {form: {"ms" a layer, "err", "gb_s", "share" of 819 GB/s},
    "+slice" forms also "slice_ms"}, "pages_a_step"."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.afmoe import AfmoeConfig
    from ray_tpu.ops import paged_attention as pa

    c = config or AfmoeConfig(max_position_embeddings=9216, **AFMOE_CUT)
    block = c.serving_block()
    chosen = block.kv_kernels(block_size)
    if tiles:
        pa.kv_sizes = lambda *a, **kw: pa.KVSizes(chosen["all"].q_block,
                                                  *tiles, True)
        jax.clear_caches()
    rng = np.random.RandomState(seed)
    H, K, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    ring = block.groups[1].ring_width(block_size, piece)
    pools = {a.name: jax.random.normal(
        jax.random.fold_in(jax.random.key(seed), i), a.shape, a.dtype)
        for i, a in enumerate(block.cache_arrays(
            {"all": pages, "window": pages}, block_size))}
    ctx = rng.randint(*context, rows)
    full_width = -(-(context[1] + piece) // block_size)

    def timed(group, q_lens, kv_lens, width, window):
        S, layers = len(q_lens), pools[f"k_{group}"].shape[0]
        tables = jnp.asarray(rng.randint(0, pages, (S, width)), jnp.int32)
        q = jax.random.normal(jax.random.key(seed + 2),
                              (int(sum(q_lens)), H, hd), c.dtype)
        scalars = (jnp.asarray(kv_lens, jnp.int32),
                   jnp.asarray(np.asarray(kv_lens) - np.asarray(q_lens),
                               jnp.int32),
                   jnp.asarray(np.concatenate([[0], np.cumsum(q_lens)]),
                               jnp.int32))
        kw = dict(scale=hd ** -0.5, kv_heads=K, window=window)

        @jax.jit
        def loop(q, k_pool, v_pool, tables, *scalars):
            def layer(i, total):
                li = i % layers
                # q moves with the ITERATION: the full group has one layer,
                # and a call that does not move is hoisted out of the loop
                return total + jnp.sum(pa.ragged_paged_attention_unified(
                    q + i.astype(q.dtype) * 1e-3, k_pool, v_pool, li,
                    tables, *scalars, **kw).astype(jnp.float32))

            return jax.lax.fori_loop(0, calls * layers, layer,
                                     jnp.float32(0))

        args = (q, pools[f"k_{group}"], pools[f"v_{group}"], tables,
                *scalars)
        # against the reference a sequence at a time (it gathers a padded
        # context: all 32 at once are 12 GB), the first two and the last
        got = jax.jit(lambda *a: pa.ragged_paged_attention_unified(
            *a, **kw))(args[0], *args[1:3], jnp.int32(0), *args[3:])
        one = jax.jit(lambda *a: pa.ragged_paged_attention_unified_reference(
            *a, **kw))
        cu, err = np.concatenate([[0], np.cumsum(q_lens)]), 0.0
        for i in sorted({0, 1, S - 1}):
            lo, hi = int(cu[i]), int(cu[i + 1])
            want = one(q[lo:hi], *args[1:3], jnp.int32(0), tables[i:i + 1],
                       scalars[0][i:i + 1], scalars[1][i:i + 1],
                       jnp.asarray([0, hi - lo], jnp.int32))
            err = max(err, _rel_err(got[lo:hi], want))
        loop(*args).block_until_ready()                     # compiles
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            loop(*args).block_until_ready()
            best = min(best, time.time() - t0)
        return best / (calls * layers) * 1e3, err

    def cell(timing, tokens):
        ms, err = timing
        gb_s = tokens * K * 2 * hd * 2 / ms / 1e6
        return {"ms": round(ms, 4), "err": round(err, 5),
                "gb_s": round(gb_s, 3), "share": round(gb_s / 819.0, 4)}

    W = c.sliding_window
    blocks = -(-piece // block.q_block)
    out = {"pages_a_step": {g: [s.pages_one, s.pages_many] for g, s in (
        block.kv_kernels(block_size).items())}}
    for form, group, window, width, seen in (
            ("full", "all", None, full_width, lambda n: n),
            ("window", "window", W, ring, lambda n: np.minimum(n, W))):
        alone = cell(timed(group, [1] * rows, ctx, width, window),
                     float(seen(ctx).sum()))
        last = int(ctx.min()) + piece
        both = timed(group, [1] * rows + [piece], list(ctx) + [last], width,
                     window)
        # The slice's block j walks the context up to its own last token
        # (with a window: from its first token's oldest visible position).
        slice_tokens = blocks * float(seen(np.asarray(last - piece / 2))) + (
            piece if window else 0)
        out[f"{form}_decode"] = alone
        out[f"{form}_decode+slice"] = dict(
            cell(both, float(seen(ctx).sum()) + slice_tokens),
            slice_ms=round(both[0] - alone["ms"], 4), slice_blocks=blocks)
    return out


def _child_afmoe_kernels(args) -> None:
    """Not one of `main`'s phases: `--phase afmoe_kernels` alone. `--sweep
    16x16,32x64`: the same at those pages a step of a block of one token x
    of many, in place of `kv_sizes`' (a size the compiler refuses for want of
    VMEM is reported and passed over)."""
    device = require_tpu(1)
    bad = {}
    sweep = [tuple(map(int, tiles.split("x")))
             for tiles in filter(None, args.sweep.split(","))]
    for tiles in [None] + sweep:
        try:
            result = afmoe_kernel_timing(seed=args.seed, tiles=tiles)
        except Exception as e:      # noqa: BLE001 - a sweep goes on
            if not tiles:
                raise
            emit("afmoe_kernels", ok=False, tiles=tiles,
                 refused=f"{type(e).__name__}: {str(e)[:300]}")
            continue
        bad.update({f"{tiles}:{n}": c["err"] for n, c in result.items()
                    if "err" in c and not c["err"] <= BF16_REL_TOL})
        emit("afmoe_kernels", ok=not bad, device=device,
             tolerance=BF16_REL_TOL, unit="ms a layer", tiles=tiles,
             **result)
    if bad:
        raise SystemExit(f"chip_smoke: the kernel is not the reference's: "
                         f"{bad}")


# LFM2-24B-A2B as `lfm2moe-longout-closed64` cuts it
# (benchmarks/configs/lfm2-24b-a2b-l9.json): the published layers 1-9.
LFM2_CUT = dict(
    num_dense_layers=1,
    layer_types=("conv", "full_attention", "conv", "conv", "conv",
                 "full_attention", "conv", "conv", "conv"))


def lfm2_grouped_shapes(cell: str, sizes: dict = None) -> dict:
    """`grouped_shapes` for the cell that holds EVERY expert: 64 groups of
    (2048, 1536) twice and (1536, 2048), 4 picks a row, 4 pairs a group on a
    decode tick of 64 rows and 12 on a tick with a 128-token slice."""
    if sizes is None:
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               "lfm2-24b-a2b-l9.json")) as f:
            sizes = json.load(f)["sizes"]
    d, ff = sizes["hidden_size"], sizes["moe_intermediate_size"]
    return {"products": [(d, ff)] * 2 + [(ff, d)],
            "held": sizes["num_experts"],
            "published": sizes["num_experts_published"],
            "picks": sizes["num_experts_per_tok"],
            "dtype": sizes["torch_dtype"], "rows": (64, 64 + 128)}


def lfm2_kernel_timing(*, seed: int, rows: int = 64,
                       contexts=(1024, 4096, 8191), mix=(256, 8191),
                       piece: int = 128, pages: int = 32768,
                       block_size: int = 16, calls: int = 4, tiles=None,
                       config=None) -> dict:
    """Time LFM2-24B-A2B's K/V layers ALONE in the PAIR FORM BY RUNS at the
    shapes the cell `lfm2moe-longout-closed64` gives them (32 query heads of
    64 as half-zero 128-lane rows in runs of four over 4 kv pairs of 128 +
    128 lanes, row pools of 512 lanes as the model declares them, passed as
    arguments; q moving with the iteration, or XLA hoists the kernel out of
    the loop): `rows` decode rows at each context of `contexts`, and a tick
    of `rows` - 1 decode rows at contexts spread log-uniformly over `mix`
    beside one `piece`-token slice, every form through `pair_queries` /
    `pair_outputs` against PLAIN grouped-query attention (the jnp reference
    at `kv_heads` K over the same pools) on its first two sequences and its
    last. `tiles` (pages a step of a block of one token, of many): in place
    of `kv_sizes`'. -> {form: {"ms" a layer with the pair form's two
    re-layouts, "err", "gb_s" of USEFUL bytes (a token 2 x K x hd x 2 B a
    layer), "share" of 819 GB/s}, "tick" also "slice_ms"}, "pages_a_step"."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.lfm2_moe import Lfm2MoeConfig
    from ray_tpu.ops import paged_attention as pa

    c = config or Lfm2MoeConfig(max_position_embeddings=8192, **LFM2_CUT)
    block = c.serving_block()
    if tiles:
        q_block = block.kv_kernels(block_size)["all"].q_block
        pa.kv_sizes = lambda *a, **kw: pa.KVSizes(q_block, *tiles, True)
        jax.clear_caches()
    rng = np.random.RandomState(seed)
    H, K, hd, G = (c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                   block.run)
    k_pool, v_pool = (jax.random.normal(
        jax.random.fold_in(jax.random.key(seed), i), a.shape, a.dtype)
        for i, a in enumerate(block.cache_arrays(
            {"all": pages, "state": 1}, block_size)[:2]))
    layers = k_pool.shape[0]
    width = -(-(max(max(contexts), mix[1]) + piece) // block_size)

    def paired(q, *a):
        return pa.pair_outputs(pa.ragged_paged_attention_unified(
            pa.pair_queries(q, G), *a, scale=hd ** -0.5, kv_heads=K // 2), G)

    def timed(q_lens, kv_lens):
        S = len(q_lens)
        tables = jnp.asarray(rng.randint(0, pages, (S, width)), jnp.int32)
        q = jax.random.normal(jax.random.key(seed + 2),
                              (int(sum(q_lens)), H, hd), c.dtype)
        cu = np.concatenate([[0], np.cumsum(q_lens)])
        scalars = (jnp.asarray(kv_lens, jnp.int32),
                   jnp.asarray(np.asarray(kv_lens) - np.asarray(q_lens),
                               jnp.int32), jnp.asarray(cu, jnp.int32))

        @jax.jit
        def loop(q, k_pool, v_pool, tables, *scalars):
            def layer(i, total):
                return total + jnp.sum(paired(
                    q + i.astype(q.dtype) * 1e-3, k_pool, v_pool, i % layers,
                    tables, *scalars).astype(jnp.float32))

            return jax.lax.fori_loop(0, calls * layers, layer,
                                     jnp.float32(0))

        args = (q, k_pool, v_pool, tables, *scalars)
        got = jax.jit(paired)(q, k_pool, v_pool, jnp.int32(1), tables,
                              *scalars)
        # plain GQA a sequence at a time (the reference gathers a padded
        # context), the first two and the last
        plain = jax.jit(lambda *a: pa.ragged_paged_attention_unified_reference(
            *a, scale=hd ** -0.5, kv_heads=K))
        err = 0.0
        for i in sorted({0, 1, S - 1}):
            lo, hi = int(cu[i]), int(cu[i + 1])
            want = plain(q[lo:hi], k_pool, v_pool, jnp.int32(1),
                         tables[i:i + 1], scalars[0][i:i + 1],
                         scalars[1][i:i + 1],
                         jnp.asarray([0, hi - lo], jnp.int32))
            err = max(err, _rel_err(got[lo:hi], want))
        loop(*args).block_until_ready()                     # compiles
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            loop(*args).block_until_ready()
            best = min(best, time.time() - t0)
        return best / (calls * layers) * 1e3, err

    def cell(timing, tokens):
        ms, err = timing
        gb_s = tokens * K * 2 * hd * 2 / ms / 1e6
        return {"ms": round(ms, 4), "err": round(err, 5),
                "gb_s": round(gb_s, 3), "share": round(gb_s / 819.0, 4)}

    sizes = block.kv_kernels(block_size)["all"]
    out = {"pages_a_step": [sizes.pages_one, sizes.pages_many]}
    for context in contexts:
        ctx = rng.randint(int(0.9 * context), context + 1, rows)
        out[f"decode_{context}"] = cell(timed([1] * rows, ctx),
                                        float(ctx.sum()))
    ctx = np.exp(rng.uniform(np.log(mix[0]), np.log(mix[1]),
                             rows - 1)).astype(int)
    alone = cell(timed([1] * (rows - 1), ctx), float(ctx.sum()))
    last = int(np.median(ctx)) + piece
    both = timed([1] * (rows - 1) + [piece], list(ctx) + [last])
    blocks = -(-piece // block.q_block)
    out["tick_decode"] = alone
    out["tick"] = dict(
        cell(both, float(ctx.sum()) + blocks * (last - piece / 2)),
        slice_ms=round(both[0] - alone["ms"], 4), slice_blocks=blocks)
    return out


def _child_lfm2_kernels(args) -> None:
    """Not one of `main`'s phases: `--phase lfm2_kernels` alone: the row
    kernel in the pair form by runs at the cell's shapes, then the grouped
    product at 64 groups of 4 and of 12 rows. `--sweep 16x32,32x32`: the
    kernel again at those pages a step of a block of one token x of many, in
    place of `kv_sizes`' (a size the compiler refuses for want of VMEM is
    reported and passed over)."""
    device = require_tpu(1)
    bad = {}
    sweep = [tuple(map(int, tiles.split("x")))
             for tiles in filter(None, args.sweep.split(","))]
    for tiles in [None] + sweep:
        try:
            result = lfm2_kernel_timing(seed=args.seed, tiles=tiles)
        except Exception as e:      # noqa: BLE001 - a sweep goes on
            if not tiles:
                raise
            emit("lfm2_kernels", ok=False, tiles=tiles,
                 refused=f"{type(e).__name__}: {str(e)[:300]}")
            continue
        bad.update({f"{tiles}:{n}": c["err"] for n, c in result.items()
                    if "err" in c and not c["err"] <= BF16_REL_TOL})
        emit("lfm2_kernels", ok=not bad, device=device,
             tolerance=BF16_REL_TOL, unit="ms a layer", tiles=tiles,
             **result)
    if bad:
        raise SystemExit(f"chip_smoke: the pair form by runs is not plain "
                         f"grouped-query attention: {bad}")
    result = grouped_dot_timing(["lfm2moe"], seed=args.seed,
                                shapes=lfm2_grouped_shapes)
    ok = all(c["kernel"]["err"] <= max(2 * c["ragged"]["err"], 1e-5)
             and c["kernel"]["behind"] == 0 for c in result.values())
    emit("lfm2_grouped_dot", ok=ok, device=device,
         unit="ms a product; GB/s of the met experts' weights", **result)
    if not ok:
        raise SystemExit("chip_smoke: the kernel is not the oracle's")


# What `--phase afmoe_check` holds a run to, and why. The logits and the
# routed choices: the benchmark's own tolerance and margin. But a softmax
# over thousands of near-equal scores hides a mask in the LOGITS of random
# weights (PERF.md section 7, PR 57 (5)), so EVERY layer's attention output
# before its gate is compared too, at the decode rows and every row of the
# prompt's last eight slices (each token of a slice attends under its own
# window): max |difference| over max |reference| a layer, bfloat16
# probabilities and values against float32 (`minicpm_sala_check` read 1-2%).
# The six controls are the reference with ONE term changed, following the
# program's experts all the same: each must fail by one of the three limits.
AFMOE_CONTROLS = ("no_window", "full_rotated", "no_gate", "no_post_mlp_norm",
                  "no_bias", "no_route_scale")


def afmoe_check(config, *, seed: int, n_prompt: int, n_decode: int,
                chunk: int, num_blocks: int, watch_slices: int,
                block_size: int = 16, controls=AFMOE_CONTROLS,
                attention_impl: str = "auto") -> dict:
    """ONE seeded prompt of `n_prompt` tokens served in the engine's slices
    through `ModelRunner.step` and `n_decode` rows decoded through the cache
    (the timed path's own programs; the window group's ring wraps once the
    prompt passes the window), against the plain float32 reference computed
    in blocks, which FOLLOWS the program's experts. -> {"rel_err" of the
    logits at the last prompt row and the decode rows, "attended_err" the
    largest of the layers' attention outputs' at every row of the last
    `watch_slices` slices and the decode rows, "attended_err_by_layer",
    routed choices and their shortfall, "controls": the same three of the
    reference with one term changed}; then, through an LLMEngine over the
    same runner, "hit": the same prompt served twice, the second a prefix
    hit on both groups, and both requests' greedy tokens."""
    import importlib

    import jax
    import numpy as np

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.sampling import SamplingParams

    module = importlib.import_module(type(config).__module__)
    ref = importlib.import_module(type(config).__module__ + "_reference")
    params = module.init_params(config, jax.random.key(seed))
    runner = ModelRunner(config, params, num_blocks=num_blocks,
                         block_size=block_size, chunk_size=chunk,
                         attention_impl=attention_impl, max_batch=2)
    total = n_prompt + n_decode
    tokens = np.random.default_rng([seed, 7]).integers(
        1, config.vocab_size, (1, total)).astype(np.int32)
    tables = np.zeros((1, runner.max_blocks_per_seq), dtype=np.int32)
    pages = -(-total // runner.block_size)
    tables[0, :pages] = runner.num_blocks - 1 - np.arange(pages)
    positions = list(range(n_prompt - 1, total - 1))
    first = max(0, -(-n_prompt // chunk) - watch_slices) * chunk
    watch = list(range(first, total - 1))
    sizes = config.reference_sizes()
    rel = lambda a, b: float(np.abs(a - b).max() / np.abs(b).max())
    got, attended, kept = [], [], []

    def step(tok, start, bq):
        n = tok.shape[1]
        padded = np.zeros((1, bq), dtype=np.int32)
        padded[:, :n] = tok
        logits = np.asarray(runner.step(
            padded, np.full(1, start, np.int32),
            np.full(1, start + n, np.int32), np.full(1, n, np.int32),
            tables), dtype=np.float32)
        kept.append(np.asarray(runner.last_routing)[:, :, :n])
        if start >= first:
            attended.append(np.asarray(
                runner.last_layer_outputs["attended"][:, :, :n],
                dtype=np.float32))
        return logits

    t0 = time.time()
    for start in range(0, n_prompt, chunk):
        n = min(chunk, n_prompt - start)
        logits = step(tokens[:, start:start + n], start, chunk)
    got.append(logits)
    for pos in range(n_prompt, total):
        got.append(step(tokens[:, pos:pos + 1], pos, 1))
    got = np.stack(got[:-1], axis=1)
    if not np.isfinite(got).all():
        raise AssertionError("logits are not finite")
    attended = np.concatenate(attended, axis=2)[:, :, :len(watch)]
    kept = np.concatenate(kept, axis=2)[:, :, :total - 1]
    t1 = time.time()

    def compare(fault=None):
        want, found = ref.logits_at(params, tokens[:, :total - 1], positions,
                                    sizes, kept=kept, fault=fault,
                                    watch=watch)
        by_layer = [round(rel(attended[li], found["attended"][li]), 5)
                    for li in range(len(attended))]
        return {"rel_err": rel(got, np.asarray(want)),
                "attended_err": max(by_layer),
                "attended_err_by_layer": by_layer,
                **one_group_shortfall(found["scores"], kept)}

    out = dict(compare(), positions=total, attended_rows=len(watch),
               program_s=round(t1 - t0, 3),
               attention_impl=runner.attention_impl)
    out["reference_s"] = round(time.time() - t1, 3)
    out["controls"] = {
        name: {k: v for k, v in compare(name).items()
               if k in ("rel_err", "attended_err", "shortfall_max")}
        for name in controls}
    # The second leg: the prompt served twice through the engine.
    engine = LLMEngine(runner, max_batch_size=2, prefill_chunk=chunk)
    prompt = tokens[0, :n_prompt].tolist()
    sp = SamplingParams(max_tokens=n_decode, temperature=0.0)
    cold = engine.generate([prompt], sp)[0].output_token_ids
    before = engine.stats()
    warm = engine.generate([prompt], sp)[0].output_token_ids
    stats = engine.stats()
    records = engine.tick_records()
    out["hit"] = {
        "tokens_equal": cold == warm,
        "first_token_is_the_steps": cold[0] == int(got[0, 0].argmax()),
        "prefix_hits": stats["prefix_hits"] - before["prefix_hits"],
        "prefix_hits_cut_short": stats["prefix_hits_cut_short"],
        "prefix_tokens_saved": (stats["prefix_tokens_saved"]
                                - before["prefix_tokens_saved"]),
        "window_tail_pages": sum(t.get("window_tail_pages", 0)
                                 for t in records),
        "window_pages_freed": sum(t.get("window_pages_freed", 0)
                                  for t in records),
        "kv_groups": stats["kv_groups"]}
    return out


def _child_afmoe_check(args) -> None:
    """Not one of `main`'s phases: Trinity-Large-Preview at its published
    widths as the cell cuts it, a prompt of 8,192 tokens (two windows of
    4,096: every window layer's ring wraps) in slices of 128 and 8 decode
    rows through the cache, the reference following the program's experts;
    the six controls, each of which must fail; and the prompt served twice
    through an engine, the second time a hit on both groups."""
    from ray_tpu.models.afmoe import AfmoeConfig

    device = require_tpu(1)
    result = afmoe_check(
        AfmoeConfig(max_position_embeddings=9216, **AFMOE_CUT),
        seed=args.seed, n_prompt=8192, n_decode=8, chunk=128,
        num_blocks=2048, watch_slices=8)
    if result["attention_impl"] != "pallas":
        raise AssertionError(f"not the Pallas kernels: {result}")

    def passes(r):
        return (r["rel_err"] <= LOGITS_REL_TOL
                and r["attended_err"] <= ATTENDED_REL_TOL
                and r["shortfall_max"] <= ROUTING_TIE_MARGIN)

    passed = [n for n, r in result["controls"].items() if passes(r)]
    hit = result["hit"]
    hit_ok = (hit["tokens_equal"] and hit["first_token_is_the_steps"]
              and hit["prefix_hits"] == 1 and not hit["prefix_hits_cut_short"]
              and hit["prefix_tokens_saved"] == 8176
              and hit["window_tail_pages"] == 256)
    ok = passes(result) and not passed and hit_ok
    emit("afmoe_check", ok=ok, device=device, tolerance=LOGITS_REL_TOL,
         attended_tolerance=ATTENDED_REL_TOL, margin=ROUTING_TIE_MARGIN,
         controls_that_pass=passed, hit_ok=hit_ok, **result)
    if not ok:
        raise SystemExit(f"chip_smoke: the program is not the reference's, "
                         f"a control is, or the second request missed: "
                         f"{result}")


def _model(n_layers: int):
    from ray_tpu.models import llama

    return llama.LlamaConfig.llama3_8b(n_layers=n_layers,
                                       remat_policy="dots")


def _child_latent(args) -> None:
    """Not one of `main`'s phases: `--phase latent` alone. `--sweep
    32x24,64x32`: the same at those pages a step of a block of one token x
    of many, in place of `latent_kv_pages`' (how `LATENT_TILE_ONE` /
    `LATENT_TILE_MANY` are chosen)."""
    import jax

    from ray_tpu.ops import paged_attention as pa

    device = require_tpu(1)
    bad = {}
    sweep = [tuple(map(int, tiles.split("x")))
             for tiles in filter(None, args.sweep.split(","))]
    for tiles in sweep or [None]:
        if tiles:
            pa.latent_kv_pages = lambda *_, t=tiles: t
            jax.clear_caches()
        result = latent_kernel_timing(LATENT_SHAPES, seed=args.seed)
        bad.update({f"{tiles}:{n}": c["err"] for n, c in result.items()
                    if not c["err"] <= BF16_REL_TOL})
        emit("latent", ok=not bad, device=device, tolerance=BF16_REL_TOL,
             unit="ms a pass over the layers",
             pages_a_step=pa.latent_kv_pages(128, 640, 512, 16), **result)
    if bad:
        raise SystemExit(f"chip_smoke: the kernel is not the reference's: "
                         f"{bad}")


def _child_kernels(args) -> None:
    """In a process of its own: JAX's tracing cache hands a kernel the
    source locations of whichever kernel first traced the same inner shapes,
    and those are part of the compile-cache key. Checked in the server's
    process, the server's programs would get other keys than a replica's."""
    device = require_tpu(1)
    emit("kernels", ok=True, device=device, tolerance=BF16_REL_TOL,
         rel_err=kernel_checks(_model(SERVE_LAYERS), seed=args.seed,
                               num_kv_blocks=KV_BLOCKS),
         mimo_layers_alone=mimo_kernel_timing(seed=args.seed),
         table_decode=table_decode_timing(seed=args.seed),
         kv5d_decode=kv5d_decode_timing(seed=args.seed))


def _child_serve(args) -> None:
    device = require_tpu(1)
    cfg = _model(SERVE_LAYERS)
    result, _ = serve_phase(cfg, seed=args.seed, num_kv_blocks=KV_BLOCKS,
                            prompt_lens=PROMPT_LENS, max_tokens=MAX_TOKENS)
    if result["attention_impl"] != "pallas":
        raise AssertionError(f"not the Pallas kernels: {result}")
    emit("serve", ok=True, device=device, layers=SERVE_LAYERS,
         kv_blocks=KV_BLOCKS, **result)


def _train_kwargs(args) -> dict:
    return dict(seed=args.seed, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                steps=TRAIN_STEPS, lr=TRAIN_LR)


def _child_train(args) -> None:
    from ray_tpu.parallel.mesh import MeshConfig

    device = require_tpu(1)
    result = train_phase(_model(TRAIN_LAYERS), mesh_config=MeshConfig(),
                         **_train_kwargs(args))
    if result["pallas_kernels_in_step"] < 3:
        raise AssertionError(
            f'"auto" gave way to the reference: {result}')
    emit("train", ok=True, device=device, layers=TRAIN_LAYERS, **result)


def _child_train4(args) -> None:
    """The train step on fsdp=2 x tp=2 against the same seed, batch and
    depth on a one-device mesh, in this one process."""
    from ray_tpu.parallel.mesh import MeshConfig

    device = require_tpu(4)
    cfg = _model(TRAIN_LAYERS)
    four = train_phase(cfg, mesh_config=MeshConfig(fsdp=2, tp=2),
                       **_train_kwargs(args))
    one = train_phase(cfg, mesh_config=MeshConfig(), **_train_kwargs(args))
    rel = [abs(a - b) / abs(b) for a, b in zip(four["losses"], one["losses"])]
    if (four["pallas_kernels_in_step"] < 3 or len(four["param_devices"]) != 4
            or max(rel) > LOSS_REL_TOL):
        raise AssertionError(f"four-device train step: {four} vs {one}")
    emit("train4", ok=True, device=device, layers=TRAIN_LAYERS,
         loss_rel_diff=rel, tolerance=LOSS_REL_TOL, four=four, one=one)


def _child_serve4(args) -> None:
    """LLMServer with tensor_parallel=4 against tensor_parallel=1 on the
    same requests. Greedy tokens agree until the first near-tie of two
    logits (random weights make those common), so the check is on where the
    streams part, not on equality: chance agreement is 1 in the vocabulary."""
    device = require_tpu(4)
    cfg = _model(SERVE_LAYERS)
    kw = dict(seed=args.seed, num_kv_blocks=KV_BLOCKS // 2,
              prompt_lens=PROMPT_LENS, max_tokens=MAX_TOKENS)
    tp4, tokens4 = serve_phase(cfg, tensor_parallel=4, **kw)
    tp1, tokens1 = serve_phase(cfg, tensor_parallel=1, **kw)
    greedy = list(tokens1)[1:]      # request 0 samples
    common = {}
    for rid in greedy:
        a, b = tokens4[rid], tokens1[rid]
        common[rid] = next((i for i, (x, y) in enumerate(zip(a, b))
                            if x != y), len(a))
    first_ok = sum(1 for n in common.values() if n >= 1)
    if (tp4["attention_impl"] != "pallas" or len(tp4["param_devices"]) != 4
            or first_ok < len(greedy) - 1):
        raise AssertionError(f"tensor_parallel=4: {tp4}, common prefix "
                             f"with tensor_parallel=1: {common}")
    emit("serve4", ok=True, device=device, layers=SERVE_LAYERS,
         common_prefix_tokens=common, of=MAX_TOKENS, tp4=tp4, tp1=tp1)


CHILDREN = {"kernels": _child_kernels, "serve": _child_serve,
            "train": _child_train, "long_context": _child_long_context,
            "train4": _child_train4, "serve4": _child_serve4,
            "sampler_filter": _child_sampler_filter,
            "power_retention": _child_power_retention,
            "retention_check": _child_retention_check,
            "kda": _child_kda, "kda_check": _child_kda_check,
            "latent": _child_latent,
            "glm_dsa": _child_glm_dsa,
            "glm_dsa_check": _child_glm_dsa_check,
            "ssd": _child_ssd, "nemotron_h_check": _child_nemotron_h_check,
            "minicpm_sala_check": _child_minicpm_sala_check,
            "grouped_dot": _child_grouped_dot,
            "afmoe_kernels": _child_afmoe_kernels,
            "afmoe_check": _child_afmoe_check,
            "lfm2_kernels": _child_lfm2_kernels}


# --------------------------------------------------------------------------
# The parent: never initializes JAX.
# --------------------------------------------------------------------------

def run_child(phase: str, args, timeout: float) -> dict:
    """Run one phase in a child of its own, pass its lines through, and
    return the phase's own JSON line. The child leads a process group, so
    nothing it started outlives it."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         "--seed", str(args.seed)],
        stdout=subprocess.PIPE, text=True, cwd=ROOT, start_new_session=True)
    timer = threading.Timer(timeout, os.killpg, (proc.pid, 9))
    timer.start()
    lines = []
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            lines.append(line)
        proc.wait()
    finally:
        timer.cancel()
        try:
            os.killpg(proc.pid, 9)
        except ProcessLookupError:
            pass
    if proc.returncode != 0:
        raise SystemExit(f"chip_smoke: phase {phase} failed "
                         f"(exit {proc.returncode})")
    for line in reversed(lines):
        if line.startswith("{"):
            result = json.loads(line)
            if result.get("phase") == phase and result.get("ok") is True:
                return result
    raise SystemExit(f"chip_smoke: phase {phase} printed no result")


def emit_cache(after: str) -> None:
    """What the compile cache holds now. Where it is capped from outside
    (JAX_COMPILATION_CACHE_MAX_SIZE) and the programs outgrow the cap, the
    oldest are evicted and a later phase compiles them again."""
    path = os.environ["JAX_COMPILATION_CACHE_DIR"]
    files = [os.path.join(path, f) for f in os.listdir(path)] \
        if os.path.isdir(path) else []
    emit("cache", after=after, dir=path, entries=len(files),
         bytes=sum(os.path.getsize(f) for f in files),
         max_bytes=os.environ.get("JAX_COMPILATION_CACHE_MAX_SIZE"))


def _libtpu_loaded() -> bool:
    with open("/proc/self/maps") as f:
        return "libtpu" in f.read()


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sweep", default="",
                    help="--phase power_retention: FOLDxWALK_TILESxUNROLL, ...; "
                         "--phase glm_dsa: tokens a walk of the index "
                         "kernel, e.g. 8,32 (its leg alone); --phase "
                         "grouped_dot: [cell+cell:]ROW_TILExK_TILE, ...; "
                         "--phase ssd, --phase kda: folds for the decode "
                         "rows, e.g. 8,16,32; --phase latent, --phase afmoe_kernels, "
                         "--phase lfm2_kernels: pages a step ONExMANY, ...")
    ap.add_argument("--phase", choices=sorted(CHILDREN),
                    help="internal: run this phase in this process")
    args = ap.parse_args()
    # JAX serializes a Pallas kernel's Mosaic module with its locations, and
    # by default a location is the Python call stack. The cache key of every
    # step program would then depend on who called it, and the cluster's
    # replica would find none of what `serve` compiled. A phase run alone
    # takes it too: a kernel's events are then named as the benchmark's are
    # (`kda_call.<n>`, which `traced_ms` looks for).
    os.environ.setdefault("JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS", "0")
    if args.phase:
        CHILDREN[args.phase](args)
        return

    # Before any child or worker starts, so that all of them inherit it.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
    import ray_tpu  # noqa: F401

    if "jax" in sys.modules:
        raise SystemExit("chip_smoke: `import ray_tpu` imported jax")
    t0 = time.time()
    if args.chips == 4:
        device = run_child("train4", args, timeout=900)["device"]
        device4 = run_child("serve4", args, timeout=1500)["device"]
        if device4 != device:
            raise SystemExit(f"chip_smoke: {device4} != {device}")
    else:
        # train before serve: the replica of `cluster` then finds serve's
        # programs as the cache's newest entries, whatever its cap.
        run_child("train", args, timeout=400)
        emit_cache("train")
        run_child("kernels", args, timeout=300)
        serve = run_child("serve", args, timeout=900)
        device = serve["device"]
        emit_cache("serve")
        # A family with recurrent and window layers, past its window (the
        # benchmark's own check is shorter than that window).
        run_child("long_context", args, timeout=900)
        # The cluster's driver is this process. It imports jax (the model
        # configuration's dtype) but must never load the TPU's library: the
        # replica's worker is the one process that may.
        result, _ = cluster_phase(
            _model(SERVE_LAYERS), seed=args.seed, num_kv_blocks=KV_BLOCKS,
            prompt_lens=PROMPT_LENS[:2], max_tokens=MAX_TOKENS)
        if _libtpu_loaded():
            raise SystemExit("chip_smoke: the parent loaded libtpu")
        if not any("Tpu" in d or "TPU" in d
                   for d in result["replica_devices"]):
            raise SystemExit(f"chip_smoke: replica not on the TPU: {result}")
        emit("cluster", ok=True, serve_warmup_s=serve["warmup_s"], **result)
        emit_cache("cluster")
    emit("done", seconds=round(time.time() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
