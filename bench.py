"""Headline benchmark: flagship Llama training throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "device": {...},
   "detail": {...}}

The reference publishes a scalability envelope, not tokens/sec (BASELINE.md);
the repo's north-star target is Llama-3-8B FSDP at >=45% MFU on v5e. On one
chip we run the same training math (fwd+bwd+adamw, bf16, remat) at a
~1B-parameter configuration and report tokens/sec/chip with model FLOPs
utilization; vs_baseline = achieved_MFU / 0.45 target. `detail` also carries:
  * detail["kernels"] — the Pallas kernels compiled by Mosaic
    (interpret=False) with numerics checks vs the jnp references and
    per-kernel us/op timings.
  * detail["serve"]   — paged-engine serving TTFT p50/p95 + decode tok/s.

There is no CPU mode: a number from a CPU run is not a device number.
Finding no TPU is an error, a device this file has no peak for is an error,
and so is a failed leg — the exit code says so. This file predates the
benchmark contract (ROADMAP S1); `chip_smoke.py` is the proof that the
system runs on the chip.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time

# bf16 peak FLOP/s per chip, keyed by a substring of jax's device_kind
# (Google Cloud TPU documentation, per-generation system architecture pages).
PEAK_FLOPS = {
    "v5 lite": 197e12,   # v5e
    "v5p": 459e12,
    "v4": 275e12,
    "v6 lite": 918e12,   # v6e
}


def require_tpu() -> dict:
    """The device as jax reports it; no TPU is an error, not a fallback."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        raise SystemExit(f"bench: jax found no TPU: {device}")
    return device


def detect_peak() -> float:
    import jax

    kind = jax.devices()[0].device_kind.lower()
    for name, peak in PEAK_FLOPS.items():
        if name in kind:
            return peak
    raise SystemExit(f"bench: no peak FLOP/s known for device kind {kind!r}")


def _emit(result: dict) -> None:
    result["device"] = require_tpu()
    print(json.dumps(result, default=str), flush=True)


def _sync(x) -> float:
    """Force completion via a device->host scalar fetch."""
    import jax.numpy as jnp

    return float(jnp.asarray(x).reshape(-1)[0])


def kernels_bench() -> dict:
    """Compile the Pallas kernels with Mosaic (interpret=False), check
    numerics vs the jnp references, and time them. Returns a dict for
    detail["kernels"]; a kernel that fails to lower raises."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention as att
    from ray_tpu.ops import paged_attention as pa

    out: dict = {}

    def timeit(fn, *args, iters=20):
        fn(*args)  # compile
        _sync(fn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            r = fn(*args)
        _sync(r)
        return (time.perf_counter() - t0) / iters * 1e6  # us/op

    def max_err(got, want):
        return float(np.max(np.abs(np.asarray(got, dtype=np.float32)
                                   - np.asarray(want, dtype=np.float32))))

    # --- flash attention forward -----------------------------------------
    b, sq, h, d = 4, 2048, 16, 128
    key = jax.random.key(0)
    q = jax.random.normal(key, (b, sq, h, d), dtype=jnp.bfloat16)
    k = jax.random.normal(key, (b, sq, h // 2, d), dtype=jnp.bfloat16)
    v = jax.random.normal(key, (b, sq, h // 2, d), dtype=jnp.bfloat16)
    flash = jax.jit(lambda q, k, v: att.flash_attention_fwd(
        q, k, v, causal=True, interpret=False))
    ref = jax.jit(lambda q, k, v: att.mha_reference(q, k, v, causal=True))
    err = max_err(flash(q, k, v), ref(q, k, v))
    us = timeit(flash, q, k, v)
    # attention flops: 4 * b*h*sq^2*d (qk + pv, fwd), causal halves it
    flops = 4 * b * h * sq * sq * d / 2
    out["flash_attention_fwd"] = {
        "ok": err < 0.06, "max_err": round(err, 5),
        "us_per_op": round(us, 1),
        "tflops": round(flops / (us * 1e-6) / 1e12, 2),
        "shape": [b, sq, h, d],
    }

    # --- flash attention backward (training path) ------------------------
    key = jax.random.key(3)
    q = jax.random.normal(key, (b, sq, h, d), dtype=jnp.bfloat16)
    k = jax.random.normal(key, (b, sq, h // 2, d), dtype=jnp.bfloat16)
    v = jax.random.normal(key, (b, sq, h // 2, d), dtype=jnp.bfloat16)
    grad_flash = jax.jit(jax.grad(lambda q, k, v: (att.flash_attention(
        q, k, v, causal=True, interpret=False) ** 2).sum(),
        argnums=(0, 1, 2)))
    grad_ref = jax.jit(jax.grad(lambda q, k, v: (att.mha_reference(
        q, k, v, causal=True) ** 2).sum(), argnums=(0, 1, 2)))
    err = max(max_err(a, b_) for a, b_ in zip(grad_flash(q, k, v),
                                              grad_ref(q, k, v)))
    us = timeit(lambda *a: grad_flash(*a)[0], q, k, v)
    # bwd attention flops ~ 2.5x fwd (dq + dkv recompute), causal halves
    flops = 10 * b * h * sq * sq * d / 2
    out["flash_attention_bwd"] = {
        "ok": err < 0.75,  # grad-of-square amplifies bf16 noise
        "max_err": round(err, 4),
        "us_per_op": round(us, 1),
        "tflops": round(flops / (us * 1e-6) / 1e12, 2),
        "shape": [b, sq, h, d],
    }

    # --- ragged paged attention (decode shape) ---------------------------
    S, Bq, H, hd, K, P, ps, mp = 16, 1, 16, 128, 8, 2048, 16, 128
    key = jax.random.key(1)
    q = jax.random.normal(key, (S, Bq, H, hd), dtype=jnp.bfloat16)
    kp = jax.random.normal(key, (K, P, ps, hd), dtype=jnp.bfloat16)
    vp = jax.random.normal(key, (K, P, ps, hd), dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    kv_lens = jnp.asarray(rng.randint(ps, mp * ps, S), dtype=jnp.int32)
    bt = jnp.asarray(rng.randint(0, P, (S, mp)), dtype=jnp.int32)
    q_pos = kv_lens - Bq
    paged = jax.jit(lambda *a: pa.ragged_paged_attention(
        *a, interpret=False))
    pref = jax.jit(pa.ragged_paged_attention_reference)
    err = max_err(paged(q, kp, vp, bt, kv_lens, q_pos),
                  pref(q, kp, vp, bt, kv_lens, q_pos))
    us = timeit(paged, q, kp, vp, bt, kv_lens, q_pos)
    out["ragged_paged_attention"] = {
        "ok": err < 0.06, "max_err": round(err, 5),
        "us_per_op": round(us, 1),
        "shape": {"S": S, "H": H, "hd": hd, "page": ps,
                  "mean_ctx": int(np.mean(np.asarray(kv_lens)))},
    }
    failed = [name for name, r in out.items() if not r["ok"]]
    if failed:
        raise SystemExit(f"bench: kernels differ from their references: "
                         f"{ {n: out[n] for n in failed} }")
    return out


def serve_bench_result() -> dict:
    """Serving TTFT + decode throughput on one chip via the native paged
    engine (north star: 8B <150ms p50 TTFT on v5e; scaled-down model on the
    single dev chip). Returns a dict for detail["serve"]."""
    import numpy as np

    import jax

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.models import llama

    # ~1.9B-param llama (hd=128 so the Pallas kernel engages) in bf16.
    config = llama.LlamaConfig(
        vocab_size=32000, d_model=2048, n_layers=18, n_heads=16,
        n_kv_heads=8, d_ff=8192, max_seq=2048)
    num_blocks, prompt_len, gen_tokens, n_requests = 1024, 512, 64, 8

    params = llama.init_params(config, jax.random.key(0))
    runner = ModelRunner(config, params, num_blocks=num_blocks,
                         block_size=16, chunk_size=512)
    # ONE engine serves every leg: decode_multi_step=8 makes warmup
    # compile the k-step scan programs alongside the whole grid, and the
    # per-dispatch multi_step flag flips between measurement modes — no
    # second engine, no duplicate warmup.
    engine = LLMEngine(runner, max_batch_size=8,
                       prefill_chunk=512,
                       pipeline_depth=8, decode_multi_step=8)
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, config.vocab_size, prompt_len).tolist()

    # Warmup: precompile the full bucket grid (vLLM-TPU-style), then one
    # real request for the host-side paths. Without the grid warmup the
    # prefix-cache leg's short-suffix bucket compiled INSIDE the timed
    # region (13.2 s "TTFT" in the first r4 live run).
    engine.warmup()  # compiles the k-step scan programs too (flag is 8)
    engine.generate([prompt], SamplingParams(max_tokens=4))
    engine.multi_step = 1  # sequential-latency legs run single-step

    ttfts, decode_times, decoded = [], [], 0
    for _ in range(n_requests):
        p = rng.randint(1, config.vocab_size, prompt_len).tolist()
        t0 = time.perf_counter()
        first_at = None
        for i, _tok in enumerate(engine.stream(
                p, SamplingParams(max_tokens=gen_tokens))):
            if i == 0:
                first_at = time.perf_counter() - t0
        total = time.perf_counter() - t0
        ttfts.append(first_at)
        decode_times.append(total - first_at)
        decoded += gen_tokens - 1
    # Prefix-cache TTFT: a request whose prompt shares a long cached
    # prefix (the agent/system-prompt serving pattern) skips that
    # prefill compute entirely.
    shared = rng.randint(1, config.vocab_size, prompt_len).tolist()
    t0 = time.perf_counter()
    for i, _tok in enumerate(engine.stream(
            shared, SamplingParams(max_tokens=4))):
        if i == 0:
            cold_ttft = time.perf_counter() - t0
    tail = rng.randint(1, config.vocab_size, 8).tolist()
    t0 = time.perf_counter()
    for i, _tok in enumerate(engine.stream(
            shared[:-8] + tail, SamplingParams(max_tokens=4))):
        if i == 0:
            warm_ttft = time.perf_counter() - t0
    ttfts.sort()
    p50 = ttfts[len(ttfts) // 2]
    p95 = ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.95))]
    decode_tok_s = decoded / max(sum(decode_times), 1e-9)

    # Multi-step decode probe: k tokens per dispatch via the on-device
    # scan. Same engine, flag flipped — the scan programs were compiled
    # in the single warmup above. Where dispatch latency rivals per-token
    # compute this is the decode-throughput lever; the headline decode
    # number reports the better of the two.
    multi_k = 8
    engine.multi_step = multi_k
    m_decoded, m_time = 0, 0.0
    for _ in range(n_requests):
        p = rng.randint(1, config.vocab_size, prompt_len).tolist()
        t0 = time.perf_counter()
        first_at = None
        for i, _tok in enumerate(engine.stream(
                p, SamplingParams(max_tokens=gen_tokens))):
            if i == 0:
                first_at = time.perf_counter() - t0
        m_time += time.perf_counter() - t0 - first_at
        # The first yield lands after a FULL k-token dispatch, so the
        # post-first_at window covers gen_tokens - k tokens (counting
        # gen-1 like the single-step leg would inflate this number by
        # ~k/gen and could crown multi-step on measurement bias).
        m_decoded += max(gen_tokens - multi_k, 1)
    multi_tok_s = m_decoded / max(m_time, 1e-9)

    # Throughput under load: all requests in flight at once — continuous
    # batching aggregates decode across the whole batch (the number that
    # scales serving cost, vs the latency-oriented sequential runs above).
    # Swept over concurrency levels for a SATURATION curve: past the
    # running-batch/page capacity, extra requests queue and the aggregate
    # should plateau, not fall — that plateau is the chip's serving
    # capacity. The first (base) level feeds the headline throughput
    # number.
    saturation = {}
    levels = (n_requests, 32, 128)
    engine.multi_step = multi_k if multi_tok_s > decode_tok_s else 1
    for level in levels:
        # Concurrent admission batches the prefills into ONE (batch, chunk)
        # dispatch — a bucket the LIGHT warmup above deliberately skips
        # (production servers warmup(full=True)). One untimed pass with the
        # same admission shape compiles it; fresh random prompts in the
        # timed pass keep the prefix cache cold so only programs are warm,
        # not KV.
        warm_prompts = [rng.randint(1, config.vocab_size,
                                    prompt_len).tolist()
                        for _ in range(level)]
        engine.generate(warm_prompts, SamplingParams(max_tokens=8))
        prompts = [rng.randint(1, config.vocab_size, prompt_len).tolist()
                   for _ in range(level)]
        t0 = time.perf_counter()
        outs = engine.generate(prompts,
                               SamplingParams(max_tokens=gen_tokens))
        wall = time.perf_counter() - t0
        total = sum(len(o.output_token_ids) for o in outs)
        level_tok_s = total / max(wall, 1e-9)
        saturation[level] = round(level_tok_s, 1)
        if level == n_requests:
            # Only the base level may feed the headline number —
            # promoting a higher-concurrency aggregate would compare
            # across rounds at different concurrency unmarked.
            throughput_tok_s = level_tok_s
    return {
        "ttft_p50_ms": round(p50 * 1000, 2),
        "ttft_p95_ms": round(p95 * 1000, 2),
        "prefix_cache": {
            "cold_ttft_ms": round(cold_ttft * 1000, 2),
            "cached_prefix_ttft_ms": round(warm_ttft * 1000, 2),
            "tokens_saved": int(
                engine.block_manager.prefix_tokens_saved),
        },
        "vs_target": round(0.150 / max(p50, 1e-9), 3),  # >1 beats 150ms
        "decode_tokens_per_sec": round(max(decode_tok_s, multi_tok_s), 1),
        "decode_single_step": round(decode_tok_s, 1),
        "decode_multi_step_k": multi_k,
        "decode_multi_step": round(multi_tok_s, 1),
        "throughput_tokens_per_sec": round(throughput_tok_s, 1),
        "saturation_curve": saturation,   # concurrency -> aggregate tok/s
        "prompt_len": prompt_len,
        "gen_tokens": gen_tokens,
        "requests": n_requests,
        "attention_impl": runner.attention_impl,
        "params_b": round(config.num_params() / 1e9, 3),
    }


def serve_bench():
    """`python bench.py --serve`: standalone serving probe."""
    require_tpu()
    result = serve_bench_result()
    _emit({
        "metric": "llm_serve_ttft_p50_ms",
        "value": result["ttft_p50_ms"],
        "unit": "ms",
        "vs_baseline": result["vs_target"],
        "detail": result,
    })


def kernels_main():
    """`python bench.py --kernels`: standalone Mosaic kernel validation."""
    require_tpu()
    _emit({
        "metric": "pallas_kernels_ok",
        "value": 1,
        "unit": "bool",
        "vs_baseline": 1,
        "detail": kernels_bench(),
    })


def main():
    require_tpu()
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama

    # ~0.9B params: fits one 16GB v5e chip with bf16 params + adam
    # moments (mu bf16, nu fp32). remat_policy="dots": save matmul
    # outputs, recompute elementwise/scores; batch 6/8 exceed HBM under
    # this policy.
    config = llama.LlamaConfig(
        vocab_size=32000, d_model=2048, n_layers=14, n_heads=16,
        n_kv_heads=8, d_ff=7168, max_seq=2048, remat_policy="dots")
    batch, seq, steps = 4, 2048, 10

    opt = optax.adamw(1e-4, b1=0.9, b2=0.95,
                      mu_dtype=jnp.bfloat16)

    def init_state_for(cfg):
        @jax.jit
        def _init(key):
            params = llama.init_params(cfg, key)
            return {"params": params, "opt": opt.init(params)}

        return _init

    init_state = init_state_for(config)

    from functools import partial

    def make_step(cfg):
        """One jitted train step closed over cfg — shared by the impl
        probes and the headline run so probe math can never drift from
        the timed math, and the winner's compiled step is REUSED."""

        @partial(jax.jit, donate_argnums=(0,))
        def step(state, tokens):
            def loss(p):
                l, _m = llama.loss_fn(p, {"tokens": tokens}, cfg)
                return l

            l, grads = jax.value_and_grad(loss)(state["params"])
            updates, opt_state = opt.update(grads, state["opt"],
                                            state["params"])
            return {"params": optax.apply_updates(state["params"], updates),
                    "opt": opt_state}, l

        return step

    def timed_steps(step_fn, st, toks, n):
        """Seconds per step over n steps after compile + settle."""
        for _i in range(2):
            st, l = step_fn(st, toks)
            _ = float(l)
        t0 = time.perf_counter()
        for _i in range(n):
            st, l = step_fn(st, toks)
        loss = float(l)  # forces completion of the whole chain
        return (time.perf_counter() - t0) / n, loss

    def timed_if_it_fits(step_fn, st, toks, n):
        """timed_steps, or None where the program does not fit the chip's
        memory: a reading for a probe that looks for what fits, not a
        failed leg. Any other failure raises."""
        try:
            return timed_steps(step_fn, st, toks, n)
        except jax.errors.JaxRuntimeError as exc:
            if "RESOURCE_EXHAUSTED" not in str(exc):
                raise
            return None

    def longctx_probe(base_cfg):
        """Train-step throughput at long sequence, batch 1. Flash-only:
        past 4k the unfused reference attention materializes (1, h, s, s)
        fp32 scores — the Pallas fwd+bwd (ops/attention.py) is what makes
        long context fit at all. Remat policy per seq: "dots" up to 8k;
        "flash" (save the kernel's out+lse, skip its O(s^2) recompute in
        backward — models/llama.py) at 16k/32k, where "dots" busts HBM
        and full remat pays the quadratic kernel twice. 3 timed steps
        after compile per point; a point that does not fit is recorded."""
        import gc

        points = []
        for lc_seq, policy in ((8192, "dots"), (16384, "flash"),
                               (32768, "flash")):
            cfg = dataclasses.replace(base_cfg, max_seq=lc_seq,
                                      attention_impl="flash",
                                      remat_policy=policy)
            lc_tokens = jax.random.randint(
                jax.random.key(3), (1, lc_seq + 1), 0, cfg.vocab_size)
            timed = timed_if_it_fits(
                make_step(cfg), init_state_for(cfg)(jax.random.key(2)),
                lc_tokens, 3)
            gc.collect()  # the point's state must not meet the next one's
            if timed is None:
                points.append({"seq": lc_seq, "remat_policy": policy,
                               "fits": False})
                continue
            sps, lc_loss = timed
            tok_s = lc_seq / sps
            mfu = tok_s * cfg.flops_per_token(lc_seq) / detect_peak()
            points.append({"seq": lc_seq, "batch": 1,
                           "tokens_per_sec": round(tok_s, 1),
                           "mfu": round(mfu, 4), "steps": 3,
                           "loss": lc_loss, "remat_policy": policy,
                           "attention_impl": "flash"})
        return points

    # Attention impl self-selection: "auto" routes this config (hd=128,
    # seq=2048) through the Pallas flash fwd+bwd on TPU. Race short probes
    # of it and the XLA-fused reference and train with the winner; a
    # candidate that fails is an error.
    tokens0 = jax.random.randint(jax.random.key(1), (batch, seq + 1),
                                 0, config.vocab_size)
    candidates, attn_probe = {}, {}
    for impl in ("reference", "flash"):
        candidates[impl] = make_step(
            dataclasses.replace(config, attention_impl=impl))
        sps, _ = timed_steps(candidates[impl], init_state(jax.random.key(0)),
                             tokens0, 5)
        attn_probe[impl] = round(sps, 4)
    attn_impl = min(attn_probe, key=attn_probe.get)
    config = dataclasses.replace(config, attention_impl=attn_impl)
    train_step = candidates[attn_impl]

    # Batch-size probe: the batch-scaling curve (4/8/16 x 2048). "dots"
    # exceeds HBM at batch >= 6, so the larger batches run the "flash"
    # remat policy (save the kernel's out+lse only; O(s) memory), then full
    # remat. Compare tokens/s (not s/step) against the batch-4 winner and
    # train with whichever batch feeds the MXU best. A batch that does not
    # fit the chip is a reading of this probe (recorded, loses the race);
    # any other failure is an error.
    batch_probe = {batch: round(batch * seq / attn_probe[attn_impl], 1)}
    batch_policy = {batch: config.remat_policy}
    best_tok_s = batch_probe[batch]
    for bsz in (8, 16):
        for policy in ("flash", "full"):
            step_b = make_step(dataclasses.replace(config,
                                                   remat_policy=policy))
            toks_b = jax.random.randint(
                jax.random.key(1), (bsz, seq + 1), 0, config.vocab_size)
            timed = timed_if_it_fits(
                step_b, init_state(jax.random.key(0)), toks_b, 5)
            if timed is None:
                batch_probe[f"{bsz}/{policy}"] = "does not fit"
                continue
            batch_probe[bsz] = round(bsz * seq / timed[0], 1)
            batch_policy[bsz] = policy
            if batch_probe[bsz] > best_tok_s:
                # The headline must run the policy its winning batch was
                # probed with — a flash-policy winner under "dots" would
                # not fit at batch 8/16.
                batch, best_tok_s, train_step = bsz, batch_probe[bsz], step_b
                config = dataclasses.replace(config, remat_policy=policy)
            break
    attn_probe["batch_tokens_per_s"] = batch_probe
    attn_probe["batch_remat_policy"] = batch_policy

    tokens = jax.random.randint(jax.random.key(1), (batch, seq + 1), 0,
                                config.vocab_size)
    sps, final_loss = timed_steps(train_step, init_state(jax.random.key(0)),
                                  tokens, steps)
    tokens_per_step = batch * seq
    tok_s = tokens_per_step / sps
    mfu = tok_s * config.flops_per_token(seq) / detect_peak()

    result = {
        "metric": "llama1b_train_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "detail": {
            "mfu": round(mfu, 4),
            "params_b": round(config.num_params() / 1e9, 3),
            "batch_tokens": tokens_per_step,
            "steps": steps,
            "loss": final_loss,
            "attention_impl": config.attention_impl,
            "attn_probe_s_per_step": attn_probe,
        },
    }
    # Secondary legs ride the same invocation; a leg that fails fails the
    # run. The training state above is out of scope by now: the serve leg
    # allocates a ~1.9B-param model + KV cache and must not compete with
    # ~7GB of dead training state on a 16GB chip. Long context runs BEFORE
    # serve for the same reason.
    import gc

    gc.collect()
    result["detail"]["kernels"] = kernels_bench()
    result["detail"]["long_context"] = longctx_probe(config)
    gc.collect()
    result["detail"]["serve"] = serve_bench_result()
    _emit(result)


if __name__ == "__main__":
    if "--serve" in sys.argv:
        serve_bench()
    elif "--kernels" in sys.argv:
        kernels_main()
    else:
        main()
