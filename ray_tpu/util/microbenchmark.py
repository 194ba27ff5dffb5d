"""Core-runtime microbenchmarks.

Reference analog: python/ray/_private/ray_perf.py:93-315 (the `ray
microbenchmark` CLI): put/get ops, task throughput sync/async, 1:1 and
n:n actor call rates — the numbers the release pipeline tracks per build.
Run via `python -m ray_tpu.scripts microbenchmark [--scale N]`.
"""

from __future__ import annotations

import json
import time
from typing import Dict, List


def _rate(n: int, seconds: float) -> float:
    return n / max(seconds, 1e-9)


def run(scale: float = 1.0, num_cpus: int = 4) -> List[Dict]:
    import numpy as np

    import ray_tpu

    owns_cluster = not ray_tpu.is_initialized()
    if owns_cluster:
        ray_tpu.init(num_cpus=num_cpus)
    results: List[Dict] = []

    def record(name: str, n: int, seconds: float, unit: str = "ops/s"):
        results.append({"benchmark": name, "value": round(_rate(n, seconds), 1),
                        "unit": unit, "n": n})

    try:
        # -- object store ------------------------------------------------
        n = int(1000 * scale)
        t0 = time.perf_counter()
        refs = [ray_tpu.put(i) for i in range(n)]
        record("put_small_ops", n, time.perf_counter() - t0)
        t0 = time.perf_counter()
        ray_tpu.get(refs)
        record("get_small_ops", n, time.perf_counter() - t0)
        del refs

        m = max(4, int(64 * scale))
        payload = np.zeros(1 << 20, dtype=np.uint8)  # 1 MiB
        # Warmup: settle cluster-boot CPU contention and page-fault the
        # arena region this loop will reuse (steady-state bandwidth is the
        # number the release pipeline tracks; ray_perf.py warms up too).
        # The first large put triggers the driver's lazy arena-prefault
        # walk. On small boxes that walk competes with the copy loop for
        # the same cores, so wait for it to finish before timing
        # (production hosts hide the walk behind spare cores; the steady
        # state is the tracked number).
        from ray_tpu.core.worker import global_worker

        warm_refs = [ray_tpu.put(payload)]
        store = global_worker().store
        deadline = time.monotonic() + 15.0
        while (store is not None and not store.prefaulted
               and store.prefault_inflight  # never-warm hosts: don't stall
               and time.monotonic() < deadline):
            time.sleep(0.1)
        warm_refs += [ray_tpu.put(payload) for _ in range(min(32, m))]
        # Free the warmup objects deterministically so trial occupancy
        # (3 x m MiB) doesn't depend on GC timing on small stores.
        del warm_refs
        # Best of 3 trials: on small/shared boxes a single descheduling
        # blip inside one trial halves the apparent bandwidth, so the
        # bandwidth legs report peak steady state (standard for bandwidth
        # suites — STREAM does the same).
        put_best = get_best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            big = [ray_tpu.put(payload) for _ in range(m)]
            dt = time.perf_counter() - t0
            put_best = max(put_best, m / (1 << 10) / max(dt, 1e-9))
            t0 = time.perf_counter()
            ray_tpu.get(big)
            dt = time.perf_counter() - t0
            get_best = max(get_best, m / (1 << 10) / max(dt, 1e-9))
            del big
        results.append({"benchmark": "put_1mib_gbps",
                        "value": round(put_best, 3),
                        "unit": "GiB/s", "n": m, "trials": 3})
        results.append({"benchmark": "get_1mib_gbps",
                        "value": round(get_best, 3),
                        "unit": "GiB/s", "n": m, "trials": 3})

        # -- tasks -------------------------------------------------------
        @ray_tpu.remote
        def nop():
            return None

        # Warm the WHOLE worker pool (a single probe task would leave the
        # batch benchmarks measuring process-spawn ramp, not steady state).
        ray_tpu.get([nop.remote() for _ in range(num_cpus * 8)], timeout=300)
        n = int(100 * scale)
        t0 = time.perf_counter()
        for _ in range(n):
            ray_tpu.get(nop.remote(), timeout=120)
        record("tasks_sync", n, time.perf_counter() - t0)

        n = int(500 * scale)
        t0 = time.perf_counter()
        ray_tpu.get([nop.remote() for _ in range(n)], timeout=300)
        record("tasks_async_batch", n, time.perf_counter() - t0)

        # -- actors ------------------------------------------------------
        @ray_tpu.remote
        class Actor:
            def noop(self):
                return None

        a = Actor.remote()
        ray_tpu.get(a.noop.remote(), timeout=120)
        n = int(200 * scale)
        t0 = time.perf_counter()
        for _ in range(n):
            ray_tpu.get(a.noop.remote(), timeout=120)
        record("actor_calls_sync_1_1", n, time.perf_counter() - t0)

        n = int(1000 * scale)
        t0 = time.perf_counter()
        ray_tpu.get([a.noop.remote() for _ in range(n)], timeout=300)
        record("actor_calls_async_1_1", n, time.perf_counter() - t0)

        workers = [Actor.remote() for _ in range(4)]
        for w in workers:
            ray_tpu.get(w.noop.remote(), timeout=120)
        n = int(250 * scale)
        t0 = time.perf_counter()
        ray_tpu.get([w.noop.remote() for w in workers for _ in range(n)],
                    timeout=300)
        record("actor_calls_async_n_n", n * len(workers),
               time.perf_counter() - t0)
        # Benchmark actors must not outlive the run on a shared cluster.
        for actor in [a, *workers]:
            try:
                ray_tpu.kill(actor)
            except Exception:
                pass

        # -- compiled-graph channels vs actor RPC ------------------------
        # The zero-copy number the compiled-DAG work exists for: hand a
        # 1 MiB device activation to another actor and back, once over
        # DeviceChannels (raw bytes through the shm ring, no pickle) and
        # once as a plain actor call (task submission + object store).
        results.extend(_bench_channel_vs_rpc(scale))

        # -- out-of-graph collectives: ring vs hub -----------------------
        results.extend(_bench_collectives(scale))

        # -- LLM serving plane: router affinity + disaggregation ---------
        results.extend(_bench_serve_mixed(scale))

        # -- LLM fleet resilience: failover replay + live migration ------
        results.extend(_bench_serve_resilience(scale))

        # -- tiered prefix store: cluster-table adopt vs re-prefill ------
        results.extend(_bench_serve_prefix_store(scale))

        # -- closed-loop load sweep: 1->N replicas, drain churn mid-run --
        results.extend(_bench_serve_load_sweep(scale))

        # -- RLHF pipeline: colocated vs disaggregated placement ---------
        results.extend(_bench_rlhf(scale))

        # -- checkpoint plane: sync stall vs async snapshot-only stall ---
        results.extend(_bench_checkpoint(scale))

        # -- streaming data plane: pipelined ingestion vs bulk batch -----
        results.extend(_bench_data_stream(scale))

        # -- metrics history plane: ingest rate, query ms, serve overhead
        results.extend(_bench_metrics_history(scale))

        # -- control-plane scale envelope: batched vs per-item leases ----
        results.extend(_bench_scale_envelope(scale))
    finally:
        if owns_cluster:
            ray_tpu.shutdown()
    return results


def _bench_channel_vs_rpc(scale: float) -> List[Dict]:
    """1 MiB activation stream: driver -> actor -> driver, via DeviceChannels
    and via actor RPC. This is the pipeline-parallel steady state — a stream
    of microbatch activations through a stage — not a synchronous ping-pong,
    so both legs are run with in-flight depth (ring capacity / async task
    batch) and report the best of 3 steady-state windows (same rationale as
    the put/get bandwidth legs above: one descheduling blip on a small box
    halves a single trial). Items/s and effective GiB/s (2 MiB per item)."""
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.dag.channel import ChannelClosed
    from ray_tpu.dag.device_channel import DeviceChannel

    @ray_tpu.remote
    class _Relay:
        def pump(self, in_ch, out_ch):
            n = 0
            try:
                while True:
                    out_ch.write(in_ch.read())
                    n += 1
            except ChannelClosed:
                pass
            finally:
                in_ch.close_read()
                try:
                    out_ch.close_write(timeout=10)
                except BaseException:
                    pass
                in_ch.drain()
            return n

        def echo(self, x):
            return x

    payload = jnp.zeros((1 << 18,), dtype=jnp.float32)  # 1 MiB on device
    n = max(8, int(64 * scale))
    depth = 8  # in-flight items: ring slack / async task window
    out: List[Dict] = []

    def _record(name: str, items: int, dt: float):
        out.append({"benchmark": name, "value": round(_rate(items, dt), 1),
                    "unit": "items/s", "n": items, "trials": 3})
        out.append({"benchmark": f"{name}_gbps",
                    "value": round(2 * items / (1 << 10) / max(dt, 1e-9), 3),
                    "unit": "GiB/s", "n": items, "trials": 3})

    relay = _Relay.remote()
    in_ch = DeviceChannel(capacity=depth + 1)
    out_ch = DeviceChannel(capacity=depth + 1)
    pump_ref = relay.pump.remote(in_ch, out_ch)
    for _ in range(4):  # warmup: channel opens + jit-free steady state
        in_ch.write(payload, timeout=60)
        out_ch.read(timeout=60)
    # Fill the ring to depth once, then time windows with the pipeline kept
    # full throughout — every timed item is one write + one read at steady
    # state, never the fill/drain ramps.
    for _ in range(depth):
        in_ch.write(payload, timeout=60)
    chan_best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            in_ch.write(payload, timeout=60)
            out_ch.read(timeout=60)
        chan_best = max(chan_best, n / (time.perf_counter() - t0))
    for _ in range(depth):
        out_ch.read(timeout=60)
    _record("channel_stream_1mib", n, n / chan_best)
    in_ch.close_write(timeout=10)
    try:
        while True:
            out_ch.read(timeout=10)
    except (ChannelClosed, TimeoutError):
        pass
    out_ch.close_read()
    out_ch.drain()
    ray_tpu.get(pump_ref, timeout=60)

    for _ in range(4):
        ray_tpu.get(relay.echo.remote(payload), timeout=60)
    pending = []
    for _ in range(depth):
        pending.append(relay.echo.remote(payload))
    rpc_best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            pending.append(relay.echo.remote(payload))
            ray_tpu.get(pending.pop(0), timeout=60)
        rpc_best = max(rpc_best, n / (time.perf_counter() - t0))
    for ref in pending:
        ray_tpu.get(ref, timeout=60)
    _record("rpc_stream_1mib", n, n / rpc_best)
    try:
        ray_tpu.kill(relay)
    except Exception:
        pass
    out.extend(_bench_pipeline_step(scale))
    return out


def _bench_pipeline_step(scale: float) -> List[Dict]:
    """End-to-end pipeline steady state: a 2-stage ActorPipeline train step
    over DeviceChannels (persistent loops, static schedules, zero host
    pickling) vs the same step over per-op actor RPC (one task per fwd/bwd,
    activations through the object plane). The channel win here is the
    number the compiled-DAG work exists for — it includes everything the
    raw stream legs leave out: task dispatch, driver coordination, and
    stage overlap."""
    import jax
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.models import llama
    from ray_tpu.parallel.pipeline import ActorPipeline

    config = llama.LlamaConfig.tiny(n_layers=4, max_seq=32,
                                    dtype=jnp.float32, remat=False)
    params = llama.init_params(config, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 33), 0,
                                config.vocab_size)
    n = max(3, int(16 * scale))
    out: List[Dict] = []
    for transport in ("channel", "rpc"):
        pipe = ActorPipeline(config, params, n_stages=2, lr=1e-3,
                             transport=transport)
        for _ in range(2):  # warmup: jit compilation + loop launch
            pipe.train_step(tokens, n_microbatches=4)
        t0 = time.perf_counter()
        for _ in range(n):
            pipe.train_step(tokens, n_microbatches=4)
        dt = time.perf_counter() - t0
        pipe.shutdown()
        for actor in pipe.actors:
            try:
                ray_tpu.kill(actor)
            except Exception:
                pass
        out.append({"benchmark": f"pipeline_step_{transport}",
                    "value": round(_rate(n, dt), 2), "unit": "steps/s",
                    "n": n})
    return out


def _bench_collectives(scale: float) -> List[Dict]:
    """Out-of-graph collective data plane: chunked zero-pickle ring vs the
    legacy rank-0 hub, 4 thread-hosted TCPCommunicators over an in-memory
    KV (pure transport, no cluster in the loop). Two pairs of legs:

      * allreduce_{ring,hub}_16mib — one 16 MiB float32 allreduce at 4
        ranks; MiB/s of reduced payload (best of 3: the ring-vs-hub RATIO
        is the tracked number and one descheduling blip inside a trial on
        a small box would corrupt it).
      * ddp_grads_{bucketed,flat} — allreduce_gradients steady state on a
        32-leaf ~8 MiB gradient pytree: per-dtype 4 MiB buckets launched
        async as they fill (overlapped) vs the old concatenate-everything
        single blocking reduction.
    """
    import threading

    import numpy as np

    from ray_tpu.collective.cpu_group import TCPCommunicator
    from ray_tpu.train.backend import reduce_gradients

    out: List[Dict] = []
    kv, kv_lock = {}, threading.Lock()

    def kv_put(key, value):
        with kv_lock:
            kv[key] = value

    def kv_get(key):
        with kv_lock:
            return kv.get(key)

    world = 4

    def make_group(name, **kwargs):
        comms = [None] * world

        def build(r):
            comms[r] = TCPCommunicator(r, world, name, kv_put, kv_get,
                                       timeout=60, **kwargs)

        ts = [threading.Thread(target=build, args=(r,), daemon=True,
                               name=f"bench-build-{r}") for r in range(world)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        assert all(comms), comms
        return comms

    def par(comms, fn):
        errs = []

        def run_rank(c):
            try:
                fn(c)
            except BaseException as e:  # pragma: no cover
                errs.append(e)

        ts = [threading.Thread(target=run_rank, args=(c,), daemon=True,
                               name=f"bench-rank-{c.rank}") for c in comms]
        for t in ts:
            t.start()
        for t in ts:
            t.join(300)
        if errs:
            raise errs[0]

    mib = 16
    payload = np.ones((mib << 20) // 4, dtype=np.float32)
    for algo in ("hub", "ring"):
        comms = make_group(f"bench-allreduce-{algo}", topology=algo)
        try:
            par(comms, lambda c: c.allreduce(np.ones(64, np.float32), "sum"))
            best = 0.0
            for _ in range(3):
                t0 = time.perf_counter()
                par(comms, lambda c: c.allreduce(payload, "sum"))
                best = max(best, mib / (time.perf_counter() - t0))
            out.append({"benchmark": f"allreduce_{algo}_16mib",
                        "value": round(best, 1), "unit": "MiB/s",
                        "n": mib, "trials": 3})
        finally:
            for c in comms:
                c.close()

    # DDP gradient sync: same tree, flat (the old np.concatenate-everything
    # path) vs bucketed-overlapped (the shipped reduce_gradients).
    grads = {f"layer{i}": np.ones(1 << 16, np.float32) for i in range(32)}

    def flat_reduce(comm):
        flat = np.concatenate([v.ravel() for v in grads.values()])
        reduced = comm.allreduce(flat, op="mean")
        offset, res = 0, {}
        for k, v in grads.items():
            res[k] = reduced[offset:offset + v.size].reshape(v.shape)
            offset += v.size
        return res

    comms = make_group("bench-ddp")
    try:
        steps = max(2, int(4 * scale))
        for name, step_fn in (("ddp_grads_flat", flat_reduce),
                              ("ddp_grads_bucketed",
                               lambda c: reduce_gradients(c, grads))):
            par(comms, step_fn)  # warmup: links + first-op ramp
            best = 0.0
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(steps):
                    par(comms, step_fn)
                best = max(best, steps / (time.perf_counter() - t0))
            out.append({"benchmark": name, "value": round(best, 2),
                        "unit": "steps/s", "n": steps, "trials": 3})
    finally:
        for c in comms:
            c.close()
    return out


def _bench_serve_mixed(scale: float) -> List[Dict]:
    """LLM serving plane (llm/router.py + llm/disagg.py), in-process — two
    tiny fp32 engines on CPU, no serve actors in the loop, so the legs
    isolate routing policy and prefill placement rather than RPC cost.

      * serve_mixed_*_{affinity,random} — a shared-system-prompt workload
        (6 distinct 33-token prefixes, repeated) routed by RouterCore
        prefix affinity vs uniform random over 2 replicas: p99 TTFT,
        aggregate tokens/s, and prefix tokens saved (the hit-rate signal).
      * serve_{unified,disagg}_itl_p99_ms — a chatty stream's p99
        inter-token gap while long prompts continuously arrive: unified
        (prefill slices share each tick with the chatty decode row on one
        replica) vs disaggregated (a PrefillServer runs the long prefills
        and streams KV pages over the handoff wire; decode only decodes).
    """
    import random as _random
    import threading

    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.llm.disagg import PrefillServer
    from ray_tpu.llm.router import RouterCore
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.llm.serving import LLMConfig, LLMServer, build_engine
    from ray_tpu.models import llama

    out: List[Dict] = []
    config = llama.LlamaConfig.tiny(vocab_size=128, max_seq=256,
                                    dtype=jnp.float32)

    def cfg(**kw):
        base = dict(model_config=config, num_kv_blocks=128, block_size=8,
                    max_batch_size=4, prefill_chunk=8, warmup_buckets="off")
        base.update(kw)
        return LLMConfig(**base)

    # ---- router: prefix affinity vs random over 2 replicas -------------
    sys_prompts = [[(s * 11 + 5 * i + 2) % 128 for i in range(65)]
                   for s in range(6)]
    reps = max(2, int(3 * scale))
    order = [sys_prompts[i % 6] for i in range(6 * reps)]

    def drive(eng, prompt, max_tokens=8):
        t0 = time.perf_counter()
        eng.add_request(prompt, SamplingParams(max_tokens=max_tokens))
        ttft, n = None, 0
        while eng.has_unfinished():
            for o in eng.step():
                if o.new_token_ids and ttft is None:
                    ttft = time.perf_counter() - t0
                n += len(o.new_token_ids)
        return ttft if ttft is not None else time.perf_counter() - t0, n

    for mode in ("affinity", "random"):
        # Best of 2 trials (fresh engines + router state each): tokens/s on
        # a small shared box swings ~20% on scheduler noise, while the
        # prefix-savings number is deterministic per policy.
        best_tps, best_ttft, saved, total_tokens = 0.0, float("inf"), 0, 0
        for _ in range(2):
            engines = [build_engine(cfg()) for _ in range(2)]
            for e in engines:  # pay first-hit XLA compiles outside timing
                drive(e, [(3 * i + 1) % 128 for i in range(33)])
            core = RouterCore(2, block_size=8)
            rng = _random.Random(0)
            ttfts: List[float] = []
            total_tokens = 0
            t0 = time.perf_counter()
            for p in order:
                idx = (core.pick(p)[0] if mode == "affinity"
                       else rng.randrange(2))
                ttft, n = drive(engines[idx], p)
                ttfts.append(ttft)
                total_tokens += n
            elapsed = time.perf_counter() - t0
            best_tps = max(best_tps, total_tokens / elapsed)
            best_ttft = min(best_ttft, float(np.percentile(ttfts, 99)))
            saved = sum(e.block_manager.prefix_tokens_saved for e in engines)
        out.append({"benchmark": f"serve_mixed_ttft_p99_ms_{mode}",
                    "value": round(best_ttft * 1e3, 2),
                    "unit": "ms", "n": len(order), "trials": 2})
        out.append({"benchmark": f"serve_mixed_tokens_per_s_{mode}",
                    "value": round(best_tps, 1),
                    "unit": "tokens/s", "n": total_tokens, "trials": 2})
        out.append({"benchmark": f"serve_mixed_prefix_tokens_saved_{mode}",
                    "value": saved, "unit": "tokens", "n": len(order)})

    # ---- disaggregation: chatty inter-token latency under long-prompt
    # pressure. Each long prompt is unique (a shared prefix would let the
    # prefix cache hide the very prefill cost the leg measures).
    chatty_tokens = max(40, int(120 * scale))
    long_seq = [0]

    def next_long():
        long_seq[0] += 1
        j = long_seq[0]
        return [(13 * i + 7 * j + j * j) % 128 for i in range(225)]

    def chatty_gaps(server, submit_long):
        stop = threading.Event()
        done = [0]                 # pressure completions (2 tokens each)

        def pressure():
            while not stop.is_set():
                try:
                    submit_long()
                except Exception:
                    return
                done[0] += 1

        # Two pressure threads keep a long prefill in flight continuously —
        # a lone thread leaves idle windows between requests that let the
        # one-replica legs decode unimpeded and corrupt the comparison.
        ts = [threading.Thread(target=pressure, daemon=True,
                               name=f"bench-pressure-{i}")
              for i in range(2)]
        gen = server.completions_stream(
            {"prompt": [3, 1, 4, 1, 5], "max_tokens": chatty_tokens})
        next(gen)                  # chatty decoding before pressure starts
        for t in ts:
            t.start()
        gaps, t0 = [], time.perf_counter()
        last = t0
        for chunk in gen:
            now = time.perf_counter()
            if chunk.get("token") is not None:
                gaps.append(now - last)
                last = now
        elapsed = last - t0
        stop.set()
        for t in ts:
            t.join(60)
        return gaps, elapsed, done[0]

    # One-shot 225-token prefill chunks: the regime disaggregation targets
    # is an expensive chunk stalling the decode batch (big models / long
    # prompts); chunk=8 on the tiny model makes a chunk as cheap as a
    # decode step and measures nothing.
    # The unified leg runs a 64-token budget: the composer slices the
    # 225-token prefills across ticks with the chatty decode row riding
    # EVERY launch, so the inter-token gap is one small mixed launch.
    unified = LLMServer(cfg(prefill_chunk=256, token_budget=64))
    decode = LLMServer(cfg(prefill_chunk=256, disaggregate=1))
    addr = decode.handoff_address()

    # The prefill tier runs on its own hardware in production; on this
    # shared bench box, running its compute concurrently would bill the
    # decode leg for the very work disaggregation moves off-replica. So
    # prefill the long prompts UNTIMED and have the pressure thread replay
    # the captured handoffs over the real wire — socket receive, page
    # adoption, and the adopted requests' decode ARE the decode replica's
    # steady-state costs, and they stay in the timed window.
    from ray_tpu.llm.disagg import send_handoff

    peng = build_engine(cfg(prefill_chunk=256), prefill_only=True)

    def capture_handoffs(n):
        pre = []
        for _ in range(n):
            rid = peng.add_request(next_long(), SamplingParams(max_tokens=2))
            while not any(o.request_id == rid for o in peng.step()):
                pass
            state = peng.export_request(rid)
            blocks = state.pop("blocks")
            pages = peng.runner.gather_pages(blocks)
            peng.block_manager.release_blocks(blocks)
            pre.append((state, pages))
        return pre

    def replay_handoff(pre):
        state, pages = pre.pop()   # IndexError when drained ends the thread
        send_handoff(addr, state, *pages)
        decode.completions_collect(state["id"])

    # The unified leg runs with tracing OFF and the traced leg — the SAME
    # server, same workload, already warm — with tracing ON: their tokens/s
    # ratio is the per-request tracing overhead, budgeted at <=2% (the
    # spans are ring appends and a handful of time.time() calls; anything
    # bigger means a span landed on the per-token hot path). Sharing the
    # engine keeps compile/warmup state identical across the pair.
    from ray_tpu.util import tracing as _tracing

    legs = (("unified", unified,
             lambda _pre: unified.completions(
                 {"prompt": next_long(), "max_tokens": 2}),
             lambda: None, False),
            ("traced", unified,
             lambda _pre: unified.completions(
                 {"prompt": next_long(), "max_tokens": 2}),
             lambda: None, True),
            ("disagg", decode, replay_handoff,
             lambda: capture_handoffs(80), None))
    tps_by_leg: Dict[str, float] = {}
    # Best of 2 trials per leg: a descheduling blip in the pressure thread
    # on a small box corrupts the tail the leg exists to compare.
    for name, server, submit_long, setup, trace_on in legs:
        was_enabled = _tracing.enabled()
        if trace_on is not None:
            _tracing.set_enabled(trace_on)
        try:
            best, best_tps, n = float("inf"), 0.0, 0
            for _ in range(2):
                pre = setup()
                gaps, elapsed, done = chatty_gaps(server,
                                                  lambda: submit_long(pre))
                n = len(gaps)
                best = min(best, float(np.percentile(gaps, 99)))
                if elapsed > 0:
                    best_tps = max(best_tps,
                                   (len(gaps) + 2 * done) / elapsed)
        finally:
            _tracing.set_enabled(was_enabled)
        out.append({"benchmark": f"serve_{name}_itl_p99_ms",
                    "value": round(best * 1e3, 2),
                    "unit": "ms", "n": n, "trials": 2})
        # tokens/s under the same pressure (chatty + pressure completions):
        # the guard that a better tail wasn't bought by starving throughput.
        # The disagg leg's pressure tokens ride pre-captured handoffs, not
        # comparable work — only the apples-to-apples legs report it.
        if name in ("unified", "traced"):
            tps_by_leg[name] = best_tps
            out.append({"benchmark": f"serve_{name}_tokens_per_s",
                        "value": round(best_tps, 1),
                        "unit": "tokens/s", "n": n, "trials": 2})
    if tps_by_leg.get("unified") and tps_by_leg.get("traced"):
        overhead = 100.0 * (1.0 - tps_by_leg["traced"]
                            / tps_by_leg["unified"])
        out.append({"benchmark": "serve_tracing_overhead_pct",
                    "value": round(overhead, 2), "unit": "%",
                    "n": 1, "trials": 2})
    return out


def _bench_serve_resilience(scale: float) -> List[Dict]:
    """LLM fleet resilience (llm/router.py FleetSupervisor + llm/serving.py
    migrate_sessions), in-process — tiny fp32 engines, no actors, so the
    legs price the recovery MACHINERY rather than RPC or respawn cost.

      * serve_failover_recovery_ms — wall-clock from a replica call
        failing mid-request to the router handing back the COMPLETED
        response replayed on the survivor (ejection + affinity prune +
        seeded replay, end to end).
      * serve_migrate_session_ms — marginal cost of live-draining one
        mid-decode session: export + KV-page gather, raw-frame wire,
        adoption on a QUIET target. One session per timed migrate, and
        engines are reused across trials, so min-of-trials prices the
        warm machinery — not XLA compiles, and not the target's resumed
        decode of earlier adoptees (that is the request's own remaining
        work, which on this 1-core box would otherwise serialize into
        the measurement).
      * serve_reprefill_baseline_ms — what the same session costs WITHOUT
        migration: full re-prefill of the accumulated context to the
        first token, same reuse discipline. On the tiny CPU model
        re-prefill is cheap, so the gap here is a floor, not the
        headline — it widens with model size and context length.
    """
    import threading

    import jax.numpy as jnp

    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.llm.serving import LLMConfig, LLMServer, build_engine
    from ray_tpu.models import llama

    out: List[Dict] = []
    config = llama.LlamaConfig.tiny(vocab_size=128, max_seq=256,
                                    dtype=jnp.float32)

    def cfg(**kw):
        base = dict(model_config=config, num_kv_blocks=128, block_size=8,
                    max_batch_size=4, prefill_chunk=8, warmup_buckets="off")
        base.update(kw)
        return LLMConfig(**base)

    def prompt(seed, n=65):
        return [(seed * 11 + 5 * i + 2) % 128 for i in range(n)]

    # ---- failover recovery: dead replica -> replayed completion --------
    class DeadReplica:
        """First-pick victim: takes the request, then the 'actor' dies."""

        def completions(self, request):
            raise ConnectionError("replica died mid-call")

        def engine_stats(self):
            return {"running": 0, "waiting": 0, "prefilling": 0,
                    "free_kv_blocks": 128, "total_kv_blocks": 128}

        def abort(self, rid):
            return False

    survivor = LLMServer(cfg())
    survivor.completions({"prompt": prompt(0), "max_tokens": 4})  # compiles
    trials = max(3, int(5 * scale))
    recovery: List[float] = []
    for t in range(trials):
        core = RouterCore(2, fail_threshold=1)
        sup = FleetSupervisor(core, [LocalReplica(DeadReplica(), "dead"),
                                     LocalReplica(survivor, "live")])
        # Pin the session to the dead replica so the timed request always
        # pays the failure (pow2 would dodge it half the time).
        core._session_owner["bench"] = 0
        t0 = time.perf_counter()
        resp = sup.completions({"prompt": prompt(t + 1), "max_tokens": 8,
                                "session_id": "bench"})
        recovery.append(time.perf_counter() - t0)
        assert "choices" in resp and sup.failovers == 1, resp
    out.append({"benchmark": "serve_failover_recovery_ms",
                "value": round(min(recovery) * 1e3, 2),
                "unit": "ms", "n": trials})

    # ---- live migration vs re-prefill ----------------------------------
    # A mid-size model for this pair: migration moves KV BYTES while
    # re-prefill re-runs the MODEL over every context token, so the
    # 2-layer/d64 toy (where 129 tokens prefill in ~8 ms) would understate
    # the gap to nothing. d256x4 keeps compile time tolerable on a CI box
    # while giving prefill real work; production models widen it further.
    mid = llama.LlamaConfig(vocab_size=128, d_model=256, n_layers=4,
                            n_heads=8, n_kv_heads=4, d_ff=1024,
                            max_seq=256, dtype=jnp.float32)
    trials = max(3, int(4 * scale))
    ctx_tokens = 129          # long context = the cost re-prefill repays
    src = LLMServer(cfg(model_config=mid))
    dst = LLMServer(cfg(model_config=mid))
    migrate_ms, reprefill_ms = [], []
    for trial in range(trials):
        rid = f"mig-{trial}"
        req = {"prompt": prompt(trial + 7, ctx_tokens), "max_tokens": 64,
               "request_id": rid}
        th = threading.Thread(target=lambda r=dict(req):
                              _swallow(src.completions, r), daemon=True,
                              name=f"bench-migrate-src-{trial}")
        th.start()
        deadline = time.monotonic() + 30
        while (src.engine_stats()["running"] < 1
               and time.monotonic() < deadline):
            time.sleep(0.005)
        t0 = time.perf_counter()
        summary = src.migrate_sessions(dst.handoff_address())
        if len(summary["migrated"]) == 1:
            migrate_ms.append((time.perf_counter() - t0) * 1e3)
        th.join(30)
        src.resume_admission()
        # Let the adoptee decode out so the next trial's target is quiet.
        deadline = time.monotonic() + 30
        while (dst.engine_stats()["running"] > 0
               and time.monotonic() < deadline):
            time.sleep(0.005)
    # Baseline: the same accumulated context re-prefilled from scratch to
    # its first token (what failover-without-migration costs). One engine
    # reused across trials for the same warm-compile discipline.
    eng = build_engine(cfg(model_config=mid))
    for trial in range(trials):
        t0 = time.perf_counter()
        rid = eng.add_request(prompt(trial + 7, ctx_tokens),
                              SamplingParams(max_tokens=1))
        while not any(o.request_id == rid and o.new_token_ids
                      for o in eng.step()):
            pass
        reprefill_ms.append((time.perf_counter() - t0) * 1e3)
    out.append({"benchmark": "serve_migrate_session_ms",
                "value": round(min(migrate_ms), 2) if migrate_ms else -1.0,
                "unit": "ms", "n": 1, "trials": trials})
    out.append({"benchmark": "serve_reprefill_baseline_ms",
                "value": round(min(reprefill_ms), 2),
                "unit": "ms", "n": 1, "trials": trials})
    return out


def _swallow(fn, *args):
    """Bench collector thread body: resilience errors are the scenario."""
    try:
        fn(*args)
    except Exception:
        pass


def _bench_serve_prefix_store(scale: float) -> List[Dict]:
    """Tiered prefix store (llm/prefix_store.py): what adopting a spilled
    prefix from the GCS cluster table costs vs re-prefilling it.

      * serve_prefix_adopt_ms — first token for the SAME d256x4 /
        129-token contexts as serve_reprefill_baseline_ms, but the
        context's 16 KV blocks were published into the cluster prefix
        table by a (since churned-out) owner engine, so the adopter pays
        a table lookup + page scatter + a 1-block tail prefill instead of
        re-running the model over the full context. The table transport
        is the GCS handler invoked in-process, so the leg prices the
        store machinery (codec, verification, scatter), not RPC.
    """
    import asyncio

    import jax.numpy as jnp

    from ray_tpu.llm.prefix_store import ClusterPrefixStore, HostPrefixTier
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.llm.serving import LLMConfig, build_engine
    from ray_tpu.models import llama
    from ray_tpu.runtime.gcs.server import GcsServer

    mid = llama.LlamaConfig(vocab_size=128, d_model=256, n_layers=4,
                            n_heads=8, n_kv_heads=4, d_ff=1024,
                            max_seq=256, dtype=jnp.float32)
    cfg = LLMConfig(model_config=mid, num_kv_blocks=48, block_size=8,
                    max_batch_size=4, prefill_chunk=8, warmup_buckets="off")

    def prompt(seed, n=65):
        # Same generator as _bench_serve_resilience: seeds trial+7 give
        # bit-identical contexts to the re-prefill baseline's.
        return [(seed * 11 + 5 * i + 2) % 128 for i in range(n)]

    srv = GcsServer()

    def transport(method, m, payload=b""):
        r = asyncio.run(getattr(srv, f"handle_{method}")(None, m, payload))
        return r.m, r.payload

    trials = max(3, int(4 * scale))
    ctx_tokens = 129

    # The owner: a tiny host tier whose watermark demotes straight into
    # the cluster table. Serving each context then churning the pool
    # publishes the context's blocks — the owner then "dies" (is dropped).
    src = build_engine(cfg)
    src.attach_prefix_store(
        host_tier=HostPrefixTier(96 << 10, low_watermark=0.05),
        cluster_store=ClusterPrefixStore(8, replica="bench-owner",
                                         transport=transport))

    def first_token(eng, toks):
        rid = eng.add_request(toks, SamplingParams(max_tokens=1))
        while not any(o.request_id == rid and o.new_token_ids
                      for o in eng.step()):
            pass

    for trial in range(-1, trials):          # -1 = warmup context
        first_token(src, prompt(trial + 7, ctx_tokens))
        for f in range(6):                   # churn: evict -> spill -> demote
            first_token(src, prompt(1000 + trial * 10 + f, 41))
    published = src.cluster_store.published
    del src

    adopter = build_engine(cfg)
    adopter.attach_prefix_store(
        cluster_store=ClusterPrefixStore(8, replica="bench-adopter",
                                         transport=transport))
    first_token(adopter, prompt(6, ctx_tokens))  # warm compile, via adopt
    adopt_ms: List[float] = []
    for trial in range(trials):
        hits0 = adopter.cluster_prefix_hits
        t0 = time.perf_counter()
        first_token(adopter, prompt(trial + 7, ctx_tokens))
        dt = (time.perf_counter() - t0) * 1e3
        if adopter.cluster_prefix_hits - hits0 >= ctx_tokens // 8 - 1:
            adopt_ms.append(dt)              # only count real adoptions
    return [{"benchmark": "serve_prefix_adopt_ms",
             "value": round(min(adopt_ms), 2) if adopt_ms else -1.0,
             "unit": "ms", "n": 1, "trials": trials,
             "published_blocks": published}]


def _bench_serve_load_sweep(scale: float) -> List[Dict]:
    """Closed-loop load sweep over fleet sizes (ROADMAP 2b): N client
    threads each keep exactly one request in flight against a
    FleetSupervisor fronting 1 then 2 in-process replicas, reporting
    decode throughput and p99 TTFT per (replicas, clients) point. Every
    third request asks for max_tokens=1, so its wall latency IS the
    time-to-first-token under the surrounding load — no streaming hooks
    needed. The last point repeats (2 replicas, 4 clients) with a
    drain-based scale-down fired mid-window: the sweep's churn leg, where
    every request must still complete (drain migrates, it never kills).
    """
    import threading

    import jax.numpy as jnp

    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import LLMConfig, LLMServer
    from ray_tpu.models import llama

    config = llama.LlamaConfig.tiny(vocab_size=128, max_seq=256,
                                    dtype=jnp.float32)
    cfg = LLMConfig(model_config=config, num_kv_blocks=128, block_size=8,
                    max_batch_size=4, prefill_chunk=8, warmup_buckets="off")

    def prompt(seed, n=33):
        return [(seed * 11 + 5 * i + 2) % 128 for i in range(n)]

    servers = [LLMServer(cfg), LLMServer(cfg)]
    for s in servers:
        s.completions({"prompt": prompt(0), "max_tokens": 4})  # compiles

    def run_point(n_replicas, clients, n_reqs, churn=False):
        sup = FleetSupervisor(
            RouterCore(n_replicas, block_size=8),
            [LocalReplica(servers[i], f"sweep-{i}")
             for i in range(n_replicas)])
        lock = threading.Lock()
        state = {"next": 0, "tokens": 0, "ttft": [], "errors": 0}

        def client():
            while True:
                with lock:
                    i = state["next"]
                    state["next"] += 1
                if i >= n_reqs:
                    return
                probe = i % 3 == 0
                t0 = time.perf_counter()
                try:
                    resp = sup.completions(
                        {"prompt": prompt(100 + i),
                         "max_tokens": 1 if probe else 16})
                except Exception:
                    with lock:
                        state["errors"] += 1
                    continue
                dt = time.perf_counter() - t0
                with lock:
                    state["tokens"] += len(
                        resp["choices"][0]["token_ids"])
                    if probe:
                        state["ttft"].append(dt)

        threads = [threading.Thread(target=client, daemon=True,
                                    name=f"sweep-client-{c}")
                   for c in range(clients)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        if churn:
            while state["next"] < n_reqs // 3:
                time.sleep(0.002)
            _swallow(sup.drain_replica, 1, 0)  # scale-down under load
        for th in threads:
            th.join(300)
        wall = time.perf_counter() - t0
        ttft = sorted(state["ttft"])
        p99 = ttft[min(len(ttft) - 1, int(0.99 * len(ttft)))] if ttft \
            else -1.0
        return (state["tokens"] / wall, p99 * 1e3, state["errors"])

    out: List[Dict] = []
    n_reqs = max(9, int(18 * scale))
    for n_replicas, clients, churn in ((1, 1, False), (1, 4, False),
                                       (2, 4, False), (2, 4, True)):
        tps, p99_ms, errors = run_point(n_replicas, clients, n_reqs,
                                        churn=churn)
        tag = f"r{n_replicas}_c{clients}" + ("_churn" if churn else "")
        out.append({"benchmark": f"serve_sweep_tokens_per_s_{tag}",
                    "value": round(tps, 1), "unit": "tokens/s",
                    "n": n_reqs, "errors": errors})
        out.append({"benchmark": f"serve_sweep_ttft_p99_ms_{tag}",
                    "value": round(p99_ms, 2), "unit": "ms",
                    "n": n_reqs, "errors": errors})
    return out


def _bench_rlhf(scale: float) -> List[Dict]:
    """RLHF pipeline (rlhf/): the full rollout -> PPO update -> weight-sync
    loop on a tiny fp32 model, once per placement mode.

      * rlhf_colocated_steps_per_s — generator in-process with the driver,
        weight sync via device-channel hot-swap.
      * rlhf_disagg_steps_per_s — generator as a dedicated actor, weight
        sync via object-plane publish + fanout broadcast.
      * rlhf_weight_sync_ms — mean per-iteration sync latency, one value
        per mode. The gap between the modes is the sync tax the adaptive
        placement policy trades against rollout/update goodput.
    """
    from ray_tpu.rlhf import RLHFConfig, RLHFTrainer

    out: List[Dict] = []
    iters = max(2, int(3 * scale))
    model = dict(vocab_size=128, d_model=32, n_layers=2, n_heads=2,
                 n_kv_heads=2, d_ff=64, max_seq=128)
    for mode in ("colocated", "disaggregated"):
        trainer = RLHFTrainer(RLHFConfig(
            model_kwargs=model, placement_mode=mode,
            iterations=iters, prompts_per_iter=2, prompt_len=4,
            max_new_tokens=4, run_name=f"bench-rlhf-{mode}"))
        try:
            t0 = time.perf_counter()
            result = trainer.run()
            elapsed = time.perf_counter() - t0
        finally:
            trainer.shutdown()
        tag = "colocated" if mode == "colocated" else "disagg"
        out.append({"benchmark": f"rlhf_{tag}_steps_per_s",
                    "value": round(iters / elapsed, 3),
                    "unit": "steps/s", "n": iters, "trials": 1})
        sync = result["sync_ms"]
        out.append({"benchmark": "rlhf_weight_sync_ms",
                    "value": round(sum(sync) / max(1, len(sync)), 2),
                    "unit": f"ms ({tag})", "n": len(sync), "trials": 1})
    return out


def run_scale_envelope(n_requests: int = 192, fake_nodes: int = 1000,
                       trials: int = 3) -> Dict[str, Dict]:
    """Control-plane scale envelope: lease throughput and time-to-first-
    lease against a real GCS + real raylet carrying a 1k-fake-node
    cluster view, with worker SPAWN stubbed out (granted leases resolve
    to instantly-ready fake workers) so the numbers isolate the
    scheduling/RPC path — batched LeaseBatchRequestMsg frames vs one
    lease_worker2 call per request.

    Returns {leg_name: {"value", "unit", "n", "trials"}}; shared by the
    microbench CLI and tests/test_scale_envelope.py.
    """
    import asyncio
    import os
    import tempfile
    import time as _time
    from types import SimpleNamespace

    from ray_tpu.config import cfg
    from ray_tpu.runtime import wire
    from ray_tpu.runtime.gcs.server import GcsServer, NodeRecord
    from ray_tpu.runtime.raylet.raylet import Raylet, WorkerHandle
    from ray_tpu.runtime.rpc import RpcClient

    async def _run() -> Dict[str, Dict]:
        gcs = await GcsServer().start()
        # A 1k-node cluster's worth of node records: the raylet's first
        # heartbeat pulls this as its full view snapshot, and every GCS
        # pass that walks nodes walks all of them.
        fakes = []
        for i in range(fake_nodes):
            nid = b"fake" + i.to_bytes(12, "big")
            rec = NodeRecord(nid, ("127.0.0.1", 30000 + i), {"CPU": 4.0},
                             "", False, {})
            gcs._nodes[nid] = rec
            gcs._bump_view(rec)
            fakes.append(rec)
        session = tempfile.mkdtemp(prefix="ray-tpu-scale-bench-")
        raylet = Raylet(gcs.address, session, {"CPU": 1e9}, {},
                        object_store_memory=32 << 20)

        def fake_spawn():
            wid = os.urandom(16)
            proc = SimpleNamespace(poll=lambda: None,
                                   terminate=lambda: None,
                                   kill=lambda: None,
                                   wait=lambda timeout=None: 0, pid=0)
            h = WorkerHandle(wid, proc)
            h.address = ("127.0.0.1", 1)
            h.ready.set()
            raylet._workers[wid] = h
            return h

        raylet._spawn_worker = fake_spawn
        await raylet.start()

        waiters: Dict[bytes, asyncio.Future] = {}

        async def on_push(method, data):
            if method != "lease_grant":
                return
            fut = waiters.pop(data.get("req_id"), None)
            if fut is not None and not fut.done():
                fut.set_result(
                    wire.LeaseReplyMsg.decode(data["m"]).to_reply())

        client = RpcClient(*raylet.server.address, on_push=on_push)
        await client.connect(timeout=15)

        def _reqs(n):
            return [wire.LeaseRequestMsg(resources={"CPU": 1.0},
                                         req_id=os.urandom(8))
                    for _ in range(n)]

        async def lease_batched(reqs) -> List[asyncio.Future]:
            """One lease_batch2 frame; returns a future per entry
            (inline entries resolved, pending ones resolve via push)."""
            loop = asyncio.get_event_loop()
            futs = {r.req_id: loop.create_future() for r in reqs}
            waiters.update(futs)
            encoded = await client.call(
                "lease_batch2",
                m=wire.LeaseBatchRequestMsg(entries=reqs).encode())
            reply = wire.LeaseBatchReplyMsg.decode(encoded)
            for entry in reply.entries:
                fut = futs.get(entry.req_id)
                if fut is not None and not fut.done():
                    waiters.pop(entry.req_id, None)
                    fut.set_result(entry.to_reply())
            return list(futs.values())

        async def lease_per_item(req) -> dict:
            encoded = await client.call("lease_worker2", m=req.encode())
            return wire.LeaseReplyMsg.decode(encoded).to_reply()

        def _refresh_fakes():
            now = _time.monotonic()
            for rec in fakes:
                rec.last_heartbeat = now

        batch_max = cfg().lease_batch_max

        async def leg_batched(n) -> float:
            _refresh_fakes()
            reqs = _reqs(n)
            t0 = _time.perf_counter()
            futs = await asyncio.gather(
                *(lease_batched(reqs[i:i + batch_max])
                  for i in range(0, n, batch_max)))
            replies = await asyncio.gather(
                *(f for group in futs for f in group))
            dt = _time.perf_counter() - t0
            assert all(r.get("ok") for r in replies)
            return dt

        async def leg_per_item(n) -> float:
            _refresh_fakes()
            reqs = _reqs(n)
            t0 = _time.perf_counter()
            replies = await asyncio.gather(*(lease_per_item(r)
                                             for r in reqs))
            dt = _time.perf_counter() - t0
            assert all(r.get("ok") for r in replies)
            return dt

        async def leg_ttfl(batched: bool) -> float:
            """Time from frame(s) leaving the client to the FIRST granted
            lease, cold queues, 1k-node view live on both sides."""
            _refresh_fakes()
            reqs = _reqs(batch_max)
            t0 = _time.perf_counter()
            if batched:
                futs = await lease_batched(reqs)
                done, rest = await asyncio.wait(
                    futs, return_when=asyncio.FIRST_COMPLETED)
            else:
                done, rest = await asyncio.wait(
                    [asyncio.ensure_future(lease_per_item(r))
                     for r in reqs],
                    return_when=asyncio.FIRST_COMPLETED)
            dt = _time.perf_counter() - t0
            assert next(iter(done)).result().get("ok")
            await asyncio.gather(*rest)  # drain so legs don't overlap
            return dt

        try:
            best: Dict[str, float] = {}
            for _ in range(trials):
                dt = await leg_batched(n_requests)
                best["sched_tasks_per_s"] = max(
                    best.get("sched_tasks_per_s", 0.0),
                    _rate(n_requests, dt))
                dt = await leg_per_item(n_requests)
                best["sched_tasks_per_s_per_item"] = max(
                    best.get("sched_tasks_per_s_per_item", 0.0),
                    _rate(n_requests, dt))
                best["time_to_first_lease_1k_fake_nodes"] = min(
                    best.get("time_to_first_lease_1k_fake_nodes",
                             float("inf")),
                    await leg_ttfl(batched=True))
                best["time_to_first_lease_1k_fake_nodes_per_item"] = min(
                    best.get("time_to_first_lease_1k_fake_nodes_per_item",
                             float("inf")),
                    await leg_ttfl(batched=False))
            return {
                name: {"value": round(v, 1 if "per_s" in name else 4),
                       "unit": "leases/s" if "per_s" in name else "s",
                       "n": (n_requests if "per_s" in name else batch_max),
                       "trials": trials}
                for name, v in best.items()}
        finally:
            await client.close()
            raylet._shutdown.set()
            try:
                await asyncio.wait_for(raylet._cleanup(), timeout=10)
            except Exception:
                pass
            if gcs._health_task is not None:
                gcs._health_task.cancel()
            await gcs.server.close()

    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(_run())
    finally:
        loop.close()


def _bench_checkpoint(scale: float) -> List[Dict]:
    """Checkpoint plane (checkpoint/): what a train step actually stalls
    for, per save of a ~64 MiB fp32 state, best of 3.

      * ckpt_sync_stall_ms — the old way: snapshot + serialize + fsync +
        commit inline with the step.
      * ckpt_async_stall_ms — `save_async` return latency: the
        device->host snapshot only; persistence runs on the background
        thread (flushed between trials so runs don't overlap).
      * ckpt_restore_reshard_ms — read a 4-way checkpoint back as one
        rank of a 2-way world (manifest read + global reassembly +
        re-slice), the elastic-restore path.
    """
    import os
    import shutil
    import tempfile

    import numpy as np

    from ray_tpu.checkpoint import CheckpointPlane, restore_shard, save_sharded

    mib = max(8, int(64 * scale))
    n_arrays = 8
    per = (mib * (1 << 20)) // (4 * n_arrays)
    tree = {f"layer_{i}": np.arange(per, dtype=np.float32) + i
            for i in range(n_arrays)}
    root = tempfile.mkdtemp(prefix="ckpt-bench-")
    plane = CheckpointPlane()
    out: List[Dict] = []
    try:
        sync_ms, async_ms = [], []
        for trial in range(3):
            d = os.path.join(root, f"sync-{trial}")
            t0 = time.perf_counter()
            save_sharded(tree, d, name="state", rank=0, world=1, step=trial)
            sync_ms.append((time.perf_counter() - t0) * 1e3)
        for trial in range(3):
            d = os.path.join(root, f"async-{trial}")
            t0 = time.perf_counter()
            plane.save_async(tree, d, name="state", rank=0, world=1,
                             step=trial)
            async_ms.append((time.perf_counter() - t0) * 1e3)
            plane.flush(60)
        out.append({"benchmark": "ckpt_sync_stall_ms",
                    "value": round(min(sync_ms), 3),
                    "unit": f"ms ({mib} MiB)", "n": 1, "trials": 3})
        out.append({"benchmark": "ckpt_async_stall_ms",
                    "value": round(min(async_ms), 3),
                    "unit": f"ms ({mib} MiB)", "n": 1, "trials": 3})
        d4 = os.path.join(root, "sharded-4way")
        for r in range(4):
            save_sharded(tree, d4, name="state", rank=r, world=4)
        reshard_ms = []
        for _ in range(3):
            t0 = time.perf_counter()
            restore_shard(d4, rank=0, world=2, name="state")
            reshard_ms.append((time.perf_counter() - t0) * 1e3)
        out.append({"benchmark": "ckpt_restore_reshard_ms",
                    "value": round(min(reshard_ms), 3),
                    "unit": f"ms ({mib} MiB, 4->2)", "n": 1, "trials": 3})
    finally:
        plane.close()
        shutil.rmtree(root, ignore_errors=True)
    return out


def _bench_data_stream(scale: float) -> List[Dict]:
    """Streaming vs batch ingestion on a transform-heavy dataset, best of
    3 — the data plane's tentpole number.

      * data_batch_steps_per_s  — bulk execution: materialize every block
        (all reads + transforms run to completion), THEN run the consume
        loop. Ingestion and compute serialize.
      * data_stream_steps_per_s — StreamingIterator: blocks produce in a
        pipelined, backpressured graph while the consumer computes, so
        ingestion hides behind the step.
      * data_prefetch_hit_rate  — fraction of batches served without the
        consumer blocking, from the same streaming trials.

    The transform sleeps (IO-shaped work: decode/augment/fetch) so the
    legs measure overlap, not this host's arithmetic throughput; the
    consumer's per-batch "train step" is a matched sleep."""
    from ray_tpu import data as rdata

    nblocks = max(8, int(24 * scale))
    rows_per_block = 64
    step_s = 0.020       # consumer compute per batch (one batch per block)
    transform_s = 0.060  # per-block transform cost, runs on the cluster

    def slow_transform(batch):
        time.sleep(transform_s)
        return {"x": batch["id"] * 2}

    def make_ds():
        return rdata.range(nblocks * rows_per_block,
                           parallelism=nblocks).map_batches(slow_transform)

    def consume(it) -> int:
        steps = 0
        for _ in it:
            time.sleep(step_s)
            steps += 1
        return steps

    batch_best = stream_best = hit_best = 0.0
    for _ in range(3):
        # Bulk: materialize first (every read+transform completes), then
        # iterate the resident blocks.
        t0 = time.perf_counter()
        mat = make_ds().materialize()
        steps = consume(mat.iter_batches(batch_size=rows_per_block))
        batch_best = max(batch_best,
                         steps / max(time.perf_counter() - t0, 1e-9))
        t0 = time.perf_counter()
        it = make_ds().iter_batches(batch_size=rows_per_block,
                                    prefetch_batches=4)
        steps = consume(it)
        stream_best = max(stream_best,
                          steps / max(time.perf_counter() - t0, 1e-9))
        hit_best = max(hit_best, it.prefetch_hit_rate)
    return [
        {"benchmark": "data_batch_steps_per_s",
         "value": round(batch_best, 1), "unit": "steps/s",
         "n": nblocks, "trials": 3},
        {"benchmark": "data_stream_steps_per_s",
         "value": round(stream_best, 1), "unit": "steps/s",
         "n": nblocks, "trials": 3},
        {"benchmark": "data_prefetch_hit_rate",
         "value": round(hit_best, 3), "unit": "fraction",
         "n": nblocks, "trials": 3},
    ]


def _bench_metrics_history(scale: float) -> List[Dict]:
    """GCS metrics-history plane (runtime/gcs/server.py ring ingest):

      * metrics_history_ingest_per_s — MetricsReportMsg flushes folded
        into the time-series rings per second. Each flush is a realistic
        payload (24 moving counters, 4 gauges, 2 tagged histograms, the
        json a worker actually ships), spread over 4 reporters so the
        crc32 sharding is exercised; payload encoding is pre-built so the
        leg prices ingest (json parse, delta diff, ring append, budget
        check) and nothing else.
      * metrics_history_query_ms — one windowed query (counter rate and
        histogram p99 over the ingested rings) through the public
        handler, mean wall ms.
      * metrics_history_overhead_pct — what co-hosting ingest costs a
        serving replica: the SAME warm engine decode workload run twice,
        once with a background flusher thread doing only the snapshot-KV
        write (the pre-history GCS behavior) and once with the thread
        ALSO folding every flush into the rings. The 50 ms cadence is a
        20-reporter fleet at the production 1 s flush interval, with the
        GCS sharing the replica's core — already pessimistic (deployed,
        ingest runs on the GCS host, never the serving path). Budget
        <=2%: anything bigger means ring work leaked somewhere hot.
    """
    import asyncio
    import threading

    import jax.numpy as jnp

    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.llm.serving import LLMConfig, build_engine
    from ray_tpu.models import llama
    from ray_tpu.runtime.gcs.server import GcsServer

    out: List[Dict] = []
    srv = GcsServer()
    bounds = [0.1, 0.5, 1, 5, 10, 50, 100, 500, 1000, 5000]

    def payload(i: int) -> bytes:
        snaps = [{"name": f"ray_tpu_bench_c{j}_total", "type": "counter",
                  "values": {"[]": float(i * (j + 1))}} for j in range(24)]
        snaps += [{"name": f"ray_tpu_bench_g{j}", "type": "gauge",
                   "values": {"[]": float((i * 7 + j) % 100)}}
                  for j in range(4)]
        for hname in ("ray_tpu_bench_ttft_ms", "ray_tpu_bench_itl_ms"):
            buckets = [0] * (len(bounds) + 1)
            buckets[(i + len(hname)) % len(buckets)] = 3 * (i + 1)
            snaps.append({"name": hname, "type": "histogram",
                          "boundaries": bounds,
                          "histograms": {'[["phase", "p"]]': {
                              "buckets": buckets, "sum": 40.0 * (i + 1),
                              "count": 3 * (i + 1)}}})
        return json.dumps(snaps).encode()

    n_flushes = max(400, int(1500 * scale))
    payloads = [payload(i) for i in range(n_flushes)]
    base = time.time() - n_flushes  # one synthetic flush per second
    t0 = time.perf_counter()
    for i, p in enumerate(payloads):
        srv._ingest_metrics_history(f"{i % 4:02x}" * 14, 1, p,
                                    now=base + i)
    out.append({"benchmark": "metrics_history_ingest_per_s",
                "value": round(_rate(n_flushes, time.perf_counter() - t0),
                               1),
                "unit": "flushes/s", "n": n_flushes})

    q_trials = max(20, int(50 * scale))
    t0 = time.perf_counter()
    for i in range(q_trials):
        if i % 2:
            asyncio.run(srv.handle_metrics_history(
                None, "ray_tpu_bench_c0_total", window_s=60.0, agg="rate"))
        else:
            asyncio.run(srv.handle_metrics_history(
                None, "ray_tpu_bench_ttft_ms", window_s=60.0, agg="p99"))
    out.append({"benchmark": "metrics_history_query_ms",
                "value": round((time.perf_counter() - t0) / q_trials * 1e3,
                               3),
                "unit": "ms", "n": q_trials})

    # -- serving overhead: decode loop +/- ring ingest beside it ---------
    mid = llama.LlamaConfig(vocab_size=128, d_model=128, n_layers=2,
                            n_heads=4, n_kv_heads=4, d_ff=512,
                            max_seq=128, dtype=jnp.float32)
    eng = build_engine(LLMConfig(model_config=mid, num_kv_blocks=32,
                                 block_size=8, max_batch_size=4,
                                 prefill_chunk=16, warmup_buckets="off"))

    def decode_workload() -> int:
        for s in range(4):
            eng.add_request([(s * 13 + 5 * i) % 128 for i in range(24)],
                            SamplingParams(max_tokens=24))
        tokens = 0
        while eng.has_unfinished():
            for o in eng.step():
                tokens += len(o.new_token_ids)
        return tokens

    decode_workload()                      # warm the compile cache

    def timed_leg(with_history: bool) -> float:
        stop = threading.Event()
        counter = [0]

        def flusher():
            i = 0
            while not stop.is_set():
                p = payloads[i % n_flushes]
                srv._kv[b"metrics:bench:1"] = p        # the KV write both
                if with_history:                       # modes always paid
                    srv._ingest_metrics_history(
                        "bb" * 14, 1, p, now=base + n_flushes + i)
                counter[0] = i = i + 1
                time.sleep(0.05)

        th = threading.Thread(target=flusher, daemon=True,
                              name="bench-mh-flusher")
        th.start()
        try:
            t0 = time.perf_counter()
            tokens = decode_workload()
            return _rate(tokens, time.perf_counter() - t0)
        finally:
            stop.set()
            th.join(timeout=5)

    # Interleaved best-of-3 pairs: box-load drift on a shared 1-core host
    # swamps a small delta unless both legs see the same weather.
    tps = {"snapshot_only": 0.0, "history": 0.0}
    for _ in range(3):
        tps["snapshot_only"] = max(tps["snapshot_only"], timed_leg(False))
        tps["history"] = max(tps["history"], timed_leg(True))
    overhead = 100.0 * (1.0 - tps["history"] / tps["snapshot_only"])
    out.append({"benchmark": "metrics_history_overhead_pct",
                "value": round(overhead, 2), "unit": "%", "n": 1,
                "trials": 3})
    return out


def _bench_scale_envelope(scale: float) -> List[Dict]:
    """Batched vs per-item control-plane legs."""
    legs = run_scale_envelope(n_requests=max(64, int(192 * scale)))
    return [{"benchmark": name, **rec} for name, rec in legs.items()]


def main(scale: float = 1.0, as_json: bool = False) -> List[Dict]:
    results = run(scale=scale)
    if as_json:
        print(json.dumps(results))
    else:
        width = max(len(r["benchmark"]) for r in results)
        for r in results:
            digits = {"GiB/s": 3, "s": 4}.get(r["unit"], 1)
            print(f"{r['benchmark']:<{width}}  {r['value']:>12,.{digits}f} "
                  f"{r['unit']} (n={r['n']})")
    return results


if __name__ == "__main__":
    main()
