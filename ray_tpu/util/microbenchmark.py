"""Core-runtime microbenchmarks.

Reference analog: python/ray/_private/ray_perf.py:93-315 (the `ray
microbenchmark` CLI): put/get ops, task throughput sync/async, 1:1 and
n:n actor call rates — the numbers the release pipeline tracks per build —
and the control plane's scale envelope (`run_scale_envelope`, which
`tests/test_scale_envelope.py` holds to its claims).
Run via `python -m ray_tpu.scripts microbenchmark [--scale N]`.

Wall-clock readings of the core runtime on whatever host runs them. Nothing
here measures a model, a kernel or the chip, and nothing here is evidence
about a cell of `BENCHMARK.json`: that is `benchmarks/` (README there).
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

        # -- control-plane scale envelope: batched vs per-item leases ----
        results.extend(_bench_scale_envelope(scale))
    finally:
        if owns_cluster:
            ray_tpu.shutdown()
    return results


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
