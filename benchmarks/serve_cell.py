"""Runner for serving traffic (`kind` "closed" or "open"): one `LLMServer` in
this process, clients as threads on `completions_stream`, one window.

Order of a run: build the server (light warm-up), check it against the plain
reference and against itself, serve the traffic's shared prefixes once, start
the load, let `warm_s` seconds of it pass untimed, then the window. The load
keeps running after the window until every request due inside it has its first
token (at most GRACE_S), so that the window's tail sees the window's load.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List

import numpy as np

import traffic as traffic_mod
from harness import (Request, Run, load_module, memory_peak_bytes, new_run,
                     note, seed32, traced)

GRACE_S = 15.0
TRACE_S = 3.0          # the traced slice of a --trace 1 window
# Last-position logits, program (bf16 weights and activations, Pallas paged
# attention) against the float32 reference on the same bf16 weights:
# max |difference| over max |reference logit|. bf16 keeps 8 mantissa bits
# (0.4% a rounding) and the program rounds after every matmul of every layer;
# see PERF.md section 2 for the reading this limit stands on. An fp8 or int8
# path, at 3-6% a rounding, would not pass it.
LOGITS_REL_TOL = 3e-2
# A family that routes (one that defines `reference_logits_routed`) is held to
# LOGITS_REL_TOL with the reference following the experts the program kept,
# and every choice the reference would not have made has to be a near tie:
# its shortfall (README.md, "The family file") at most this. A router sees a
# hidden state that differs from the reference's by bf16 rounding, so some
# token-layers in a hundred keep another expert than the reference would; in
# a toy of the DeepSeek-V2 block (tests/test_routed_check.py) those fall short
# by up to 3.8% over 18 seeds (4.3% in ISSUE 28's copy of it; PERF.md section
# 6), while a router without the group limit reads 60% and more and a k-th
# expert that is any expert 99%. 0.1 is 2.3x the largest sound reading. The
# first routed family reads it again on the chip; only a `benchmark` issue
# may then tighten it.
ROUTING_TIE_MARGIN = 0.1
CHECK_PROMPT, CHECK_DECODE = 256, 8


def _valid(ids, vocab: int) -> bool:
    return all(isinstance(t, int) and 0 <= t < vocab for t in ids)


def check_logits(server, family, sizes: Dict, seed: int) -> Dict:
    """Prefill two seeded prompts through the paged cache in chunks, then
    teacher-forced decode positions, by `ModelRunner.step`; compare each
    last-position logits row with the reference's full forward pass.

    Where the family defines `reference_logits_routed`, the reference takes
    the experts the program kept for every row (`runner.last_routing` after
    each step) and also returns each differing choice's shortfall: the same
    comparison at the same tolerance, and one more condition."""
    runner = server.engine.runner
    routed = hasattr(family, "reference_logits_routed")
    n_prompt = min(CHECK_PROMPT, sizes["max_position_embeddings"] // 2)
    total = n_prompt + CHECK_DECODE
    rng = np.random.default_rng([seed, 7])
    tokens = rng.integers(1, sizes["vocab_size"], (2, total)).astype(np.int32)
    pages = -(-total // runner.block_size)
    tables = np.zeros((2, runner.max_blocks_per_seq), dtype=np.int32)
    for i in range(2):   # the pool's last pages: nothing has been served yet
        tables[i, :pages] = runner.num_blocks - 1 - i * pages - np.arange(pages)
    got, routing = [], []

    def step(tok, start):
        n = tok.shape[1]
        bq = runner.chunk_bucket(n) if n > 1 else 1
        padded = np.zeros((2, bq), dtype=np.int32)
        padded[:, :n] = tok
        logits = np.asarray(runner.step(
            padded, np.full(2, start, np.int32), np.full(2, start + n, np.int32),
            np.full(2, n, np.int32), tables), dtype=np.float32)
        if routed:   # (routed_layers, 2, bq, top_k): the padded rows go
            routing.append(np.asarray(runner.last_routing)[:, :, :n])
        return logits

    t0 = time.time()
    with server._lock:     # the engine loop is idle; keep it so
        for start in range(0, n_prompt, runner.chunk_size):
            n = min(runner.chunk_size, n_prompt - start)
            logits = step(tokens[:, start:start + n], start)
        got.append(logits)
        for pos in range(n_prompt, total):
            got.append(step(tokens[:, pos:pos + 1], pos))
    got = np.stack(got[:-1], axis=1)     # positions n_prompt-1 .. total-2
    t1 = time.time()
    positions = list(range(n_prompt - 1, total - 1))
    ties = {}
    if routed:
        want, shortfall = family.reference_logits_routed(
            runner.params, tokens, positions, sizes,
            np.concatenate(routing, axis=2))   # (routed_layers, 2, total, k)
        shortfall = np.asarray(shortfall)      # (routed_layers, 2, total)
        ties = {"routed_choices": int(shortfall.size),
                "routed_differ": int(np.count_nonzero(shortfall)),
                "shortfall_max": float(shortfall.max()),
                "tie_margin": ROUTING_TIE_MARGIN}
    else:
        want = family.reference_logits_at(runner.params, tokens, positions,
                                          sizes)
    want = np.asarray(want)
    err = float(np.abs(got - want).max() / np.abs(want).max())
    rms = float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))
    ok = (np.isfinite(got).all() and err <= LOGITS_REL_TOL
          and ties.get("shortfall_max", 0.0) <= ROUTING_TIE_MARGIN)
    return {"ok": bool(ok), "rel_err": err, "rel_rms": rms,
            "tolerance": LOGITS_REL_TOL, **ties,
            "positions": len(positions) * 2, "program_s": round(t1 - t0, 3),
            "reference_s": round(time.time() - t1, 3)}


def _collect(server, request: Dict) -> List[int]:
    for event in server.completions_stream(request):
        if event["finished"]:
            return event["token_ids"]
    return []


def check_repeat(server, sizes: Dict, seed: int) -> Dict:
    """One greedy request served alone: once to fill the prefix cache, then
    twice more; those two run the same programs on the same inputs and must
    return the same tokens."""
    n = min(CHECK_PROMPT, sizes["max_position_embeddings"] // 2)
    prompt = np.random.default_rng([seed, 8]).integers(
        1, sizes["vocab_size"], n).tolist()
    t0 = time.time()
    runs = [_collect(server, {"prompt": prompt, "max_tokens": 16,
                              "request_id": f"check-{seed}-{i}"})
            for i in range(3)]
    ok = (runs[1] == runs[2] and len(runs[1]) == 16
          and _valid(runs[1], sizes["vocab_size"]))
    return {"ok": ok, "seconds": round(time.time() - t0, 3),
            "equals_uncached_run": runs[0] == runs[1]}


class Load:
    """The clients: threads that each hold one stream at a time."""

    def __init__(self, server, plan: Dict, traffic: Dict, vocab: int):
        self.server, self.traffic, self.vocab = server, traffic, vocab
        self.plan = plan["requests"]
        self.records: List[Request] = []
        self.late_ms: List[float] = []
        self.stop = threading.Event()
        self.exhausted = False
        self._next = 0
        self._lock = threading.Lock()
        self._threads: List[threading.Thread] = []

    def _take(self):
        with self._lock:
            if self._next >= len(self.plan):
                self.exhausted = True
                return None
            k, self._next = self._next, self._next + 1
            return self.plan[k]

    def _serve(self, spec: Dict, due: float) -> None:
        request = {k: v for k, v in spec.items() if k != "due_s"}
        rec = Request(id=spec["request_id"], prompt_len=len(spec["prompt"]),
                      max_tokens=spec["max_tokens"], due=due)
        with self._lock:
            self.records.append(rec)
        rec.sent = time.time()
        stream = self.server.completions_stream(request)
        try:
            for event in stream:
                now = time.time()
                if event["finished"]:
                    rec.n_tokens = len(event["token_ids"])
                    if (rec.n_tokens != rec.max_tokens
                            or not _valid(event["token_ids"], self.vocab)):
                        rec.error = f"bad completion: {rec.n_tokens} ids"
                    rec.done = now
                else:
                    rec.token_times.append(now)
                if self.stop.is_set():
                    break
        except Exception as e:   # a failed request is a result, not a crash
            rec.error = repr(e)
        finally:
            stream.close()       # aborts the request if it is unfinished

    def _closed_client(self, start_at: float) -> None:
        while not self.stop.wait(max(0.0, start_at - time.time())):
            spec = self._take()
            if spec is None:
                return
            self._serve(spec, time.time())
            start_at = 0.0

    def _open_dispatcher(self, start: float) -> None:
        while not self.stop.is_set():
            spec = self._take()
            if spec is None:
                return
            due = start + spec["due_s"]
            if self.stop.wait(max(0.0, due - time.time())):
                return
            self.late_ms.append((time.time() - due) * 1e3)
            threading.Thread(target=self._serve, args=(spec, due),
                             daemon=True, name=spec["request_id"]).start()

    def start(self) -> float:
        start = time.time()
        if self.traffic["kind"] == "closed":
            n = int(self.traffic["clients"])
            for c in range(n):
                at = start + c * float(self.traffic.get("ramp_s", 0.0)) / n
                self._threads.append(threading.Thread(
                    target=self._closed_client, args=(at,), daemon=True,
                    name=f"client-{c}"))
        else:
            self._threads.append(threading.Thread(
                target=self._open_dispatcher, args=(start,), daemon=True,
                name="arrivals"))
        for t in self._threads:
            t.start()
        return start

    def finish(self) -> None:
        """Stop sending; abort what is unfinished. A client whose request is
        still queued in the engine blocks on its stream until the server's
        own timeout: it is a daemon thread and is left to the process's end."""
        self.stop.set()
        deadline = time.time() + 3.0
        for t in self._threads:
            t.join(timeout=max(0.0, deadline - time.time()))
        with self._lock:
            records = list(self.records)
        for rec in records:
            if rec.done is None and rec.error is None:
                self.server.abort(rec.id)


def _backlog(records: List[Request], t: float) -> int:
    return sum(1 for r in records
               if r.due <= t and (r.done is None or r.done > t))


def run_cell(ctx) -> Run:
    import jax

    from ray_tpu.llm.serving import LLMConfig, LLMServer
    from ray_tpu.util import tracing

    config, traffic, seed = ctx.config, ctx.traffic, ctx.seed
    sizes, deployment = config["sizes"], config["deployment"]
    family = load_module("families", config["family"])
    run = new_run(ctx)

    t = time.time()
    server = LLMServer(LLMConfig(
        model_config=family.model_config(sizes), seed=seed32(seed),
        num_kv_blocks=deployment["num_kv_blocks"],
        max_batch_size=deployment["max_batch_size"],
        warmup_buckets="light"))
    warm = server.engine_stats()
    note(phase="server", seconds=round(time.time() - t, 3),
         warmup_s=warm["warmup_s"], warmup_shapes=warm["warmup_shapes"],
         attention_impl=server.engine.runner.attention_impl,
         token_budget=warm["token_budget"])
    if ctx.require_kernels and server.engine.runner.attention_impl != "pallas":
        run.problems.append("the server is not on the Pallas paged kernels")

    run.checks["logits"] = check_logits(server, family, sizes, seed)
    run.checks["repeat"] = check_repeat(server, sizes, seed)
    note(phase="checks", **run.checks)
    for name, check in run.checks.items():
        if not check["ok"]:
            run.problems.append(f"check {name} failed: {check}")

    checked = server.engine_stats()
    t = time.time()
    plan = traffic_mod.make_requests(traffic, seed, sizes["vocab_size"])
    for i, prefix in enumerate(plan["prefixes"]):
        _collect(server, {"prompt": prefix, "max_tokens": 1,
                          "request_id": f"prefix-{seed}-{i}"})
    note(phase="traffic", requests=len(plan["requests"]),
         prefixes=len(plan["prefixes"]), seconds=round(time.time() - t, 3))

    load = Load(server, plan, traffic, sizes["vocab_size"])
    started = load.start()
    time.sleep(max(0.0, started + float(traffic["warm_s"]) - time.time()))
    run.stats_before = server.engine_stats()
    run.t0 = time.time()
    if ctx.trace:   # a slice that starts a third of the way into the window
        time.sleep(max(0.0, run.t0 + ctx.seconds / 3.0 - time.time()))
        run.trace = traced(os.path.join(ctx.out_dir, "trace"),
                           lambda: time.sleep(min(TRACE_S, ctx.seconds / 3.0)))
    time.sleep(max(0.0, run.t0 + ctx.seconds - time.time()))
    run.stats_after = server.engine_stats()
    run.t1 = time.time()
    deadline = run.t1 + GRACE_S
    while time.time() < deadline and any(
            not r.token_times and r.error is None
            for r in load.records if run.in_window(r.due)):
        time.sleep(0.05)
    grace = time.time() - run.t1
    load.finish()

    run.requests = sorted(load.records, key=lambda r: r.due)
    run.late_ms = load.late_ms
    run.ticks = server.flight_records()
    run.spans = [s for s in tracing.get_spans()
                 if s["name"].startswith("llm:")]
    end = server.engine_stats()
    compiles = run.stats_after["step_compiles"] - run.stats_before["step_compiles"]
    recompiled = [t for t in run.window_ticks() if t.get("recompile")]
    if compiles or recompiled:
        run.problems.append(f"{compiles} step compiles and "
                            f"{len(recompiled)} tick recompiles in the window")
    if load.exhausted:
        run.problems.append("the traffic file's max_requests ran out")
    mid = (run.t0 + run.t1) / 2.0
    note(phase="window", seconds=round(run.window_s, 3),
         grace_s=round(grace, 3), requests_total=len(run.requests),
         requests_due_in_window=len(run.window_requests()),
         requests_completed_in_window=sum(
             1 for r in run.requests if run.in_window(r.done)),
         step_compiles_in_window=compiles,
         step_compiles_in_warm_traffic=run.stats_before["step_compiles"]
         - checked["step_compiles"],
         backlog_mid=_backlog(run.requests, mid),
         backlog_end=_backlog(run.requests, run.t1),
         late_ms_max=max(load.late_ms, default=0.0),
         late_ms_mean=(sum(load.late_ms) / len(load.late_ms)
                       if load.late_ms else 0.0),
         ticks_in_window=len(run.window_ticks()),
         slowest_ticks=_slowest_ticks(run, 3),
         free_kv_blocks=end["free_kv_blocks"],
         memory_peak_bytes=memory_peak_bytes(jax.local_devices()))
    server._handoff.close()
    return run


def _slowest_ticks(run: Run, n: int):
    """The window's `n` ticks that took longest, the gap before each counted:
    where a run reads far from the others, its log says in which phase of
    which tick the time went (a stall of the host shows here or nowhere)."""
    phases = ("since_prev_ms", "admit_ms", "compose_ms", "dispatch_ms",
              "wait_ms", "commit_ms")
    ticks = sorted(run.window_ticks(), key=lambda t: -(
        t.get("dur_ms", 0.0) + t.get("since_prev_ms", 0.0)))[:n]
    return [dict({k: t[k] for k in phases if k in t},
                 at_s=round(t["t"] - run.t0, 3), dur_ms=t.get("dur_ms"),
                 kind=t.get("kind"), prefill_rows=t.get("prefill_rows"),
                 decode_rows=t.get("decode_rows")) for t in ticks]


def host_intervals(run: Run):
    """What the host was doing, for the idle gaps' labels: the engine's
    ticks, by kind and rows."""
    return [(t["t"], t["t"] + t["dur_ms"] / 1e3,
             f"in_tick:{t.get('kind')}:prefill_rows={t.get('prefill_rows', 0)}")
            for t in run.ticks if "t" in t and "dur_ms" in t]


def attempted_failed(run: Run):
    """Requests due in the window; of those, the ones that raised, returned
    a wrong completion, or had no first token when the load stopped."""
    attempted = run.window_requests()
    failed = [r for r in attempted if r.error or not r.token_times]
    return len(attempted), len(failed)
