"""LLM serving deployment: OpenAI-style completions over the native engine.

Reference analog: python/ray/llm/_internal/serve/ (VLLMEngine wrapper
vllm_engine.py:222, vllm_deployment.py, the OpenAI router deployments/
routers/, build_openai_app). Ours wraps the native paged-attention engine
(ray_tpu.llm.engine) in a serve deployment. The engine loop runs on a
background thread inside the replica (the vLLM MQEngine pattern collapsed
in-process): request threads enqueue prompts and consume per-request token
queues, so many requests stream concurrently through one continuously-
batched engine. TP maps to a mesh inside the replica (SERVE_RULES sharding),
placed via num_tpus — the reference plans TP x PP placement groups around
vLLM (vllm_models.py:117-168).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import queue
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from ray_tpu import serve
from ray_tpu.util import tracing


class RequestTimeoutError(TimeoutError):
    """No engine output arrived within LLMConfig.stream_timeout_s. The
    request has been aborted and its KV pages released — retrying is safe."""


class ReplicaDrainingError(RuntimeError):
    """This replica has stopped admitting (node drain / scale-down
    retirement in progress). The request was NOT submitted; route it to a
    healthy replica. The message embeds "REPLICA_DRAINING" so routers can
    classify it across actor RPC boundaries that re-wrap exception types."""

    def __init__(self, tag: str = ""):
        super().__init__(f"REPLICA_DRAINING {tag}: replica is draining; "
                         "resubmit on a healthy replica")


class SessionMigratedError(RuntimeError):
    """The replica exported this in-flight request to another replica
    (drain/scale-down migration) while a consumer was collecting it.

    mode "kv": the adoptive replica already holds the live stream — the
    consumer re-collects there with completions_collect(request_id), zero
    re-prefill. mode "replay": re-submit the same request (same
    request_id) anywhere healthy; seeded sampling reproduces the identical
    token stream from the prompt. The message embeds
    "SESSION_MIGRATED <mode> <rid>" so routers can classify the error
    across actor RPC boundaries that re-wrap exception types."""

    def __init__(self, rid: str, mode: str):
        super().__init__(f"SESSION_MIGRATED {mode} {rid}")
        self.rid = rid
        self.mode = mode


@dataclasses.dataclass
class LLMConfig:
    # The model's configuration dataclass (models/llama.py's LlamaConfig,
    # models/deepseek_v2.py's DeepseekV2Config, models/mimo_v2_flash.py's
    # MimoV2FlashConfig): its module supplies
    # init_params, its serving block the layer step and the cache spec.
    model_config: Any = None
    params_checkpoint: Optional[str] = None  # dir with saved params pytree
    seed: int = 0
    num_kv_blocks: int = 256
    block_size: int = 16
    max_batch_size: int = 8
    num_replicas: int = 1
    num_tpus_per_replica: float = 0.0
    tensor_parallel: int = 1            # tp axis size of the in-replica mesh
    prefill_chunk: int = 128
    tokenizer: Any = None
    # Multi-LoRA (llm/lora.py): preloaded adapters + slot-table sizing.
    lora_adapters: Any = None           # list[LoRAAdapter] | None
    max_loras: int = 8
    lora_rank: int = 8
    # Engine features (llm/engine.py): automatic prefix caching (shared
    # system prompts skip prefill) and n-gram speculative decoding (tokens
    # proposed from the sequence's own history, verified at any
    # temperature).
    enable_prefix_caching: bool = True
    speculative_ngram: int = 0
    # The engine's tick (engine.py _mixed_tick): decode rows, spec-verify
    # rows, and prefill chunk slices share ONE kernel launch per step,
    # bucketed on total token count. token_budget=None sizes the flat-token
    # ceiling as prefill_chunk + max_batch * (1 + speculative_ngram).
    token_budget: Optional[int] = None
    # Precompile the tick's programs at replica start so user requests
    # don't pay XLA compiles mid-stream (vLLM-TPU startup precompile; a
    # cold bucket costs seconds of TTFT on multi-B-param models). "light" =
    # the mixed step over its token ladder, which is every program a
    # request without a repetition penalty runs; "full" = that and the
    # host-logits head over the same ladder (twice the startup compiles,
    # no request shape compiles mid-stream); "off" = lazy. True/False alias
    # full/off.
    warmup_buckets: Any = "full"
    # Serving-plane knobs (llm/router.py, llm/disagg.py). routing="affinity"
    # fronts the replica fleet with the prefix-cache-affinity router
    # deployment; slo_ttft_s > 0 arms its admission gate (projected TTFT
    # above the SLO -> shed with a 429-shaped error instead of queueing
    # unboundedly); disaggregate=N runs N dedicated prefill replicas that
    # stream populated KV pages to the decode replicas over the zero-pickle
    # handoff wire (llm/disagg.py).
    routing: str = "pow2"               # "pow2" | "affinity"
    slo_ttft_s: float = 0.0
    disaggregate: int = 0
    handoff_host: str = "127.0.0.1"
    # How long completions/streams wait for the next engine output before
    # aborting the request (the abandoned-request guard).
    stream_timeout_s: float = 300.0
    # Tiered KV prefix store (llm/prefix_store.py): cold-but-reusable
    # prefix pages spill to host RAM before dropping (tier 1), and host-
    # tier victims publish into the GCS cluster prefix table (tier 2) so
    # any replica can adopt the shared working set after the owner dies,
    # drains, or the deployment restarts.
    host_prefix_mb: float = 32.0        # 0 disables the host tier
    host_prefix_low_watermark: float = 0.8
    cluster_prefix_store: bool = True   # publish/adopt via the GCS table
    # LoRA pool autoscaling (llm/lora.py LoRAPoolPolicy): grow/shrink the
    # adapter slot table off the same engine_stats() telemetry that drives
    # ReplicaPolicy.
    lora_autoscale: bool = False
    lora_min_slots: int = 1
    lora_max_slots: int = 32
    # Deployment name, stamped by build_llm_deployment — keys this fleet's
    # rows in the cluster prefix table so delete_deployment can purge them.
    deployment_name: str = ""


def _node_hex() -> Optional[str]:
    """This process's cluster node id (hex), when a core worker exists —
    the join key the router uses to map NODE_DRAINING/NODE_DEAD events to
    replicas. None outside a cluster (in-process tests, microbench)."""
    try:
        from ray_tpu.core import worker as worker_mod

        if worker_mod.is_initialized():
            nid = worker_mod.global_worker().node_id
            if isinstance(nid, (bytes, bytearray)):
                return bytes(nid).hex()
            return str(nid) if nid is not None else None
    except Exception:
        pass
    return None


def _tree_nbytes(tree) -> int:
    import jax

    return sum(getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(tree))


class _StartupAccount:
    """A replica's way to ready, watched (docs/observability.md, "Why did
    this replica take so long to be ready"): a span `llm:startup` from the
    account's making to `ready()`, tagged `replica`, with
    `llm:startup:params` and `:place` cut by `phase()` and `:warmup` written
    by `engine.warmup` under it, each with the compile ledger's stages
    inside its extent; the same cuts as annotations on a profile's host
    plane (`PhaseClock`); and the sums as `engine.startup`.

    It is an object of its own so that `build_engine` holds ONE name for it
    and calls it between its statements: the account adds no local and no
    stack slot to `build_engine`'s frame (nor to `LLMServer.__init__`'s,
    `LLMEngine.warmup`'s or `ModelRunner.step_mixed`'s). That is not
    tidiness: every trace of a step program recurses some hundred Python
    frames deep on top of those, CPython 3.12 lays frames in 16 KiB chunks
    that it maps when a call does not fit and UNMAPS when that call
    returns, and a word more beneath the trace moves which of the trace's
    hot calls sits on a chunk's edge and pays for the mapping every time it
    is made: eight EMPTY frames pushed beneath the parent's build made
    chat's warm-up 16.5 s where it was 11.8, three made it 10.8, and a
    first form of this account (a `with` and a dozen locals) read 1.5-1.9 s
    over the parent's in the benchmark with every instrument switched off
    (PERF.md section 6, PR 55)."""

    def __init__(self, replica: Optional[str]):
        tracing.watch_compiles()
        self._clock = tracing.PhaseClock("llm:startup")
        self._span = tracing.span("llm:startup", "llm",
                                  replica=replica or str(os.getpid()))
        self._clock.__enter__()
        self.account = self._span.__enter__()
        self._begin = self._start = self._clock.mark(None)
        self._ledger = self._before = tracing.compile_totals()
        self._children: Dict[str, float] = {}

    def phase(self, name: str) -> None:
        """A child begins now (a host instant: nothing waits)."""
        self._start = self._clock.mark(name)
        self._before = tracing.compile_totals()

    def _child(self, name: str, **attrs) -> None:
        end = self._clock.mark(None)
        self._children[name + "_s"] = end - self._start
        tracing.record_span(
            "llm:startup:" + name, "llm", self._start, end, **attrs,
            **tracing.stage_args(tracing.compile_since(self._before)))

    def params_drawn(self, params, source: str) -> None:
        """`params` ends at the draw's RETURN: an eager draw's last programs
        may still run on the device, and what they take shows in the next
        span that needs their result."""
        self._child("params", source=source, bytes=_tree_nbytes(params))

    def placed(self, runner) -> None:
        self._child(
            "place", param_bytes=_tree_nbytes(runner.params),
            cache_bytes=_tree_nbytes(runner.cache), pages=runner.num_blocks,
            slots=runner.group_pages.get(runner.state_group, 0))

    def ready(self, engine, config) -> None:
        """The account, once: every second since the account's making is in
        one of the three children or in `other_s` (imports, the mesh,
        adapters, the engine's own construction); the stages and the cache's
        counts are the ledger's over the whole call, the draw's programs
        included. `engine.startup` is a copy fixed here."""
        gained = tracing.compile_since(self._ledger)
        children = dict(self._children, warmup_s=engine.warmup_s)
        total = self._clock.mark(None) - self._begin
        self.account.update(
            model=type(config).__name__, total_s=round(total, 3),
            **{key: round(value, 3) for key, value in children.items()},
            other_s=round(max(0.0, total - sum(children.values())), 3),
            **{key: round(gained[key], 3)
               for key in tracing.COMPILE_STAGE_KEYS},
            programs=engine.warmup_shapes, compiles=gained["compiles"],
            cache_hits=gained["cache_hits"],
            cache_misses=gained["cache_misses"],
            device_tail_s=round(engine.warmup_device_tail_s, 3))
        engine.startup = dict(self.account)
        self.close()

    def close(self, *exc) -> None:
        """End the span and the annotations (also where the build raised)."""
        self._span.__exit__(*(exc or (None, None, None)))
        self._clock.__exit__(*(exc or (None, None, None)))


def build_engine(llm_config: LLMConfig, prefill_only: bool = False, *,
                 replica: Optional[str] = None):
    """Construct a ready LLMEngine per config. Shared by decode replicas
    (LLMServer) and the prefill tier (disagg.PrefillServer).

    The way to ready is watched by a `_StartupAccount` (`acct`), called
    between the statements; the cuts are host instants and the one wait of
    a start is the one that ends `engine.warmup`."""
    import jax

    from ray_tpu import models
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner

    acct = _StartupAccount(replica)
    try:
        config = llm_config.model_config or models.default_config()
        acct.phase("params")
        if llm_config.params_checkpoint:
            from ray_tpu.train.checkpoint import Checkpoint

            params = Checkpoint(llm_config.params_checkpoint).load_pytree()
            acct.params_drawn(params, "checkpoint")
        else:   # one cached program a configuration; the seed is its argument
            params = models.draw_params(config,
                                        jax.random.key(llm_config.seed))
            acct.params_drawn(params, "init")
        mesh = None
        if llm_config.tensor_parallel > 1:
            from ray_tpu.parallel.mesh import MeshConfig, build_mesh

            mesh = build_mesh(
                MeshConfig(tp=llm_config.tensor_parallel),
                devices=jax.devices()[:llm_config.tensor_parallel])
        lora_manager = None
        if llm_config.lora_adapters:
            from ray_tpu.llm.lora import LoRAManager

            lora_manager = LoRAManager(config, n_slots=llm_config.max_loras,
                                       rank=llm_config.lora_rank)
            for adapter in llm_config.lora_adapters:
                lora_manager.load_adapter(adapter)
        acct.phase("place")
        runner = ModelRunner(config, params,
                             num_blocks=llm_config.num_kv_blocks,
                             block_size=llm_config.block_size,
                             chunk_size=llm_config.prefill_chunk,
                             mesh=mesh, lora_manager=lora_manager,
                             max_batch=llm_config.max_batch_size)
        acct.placed(runner)
        engine = LLMEngine(
            runner, max_batch_size=llm_config.max_batch_size,
            tokenizer=llm_config.tokenizer,
            prefill_chunk=llm_config.prefill_chunk,
            enable_prefix_caching=llm_config.enable_prefix_caching,
            speculative_ngram=llm_config.speculative_ngram,
            token_budget=llm_config.token_budget,
            prefill_only=prefill_only)
        wm = llm_config.warmup_buckets
        wm = {True: "full", False: "off"}.get(wm, wm)
        if wm not in ("off", "light", "full"):
            raise ValueError(f"warmup_buckets: {wm!r} not off/light/full")
        acct.phase("warmup")
        if wm != "off":
            engine.warmup(full=wm == "full")
        acct.ready(engine, config)
    except BaseException:
        acct.close(*sys.exc_info())
        raise
    return engine


def _startup_line(up: Dict) -> str:
    """A replica's start-up account (`engine.startup`) in one line."""
    return (
        "ready in %.1fs: params %.1f, place %.1f, warm-up %.1f (%d programs, "
        "the device's tail %.1f), other %.1f; of the whole start trace %.1f, "
        "lower %.1f, compile %.1f, cache read %.1f; %d compiles, compile "
        "cache %d hits, %d misses" % (
            up["total_s"], up["params_s"], up["place_s"], up["warmup_s"],
            up["programs"], up["device_tail_s"], up["other_s"],
            up["trace_s"], up["lower_s"], up["compile_s"],
            up["cache_read_s"], up["compiles"], up["cache_hits"],
            up["cache_misses"]))


class LLMServer:
    """The replica callable: owns one engine instance + its step loop."""

    def __init__(self, llm_config: LLMConfig):
        self._replica_tag = f"{os.getpid()}-{uuid.uuid4().hex[:6]}"
        self.engine = build_engine(llm_config, replica=self._replica_tag)
        self.config = llm_config
        self.tokenizer = llm_config.tokenizer
        self._timeout_s = llm_config.stream_timeout_s
        # The devices that hold this replica's parameter shards.
        import jax

        self._devices = sorted({str(d) for leaf in jax.tree.leaves(
            self.engine.runner.params) for d in leaf.devices()})
        logging.getLogger(__name__).info(
            "replica %s on %s: %s", self._replica_tag, self._devices,
            _startup_line(self.engine.startup))
        self._lock = threading.Lock()
        # request_id -> per-request event queue; the engine loop fans
        # RequestOutputs out to these (token-at-a-time streaming).
        self._streams: Dict[str, queue.Queue] = {}
        # Decode-throughput EWMA published via engine_stats()/gauges.
        self._tokens_per_s = 0.0
        self._tok_count = 0
        self._tok_t0 = time.monotonic()
        self._gauges = self._bind_gauges()
        self._stall_seconds: Dict[str, float] = {}    # published, by cause
        # Tiered prefix store (llm/prefix_store.py): host spill tier +
        # cluster publish/adopt, each optional per config. The cluster tier
        # degrades to None outside a cluster (in-process tests, bench).
        host_tier = cluster_store = None
        if llm_config.host_prefix_mb > 0:
            from ray_tpu.llm.prefix_store import HostPrefixTier

            host_tier = HostPrefixTier(
                int(llm_config.host_prefix_mb * (1 << 20)),
                low_watermark=llm_config.host_prefix_low_watermark)
        if llm_config.cluster_prefix_store:
            from ray_tpu.llm.prefix_store import ClusterPrefixStore

            store = ClusterPrefixStore(
                llm_config.block_size, replica=self._replica_tag,
                deployment=llm_config.deployment_name)
            if store.available():
                cluster_store = store
        if host_tier is not None or cluster_store is not None:
            self.engine.attach_prefix_store(host_tier=host_tier,
                                            cluster_store=cluster_store)
        # LoRA pool autoscaling: ticked at 1 Hz from the engine loop.
        self._lora_policy = None
        if llm_config.lora_autoscale and self.engine.runner.lora is not None:
            from ray_tpu.llm.lora import (LoRAPoolPolicy,
                                          LoRAPoolPolicyConfig)

            self._lora_policy = LoRAPoolPolicy(LoRAPoolPolicyConfig(
                min_slots=llm_config.lora_min_slots,
                max_slots=llm_config.lora_max_slots))
        # KV stream listener — always on: prefill replicas stream populated
        # pages here in disaggregated mode, and draining peers migrate live
        # sessions here in every mode (llm/disagg.py wire).
        from ray_tpu.llm.disagg import KVStreamServer

        self._handoff = KVStreamServer(self._adopt_handoff,
                                       host=llm_config.handoff_host)
        # Set when this replica is being retired (node drain / scale-down):
        # new submissions bounce with ReplicaDrainingError and
        # migrate_sessions moves the live ones out.
        self._draining = False
        self._sessions_migrated_out = 0
        self._loop = threading.Thread(target=self._engine_loop, daemon=True,
                                      name=f"llm-engine-{self._replica_tag}")
        self._loop.start()

    def _bind_gauges(self):
        from ray_tpu.runtime import metric_defs as md

        tags = {"replica": self._replica_tag}
        return {
            "running": md.LLM_RUNNING.bind(tags),
            "waiting": md.LLM_WAITING.bind(tags),
            "prefilling": md.LLM_PREFILLING.bind(tags),
            "free_kv_blocks": md.LLM_KV_FREE_BLOCKS.bind(tags),
            "total_kv_blocks": md.LLM_KV_TOTAL_BLOCKS.bind(tags),
            "prefix_hits": md.LLM_PREFIX_HITS.bind(tags),
            "prefix_tokens_saved": md.LLM_PREFIX_TOKENS_SAVED.bind(tags),
            "tokens_per_s": md.LLM_TOKENS_PER_S.bind(tags),
        }

    # ---- engine loop -----------------------------------------------------

    def _engine_loop(self):
        from ray_tpu.util import tracing

        log = logging.getLogger(__name__)
        while True:
            try:
                with self._lock:
                    busy = self.engine.has_unfinished()
                    outs = self.engine.step() if busy else []
            except Exception as e:
                # A wedged engine must not silently strand every request:
                # surface the failure to all waiters and reset to a clean
                # scheduler state.
                log.exception("engine step failed; failing active requests")
                with self._lock:
                    # Force-release everything, the step in flight's handle
                    # and requests included.
                    self.engine.drop_all()
                for q in list(self._streams.values()):
                    q.put(e)
                continue
            # Between two step() calls: the flight record's since_prev_ms,
            # and `llm:loop` on the profiler's host plane.
            with tracing.PhaseClock("llm:loop"):
                for out in outs:
                    self._tok_count += len(out.new_token_ids)
                    q = self._streams.get(out.request_id)
                    if q is not None:
                        q.put(out)
                now = time.monotonic()
                if now - self._tok_t0 >= 1.0:
                    rate = self._tok_count / (now - self._tok_t0)
                    self._tokens_per_s = (rate if self._tokens_per_s == 0.0
                                          else 0.7 * self._tokens_per_s
                                          + 0.3 * rate)
                    self._tok_count = 0
                    self._tok_t0 = now
                    try:
                        self._publish_gauges()
                    except Exception:
                        pass
                    if self._lora_policy is not None:
                        try:
                            self._lora_pool_tick(now)
                        except Exception:
                            pass
            if not busy:
                time.sleep(0.005)

    def _submit(self, prompt, params, lora_name=None,
                request_id: Optional[str] = None) -> str:
        if self._draining:
            raise ReplicaDrainingError(self._replica_tag)
        # Honor a caller-assigned id (the router names requests): the
        # engine seeds sampling from crc32(request_id) when no explicit
        # seed is set, so a failover replay under the same id reproduces
        # the identical token stream on any replica.
        rid = request_id or uuid.uuid4().hex[:12]
        q: queue.Queue = queue.Queue()
        self._streams[rid] = q
        try:
            with self._lock:
                self.engine.add_request(prompt, params, request_id=rid,
                                        lora_name=lora_name)
        except Exception:
            self._streams.pop(rid, None)
            raise
        return rid

    def _parse(self, request: Dict):
        from ray_tpu.llm.sampling import SamplingParams

        prompt = request.get("prompt", [])
        if isinstance(prompt, str):
            if self.tokenizer is None:
                raise ValueError("string prompts require a tokenizer")
            prompt = self.tokenizer.encode(prompt)
        params = SamplingParams(
            temperature=float(request.get("temperature", 0.0)),
            top_k=int(request.get("top_k", 0)),
            top_p=float(request.get("top_p", 1.0)),
            max_tokens=int(request.get("max_tokens", 32)),
            stop_token_ids=request.get("stop_token_ids"),
            seed=request.get("seed"))
        return prompt, params, request.get("lora_name"), \
            request.get("request_id")

    def _abort(self, rid: str) -> bool:
        """Stop decoding for a dead consumer and free its KV pages."""
        with self._lock:
            aborted = self.engine.abort_request(rid)
        self._streams.pop(rid, None)
        return aborted

    def abort(self, rid: str) -> bool:
        """Router-facing abort: after a collect call fails mid-generation
        the router replays the request elsewhere and aborts the orphan here
        so it stops burning decode compute and KV pages. Idempotent."""
        return self._abort(rid)

    # ---- stats / observability ------------------------------------------

    def engine_stats(self) -> Dict:
        """Per-replica load signal the router's pow2/admission logic
        consumes; also pushes the same numbers to the bound gauges."""
        with self._lock:
            s = self.engine.stats()
        s["tokens_per_s"] = round(self._tokens_per_s, 1)
        s["replica"] = self._replica_tag
        s["devices"] = self._devices
        s["draining"] = self._draining
        s["node_id"] = _node_hex()
        s["sessions_migrated_out"] = self._sessions_migrated_out
        if self._handoff is not None:
            s["handoff_address"] = list(self._handoff.address)
            s["handoffs_adopted"] = self._handoff.handoffs_adopted
            s["handoffs_rejected"] = self._handoff.handoffs_rejected
        try:
            self._publish_gauges(s)
        except Exception:
            pass
        return s

    def flight_records(self, limit: Optional[int] = None,
                       request_id: Optional[str] = None,
                       stalls: bool = False) -> List:
        """Engine tick flight recorder (llm/engine.py): the per-tick batch
        composition / budget / recompile ring, for attributing a slow token
        to its cause. `request_id` filters to ticks that emitted for it;
        `stalls=True` returns the last long ticks instead, each with the
        record after it, which outlive the ring (`engine.stall_records`)."""
        with self._lock:
            return self.engine.tick_records(limit=limit,
                                            request_id=request_id,
                                            stalls=stalls)

    def _publish_gauges(self, s: Optional[Dict] = None):
        from_loop = s is None
        if from_loop:
            with self._lock:
                s = self.engine.stats()
            s["tokens_per_s"] = round(self._tokens_per_s, 1)
        g = self._gauges
        g["running"].set(s["running"])
        g["waiting"].set(s["waiting"])
        g["prefilling"].set(s["prefilling"])
        g["free_kv_blocks"].set(s["free_kv_blocks"])
        g["total_kv_blocks"].set(s["total_kv_blocks"])
        g["prefix_hits"].set(s["prefix_hits"])
        g["prefix_tokens_saved"].set(s["prefix_tokens_saved"])
        g["tokens_per_s"].set(s["tokens_per_s"])
        if not from_loop:
            return
        # The time account's stalls, as a counter's growth by cause: from
        # the engine loop's call alone, so that one thread books it.
        from ray_tpu.runtime import metric_defs as md

        for cause, entry in s["time"]["stalls"].items():
            grown = entry["seconds"] - self._stall_seconds.get(cause, 0.0)
            if grown > 0:
                self._stall_seconds[cause] = entry["seconds"]
                md.LLM_STALL_SECONDS.inc(grown, tags={"cause": cause})

    # ---- KV handoff + live migration (llm/disagg.py wire) ----------------

    def handoff_address(self) -> List:
        return list(self._handoff.address)

    def resume_admission(self) -> None:
        """Cancel a drain: start admitting again. For the reprieve path —
        the node's drain was withdrawn, or a scale-down decision reversed
        before the replica was retired. Sessions already migrated out
        stay migrated (their KV lives on the adoptive replica now)."""
        self._draining = False

    def migrate_sessions(self, target_address, *,
                         timeout: float = 60.0) -> Dict:
        """Drain-plane live migration: stop admitting, then move every live
        request to `target_address` (another replica's KV stream listener).

        Decoding requests travel with their populated KV pages over the
        zero-pickle raw-frame wire — whole-stream-or-discard, so a target
        dying mid-adopt leaves nothing torn and the request falls back to
        seeded replay from the prompt. Requests still queued or mid-prefill
        always take the replay path (their partial KV is discarded whole).
        A request is either finished (its last tick delivered it, under
        this lock) or live and exported — migration never double-delivers.
        Consumers blocked in
        completions/_collect get a SessionMigratedError naming the mode so
        the router re-collects (kv) or re-submits (replay); no client ever
        observes this replica going away. Returns per-mode rid lists."""
        from ray_tpu.llm.disagg import migrate_session

        self._draining = True
        migrated: List[str] = []
        replayed: List[str] = []
        exports: List[tuple] = []
        with self._lock:
            # Under the lock no tick is running, and export_session settles
            # the step in flight: its tokens are on the host, and no device
            # write can land in an exported page.
            live = ([r.id for r in self.engine.running]
                    + [r.id for r in self.engine.prefilling]
                    + [r.id for r in self.engine.waiting])
            for rid in live:
                state, mode = self.engine.export_session(rid)
                if state is None:
                    continue
                if mode == "kv":
                    blocks = state.pop("blocks")
                    pages = self.engine.runner.gather_pages(blocks)
                    self.engine.block_manager.release_blocks(blocks)
                    exports.append((rid, state, pages))
                else:
                    replayed.append(rid)
        # Stream outside the lock (PrefillServer's discipline: socket time
        # must never serialize engine work — and the failure path below
        # must not hold the engine hostage either).
        send_failed: List[str] = []
        for rid, state, pages in exports:
            # The pause is a first-class trace span, not a silent gap: it
            # starts at export (the engine stamped t_handoff then — decode
            # stopped for this request the moment it left the scheduler)
            # and ends when the target acked adoption. The adopter books
            # the same interval into the request's stall_s via t_handoff.
            t_pause0 = (state.get("timing") or {}).get("t_handoff",
                                                       time.time())
            from ray_tpu.util import tracing

            try:
                # The stream rides under the request's trace context so the
                # kv_handoff span (opened inside send_handoff) — and the
                # adopter's kv_adopt span parent-linked to it over the wire
                # — stitch into this request's trace, not a fresh one.
                with tracing.trace_context(tracing.request_trace_id(rid),
                                           None):
                    migrate_session(target_address, state, *pages,
                                    timeout=timeout)
                    migrated.append(rid)
                    tracing.record_span(
                        "llm:migration_pause", "llm", t_pause0, time.time(),
                        request_id=rid, source=self._replica_tag, mode="kv")
                self.engine.flight_records.append({
                    "t": t_pause0, "kind": "migration_pause",
                    "dur_ms": round((time.time() - t_pause0) * 1e3, 3),
                    "emitted": {}, "request_id": rid})
            except Exception:
                # Atomic wire: nothing half-adopted — but a timeout with a
                # LOST ACK can leave the session fully adopted (decoding
                # with no consumer) on the target while we replay it from
                # the prompt. Report these rids so the router best-effort
                # aborts them on the target before the replay starts.
                send_failed.append(rid)
                replayed.append(rid)
        for rid in migrated:
            q = self._streams.get(rid)
            if q is not None:
                q.put(SessionMigratedError(rid, "kv"))
        for rid in replayed:
            q = self._streams.get(rid)
            if q is not None:
                q.put(SessionMigratedError(rid, "replay"))
        self._sessions_migrated_out += len(migrated)
        return {"migrated": migrated, "replayed": replayed,
                "send_failed": send_failed,
                "replica": self._replica_tag}

    def push_prefixes(self, target_address, *, limit: int = 16,
                      timeout: float = 60.0) -> Dict:
        """Drain-plane working-set handoff: stream the hottest reusable
        prefix pages (device `reusable` pool first, then the host tier) to
        `target_address` — another replica's KV stream listener — so a
        drain's successor starts warm instead of re-prefilling the shared
        prompts. Same whole-or-nothing raw-frame wire as
        migrate_sessions; a failed send costs nothing (the pages were
        already spill candidates)."""
        from ray_tpu.llm.disagg import send_handoff

        with self._lock:
            export = self.engine.export_prefixes(limit=limit)
        if export is None:
            return {"pushed": 0, "replica": self._replica_tag}
        state, *pages = export
        try:
            send_handoff(target_address, state, *pages, timeout=timeout)
        except Exception:
            return {"pushed": 0, "replica": self._replica_tag,
                    "error": "send_failed"}
        return {"pushed": len(state["entries"]),
                "replica": self._replica_tag}

    def _lora_pool_tick(self, now: float) -> None:
        """1 Hz LoRA pool autoscale: LoRAPoolPolicy reads the engine stats
        and, when the watermarks say so, resizes the adapter slot table
        under the engine lock (the resize rebuilds the stacked tensors, so
        it must not race a step)."""
        mgr = self.engine.runner.lora
        with self._lock:
            target = self._lora_policy.desired(self.engine.stats(),
                                               now)
            if target is not None and target != mgr.n_slots - 1:
                mgr.resize(target)

    def _adopt_handoff(self, state: Dict, *pages) -> bool:
        # Drain-plane prefix push (push_prefixes): cached pages, not a
        # live session — adopt straight into the prefix cache; no stream
        # queue, no consumer.
        if state.get("prefix"):
            with self._lock:
                return self.engine.adopt_prefix(state, *pages) > 0
        # The stream queue must exist BEFORE the request can start decoding
        # (the engine loop drops outputs with no queue), and the ack goes
        # back only after adopt_request returns — so by the time the router
        # calls completions_collect, both are in place.
        rid = state["id"]
        q: queue.Queue = queue.Queue()
        self._streams[rid] = q
        with self._lock:
            ok = self.engine.adopt_request(state, *pages)
        if not ok:
            self._streams.pop(rid, None)
        return ok

    # ---- LoRA management (multiplex) ------------------------------------

    def load_lora_adapter(self, adapter) -> Dict:
        """Dynamically install a LoRAAdapter (LRU-evicting when full)."""
        if self.engine.runner.lora is None:
            raise ValueError("replica built without LoRA support "
                             "(set LLMConfig.lora_adapters)")
        with self._lock:
            slot = self.engine.runner.lora.load_adapter(adapter)
        return {"name": adapter.name, "slot": slot}

    def list_lora_adapters(self) -> Dict:
        mgr = self.engine.runner.lora
        return {"adapters": mgr.loaded if mgr is not None else []}

    # ---- API -------------------------------------------------------------

    def __call__(self, request: Dict) -> Dict:
        return self.completions(request)

    def completions(self, request: Dict) -> Dict:
        """OpenAI-ish /v1/completions: {"prompt": str|[int], "max_tokens",
        "temperature", "top_k", "top_p", "stop_token_ids"}."""
        prompt, params, lora_name, rid = self._parse(request)
        rid = self._submit(prompt, params, lora_name, rid)
        return self._collect(rid)

    def completions_collect(self, request_id: str) -> Dict:
        """Wait out an already-submitted request (the router calls this on
        the decode replica after a prefill handoff was adopted)."""
        if request_id not in self._streams:
            raise KeyError(f"unknown request {request_id!r} "
                           "(handoff not adopted here?)")
        return self._collect(request_id)

    def _collect(self, rid: str) -> Dict:
        from ray_tpu.llm.disagg import _completion_response

        q = self._streams[rid]
        try:
            while True:
                try:
                    out = q.get(timeout=self._timeout_s)
                except queue.Empty:
                    # Consumer still here but the engine went silent, or the
                    # client's deadline passed: stop burning KV blocks.
                    self._abort(rid)
                    raise RequestTimeoutError(
                        f"request {rid}: no engine output within "
                        f"{self._timeout_s}s; request aborted") from None
                if isinstance(out, Exception):
                    raise out
                if out.finished:
                    break
        finally:
            self._streams.pop(rid, None)
        return _completion_response(out)

    def completions_stream(self, request: Dict):
        """Streaming completions: a generator of OpenAI-style chunk events,
        one per sampled token. Consume through
        handle.options("completions_stream").remote_stream(request)."""
        prompt, params, lora_name, rid = self._parse(request)
        rid = self._submit(prompt, params, lora_name, rid)
        q = self._streams[rid]
        finished = False
        try:
            while True:
                try:
                    out = q.get(timeout=self._timeout_s)
                except queue.Empty:
                    raise RequestTimeoutError(
                        f"request {rid}: no engine output within "
                        f"{self._timeout_s}s; request aborted") from None
                if isinstance(out, Exception):
                    raise out
                for t in out.new_token_ids:
                    yield {"id": rid, "object": "text_completion.chunk",
                           "token": int(t), "finished": False}
                if out.finished:
                    finished = True
                    yield {"id": rid, "object": "text_completion.chunk",
                           "token": None, "finished": True,
                           "finish_reason": out.finish_reason,
                           "text": out.text,
                           "token_ids": out.output_token_ids}
                    return
        finally:
            # Runs on timeout, engine error, AND consumer disappearance
            # (GeneratorExit via _StreamingResponse.__del__): an unfinished
            # request must not keep decoding to max_tokens for a dead
            # stream — abort it and free its pages.
            if not finished:
                self._abort(rid)
            self._streams.pop(rid, None)


def build_llm_deployment(llm_config: LLMConfig, name: str = "llm") -> Any:
    if llm_config.deployment_name != name:
        llm_config = dataclasses.replace(llm_config, deployment_name=name)
    dep = serve.deployment(LLMServer).options(
        name=name, num_replicas=llm_config.num_replicas,
        num_tpus=llm_config.num_tpus_per_replica,
        max_ongoing_requests=llm_config.max_batch_size)
    return dep.bind(llm_config)


def build_routed_app(llm_config: LLMConfig, name: str = "v1-completions",
                     *, http: bool = True):
    """Deploys the full serving plane: `{name}-engine` (decode or colocated
    replicas), optionally `{name}-prefill` (disaggregate > 0), and the
    `{name}` router deployment fronting them. Returns the router handle."""
    from ray_tpu.llm.disagg import PrefillServer
    from ray_tpu.llm.router import LLMRouter

    tiers = [build_llm_deployment(llm_config, f"{name}-engine")]
    prefill_name = None
    if llm_config.disaggregate > 0:
        prefill_name = f"{name}-prefill"
        tiers.append(serve.deployment(PrefillServer).options(
            name=prefill_name, num_replicas=llm_config.disaggregate,
            num_tpus=llm_config.num_tpus_per_replica,
            max_ongoing_requests=llm_config.max_batch_size,
        ).bind(llm_config))
    # Tiers first: the router resolves their replica handles lazily on the
    # first request, and they must already be deployed by then.
    serve.run(tiers)
    router = serve.deployment(LLMRouter).options(
        name=name, num_replicas=1,
        max_ongoing_requests=8 * llm_config.max_batch_size,
    ).bind(llm_config, f"{name}-engine", prefill_name)
    return serve.run(router, http=http)


def build_openai_app(llm_config: LLMConfig, name: str = "v1-completions"):
    """Deploys the engine and the HTTP ingress; POST /{name} serves
    completions. With routing="affinity" or disaggregate > 0 the app gets
    the router front (build_routed_app) instead of a bare replica fleet."""
    if llm_config.routing == "affinity" or llm_config.disaggregate > 0:
        return build_routed_app(llm_config, name)
    handle = serve.run(build_llm_deployment(llm_config, name), http=True)
    return handle
