"""Native runtime metric definitions: the central table of what the
runtime itself measures.

Reference analog: src/ray/stats/metric_defs.cc (every native metric —
task counts, scheduler state, object store usage, gRPC latencies — defined
in one place and exported through the metrics agent). Ours defines the
runtime metrics once; components import and bump them, and every process's
metrics ride the existing snapshot/Prometheus path (util/metrics.py +
dashboard /metrics).
"""

from __future__ import annotations

from ray_tpu.util.metrics import Counter, Gauge, Histogram

# -- core worker -----------------------------------------------------------

TASKS_SUBMITTED = Counter(
    "ray_tpu_tasks_submitted_total",
    "task submissions from this process (normal tasks)")
TASKS_FINISHED = Counter(
    "ray_tpu_tasks_finished_total",
    "tasks whose result landed back at this owner, by outcome",
    tag_keys=("outcome",))                       # ok | error | retried
ACTOR_CALLS = Counter(
    "ray_tpu_actor_calls_total", "actor method submissions")
OBJECTS_OWNED = Gauge(
    "ray_tpu_owned_objects", "objects this worker currently owns")
SPILLED_BYTES = Counter(
    "ray_tpu_spilled_bytes_total", "bytes spilled to external storage")
RESTORED_BYTES = Counter(
    "ray_tpu_restored_bytes_total", "bytes restored from external storage")
RECONSTRUCTIONS = Counter(
    "ray_tpu_object_reconstructions_total",
    "lineage re-executions triggered by lost objects")
TASK_EVENTS_DROPPED = Counter(
    "ray_tpu_task_events_dropped_total",
    "task state events trimmed from this worker's buffer before flush "
    "(buffer overflow; raise task_events_max or lower the flush interval)")

# -- raylet ----------------------------------------------------------------

LEASES_GRANTED = Counter(
    "ray_tpu_leases_granted_total", "worker leases granted by this raylet")
LEASES_SPILLED = Counter(
    "ray_tpu_leases_spilled_total",
    "lease requests redirected to another node (spillback)")
WORKERS_STARTED = Counter(
    "ray_tpu_workers_started_total", "worker processes spawned")
OOM_KILLS = Counter(
    "ray_tpu_oom_kills_total", "workers killed by the memory monitor")
PENDING_LEASES = Gauge(
    "ray_tpu_pending_leases", "queued lease requests on this raylet")
OBJECT_STORE_USED = Gauge(
    "ray_tpu_object_store_used_bytes",
    "bytes occupied in this node's shared object-store arena",
    tag_keys=("node",))
OBJECT_STORE_CAPACITY = Gauge(
    "ray_tpu_object_store_capacity_bytes",
    "total size of this node's shared object-store arena",
    tag_keys=("node",))
OBJECT_STORE_SPILLED = Gauge(
    "ray_tpu_object_store_spilled_bytes",
    "bytes currently resident in this node's spill directory",
    tag_keys=("node",))
NODES_DRAINING = Gauge(
    "ray_tpu_nodes_draining",
    "1 while this node is draining toward an announced retirement "
    "deadline (advance-notice preemption), 0 otherwise",
    tag_keys=("node",))

# -- object plane ----------------------------------------------------------

PULLS_SERVED = Counter(
    "ray_tpu_object_pulls_served_total",
    "cross-node object chunk reads served")
PULL_LATENCY = Histogram(
    "ray_tpu_object_pull_seconds", "end-to-end remote object pull latency",
    boundaries=[0.001, 0.01, 0.05, 0.25, 1.0, 5.0, 30.0])

# -- data ------------------------------------------------------------------

DATA_BACKPRESSURE = Counter(
    "ray_tpu_data_backpressure_total",
    "dataset producer throttle ENGAGEMENTS (idle->throttled transitions) "
    "under object-store pressure")
DATA_BLOCKS_PRODUCED = Counter(
    "ray_tpu_data_blocks_produced_total",
    "blocks pulled through streaming data-plane producers (all consumers "
    "on this process)")
DATA_INPUT_WAIT_MS = Histogram(
    "ray_tpu_data_input_wait_ms",
    "time a streaming consumer blocked in next(batch) — near-zero means "
    "the pipeline fully hid ingestion behind compute",
    boundaries=[0.1, 0.5, 1, 5, 10, 50, 100, 500, 1000])
DATA_BACKLOG_DEPTH = Gauge(
    "ray_tpu_data_backlog_depth",
    "produced-but-unconsumed batches in this process's streaming rings "
    "(bounded by prefetch_batches — the backpressure proof)")

# -- collectives -----------------------------------------------------------
# Per-(op, algo) traffic and latency of the out-of-graph collective plane.
# `algo` distinguishes the chunked ring data plane from the legacy rank-0
# hub; components bind() a tag set once and bump the bound handles so the
# per-chunk accounting stays off the hot path.

COLLECTIVE_OPS = Counter(
    "ray_tpu_collective_ops_total",
    "out-of-graph collective operations completed",
    tag_keys=("op", "algo"))
COLLECTIVE_BYTES_SENT = Counter(
    "ray_tpu_collective_bytes_sent_total",
    "bytes sent on collective data-plane links",
    tag_keys=("op", "algo"))
COLLECTIVE_BYTES_RECV = Counter(
    "ray_tpu_collective_bytes_recv_total",
    "bytes received on collective data-plane links",
    tag_keys=("op", "algo"))
COLLECTIVE_OP_LATENCY = Histogram(
    "ray_tpu_collective_op_seconds",
    "end-to-end latency of out-of-graph collective ops",
    boundaries=[0.001, 0.01, 0.05, 0.25, 1.0, 5.0, 30.0],
    tag_keys=("op", "algo"))

# -- serve / llm -----------------------------------------------------------

SERVE_REQUESTS = Counter(
    "ray_tpu_serve_requests_total", "requests routed through handles",
    tag_keys=("deployment",))
LLM_TOKENS_GENERATED = Counter(
    "ray_tpu_llm_tokens_generated_total", "tokens sampled by LLM engines")
LLM_STEP_COMPILES = Counter(
    "ray_tpu_llm_step_compiles_total",
    "XLA compiles triggered by new step-shape signatures (warmup pays "
    "these; any growth in the steady-state loop is a silent-recompile "
    "stall worth chasing)")

# Speculative decoding (engine n-gram drafts + unified-tick acceptance
# sampling): the accepted/proposed ratio is the speculation win per
# deployment — near 1.0 means the draft source predicts the model well,
# near 0 means verify launches are wasted work.
LLM_SPEC_PROPOSED = Counter(
    "ray_tpu_llm_spec_proposed_total",
    "draft tokens submitted to speculative verification")
LLM_SPEC_ACCEPTED = Counter(
    "ray_tpu_llm_spec_accepted_total",
    "draft tokens accepted by speculative verification")

# A model that routes tokens to experts and holds a share of them
# (models/deepseek_v2.py): rows the held experts computed, and how unevenly
# the last unified tick spread them (busiest expert's rows x held experts /
# rows; 1.0 = even). The flight record keeps both per tick (`expert_rows`,
# `expert_rows_max`, beside `routed_rows`, every pick, held or not).
LLM_EXPERT_ROWS = Counter(
    "ray_tpu_llm_expert_rows_total",
    "token-expert pairs computed by the experts this replica holds")
LLM_EXPERT_LOAD_SKEW = Gauge(
    "ray_tpu_llm_expert_load_skew",
    "busiest held expert's rows over the mean held expert's, last tick")

# Per-replica engine depth + KV occupancy: the same numbers
# LLMServer.engine_stats() feeds the router's pow2/admission logic, pushed
# as gauges so dashboards see what the router sees.
LLM_RUNNING = Gauge(
    "ray_tpu_llm_running", "requests in decode on this replica",
    tag_keys=("replica",))
LLM_WAITING = Gauge(
    "ray_tpu_llm_waiting", "requests queued before prefill on this replica",
    tag_keys=("replica",))
LLM_PREFILLING = Gauge(
    "ray_tpu_llm_prefilling", "requests mid-chunked-prefill on this replica",
    tag_keys=("replica",))
LLM_KV_FREE_BLOCKS = Gauge(
    "ray_tpu_llm_kv_free_blocks", "free KV cache pages on this replica",
    tag_keys=("replica",))
LLM_KV_TOTAL_BLOCKS = Gauge(
    "ray_tpu_llm_kv_total_blocks", "total KV cache pages on this replica",
    tag_keys=("replica",))
LLM_PREFIX_HITS = Gauge(
    "ray_tpu_llm_prefix_hits", "prefix-cache block hits (cumulative)",
    tag_keys=("replica",))
LLM_PREFIX_TOKENS_SAVED = Gauge(
    "ray_tpu_llm_prefix_tokens_saved",
    "prompt tokens skipped via prefix cache (cumulative)",
    tag_keys=("replica",))
LLM_TOKENS_PER_S = Gauge(
    "ray_tpu_llm_tokens_per_s", "decode throughput EWMA on this replica",
    tag_keys=("replica",))

# Router plane (llm/router.py) + disaggregated KV handoffs (llm/disagg.py).
LLM_ROUTER_SHED = Counter(
    "ray_tpu_llm_router_shed_total",
    "requests shed by SLO admission (projected TTFT over the SLO)",
    tag_keys=("deployment",))
LLM_ROUTER_AFFINITY = Counter(
    "ray_tpu_llm_router_affinity_total",
    "router picks by prefix/session-affinity outcome",
    tag_keys=("outcome",))                       # hit | miss
LLM_KV_HANDOFFS = Counter(
    "ray_tpu_llm_kv_handoffs_total",
    "prefill->decode KV page handoffs adopted")

# Per-request latency attribution (llm/engine.py _trace_first_token,
# adopt_request, _finish_trace): each request decomposes its TTFT into
# queue/prefill/handoff time, observed when each phase ends (the first two at
# the first token, so a request that is still decoding counts), and at its
# finish its mean inter-token gap into decode/stall time — the histogram
# twins of the per-request trace spans, so fleet-wide tail regressions name a
# phase before anyone pulls a single trace.
LLM_TTFT_BREAKDOWN_MS = Histogram(
    "ray_tpu_llm_ttft_breakdown_ms",
    "per-request time-to-first-token by phase: queue (submit->admit), "
    "prefill (admit->first token), handoff (disagg KV stream gaps)",
    boundaries=[0.1, 0.5, 1, 5, 10, 50, 100, 500, 1000, 5000],
    tag_keys=("phase",))                         # queue | prefill | handoff
LLM_ITL_BREAKDOWN_MS = Histogram(
    "ray_tpu_llm_itl_breakdown_ms",
    "per-request MEAN inter-token gap by phase: decode (engine ticks) and "
    "stall (migration pauses amortized over the request's gaps)",
    boundaries=[0.1, 0.5, 1, 5, 10, 50, 100, 500, 1000],
    tag_keys=("phase",))                         # decode | stall

# The engine's time account (llm/engine.py, "The time account"): the excess
# of every long tick over the median period, by the one cause it was put down
# to. Published once a second from LLMServer._publish_gauges as the growth of
# engine.stats()["time"]["stalls"].
LLM_STALL_SECONDS = Counter(
    "ray_tpu_llm_stall_seconds_total",
    "seconds of long engine ticks beyond the median period, by cause",
    tag_keys=("cause",))    # gc | host_late | device | wait | recompile |
                            # host_work:<phase> | host_blocked:<phase>

# Fleet resilience (llm/router.py FleetSupervisor): failover replays,
# drain-plane session migrations, and the live-replica count the router's
# health tracker believes in. All roll up into
# state.summary()["llm_serving"] like every other ray_tpu_llm_* series.
LLM_FAILOVERS = Counter(
    "ray_tpu_llm_failovers_total",
    "in-flight requests replayed on a surviving replica after a failure",
    tag_keys=("deployment",))
LLM_SESSIONS_MIGRATED = Counter(
    "ray_tpu_llm_sessions_migrated_total",
    "live sessions moved replica->replica (KV pages over the drain plane)",
    tag_keys=("deployment",))
LLM_REPLICAS_HEALTHY = Gauge(
    "ray_tpu_llm_replicas_healthy",
    "replicas the router currently considers live and routable",
    tag_keys=("deployment",))

# Tiered KV prefix store (llm/prefix_store.py): tier="host" is the
# replica-local pinned-RAM spill pool, tier="store" the GCS-homed cluster
# table that survives replica death and restarts.
LLM_PREFIX_SPILLS = Counter(
    "ray_tpu_llm_prefix_spills_total",
    "prefix KV pages demoted into a store tier instead of being dropped",
    tag_keys=("tier",))                          # host | store
LLM_PREFIX_ADOPTIONS = Counter(
    "ray_tpu_llm_prefix_adoptions_total",
    "spilled prefix blocks re-adopted into an engine (re-prefill avoided)",
    tag_keys=("tier",))                          # host | store
LLM_PREFIX_STORE_BYTES = Gauge(
    "ray_tpu_llm_prefix_store_bytes",
    "bytes currently held in this replica's host prefix tier")
LLM_PREFIX_STALE_REJECTED = Counter(
    "ray_tpu_llm_prefix_stale_rejected_total",
    "spilled prefix entries refused at adoption (weights version mismatch)")

# Checkpoint plane (checkpoint/plane.py): the snapshot histogram is the
# train-step stall, the persist histogram is the background cost — the
# 5x-plus gap between them is the async plane's whole point.
CKPT_SNAPSHOT_MS = Histogram(
    "ray_tpu_ckpt_snapshot_ms",
    "device->host snapshot stall per save (the only part a train step "
    "waits for)",
    boundaries=[0.1, 0.5, 1, 5, 10, 50, 100, 500, 1000])
CKPT_PERSIST_MS = Histogram(
    "ray_tpu_ckpt_persist_ms",
    "background shard persist + commit duration per save",
    boundaries=[1, 5, 10, 50, 100, 500, 1000, 5000, 30000])
CKPT_BYTES = Counter(
    "ray_tpu_ckpt_bytes_total",
    "checkpoint bytes persisted by this process (per-rank shard bytes)")


ALL_METRICS = [v for v in list(globals().values())
               if isinstance(v, (Counter, Gauge, Histogram))]
