"""Central runtime config table: typed tunables, env-overridable.

Reference analog: src/ray/common/ray_config_def.h (223 RAY_CONFIG macros,
overridable via RAY_* env vars and the _system_config dict passed at init,
serialized to components). Ours: one table; override precedence is
    _system_config (init kwarg)  >  RAY_TPU_<NAME> env var  >  default.
Components read `cfg().<name>` at use time, so test fixtures and
_system_config can retune without import-order games.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Tuple

# name -> (type, default, doc)
_DEFS: Dict[str, Tuple[type, Any, str]] = {
    # -- core worker -------------------------------------------------------
    "inline_result_max": (int, 100 * 1024,
                          "max bytes for inline (non-plasma) task results"),
    "lease_idle_timeout_s": (float, 1.0,
                             "idle worker lease kept warm before return"),
    "lease_max_inflight_requests": (int, 64,
                                    "outstanding worker-lease requests per "
                                    "scheduling key"),
    "actor_max_inflight_calls": (int, 128,
                                 "pipelined in-flight calls per actor client"),
    "pull_chunk_bytes": (int, 4 << 20, "chunk size for remote object pulls"),
    "lineage_max_entries": (int, 100_000, "owner-side lineage cap"),
    "max_dependency_reconstructions": (int, 3,
                                       "per-task cap on recursive lost-arg "
                                       "recoveries before the error surfaces"),
    "reconstruction_attempts": (int, 3,
                                "re-executions before an object is lost"),
    # -- raylet / GCS ------------------------------------------------------
    "heartbeat_interval_s": (float, 2.0, "raylet resource heartbeat period"),
    "lease_batch_max": (int, 64,
                        "lease requests coalesced into one "
                        "LeaseBatchRequestMsg frame per raylet per pump "
                        "(the raylet grants the batch in one scheduling "
                        "pass)"),
    "worker_prestart": (int, 0,
                        "idle workers spawned at raylet start (0 = spawn on "
                        "first lease; capped by the node's CPU count)"),
    "job_keepalive_interval_s": (float, 2.0,
                                 "driver job-heartbeat period (owner-death "
                                 "detection for auto-started clusters)"),
    "health_check_interval_s": (float, 2.0, "GCS node health check period"),
    "health_check_failure_threshold": (int, 3,
                                       "missed health checks before a node "
                                       "is declared dead"),
    "worker_monitor_interval_s": (float, 0.2,
                                  "raylet child-process poll period"),
    "worker_pool_max_idle": (int, 8,
                             "idle workers kept per raylet; beyond this the "
                             "oldest idle worker is terminated (bounds pool "
                             "growth across distinct runtime_envs)"),
    "runtime_env_cache_bytes": (int, 10 << 30,
                                "per-node budget for materialized runtime-env "
                                "URIs (packages, pip venvs); unpinned URIs "
                                "evict LRU-first beyond this"),
    "pg_retry_interval_s": (float, 0.2,
                            "GCS retry period for PENDING placement groups"),
    "memory_monitor_interval_s": (float, 1.0, "OOM monitor sample period"),
    "memory_usage_threshold": (float, 0.95,
                               "fraction of system memory triggering the "
                               "OOM killer"),
    # -- object store ------------------------------------------------------
    "object_store_memory_default": (int, 2 << 30,
                                    "default shm store capacity bytes"),
    "spill_chunk_bytes": (int, 8 << 20, "spill file IO chunk"),
    "spill_high_watermark": (float, 0.85,
                             "store fill fraction where the raylet starts "
                             "proactive background spilling (0 disables)"),
    "spill_low_watermark": (float, 0.70,
                            "proactive spilling stops below this fill "
                            "fraction"),
    "pull_admission_concurrency": (int, 16,
                                   "concurrent cross-node chunk reads a "
                                   "raylet serves (admission control)"),
    "broadcast_fanout": (int, 2, "relay-tree fanout for object broadcast"),
    # -- data --------------------------------------------------------------
    "data_store_highwater": (float, 0.8,
                             "object-store fill fraction where dataset "
                             "producers start throttling"),
    "data_max_in_flight": (int, 8,
                           "bounded in-flight block tasks per stage"),
    "data_task_timeout_s": (float, 600.0, "per block-task wait timeout"),
    # -- serve -------------------------------------------------------------
    "serve_autoscale_interval_s": (float, 1.0, "controller autoscale tick"),
    "serve_handle_refresh_s": (float, 1.0,
                               "handle replica-set re-poll period"),
    "serve_replica_health_timeout_s": (float, 300.0,
                                       "replica construction deadline"),
    # -- llm engine --------------------------------------------------------
    "llm_prefill_chunk": (int, 128, "default chunked-prefill token budget"),
    # -- observability -----------------------------------------------------
    "task_events_max": (int, 10_000,
                        "task state events retained by the GCS"),
    "task_events_flush_interval_s": (float, 1.0,
                                     "worker-side task event batch period"),
    "event_flush_batch_max": (int, 2000,
                              "task events per TaskEventBatchMsg frame; a "
                              "fuller buffer ships in multiple frames on "
                              "the same tick"),
    "gcs_ring_shards": (int, 16,
                        "per-node shards of the GCS task-event ring; "
                        "ingest and index upkeep are O(shard), reads "
                        "merge across shards"),
    "cluster_events_max": (int, 10_000,
                           "structured cluster events retained by the GCS "
                           "event ring (see runtime/events.py)"),
    "stall_detector_interval_s": (float, 2.0,
                                  "GCS wait-graph detector tick period "
                                  "(cycle -> DEADLOCK_DETECTED, old edge "
                                  "-> TASK_STALLED)"),
    "stall_threshold_s": (float, 30.0,
                          "a wait-graph edge blocked longer than this is "
                          "reported as TASK_STALLED"),
    "wait_edge_max_age_s": (float, 15.0,
                            "GCS drops a reporter's wait edges not "
                            "refreshed within this window (crashed or "
                            "unblocked worker)"),
    "metrics_history_enabled": (bool, True,
                                "GCS folds every metrics flush into sharded "
                                "time-series rings (windowed queries, "
                                "link utilization, alerting); off = "
                                "latest-snapshot-only, the pre-history "
                                "behavior"),
    "metrics_history_max_bytes": (int, 8 << 20,
                                  "byte budget for the GCS metric-history "
                                  "rings; oldest points are evicted first "
                                  "once the estimate crosses it"),
    "alert_eval_interval_s": (float, 2.0,
                              "GCS alert-table evaluation tick period "
                              "(rules in runtime/alert_defs.py -> "
                              "ALERT_FIRING / ALERT_RESOLVED events)"),
    # -- collectives -------------------------------------------------------
    "collective_watchdog_interval_s": (float, 1.0,
                                       "peer-liveness/abort poll period of "
                                       "the collective watchdog during "
                                       "blocking ops"),
    "collective_peer_miss_threshold": (int, 3,
                                       "consecutive stale watchdog "
                                       "heartbeats before a collective peer "
                                       "is declared lost and the group "
                                       "aborts"),
    "collective_op_timeout_s": (float, 120.0,
                                "per-op deadline for blocking out-of-graph "
                                "collective ops"),
    "collective_topology": (str, "ring",
                            "out-of-graph collective data plane: 'ring' "
                            "(chunked ring algorithms over p2p links, "
                            "zero-pickle raw frames) or 'hub' (legacy "
                            "rank-0 star, pickled payloads)"),
    "collective_chunk_bytes": (int, 1 << 20,
                               "chunk size for ring collective transfers; "
                               "large tensors pipeline across hops in "
                               "chunks of this size and per-op scratch "
                               "memory stays bounded at one chunk"),
    "ddp_bucket_bytes": (int, 4 << 20,
                         "gradient-coalescing bucket size for "
                         "allreduce_gradients; each per-dtype bucket "
                         "launches its ring allreduce as it fills so "
                         "reduction overlaps the remaining flatten work"),
    # -- rlhf --------------------------------------------------------------
    "rlhf_placement_check_interval": (int, 1,
                                      "PPO iterations between adaptive "
                                      "placement evaluations"),
    "rlhf_rollout_frac_high": (float, 0.60,
                               "rollout share of iteration wall time above "
                               "which the adaptive policy disaggregates "
                               "(generation dominates: give the generator "
                               "its own gang and KV pool)"),
    "rlhf_rollout_frac_low": (float, 0.35,
                              "rollout share below which the adaptive "
                              "policy re-colocates (updates dominate: "
                              "reclaim the slice, cheap in-place sync)"),
    "rlhf_kv_pressure_high": (float, 0.75,
                              "KV pool occupancy fraction treated as "
                              "generator memory pressure; at/above this a "
                              "colocated generator disaggregates even if "
                              "rollout time alone would not justify it"),
    "rlhf_placement_min_dwell": (int, 2,
                                 "iterations a placement mode must persist "
                                 "before the policy may switch again "
                                 "(hysteresis against signal flapping)"),
    # -- train -------------------------------------------------------------
    "train_poll_interval_s": (float, 0.2, "controller worker poll period"),
    "train_elastic_check_interval_s": (float, 10.0,
                                       "elastic scaling evaluation period"),
    "train_restart_resource_wait_s": (float, 30.0,
                                      "max wait for cluster capacity to fit "
                                      "the worker group before a failure "
                                      "restart attempt (gang restarts race "
                                      "the autoscaler replacing a slice)"),
    "train_drain_check_interval_s": (float, 1.0,
                                     "how often the Train controller polls "
                                     "for NODE_DRAINING events overlapping "
                                     "its worker group (must be well under "
                                     "the shortest expected drain notice)"),
    # -- checkpoint plane ----------------------------------------------------
    "ckpt_fsync": (bool, True,
                   "fsync shard/manifest files before the atomic rename; "
                   "disable only in tests where durability is irrelevant"),
    "ckpt_commit_wait_s": (float, 60.0,
                           "how long rank 0's persister waits for the last "
                           "rank's manifest commit before reporting the "
                           "save as uncommitted"),
    "ckpt_flush_timeout_s": (float, 30.0,
                             "max wait for in-flight background persists "
                             "when a worker group quiesces (drain/resize)"),
    "ckpt_replicate": (bool, False,
                       "replicate completed checkpoint shards to peer "
                       "object stores via the broadcast fanout tree and "
                       "register them in the GCS relocation table"),
    "ckpt_replicate_timeout_s": (float, 60.0,
                                 "per-shard timeout for the replication "
                                 "fanout"),
    # -- drain / preemption --------------------------------------------------
    "drain_deadline_default_s": (float, 30.0,
                                 "drain notice window used when an "
                                 "autoscaler preemption notice carries no "
                                 "explicit deadline"),
    "actor_restart_capacity_wait_s": (float, 30.0,
                                      "max wait for a feasible node during "
                                      "an actor restart (a preempted node's "
                                      "replacement races registration) "
                                      "before the restart fails"),
}


class RayTpuConfig:
    def __init__(self):
        self._values: Dict[str, Any] = {}
        for name, (typ, default, _doc) in _DEFS.items():
            env = os.environ.get(f"RAY_TPU_{name.upper()}")
            if env is not None:
                try:
                    self._values[name] = (typ(env) if typ is not bool
                                          else env not in ("0", "false", ""))
                except ValueError:
                    raise ValueError(
                        f"bad value for RAY_TPU_{name.upper()}: {env!r}")
            else:
                self._values[name] = default

    def __getattr__(self, name: str):
        try:
            return self.__dict__["_values"][name]
        except KeyError:
            raise AttributeError(f"unknown config {name!r}") from None

    def apply_overrides(self, overrides: Dict[str, Any]):
        """init(_system_config=...) path; unknown keys are an error (typos
        must not silently no-op)."""
        for k, v in overrides.items():
            if k not in _DEFS:
                raise ValueError(f"unknown system config key {k!r}")
            self._values[k] = _DEFS[k][0](v)

    def as_dict(self) -> Dict[str, Any]:
        return dict(self._values)


_instance = None
_lock = threading.Lock()


def cfg() -> RayTpuConfig:
    global _instance
    if _instance is None:
        with _lock:
            if _instance is None:
                _instance = RayTpuConfig()
    return _instance


def reset_for_testing():
    global _instance
    _instance = None
