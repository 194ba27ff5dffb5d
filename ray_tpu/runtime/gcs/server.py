"""GCS: the cluster-global control service.

Reference analog: src/ray/gcs/gcs_server/ (GcsServer gcs_server.h:89). One per
cluster. Owns: internal KV (function/class table lives here —
gcs_function_manager.h:32), node table (gcs_node_manager), actor directory +
lifecycle state machine (gcs_actor_manager.h:291), named actors, placement
groups (gcs_placement_group_manager, 2-phase Prepare/Commit), and cluster
pubsub (InternalPubSubHandler). Persistence is the in-memory store client
(in_memory_store_client.h); the StoreClient seam for a Redis-backed version
is `self._kv` + the table dicts.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import time
import zlib
from typing import Any, Dict, List, Optional, Set, Tuple

from ray_tpu.core.task_spec import ActorSpec
from ray_tpu.runtime.rpc import RpcClient, RpcServer, ServerConnection
from ray_tpu.runtime import scheduling

logger = logging.getLogger(__name__)

# Actor lifecycle states (gcs_actor_manager.h state machine)
PENDING_CREATION = "PENDING_CREATION"
ALIVE = "ALIVE"
RESTARTING = "RESTARTING"
DEAD = "DEAD"


def _find_cycles(graph: dict) -> list:
    """Distinct elementary cycles of a small digraph (iterative DFS; the
    wait-graph has one node per blocked actor/process, so tiny). Each
    cycle is reported once regardless of entry point."""
    cycles, seen = [], set()
    for start in graph:
        stack = [(start, iter(graph.get(start, ())))]
        path, onpath = [start], {start}
        while stack:
            node, it = stack[-1]
            advanced = False
            for nxt in it:
                if nxt in onpath:
                    i = path.index(nxt)
                    cyc = tuple(path[i:])
                    key = frozenset(cyc)
                    if key not in seen:
                        seen.add(key)
                        cycles.append(list(cyc))
                    continue
                if nxt in graph:
                    stack.append((nxt, iter(graph.get(nxt, ()))))
                    path.append(nxt)
                    onpath.add(nxt)
                    advanced = True
                    break
            if not advanced:
                stack.pop()
                onpath.discard(path.pop())
    return cycles


class NodeRecord:
    def __init__(self, node_id: bytes, address: Tuple[str, int], resources: Dict[str, float],
                 object_store_path: str, is_head: bool, labels: Dict[str, str]):
        self.node_id = node_id
        self.address = address
        self.resources = dict(resources)
        self.available = dict(resources)  # updated by resource reports
        self.object_store_path = object_store_path
        self.is_head = is_head
        self.labels = dict(labels)
        self.alive = True
        self.last_heartbeat = time.monotonic()
        self.client: Optional[RpcClient] = None
        # Latest per-scheduling-class lease backlog reported by heartbeat.
        self.backlog: List[dict] = []
        # Two-phase drain (DrainNode analog, node_manager.proto): the node
        # is still ALIVE — running work finishes, objects stay readable —
        # but the scheduler/PGs route around it until drain_deadline
        # (wall-clock; drain_deadline_mono is the GCS-local enforcement
        # clock), when it is killed for real.
        self.draining = False
        self.drain_reason = ""
        self.drain_deadline = 0.0          # unix seconds (advisory, wire)
        self.drain_deadline_mono = 0.0     # monotonic (enforcement)
        # Why the node died (kept in the view so workers deciding whether a
        # death consumes retry budget can classify it — death_cause()).
        self.death_reason = ""

    def view(self) -> dict:
        return {
            "node_id": self.node_id,
            "address": self.address,
            "resources": dict(self.resources),
            "available": dict(self.available),
            "object_store_path": self.object_store_path,
            "is_head": self.is_head,
            "labels": dict(self.labels),
            "alive": self.alive,
            "draining": self.draining,
            "drain_reason": self.drain_reason,
            "drain_deadline": self.drain_deadline,
            "death_reason": self.death_reason,
        }


class ActorRecord:
    def __init__(self, spec: ActorSpec):
        self.spec = spec
        self.state = PENDING_CREATION
        self.address: Optional[Tuple[str, int]] = None
        self.node_id: Optional[bytes] = None
        self.worker_id: Optional[bytes] = None
        self.restarts_used = 0
        self.death_reason = ""

    def view(self) -> dict:
        return {
            "actor_id": self.spec.actor_id,
            "name": self.spec.name,
            "class_name": self.spec.class_name,
            "state": self.state,
            "address": self.address,
            "node_id": self.node_id,
            "restarts_used": self.restarts_used,
            "max_restarts": self.spec.max_restarts,
            "death_reason": self.death_reason,
        }


class GcsServer:
    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 storage_path: Optional[str] = None):
        from ray_tpu.runtime.gcs.storage import (
            InMemoryStoreClient,
            SqliteStoreClient,
        )

        # StoreClient seam (store_client/: in-memory vs Redis-analog sqlite).
        self._store = (SqliteStoreClient(storage_path) if storage_path
                       else InMemoryStoreClient())
        self.server = RpcServer(host, port)
        self.server.register_all(self)
        self.server.on_disconnect = self._on_disconnect
        self._kv: Dict[bytes, bytes] = {}
        self._nodes: Dict[bytes, NodeRecord] = {}
        self._actors: Dict[bytes, ActorRecord] = {}
        self._named_actors: Dict[Tuple[str, str], bytes] = {}  # (namespace, name) -> actor_id
        self._subscribers: Dict[str, Set[ServerConnection]] = {}
        self._actor_locks: Dict[bytes, asyncio.Lock] = {}
        self._pg_manager = None  # installed in M4 (placement groups)
        self._health_task = None
        self._shutdown = asyncio.Event()
        # Job/task event tables (state API)
        self._job_counter = 0
        self._jobs: Dict[int, dict] = {}
        # Strong refs to fire-and-forget tasks: asyncio holds only weak
        # refs, so an unpinned background task (e.g. the owner-death
        # shutdown) can be garbage-collected mid-await and silently vanish.
        self._bg_tasks: Set[asyncio.Task] = set()
        # Resource-view change log (ray_syncer analog; see _bump_view).
        import collections

        self._view_version = 0
        self._view_log: "collections.deque" = collections.deque(maxlen=1024)
        # Epoch/instance id: version numbers are meaningless across GCS
        # restarts (a restored raylet's old-epoch version can be <= the new
        # epoch's current version and silently skip restore-seeded entries),
        # so every view reply carries this id and a mismatch forces a full
        # snapshot.
        import uuid

        self._view_epoch = uuid.uuid4().hex

    def _spawn_bg(self, coro) -> "asyncio.Task":
        task = asyncio.ensure_future(coro)
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)
        return task

    async def start(self):
        await self.server.start()
        from ray_tpu.runtime.gcs.placement_groups import PlacementGroupManager
        self._pg_manager = PlacementGroupManager(self)
        await self._restore()
        self._health_task = asyncio.ensure_future(self._health_check_loop())
        logger.info("GCS listening on %s:%d", self.server.host, self.server.port)
        return self

    @property
    def address(self):
        return self.server.address

    # ---- persistence (gcs FT: restart + reload, redis_store_client.h) ----

    def _persist_actor(self, rec: "ActorRecord"):
        import pickle

        try:
            self._store.put("actors", rec.spec.actor_id, pickle.dumps({
                "spec": rec.spec, "state": rec.state, "address": rec.address,
                "node_id": rec.node_id, "worker_id": rec.worker_id,
                "restarts_used": rec.restarts_used,
                "death_reason": rec.death_reason}))
        except Exception:
            logger.exception("actor persist failed")

    def _persist_node(self, rec: "NodeRecord"):
        import pickle

        try:
            self._store.put("nodes", rec.node_id, pickle.dumps({
                "node_id": rec.node_id, "address": rec.address,
                "resources": rec.resources, "available": rec.available,
                "object_store_path": rec.object_store_path,
                "is_head": rec.is_head, "labels": rec.labels,
                "alive": rec.alive, "draining": rec.draining,
                "drain_reason": rec.drain_reason,
                "drain_deadline": rec.drain_deadline}))
        except Exception:
            logger.exception("node persist failed")

    def persist_pg(self, rec):
        import pickle

        try:
            self._store.put("placement_groups", rec.pg_id, pickle.dumps({
                "pg_id": rec.pg_id, "bundles": rec.bundles,
                "strategy": rec.strategy, "name": rec.name,
                "state": rec.state, "locations": rec.locations}))
        except Exception:
            logger.exception("pg persist failed")

    async def _restore(self):
        """Reload tables after a GCS restart. Raylets and workers keep
        running while the GCS is down (only control-plane ops stall); their
        reconnecting clients re-register/resubscribe when we come back
        (NotifyGCSRestart analog, node_manager.proto:401)."""
        import pickle

        for key, value in self._store.load_all("kv"):
            self._kv[key] = value
        for key, blob in self._store.load_all("autoscaler"):
            if key == b"requested_resources":
                self._requested_resources = pickle.loads(blob)
        for _, blob in self._store.load_all("jobs"):
            job = pickle.loads(blob)
            self._jobs[job["job_id"]] = job
            self._job_counter = max(self._job_counter, job["job_id"])
        restored_nodes = 0
        for _, blob in self._store.load_all("nodes"):
            d = pickle.loads(blob)
            if not d["alive"]:
                continue
            rec = NodeRecord(d["node_id"], tuple(d["address"]), d["resources"],
                             d["object_store_path"], d["is_head"], d["labels"])
            rec.available = d["available"]
            if d.get("draining"):
                # Monotonic deadlines don't survive the restart: re-derive
                # remaining notice from the persisted wall-clock deadline.
                rec.draining = True
                rec.drain_reason = d.get("drain_reason", "")
                rec.drain_deadline = d.get("drain_deadline", 0.0)
                rec.drain_deadline_mono = (
                    time.monotonic()
                    + max(0.0, rec.drain_deadline - time.time()))
            self._nodes[d["node_id"]] = rec
            restored_nodes += 1
            # Seed the view log so delta-synced raylets learn restored
            # (possibly idle, never-bumping) nodes.
            self._bump_view(rec)
            # Reconnect to the raylet in the background; health checks reap
            # it if it's truly gone.
            asyncio.ensure_future(self._reconnect_node(rec))
        for _, blob in self._store.load_all("actors"):
            d = pickle.loads(blob)
            rec = ActorRecord(d["spec"])
            rec.state = d["state"]
            rec.address = tuple(d["address"]) if d["address"] else None
            rec.node_id = d["node_id"]
            rec.worker_id = d["worker_id"]
            rec.restarts_used = d["restarts_used"]
            rec.death_reason = d["death_reason"]
            self._actors[rec.spec.actor_id] = rec
            self._actor_locks[rec.spec.actor_id] = asyncio.Lock()
            if rec.spec.name and rec.state != DEAD:
                self._named_actors[(rec.spec.namespace, rec.spec.name)] = \
                    rec.spec.actor_id
        for _, blob in self._store.load_all("placement_groups"):
            d = pickle.loads(blob)
            self._pg_manager.restore_record(d)
        # Restored PENDING groups need the retry loop running again or
        # they would only re-place on the next unrelated create/remove.
        self._pg_manager.kick()
        if restored_nodes or self._actors or self._kv:
            logger.info("GCS restored: %d nodes, %d actors, %d kv keys",
                        restored_nodes, len(self._actors), len(self._kv))

    async def _reconnect_node(self, rec: "NodeRecord"):
        try:
            client = RpcClient(*rec.address)
            await client.connect(timeout=10)
            rec.client = client
            rec.last_heartbeat = time.monotonic()
        except Exception:
            await self._mark_node_dead(rec.node_id,
                                       "unreachable after GCS restart")

    # ---- node management -------------------------------------------------

    async def handle_register_node(self, conn, node_id, address, resources,
                                   object_store_path, is_head=False, labels=None):
        rec = NodeRecord(node_id, tuple(address), resources, object_store_path,
                         is_head, labels or {})
        client = RpcClient(*rec.address)
        await client.connect(timeout=10)
        rec.client = client
        self._nodes[node_id] = rec
        conn.meta["node_id"] = node_id
        self._persist_node(rec)
        self._bump_view(rec)
        await self.publish("node", {"event": "added", "node": rec.view()})
        logger.info("node %s registered at %s resources=%s",
                    node_id.hex()[:12], rec.address, resources)
        return {"ok": True, "nodes": [n.view() for n in self._nodes.values()]}

    # ---- resource-view sync (ray_syncer analog) --------------------------
    #
    # Reference: src/ray/common/ray_syncer/ — every raylet needs an
    # eventually-consistent view of cluster resources for spillback routing.
    # Instead of each raylet pulling the FULL node table every heartbeat
    # (O(N^2) bytes/sec cluster-wide), the GCS keeps a versioned change log
    # and piggybacks only the deltas since the raylet's known version on the
    # heartbeat reply; an idle cluster exchanges empty deltas.

    def _bump_view(self, rec: "NodeRecord"):
        self._view_version += 1
        self._view_log.append((self._view_version, rec.view()))

    def _view_deltas(self, known_version: int,
                     known_epoch: Optional[str] = None):
        if (known_epoch != self._view_epoch
                or known_version > self._view_version
                or (self._view_log
                    and known_version < self._view_log[0][0] - 1)):
            # Different GCS epoch (restart — raw version numbers don't
            # compare across epochs), behind the capped log, or AHEAD of us:
            # full snapshot either way — delta-matching would silently drop
            # changes.
            return {"version": self._view_version,
                    "epoch": self._view_epoch,
                    "full": [n.view() for n in self._nodes.values()]}
        latest: Dict[bytes, dict] = {}
        for ver, view in self._view_log:
            if ver > known_version:
                latest[view["node_id"]] = view
        return {"version": self._view_version,
                "epoch": self._view_epoch,
                "deltas": list(latest.values())}

    async def handle_node_heartbeat(self, conn, node_id, available=None,
                                    backlog=None,
                                    known_version: Optional[int] = None,
                                    known_epoch: Optional[str] = None):
        rec = self._nodes.get(node_id)
        if rec is None:
            return {"ok": False, "unknown": True}
        rec.last_heartbeat = time.monotonic()
        if backlog is not None:
            # Per-scheduling-class lease backlog (autoscaler demand feed,
            # gcs_autoscaler_state_manager.cc analog). Not part of the
            # versioned view — demand is advisory, not routing state.
            rec.backlog = backlog
        if available is not None and rec.available != available:
            rec.available = dict(available)
            self._bump_view(rec)
        reply = {"ok": True}
        if known_version is not None:
            reply["view"] = self._view_deltas(known_version, known_epoch)
        return reply

    async def handle_node_heartbeat2(self, conn, m: bytes):
        """Typed-schema heartbeat (runtime/wire.py HeartbeatMsg in,
        ViewDeltaMsg out): the cross-version-evolvable twin of
        node_heartbeat. New fields on either message are invisible to old
        peers (unknown field numbers skip on decode); removed ones decode
        to defaults — protobuf evolution rules without the compiler."""
        from ray_tpu.runtime import wire

        hb = wire.HeartbeatMsg.decode(m)
        reply = await self.handle_node_heartbeat(
            conn, hb.node_id, available=hb.available or None,
            backlog=hb.backlog,
            known_version=hb.known_version if hb.known_version >= 0 else None,
            known_epoch=hb.known_epoch or None)
        view = reply.pop("view", None)
        if view is not None:
            nodes_key = "full" if "full" in view else "deltas"
            msg = wire.ViewDeltaMsg(
                version=view["version"], epoch=view.get("epoch") or "",
                is_full=nodes_key == "full")
            encoded = [wire.NodeInfoMsg(
                node_id=n["node_id"], host=n["address"][0],
                port=int(n["address"][1]), resources=n["resources"],
                available=n["available"], labels=n["labels"],
                is_head=n["is_head"], alive=n["alive"],
                object_store_path=n["object_store_path"],
                draining=bool(n.get("draining")),
                drain_deadline=float(n.get("drain_deadline") or 0.0))
                for n in view[nodes_key]]
            if nodes_key == "full":
                msg.full = encoded
            else:
                msg.deltas = encoded
            reply["view"] = msg.encode()
        return reply

    async def handle_get_nodes(self, conn, only_alive=True):
        return [n.view() for n in self._nodes.values() if n.alive or not only_alive]

    async def handle_cluster_demand(self, conn):
        """Heartbeat-aggregated per-node lease backlog (autoscaler demand
        feed — GcsAutoscalerStateManager analog): one RPC instead of a
        node_stats fan-out to every raylet."""
        return [{"node_id": n.node_id, "backlog": n.backlog}
                for n in self._nodes.values() if n.alive and n.backlog]

    async def handle_request_resources(self, conn, bundles):
        """Explicit demand floor (autoscaler/sdk request_resources analog):
        the autoscaler scales to hold these bundles EVEN WITHOUT queued
        work. Each call REPLACES the previous request (the reference
        semantics); an empty list clears it. Persisted: the floor must
        survive a GCS restart or the pre-scaled nodes idle out right
        before the burst the operator scaled for."""
        import pickle

        self._requested_resources = [dict(b) for b in (bundles or [])]
        try:
            self._store.put("autoscaler", b"requested_resources",
                            pickle.dumps(self._requested_resources))
        except Exception:
            logger.exception("persisting requested_resources failed")
        return {"ok": True, "count": len(self._requested_resources)}

    async def handle_get_requested_resources(self, conn):
        return list(getattr(self, "_requested_resources", []))

    async def handle_drain_node(self, conn, node_id, reason: str = "drained",
                                deadline_s: Optional[float] = None):
        """Two-phase node retirement (DrainNode analog, node_manager.proto).

        With a positive `deadline_s` (advance notice — the spot-preemption
        shape) the node enters DRAINING: it stays alive, the scheduler and
        placement groups stop leasing onto it, its raylet migrates primary
        object copies to live peers, and drain-aware consumers (Train,
        RLHF) checkpoint and re-form proactively. At the deadline the
        health loop kills it for real with the preempted marker so
        whatever didn't make it falls back to the reactive paths without
        consuming retry budgets.

        `deadline_s` None/<=0 keeps the legacy immediate-kill semantics —
        this IS the 0-notice reactive path."""
        if deadline_s is None or deadline_s <= 0:
            rec = self._nodes.get(node_id)
            if rec is not None and rec.alive:
                # Even a 0-notice drain is an ANNOUNCED retirement: flag it
                # so _mark_node_dead stamps the preemption marker (typed
                # cause, retry-budget exemption) and records NODE_PREEMPTED.
                rec.draining = True
                if not rec.drain_reason:
                    rec.drain_reason = reason
            await self._mark_node_dead(node_id, reason)
            return {"ok": True, "draining": False}
        rec = self._nodes.get(node_id)
        if rec is None or not rec.alive:
            return {"ok": False, "unknown": True}
        if not rec.draining:
            rec.draining = True
            rec.drain_reason = reason
        # Repeated notices tighten (never extend) the window: the cloud's
        # second notice is always sooner than the first.
        new_mono = time.monotonic() + deadline_s
        if rec.drain_deadline_mono <= 0 or new_mono < rec.drain_deadline_mono:
            rec.drain_deadline_mono = new_mono
            rec.drain_deadline = time.time() + deadline_s
        self._persist_node(rec)
        self._bump_view(rec)
        logger.warning("node %s DRAINING (%s): deadline in %.1fs",
                       node_id.hex()[:12], reason, deadline_s)
        from ray_tpu.runtime import events as events_mod

        self._record_event(events_mod.make_event(
            events_mod.NODE_DRAINING,
            f"node {node_id.hex()[:12]} draining ({reason}): "
            f"deadline in {deadline_s:.1f}s",
            severity=events_mod.WARNING, source="gcs", node_id=node_id,
            slice_name=rec.labels.get("tpu-slice-name"),
            labels={"deadline_s": f"{deadline_s:.1f}", "reason": reason}))
        await self.publish("node", {"event": "draining", "node": rec.view(),
                                    "reason": reason,
                                    "deadline_s": deadline_s})
        # Tell the raylet so it stops granting leases and starts migrating
        # its primary object copies (best-effort: the view delta is the
        # backup signal).
        if rec.client is not None:
            self._spawn_bg(self._notify_drain(rec, reason, deadline_s))
        return {"ok": True, "draining": True,
                "deadline": rec.drain_deadline}

    async def _notify_drain(self, rec: "NodeRecord", reason: str,
                            deadline_s: float):
        try:
            await rec.client.call("drain_self", reason=reason,
                                  deadline_s=deadline_s, timeout=5)
        except Exception as e:
            logger.debug("drain_self notify to %s failed: %r",
                         rec.node_id.hex()[:12], e)

    # ---- object relocation (drain-time primary-copy migration) -----------
    #
    # While a node drains, its raylet pushes primary object copies to live
    # peers and reports the new homes here. Workers that later hit
    # ObjectLostError for an oid ask `locate_object` BEFORE falling back to
    # lineage reconstruction, so objects that had time to move survive the
    # preemption without re-execution.

    async def handle_report_object_locations(self, conn, node_id,
                                             oids) -> dict:
        table = getattr(self, "_object_relocations", None)
        if table is None:
            table = self._object_relocations = {}
        for oid in oids:
            table[bytes(oid)] = node_id
        return {"ok": True, "count": len(oids)}

    async def handle_locate_object(self, conn, oid: bytes) -> dict:
        table = getattr(self, "_object_relocations", None)
        holder = table.get(oid) if table else None
        if holder is None:
            return {"found": False}
        rec = self._nodes.get(holder)
        if rec is None or not rec.alive:
            return {"found": False}
        return {"found": True, "node_id": holder,
                "address": list(rec.address)}

    # ---- checkpoint shard registry (checkpoint/plane.py replication) -----
    #
    # A completed checkpoint shard that was broadcast to peer object stores
    # registers here: the shard row records where the durable file lives,
    # and each replica oid lands in the drain relocation table homed on a
    # live PEER of the reporting node (the broadcast placed a copy on every
    # node) — so when the writer's node drains and dies at its deadline,
    # `locate_object` already points somewhere that survives it.

    async def handle_register_checkpoint_shards(self, conn, path: str,
                                                name: str, shard: int,
                                                world: int, step=None,
                                                nbytes: int = 0,
                                                oids=(), node_id=None
                                                ) -> dict:
        shards = getattr(self, "_ckpt_shards", None)
        if shards is None:
            shards = self._ckpt_shards = {}
        shards[(path, name, int(shard), int(world))] = {
            "path": path, "name": name, "shard": int(shard),
            "world": int(world), "step": step, "nbytes": int(nbytes),
            "oids": [bytes(o) for o in oids],
            "node_id": node_id, "time": time.time()}
        table = getattr(self, "_object_relocations", None)
        if table is None:
            table = self._object_relocations = {}
        peer = None
        for nid, rec in self._nodes.items():
            if rec.alive and not rec.draining and nid != node_id:
                peer = nid
                break
        home = peer if peer is not None else node_id
        relocated = 0
        if home is not None:
            for oid in oids:
                table[bytes(oid)] = home
                relocated += 1
        return {"ok": True, "relocated": relocated,
                "home": home.hex() if isinstance(home, bytes) else home}

    async def handle_list_checkpoint_shards(self, conn,
                                            path: Optional[str] = None
                                            ) -> list:
        shards = getattr(self, "_ckpt_shards", None) or {}
        rows = [dict(v, oids=[o.hex() for o in v["oids"]],
                     node_id=(v["node_id"].hex()
                              if isinstance(v["node_id"], bytes)
                              else v["node_id"]))
                for v in shards.values()
                if path is None or v["path"] == path]
        rows.sort(key=lambda r: (r["path"], r["name"], r["shard"]))
        return rows

    # ---- cluster prefix store (llm/prefix_store.py) -----------------------
    #
    # digest -> spilled KV prefix pages + adoption metadata, modeled on the
    # checkpoint shard registry above, with one deliberate difference: the
    # page bytes are homed HERE (the GCS byte plane), not in a worker's
    # object store — worker-owned objects ride the owner-addressed
    # ownership protocol and are reaped when their owner dies, which is
    # the exact event a spilled prefix must survive. Traffic is raw-frame
    # RPC both directions (rpc.py call_raw): the handlers below never
    # pickle a page byte. Byte-capacity LRU so replicas can't flood the
    # head node's RAM.

    PREFIX_STORE_CAPACITY = 256 << 20

    def _prefix_table(self):
        tbl = getattr(self, "_prefix_entries", None)
        if tbl is None:
            from collections import OrderedDict

            tbl = self._prefix_entries = OrderedDict()
            self._prefix_bytes = 0
        return tbl

    @staticmethod
    def _prefix_row_msg(key: bytes, row: dict):
        from ray_tpu.runtime import wire

        return wire.PrefixEntryMsg(
            digest=key, lora_id=row["lora_id"],
            weights_version=row["weights_version"],
            block_size=row["block_size"], n_tokens=row["n_tokens"],
            token_ids=row["token_ids"], nbytes=len(row["payload"]),
            owner_replica=row["owner_replica"], node_id=row["node_id"],
            deployment=row["deployment"])

    async def handle_prefix_upsert(self, conn, m, payload):
        from ray_tpu.runtime.rpc import RawReply
        from ray_tpu.runtime import wire

        ent = wire.PrefixEntryMsg.decode(bytes(m))
        buf = bytes(payload)
        if not ent.digest or not buf or not ent.token_ids:
            return RawReply(wire.AckMsg(
                ok=False, error="empty prefix upsert").encode())
        tbl = self._prefix_table()
        key = bytes(ent.digest)
        old = tbl.pop(key, None)
        if old is not None:
            self._prefix_bytes -= len(old["payload"])
        tbl[key] = {
            "lora_id": ent.lora_id, "weights_version": ent.weights_version,
            "block_size": ent.block_size, "n_tokens": ent.n_tokens,
            "token_ids": list(ent.token_ids),
            "owner_replica": ent.owner_replica,
            "node_id": bytes(ent.node_id), "deployment": ent.deployment,
            "payload": buf, "time": time.time()}
        self._prefix_bytes += len(buf)
        while tbl and self._prefix_bytes > self.PREFIX_STORE_CAPACITY:
            _, victim = tbl.popitem(last=False)
            self._prefix_bytes -= len(victim["payload"])
        return RawReply(wire.AckMsg(ok=True,
                                    existed=old is not None).encode())

    async def handle_prefix_lookup(self, conn, m, payload):
        """Answer with the CONTIGUOUS run of entries held from digests[0]
        upward (the caller lists its missing chain longest-last); the
        reply payload is the matching spill buffers concatenated — frames
        are self-delimiting, so the adopter decodes them back apart."""
        from ray_tpu.runtime.rpc import RawReply
        from ray_tpu.runtime import wire

        q = wire.PrefixLookupMsg.decode(bytes(m))
        tbl = self._prefix_table()
        entries, bufs = [], []
        for d in (q.digests or ()):
            key = bytes(d)
            row = tbl.get(key)
            # weights_version <= 0 in the query means "any": the router's
            # metadata-only owner probe doesn't know the fleet's weights
            # version. Adopters always pass their exact version AND
            # re-verify it per entry client-side, so a relaxed probe can
            # never smuggle stale KV into an engine.
            if (row is None or row["lora_id"] != q.lora_id
                    or (q.weights_version > 0
                        and row["weights_version"] != q.weights_version)
                    or row["block_size"] != q.block_size):
                break
            tbl.move_to_end(key)
            if q.replica:
                # The adopter is about to hold these pages hot: it becomes
                # the live-owner hint the router's fallback routes to.
                row["owner_replica"] = q.replica
            entries.append(self._prefix_row_msg(key, row))
            if q.want_payload:
                bufs.append(row["payload"])
        reply = wire.PrefixLookupReplyMsg(found=bool(entries),
                                          entries=entries)
        return RawReply(reply.encode(), payload=b"".join(bufs))

    async def handle_prefix_purge(self, conn, m, payload):
        from ray_tpu.runtime.rpc import RawReply
        from ray_tpu.runtime import wire

        q = wire.PrefixPurgeMsg.decode(bytes(m))
        purged, cleared = self._purge_prefix_entries(
            owner_replica=q.owner_replica, node_id=bytes(q.node_id),
            deployment=q.deployment,
            digests=[bytes(d) for d in (q.digests or ())],
            below_weights_version=q.below_weights_version,
            clear_owner_only=q.clear_owner_only)
        return RawReply(wire.PrefixPurgeReplyMsg(
            ok=True, purged=purged, owners_cleared=cleared).encode())

    def _purge_prefix_entries(self, *, owner_replica: str = "",
                              node_id: bytes = b"", deployment: str = "",
                              digests=(), below_weights_version: int = 0,
                              clear_owner_only: bool = False):
        """Prune the prefix table (OR across the given selectors; no
        selector matches nothing). clear_owner_only blanks the live-owner
        hint but keeps the row adoptable — the replica-death path, where
        the pages (GCS-homed) are still valid but a routing hint naming a
        dead or re-registered replica would serve a stale owner hit."""
        tbl = getattr(self, "_prefix_entries", None)
        if not tbl:
            return 0, 0
        digest_set = set(digests)

        def match(key, row):
            if key in digest_set:
                return True
            if owner_replica and row["owner_replica"] == owner_replica:
                return True
            if node_id and row["node_id"] == node_id:
                return True
            if deployment and row["deployment"] == deployment:
                return True
            return bool(below_weights_version
                        and row["weights_version"] < below_weights_version)

        purged = cleared = 0
        for key in [k for k, r in tbl.items() if match(k, r)]:
            if clear_owner_only:
                tbl[key]["owner_replica"] = ""
                tbl[key]["node_id"] = b""
                cleared += 1
            else:
                row = tbl.pop(key)
                self._prefix_bytes -= len(row["payload"])
                purged += 1
        return purged, cleared

    async def _on_disconnect(self, conn: ServerConnection):
        for subs in self._subscribers.values():
            subs.discard(conn)
        node_id = conn.meta.get("node_id")
        if node_id is not None and node_id in self._nodes and self._nodes[node_id].alive:
            # A draining node's disconnect IS the announced preemption —
            # don't overwrite the cause with a generic "disconnected" (the
            # typed-cause plumbing downstream keys off the reason string).
            rec = self._nodes[node_id]
            if rec.draining:
                reason = (f"node preempted at end of drain "
                          f"({rec.drain_reason})")
            else:
                reason = "raylet disconnected"
            await self._mark_node_dead(node_id, reason)
        job_id = conn.meta.get("job_id")
        if job_id is not None and job_id in self._jobs:
            self._jobs[job_id]["alive"] = False
            self._persist_job(self._jobs[job_id])
        if conn.meta.get("owns_cluster") and not self._shutdown.is_set():
            self._spawn_bg(self._shutdown_if_owner_gone(job_id))

    async def _shutdown_if_owner_gone(self, job_id, grace_s: float = 10.0):
        """Tear the cluster down unless the owning driver reconnects and
        re-claims its job within the grace period (a transient socket drop
        of an auto_reconnect client must not kill the cluster — the driver
        heartbeats its job every couple of seconds, so a live driver always
        re-claims well inside the grace)."""
        await asyncio.sleep(grace_s)
        job = self._jobs.get(job_id)
        if job is not None and job.get("alive"):
            return
        if self._shutdown.is_set():
            return
        logger.warning("cluster-owning driver (job %s) disconnected; "
                       "shutting the cluster down", job_id)
        await self._do_shutdown()

    async def handle_claim_job(self, conn, job_id, owns_cluster: bool = False):
        """Re-attach a driver connection to its job (register_job docstring).
        Doubles as the driver's job heartbeat: called periodically so even
        an otherwise-idle driver re-claims after a transparent reconnect."""
        conn.meta["job_id"] = job_id
        if owns_cluster:
            conn.meta["owns_cluster"] = True
        job = self._jobs.get(job_id)
        if job is not None and not job.get("alive"):
            job["alive"] = True
            self._persist_job(job)
        return {"ok": True}

    async def _mark_node_dead(self, node_id: bytes, reason: str,
                              _slice_cascade: bool = True):
        rec = self._nodes.get(node_id)
        if rec is None or not rec.alive:
            return
        from ray_tpu.core.exceptions import NODE_PREEMPTED_MARKER

        # A drained node's death is a PLANNED retirement: stamp the typed
        # preemption marker into the reason (it survives the string-shaped
        # death plumbing to actors/tasks/objects, where `death_cause`
        # recovers it) and record the paired NODE_PREEMPTED event.
        was_draining = rec.draining
        if was_draining and NODE_PREEMPTED_MARKER not in reason:
            reason = f"{NODE_PREEMPTED_MARKER}: {reason}"
        rec.alive = False
        rec.draining = False
        rec.death_reason = reason
        self._persist_node(rec)
        self._bump_view(rec)
        logger.warning("node %s marked dead: %s", node_id.hex()[:12], reason)
        from ray_tpu.runtime import events as events_mod

        self._record_event(events_mod.make_event(
            events_mod.NODE_DEAD, f"node {node_id.hex()[:12]} dead: {reason}",
            severity=events_mod.ERROR, source="gcs", node_id=node_id,
            slice_name=rec.labels.get("tpu-slice-name")))
        if was_draining:
            self._record_event(events_mod.make_event(
                events_mod.NODE_PREEMPTED,
                f"node {node_id.hex()[:12]} preempted at drain deadline "
                f"({rec.drain_reason})",
                severity=events_mod.WARNING, source="gcs", node_id=node_id,
                slice_name=rec.labels.get("tpu-slice-name"),
                labels={"reason": rec.drain_reason}))
        # Relocation entries pointing AT the dead node are stale; entries
        # migrated OFF it (to live peers) stay valid. Checkpoint-shard
        # replicas are special: the broadcast placed a copy on EVERY node,
        # so their entries re-home to a surviving peer instead of dropping.
        table = getattr(self, "_object_relocations", None)
        if table:
            ckpt_oids = {bytes(o) for row in
                         (getattr(self, "_ckpt_shards", None) or {}).values()
                         for o in row["oids"]}
            new_home = next((nid for nid, r in self._nodes.items()
                             if r.alive and not r.draining
                             and nid != node_id), None)
            for oid in [o for o, holder in table.items()
                        if holder == node_id]:
                if oid in ckpt_oids and new_home is not None:
                    table[oid] = new_home
                else:
                    table.pop(oid, None)
        # A dead node never flushes metrics again — drop its
        # `metrics:<node>:<pid>` KV snapshots so the dashboard /metrics
        # aggregation stops counting ghost processes forever.
        stale_prefix = f"metrics:{node_id.hex()}:".encode()
        for key in [k for k in self._kv if k.startswith(stale_prefix)]:
            self._kv.pop(key, None)
            try:
                self._store.delete("kv", key)
            except Exception:
                pass
        # ... and its time-series rings: a dead node's history would only
        # pin ring budget that live reporters need.
        self._mh_purge_reporter(f"{node_id.hex()}:")
        # Same hygiene for the cluster prefix table, in the SAME tick: a
        # dead node's replicas never touch their spilled prefixes again,
        # so their live-owner hints must not survive to misroute a router
        # fallback (a later re-registered node could otherwise serve a
        # stale owner hit). The pages themselves are GCS-homed and stay
        # adoptable by any survivor — that is the point of the store.
        self._purge_prefix_entries(node_id=node_id, clear_owner_only=True)
        await self.publish("node", {"event": "removed", "node": rec.view(), "reason": reason})
        # Slice fate-sharing: a multi-host ICI slice is ONE failure domain.
        # Losing any host breaks the slice's collectives, so every sibling
        # is marked dead in the SAME tick (not after its own heartbeat
        # timeout) and actors on the slice die with the slice-lost marker.
        from ray_tpu.core.exceptions import TPU_SLICE_LOST_MARKER

        slice_name = rec.labels.get("tpu-slice-name")
        if slice_name and TPU_SLICE_LOST_MARKER not in reason:
            reason = (f"{TPU_SLICE_LOST_MARKER}: slice {slice_name!r} "
                      f"lost ({reason})")
        if _slice_cascade and slice_name:
            await self._fate_share_slice(slice_name, node_id, reason)
        # Fail/restart actors that lived on that node.
        for actor in list(self._actors.values()):
            if actor.node_id == node_id and actor.state in (ALIVE, PENDING_CREATION):
                asyncio.ensure_future(
                    self._handle_actor_failure(actor.spec.actor_id, f"node died: {reason}"))
        if self._pg_manager is not None:
            await self._pg_manager.on_node_dead(node_id)

    async def _fate_share_slice(self, slice_name: str, origin: bytes,
                                reason: str):
        """Mark every sibling host of a lost slice dead NOW, notify their
        raylets (they kill local workers and shut down — nothing may keep
        running against a broken ICI domain), and publish a typed
        `slice_lost` event. Also recorded in the KV so pollers (tests,
        dashboards) can observe slice loss without a subscription."""
        from ray_tpu.runtime import wire

        siblings = [n for n in self._nodes.values()
                    if n.alive and n.node_id != origin
                    and n.labels.get("tpu-slice-name") == slice_name]
        members = [origin] + [n.node_id for n in siblings]
        msg = wire.SliceLostMsg(slice_name=slice_name, nodes=members,
                                origin_node=origin, reason=reason)
        encoded = msg.encode()
        for sib in siblings:
            if sib.client is not None:
                self._spawn_bg(self._notify_slice_lost(sib, encoded))
            await self._mark_node_dead(sib.node_id, reason,
                                       _slice_cascade=False)
        logger.warning("slice %r lost (%d host(s) fate-shared): %s",
                       slice_name, len(siblings), reason)
        from ray_tpu.runtime import events as events_mod

        self._record_event(events_mod.make_event(
            events_mod.SLICE_LOST,
            f"slice {slice_name!r} lost ({len(members)} host(s) "
            f"fate-shared): {reason}",
            severity=events_mod.ERROR, source="gcs", node_id=origin,
            slice_name=slice_name,
            labels={"hosts": str(len(members)),
                    "members": ",".join(m.hex()[:12] for m in members)}))
        key = f"slice_lost:{slice_name}".encode()
        self._kv[key] = reason.encode()
        try:
            self._store.put("kv", key, self._kv[key])
        except Exception:
            logger.exception("slice_lost kv persist failed")
        await self.publish("slice_lost", {
            "slice_name": slice_name, "reason": reason, "m": encoded})

    async def _notify_slice_lost(self, rec: "NodeRecord", encoded: bytes):
        try:
            await rec.client.call("slice_lost", m=encoded, timeout=5)
        except Exception as e:
            # Best effort: the sibling may already be unreachable (it is
            # marked dead regardless).
            logger.debug("slice_lost notify to %s failed: %r",
                         rec.node_id.hex()[:12], e)

    async def _health_check_loop(self):
        # gcs_health_check_manager analog: periodic liveness by heartbeat age.
        from ray_tpu.config import cfg

        while not self._shutdown.is_set():
            await asyncio.sleep(1.0)
            now = time.monotonic()
            for rec in list(self._nodes.values()):
                if rec.alive and now - rec.last_heartbeat > 30.0:
                    await self._mark_node_dead(rec.node_id, "heartbeat timeout")
                elif (rec.alive and rec.draining
                        and rec.drain_deadline_mono > 0
                        and now >= rec.drain_deadline_mono):
                    # Drain window expired: the retirement happens NOW even
                    # if the cloud hasn't actually revoked the VM yet —
                    # deadline semantics must be deterministic for callers.
                    await self._mark_node_dead(
                        rec.node_id,
                        f"node preempted at end of drain "
                        f"({rec.drain_reason})")
            # Wait-graph detector rides the same loop at its own cadence.
            last = getattr(self, "_last_stall_tick", 0.0)
            if now - last >= cfg().stall_detector_interval_s:
                self._last_stall_tick = now
                try:
                    self._stall_detector_tick()
                except Exception:
                    logger.exception("stall detector tick failed")
            # So does the alert evaluator (rules over the history rings).
            last = getattr(self, "_last_alert_tick", 0.0)
            if now - last >= cfg().alert_eval_interval_s:
                self._last_alert_tick = now
                try:
                    self._alert_eval_tick()
                except Exception:
                    logger.exception("alert evaluator tick failed")

    # ---- KV (function/class table, runtime metadata) ---------------------

    async def handle_kv_put(self, conn, key: bytes, value: bytes, overwrite=True):
        if not overwrite and key in self._kv:
            return {"ok": False, "exists": True}
        self._kv[key] = value
        try:
            self._store.put("kv", key, value)
        except Exception:
            logger.exception("kv persist failed")
        return {"ok": True}

    async def handle_report_metrics2(self, conn, m: bytes):
        """Typed metrics flush (MetricsReportMsg): one schema'd frame per
        reporter per tick, filed under the same metrics:<node>:<pid> KV key
        the legacy kv_put path used, so every reader (dashboard /metrics,
        state.metrics_snapshot) is oblivious to the transport change.
        Skips the persistence write — metrics snapshots are ephemeral."""
        from ray_tpu.runtime import wire

        msg = wire.MetricsReportMsg.decode(m)
        self._kv[f"metrics:{msg.node}:{msg.pid}".encode()] = msg.payload
        try:
            self._ingest_metrics_history(msg.node, msg.pid, msg.payload)
        except Exception:
            # History is an overlay on the snapshot plane; a malformed
            # payload must not fail the flush the snapshot path accepted.
            logger.exception("metrics history ingest failed")
        return {"ok": True}

    async def handle_kv_get(self, conn, key: bytes):
        return {"value": self._kv.get(key)}

    async def handle_kv_del(self, conn, key: bytes):
        self._store.delete("kv", key)
        return {"ok": self._kv.pop(key, None) is not None}

    async def handle_kv_keys(self, conn, prefix: bytes = b""):
        return {"keys": [k for k in self._kv if k.startswith(prefix)]}

    # ---- metrics history plane -------------------------------------------
    #
    # Every MetricsReportMsg flush is additionally folded into crc32-sharded
    # fixed-budget time-series rings (the task-event `gcs_ring_shards`
    # pattern): counters/gauges store (ts, cumulative value) points per
    # (series, tag set, reporter), histograms store per-flush bucket DELTAS
    # so any window's distribution — and therefore any quantile — can be
    # reconstructed by summing deltas. The whole structure is byte-capped
    # (`metrics_history_max_bytes`), evicting oldest points first. Zero new
    # wire frames: the payload is the same JSON the snapshot plane already
    # ships; history only changes what the GCS *keeps*.

    _MH_POINT_COST = 32          # rough bytes per scalar (ts, value) point

    def _metrics_history_shards(self) -> list:
        shards = getattr(self, "_mh_shards", None)
        if shards is None:
            from ray_tpu.config import cfg

            n = max(1, cfg().gcs_ring_shards)
            per = max(4096, cfg().metrics_history_max_bytes // n)
            shards = self._mh_shards = [
                {"series": {}, "bytes": 0, "budget": per} for _ in range(n)]
            self._mh_prev_hist = {}   # reporter -> {series key: cumulative}
            self._mh_flushes = 0
            self._mh_evicted_points = 0
        return shards

    def _mh_shard_for(self, skey: str) -> dict:
        shards = self._metrics_history_shards()
        return shards[zlib.crc32(skey.encode()) % len(shards)]

    def _ingest_metrics_history(self, node: str, pid: int, payload: bytes,
                                now: float = None):
        from ray_tpu.config import cfg

        if not cfg().metrics_history_enabled:
            return
        snaps = json.loads(payload)
        if now is None:
            now = time.time()
        reporter = f"{node}:{pid}"
        self._metrics_history_shards()
        self._mh_flushes += 1
        prev_hist = self._mh_prev_hist.setdefault(reporter, {})
        touched = set()
        for snap in snaps:
            name, typ = snap.get("name"), snap.get("type")
            if not name:
                continue
            if typ == "histogram":
                boundaries = snap.get("boundaries") or []
                for tkey, h in (snap.get("histograms") or {}).items():
                    skey = f"{name}|{tkey}|{reporter}"
                    cur = (list(h.get("buckets") or []),
                           float(h.get("sum", 0.0)), int(h.get("count", 0)))
                    last = prev_hist.get(skey)
                    prev_hist[skey] = cur
                    if last is not None and cur[2] >= last[2] \
                            and len(cur[0]) == len(last[0]):
                        dcount = cur[2] - last[2]
                        if dcount == 0:
                            continue      # idle flush: store nothing
                        delta = ([max(0, c - p)
                                  for c, p in zip(cur[0], last[0])],
                                 max(0.0, cur[1] - last[1]), dcount)
                    else:
                        # First sight, or the reporter restarted (pid
                        # reuse): the whole cumulative state is the delta.
                        delta = cur
                        if delta[2] == 0:
                            continue
                    rec = self._mh_series(skey, name, tkey, reporter,
                                          "histogram", boundaries)
                    rec["points"].append(
                        (now, tuple(delta[0]), delta[1], delta[2]))
                    shard = self._mh_shard_for(skey)
                    shard["bytes"] += rec["psize"]
                    touched.add(id(shard))
            elif typ in ("counter", "gauge"):
                for tkey, v in (snap.get("values") or {}).items():
                    skey = f"{name}|{tkey}|{reporter}"
                    rec = self._mh_series(skey, name, tkey, reporter, typ)
                    pts = rec["points"]
                    # An idle counter repeats its cumulative value every
                    # flush; storing the repeats buys nothing (rate/delta
                    # fold consecutive differences). Gauges keep every
                    # sample — a flat gauge is data, "no samples" is not.
                    if typ == "counter" and pts and pts[-1][1] == v:
                        continue
                    pts.append((now, float(v)))
                    shard = self._mh_shard_for(skey)
                    shard["bytes"] += rec["psize"]
                    touched.add(id(shard))
        for shard in self._mh_shards:
            if id(shard) in touched and shard["bytes"] > shard["budget"]:
                self._mh_evict(shard)

    def _mh_series(self, skey: str, name: str, tkey: str, reporter: str,
                   kind: str, boundaries=None) -> dict:
        from collections import deque

        shard = self._mh_shard_for(skey)
        rec = shard["series"].get(skey)
        if rec is None:
            psize = (self._MH_POINT_COST if boundaries is None
                     else 48 + 8 * (len(boundaries) + 1))
            try:
                tagmap = dict(json.loads(tkey))
            except Exception:
                tagmap = {}
            rec = shard["series"][skey] = {
                "name": name, "tags": tagmap, "reporter": reporter,
                "kind": kind, "boundaries": list(boundaries or ()),
                "points": deque(), "psize": psize}
        return rec

    def _mh_evict(self, shard: dict):
        """Oldest-window eviction: while the shard is over budget, drop
        points from the head of whichever series currently holds the
        oldest one (batched so a large overshoot is not O(n) min-scans)."""
        series = shard["series"]
        while shard["bytes"] > shard["budget"] and series:
            rec = min(series.values(), key=lambda r: r["points"][0][0])
            pts = rec["points"]
            drop = max(8, len(pts) // 16)
            while drop and pts and shard["bytes"] > shard["budget"]:
                pts.popleft()
                shard["bytes"] -= rec["psize"]
                self._mh_evicted_points += 1
                drop -= 1
            if not pts:
                for k, r in list(series.items()):
                    if r is rec:
                        del series[k]
                        break

    def _mh_purge_reporter(self, who: str):
        """Drop every history series for one reporter — an exact
        `node:pid` (worker death) or a `node:` prefix (node death; the
        trailing colon keeps pid 123 from shadowing pid 1234)."""
        def match(reporter: str) -> bool:
            return (reporter == who
                    or (who.endswith(":") and reporter.startswith(who)))

        for shard in getattr(self, "_mh_shards", None) or ():
            stale = [k for k, r in shard["series"].items()
                     if match(r["reporter"])]
            for k in stale:
                rec = shard["series"].pop(k)
                shard["bytes"] -= rec["psize"] * len(rec["points"])
        prev = getattr(self, "_mh_prev_hist", None) or {}
        for reporter in [r for r in prev if match(r)]:
            del prev[reporter]

    def _mh_match(self, name: str, tags=None) -> list:
        """Every series record for `name` whose tag set contains `tags`."""
        out = []
        for shard in self._metrics_history_shards():
            for rec in shard["series"].values():
                if rec["name"] != name:
                    continue
                if tags and any(rec["tags"].get(k) != v
                                for k, v in tags.items()):
                    continue
                out.append(rec)
        return out

    @staticmethod
    def _mh_counter_delta(points, cutoff: float) -> float:
        """Sum of positive increments landing inside the window. The last
        pre-window point is the baseline, so an increment that *crossed*
        the window edge counts; resets (process restart) clamp to 0
        instead of going negative."""
        total, prev = 0.0, None
        for ts, v in points:
            if prev is not None and ts >= cutoff:
                total += max(0.0, v - prev)
            prev = v
        return total

    def _mh_window(self, name: str, tags=None, window_s: float = 60.0,
                   agg: str = None, now: float = None):
        """One windowed aggregate over every matching series, plus the
        per-node contribution split (alert attribution, link matrix).

        agg: counters `rate` (default) / `delta`; gauges `mean` (default)
        / `last`; histograms `pNN` (p99 default) / `mean` / `rate`
        (observations per second). Returns (value_or_None, by_node dict,
        extras dict)."""
        if now is None:
            now = time.time()
        cutoff = now - max(window_s, 1e-9)
        recs = self._mh_match(name, tags)
        if not recs:
            return None, {}, {"series": 0}
        kind = recs[0]["kind"]
        by_node: Dict[str, float] = {}

        def book(rec, amount):
            node = rec["reporter"].split(":", 1)[0]
            by_node[node] = by_node.get(node, 0.0) + amount

        if kind == "histogram":
            boundaries, buckets = [], []
            total_sum = total_count = 0.0
            for rec in recs:
                if not boundaries and rec["boundaries"]:
                    boundaries = rec["boundaries"]
                    buckets = [0.0] * (len(boundaries) + 1)
                contrib = 0.0
                for ts, db, dsum, dcount in rec["points"]:
                    if ts < cutoff:
                        continue
                    if len(db) == len(buckets):
                        for i, c in enumerate(db):
                            buckets[i] += c
                    total_sum += dsum
                    total_count += dcount
                    contrib += dcount
                book(rec, contrib)
            extras = {"series": len(recs), "count": total_count,
                      "sum": total_sum, "boundaries": boundaries,
                      "buckets": buckets}
            if total_count <= 0:
                return None, by_node, extras
            agg = agg or "p99"
            if agg == "mean":
                return total_sum / total_count, by_node, extras
            if agg in ("rate", "delta"):
                val = (total_count if agg == "delta"
                       else total_count / window_s)
                return val, by_node, extras
            if agg.startswith("p"):
                from ray_tpu.util.metrics import histogram_quantile

                q = float(agg[1:]) / 100.0
                return (histogram_quantile(boundaries, buckets, q),
                        by_node, extras)
            raise ValueError(f"unknown histogram agg {agg!r}")
        if kind == "counter":
            agg = agg or "rate"
            if agg not in ("rate", "delta"):
                raise ValueError(f"unknown counter agg {agg!r}")
            total = 0.0
            for rec in recs:
                d = self._mh_counter_delta(rec["points"], cutoff)
                book(rec, d)
                total += d
            value = total if agg == "delta" else total / window_s
            return value, by_node, {"series": len(recs)}
        # gauge
        agg = agg or "mean"
        if agg not in ("mean", "last"):
            raise ValueError(f"unknown gauge agg {agg!r}")
        vals = []
        for rec in recs:
            pts = [v for ts, v in rec["points"] if ts >= cutoff]
            if not pts and rec["points"]:
                # A quiet gauge still has a current value: fall back to
                # its most recent sample so `mean` reflects level, not
                # flush cadence.
                pts = [rec["points"][-1][1]]
            if pts:
                per = pts[-1] if agg == "last" else sum(pts) / len(pts)
                vals.append(per)
                book(rec, per)
        if not vals:
            return None, by_node, {"series": len(recs)}
        return sum(vals) / len(vals), by_node, {"series": len(recs)}

    async def handle_metrics_history(self, conn, name: str, tags=None,
                                     window_s: float = 60.0, agg=None,
                                     points_limit: int = 240):
        """Windowed query over the history rings (`state.metrics_history`
        / `scripts metrics` / dashboard sparklines). Returns the aggregate
        plus the raw per-series point tails for plotting."""
        value, by_node, extras = self._mh_window(
            name, tags=tags, window_s=window_s, agg=agg)
        series = []
        for rec in self._mh_match(name, tags):
            pts = list(rec["points"])[-max(1, points_limit):]
            if rec["kind"] == "histogram":
                # Per-flush mean: the plottable scalar a bucket-delta
                # point reduces to.
                plotted = [[ts, (dsum / dcount) if dcount else 0.0]
                           for ts, _db, dsum, dcount in pts]
            else:
                plotted = [[ts, v] for ts, v in pts]
            series.append({"name": rec["name"], "tags": rec["tags"],
                           "reporter": rec["reporter"], "kind": rec["kind"],
                           "points": plotted})
        return {"name": name, "window_s": window_s, "agg": agg,
                "value": value, "by_node": by_node, "series": series,
                **{k: v for k, v in extras.items()
                   if k in ("count", "sum")}}

    async def handle_metrics_history_stats(self, conn):
        """Ingest-side health of the history plane (budget pressure,
        eviction churn) — `handle_task_event_stats` symmetry."""
        shards = getattr(self, "_mh_shards", None) or []
        return {
            "shards": len(shards),
            "series": sum(len(s["series"]) for s in shards),
            "points": sum(len(r["points"]) for s in shards
                          for r in s["series"].values()),
            "bytes": sum(s["bytes"] for s in shards),
            "budget_bytes": sum(s["budget"] for s in shards),
            "evicted_points": getattr(self, "_mh_evicted_points", 0),
            "flushes_ingested": getattr(self, "_mh_flushes", 0),
        }

    async def handle_link_utilization(self, conn, window_s: float = 30.0):
        """Observed per-link bandwidth matrix, derived from the (op, algo)-
        tagged collective byte counters in the history rings and attributed
        to topology links: a slice-labeled node's traffic rides the ICI
        ring link toward its worker-id successor (rx from its predecessor),
        an unlabeled node's traffic is host/DCN egress. This is the feed
        for the ROADMAP-3 contention model — schedulers act on measured
        goodput per link, not instantaneous readings."""
        now = time.time()
        cutoff = now - max(window_s, 1e-9)
        # node hex -> (slice, worker index) from the live node table.
        slices: Dict[str, list] = {}
        place: Dict[str, tuple] = {}
        for nid, rec in self._nodes.items():
            if not rec.alive:
                continue
            sl = rec.labels.get("tpu-slice-name")
            if sl is None:
                continue
            try:
                w = int(rec.labels.get("tpu-worker-id", -1))
            except (TypeError, ValueError):
                w = -1
            if w >= 0:
                place[nid.hex()] = (sl, w)
                slices.setdefault(sl, []).append(w)
        for sl in slices:
            slices[sl] = sorted(set(slices[sl]))
        links: Dict[str, dict] = {}
        nodes: Dict[str, dict] = {}

        def link_rec(key, kind, slice_name=None):
            return links.setdefault(key, {
                "link": key, "kind": kind, "slice": slice_name,
                "tx_bytes_per_s": 0.0, "rx_bytes_per_s": 0.0, "by_op": {}})

        for direction, metric in (
                ("tx", "ray_tpu_collective_bytes_sent_total"),
                ("rx", "ray_tpu_collective_bytes_recv_total")):
            for rec in self._mh_match(metric):
                rate = self._mh_counter_delta(
                    rec["points"], cutoff) / window_s
                if rate <= 0:
                    continue
                node = rec["reporter"].split(":", 1)[0]
                nrec = nodes.setdefault(node, {"tx_bytes_per_s": 0.0,
                                               "rx_bytes_per_s": 0.0})
                nrec[f"{direction}_bytes_per_s"] += rate
                sl_w = place.get(node)
                if sl_w and len(slices.get(sl_w[0], ())) > 1:
                    sl, w = sl_w
                    ring = slices[sl]
                    pos = ring.index(w)
                    peer = (ring[(pos + 1) % len(ring)] if direction == "tx"
                            else ring[(pos - 1) % len(ring)])
                    lo, hi = (w, peer) if direction == "tx" else (peer, w)
                    key = f"ici:{sl}:{lo}->{hi}"
                    lrec = link_rec(key, "ici", sl)
                else:
                    key = f"host:{node[:12]}"
                    lrec = link_rec(key, "host")
                lrec[f"{direction}_bytes_per_s"] += rate
                op = "/".join(str(rec["tags"].get(k, "?"))
                              for k in ("op", "algo"))
                lrec["by_op"][op] = lrec["by_op"].get(op, 0.0) + rate
        return {"window_s": window_s,
                "links": sorted(links.values(), key=lambda l: l["link"]),
                "nodes": nodes}

    # ---- alert evaluator (runtime/alert_defs.py) -------------------------

    def _alert_eval_tick(self, now: float = None):
        """Walk the declarative alert table against the history rings.
        Signature-dedup mirrors the stall detector — an ongoing condition
        emits ALERT_FIRING once — but a signature LEAVING the active set
        additionally emits ALERT_RESOLVED (the stall detector retires
        silently; an alert's all-clear is itself a signal)."""
        from ray_tpu.runtime import alert_defs
        from ray_tpu.runtime import events as events_mod

        if now is None:
            now = time.time()
        sigs = getattr(self, "_alert_sigs", None)
        if sigs is None:
            sigs = self._alert_sigs = set()
        state = getattr(self, "_alert_state", None)
        if state is None:
            state = self._alert_state = {}
        active = set()
        for rule in alert_defs.ALERT_RULES:
            name = rule["name"]
            try:
                firing, value, by_node = self._alert_eval_rule(rule, now)
            except Exception:
                logger.exception("alert rule %s evaluation failed", name)
                continue
            st = state.setdefault(name, {"state": "ok", "since": None})
            st.update({"value": value, "severity": rule["severity"],
                       "series": rule["series"], "summary":
                       rule.get("summary", ""), "checked": now})
            if not firing:
                st["state"], st["since"] = "ok", None
                continue
            active.add(name)
            if st["state"] != "firing":
                st["since"] = now
            st["state"] = "firing"
            if name in sigs:
                continue
            sigs.add(name)
            top_node = max(by_node, key=by_node.get) if by_node else None
            labels = {"rule": name, "series": rule["series"],
                      "value": f"{value:.6g}" if value is not None else "",
                      "threshold": str(rule.get("threshold", "")),
                      "kind": rule.get("kind", "threshold")}
            if rule.get("tags"):
                labels.update({f"tag_{k}": str(v)
                               for k, v in rule["tags"].items()})
            self._record_event(events_mod.make_event(
                events_mod.ALERT_FIRING,
                f"alert {name}: {rule.get('summary', rule['series'])} "
                f"(value {value:.6g} vs threshold "
                f"{rule.get('threshold')})" if value is not None else
                f"alert {name}: {rule.get('summary', rule['series'])}",
                severity=rule["severity"], source="gcs",
                node_id=top_node, labels=labels))
            logger.warning("ALERT_FIRING %s value=%s", name, value)
        for name in sorted(sigs - active):
            st = state.get(name, {})
            self._record_event(events_mod.make_event(
                events_mod.ALERT_RESOLVED,
                f"alert {name} resolved",
                severity=events_mod.INFO, source="gcs",
                labels={"rule": name, "series": st.get("series", "")}))
            logger.info("ALERT_RESOLVED %s", name)
        sigs.intersection_update(active)

    def _alert_eval_rule(self, rule: dict, now: float):
        """Evaluate one rule. Returns (firing, observed value, by_node)."""
        tags = rule.get("tags")
        if rule.get("kind") == "burn_rate":
            short, s_node = self._mh_burn_rate(
                rule["series"], tags, rule["slo_ms"], rule["objective"],
                rule["short_window_s"], now)
            long, _ = self._mh_burn_rate(
                rule["series"], tags, rule["slo_ms"], rule["objective"],
                rule["long_window_s"], now)
            # Both windows must burn: the long window filters single-tick
            # blips, the short one makes recovery resolve promptly.
            if short is None or long is None:
                return False, short, s_node
            thr = rule["threshold"]
            return (short >= thr and long >= thr), short, s_node
        value, by_node, _ = self._mh_window(
            rule["series"], tags=tags, window_s=rule["window_s"],
            agg=rule.get("agg"), now=now)
        if value is None:
            return False, None, by_node
        op = rule.get("op", ">")
        thr = rule["threshold"]
        firing = {"<": value < thr, "<=": value <= thr,
                  ">": value > thr, ">=": value >= thr}[op]
        return firing, value, by_node

    def _mh_burn_rate(self, series: str, tags, slo_ms: float,
                      objective: float, window_s: float, now: float):
        """SLO burn rate over one window: the fraction of observations
        breaching the SLO, divided by the error budget (1 - objective).
        1.0 = burning exactly at budget; 10x = the window's traffic would
        exhaust a month's budget in ~3 days. None = no traffic (a silent
        service is not burning)."""
        _, by_node, extras = self._mh_window(
            series, tags=tags, window_s=window_s, agg="mean", now=now)
        total = extras.get("count") or 0.0
        if total <= 0:
            return None, by_node
        boundaries = extras.get("boundaries") or []
        buckets = extras.get("buckets") or []
        breaches = 0.0
        for i, c in enumerate(buckets):
            lower = boundaries[i - 1] if i > 0 else 0.0
            if i >= len(boundaries):
                lower = boundaries[-1] if boundaries else 0.0
            if lower >= slo_ms:
                breaches += c
        frac = breaches / total
        return frac / max(1e-9, 1.0 - objective), by_node

    async def handle_list_alerts(self, conn):
        """Current rule states (`state.summary()["alerts"]` data source).
        Rules never evaluated yet report state "ok" with no value."""
        from ray_tpu.runtime import alert_defs

        state = getattr(self, "_alert_state", None) or {}
        rules = []
        for rule in alert_defs.ALERT_RULES:
            st = state.get(rule["name"], {})
            rules.append({
                "name": rule["name"], "series": rule["series"],
                "kind": rule.get("kind", "threshold"),
                "severity": rule["severity"],
                "summary": rule.get("summary", ""),
                "state": st.get("state", "ok"),
                "since": st.get("since"), "value": st.get("value"),
                "threshold": rule.get("threshold"),
            })
        return {"rules": rules,
                "firing": sorted(getattr(self, "_alert_sigs", ()) or ())}

    # ---- pubsub ----------------------------------------------------------

    async def handle_subscribe(self, conn, channels: List[str]):
        for ch in channels:
            self._subscribers.setdefault(ch, set()).add(conn)
        return {"ok": True}

    async def handle_publish(self, conn, channel: str, message: Any):
        await self.publish(channel, message)
        return {"ok": True}

    async def publish(self, channel: str, message: Any):
        dead = []
        for conn in self._subscribers.get(channel, ()):  # long-poll-free push
            try:
                await conn.push("pubsub", {"channel": channel, "message": message})
            except Exception:
                dead.append(conn)
        for conn in dead:
            self._subscribers.get(channel, set()).discard(conn)

    # ---- job table --------------------------------------------------------

    async def handle_register_job(self, conn, metadata=None,
                                  owns_cluster: bool = False,
                                  token: Optional[str] = None):
        """`owns_cluster=True` marks this driver connection as the owner of
        an auto-started cluster: if the driver dies (connection drops
        without a graceful shutdown), the whole cluster is torn down —
        otherwise a SIGKILLed driver leaks GCS/raylet/worker processes
        forever (reference: ray.init()-owned clusters die with the driver).

        `token` makes registration idempotent under the client's
        auto_reconnect retry: a lost reply must not create a second job
        whose orphaned owner connection would later tear the cluster down
        under a live driver."""
        if token:
            for job in self._jobs.values():
                if job.get("token") == token:
                    conn.meta["job_id"] = job["job_id"]
                    if owns_cluster:
                        conn.meta["owns_cluster"] = True
                    job["alive"] = True
                    self._persist_job(job)
                    return {"job_id": job["job_id"]}
        self._job_counter += 1
        job_id = self._job_counter
        conn.meta["job_id"] = job_id
        if owns_cluster:
            conn.meta["owns_cluster"] = True
        self._jobs[job_id] = {"job_id": job_id, "start_time": time.time(),
                              "metadata": metadata or {}, "alive": True,
                              "token": token}
        self._persist_job(self._jobs[job_id])
        return {"job_id": job_id}

    def _persist_job(self, job: dict):
        import pickle

        try:
            self._store.put("jobs", str(job["job_id"]).encode(),
                            pickle.dumps(job))
        except Exception:
            logger.exception("job persist failed")

    async def handle_get_jobs(self, conn):
        return list(self._jobs.values())

    # ---- actor management (gcs_actor_manager.h:291 state machine) --------

    async def handle_create_actor(self, conn, spec: ActorSpec):
        if spec.name:
            key = (spec.namespace, spec.name)
            if key in self._named_actors:
                existing = self._actors[self._named_actors[key]]
                if existing.state != DEAD:
                    return {"ok": False, "error": f"actor name {spec.name!r} already taken"}
            self._named_actors[key] = spec.actor_id
        record = ActorRecord(spec)
        self._actors[spec.actor_id] = record
        self._actor_locks[spec.actor_id] = asyncio.Lock()
        self._persist_actor(record)
        try:
            await self._schedule_and_create(record)
        except Exception as e:
            record.state = DEAD
            record.death_reason = f"creation failed: {e!r}"
            self._persist_actor(record)
            return {"ok": False, "error": record.death_reason}
        return {"ok": True, "address": record.address, "actor_id": spec.actor_id}

    async def _schedule_and_create(self, record: ActorRecord):
        """GcsActorScheduler analog (gcs_actor_scheduler.h:111): lease a worker
        from a raylet, push the creation task to it, record the address."""
        spec = record.spec
        last_err = None
        import os as _os
        # Failed leases still need their req_ids canceled at the raylet (a
        # pending lease, or a grant that raced the timeout, must not leak
        # worker resources) — but a dead node's cancel must not stall the
        # scheduling loop, so cancels accumulate per node and fire batched
        # in the background at exit.
        pending_cancels: Dict[bytes, list] = {}

        def _flush_cancels():
            for nid, req_ids in pending_cancels.items():
                node_rec = self._nodes.get(nid)
                if node_rec is None or not node_rec.alive:
                    continue
                asyncio.ensure_future(
                    self._cancel_leases_at(node_rec, req_ids))

        try:
            for node in scheduling.rank_nodes_for_actor(self._nodes, spec,
                                                        self._pg_manager):
                req_id = _os.urandom(8)
                try:
                    lease = await node.client.call(
                        "lease_worker", resources=spec.resources,
                        for_actor=True,
                        placement_group_id=spec.placement_group_id,
                        bundle_index=spec.placement_group_bundle_index,
                        req_id=req_id, timeout=60)
                except Exception as e:
                    last_err = e
                    pending_cancels.setdefault(node.node_id, []).append(req_id)
                    continue
                if not lease.get("ok"):
                    last_err = RuntimeError(lease.get("error", "lease refused"))
                    continue
                worker_addr = tuple(lease["worker_address"])
                logger.debug("pushing create_actor %s to worker %s at %s",
                             spec.actor_id.hex()[:12],
                             lease["worker_id"].hex()[:12], worker_addr)
                worker_client = RpcClient(*worker_addr)
                try:
                    await worker_client.connect(timeout=15)
                    reply = await worker_client.call("create_actor", spec=spec,
                                                     timeout=300)
                    if not reply.get("ok"):
                        raise RuntimeError(
                            reply.get("error", "actor __init__ failed"))
                except Exception as e:
                    last_err = e
                    try:
                        await node.client.call(
                            "return_worker", lease_id=lease["lease_id"],
                            worker_dead=True)
                    except Exception:
                        pass
                    # __init__ raising is terminal, not a scheduling failure.
                    if isinstance(e, RuntimeError):
                        raise
                    continue
                finally:
                    await worker_client.close()
                record.state = ALIVE
                record.address = worker_addr
                record.node_id = node.node_id
                record.worker_id = lease["worker_id"]
                self._persist_actor(record)
                await self.publish("actor",
                                   {"event": "alive", "actor": record.view()})
                return
        finally:
            _flush_cancels()
        raise RuntimeError(f"no feasible node for actor {spec.class_name} "
                           f"(resources={spec.resources}): {last_err!r}")

    async def _cancel_leases_at(self, node: NodeRecord, req_ids: list):
        """Best-effort batched lease cancel at one raylet: a single
        cancel_lease_batch frame, per-id fallback against an old raylet; a
        node that died in the meantime is tolerated silently."""
        try:
            await node.client.call("cancel_lease_batch",
                                   req_ids=list(req_ids), timeout=10)
            return
        except Exception as e:
            from ray_tpu.runtime.rpc import ConnectionLost, RpcError
            if not (isinstance(e, RpcError)
                    and not isinstance(e, ConnectionLost)
                    and "no handler" in str(e)):
                return  # dead/unreachable node: nothing left to cancel
        results = await asyncio.gather(
            *(node.client.call("cancel_lease_request", req_id=rid, timeout=10)
              for rid in req_ids),
            return_exceptions=True)
        del results  # best-effort: failures mean the node is going away

    async def handle_get_actor(self, conn, actor_id: Optional[bytes] = None,
                               name: Optional[str] = None, namespace: str = "default"):
        if actor_id is None and name is not None:
            actor_id = self._named_actors.get((namespace, name))
        rec = self._actors.get(actor_id) if actor_id else None
        if rec is None:
            return {"found": False}
        return {"found": True, **rec.view()}

    async def handle_report_task_events(self, conn, events,
                                        wait_edges=None, reporter=None,
                                        node_id=None):
        """Batched task state transitions from workers/drivers
        (GcsTaskManager analog; task_event_buffer.h:224 export path) —
        legacy pickled envelope; new workers ship one typed
        TaskEventBatchMsg frame via report_task_events2 instead.

        `wait_edges` piggybacks the reporter's blocked-on edges on the
        same flush tick: None = no update, a list (possibly empty, to
        clear) replaces the reporter's previous edge set in the cluster
        wait-graph."""
        self._ingest_task_events(events, wait_edges, reporter, node_id, 0)
        return {"ok": True}

    async def handle_report_task_events2(self, conn, m: bytes):
        """Typed twin of handle_report_task_events: the whole flush tick
        arrives as one TaskEventBatchMsg frame (events + wait edges + the
        reporter's buffer-overflow drop count) instead of N dict-pickles."""
        from ray_tpu.runtime import wire

        msg = wire.TaskEventBatchMsg.decode(m)
        self._ingest_task_events(
            [e.to_event() for e in msg.events],
            msg.wait_edges if msg.has_wait_edges else None,
            msg.reporter or None, msg.node_id or None, msg.dropped)
        return {"ok": True}

    def _event_shards(self) -> list:
        """The task-event store, sharded by origin node: each shard is an
        independent bounded ring + latest-per-task index so ingest and
        index upkeep touch ONE shard — a 1k-node cluster's GCS tick stays
        O(shard), not O(cluster). Readers merge across shards."""
        shards = getattr(self, "_task_event_shards", None)
        if shards is None:
            from collections import deque

            from ray_tpu.config import cfg

            n = max(1, cfg().gcs_ring_shards)
            per = max(1, cfg().task_events_max // n)
            shards = self._task_event_shards = [
                {"ring": deque(maxlen=per), "latest": {}} for _ in range(n)]
            self._task_events_dropped_total = 0
        return shards

    def _shard_for(self, key) -> dict:
        shards = self._event_shards()
        if isinstance(key, str):
            key = key.encode()
        return shards[zlib.crc32(key or b"") % len(shards)]

    def _ingest_task_events(self, events, wait_edges, reporter, node_id,
                            dropped: int):
        if wait_edges is not None and reporter is not None:
            table = getattr(self, "_wait_edges", None)
            if table is None:
                table = self._wait_edges = {}
            if wait_edges:
                table[reporter] = {
                    "edges": list(wait_edges), "time": time.time(),
                    "node_id": (node_id.hex()
                                if isinstance(node_id, (bytes, bytearray))
                                else node_id)}
            else:
                table.pop(reporter, None)
        shard = self._shard_for(node_id or reporter or b"")
        if dropped:
            self._task_events_dropped_total = (
                getattr(self, "_task_events_dropped_total", 0) + dropped)
        ring, latest = shard["ring"], shard["latest"]
        for ev in events:
            ring.append(ev)
            cur = latest.get(ev["task_id"])
            if cur is None or ev["time"] >= cur["time"]:
                latest[ev["task_id"]] = ev
            # Bound the per-task index alongside its own ring only.
            if len(latest) > ring.maxlen:
                alive = {e["task_id"] for e in ring}
                stale = [k for k in latest if k not in alive]
                for k in stale:
                    del latest[k]
                shard["latest"] = latest

    async def handle_task_event_stats(self, conn):
        """Ingest-side health of the task-event plane: shard layout plus
        the cluster-wide count of events workers trimmed before flush
        (satellite of ray_tpu_task_events_dropped_total)."""
        shards = getattr(self, "_task_event_shards", None) or []
        return {
            "shards": len(shards),
            "events_stored": sum(len(s["ring"]) for s in shards),
            "tasks_indexed": sum(len(s["latest"]) for s in shards),
            "events_dropped_total":
                getattr(self, "_task_events_dropped_total", 0),
        }

    # ---- cluster wait-graph + stall/deadlock detector --------------------
    #
    # Workers piggyback blocked-on edges (task -> object -> owner task,
    # collective member -> group, channel reader -> channel) onto their
    # task-event flush; the GCS assembles them into one graph and a
    # periodic tick (a) finds actor-level cycles -> DEADLOCK_DETECTED and
    # (b) flags edges blocked past `stall_threshold_s` -> TASK_STALLED,
    # with collective edges grouped per group so the event names the
    # STRAGGLER ranks (members NOT blocked) rather than the whole gang —
    # the cross-link into the failure-domain plane.

    def _wait_edge_snapshot(self) -> list:
        """Live wait-graph edges, flattened with reporter attribution.
        Edges whose reporter stopped refreshing (crashed or unblocked
        worker) age out after `wait_edge_max_age_s`."""
        from ray_tpu.config import cfg

        table = getattr(self, "_wait_edges", None)
        if not table:
            return []
        now = time.time()
        max_age = cfg().wait_edge_max_age_s
        edges = []
        for reporter, rec in list(table.items()):
            if now - rec["time"] > max_age:
                table.pop(reporter, None)
                continue
            for e in rec["edges"]:
                e2 = dict(e)
                e2["reporter"] = reporter
                if rec.get("node_id") and "node_id" not in e2:
                    e2["node_id"] = rec["node_id"]
                edges.append(e2)
        return edges

    def _edge_node_slice(self, edge: dict):
        """(node hex, slice name) attribution for an edge's reporter."""
        node_hex = edge.get("node_id")
        if not node_hex:
            return None, None
        try:
            rec = self._nodes.get(bytes.fromhex(node_hex))
        except (ValueError, TypeError):
            rec = None
        return node_hex, (rec.labels.get("tpu-slice-name")
                          if rec else None)

    @staticmethod
    def _edge_stack(edge: dict) -> str:
        return "\n".join(edge.get("stack", ())[-2:])

    def _stall_detector_tick(self):
        from ray_tpu.config import cfg
        from ray_tpu.runtime import events as events_mod

        edges = self._wait_edge_snapshot()
        sigs = getattr(self, "_stall_sigs", None)
        if sigs is None:
            sigs = self._stall_sigs = set()
        active = set()
        counts = {"stalled_tasks": 0, "deadlocks": 0}
        now = time.time()
        threshold = cfg().stall_threshold_s

        # (a) Cycles: unit = actor when known, else the reporter process.
        graph: dict = {}
        cycle_edges: dict = {}
        for e in edges:
            if e.get("kind") != "object_get":
                continue
            src = e.get("waiter_actor") or e.get("reporter")
            dst = e.get("target_actor")
            if src and dst and src != dst:
                graph.setdefault(src, set()).add(dst)
                cycle_edges.setdefault((src, dst), e)
        deadlocks = _find_cycles(graph)
        self._active_deadlocks = deadlocks
        counts["deadlocks"] = len(deadlocks)
        for cyc in deadlocks:
            sig = ("deadlock", frozenset(cyc))
            active.add(sig)
            if sig in sigs:
                continue
            sigs.add(sig)
            hops, labels = [], {}
            for i, src in enumerate(cyc):
                dst = cyc[(i + 1) % len(cyc)]
                e = cycle_edges.get((src, dst), {})
                hops.append(
                    f"{src[:12]} waits on object {e.get('oid', '?')} "
                    f"({e.get('target_name', '?')}) held by {dst[:12]}")
                stack = self._edge_stack(e)
                if stack:
                    labels[f"stack_{src[:12]}"] = stack
            node_hex, slice_name = self._edge_node_slice(
                cycle_edges.get((cyc[0], cyc[1 % len(cyc)]), {}))
            labels["members"] = ",".join(c[:12] for c in cyc)
            self._record_event(events_mod.make_event(
                events_mod.DEADLOCK_DETECTED,
                f"wait-graph cycle across {len(cyc)} waiter(s): "
                + "; ".join(hops),
                severity=events_mod.ERROR, source="gcs",
                node_id=node_hex, slice_name=slice_name,
                actor_id=cyc[0], labels=labels))
            logger.error("deadlock detected: %s", "; ".join(hops))

        # (b) Long-stalled edges. Collective edges are grouped per group
        # so one event attributes the straggler ranks; everything else
        # stalls individually.
        coll: dict = {}
        for e in edges:
            if e.get("kind") == "collective_op":
                coll.setdefault(e.get("group"), []).append(e)
                continue
            age = now - e.get("since", now)
            if age < threshold:
                continue
            counts["stalled_tasks"] += 1
            sig = ("stall", e.get("reporter"), e.get("kind"),
                   e.get("oid") or e.get("channel"))
            active.add(sig)
            if sig in sigs:
                continue
            sigs.add(sig)
            node_hex, slice_name = self._edge_node_slice(e)
            who = (e.get("waiter_name") or e.get("waiter_task")
                   or e.get("reporter"))
            what = (f"object {e.get('oid')}" if e.get("oid")
                    else f"channel {e.get('channel')}")
            labels = {"kind": e.get("kind", ""), "reporter":
                      str(e.get("reporter", ""))}
            if e.get("oid"):
                labels["oid"] = e["oid"]
            if e.get("owner"):
                labels["owner"] = str(e["owner"])
            stack = self._edge_stack(e)
            if stack:
                labels["stack"] = stack
            self._record_event(events_mod.make_event(
                events_mod.TASK_STALLED,
                f"{who} blocked on {what} for {age:.0f}s "
                f"(threshold {threshold:g}s)",
                severity=events_mod.WARNING, source="gcs",
                node_id=node_hex, slice_name=slice_name,
                actor_id=e.get("waiter_actor"), labels=labels))
            logger.warning("stalled: %s blocked on %s for %.0fs",
                           who, what, age)
        for group, ges in coll.items():
            stalled = [e for e in ges
                       if now - e.get("since", now) >= threshold]
            if not stalled:
                continue
            counts["stalled_tasks"] += len(stalled)
            blocked_ranks = sorted({e.get("rank") for e in stalled
                                    if e.get("rank") is not None})
            world = next((e.get("world_size") for e in stalled
                          if e.get("world_size")), None)
            stragglers = (sorted(set(range(world)) - set(blocked_ranks))
                          if world else [])
            sig = ("stall_collective", group, tuple(blocked_ranks))
            active.add(sig)
            if sig in sigs:
                continue
            sigs.add(sig)
            age = max(now - e.get("since", now) for e in stalled)
            e0 = stalled[0]
            node_hex, slice_name = self._edge_node_slice(e0)
            msg = (f"collective group {group!r}: rank(s) "
                   f"{blocked_ranks} blocked in op "
                   f"#{e0.get('op_id', '?')} for {age:.0f}s")
            if stragglers:
                msg += (f"; straggler rank(s) {stragglers} have not "
                        f"entered the op")
            labels = {"group": str(group),
                      "blocked_ranks": ",".join(map(str, blocked_ranks)),
                      "straggler_ranks": ",".join(map(str, stragglers)),
                      "op_id": str(e0.get("op_id", ""))}
            stack = self._edge_stack(e0)
            if stack:
                labels["stack"] = stack
            self._record_event(events_mod.make_event(
                events_mod.TASK_STALLED, msg,
                severity=events_mod.WARNING, source="gcs",
                node_id=node_hex, slice_name=slice_name,
                labels=labels))
            logger.warning("%s", msg)
        # Retire resolved conditions so a recurrence re-alerts.
        sigs.intersection_update(active)
        self._stall_counts = counts

    async def handle_wait_graph(self, conn):
        """The assembled cluster wait-graph plus the detector's current
        verdict counts (`state.wait_graph()` / dashboard data source)."""
        return {
            "edges": self._wait_edge_snapshot(),
            "cycles": list(getattr(self, "_active_deadlocks", [])),
            **getattr(self, "_stall_counts",
                      {"stalled_tasks": 0, "deadlocks": 0}),
        }

    # ---- cluster event bus (runtime/events.py) ---------------------------

    def _record_event(self, ev: dict):
        """Append one typed cluster event to the bounded ring (see
        runtime/events.py for the record shape and the emitter list)."""
        from collections import deque

        from ray_tpu.config import cfg

        store = getattr(self, "_cluster_events", None)
        if store is None:
            store = self._cluster_events = deque(
                maxlen=cfg().cluster_events_max)
        store.append(ev)

    async def handle_report_events(self, conn, events):
        """Batched typed cluster events from any component (best-effort
        emitters: raylets, collective ranks, autoscaler, Train)."""
        for ev in events:
            if isinstance(ev, dict):
                self._record_event(dict(ev))
        return {"ok": True}

    async def handle_list_events(self, conn, event_type=None, severity=None,
                                 source=None, limit: int = 100):
        """Newest-first filtered view of the cluster event ring."""
        store = getattr(self, "_cluster_events", None) or ()
        out = []
        for ev in reversed(store):
            if event_type is not None and ev.get("type") != event_type:
                continue
            if severity is not None and ev.get("severity") != severity:
                continue
            if source is not None and ev.get("source") != source:
                continue
            out.append(ev)
            if len(out) >= limit:
                break
        return out

    async def handle_list_tasks(self, conn, state=None, name=None,
                                limit: int = 1000):
        shards = getattr(self, "_task_event_shards", None) or []
        out = []
        for ev in sorted((ev for s in shards for ev in s["latest"].values()),
                         key=lambda e: -e["time"]):
            if state is not None and ev["state"] != state:
                continue
            if name is not None and name not in ev["name"]:
                continue
            out.append(ev)
            if len(out) >= limit:
                break
        return out

    async def handle_get_task(self, conn, task_id_hex: str):
        """Per-task drill-through: the FULL transition history of one task
        (every recorded state event, oldest first), matched by hex id or
        unambiguous prefix — the dashboard task page's data source."""
        def _hex(tid):
            return tid.hex() if isinstance(tid, bytes) else str(tid)

        shards = getattr(self, "_task_event_shards", None) or []
        events = [ev for s in shards for ev in s["ring"]
                  if _hex(ev["task_id"]).startswith(task_id_hex)]
        ids = {_hex(ev["task_id"]) for ev in events}
        if len(ids) > 1:
            return {"error": f"ambiguous task id prefix {task_id_hex!r} "
                             f"({len(ids)} matches)"}
        return {"found": bool(events),
                "events": sorted(events, key=lambda e: e["time"])}

    async def handle_task_timeline(self, conn, limit: int = 2000):
        """Full state-transition log (not just latest-per-task): the
        dashboard timeline pairs RUNNING->FINISHED/FAILED per task into
        per-worker execution bars (GcsTaskManager export / `ray timeline`
        analog)."""
        shards = getattr(self, "_task_event_shards", None) or []
        events = sorted((ev for s in shards for ev in s["ring"]),
                        key=lambda e: e["time"])[-limit:]
        return events

    async def handle_list_actors(self, conn):
        return [r.view() for r in self._actors.values()]

    async def handle_kill_actor(self, conn, actor_id: bytes, no_restart=True):
        rec = self._actors.get(actor_id)
        if rec is None:
            return {"ok": False}
        if no_restart:
            rec.spec.max_restarts = 0
        node = self._nodes.get(rec.node_id) if rec.node_id else None
        if node is not None and node.alive and rec.worker_id is not None:
            try:
                await node.client.call("kill_worker", worker_id=rec.worker_id)
            except Exception:
                pass
        return {"ok": True}

    async def handle_report_worker_death(self, conn, node_id, worker_id, actor_id=None,
                                         reason="", pid=None):
        """Raylet tells us a worker process exited (node_manager death path).
        Republished on the 'worker_death' channel so object owners can prune
        dead borrowers (reference_count.h borrower-failure handling).

        When the raylet names the dead worker's os pid, the reporter's
        `metrics:<node>:<pid>` snapshot and its history rings are purged
        here — the per-worker flavor of the dead-node metrics purge (a pid
        that exited while its node stayed alive would otherwise count
        toward /metrics aggregation forever)."""
        if actor_id is not None:
            await self._handle_actor_failure(actor_id, reason or "worker died")
        if pid is not None:
            node_hex = (node_id.hex() if isinstance(node_id, bytes)
                        else str(node_id))
            key = f"metrics:{node_hex}:{pid}".encode()
            self._kv.pop(key, None)
            try:
                self._store.delete("kv", key)
            except Exception:
                pass
            self._mh_purge_reporter(f"{node_hex}:{pid}")
        await self.publish("worker_death", {
            "worker_id": worker_id.hex() if isinstance(worker_id, bytes)
            else worker_id, "reason": reason})
        return {"ok": True}

    async def _handle_actor_failure(self, actor_id: bytes, reason: str):
        rec = self._actors.get(actor_id)
        if rec is None or rec.state == DEAD:
            return
        lock = self._actor_locks.setdefault(actor_id, asyncio.Lock())
        async with lock:
            if rec.state == DEAD:
                return
            # Infinite-retry-on-preemption: a death caused by an ANNOUNCED
            # node retirement does not consume the restart budget (the
            # reference framework's drained-node semantics) — only actors
            # that are restartable at all (max_restarts > 0) qualify.
            from ray_tpu.core.exceptions import death_cause, CAUSE_PREEMPTION

            preempted = (death_cause(reason) == CAUSE_PREEMPTION
                         and rec.spec.max_restarts > 0)
            if preempted or rec.restarts_used < rec.spec.max_restarts:
                if not preempted:
                    rec.restarts_used += 1
                rec.state = RESTARTING
                rec.address = None
                await self.publish("actor", {"event": "restarting", "actor": rec.view()})
                try:
                    # Only an ANNOUNCED retirement has replacement capacity
                    # in flight worth waiting for; a plain crash keeps the
                    # old fail-fast semantics (an actor whose resource no
                    # longer exists anywhere must die, not stall).
                    if preempted:
                        await self._restart_with_capacity_wait(rec)
                    else:
                        await self._schedule_and_create(rec)
                except Exception as e:
                    rec.state = DEAD
                    rec.death_reason = f"restart failed: {e!r}"
                    self._persist_actor(rec)
                    await self.publish("actor", {"event": "dead", "actor": rec.view()})
            else:
                rec.state = DEAD
                rec.death_reason = reason
                self._persist_actor(rec)
                await self.publish("actor", {"event": "dead", "actor": rec.view()})

    async def _restart_with_capacity_wait(self, rec: "ActorRecord"):
        """Restart a PREEMPTED actor, waiting out a transient capacity gap.

        A restart triggered by an announced node retirement routinely
        RACES the capacity that replaces the node (the autoscaler
        launches at preemption notice time, but registration takes
        seconds) — failing the actor permanently on the first 'no
        feasible node' would make every graceful drain a coin flip.
        Only the feasibility error retries; anything else (e.g.
        __init__ raising) is terminal as before."""
        from ray_tpu.config import cfg

        deadline = time.monotonic() + cfg().actor_restart_capacity_wait_s
        while True:
            try:
                await self._schedule_and_create(rec)
                return
            except RuntimeError as e:
                if (not str(e).startswith("no feasible node")
                        or time.monotonic() >= deadline):
                    raise
                logger.info(
                    "actor %s restart waiting for capacity (%s)",
                    rec.spec.actor_id.hex()[:12], e)
                await asyncio.sleep(1.0)

    # ---- placement groups (delegated, see gcs/placement_groups.py) -------

    async def handle_create_placement_group(self, conn, **kw):
        return await self._pg_manager.create(**kw)

    async def handle_remove_placement_group(self, conn, **kw):
        return await self._pg_manager.remove(**kw)

    async def handle_get_placement_group(self, conn, **kw):
        return await self._pg_manager.get(**kw)

    async def handle_list_placement_groups(self, conn):
        return await self._pg_manager.list()

    # ---- shutdown ---------------------------------------------------------

    async def handle_shutdown_cluster(self, conn):
        self._spawn_bg(self._do_shutdown())
        return {"ok": True}

    async def _do_shutdown(self):
        logger.info("cluster shutdown: notifying %d nodes", len(self._nodes))
        await asyncio.sleep(0.05)  # let the reply flush
        for rec in self._nodes.values():
            if rec.alive and rec.client is not None:
                try:
                    await rec.client.call("shutdown_node", timeout=5)
                except Exception as e:
                    logger.warning("shutdown_node to %s failed: %r",
                                   rec.node_id.hex()[:12], e)
        # Whoever outlives a raylet removes its arena (node.stop_raylet). At
        # the cluster's end that is this process, for every node it ever
        # heard of: one that died unasked (SIGKILL, the OOM killer) left its
        # file in /dev/shm, one that was just asked unlinks its own and finds
        # it gone, and another host's path is no file here.
        for rec in self._nodes.values():
            try:
                os.unlink(rec.object_store_path)
            except OSError:
                pass
        logger.info("cluster shutdown: nodes notified; stopping GCS")
        self._shutdown.set()

    async def wait_for_shutdown(self):
        await self._shutdown.wait()
        if self._health_task:
            self._health_task.cancel()
        await self.server.close()
