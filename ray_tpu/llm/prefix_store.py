"""Two-tier cluster-wide KV prefix store.

The BlockManager's prefix cache (llm/engine.py) is a per-replica LRU: when
allocation pressure recycles a parked `reusable` block, its KV is gone, and
when the replica dies the whole shared working set dies with it — every
survivor re-prefills the same system prompt from scratch. This module adds
the two tiers that make cold prefix pages outlive both events:

  * Tier 1 — HostPrefixTier: a byte-capacity LRU of evicted prefix blocks
    in host RAM. BlockManager's eviction path (the `reusable.popitem` in
    `_take_free_block`) hands the victim block here before dropping it;
    admission (`LLMEngine._admit`) promotes matching blocks straight back
    into fresh device pages instead of re-prefilling them.

  * Tier 2 — ClusterPrefixStore: host-tier victims are demoted over the
    zero-pickle raw-frame RPC wire (rpc.py call_raw) into a GCS-resident
    prefix table modeled on the checkpoint shard-relocation registry. The
    pages are homed in the GCS byte plane ON PURPOSE: objects a replica
    `put()`s ride the owner-addressed ownership protocol (core/worker.py)
    and are reaped by delete-on-zero when their owner dies — exactly the
    event this store must survive. ANY replica can adopt a spilled prefix;
    the working set survives replica death, drain-based scale-down, and
    serving-fleet restarts.

Addressing: cluster entries are keyed by `prefix_digest_chain` under a
FIXED salt (`CLUSTER_PREFIX_SALT`) seeded with the adapter name, because
cluster addresses must be comparable across processes — the opposite of
the engine's deliberately per-process salt. The anti-forgery property the
random salt bought moves to adoption time: every entry carries its full
root-anchored token prefix, and an adopter scatters pages only after
verifying those tokens byte-for-byte against its own prompt (plus a
weights_version equality check, so KV spilled under old weights is never
decoded against new ones).

Wire format of a spilled payload (one bytes buffer, identical frame layout
to the disagg/migration socket stream so the codec is shared muscle):

    [u64 body len][u8 kind=2][JSON meta: kv dtype/shape]
    [u64][u8 kind=1][97B _AMETA][raw k-page bytes]   x ceil(bytes/1MiB)
    [u64][u8 kind=1][97B _AMETA][raw v-page bytes]   x ceil(bytes/1MiB)

Frames are self-delimiting, so a lookup reply carrying N blocks is simply
N buffers concatenated. Decoding is whole-or-nothing: a truncated buffer
raises TruncatedSpillError and the adopter registers NOTHING (the same
ack-after-adoption discipline as session migration).

This module is on graftlint's hot-pickle frozen path set: no pickle,
either direction, ever — counter-proven by tests/test_prefix_store.py.
"""

from __future__ import annotations

import json
import logging
import threading
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu.collective.cpu_group import (
    _AMETA, _HDR, _K_ARRAY, _chunks, _frame_views)
from ray_tpu.core import serialization as _ser

logger = logging.getLogger(__name__)

# JSON control frame kind — shared with llm/disagg.py's handoff wire.
_K_JSON = 2
_CHUNK_BYTES = 1 << 20

# Fixed cross-process salt for cluster prefix addresses (the engine's
# per-process _PREFIX_CACHE_SALT deliberately prevents cross-replica digest
# comparison; the cluster table requires it). blake2b keyed hashing caps
# keys at 64 bytes.
CLUSTER_PREFIX_SALT = b"ray-tpu/cluster-prefix-store/v1"


def cluster_chain(tokens: Sequence[int], block_size: int,
                  lora_id: str = "") -> List[bytes]:
    """Cluster-comparable digest chain for `tokens`, seeded by adapter name
    (LoRA changes wk/wv, so KV content differs per adapter and entries are
    keyed per `lora_id`)."""
    from ray_tpu.llm.engine import prefix_digest_chain

    return prefix_digest_chain(tokens, block_size, salt=CLUSTER_PREFIX_SALT,
                               seed=(lora_id or "").encode())


class TruncatedSpillError(RuntimeError):
    """A spilled payload buffer ended mid-frame: discard it whole."""


# --------------------------------------------------------------- page codec


def encode_pages(meta: dict, *pages) -> bytes:
    """Serialize (meta, *pages) into one raw-frame buffer (format above):
    the page arrays of one cache spec (K and V; a latent pool's one), all of
    one dtype and wire shape. dtype, shape and the count ride in the JSON
    frame; array frames carry raw bytes."""
    pages = [np.ascontiguousarray(p) for p in pages]
    meta = dict(meta)
    meta["kv_dtype"] = str(pages[0].dtype)
    meta["kv_shape"] = list(pages[0].shape)
    meta["kv_arrays"] = len(pages)
    body = json.dumps(meta).encode()
    parts: List[bytes] = [_HDR.pack(len(body), _K_JSON), body]
    for arr in pages:
        flat = arr.reshape(-1).view(np.uint8)
        for off, n in _chunks(0, flat.size, _CHUNK_BYTES):
            for view in _frame_views(flat[off:off + n], flat.shape, off):
                parts.append(bytes(view))
    return b"".join(parts)


class _BufReader:
    """Sequential frame reader over a spilled-payload buffer. Mirrors
    disagg._recv_frame's semantics — including the deserialize_fast counter
    bumps the zero-pickle counter-proof keys on — with truncation raising
    instead of blocking."""

    __slots__ = ("view", "pos")

    def __init__(self, buf):
        self.view = memoryview(buf).cast("B")
        self.pos = 0

    def eof(self) -> bool:
        return self.pos >= self.view.nbytes

    def _take(self, n: int) -> memoryview:
        if self.pos + n > self.view.nbytes:
            raise TruncatedSpillError(
                f"spill buffer truncated at byte {self.pos} "
                f"(need {n} more of {self.view.nbytes})")
        out = self.view[self.pos:self.pos + n]
        self.pos += n
        return out

    def read_frame(self) -> Tuple[str, Any]:
        length, kind = _HDR.unpack(self._take(_HDR.size))
        if kind == _K_JSON:
            return "json", json.loads(bytes(self._take(length)).decode())
        if kind != _K_ARRAY:
            raise TruncatedSpillError(
                f"spill protocol error: unknown frame kind {kind}")
        fields = _AMETA.unpack(self._take(_AMETA.size))
        dtype = np.dtype(fields[0].rstrip(b"\x00").decode())
        ndim = fields[1]
        shape = tuple(fields[2:2 + ndim])
        offset, nelems = fields[10], fields[11]
        out = np.empty(shape, dtype)
        flat = out.reshape(-1)
        total, got = flat.size, 0
        while True:
            if nelems:
                chunk = self._take(length - _AMETA.size)
                memoryview(flat[offset:offset + nelems]).cast("B")[:] = chunk
                got += nelems
            _ser.counters["deserialize_fast"] += 1
            if got >= total:
                return "array", flat
            length, kind = _HDR.unpack(self._take(_HDR.size))
            if kind != _K_ARRAY:
                raise TruncatedSpillError(
                    "spill protocol error: truncated array stream")
            fields = _AMETA.unpack(self._take(_AMETA.size))
            offset, nelems = fields[10], fields[11]


def decode_pages(reader) -> tuple:
    """Decode one (meta, *pages) off a _BufReader (or a buffer): (meta, k,
    v) for a (K, V) cache."""
    r = reader if isinstance(reader, _BufReader) else _BufReader(reader)
    kind, meta = r.read_frame()
    if kind != "json":
        raise TruncatedSpillError("spill buffer missing its meta frame")
    frames = [r.read_frame() for _ in range(int(meta.pop("kv_arrays", 2)))]
    if any(kind != "array" for kind, _ in frames):
        raise TruncatedSpillError("spill buffer missing a page array")
    dtype = np.dtype(meta.pop("kv_dtype"))
    shape = tuple(meta.pop("kv_shape"))
    return (meta,) + tuple(flat.view(dtype).reshape(shape)
                           for _, flat in frames)


# The keys of an entry that hold its page arrays, where the entry does not
# name them under "arrays": a (K, V) cache's, the wire's first shape.
DEFAULT_ARRAYS = ("k", "v")


def entry_arrays(entry: dict) -> Tuple[str, ...]:
    return tuple(entry.get("arrays") or DEFAULT_ARRAYS)


def decode_all(buf) -> List[tuple]:
    """Decode every concatenated (meta, *pages) in `buf` — the shape
    of a multi-block lookup reply. Whole-or-nothing: any truncation raises
    and the caller adopts none of it."""
    r = _BufReader(buf)
    out = []
    while not r.eof():
        out.append(decode_pages(r))
    return out


# ------------------------------------------------------------------- tier 1


# How long get() waits for pages that are on their way before it answers
# with a miss: they land within milliseconds, so this only bounds a wedge.
_LAND_TIMEOUT_S = 60.0


class _Spill:
    """A host-tier entry whose pages are still on their way from the
    device: HostPrefixTier.reserve made room for it, `land` brings the
    arrays. `fate` is what landing does with them, and is settled under the
    tier's lock by whatever happens to the entry meanwhile: KEEP (it still
    sits in the tier), DEMOTE (a watermark victim with a demotion hook:
    publish, do not keep) or DROP (a victim with no hook, replaced, or
    cleared: discard)."""

    __slots__ = ("entry", "landed", "fate")
    KEEP, DEMOTE, DROP = "keep", "demote", "drop"

    def __init__(self, entry: dict):
        self.entry = entry
        self.landed = False
        self.fate = self.KEEP


class HostPrefixTier:
    """Byte-capacity LRU of evicted prefix blocks in host RAM.

    Entries are per-block: {digest, tokens (root-anchored, through this
    block), arrays + one page per cache array, lora_slot, lora_name,
    weights_version, nbytes}. Crossing the high watermark demotes LRU
    victims through `on_demote` (wired to ClusterPrefixStore.publish) down
    to the low watermark — promotion back to the device happens in
    LLMEngine._admit via get().

    Two ways in. `put` takes an entry with its pages. `reserve` + `land`
    take a BURST of evictions whose pages are still on the device (the
    engine's batched spill): reserve runs put's arithmetic for every entry
    of the burst in eviction order, on the caller's thread, so LRU order,
    bytes and victims are exactly what one put a page would have left; the
    arrays land later from another thread. An entry the burst itself
    pushes out again, with no demotion hook to publish it, is never read
    off the device at all: no get() can fall between its put and its
    demotion."""

    def __init__(self, capacity_bytes: int, *,
                 high_watermark: float = 1.0, low_watermark: float = 0.8,
                 on_demote: Optional[Callable[[dict], None]] = None):
        self.capacity_bytes = int(capacity_bytes)
        self.high = float(high_watermark)
        self.low = float(low_watermark)
        self.on_demote = on_demote
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.spills = 0
        self.demotions = 0
        self._entries: "OrderedDict[bytes, dict]" = OrderedDict()
        # digest -> the _Spill of an entry of _entries that has not landed.
        self._inflight: Dict[bytes, _Spill] = {}
        self._unlanded = 0      # reserved and handed out, land() still due
        self._lock = threading.Lock()
        self._landed = threading.Condition(self._lock)

    def __len__(self) -> int:
        return len(self._entries)

    def _insert(self, digest: bytes, entry: dict,
                spill: Optional[_Spill] = None) -> List[dict]:
        """Under the lock: `entry` becomes the most recently used one, and
        if that crossed the high watermark the LRU end is demoted down to
        the low one. Returns the victims, each counted in `demotions`; one
        still in flight has its fate settled here and is left out (landing
        publishes or discards it)."""
        old = self._entries.pop(digest, None)
        if old is not None:
            self.bytes -= int(old["nbytes"])
            self._settle(old, _Spill.DROP)
        self._entries[digest] = entry
        self.bytes += int(entry["nbytes"])
        if spill is not None:
            self._inflight[digest] = spill
        victims: List[dict] = []
        if self.bytes > self.high * self.capacity_bytes:
            floor = self.low * self.capacity_bytes
            fate = (_Spill.DEMOTE if self.on_demote is not None
                    else _Spill.DROP)
            while self._entries and self.bytes > floor:
                _, victim = self._entries.popitem(last=False)
                self.bytes -= int(victim["nbytes"])
                self.demotions += 1
                if not self._settle(victim, fate):
                    victims.append(victim)
        return victims

    def _settle(self, entry: dict, fate: str) -> bool:
        """Under the lock: `entry` left _entries. If it is in flight, its
        landing now has `fate`; returns whether it was."""
        spill = self._inflight.get(entry["digest"])
        if spill is None or spill.entry is not entry:
            return False
        del self._inflight[entry["digest"]]
        spill.fate = fate
        return True

    def _publish(self, victims: Sequence[dict]) -> None:
        for victim in victims:
            if self.on_demote is not None:
                try:
                    self.on_demote(victim)
                except Exception:
                    logger.exception("prefix demotion to cluster store failed")

    def put(self, digest: bytes, entry: dict) -> None:
        entry = dict(entry)
        entry["digest"] = digest
        if int(entry["nbytes"]) > self.capacity_bytes:
            return  # one block larger than the whole tier: never fits
        with self._lock:
            demoted = self._insert(digest, entry)
            self.spills += 1
        self._metric("host")
        self._publish(demoted)
        self._gauge()

    def reserve(self, burst: Sequence[Tuple[bytes, dict]]
                ) -> List[Optional[_Spill]]:
        """put() for a burst of evictions, [(digest, entry without its
        arrays)] in eviction order, before their pages are read. Returns a
        _Spill for every entry whose pages are wanted (the caller reads
        them and calls land) and None for every one that need not be read:
        larger than the tier, or pushed out again by the burst's later
        entries with no demotion hook to publish it. Victims that hold
        their arrays are published here, as put publishes them."""
        spills: List[Optional[_Spill]] = []
        demoted: List[dict] = []
        with self._lock:
            for digest, entry in burst:
                entry = dict(entry)
                entry["digest"] = digest
                if int(entry["nbytes"]) > self.capacity_bytes:
                    spills.append(None)
                    continue
                spills.append(_Spill(entry))
                demoted.extend(self._insert(digest, entry, spills[-1]))
            spills = [None if s is None or s.fate == _Spill.DROP else s
                      for s in spills]
            wanted = sum(s is not None for s in spills)
            self.spills += wanted
            self._unlanded += wanted
        if wanted:
            self._metric("host", wanted)
        self._publish(demoted)
        self._gauge()
        return spills

    def land(self, spill: _Spill, fields: Optional[dict]) -> None:
        """The pages of a reserved entry have reached the host: `fields` is
        {"arrays": names, name: page, ...}, or None when reading them
        failed (the entry is withdrawn: a future cache miss)."""
        entry = spill.entry
        with self._lock:
            fate = spill.fate
            if fate == _Spill.KEEP:
                del self._inflight[entry["digest"]]
                if fields is None:
                    del self._entries[entry["digest"]]
                    self.bytes -= int(entry["nbytes"])
            if fields is not None:
                entry.update(fields)
            spill.landed = True
            self._unlanded -= 1
            self._landed.notify_all()
        if fate == _Spill.DEMOTE and fields is not None:
            self._publish([entry])
        self._gauge()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait until every reserved entry has landed."""
        with self._landed:
            return self._landed.wait_for(lambda: self._unlanded <= 0,
                                         timeout)

    def get(self, digest: bytes) -> Optional[dict]:
        with self._landed:
            e = self._entries.get(digest)
            spill = self._inflight.get(digest)
            if e is not None and spill is not None:
                # Its pages are on their way: the answer is the entry, once
                # they land (unless the read fails, or a clear() meanwhile).
                self._landed.wait_for(lambda: spill.landed, _LAND_TIMEOUT_S)
                if not spill.landed or self._entries.get(digest) is not e:
                    e = None
            if e is None:
                self.misses += 1
                return None
            self._entries.move_to_end(digest)
            self.hits += 1
            return e

    def clear(self) -> int:
        """Drop everything (weight hot-swap: cached KV is stale)."""
        with self._lock:
            n = len(self._entries)
            self._entries.clear()
            self.bytes = 0
            # Pages still on their way were read under the old weights too.
            for spill in self._inflight.values():
                spill.fate = _Spill.DROP
            self._inflight.clear()
        self._gauge()
        return n

    def hottest(self, limit: int) -> List[dict]:
        """Most-recently-touched entries first (drain-time push set)."""
        self.drain()
        with self._lock:
            return [self._entries[k]
                    for k in list(reversed(self._entries))[:limit]]

    def stats(self) -> Dict[str, int]:
        return {"entries": len(self._entries), "bytes": self.bytes,
                "hits": self.hits, "misses": self.misses,
                "spills": self.spills, "demotions": self.demotions,
                "inflight": self._unlanded}

    @staticmethod
    def _metric(tier: str, n: int = 1):
        try:
            from ray_tpu.runtime import metric_defs

            metric_defs.LLM_PREFIX_SPILLS.inc(n, tags={"tier": tier})
        except Exception:
            pass

    def _gauge(self):
        try:
            from ray_tpu.runtime import metric_defs

            metric_defs.LLM_PREFIX_STORE_BYTES.set(self.bytes)
        except Exception:
            pass


# ------------------------------------------------------------------- tier 2


class ClusterPrefixStore:
    """Client for the GCS prefix table (gcs/server.py handle_prefix_*).

    All traffic rides `call_raw` — schema'd wire.Prefix*Msg headers plus
    raw-frame page payloads, zero pickle in either direction. Every method
    is best-effort: a missing worker, an old GCS ("no handler"), or a
    timeout degrades to a cache miss, never an engine error. `transport`
    is injectable for tests (a direct bridge onto a GcsServer instance)."""

    def __init__(self, block_size: int, *, replica: str = "",
                 deployment: str = "", timeout_s: float = 5.0,
                 transport: Optional[Callable] = None):
        self.block_size = int(block_size)
        self.replica = replica
        self.deployment = deployment
        self.timeout_s = float(timeout_s)
        self._transport = transport
        self.published = 0
        self.adopted_blocks = 0
        self.stale_rejected = 0
        self.errors = 0

    # -- transport ---------------------------------------------------------

    def _call(self, method: str, m: bytes, payload=b"",
              wait: bool = True) -> Optional[Tuple[bytes, Any]]:
        """One raw-frame RPC to the GCS. wait=False fires and forgets (the
        demotion path must never stall the engine's scheduling tick on a
        head-node round trip)."""
        if self._transport is not None:
            return self._transport(method, m, payload)
        try:
            from ray_tpu.core.worker import global_worker

            core = global_worker()
        except Exception:
            return None
        try:
            coro = core.gcs.call_raw(method, m=m, payload=payload,
                                     timeout=self.timeout_s)
            if not wait:
                core.io.spawn(coro)
                return None
            return core.io.run(coro, timeout=self.timeout_s + 2)
        except Exception:
            self.errors += 1
            return None

    def available(self) -> bool:
        if self._transport is not None:
            return True
        try:
            from ray_tpu.core.worker import global_worker

            return global_worker() is not None
        except Exception:
            return False

    def _node_id(self) -> bytes:
        try:
            from ray_tpu.core.worker import global_worker

            return bytes(global_worker().node_id)
        except Exception:
            return b""

    # -- operations ----------------------------------------------------------

    def publish(self, entry: dict, *, wait: bool = False) -> bool:
        """Demote one host-tier victim into the cluster table. The payload
        buffer is complete before the RPC leaves — an upsert either lands
        whole or not at all (partial spills cannot exist server-side)."""
        from ray_tpu.runtime import wire

        tokens = list(entry["tokens"])
        lora_id = entry.get("lora_name") or ""
        if not tokens or len(tokens) % self.block_size:
            return False
        digest = cluster_chain(tokens, self.block_size, lora_id)[-1]
        names = entry_arrays(entry)
        payload = encode_pages({"arrays": list(names)},
                               *(entry[n] for n in names))
        m = wire.PrefixEntryMsg(
            digest=digest, lora_id=lora_id,
            weights_version=int(entry.get("weights_version", 0)),
            block_size=self.block_size, n_tokens=len(tokens),
            token_ids=[int(t) for t in tokens], nbytes=len(payload),
            owner_replica=self.replica, node_id=self._node_id(),
            deployment=self.deployment).encode()
        import time

        from ray_tpu.util import tracing

        t0 = time.time()
        out = self._call("prefix_upsert", m, payload, wait=wait)
        tracing.record_span("llm:prefix_spill", "llm", t0, time.time(),
                            tokens=len(tokens), bytes=len(payload),
                            replica=self.replica)
        self.published += 1
        try:
            from ray_tpu.runtime import events, metric_defs

            metric_defs.LLM_PREFIX_SPILLS.inc(tags={"tier": "store"})
            events.emit(events.LLM_PREFIX_SPILLED,
                        f"prefix spilled to cluster store "
                        f"({len(tokens)} tokens, lora={lora_id or 'base'})",
                        source="llm-prefix-store",
                        labels={"replica": self.replica,
                                "deployment": self.deployment,
                                "tokens": str(len(tokens))})
        except Exception:
            pass
        if not wait:
            return True
        if out is None:
            return False
        ack = wire.AckMsg.decode(out[0])
        return bool(ack.ok)

    def lookup_pages(self, digests: Sequence[bytes], *, lora_id: str = "",
                     weights_version: int = 0) -> List[dict]:
        """Fetch the contiguous run of spilled blocks starting at
        digests[0]. Returns [] on miss/any failure; on success, a list of
        {tokens, k, v} dicts (callers still verify tokens against their own
        prompt before scattering — the adoption-side anti-forgery check)."""
        from ray_tpu.runtime import wire

        if not digests:
            return []
        import time

        from ray_tpu.util import tracing

        m = wire.PrefixLookupMsg(
            digests=[bytes(d) for d in digests], lora_id=lora_id or "",
            weights_version=int(weights_version),
            block_size=self.block_size, want_payload=True,
            replica=self.replica).encode()
        t0 = time.time()
        out = self._call("prefix_lookup", m)
        tracing.record_span("llm:prefix_fetch", "llm", t0, time.time(),
                            digests=len(digests), hit=out is not None,
                            replica=self.replica)
        if out is None:
            return []
        m_reply, payload = out
        reply = wire.PrefixLookupReplyMsg.decode(bytes(m_reply))
        if not reply.found or not reply.entries:
            return []
        try:
            triples = decode_all(payload)
        except TruncatedSpillError:
            # Whole-or-nothing: a torn reply adopts NOTHING.
            self.errors += 1
            return []
        if len(triples) != len(reply.entries):
            self.errors += 1
            return []
        results = []
        for ent, (meta, *pages) in zip(reply.entries, triples):
            if ent.weights_version != int(weights_version):
                self.stale_rejected += 1
                self._stale_metric()
                break
            names = entry_arrays(meta)
            results.append({"tokens": list(ent.token_ids), "arrays": names,
                            **dict(zip(names, pages)),
                            "lora_id": ent.lora_id,
                            "weights_version": ent.weights_version})
        if results:
            self.adopted_blocks += len(results)
            try:
                from ray_tpu.runtime import events, metric_defs

                metric_defs.LLM_PREFIX_ADOPTIONS.inc(
                    len(results), tags={"tier": "store"})
                events.emit(events.LLM_PREFIX_ADOPTED,
                            f"adopted {len(results)} spilled prefix "
                            f"block(s) from the cluster store",
                            source="llm-prefix-store",
                            labels={"replica": self.replica,
                                    "deployment": self.deployment,
                                    "blocks": str(len(results))})
            except Exception:
                pass
        return results

    def lookup_owner(self, digests: Sequence[bytes], *, lora_id: str = "",
                     weights_version: int = 0) -> Optional[dict]:
        """Metadata-only probe (router fallback): does the cluster hold this
        prefix, and which live replica touched it last?"""
        from ray_tpu.runtime import wire

        if not digests:
            return None
        m = wire.PrefixLookupMsg(
            digests=[bytes(d) for d in digests], lora_id=lora_id or "",
            weights_version=int(weights_version),
            block_size=self.block_size, want_payload=False).encode()
        out = self._call("prefix_lookup", m)
        if out is None:
            return None
        reply = wire.PrefixLookupReplyMsg.decode(bytes(out[0]))
        if not reply.found or not reply.entries:
            return None
        ent = reply.entries[-1]
        return {"owner_replica": ent.owner_replica,
                "n_blocks": len(reply.entries), "n_tokens": ent.n_tokens}

    def purge(self, *, owner_replica: str = "", node_id: bytes = b"",
              deployment: str = "", digests: Sequence[bytes] = (),
              below_weights_version: int = 0,
              clear_owner_only: bool = False, wait: bool = False) -> int:
        """Prune the table. `clear_owner_only` blanks live-owner hints
        (replica eject/death: the pages — GCS-homed — stay adoptable, but
        no stale owner hit may route to a dead or re-registered replica);
        otherwise matching entries are dropped outright (deployment
        deletion, stale-weights GC). Returns rows touched, or -1 when fired
        without waiting."""
        from ray_tpu.runtime import wire

        m = wire.PrefixPurgeMsg(
            owner_replica=owner_replica, node_id=bytes(node_id),
            deployment=deployment, digests=[bytes(d) for d in digests],
            below_weights_version=int(below_weights_version),
            clear_owner_only=bool(clear_owner_only)).encode()
        out = self._call("prefix_purge", m, wait=wait)
        if not wait or out is None:
            return -1
        reply = wire.PrefixPurgeReplyMsg.decode(bytes(out[0]))
        return int(reply.purged + reply.owners_cleared)

    def _stale_metric(self):
        try:
            from ray_tpu.runtime import metric_defs

            metric_defs.LLM_PREFIX_STALE_REJECTED.inc()
        except Exception:
            pass

    def stats(self) -> Dict[str, int]:
        return {"published": self.published,
                "adopted_blocks": self.adopted_blocks,
                "stale_rejected": self.stale_rejected,
                "errors": self.errors}
