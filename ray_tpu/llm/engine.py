"""LLM engine: continuous batching over the paged-KV model runner.

Reference analog: the vLLM engine the reference wraps (SURVEY §3.5 hot loop:
"engine continuous-batching step loop (vLLM-internal in reference; Pallas
paged-attention engine in the TPU build)"). Components:

  * BlockManager — host-side page allocator for the KV pool (free list,
    per-sequence block tables, OOM preemption by recompute), by LAYER GROUP
    where the model's block has more than one (model_runner.py, "Layer
    groups"): a window group's pages are freed behind the window.
  * LLMEngine — add_request / step / generate / stream. step() admits,
    then runs ONE mixed tick: decode rows, draft-verify rows and prefill
    slices share a token-major launch (`_mixed_tick`), with one step of
    lookahead ("One step of lookahead" below). It emits a
    RequestOutput PER SAMPLED TOKEN, so callers can stream tokens before
    requests finish (the ReportGeneratorItemReturns path vLLM uses,
    core_worker.proto:462, maps to our streaming actors).

Scheduling: admission reserves pages for the whole prompt + 1 token, so
prefill never stalls mid-prompt; decode preemption (pages exhausted) evicts
the newest sequence and re-admits it later by recomputing prompt+generated
tokens (already-emitted tokens are preserved — vLLM's recompute preemption).
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
import os
import statistics
import threading
import time
import uuid
from collections import Counter, deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ray_tpu.llm.model_runner import (wire_concat, wire_page_count,
                                      wire_pages)
from ray_tpu.llm.sampling import SamplingParams, sample
from ray_tpu.ops.paged_attention import pages_in_runs
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

# Per-process key for the prefix-cache digest chain: unpredictable to
# clients, so cache addresses can't be forged across tenants.
_PREFIX_CACHE_SALT = os.urandom(16)
# Per-request prefill counters kept in `_Request.timing` and written into the
# `llm:prefill` span: prompt tokens found in the prefix cache at admission,
# ticks that gave the request a chunk, ticks it waited admitted without one,
# token-expert picks its prompt's rows made (0 unless the model routes).
PREFILL_SPAN_ARGS = ("cached_tokens", "slices", "starved_ticks",
                     "routed_rows")
# Eviction spills: the page counts the one gather program is compiled for (a
# burst is padded up to one; one beyond the largest goes in several), the
# bytes of a staging result up to which a larger size is used (32 MB reached
# the host in 6 ms on a v5e, 128 MB in 141 ms: PERF.md, PR 30), and the
# staging results that may be on their way to the host at once.
_SPILL_SIZES = (8, 32, 128)
_SPILL_STAGE_BYTES = 32 << 20
_SPILL_MAX_INFLIGHT = 4
# The time account ("The time account" in LLMEngine). A record is LONG where
# its period (`since_prev_ms + admit_ms + dur_ms`; without `since_prev_ms`
# after an idle engine) exceeds STALL_FACTOR x the median of the last
# STALL_WINDOW periods by STALL_FLOOR_MS or more, once STALL_MIN_HISTORY
# periods are known. From the ledger (PERF.md, PR 37): a closed cell's period
# is 17-39 ms, a tick with two prefill rows 1.3 x the median, a pause of the
# machine ~110 ms, a full collector pass 120-160 ms.
STALL_WINDOW = 128
STALL_MIN_HISTORY = 16
STALL_FACTOR = 2.0
STALL_FLOOR_MS = 20.0
_TIME_PHASES = ("admit", "compose", "dispatch", "wait", "commit", "loop",
                "idle")


def long_tick_excess(period_ms: float, history) -> Optional[float]:
    """A long period's excess over the median of `history` (the periods
    before it, ms), or None where the period is not long."""
    if len(history) < STALL_MIN_HISTORY or period_ms < STALL_FLOOR_MS:
        return None
    median = statistics.median_high(history)
    if period_ms - STALL_FACTOR * median < STALL_FLOOR_MS:
        return None
    return period_ms - median


def stall_cause(record: Dict, after: Optional[Dict], excess_ms: float,
                median_wait_ms: float, idle: bool = False) -> str:
    """The one cause a long record's excess is put down to, first match
    (docs/observability.md, "Why was this tick slow"). `after` is the record
    that closed next (the rule for a late wait needs its `wait_ms`), `idle`
    says the record's `since_prev_ms` was an idle engine's and no loop."""
    if record.get("recompile"):
        return "recompile"
    half = excess_ms / 2.0
    if record.get("gc_ms", 0.0) >= half:
        return "gc"
    if record.get("wait_ms", 0.0) - median_wait_ms >= half:
        # The host blocked on the device's results for most of the excess.
        # With a step queued behind the awaited one, the next wait tells
        # who was late: the device ran both steps back to back, so where
        # the next result was there already, this one had been too.
        if after is None or not record.get("lookahead"):
            return "wait"
        if after.get("wait_ms", 0.0) < median_wait_ms / 4.0:
            return "host_late"
        return "device"
    phases = {p: record.get(p + "_ms", 0.0)
              for p in ("admit", "compose", "dispatch", "commit")}
    phases["loop"] = 0.0 if idle else record.get("since_prev_ms", 0.0)
    phase = max(phases, key=phases.get)
    # On the CPU for half the excess: the thread worked. Else it held none:
    # a lock, the GIL or the scheduler kept it.
    kind = ("host_work" if record.get("cpu_ms", 0.0) >= half
            else "host_blocked")
    if (phase in ("admit", "compose", "loop")
            and record.get("spill_ms", 0.0) >= phases[phase] / 2.0):
        phase = "spill"
    return f"{kind}:{phase}"


def prefix_digest_chain(prompt: Sequence[int], block_size: int, *,
                        salt: Optional[bytes] = None,
                        seed: bytes = b"") -> List[bytes]:
    """Keyed rolling digest per FULL block of `prompt` (position-and-content
    chain, so identical blocks at different depths never collide).

    blake2b keyed with a random salt, NOT builtin hash(): hash(int)==int is
    attacker-predictable, letting a multi-tenant client construct a block
    whose chain value collides with another user's cached block — silent
    cross-request KV reuse (the vLLM prefix-cache collision vulnerability).

    `salt` defaults to the per-process engine salt (BlockManager's cache
    addresses); the serving router (llm/router.py) passes its OWN salt and
    keeps a router-local chain->replica map — per-process salts mean replica
    digests are deliberately NOT comparable across processes. `seed` mixes
    extra context into the chain root (the engine seeds with the LoRA slot;
    the router with the adapter name)."""
    out: List[bytes] = []
    h = b"prefix-chain"
    bs = block_size
    key = _PREFIX_CACHE_SALT if salt is None else salt
    n_blocks = len(prompt) // bs
    if n_blocks == 0:
        return out
    # One vectorized tobytes per block (fixed-width little-endian i64),
    # not per-token int.to_bytes: this runs at every admission on the
    # prefill scheduling path (and per routed request in the router).
    flat = np.asarray(prompt[:n_blocks * bs], dtype="<i8")
    for i in range(n_blocks):
        m = hashlib.blake2b(key=key, digest_size=16)
        m.update(h)
        m.update(seed)
        m.update(flat[i * bs:(i + 1) * bs].tobytes())
        h = m.digest()
        out.append(h)
    return out


@dataclasses.dataclass
class RequestOutput:
    request_id: str
    prompt_token_ids: List[int]
    output_token_ids: List[int]
    finished: bool
    finish_reason: Optional[str] = None
    text: Optional[str] = None
    new_token_ids: List[int] = dataclasses.field(default_factory=list)


class _Request:
    def __init__(self, request_id: str, prompt: List[int],
                 params: SamplingParams, lora_slot: int = 0):
        self.id = request_id
        self.prompt = list(prompt)
        self.params = params
        self.lora_slot = lora_slot    # 0 = base model (llm/lora.py)
        self.output: List[int] = []
        self.blocks: List[int] = []  # the "all" group's pages, by logical page
        # A window group's pages by logical page, -1 where the page was never
        # attached or has been released behind the window; `side_lo` is the
        # first logical page that may still be held, `hit_blocks` the page
        # boundary of the prefix hit that attached a shared tail.
        self.side_blocks: Dict[str, List[int]] = {}
        self.side_lo = 0
        self.hit_blocks = 0
        # A state group's slot (recurrent layers: model_runner.py, "Layer
        # groups"), held from admission to release; `restore_from` the parked
        # snapshot a prefix hit found, until the engine has copied it in.
        self.state_slot: Optional[int] = None
        self.restore_from: Optional[int] = None
        # Context tokens run through, by every step DISPATCHED; `pending`
        # tokens of steps in flight that are not in `output` yet, and
        # `flying` steps in flight that carry this request ("One step of
        # lookahead" in LLMEngine).
        self.prefilled = 0
        self.pending = 0
        self.flying = 0
        import zlib

        self.seed_val = (params.seed if params.seed is not None
                         else zlib.crc32(request_id.encode()) & 0x7FFFFFFF)
        self.finished_reason: Optional[str] = None
        self.lora_pinned = lora_slot != 0   # released once on finish
        self.prefix_hashes: Optional[List[bytes]] = None  # lazy, per prompt
        self.registered_blocks = 0  # prompt blocks made cache-addressable
        # Lifecycle timestamps (wall clock, so they compare across replicas)
        # for the TTFT/ITL decomposition. The dict travels INSIDE the
        # export_request/export_session state, so queue/prefill time spent
        # on a prefill replica stays attributed after a disagg handoff or a
        # live migration; handoff_s/pause_s accumulate the off-engine gaps.
        self.timing: Dict[str, Optional[float]] = {
            "t_submit": time.time(), "t_admit": None,
            "t_first_token": None, "t_last_token": None,
            "handoff_s": 0.0, "pause_s": 0.0,
            **dict.fromkeys(PREFILL_SPAN_ARGS, 0)}
        self.adopted = False   # arrived via KV handoff (prefill elsewhere)
        self._prompt_key: Optional[Tuple[int, ...]] = None
        self._row = (None, np.empty(0, np.int32))   # see table_row

    @property
    def prompt_key(self) -> Tuple[int, ...]:
        """The prompt as ONE tuple, made once: what every registered block
        of this request names its token prefix by (with a length)."""
        if self._prompt_key is None:
            self._prompt_key = tuple(self.prompt)
        return self._prompt_key

    def table_row(self) -> np.ndarray:
        """`blocks` as an int32 array for the tick's block table, kept beside
        the list and extended by what was appended since the last tick (a
        33k-token context is 2,100 pages: converting 32 such lists cost 4 ms
        of every tick's compose phase; my chip run, PR 33). The list is only
        ever appended to or replaced by a new one."""
        of, row = self._row
        if of is not self.blocks or len(row) > len(self.blocks):
            row = np.empty(0, np.int32)
        if len(row) < len(self.blocks):
            row = np.concatenate(
                [row, np.asarray(self.blocks[len(row):], np.int32)])
        self._row = (self.blocks, row)
        return row

    @property
    def num_tokens(self) -> int:
        return len(self.prompt) + len(self.output)

    def span(self, start: int, stop: int) -> List[int]:
        """context[start:stop] without building the context: a tick asks for
        a slice of every prefilling prompt, and a prompt may be long."""
        n = len(self.prompt)
        if stop <= n:
            return self.prompt[start:stop]
        return self.prompt[start:] + self.output[max(0, start - n):stop - n]

    @property
    def context(self) -> List[int]:
        """Tokens whose KV must exist before decode continues (prompt plus
        anything generated before a preemption)."""
        return self.prompt + self.output


class _Step:
    """A dispatched mixed step until its commit: the composed rows, the
    results as they lie on the device, and what the dispatch changed."""

    __slots__ = ("entries", "arrays", "host_sampled", "results",
                 "expert_counts", "rows")

    def __init__(self, entries: List[dict], arrays: tuple,
                 host_sampled: bool):
        self.entries = entries
        self.arrays = arrays            # the launch's host operands
        self.host_sampled = host_sampled
        self.results: tuple = ()        # device arrays, not fetched
        self.expert_counts = None
        # id(request) -> its row: where the next step finds its token.
        self.rows = {id(e["req"]): i for i, e in enumerate(entries)}


class PagePool:
    """One layer group's pages: a free list, refcounts of live pages, content
    addresses of full prompt blocks, and the parked (cached, unreferenced)
    pages in LRU order."""

    def __init__(self, num_blocks: int, window: Optional[int] = None):
        from collections import OrderedDict

        self.total = num_blocks
        self.window = window                     # None: the "all" group
        self.free: deque = deque(range(num_blocks))
        self.refcount: Dict[int, int] = {}       # live blocks
        self.cached: Dict[bytes, int] = {}       # digest -> block_id
        self.block_hash: Dict[int, bytes] = {}   # block_id -> digest
        self.reusable: "OrderedDict[int, None]" = OrderedDict()  # LRU

    def available(self) -> int:
        return len(self.free) + len(self.reusable)

    def take(self) -> Tuple[int, Optional[bytes]]:
        """A free page, else the least recently used parked one, which loses
        its content address. -> (page, the digest it was cached under)."""
        if self.free:
            return self.free.popleft(), None
        bid, _ = self.reusable.popitem(last=False)
        h = self.block_hash.pop(bid)
        self.cached.pop(h, None)
        return bid, h

    def hold(self, bid: int) -> None:
        if self.refcount.get(bid, 0) == 0:
            self.reusable.pop(bid, None)
        self.refcount[bid] = self.refcount.get(bid, 0) + 1

    def drop(self, bid: int, hot: bool = True) -> None:
        """One reference less; the last one parks a content-addressed page
        (`hot`: as the most recently used, else as the first to recycle) and
        frees any other."""
        n = self.refcount.get(bid, 1) - 1
        if n > 0:
            self.refcount[bid] = n
            return
        self.refcount.pop(bid, None)
        if bid in self.block_hash:
            self.reusable[bid] = None
            self.reusable.move_to_end(bid, last=hot)
        else:
            self.free.append(bid)

    def address(self, bid: int, h: bytes) -> bool:
        """Make page `bid` addressable under digest `h`; first writer wins."""
        if bid in self.block_hash or h in self.cached:
            return False
        self.cached[h] = bid
        self.block_hash[bid] = h
        return True

    def forget(self) -> int:
        n = len(self.cached)
        self.cached.clear()
        self.block_hash.clear()
        while self.reusable:
            bid, _ = self.reusable.popitem(last=False)
            self.free.append(bid)
        return n

    def counts(self) -> Dict[str, int]:
        return {"total": self.total, "free": len(self.free),
                "live": len(self.refcount), "parked": len(self.reusable)}


class SlotPool:
    """A state group's slots (model_runner.py, "Layer groups"): one a live
    sequence, the others free or PARKED: a snapshot of a sequence's state at
    the page boundary where its prompt's cached chain ends, under that
    page's digest. Most snapshots are cut at a boundary no other prompt
    shares (every request's own last whole page), so as in the page pool a
    HIT is what protects one: a snapshot that a hit has attached (`hit`) is
    `hot`, recycled only when no other is left, least recently hit first.
    The others (`cold`) leave by what each COST to cut, the tokens its
    request prefilled past its own restore, aged as GreedyDual-Size ages a
    cache's entries: a snapshot's credit is the clock at its parking plus
    its cost, the least credit goes first (the oldest among equals: first
    in, first out where prompts are alike) and sets the clock. So a shared
    document's snapshot, cut once by the one request that prefilled it
    whole, waits for its first hit behind the snapshots of any number of
    requests that each prefilled a tail beside it (it is cut at one depth
    only: lost, every later request on that document prefills it whole
    beside pages that are all cached), and a turn that extends its own
    earlier prompt finds that prompt's snapshot for as long as a pool of
    its like keeps it."""

    def __init__(self, total: int):
        from collections import ChainMap, OrderedDict

        self.total = total
        self.free: deque = deque(range(total))
        self.live: set = set()
        self.cold: Dict[bytes, int] = {}            # digest -> slot
        self.credit: Dict[bytes, int] = {}          # of the cold ones
        self.clock = 0
        self.hot: "OrderedDict[bytes, int]" = OrderedDict()
        self.parked = ChainMap(self.cold, self.hot)     # every snapshot

    def _take(self) -> int:
        if self.free:
            return self.free.popleft()
        if self.cold:
            h = min(self.cold, key=self.credit.__getitem__)
            self.clock = self.credit.pop(h)
            return self.cold.pop(h)
        return self.hot.popitem(last=False)[1]

    def hold(self) -> int:
        """A slot for a sequence. `total` is sized so that live sequences
        never run out (`state_group_slots`)."""
        slot = self._take()
        self.live.add(slot)
        return slot

    def release(self, slot: int) -> None:
        self.live.discard(slot)
        self.free.append(slot)

    def park(self, h: bytes, cost: int) -> Optional[int]:
        """A slot to snapshot into under digest `h`, which took `cost`
        tokens of prefill to reach; None where `h` has one (first writer
        wins)."""
        if h in self.parked:
            return None
        slot = self._take()
        self.cold[h], self.credit[h] = slot, self.clock + cost
        return slot

    def hit(self, h: bytes) -> int:
        """The slot of the snapshot under `h`, which a prefix hit attaches:
        hot from now on, and the most recently hit."""
        slot = self.hot.pop(h, None)
        if slot is None:
            slot = self.cold.pop(h)
            del self.credit[h]
        self.hot[h] = slot
        return slot

    def forget(self, h: Optional[bytes] = None) -> None:
        """Drop the snapshot under `h` (its page was recycled), or all."""
        for key in ([h] if h is not None else list(self.parked)):
            self.credit.pop(key, None)
            slot = self.cold.pop(key, None)
            if slot is None:
                slot = self.hot.pop(key, None)
            if slot is not None:
                self.free.append(slot)

    def counts(self) -> Dict[str, int]:
        return {"total": self.total, "free": len(self.free),
                "live": len(self.live), "parked": len(self.parked)}


class BlockManager:
    """Paged-KV allocator with automatic prefix caching.

    vLLM analog (reference: vllm's automatic prefix caching, placed by
    ray.llm at deployments/llm/vllm/): every FULL prompt block registers
    under a keyed rolling digest h_i = blake2b(h_{i-1}, block_tokens);
    a new request reuses the longest cached chain (refcounted, copy-free —
    cached blocks are immutable full blocks, and writes only ever target a
    sequence's own fresh tail blocks), skipping that prefix's prefill
    compute entirely. Freed cached blocks park in an LRU reuse pool and
    are recycled only under allocation pressure, so a hot system prompt
    stays resident.

    Pages are handed out by LAYER GROUP (model_runner.py, "Layer groups").
    The "all" group is this class as it always was: `free`, `refcount`,
    `cached`, `block_hash`, `reusable` are its pool's, and `req.blocks` its
    pages. `side_groups` {name: (pages, window)} adds window groups, each a
    PagePool of its own and a list `req.side_blocks[name]`:

      * a sequence's window pages are allocated a tick at a time
        (`allocate_side`) and released once no position still to be computed
        can see them (`release_behind`), during chunked prefill too;
      * a full prompt block registers in every group that still holds its
        page, under the same digest;
      * a prefix hit at page boundary b needs the "all" pages of [0, b) AND
        each window group's pages that cover [b - window, b): `match_prefix`
        takes the longest b that has both;
      * a released window page that is a registered prompt block parks like
        any cached page. The pages of a prompt's LAST window (where the next
        request that shares the whole prompt hits) and a tail that a hit
        attached park as most recently used; a page from the middle of a
        prompt parks as the first to recycle.

    `state_slots` adds a state group (recurrent layers), `states`: a slot a
    sequence from admission (`hold_state`) to `release`, never cleared (a
    sequence that starts at position 0 starts from zeros in the program).
    Pages alone do not restore a recurrent layer, so a prefix hit at page
    boundary b ALSO needs a snapshot of the state after token b * page - 1:
    one is taken where a prompt's prefill reaches its last whole page
    (`snapshot_boundary`, `park_snapshot`; the engine cuts the slice there
    and copies the slot on the device), and `match_prefix` takes the longest
    b that has pages, window tails and a snapshot. A snapshot goes when its
    page is recycled or when the slots run out: one no hit has attached
    first, the cheapest to cut again among them, then the least recently hit
    (`SlotPool`)."""

    def __init__(self, num_blocks: int, block_size: int,
                 enable_prefix_caching: bool = True,
                 side_groups: Optional[Dict[str, Tuple[int, int]]] = None,
                 state_slots: Optional[int] = None):
        self.block_size = block_size
        self.caching = enable_prefix_caching
        pool = PagePool(num_blocks)
        self.pools: Dict[str, PagePool] = {"all": pool}
        self.side: Dict[str, PagePool] = {
            name: PagePool(pages, window)
            for name, (pages, window) in (side_groups or {}).items()}
        self.pools.update(self.side)
        self.states = SlotPool(state_slots) if state_slots else None
        self.state_snapshots = 0
        self.free = pool.free
        self.refcount = pool.refcount            # live blocks
        self.cached = pool.cached                # digest -> block_id
        self.block_hash = pool.block_hash        # block_id -> digest
        self.reusable = pool.reusable            # LRU
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        # Hits that stopped before the "all" group's chain did, for want of
        # a window group's tail.
        self.prefix_hits_cut_short = 0
        # Window-group pages that hits attached (the tails), all groups.
        self.window_tail_pages = 0
        # digest -> (lora_slot, lora_name, the owning request's prompt as
        # ONE tuple, the length of the root-anchored token prefix through
        # that block): what the host/cluster prefix tiers
        # (llm/prefix_store.py) need to re-address and token-verify a block
        # after it leaves this device pool. Every block of a prompt names
        # the same tuple: a copy of the prefix a block was quadratic in the
        # prompt (33.6 M entries for one 32,768-token document). The adapter
        # NAME is resolved at registration time — while the owning request
        # still pins its slot — because slot numbers are recycled across
        # adapter loads and a spill-time resolution could attribute old KV
        # to a new adapter.
        self.digest_meta: Dict[bytes, Tuple[int, Optional[str],
                                            Tuple[int, ...], int]] = {}
        # Hooks installed by LLMEngine.attach_prefix_store: spill_fn is
        # called with (block_id, digest, digest_meta[digest]) when a parked
        # cached block is recycled. It RECORDS the victim and returns: the
        # page stays intact until the next program that writes the pool is
        # dispatched, and the engine reads it before that (_flush_spills).
        # lora_name_fn maps a pinned slot to its adapter name ("" = base
        # model).
        self.spill_fn = None
        self.lora_name_fn = None

    @property
    def one_group(self) -> bool:
        """Whether one list of page ids names a sequence's whole cache (what
        travels: spills, adoption, export, the prefix tiers)."""
        return not self.side and self.states is None

    def group_counts(self) -> Dict[str, Dict[str, int]]:
        out = {name: pool.counts() for name, pool in self.pools.items()}
        if self.states is not None:
            out["state"] = self.states.counts()
        return out

    def _slot_name(self, lora_slot: int) -> Optional[str]:
        if self.lora_name_fn is not None:
            return self.lora_name_fn(lora_slot)
        return "" if lora_slot == 0 else None

    def blocks_needed(self, num_tokens: int) -> int:
        return (num_tokens + self.block_size - 1) // self.block_size

    def _available(self) -> int:
        return len(self.free) + len(self.reusable)

    def can_allocate(self, num_tokens: int) -> bool:
        return self._available() >= self.blocks_needed(num_tokens)

    def _take_free_block(self) -> int:
        # Evicting the least-recently-used parked cached block hands it to
        # the engine as a pending spill: no device call and no host copy
        # here, however many pages an allocation evicts.
        bid, h = self.pools["all"].take()
        if h is not None:
            if self.states is not None:     # a snapshot goes with its page
                self.states.forget(h)
            meta = self.digest_meta.pop(h, None)
            if self.spill_fn is not None:
                self.spill_fn(bid, h, meta)
        return bid

    def allocate(self, req: _Request, num_tokens: int) -> bool:
        """The "all" group's pages for `num_tokens` tokens of `req`."""
        need = self.blocks_needed(num_tokens) - len(req.blocks)
        if need > self._available():
            return False
        for _ in range(max(0, need)):
            bid = self._take_free_block()
            self.refcount[bid] = self.refcount.get(bid, 0) + 1
            req.blocks.append(bid)
        return True

    # ---- window groups ---------------------------------------------------

    def tail_pages(self, pool: PagePool) -> int:
        """Pages that cover a window behind a page boundary."""
        return -(-pool.window // self.block_size)

    def allocate_side(self, req: _Request, num_tokens: int) -> None:
        """Every window group's pages for the tokens a step writes, up to
        `num_tokens` of `req`: the logical pages not attached yet. The pools
        are sized so that this cannot fail (model_runner.py,
        `window_group_pages`) while pages are released behind the window."""
        need = self.blocks_needed(num_tokens)
        for name, pool in self.side.items():
            pages = req.side_blocks.setdefault(name, [])
            if need - len(pages) > pool.available():
                raise RuntimeError(
                    f"layer group {name!r} has {pool.available()} pages left "
                    f"of {pool.total} for {need - len(pages)}: pages were "
                    "not released behind the window")
            while len(pages) < need:
                bid, _ = pool.take()
                pool.hold(bid)
                pages.append(bid)

    def _hot(self, req: _Request, pool: PagePool, page: int) -> bool:
        """Whether logical page `page` of `req` lies in a window where a
        later prompt is likely to hit: the prompt's last one, or the tail
        this request's own hit attached."""
        tail = self.tail_pages(pool)
        full = len(req.prompt) // self.block_size
        return (full - tail <= page < full
                or req.hit_blocks - tail <= page < req.hit_blocks)

    def release_behind(self, req: _Request, next_pos: int) -> int:
        """Release every window group's pages that no position from
        `next_pos` on can see; returns the pages released."""
        released = 0
        lo = None
        for name, pool in self.side.items():
            pages = req.side_blocks.get(name, ())
            first = min(max(0, next_pos - (pool.window - 1))
                        // self.block_size, len(pages))
            for page in range(req.side_lo, first):
                if pages[page] >= 0:
                    pool.drop(pages[page], self._hot(req, pool, page))
                    pages[page] = -1
                    released += 1
            lo = first if lo is None else min(lo, first)
        if lo is not None:
            req.side_lo = max(req.side_lo, lo)
        return released

    # ---- the state group ---------------------------------------------------

    def hold_state(self, req: _Request) -> None:
        if self.states is not None and req.state_slot is None:
            req.state_slot = self.states.hold()

    def snapshot_boundary(self, req: _Request) -> int:
        """The position where `req`'s prefill is cut so that its slot can be
        snapshotted: the end of the longest chain a later hit may attach
        (`match_prefix`'s limit); 0: none."""
        if self.states is None or not self.caching:
            return 0
        return (len(req.prompt) - 1) // self.block_size * self.block_size

    def park_snapshot(self, req: _Request) -> Optional[Tuple[int, int]]:
        """`req`'s prefill stands at its snapshot boundary and the page
        before it is registered: (its slot, a slot to copy it to), or None
        where that page's digest has a snapshot already or no page (the
        state is the tokens', whoever's page holds the digest)."""
        boundary = self.snapshot_boundary(req) // self.block_size
        h = req.prefix_hashes[boundary - 1]
        if h not in self.cached:
            return None
        slot = self.states.park(
            h, (boundary - req.hit_blocks) * self.block_size)
        if slot is None:
            return None
        self.state_snapshots += 1
        return req.state_slot, slot

    def release(self, req: _Request):
        self.release_blocks(req.blocks)
        req.blocks = []
        self.release_behind(req, 1 << 62)      # every window page it holds
        req.side_blocks = {}
        req.side_lo = req.hit_blocks = 0
        if req.state_slot is not None:
            self.states.release(req.state_slot)
        req.state_slot = req.restore_from = None

    def release_blocks(self, blocks: List[int]):
        """THE release path for detached block lists too (exported pages,
        error recovery): anything pushing block ids straight onto .free
        would bypass refcounts and corrupt/leak shared cached blocks."""
        pool = self.pools["all"]
        for bid in blocks:
            pool.drop(bid)

    # ---- prefix caching --------------------------------------------------
    def prefix_hashes(self, prompt: Sequence[int],
                      lora_slot: int = 0) -> List[bytes]:
        """Digest chain for this manager's cache addresses (module-level
        prefix_digest_chain under the per-process salt). The chain is seeded
        with the LoRA slot: adapters change wk/wv (llm/lora.py TARGETS), so
        KV content differs per adapter and cross-adapter sharing would be
        silently wrong."""
        slot = int(lora_slot).to_bytes(8, "little", signed=True)
        return prefix_digest_chain(prompt, self.block_size, seed=slot)

    def match_prefix(self, req: _Request, hashes: List[bytes]) -> int:
        """Attach the longest cached chain to req; returns tokens skipped.
        The prompt's final token is ALWAYS recomputed (its logits seed the
        first sampled token), capping reuse at (len(prompt)-1)//bs blocks.
        With window groups the chain stops at the last page boundary whose
        window tail every such group still holds; with a state group, at the
        last such boundary that also has a snapshot (`req.restore_from`: the
        slot to copy into the request's)."""
        if not self.caching:
            return 0
        limit = min(len(hashes), (len(req.prompt) - 1) // self.block_size)
        chain = 0
        while chain < limit and hashes[chain] in self.cached:
            chain += 1
        # ok[b]: a hit at page boundary b has all it needs beside the pages.
        ok = [True] * (chain + 1)
        for pool in self.side.values():
            tail, run = self.tail_pages(pool), 0
            for i in range(chain):
                run = run + 1 if hashes[i] in pool.cached else 0
                ok[i + 1] &= run >= min(tail, i + 1)
        if self.states is not None:
            for i in range(chain):
                ok[i + 1] &= hashes[i] in self.states.parked
        n = max(b for b in range(chain + 1) if ok[b])
        if n < chain:
            self.prefix_hits_cut_short += 1
        if n and self.states is not None:
            req.restore_from = self.states.hit(hashes[n - 1])
        pool = self.pools["all"]
        for i in range(n):
            bid = self.cached[hashes[i]]
            pool.hold(bid)
            req.blocks.append(bid)
        los = []
        for name, pool in self.side.items():
            lo = max(0, n - self.tail_pages(pool))
            pages = [-1] * lo
            for i in range(lo, n):
                bid = pool.cached[hashes[i]]
                pool.hold(bid)
                pages.append(bid)
            req.side_blocks[name] = pages
            self.window_tail_pages += n - lo
            los.append(lo)
        req.side_lo = min(los, default=0)
        req.hit_blocks = n
        skipped = n * self.block_size
        if skipped:
            self.prefix_hits += 1
            self.prefix_tokens_saved += skipped
        return skipped

    def register_block(self, req: _Request, index: int, h: bytes):
        """A full prompt block finished prefilling: make it addressable, in
        every group that holds its page. First writer wins; a duplicate
        stays private to its sequence."""
        if not self.caching:
            return
        for name, pool in self.side.items():
            pages = req.side_blocks.get(name, ())
            if index < len(pages) and pages[index] >= 0:
                pool.address(pages[index], h)
        if self.pools["all"].address(req.blocks[index], h):
            self.digest_meta[h] = (
                req.lora_slot, self._slot_name(req.lora_slot),
                req.prompt_key, (index + 1) * self.block_size)

    def register_adopted_block(self, bid: int, h: bytes, lora_slot: int,
                               tokens: Sequence[int]) -> bool:
        """Make a block adopted from the prefix store addressable under
        digest `h` (the adopter already holds a refcount on `bid`). First
        writer wins, like register_block."""
        if not self.caching or not self.pools["all"].address(bid, h):
            return False
        tokens = tuple(tokens)
        self.digest_meta[h] = (int(lora_slot), self._slot_name(lora_slot),
                               tokens, len(tokens))
        return True

    def invalidate_prefix_cache(self) -> int:
        """Drop EVERY cached prefix mapping: cached KV was computed under
        the previous weights, so after a weight hot-swap a prefix hit would
        silently decode against stale activations. Parked reusable blocks
        return to the free pool outright; blocks still referenced by live
        sequences merely lose content-addressability (their normal release
        now routes to `free` since their hash entry is gone). Returns the
        number of cache entries dropped."""
        n = self.pools["all"].forget()
        for pool in self.side.values():
            pool.forget()
        if self.states is not None:
            self.states.forget()
        self.digest_meta.clear()
        return n

    # ---- disaggregated handoff (llm/disagg.py) ---------------------------

    def adopt_blocks(self, n: int) -> Optional[List[int]]:
        """Allocate `n` fresh private pages for KV adopted from another
        replica (prefill->decode handoff). Refcounted like any allocation so
        the normal release path applies; returns None when the pool cannot
        fit them (the caller rejects the handoff, nothing partial sticks)."""
        if self._available() < n:
            return None
        out: List[int] = []
        for _ in range(n):
            bid = self._take_free_block()
            self.refcount[bid] = self.refcount.get(bid, 0) + 1
            out.append(bid)
        return out


class LLMEngine:
    def __init__(self, model_runner, *, max_batch_size: int = 8,
                 max_blocks_per_seq: Optional[int] = None,
                 tokenizer=None, prefill_chunk: Optional[int] = None,
                 enable_prefix_caching: bool = True,
                 speculative_ngram: int = 0,
                 prefill_only: bool = False,
                 token_budget: Optional[int] = None):
        self.runner = model_runner
        self.block_size = model_runner.block_size
        # The runner's layer groups past "all" (window groups): their pages
        # and windows. One sequence holds a ring of a window group's pages at
        # most, so `max_batch` rings have to fit.
        groups = tuple(getattr(model_runner, "groups", ()))[1:]
        state = next((g for g in groups if g.slots), None)
        groups = tuple(g for g in groups if not g.slots)
        side = {g.name: (model_runner.group_pages[g.name], g.window)
                for g in groups}
        if state is not None:
            if speculative_ngram:
                raise ValueError(
                    "speculative_ngram: not supported for a block with a "
                    "state group (a rejected draft would need the slot's "
                    "state rolled back; ROADMAP Queue 2)")
            if model_runner.group_pages[state.name] < 2 * max_batch_size:
                raise ValueError(
                    f"layer group {state.name!r}: "
                    f"{model_runner.group_pages[state.name]} slots do not "
                    f"hold {max_batch_size} sequences and their snapshots "
                    "(build the ModelRunner with max_batch=)")
        for g in groups:
            ring = model_runner.table_widths[g.name]
            if max_batch_size * ring > max(side[g.name][0],
                                           model_runner.num_blocks):
                raise ValueError(
                    f"layer group {g.name!r}: {side[g.name][0]} pages do "
                    f"not hold {max_batch_size} sequences' {ring}-page rings "
                    "(build the ModelRunner with max_batch=)")
        self.block_manager = BlockManager(
            model_runner.num_blocks, model_runner.block_size,
            enable_prefix_caching=enable_prefix_caching, side_groups=side,
            state_slots=(model_runner.group_pages[state.name]
                         if state is not None else None))
        self._state_group = state.name if state is not None else None
        self.state_restores = 0
        # Snapshots taken and restored since the last flight record.
        self._tick_counts = {"state_snapshots": 0, "state_restores": 0}
        # A block whose rows narrow to one a sequence before its last
        # segments (model_runner.py, `narrow_at`).
        self._narrows = getattr(getattr(model_runner, "block", None),
                                "narrow_at", None) is not None
        self.max_batch = max_batch_size
        self.max_blocks_per_seq = max_blocks_per_seq or min(
            model_runner.max_blocks_per_seq,
            model_runner.config.max_seq // model_runner.block_size)
        # Hard length cap: a sequence may never outgrow its block-table row.
        self._cap_tokens = min(model_runner.config.max_seq,
                               self.max_blocks_per_seq * self.block_size)
        # A block that routes tokens to experts: picks a token makes over
        # all routed layers, and the experts this program holds.
        block = getattr(model_runner, "block", None)
        self._picks_per_token = (getattr(block, "routed_layers", 0)
                                 * (getattr(block, "top_k", 0) or 0))
        self._held_experts = getattr(block, "held_experts", 0)
        self._tails_noted = 0       # of `bm.window_tail_pages`, in a record
        self.tokenizer = tokenizer
        self.prefill_chunk = prefill_chunk or getattr(
            model_runner, "chunk_size", 128)
        self.waiting: deque = deque()
        self.prefilling: List[_Request] = []
        self.running: List[_Request] = []
        self._rejected: List[RequestOutput] = []
        # n-gram (prompt-lookup) speculative decoding: propose up to K
        # tokens per tick from the sequence's own history, verify them as
        # one row of the mixed tick, at any temperature. 0 = off.
        self.spec_ngram = int(speculative_ngram)
        self.spec_tokens_accepted = 0
        self.spec_tokens_proposed = 0
        # Disaggregated prefill tier (llm/disagg.py): a prefill-only
        # engine's ticks carry no decode or verify row — sequences that
        # finish prefill (first token sampled) park in `running` until
        # export_request hands them to a decode replica.
        self.prefill_only = bool(prefill_only)
        # Bumped by update_weights (RLHF weight sync); rollout experiences
        # record the version they were sampled under.
        self.weights_version = 0
        # Prefill tokens actually run through the model (cache hits and
        # adopted KV excluded): the "zero re-prefill" proof for session
        # migration — an adopted sequence never adds to this.
        self.prefill_tokens_computed = 0
        # Tiered prefix store (llm/prefix_store.py), attached by the
        # serving layer via attach_prefix_store. Host tier catches device
        # evictions; cluster store makes spilled prefixes adoptable fleet
        # wide. Both optional — a bare engine behaves exactly as before.
        self.host_prefix_tier = None
        self.cluster_store = None
        self.host_prefix_hits = 0
        self.host_prefix_tokens_saved = 0
        self.cluster_prefix_hits = 0
        self.cluster_prefix_tokens_saved = 0
        # Eviction spills ("eviction spills" below): victims recorded since
        # the last flush, the staging results on their way to the host, and
        # what became of every eviction (gathered ones are the tier's
        # `spills`).
        self._pending_spills: List[tuple] = []
        self._spill_flights: deque = deque()
        self._spill_sizes: Tuple[int, ...] = ()
        self._spill_pool = None
        self.host_prefix_spills_skipped = 0
        self.host_prefix_spills_failed = 0
        self._tick_spill = [0, 0, 0.0]      # pages, skipped, seconds
        # ONE mixed kernel launch per tick: decode rows (1 token),
        # spec-verify rows (k+1 tokens) and prefill chunk slices share a
        # token-major batch bucketed on TOTAL token count, so a long
        # prompt's chunk never stalls the running decodes behind a launch
        # of its own.
        self._spec_width = 1 + self.spec_ngram
        # Token budget per tick: decode/verify rows are admitted first, the
        # remainder fills from the prefill backlog. Must cover every
        # running row's verify width, and stays a multiple of 8 (a sublane
        # tile of the flat rows; token buckets inherit it).
        budget = (int(token_budget) if token_budget else
                  self.prefill_chunk + self.max_batch * self._spec_width)
        budget = max(budget, self.max_batch * self._spec_width, 8)
        self.token_budget = -(-budget // 8) * 8
        self._warm_mixed: set = set()   # token buckets already precompiled
        self._warm_logits: set = set()  # the same, for the host-logits head
        # What warmup() cost this replica: shapes compiled and wall seconds
        # (a warm persistent compile cache shows as few seconds per shape),
        # and of the seconds those of its one closing wait: what the device
        # still owed of the programs' first runs when the host was done.
        # `startup` is the whole account of the replica's way to ready, the
        # `llm:startup` span's arguments: `serving.build_engine` sets it once
        # (None for an engine built by hand).
        self.warmup_shapes = 0
        self.warmup_s = 0.0
        self.warmup_device_tail_s = 0.0
        self.startup: Optional[Dict] = None
        # warmup()'s own two readings, kept here and not in its frame (see
        # `_note_warmup`): the ledger's totals at its start, and the host
        # instant before its closing wait.
        self._warmup_ledger: Optional[Dict] = None
        self._warmup_host_done = 0.0
        # Tick flight recorder: bounded ring of one record a step() call.
        # A record holds TWO steps (one step of lookahead): the batch the
        # call DISPATCHED (rows, token bucket, budget used, the kernels'
        # walks, a recompile flag) and what the step it COMMITTED emitted;
        # the host's clock over the call (`admit_ms`, the four phases that
        # add up to `dur_ms`, `since_prev_ms` for the loop before it, the
        # spill path's share); whether it ran ahead (`lookahead`, else
        # `settled`); and the time account's `gc_ms`, `cpu_ms` and, where
        # the record is long, `stall` (below). So a slow token is
        # attributable to a CAUSE, not just visible as a gap. Dict writes and
        # clock reads, no device sync: cheap enough to stay always-on.
        self.flight_records: deque = deque(
            maxlen=int(os.environ.get("RAY_TPU_LLM_FLIGHT_RECORDS", "256")))
        self._tick_note: Dict = {}
        self._prev_tick_end: Optional[float] = None
        # The time account ("The time account" below): seconds by phase
        # since the engine started and the first record's start, the engine
        # thread's CPU clock at the last record's end, whether the engine
        # was left idle there, the last periods and waits (ms) for the
        # medians, the long record that waits for the next one to name its
        # cause, stalls by cause as [ticks, seconds], and the last long
        # records with their successors.
        from ray_tpu.util import tracing

        tracing.watch_collector()
        self._time = dict.fromkeys(
            _TIME_PHASES + ("spill", "gc", "cpu"), 0.0)
        self._t_first: Optional[float] = None
        self._cpu_mark: Tuple[int, float] = (0, 0.0)
        self._idle = True
        self._periods: deque = deque(maxlen=STALL_WINDOW)
        self._waits: deque = deque(maxlen=STALL_WINDOW)
        self._pending_stall: Optional[tuple] = None
        self._stalls: Dict[str, List[float]] = {}
        self.stall_records: deque = deque(maxlen=64)
        # One step of lookahead (below): the step in flight, the requests
        # that left the queues while it still writes their pages, what a
        # settle() between two step() calls emitted, whether one emptied the
        # pipeline since the last tick, and the counters of stats().
        self._flight: Optional[_Step] = None
        self._leaving: List[_Request] = []
        self._stash: List[RequestOutput] = []
        self._settled_by_call = False
        self.lookahead_ticks = 0
        self.settled_ticks: Dict[str, int] = {}
        self.discarded_tokens = 0
        # What the dispatched steps asked of the device's sampling head.
        self.sampler_rows = Counter(
            sampled_rows=0, topk_rows=0, topp_rows=0)
        # A state group: the record's names for the rows and sequences the
        # recurrent layers of a dispatched step carried (the block's:
        # `ssm_rows` / `ssm_seqs` unless it says otherwise), and their sums.
        self._state_fields = (getattr(
            getattr(model_runner, "block", None), "state_fields",
            ("ssm_rows", "ssm_seqs")) if self._state_group else ())
        self.state_rows = Counter({name: 0 for name in self._state_fields})
        # Counts a block keeps of a tick by arithmetic of its own, under its
        # own names (`block.tick_fields`; `block.tick_counts(rows, tables,
        # page)` of the tick's [(tokens, first position, context after
        # them)] and the step's block table), and their sums: what a block
        # that attends to a selection of its context spares, say.
        block = getattr(model_runner, "block", None)
        self._count_tick = getattr(block, "tick_counts", None)
        self.block_counts = Counter(
            {name: 0 for name in getattr(block, "tick_fields", ())})

    # ---- API -------------------------------------------------------------

    def add_request(self, prompt_token_ids: Sequence[int],
                    params: Optional[SamplingParams] = None,
                    request_id: Optional[str] = None,
                    lora_name: Optional[str] = None) -> str:
        rid = request_id or uuid.uuid4().hex[:12]
        slot = 0
        if lora_name:
            if self.runner.lora is None:
                raise ValueError(
                    "engine has no LoRA manager; lora_name unsupported")
            slot = self.runner.lora.slot_of(lora_name)  # KeyError if absent
            # Pin until the request finishes: LRU eviction must not hand
            # this slot to another adapter mid-generation.
            self.runner.lora.pin(slot)
        self.waiting.append(_Request(rid, list(prompt_token_ids),
                                     params or SamplingParams(), slot))
        return rid

    def _unpin_lora(self, req: "_Request"):
        if req.lora_pinned:
            req.lora_pinned = False
            self.runner.lora.unpin(req.lora_slot)

    def _lora_idx(self, batch, S) -> Optional[np.ndarray]:
        if self.runner.lora is None:
            return None
        idx = np.zeros(S, dtype=np.int32)
        for i, req in enumerate(batch):
            idx[i] = req.lora_slot
        return idx

    def has_unfinished(self) -> bool:
        """False only on a settled engine: no request queued or live, no step
        in flight, nothing emitted that step() has yet to hand out."""
        return bool(self.waiting or self.prefilling or self.running
                    or self._flight is not None or self._stash)

    # ---- One step of lookahead -------------------------------------------
    #
    # The engine keeps ONE step in flight. A call of step() admits, composes
    # step n+1 from the SCHEDULED state, dispatches it, and only then waits
    # for step n's results and commits them: the device has its next launch
    # queued behind the one it runs, and the host's admit, compose, dispatch,
    # commit and the server's loop run beside the device.
    #
    #   * Scheduled state. A row without a draft yields exactly one token, so
    #     all a composer needs but the token's VALUE is arithmetic. The
    #     dispatch applies it (`_advance`): `prefilled` moves, full prompt
    #     blocks register, a prompt whose last slice rides the step enters
    #     `running`, a row that ends by `max_tokens` leaves it, a window
    #     group's pages behind the next position are released. `req.pending`
    #     counts the tokens in flight; `num_tokens + pending` is where the
    #     next step finds the request.
    #   * The values stay on the device. A decode row whose input token is
    #     still in flight names its row in that step's `samples`
    #     (ModelRunner._step_mixed, `token_src`).
    #   * What the tick carries decides (`_why_synchronous`), no option: a
    #     draft (the next index depends on acceptance, the proposer reads the
    #     host's context), a host-sampled tick, or page pressure that would
    #     preempt settle the step in flight first, and that tick runs whole
    #     inside its call. The record says so (`lookahead`, `settled`).
    #   * `settle()` first, in every public path that reads or changes
    #     request or page state between two step() calls.
    #   * Release rule: the pages of a request that a step in flight still
    #     writes are released at the commit of the LAST step that carries it
    #     (`_retire`). A stop token found at commit n while step n+1 carries
    #     the row: the row's token is discarded at commit n+1
    #     (`discarded_tokens`) and the pages go then. The device runs
    #     launches in dispatch order, so a page released at a dispatch and
    #     handed out again is written only by a later step, and
    #     `_flush_spills`' gather reads before the step that overwrites.

    def settle(self) -> None:
        """Wait for the step in flight and commit it. What it emits is handed
        out by the next step() call. Every public path that reads or changes
        request or page state outside step() calls this first (under the
        server's lock where there is a server); an idle engine is settled."""
        step, self._flight = self._flight, None
        if step is None:
            return
        self._tick_note = {}    # a commit between two records keeps none
        self._stash.extend(self._commit(step, self._fetch(step)))
        self._settled_by_call = True

    def drop_all(self) -> None:
        """After a failed step: forget the step in flight and force-release
        every request. The device runs programs in dispatch order, so
        whatever the failed tick launched writes these pages before any later
        step does; what it registered may never have been written, so the
        prefix cache goes too."""
        self._flight = None
        for queue_ in (self.running, self.prefilling, self.waiting,
                       self._leaving):
            for req in queue_:
                req.pending = req.flying = 0
                self._unpin_lora(req)
                self.block_manager.release(req)
            queue_.clear()
        self.block_manager.invalidate_prefix_cache()

    def _retire(self, req: _Request) -> None:
        """Release a request that left the queues: now, or (the release rule)
        at the commit of the last step in flight that carries it; until then
        it is in `_leaving`."""
        if req.flying:
            if req not in self._leaving:
                self._leaving.append(req)
            return
        if req in self._leaving:
            self._leaving.remove(req)
        self.block_manager.release(req)

    def step(self) -> List[RequestOutput]:
        """One engine iteration: admit, compose and dispatch one mixed step,
        then wait for and commit the step dispatched by the call before.
        Emits a RequestOutput for every request that gained tokens."""
        from ray_tpu.util import tracing

        with tracing.PhaseClock("llm:tick") as clock:
            t_admit = clock.mark("admit")
            cpu_admit = time.thread_time()
            outputs, self._stash = self._stash, []
            self._admit()
            if self._rejected:
                outputs.extend(self._rejected)
                self._rejected.clear()
            note = self._tick_note = {}
            prev = self._flight
            why = self._why_synchronous()
            landed = why is not None and prev is not None
            if landed:      # this tick cannot run ahead of the step in flight
                outputs.extend(self._commit(prev, self._fetch(prev)))
                self._flight = prev = None
            t0 = clock.mark("compose")
            step = self._mixed_tick(why == "host_sampled")
            if step is None and prev is None and not landed:
                return outputs          # nothing ran: no record
            # The call returns once the transfers and the launch are
            # enqueued; np.asarray of its results blocks the host until the
            # device is done.
            t_dispatch = clock.mark("dispatch")
            if step is not None:
                self._dispatch(step, prev)
            # A tick that runs ahead leaves its step in flight and lands the
            # one before; a synchronous tick lands its own.
            landing, self._flight = ((prev, step) if why is None
                                     else (step, None))
            t_wait = clock.mark("wait")
            host = self._fetch(landing) if landing is not None else None
            t_commit = clock.mark("commit")
            if landing is not None:
                outputs.extend(self._commit(landing, host))
            note["kind"] = "mixed"
            note["lookahead"] = step is not None and prev is not None
            if note["lookahead"]:
                self.lookahead_ticks += 1
            else:
                # Why not: what the tick carries, a settle() call since the
                # last tick, or an idle engine (the first step after one, or
                # the last step's landing with nothing left to compose).
                note["settled"] = why or (
                    "call" if self._settled_by_call else "idle")
                self.settled_ticks[note["settled"]] = (
                    self.settled_ticks.get(note["settled"], 0) + 1)
            self._settled_by_call = False
            t_end = time.time()
            cpu_end = time.thread_time()
            # The four phases are consecutive on this thread and add up to
            # dur_ms. The row counters are the DISPATCHED step's; `emitted`
            # and the expert rows the COMMITTED one's.
            note.update(
                t=t0, dur_ms=round((t_end - t0) * 1e3, 3),
                compose_ms=round((t_dispatch - t0) * 1e3, 3),
                dispatch_ms=round((t_wait - t_dispatch) * 1e3, 3),
                wait_ms=round((t_commit - t_wait) * 1e3, 3),
                commit_ms=round((t_end - t_commit) * 1e3, 3),
                # Outside [t, t + dur_ms]: admission just before it (and the
                # settling of a step that this tick could not run ahead of),
                # and since the last recorded tick ended (the server's loop
                # between two step() calls, idle sleeps included; 0 on the
                # first record).
                admit_ms=round((t0 - t_admit) * 1e3, 3),
                since_prev_ms=round(
                    (t_admit - (self._prev_tick_end or t_admit)) * 1e3, 3),
                waiting=len(self.waiting))
            # Eviction spills since the last record: pages gathered,
            # evictions the host tier had no use for, and the engine
            # thread's time in the spill path (inside admit_ms and
            # compose_ms, or in an adoption between two ticks).
            pages, skipped, spent = self._tick_spill
            self._tick_spill = [0, 0, 0.0]
            note["spill_pages"] = pages
            note["spill_skipped"] = skipped
            note["spill_ms"] = round(spent * 1e3, 3)
            if self._state_group is not None:
                note.update(self._tick_counts)
                self._tick_counts = dict.fromkeys(self._tick_counts, 0)
            # Per-request token positions emitted this tick: rid ->
            # absolute output position after the tick (gap attribution
            # joins a slow token's position to the tick that made it).
            note["emitted"] = {o.request_id: len(o.output_token_ids)
                               for o in outputs if o.new_token_ids}
            self._account(note, (t_admit, t0, t_dispatch, t_wait, t_commit,
                                 t_end), cpu_admit, cpu_end, spent)
            self._prev_tick_end = t_end
            self.flight_records.append(note)
        return outputs

    # ---- The time account ------------------------------------------------
    #
    # Where the engine thread's wall time went since the engine started, by
    # what only this process knows at the moment, so that a reader takes the
    # difference of two stats() calls and needs no ring sized to its window.
    #
    #   * Seven phases partition the wall time from the first record's start
    #     to the last one's end: the five of a call (`admit`, `compose`,
    #     `dispatch`, `wait`, `commit`), `loop` (between two calls with work
    #     left) and `idle` (between two calls with none: the engine had
    #     nothing unfinished when the call before returned). Inside them:
    #     `spill` (the eviction-spill path), `gc` (collector passes of 1 ms
    #     or more on ANY thread, util/tracing.py) and `cpu` (this thread's
    #     `time.thread_time()`), the last two over each record's PERIOD:
    #     `since_prev_ms + admit_ms + dur_ms`, without `since_prev_ms` where
    #     that was idle time.
    #   * A record whose period is LONG (`long_tick_excess`) has its excess
    #     over the median put down to one cause (`stall_cause`) when the
    #     NEXT record closes, since the rule for a late wait needs the next
    #     wait: `stall = {"ms", "cause"}` in the long record, summed by
    #     cause, and the pair kept in `stall_records` without `emitted`.

    def _account(self, note: Dict, marks: tuple, cpu_admit: float,
                 cpu_end: float, spill_s: float) -> None:
        from ray_tpu.util import tracing

        t_admit, t0, t_dispatch, t_wait, t_commit, t_end = marks
        acc = self._time
        prev_end = self._prev_tick_end
        idle = self._idle or prev_end is None
        if prev_end is None:
            self._t_first = prev_end = t_admit
        acc["idle" if idle else "loop"] += t_admit - prev_end
        acc["admit"] += t0 - t_admit
        acc["compose"] += t_dispatch - t0
        acc["dispatch"] += t_wait - t_dispatch
        acc["wait"] += t_commit - t_wait
        acc["commit"] += t_end - t_commit
        # The period: from the last record's end (from this call's start
        # after an idle engine) to this record's end, on this thread.
        start = t_admit if idle else prev_end
        thread, cpu_prev = self._cpu_mark
        ident = threading.get_ident()
        cpu_s = cpu_end - (cpu_admit if idle or thread != ident
                           else cpu_prev)
        self._cpu_mark = (ident, cpu_end)
        gc_s = tracing.collector_seconds(start, t_end)
        note["gc_ms"] = round(gc_s * 1e3, 3)
        note["cpu_ms"] = round(cpu_s * 1e3, 3)
        acc["spill"] += spill_s
        acc["gc"] += gc_s
        acc["cpu"] += cpu_s
        self._idle = not self.has_unfinished()
        pending, self._pending_stall = self._pending_stall, None
        if pending is not None:
            long, excess_ms, was_idle = pending
            cause = stall_cause(long, note, excess_ms,
                                statistics.median_high(self._waits),
                                was_idle)
            long["stall"] = {"ms": round(excess_ms, 3), "cause": cause}
            entry = self._stalls.setdefault(cause, [0, 0.0])
            entry[0] += 1
            entry[1] += excess_ms / 1e3
            self.stall_records.append(
                [{k: v for k, v in r.items() if k != "emitted"}
                 for r in (long, note)])
        period_ms = (t_end - start) * 1e3
        excess_ms = long_tick_excess(period_ms, self._periods)
        if excess_ms is not None:
            self._pending_stall = (note, excess_ms, idle)
        self._periods.append(period_ms)
        self._waits.append((t_commit - t_wait) * 1e3)

    def generate(self, prompts: List[Sequence[int]],
                 params: Optional[SamplingParams] = None,
                 ) -> List[RequestOutput]:
        ids = [self.add_request(p, params) for p in prompts]
        done: Dict[str, RequestOutput] = {}
        while self.has_unfinished():
            for out in self.step():
                if out.finished:
                    done[out.request_id] = out
        self.settle()
        return [done[i] for i in ids]

    def stream(self, prompt_token_ids: Sequence[int],
               params: Optional[SamplingParams] = None):
        """Single-request token stream: yields token ids as they are
        sampled; the engine may be concurrently serving other requests only
        if the caller drives step() elsewhere — this helper drives it."""
        rid = self.add_request(prompt_token_ids, params)
        while True:
            for out in self.step():
                if out.request_id != rid:
                    continue
                for t in out.new_token_ids:
                    yield t
                if out.finished:
                    return
            if not self.has_unfinished():
                return

    def abort_request(self, request_id: str) -> bool:
        """Drop a request wherever it lives and free its pages — the serving
        layer calls this when the client disappears (stream consumer gone,
        wait timeout) so an abandoned request stops burning decode compute
        and KV pages on a dead stream. The step in flight is settled first,
        so the pages are free at once: no program dispatched before this
        call can still write them. Returns False when the id is unknown
        (already finished/aborted)."""
        self.settle()
        for queue_ in (self.waiting, self.prefilling, self.running):
            for req in queue_:
                if req.id == request_id:
                    queue_.remove(req)
                    req.finished_reason = "abort"
                    self._unpin_lora(req)
                    self.block_manager.release(req)
                    # What the settled step emitted for it has no reader.
                    self._stash = [o for o in self._stash
                                   if o.request_id != request_id]
                    return True
        return False

    def update_weights(self, params, *, version: Optional[int] = None,
                       force: bool = False) -> Dict:
        """Hot-swap the model weights in place (RLHF weight sync).

        Validates the incoming pytree against the loaded model FIRST —
        structure, per-leaf shape, per-leaf dtype — and raises a typed
        `WeightSyncError` on any mismatch, so a malformed sync payload
        surfaces here instead of as a shape error deep inside the next
        prefill. On success the params are re-placed through the runner's
        normal placement path (sharded over the mesh when one exists) and
        the ENTIRE prefix cache is invalidated: cached KV was computed
        under the old weights and a post-swap prefix hit would be silently
        wrong. The jitted step programs close over nothing — params are an
        argument — so an identical-shaped swap triggers no recompiles.

        Refuses (WeightSyncError) while requests are in flight unless
        `force=True`: an in-flight sequence would mix logits from two
        policies mid-generation. Drain or abort first (the RLHF trainer
        syncs between rollout rounds, when the engine is idle).
        """
        import jax

        from ray_tpu.core.exceptions import WeightSyncError

        self.settle()
        if self.has_unfinished() and not force:
            raise WeightSyncError(
                "engine has unfinished requests; drain rollouts before "
                "swapping weights (or pass force=True)")
        old_paths, old_def = jax.tree_util.tree_flatten_with_path(
            self.runner.params)
        try:
            new_leaves, new_def = jax.tree.flatten(params)
        except Exception as exc:
            raise WeightSyncError(f"weight payload is not a pytree: {exc}")
        if new_def != old_def:
            raise WeightSyncError(
                f"pytree structure mismatch: engine has {old_def}, "
                f"payload has {new_def}")
        for (path, old_leaf), new_leaf in zip(old_paths, new_leaves):
            name = jax.tree_util.keystr(path)
            old_shape = tuple(old_leaf.shape)
            new_shape = tuple(np.shape(new_leaf))
            if old_shape != new_shape:
                raise WeightSyncError(
                    f"shape mismatch at {name}: engine {old_shape}, "
                    f"payload {new_shape}")
            old_dt = np.dtype(old_leaf.dtype)
            new_dt = np.dtype(getattr(new_leaf, "dtype", type(new_leaf)))
            if old_dt != new_dt:
                raise WeightSyncError(
                    f"dtype mismatch at {name}: engine {old_dt}, "
                    f"payload {new_dt}")
        self.runner.params = self.runner._place_params(params)
        invalidated = self.block_manager.invalidate_prefix_cache()
        self.weights_version = (version if version is not None
                                else self.weights_version + 1)
        # Spilled KV is as stale as cached KV after a hot-swap: drop the
        # host tier outright and GC cluster entries below the new version
        # (adoption also gates on exact version match, so a racing peer's
        # lookup can never resurrect pre-swap pages either way). Victims
        # recorded but not read yet are dropped here, and clear() sees to
        # it that pages on their way to the host do not land.
        self._tick_spill[1] += len(self._pending_spills)
        self.host_prefix_spills_skipped += len(self._pending_spills)
        self._pending_spills = []
        if self.host_prefix_tier is not None:
            invalidated += self.host_prefix_tier.clear()
        if self.cluster_store is not None:
            self.cluster_store.purge(
                below_weights_version=self.weights_version)
        return {"version": self.weights_version,
                "invalidated_prefix_entries": invalidated}

    def stats(self) -> Dict:
        """Scheduler/cache load signal for the serving router: queue depths,
        KV pool occupancy, prefix-cache effectiveness, and the queued
        prefill backlog the SLO admission estimator divides by prefill
        throughput. Cheap (no device sync) — safe to poll per request."""
        bm = self.block_manager
        backlog = sum(r.num_tokens - r.prefilled for r in self.prefilling)
        backlog += sum(r.num_tokens for r in self.waiting)
        out = {
            "waiting": len(self.waiting),
            "prefilling": len(self.prefilling),
            "running": len(self.running),
            "free_kv_blocks": bm._available(),
            "total_kv_blocks": self.runner.num_blocks,
            "block_size": self.block_size,
            "prefix_hits": bm.prefix_hits,
            "prefix_tokens_saved": bm.prefix_tokens_saved,
            # Hits cut short for want of a window group's tail, and every
            # layer group's pages: total, free, live, parked (cached and
            # unreferenced).
            "prefix_hits_cut_short": bm.prefix_hits_cut_short,
            "kv_groups": bm.group_counts(),
            # Layout, decode form, slice block and pages a step of each page
            # group's K/V kernel (`ops/paged_attention.py`, `kv_sizes`).
            "kv_kernels": self.runner.kv_kernels,
            # A state group's snapshots: taken (a prompt's prefill reached
            # its last whole page) and copied back into a request's slot (a
            # prefix hit).
            "state_snapshots": bm.state_snapshots,
            "state_restores": self.state_restores,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "queued_prefill_tokens": backlog,
            "weights_version": self.weights_version,
            # Speculation effectiveness (accepted/proposed is the win
            # ratio) + the runner's compile count: steady-state growth of
            # step_compiles flags a silent hot-loop recompile.
            "spec_tokens_proposed": self.spec_tokens_proposed,
            "spec_tokens_accepted": self.spec_tokens_accepted,
            "step_compiles": getattr(self.runner, "step_compiles", 0),
            "warmup_shapes": self.warmup_shapes,
            "warmup_s": round(self.warmup_s, 3),
            # the account of the way to ready, fixed there (a later
            # warmup() moves `warmup_s`, not this): one dict, read-only
            "startup": self.startup,
            "token_budget": self.token_budget,
            "tick_records": len(self.flight_records),
            # One step of lookahead: ticks dispatched with another step in
            # flight, the others by why not, and the tokens of rows that
            # overran a stop token.
            "lookahead_ticks": self.lookahead_ticks,
            "settled_ticks": dict(self.settled_ticks),
            "discarded_tokens": self.discarded_tokens,
            # Rows the device's sampling head filtered, and those of them
            # that asked for top-k / top-p selection passes.
            **self.sampler_rows,
            # A state group: rows and sequences the recurrent layers carried.
            **self.state_rows,
            # What the block counted of its ticks, under its own names.
            **self.block_counts,
            # The time account: cumulative seconds since the engine started.
            # The seven phases add up to `t_last - t_first` (the first
            # record's start to the last one's end, host clock); `spill`,
            # `gc`, `cpu` lie inside them; `stalls` is the long ticks' excess
            # by cause (one still waiting for its successor is not in yet).
            "time": {
                **{k: round(v, 6) for k, v in self._time.items()},
                # every record ran ahead or says why not
                "ticks": (self.lookahead_ticks
                          + sum(self.settled_ticks.values())),
                "t_first": self._t_first, "t_last": self._prev_tick_end,
                "stalls": {cause: {"ticks": n, "seconds": round(sec, 6)}
                           for cause, (n, sec) in self._stalls.items()},
            },
        }
        if self.host_prefix_tier is not None:
            t = self.host_prefix_tier.stats()
            out.update({
                "host_prefix_entries": t["entries"],
                "host_prefix_bytes": t["bytes"],
                "host_prefix_spills": t["spills"],
                "host_prefix_spills_skipped": self.host_prefix_spills_skipped,
                "host_prefix_spills_failed": self.host_prefix_spills_failed,
                "host_prefix_spills_inflight": t["inflight"],
                "host_prefix_demotions": t["demotions"],
                "host_prefix_hits": self.host_prefix_hits,
                "host_prefix_tokens_saved": self.host_prefix_tokens_saved,
            })
        if self.cluster_store is not None:
            c = self.cluster_store.stats()
            out.update({
                "cluster_prefix_published": c["published"],
                "cluster_prefix_adopted_blocks": c["adopted_blocks"],
                "cluster_prefix_stale_rejected": c["stale_rejected"],
                "cluster_prefix_hits": self.cluster_prefix_hits,
                "cluster_prefix_tokens_saved":
                    self.cluster_prefix_tokens_saved,
            })
        lm = self.runner.lora
        if lm is not None:
            out.update({
                "lora_slots": lm.n_slots - 1,
                "lora_loaded": len(getattr(lm, "_slots", {})),
                "lora_pinned": len(getattr(lm, "_pins", {})),
                "lora_loads": getattr(lm, "loads", 0),
                "lora_evictions": getattr(lm, "evictions", 0),
            })
        return out

    def tick_records(self, limit: Optional[int] = None,
                     request_id: Optional[str] = None,
                     stalls: bool = False) -> List:
        """Flight-recorder snapshot, newest last. `request_id` filters to
        ticks that emitted tokens for that request (gap attribution for one
        stream); `limit` keeps the newest N after filtering. `stalls=True`
        returns the `stall_records` ring instead: `[long record, the one
        after it]` pairs (without `emitted`), which outlive the tick ring."""
        if stalls:
            pairs = list(self.stall_records)
            return pairs if limit is None else pairs[-int(limit):]
        records = list(self.flight_records)
        if request_id is not None:
            records = [r for r in records
                       if request_id in (r.get("emitted") or {})]
        if limit is not None:
            records = records[-int(limit):]
        return records

    # ---- disaggregated prefill/decode handoff (llm/disagg.py) ------------

    def export_session(self, request_id: str):
        """Detach a live request wherever it lives for replica->replica
        migration (llm/disagg.py migrate_session). Returns (state, mode):

          * ("kv" mode) the request was decoding — state carries its block
            ids under "blocks" exactly like export_request; the caller
            gathers + streams the pages and the adopter resumes decode with
            zero re-prefill.
          * ("replay" mode) the request had not finished prefill — its
            partial KV is discarded whole (never exported torn) and state
            carries prompt/output/seed only; the importer re-runs from the
            prompt, and seeded sampling makes the retry token-identical.

        (None, None) when the id is unknown (already finished). The step in
        flight is settled first, so no exported page can still be written
        and no sampled token is waiting to be fetched: the export and
        migration preconditions hold."""
        self.settle()
        for req in self.running:
            if req.id == request_id:
                return self.export_request(request_id), "kv"
        for queue_ in (self.waiting, self.prefilling):
            for req in list(queue_):
                if req.id == request_id:
                    queue_.remove(req)
                    self._unpin_lora(req)
                    self.block_manager.release(req)
                    return {
                        "id": req.id,
                        "prompt": list(req.prompt),
                        "output": list(req.output),
                        "seed": req.seed_val,
                        "lora_slot": req.lora_slot,
                        "params": dataclasses.asdict(req.params),
                    }, "replay"
        return None, None

    def export_request(self, request_id: str) -> Optional[dict]:
        """Detach a just-prefilled request for handoff to a decode replica.
        Returns the portable request state with its (detached) block ids
        under "blocks"; the caller gathers those pages off the device
        (ModelRunner.gather_pages), streams them, and THEN releases the
        blocks via block_manager.release_blocks — shared cached prefix
        blocks stay addressable for the next prompt sharing them. Settles
        first: the request's first token is in `output`, its pages written."""
        self.settle()
        for req in self.running:
            if req.id == request_id:
                break
        else:
            return None
        self.runner.require_one_group("export_request")
        self.running.remove(req)
        self._unpin_lora(req)
        blocks, req.blocks = req.blocks, []
        return {
            "id": req.id,
            "prompt": list(req.prompt),
            "output": list(req.output),
            "seed": req.seed_val,
            "lora_slot": req.lora_slot,
            "params": dataclasses.asdict(req.params),
            "blocks": blocks,
            # t_handoff marks when the request left this engine; the adopter
            # books (adopt time - t_handoff) as handoff_s (or pause_s for a
            # migration), so the off-engine gap stays attributed.
            "timing": dict(req.timing, t_handoff=time.time()),
        }

    def adopt_request(self, state: dict, *pages) -> bool:
        """Adopt a prefilled request streamed from another replica: fresh
        private pages, the cache's arrays (gather_pages' tuple, carried
        whole) scattered in, the sequence enters decode directly.
        Decode is bit-identical to a colocated run because the device
        sampler keys on (seed, absolute position counter) — both carried in
        `state`. Returns False (nothing allocated) when the pool can't fit
        the pages; the sender keeps ownership and the router retries."""
        from ray_tpu.llm.sampling import SamplingParams

        self.settle()
        self.runner.require_one_group("adopt_request")
        params = SamplingParams(**state["params"])
        req = _Request(state["id"], list(state["prompt"]), params,
                       int(state.get("lora_slot", 0)))
        req.output = [int(t) for t in state["output"]]
        req.seed_val = int(state["seed"])
        req.adopted = True
        timing = state.get("timing")
        if timing:
            for key in ("t_submit", "t_admit", "t_first_token",
                        "t_last_token", "handoff_s", "pause_s"):
                if timing.get(key) is not None:
                    req.timing[key] = timing[key]
            t_handoff = timing.get("t_handoff")
            if t_handoff is not None:
                gap = max(0.0, time.time() - float(t_handoff))
                key = "pause_s" if state.get("migrated") else "handoff_s"
                req.timing[key] = float(req.timing.get(key) or 0.0) + gap
                if key == "handoff_s" and gap:
                    # The ttft's third phase ends here; the prefill replica
                    # observed `queue` and `prefill` at the first token.
                    from ray_tpu.runtime import metric_defs

                    metric_defs.LLM_TTFT_BREAKDOWN_MS.observe(
                        gap * 1e3, tags={"phase": "handoff"})
        n_pages = wire_page_count(pages)
        if self.block_manager.blocks_needed(req.num_tokens) > n_pages:
            # The stream must cover every context token's KV; anything less
            # is a protocol error (torn export), not pressure.
            raise ValueError(
                f"handoff for {req.id} carries {n_pages} pages; "
                f"{self.block_manager.blocks_needed(req.num_tokens)} "
                "needed")
        if req.lora_slot and self.runner.lora is None:
            raise ValueError(
                "handoff carries a LoRA slot but this replica has no LoRA "
                "manager (disaggregated tiers must preload identical "
                "adapters)")
        # Allocate headroom for the next token too when the stream covered
        # the context exactly (a migrated sequence whose context fills its
        # last block): decode resumes without an immediate allocation.
        total = max(n_pages,
                    self.block_manager.blocks_needed(req.num_tokens + 1))
        ids = self.block_manager.adopt_blocks(total)
        if ids is None:
            return False
        if req.lora_pinned:
            self.runner.lora.pin(req.lora_slot)
        req.blocks = ids
        req.prefilled = req.num_tokens
        self._flush_spills()
        self.runner.scatter_pages(ids[:n_pages], *pages)
        if self.block_manager.caching:
            # Re-register full prompt blocks under THIS replica's digest
            # chain so disaggregation composes with prefix caching: the next
            # prompt sharing the system prefix hits locally.
            req.prefix_hashes = self.block_manager.prefix_hashes(
                req.prompt, req.lora_slot)
            full = min(len(req.prompt) // self.block_size, len(ids))
            while req.registered_blocks < full:
                j = req.registered_blocks
                self.block_manager.register_block(
                    req, j, req.prefix_hashes[j])
                req.registered_blocks += 1
        self.running.append(req)
        return True

    # ---- tiered prefix store (llm/prefix_store.py) -------------------------

    def attach_prefix_store(self, host_tier=None, cluster_store=None):
        """Wire the tiered prefix store in: BlockManager evictions spill
        through `host_tier`, host-tier watermark victims demote into
        `cluster_store`, and _admit promotes from both. Either tier may be
        None (host-only works standalone; cluster-only skips host RAM). A
        block with more than one layer group takes neither tier: an entry
        is one page of one list, and a hit there needs a window group's tail
        beside it (ROADMAP Queue 2)."""
        if not self.block_manager.one_group:
            logger.info("prefix tiers are off: the block has layer groups "
                        "%s", list(self.block_manager.group_counts()))
            return
        self.host_prefix_tier = host_tier
        self.cluster_store = cluster_store
        self.block_manager.lora_name_fn = self._lora_name
        self.block_manager.spill_fn = (
            self._note_eviction if host_tier is not None else None)
        if host_tier is not None:
            if cluster_store is not None and host_tier.on_demote is None:
                host_tier.on_demote = self._demote_entry
            # The gather's sizes: 8, 32, 128 as far as one burst's wanted
            # pages can reach (the tier's capacity in pages, the pool) and
            # a staging result stays a size that reaches the host quickly.
            page = self.runner.page_nbytes
            most = min(host_tier.capacity_bytes // page,
                       self.runner.num_blocks)
            self._spill_sizes = tuple(
                n for i, n in enumerate(_SPILL_SIZES)
                if i == 0 or (_SPILL_SIZES[i - 1] < most
                              and n * page <= _SPILL_STAGE_BYTES))
            if self.warmup_shapes:      # warmed before the tier came
                t0 = time.time()
                self.warmup_shapes += self._warm_spill_gather()
                self.warmup_s += time.time() - t0

    def _lora_name(self, lora_slot: int) -> Optional[str]:
        """Adapter name for a pinned slot: "" = base model, None = cannot
        resolve (no manager / unknown slot — such KV is unaddressable)."""
        if lora_slot == 0:
            return ""
        lm = self.runner.lora
        name_of = getattr(lm, "name_of", None) if lm is not None else None
        return name_of(lora_slot) if name_of is not None else None

    # ---- eviction spills ---------------------------------------------------
    #
    # How an evicted prefix page reaches the host tier. Evicting RECORDS the
    # victim (BlockManager._take_free_block -> _note_eviction): the page is
    # intact until the next program that writes the pool. Before every such
    # program (a step, scatter_pages) _flush_spills asks the tier which of
    # the recorded victims it wants (HostPrefixTier.reserve: all of them
    # with a cluster store behind it, else those the burst itself does not
    # push out again), dispatches ONE gather for them, padded to one of
    # _spill_sizes, and hands the staging result to one worker thread, which
    # waits for the copy to the host and lands each page in the tier. The
    # device runs programs in dispatch order, so the gather reads the pages
    # before the step overwrites them, with no wait on this thread.

    def _note_eviction(self, bid: int, h: bytes, meta) -> None:
        self._pending_spills.append((bid, h, meta))

    def _flush_spills(self) -> None:
        """Dispatch the gather of the victims recorded since the last call.
        Called before anything writes the pool; costs nothing when no page
        was evicted."""
        pending = self._pending_spills
        if not pending:
            return
        t0 = time.perf_counter()
        self._pending_spills = []
        tier = self.host_prefix_tier
        burst, bids = [], []
        for bid, h, meta in pending:
            if meta is None or meta[1] is None:
                continue    # no adapter name: such KV is unaddressable
            slot, lora_name, prompt, length = meta
            burst.append((h, {"tokens": prompt[:length], "lora_slot": slot,
                              "lora_name": lora_name,
                              "weights_version": self.weights_version,
                              "nbytes": self.runner.page_nbytes}))
            bids.append(bid)
        wanted = [(bid, spill)
                  for bid, spill in zip(bids, tier.reserve(burst))
                  if spill is not None]
        top = self._spill_sizes[-1]
        for i in range(0, len(wanted), top):
            self._dispatch_spill(tier, wanted[i:i + top])
        skipped = len(pending) - len(wanted)
        self.host_prefix_spills_skipped += skipped
        self._tick_spill[0] += len(wanted)
        self._tick_spill[1] += skipped
        self._tick_spill[2] += time.perf_counter() - t0

    def _dispatch_spill(self, tier, wanted: List[tuple]) -> None:
        n = next(s for s in self._spill_sizes if s >= len(wanted))
        ids = [bid for bid, _ in wanted]
        spills = [spill for _, spill in wanted]
        try:
            staged = self.runner.gather_pages_async(
                ids + ids[-1:] * (n - len(ids)))
        except Exception:
            self._spills_failed(tier, spills)
            return
        # A staging result is HBM until it has reached the host: past the
        # bound, wait for the oldest (one wait a tick, not one a page).
        flights = self._spill_flights
        while flights and (flights[0].done()
                           or len(flights) >= _SPILL_MAX_INFLIGHT):
            flights.popleft().result()
        if self._spill_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            self._spill_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="llm-spill")
        flights.append(self._spill_pool.submit(
            self._land_spills, tier, staged, spills))

    def _land_spills(self, tier, staged, spills) -> None:
        """The worker thread's half: wait for the pages, in the wire view
        with one page a spill on axis 2, and land each in the tier."""
        try:
            arrs = [np.asarray(a) for a in staged]
            for i, spill in enumerate(spills):
                # Copies, so that a page kept does not hold its burst.
                tier.land(spill, self._entry_fields(tuple(
                    np.ascontiguousarray(p)
                    for p in wire_pages(arrs, i, i + 1))))
        except Exception:
            self._spills_failed(
                tier, [s for s in spills if not s.landed])

    def _spills_failed(self, tier, spills) -> None:
        """A failed spill is a future cache miss, never an engine error;
        it is counted and logged, not swallowed."""
        logger.exception("spilling %d evicted prefix pages to the host "
                         "tier failed", len(spills))
        self.host_prefix_spills_failed += len(spills)
        for spill in spills:
            tier.land(spill, None)

    def settle_spills(self) -> None:
        """Read every recorded victim and wait until every page on its way
        has landed in the host tier."""
        self._flush_spills()
        if self.host_prefix_tier is not None:
            self.host_prefix_tier.drain()

    def _warm_spill_gather(self) -> int:
        """Compile the gather at each of its sizes, so that the first
        eviction of a run compiles nothing. Returns the programs warmed."""
        for n in self._spill_sizes:
            for arr in self.runner.gather_pages_async([0] * n):
                arr.block_until_ready()
        return len(self._spill_sizes)

    # A host-tier or cluster-store entry holds one block's pages under the
    # cache spec's array names, and the names under "arrays" (prefix_store.py
    # reads that key; an entry without it is a (K, V) pair's).

    def _entry_fields(self, pages) -> dict:
        names = [a.name for a in self.runner.cache_arrays]
        return {"arrays": names, **dict(zip(names, pages))}

    def _entry_pages(self, entry: dict) -> tuple:
        return tuple(entry[a.name] for a in self.runner.cache_arrays)

    def _demote_entry(self, entry: dict) -> None:
        """Host-tier watermark victim -> cluster store (tier 2)."""
        if self.cluster_store is None:
            return
        self.cluster_store.publish(entry)

    def _promote_prefix(self, req: _Request) -> int:
        """Extend req's cached-chain attachment past the device tier: host
        RAM block by block, then ONE cluster-table fetch for the rest of
        the chain. Promoted blocks are scattered into fresh device pages
        and re-registered under the local digest chain, so the next prompt
        sharing them hits the device tier directly. Returns tokens saved."""
        from ray_tpu.util import tracing

        bm = self.block_manager
        bs = self.block_size
        limit = min(len(req.prefix_hashes), (len(req.prompt) - 1) // bs)
        promoted = 0
        t_adopt0 = time.time()
        tier = self.host_prefix_tier
        while tier is not None and len(req.blocks) < limit:
            j = len(req.blocks)
            # The page wanted may be a victim recorded a moment ago (by the
            # adopt_blocks below, one turn back): read it first. The tier
            # answers for a page still on its way by waiting for it.
            if any(h == req.prefix_hashes[j]
                   for _, h, _ in self._pending_spills):
                self._flush_spills()
            e = tier.get(req.prefix_hashes[j])
            if (e is None
                    or e.get("weights_version") != self.weights_version
                    or e.get("lora_name") != self._lora_name(req.lora_slot)
                    or tuple(e["tokens"])
                    != tuple(req.prompt[:(j + 1) * bs])):
                break
            ids = bm.adopt_blocks(1)
            if ids is None:
                break
            self._flush_spills()
            self.runner.scatter_pages(ids, *self._entry_pages(e))
            req.blocks.extend(ids)
            bm.register_adopted_block(ids[0], req.prefix_hashes[j],
                                      req.lora_slot, e["tokens"])
            promoted += bs
            self.host_prefix_hits += 1
            self.host_prefix_tokens_saved += bs
        if self.cluster_store is not None and len(req.blocks) < limit:
            lora_name = self._lora_name(req.lora_slot)
            if lora_name is not None:
                from ray_tpu.llm.prefix_store import cluster_chain

                j0 = len(req.blocks)
                chain = cluster_chain(req.prompt[:limit * bs], bs, lora_name)
                verified = []
                for e in self.cluster_store.lookup_pages(
                        chain[j0:limit], lora_id=lora_name,
                        weights_version=self.weights_version):
                    j = j0 + len(verified)
                    want = [int(t) for t in req.prompt[:(j + 1) * bs]]
                    if [int(t) for t in e["tokens"]] != want:
                        break  # token verification IS the forgery guard
                    verified.append((e, want))
                while verified:  # pool pressure: adopt a shorter prefix
                    ids = bm.adopt_blocks(len(verified))
                    if ids is not None:
                        break
                    verified.pop()
                if verified:
                    # One batched scatter: a per-block device write costs
                    # ~1-2 ms of dispatch each, which is most of the
                    # adopt-vs-reprefill budget for long contexts.
                    self._flush_spills()
                    self.runner.scatter_pages(ids, *wire_concat(
                        [self._entry_pages(e) for e, _ in verified]))
                    for bid, (e, want) in zip(ids, verified):
                        bm.register_adopted_block(
                            bid, req.prefix_hashes[len(req.blocks)],
                            req.lora_slot, want)
                        req.blocks.append(bid)
                        promoted += bs
                        self.cluster_prefix_hits += 1
                        self.cluster_prefix_tokens_saved += bs
        if promoted and tracing.enabled():
            # Stitch adoption into the request's trace: tokens the prefill
            # did NOT have to recompute show up as a named span instead of
            # unexplained TTFT variance.
            with tracing.trace_context(tracing.request_trace_id(req.id),
                                       None):
                tracing.record_span(
                    "llm:prefix_adopt", "llm", t_adopt0, time.time(),
                    request_id=req.id, tokens_saved=promoted)
        return promoted

    def adopt_prefix(self, state: dict, *pages) -> int:
        """Adopt prefix blocks pushed by a draining peer (llm/disagg.py
        wire, meta["prefix"]=True): scatter each block into a fresh page,
        register it under THIS engine's digest chain, and park it in the
        reusable pool — exactly as if a local request had prefilled and
        released it. Skips (never errors on) blocks it cannot place:
        stale weights, unknown adapters, token/shape mismatches, or pool
        pressure. Returns blocks adopted."""
        self.settle()
        if (int(state.get("weights_version", 0)) != self.weights_version
                or not self.block_manager.one_group):
            return 0
        entries = state.get("entries") or []
        pages = tuple(np.asarray(p) for p in pages)
        if (len(pages) != len(self.runner.cache_arrays)
                or any(p.ndim != 5 for p in pages)
                or wire_page_count(pages) != len(entries)):
            return 0
        bm = self.block_manager
        bs = self.block_size
        adopted = 0
        for i, ent in enumerate(entries):
            tokens = [int(t) for t in (ent.get("tokens") or [])]
            if not tokens or len(tokens) % bs:
                continue
            lora = ent.get("lora") or ""
            slot = 0
            if lora:
                lm = self.runner.lora
                try:
                    slot = lm.slot_of(lora) if lm is not None else None
                except KeyError:
                    slot = None
                if slot is None or self._lora_name(slot) != lora:
                    continue  # adapter not resident here: unaddressable
            seed = int(slot).to_bytes(8, "little", signed=True)
            h = prefix_digest_chain(tokens, bs, seed=seed)[-1]
            if h in bm.cached:
                continue
            ids = bm.adopt_blocks(1)
            if ids is None:
                break
            self._flush_spills()
            self.runner.scatter_pages(ids, *wire_pages(pages, i, i + 1))
            if bm.register_adopted_block(ids[0], h, slot, tokens):
                adopted += 1
            # Parks in `reusable` (hashed, refcount hits 0) — or returns
            # straight to `free` if registration lost the race.
            bm.release_blocks(ids)
        return adopted

    def export_prefixes(self, limit: int = 16):
        """Snapshot the hottest idle prefix blocks for a drain-time push
        (serving.LLMServer.push_prefixes): parked device blocks first
        (hottest), then host-tier entries. Returns (state, k, v) shaped
        for llm/disagg.py send_handoff, or None when there is nothing
        worth pushing. (state, *pages): the cache's arrays ride behind the
        state as gather_pages returns them."""
        self.settle()
        bm = self.block_manager
        if not bm.one_group:    # no page travels for more than one group
            return None
        picked = []
        for bid in reversed(bm.reusable):
            h = bm.block_hash.get(bid)
            meta = bm.digest_meta.get(h) if h is not None else None
            if meta is None:
                continue
            slot, lora_name, prompt, length = meta
            if lora_name is None:
                continue
            picked.append((bid, lora_name, prompt[:length]))
            if len(picked) >= limit:
                break
        entries, parts = [], []
        if picked:
            parts.append(self.runner.gather_pages([b for b, _, _ in picked]))
            entries.extend({"tokens": list(t), "lora": name}
                           for _, name, t in picked)
        if self.host_prefix_tier is not None and len(entries) < limit:
            self._flush_spills()    # hottest() waits for pages on their way
            for e in self.host_prefix_tier.hottest(limit - len(entries)):
                entries.append({"tokens": list(e["tokens"]),
                                "lora": e["lora_name"]})
                parts.append(self._entry_pages(e))
        if not entries:
            return None
        state = {"prefix": True, "entries": entries,
                 "weights_version": self.weights_version}
        return (state,) + wire_concat(parts)

    # ---- internals -------------------------------------------------------

    def _admit(self):
        """waiting -> prefilling while pages for (context + 1 token) and
        batch slots are available."""
        # A request that ends with the step in flight holds its pages (and a
        # window group's ring) until that step's commit: its row is taken.
        while (self.waiting and len(self.prefilling) + len(self.running)
               + len(self._leaving) < self.max_batch):
            req = self.waiting[0]
            if req.num_tokens + 1 > self._cap_tokens:
                self.waiting.popleft()
                req.finished_reason = "length"
                self._unpin_lora(req)
                self._rejected.append(RequestOutput(
                    req.id, req.prompt, list(req.output), True, "length",
                    self._detok(req.output)))
                continue
            if not self.block_manager.can_allocate(req.num_tokens + 1):
                break
            self.waiting.popleft()
            # Prefix cache: attach the longest cached chain of full prompt
            # blocks and skip their prefill compute entirely (recompute
            # admits after preemption re-match too — their KV may still be
            # resident).
            cached_tokens = 0
            if self.block_manager.caching:
                if req.prefix_hashes is None:
                    req.prefix_hashes = self.block_manager.prefix_hashes(
                        req.prompt, req.lora_slot)
                cached_tokens = self.block_manager.match_prefix(
                    req, req.prefix_hashes)
                # Device tier exhausted: promote from host RAM, then the
                # cluster store (llm/prefix_store.py) — spilled blocks
                # re-enter fresh device pages instead of re-prefilling.
                if (self.host_prefix_tier is not None
                        or self.cluster_store is not None):
                    cached_tokens += self._promote_prefix(req)
                req.registered_blocks = len(req.blocks)
            assert self.block_manager.allocate(req, req.num_tokens + 1)
            self.block_manager.hold_state(req)
            if req.restore_from is not None:
                # The hit's snapshot into the request's slot, on the device,
                # before the step that continues from it is dispatched.
                self.runner.copy_state(req.restore_from, req.state_slot)
                req.restore_from = None
                self.state_restores += 1
                self._tick_counts["state_restores"] += 1
            req.prefilled = cached_tokens
            if req.timing["t_admit"] is None:
                req.timing["t_admit"] = time.time()
                req.timing["cached_tokens"] = cached_tokens
                self._trace_admitted(req)
            self.prefilling.append(req)

    def warmup(self, *, full: bool = False) -> int:
        """Precompile the tick's programs so no user request ever pays an
        XLA compile mid-stream (vLLM's TPU backend precompiles the same way
        at startup). Without this, the first tick that lands in a new token
        bucket stalls for a full compile (observed 13 s on a ~2B model vs a
        105 ms steady-state TTFT).

        The tick's whole grid is the TOKEN ladder at one pinned batch
        bucket. Dummy rows are all padding (cu_q_lens zero), so every KV
        write lands in the scatter drop zone: the KV pool, block tables and
        scheduler state are untouched. The light set is the mixed step over
        that ladder and the spill gather, and nothing else. full=True also
        warms the host-logits head (requests with a repetition penalty)
        over the same ladder; only then does the no-compile guarantee cover
        every request. Returns the number of programs compiled.

        Watched from outside the loop (`_note_warmup`), through names that
        are not this frame's: see there."""
        from ray_tpu.llm.model_runner import token_buckets

        self._warmup_ledger = tracing.compile_totals()
        r = self.runner
        t0 = time.time()
        S = r.batch_bucket(self.max_batch)
        compiled = 0
        for Tb in token_buckets(self.token_budget):
            if Tb not in self._warm_mixed:
                r.warm_mixed(Tb, S, self._spec_width)
                self._warm_mixed.add(Tb)
                compiled += 1
            if full and Tb not in self._warm_logits:
                r.warm_mixed_logits(Tb, S)
                self._warm_logits.add(Tb)
                compiled += 1
        compiled += self._warm_spill_gather()
        if self._state_group is not None:   # the snapshot copy, junk to junk
            junk = r.group_pages[self._state_group]
            r.copy_state(junk, junk)
        # Dispatch is asynchronous: the last program has compiled, but wait
        # for the device so the seconds cover the whole warm-up.
        import jax

        self._warmup_host_done = time.time()
        jax.block_until_ready(r.cache)
        self._note_warmup(t0, compiled, full)
        return compiled

    def _note_warmup(self, t0: float, compiled: int, full: bool) -> None:
        """After warmup()'s closing wait, which is the one wait there is:
        `warmup_shapes`, `warmup_s`, and of the seconds those of that wait,
        `device_tail_s` (a program's first run hides in the next program's
        host work, and this is what did not hide), in a span
        `llm:startup:warmup` with the compile ledger's stages over the whole
        warm-up. Each program's own extent is the `llm:step_compile` span
        its dispatch wrote (`ModelRunner`).

        A method, and its readings attributes, so that warmup()'s frame is
        as large as it was before anything watched it: the traces of the
        step programs run on top of that frame, and a local more there
        moves which of their calls straddle an edge of the interpreter's
        frame stack (`serving._StartupAccount`)."""
        t1 = time.time()
        self.warmup_shapes += compiled
        self.warmup_s += t1 - t0
        self.warmup_device_tail_s += t1 - self._warmup_host_done
        tracing.record_span(
            "llm:startup:warmup", "llm", t0, t1, programs=compiled, full=full,
            device_tail_s=round(t1 - self._warmup_host_done, 4),
            **tracing.stage_args(tracing.compile_since(self._warmup_ledger)))

    def _needs_logits(self, reqs) -> bool:
        """Host sampling (a fetch of whole logits rows) is only needed for
        what the device sampler lacks (repetition penalty)."""
        return any(r.params.repetition_penalty != 1.0 for r in reqs)

    def _sampling_arrays(self, batch, S, counters):
        temps = np.zeros(S, dtype=np.float32)
        top_ks = np.zeros(S, dtype=np.int32)
        top_ps = np.ones(S, dtype=np.float32)
        seeds = np.zeros(S, dtype=np.int32)
        for i, req in enumerate(batch):
            temps[i] = req.params.temperature
            top_ks[i] = req.params.top_k
            top_ps[i] = req.params.top_p
            seeds[i] = req.seed_val
        return temps, top_ks, top_ps, seeds, np.asarray(counters, np.int32)

    # ---- n-gram speculative decode --------------------------------------

    @staticmethod
    def _ngram_propose(context: List[int], k: int, n: int = 3) -> List[int]:
        """Prompt-lookup proposal (vLLM's ngram speculative method): find
        the most recent earlier occurrence of the trailing (n-1)-gram and
        propose the k tokens that followed it. Falls back to shorter grams
        (down to matching just the last token) when the longer key has no
        earlier occurrence — the lookup-max/min ladder; a weak proposal
        costs only a wasted verify row, never a wrong token."""
        for nn in range(min(n, len(context)), 1, -1):
            key = tuple(context[-(nn - 1):])
            for i in range(len(context) - nn, -1, -1):
                if tuple(context[i:i + nn - 1]) == key:
                    prop = list(context[i + nn - 1:i + nn - 1 + k])
                    if prop:
                        return prop
        return []

    # ---- the tick -------------------------------------------------------

    def _preempt_until_decode_fits(self) -> None:
        """Every running sequence needs a page for its next token: preempt
        the newest (it re-admits later and recomputes its context) until
        the others have one."""
        for req in list(self.running):
            while (req in self.running
                   and not self.block_manager.allocate(
                       req, min(req.num_tokens + 1, self._cap_tokens))):
                victim = self.running.pop()
                victim.prefilled = 0
                self.waiting.appendleft(victim)
                self.block_manager.release(victim)

    def _kernel_walk(self, entries: List[dict]) -> Dict[str, int]:
        """`q_blocks`, `kv_pages_walked` and `kv_pages_unrolled` of a tick, by
        the arithmetic of the block's Pallas kernel (ops/paged_attention.py,
        `query_blocks`): a row of n tokens from position p is ceil(n /
        q_block) blocks, and a block walks the pages up to its own last
        token. `kv_pages_unrolled`: of the pages that blocks of ONE token walk
        through a row pool's full form, those whose DMAs the row kernel
        starts unrolled, a run at a time (`pa.pages_in_runs`; a 5-D pool, a
        latent one: 0). With a window group, its fields too
        (`window_pages_freed` counts up from here):
        `window_tail_pages`, the window-group pages that prefix hits attached
        since the last record, and `window_pool_used`, the window groups'
        pages live or parked now (their sizes: `stats()["kv_groups"]`)."""
        qb, page = self.runner.block.q_block, self.block_size
        if qb is None:      # a block without a paged layer: nothing walks
            return {"q_blocks": 0, "kv_pages_walked": 0,
                    "kv_pages_unrolled": 0}
        blocks = walked = unrolled = 0
        full = self.runner.kv_kernels.get("all", {})
        tile = full["pages"][0] if full.get("layout") == "rows" else None
        # A window layer's walk: a block starts at the page that holds its
        # first token's oldest visible position. `window_kv_tokens` are the
        # tokens inside the rows' windows, counted once (not once a layer).
        window = next((pool.window
                       for pool in self.block_manager.side.values()), None)
        w_walked = w_tokens = 0
        for e in entries:
            n = len(e["tokens"])
            for start in range(0, n, qb):
                end = min(start + qb, n)
                pages = -(-min(e["kv_len"], e["q_pos"] + end) // page)
                blocks += 1
                walked += pages
                if tile and end - start == 1:
                    unrolled += pages_in_runs(pages, tile)
                if window is not None:
                    first = max(0, e["q_pos"] + start - (window - 1))
                    w_walked += pages - first // page
            if window is not None:
                w_tokens += e["kv_len"] - max(0, e["q_pos"] - (window - 1))
        out = {"q_blocks": blocks, "kv_pages_walked": walked,
               "kv_pages_unrolled": unrolled}
        if window is not None:
            bm = self.block_manager
            out.update(window_kv_tokens=w_tokens,
                       window_pages_walked=w_walked, window_pages_freed=0,
                       window_tail_pages=(bm.window_tail_pages
                                          - self._tails_noted),
                       window_pool_used=sum(pool.total - len(pool.free)
                                            for pool in bm.side.values()))
            self._tails_noted = bm.window_tail_pages
        return out

    def _decode_batch(self) -> List[_Request]:
        """The sequences a tick gives a decode or verify row (a prefill-only
        engine: none, its `running` is parked)."""
        return [] if self.prefill_only else self.running[:self.max_batch]

    def _why_synchronous(self) -> Optional[str]:
        """Why the tick about to be composed cannot run ahead of a step in
        flight and has to land inside its own call, or None: by what it
        carries. `host_sampled`: the host samples every row from fetched
        logits (`_needs_logits`). `draft`: a decode row may carry a draft, so
        the next token's index depends on acceptance and the proposer reads
        the host's context. `pressure`: the decode rows' next pages do not
        fit, so a sequence will be preempted, and a preempted sequence's
        pages are released at once."""
        batch = self._decode_batch()
        if self._needs_logits(batch + self.prefilling):
            return "host_sampled"
        if self.spec_ngram > 0 and batch:
            return "draft"
        bm = self.block_manager
        need = sum(max(0, bm.blocks_needed(min(
            r.num_tokens + r.pending + 1, self._cap_tokens)) - len(r.blocks))
            for r in batch)
        return "pressure" if need > bm._available() else None

    def _mixed_tick(self, host_sampled: bool) -> Optional[_Step]:
        """Compose ONE mixed kernel launch (ISSUE 17 tentpole, the Ragged
        Paged Attention layout) from the scheduled state: a token-budget
        batch composer admits decode and spec-verify rows FIRST — running
        sequences never stall behind a long prompt — then fills the remaining
        budget from the prefill backlog, for ModelRunner.step_mixed, bucketed
        on total token count. A prefill-only engine composes no decode or
        verify row: its `running` is parked. None: nothing to run.

        Speculation runs at ANY temperature: greedy rows accept by argmax
        agreement with the draft and temperature>0 rows by seeded acceptance
        (rejection) sampling — keys derive from crc32(request_id) and the
        token's absolute index, so a failover replay or migrated session
        re-derives the identical accept/reject trajectory.

        A tick that carries a request the device sampler cannot serve (a
        repetition penalty: `_needs_logits`) takes the SAME backbone with
        the logits head (ModelRunner.step_mixed_logits): the rows' float32
        logits come to the host, every row of the tick is sampled there by
        `sampling.sample`, and no draft is proposed.

        The tick's record gets what the launch reads and leaves out
        (kv_tokens, prefill_tokens, starved); step() adds the host time of
        each phase."""
        from ray_tpu.llm.model_runner import _bucket, token_buckets

        W = self._spec_width
        budget = self.token_budget
        flight = self._flight
        # The batch dimension is pinned to one bucket (compiles scale with
        # the token ladder alone) — the composer must respect it as a ROW
        # cap too, or a backlog of near-finished prefills (many requests,
        # tiny remaining chunks) overflows cu/out_rows.
        S = self.runner.batch_bucket(self.max_batch)
        # -- decode / spec-verify rows first --------------------------------
        batch = self._decode_batch()
        proposals: List[List[int]] = []
        if batch:
            spec_left = budget - len(batch)   # 1 token/row is reserved
            k = 0 if host_sampled else self.spec_ngram
            for r in batch:
                room = self._cap_tokens - (r.num_tokens + r.pending + 1)
                pb = min(k, max(0, room), r.params.max_tokens
                         - len(r.output) - r.pending - 1, spec_left)
                prop = (self._ngram_propose(r.context, pb) if pb > 0 else [])
                spec_left -= len(prop)
                proposals.append(prop)
            for req, prop in zip(list(batch), list(proposals)):
                if not self.block_manager.allocate(
                        req, min(req.num_tokens + req.pending + len(prop) + 1,
                                 self._cap_tokens)):
                    # Page pressure (nothing is in flight: `pressure` in
                    # _why_synchronous): degrade to plain 1-token rows, then
                    # preempt the newest until the plain tick fits.
                    self._preempt_until_decode_fits()
                    batch = [r for r in batch if r in self.running]
                    proposals = [[] for _ in batch]
                    break
        entries: List[dict] = []
        used = 0
        for req, prop in zip(batch, proposals):
            # Where the step finds the request: behind the token in flight,
            # whose value the program takes from that step's samples.
            n = req.num_tokens + req.pending
            if req.pending:
                last, src = 0, flight.rows[id(req)]
            else:
                last, src = (req.output[-1] if req.output
                             else req.prompt[-1]), -1
            entries.append({"req": req, "tokens": [last] + prop, "prop": prop,
                            "kind": "decode", "src": src,
                            "q_pos": n - 1, "kv_len": n + len(prop),
                            "counter": n})
            used += 1 + len(prop)
        # -- remaining budget fills from the prefill backlog ----------------
        for req in list(self.prefilling):
            if len(entries) >= S:
                break
            c = min(req.num_tokens - req.prefilled, self.prefill_chunk,
                    budget - used)
            # A state group: the slice ends where the slot is snapshotted.
            cut = self.block_manager.snapshot_boundary(req) - req.prefilled
            if cut > 0:
                c = min(c, cut)
            if c <= 0:
                break
            entries.append({"req": req,
                            "tokens": req.span(req.prefilled,
                                               req.prefilled + c),
                            "prop": [], "kind": "prefill", "chunk": c,
                            "src": -1, "q_pos": req.prefilled,
                            "kv_len": req.prefilled + c,
                            "counter": req.prefilled + c})
            used += c
            self.prefill_tokens_computed += c
            req.timing["slices"] += 1
            req.timing["routed_rows"] += c * self._picks_per_token
        if self.block_manager.side:     # window groups: this tick's pages
            for e in entries:
                self.block_manager.allocate_side(e["req"], e["kv_len"])
        prefill_rows = sum(1 for e in entries if e["kind"] == "prefill")
        # Admitted prompts the budget or the row cap left without a slice
        # (slices are handed out in queue order, so they are the tail).
        starved = self.prefilling[prefill_rows:] if entries else []
        for req in starved:
            req.timing["starved_ticks"] += 1
        # -- assemble the token-major batch ---------------------------------
        Tb = _bucket(used, token_buckets(budget)) if entries else 0
        warm = self._warm_logits if host_sampled else self._warm_mixed
        recompile = bool(entries) and Tb not in warm
        # Rows the device's sampling head filters (a temperature; none in a
        # tick the host samples) and, of them, those that ask it for top-k /
        # top-p selection passes: the passes run in a step where these are
        # not 0 (ModelRunner._filter_logits).
        sampled = [] if host_sampled else [
            e["req"].params for e in entries
            if e["req"].params.temperature > 0.0]
        sampler = {"sampled_rows": len(sampled),
                   "topk_rows": sum(p.top_k > 0 for p in sampled),
                   "topp_rows": sum(p.top_p < 1.0 for p in sampled)}
        self.sampler_rows.update(sampler)
        carried = dict(zip(self._state_fields, (used, len(entries))))
        self.state_rows.update(carried)
        # The block's own counts, by its own names: of the rows and of their
        # page tables as the step lays them, so once those are laid (below);
        # all zero where nothing was composed.
        counted = dict.fromkeys(self.block_counts, 0)
        # The record of a call that only lands the step in flight (nothing
        # left to compose) holds the same counters, all zero.
        self._tick_note.update(
            budget=budget, used=used, bucket=Tb,
            recompile=recompile, host_sampled=host_sampled, **sampler,
            decode_rows=len(entries) - prefill_rows,
            prefill_rows=prefill_rows,
            spec_tokens=sum(len(e["prop"]) for e in entries),
            budget_exhausted=used >= budget,
            # Context tokens the paged kernel reads, prompt tokens it
            # prefills, admitted prompts that got no slice.
            kv_tokens=sum(e["kv_len"] for e in entries),
            prefill_tokens=sum(e.get("chunk", 0) for e in entries),
            starved=len(starved),
            # Query-context pairs the attention covers, causal: a row of n
            # tokens from position p sees p + 1 .. p + n of them.
            attn_pairs=sum(
                len(e["tokens"]) * e["q_pos"]
                + len(e["tokens"]) * (len(e["tokens"]) + 1) // 2
                for e in entries),
            # Token-expert picks, all routed layers (0: a dense model).
            routed_rows=used * self._picks_per_token,
            # A state group: rows through the scan and slots it reads and
            # writes. A block that narrows: the rows that pass its last
            # segments (one a sequence) and the context tokens they walk
            # there, counted once (not once a layer).
            **carried,
            **counted,
            **({"cross_rows": len(entries),
                "cross_kv_tokens": sum(e["kv_len"] for e in entries)}
               if self._narrows else {}),
            # What the paged kernel's walk does: query blocks of one
            # sequence, and pages each walks up to its last token (causal).
            # Over kv_tokens / page: how many times a context is read.
            **self._kernel_walk(entries))
        if not entries:
            return None
        # Every page this tick's allocations evicted is read before the
        # step that overwrites it: one gather, dispatched here.
        self._flush_spills()
        if recompile:
            # A bucket outside the warmed ladder (or a pre-warmup call):
            # compile it on a dummy BEFORE the real tokens ride it, so the
            # steady-state loop never absorbs the stall unannounced.
            if host_sampled:
                self.runner.warm_mixed_logits(Tb, S)
            else:
                self.runner.warm_mixed(Tb, S, W)
            warm.add(Tb)
        flat = np.zeros(Tb, dtype=np.int32)
        token_src = np.full(Tb, -1, dtype=np.int32)
        cu = np.zeros(S + 1, dtype=np.int32)
        q_positions = np.zeros(S, dtype=np.int32)
        kv_lens = np.zeros(S, dtype=np.int32)
        tables = self.runner.zero_tables(S)     # one a layer group
        out_rows = np.zeros((S, W), dtype=np.int32)
        props = np.zeros((S, W), dtype=np.int32)
        prop_lens = np.zeros(S, dtype=np.int32)
        counters = np.zeros(S, dtype=np.int32)
        pos = 0
        for i, e in enumerate(entries):
            n = len(e["tokens"])
            flat[pos:pos + n] = e["tokens"]
            token_src[pos] = e["src"]
            cu[i] = pos
            cu[i + 1] = pos + n
            q_positions[i] = e["q_pos"]
            kv_lens[i] = e["kv_len"]
            req = e["req"]
            tables["all"][i, :len(req.blocks)] = req.table_row()
            if req.state_slot is not None:
                tables[self._state_group][i, 0] = req.state_slot
            for name, pages in req.side_blocks.items():
                ring = tables[name]     # logical page p at column p % width
                for page in range(req.side_lo, len(pages)):
                    if pages[page] >= 0:
                        ring[i, page % ring.shape[1]] = pages[page]
            if e["kind"] == "prefill":
                # The chunk's LAST row carries the next-token logits.
                out_rows[i] = pos + n - 1
            else:
                # Row j of a decode/verify span: logits after consuming
                # proposal tokens 0..j-1 (clamped for the padding columns).
                out_rows[i] = [pos + min(j, n - 1) for j in range(W)]
            pl = len(e["prop"])
            if pl:
                props[i, :pl] = e["prop"]
            prop_lens[i] = pl
            counters[i] = e["counter"]
            pos += n
        cu[len(entries) + 1:] = pos
        if self._count_tick is not None:
            counted = self._count_tick(
                [(len(e["tokens"]), e["q_pos"], e["kv_len"])
                 for e in entries], tables["all"], self.block_size)
            self.block_counts.update(counted)
            self._tick_note.update(counted)
        reqs = [e["req"] for e in entries]
        temps, top_ks, top_ps, seeds, counters = self._sampling_arrays(
            reqs, S, counters)
        return _Step(entries, (
            flat, token_src, q_positions, kv_lens, cu, tables, out_rows,
            props, prop_lens, temps, top_ks, top_ps, seeds, counters,
            self._lora_idx(reqs, S)), host_sampled)

    def _dispatch(self, step: _Step, prev: Optional[_Step]) -> None:
        """Enqueue `step` behind `prev` (the step in flight, whose samples
        feed this one's decode rows on the device) and move the scheduler to
        where the step leaves it."""
        (flat, token_src, q_positions, kv_lens, cu, tables, out_rows, props,
         prop_lens, temps, top_ks, top_ps, seeds, counters,
         lora_idx) = step.arrays
        step.arrays = ()
        if step.host_sampled:
            step.results = (self.runner.step_mixed_logits(
                flat, q_positions, kv_lens, cu, tables, out_rows[:, 0],
                lora_idx=lora_idx),)
        else:
            step.results = self.runner.step_mixed(
                flat, q_positions, kv_lens, cu, tables, out_rows, props,
                prop_lens, temps, top_ks, top_ps, seeds, counters,
                lora_idx=lora_idx, token_src=token_src,
                prev_samples=prev.results[1] if prev is not None else None)
        step.expert_counts = self.runner.last_expert_counts
        self._advance(step)

    def _ends_by_length(self, req: _Request) -> bool:
        """`_check_finished`'s length rules, with the tokens in flight."""
        return (len(req.output) + req.pending >= req.params.max_tokens
                or req.num_tokens + req.pending >= self._cap_tokens)

    def _advance(self, step: _Step) -> None:
        """The part of a step's outcome that is arithmetic, applied at its
        dispatch: the next step is composed from here while this one runs."""
        bm = self.block_manager
        freed = 0
        for e in step.entries:
            req = e["req"]
            req.flying += 1
            if e["prop"]:
                continue    # a draft: how far the row gets, its commit says
            if e["kind"] == "prefill":
                req.prefilled += e["chunk"]
                if bm.caching:
                    # Addressable from now on: whoever hits these pages reads
                    # them in a later step, after this one wrote them.
                    full = (min(req.prefilled, len(req.prompt))
                            // self.block_size)
                    while req.registered_blocks < full:
                        j = req.registered_blocks
                        bm.register_block(req, j, req.prefix_hashes[j])
                        req.registered_blocks += 1
                    if 0 < req.prefilled == bm.snapshot_boundary(req):
                        # The slot as this step leaves it, copied behind it.
                        copy = bm.park_snapshot(req)
                        if copy is not None:
                            self.runner.copy_state(*copy)
                            self._tick_counts["state_snapshots"] += 1
                if req.prefilled >= req.num_tokens:
                    # The slice's last row samples the first token, unless
                    # the context was recomputed after a preemption: that
                    # resumes decoding without sampling again.
                    e["yields"] = not req.output
                    self.prefilling.remove(req)
                    self.running.append(req)
            else:
                e["yields"] = True
            if e.get("yields"):
                req.pending += 1
                if self._ends_by_length(req):   # this step is its last
                    self.running.remove(req)
                    self._leaving.append(req)
            if req.side_blocks:
                # Behind every window: a sequence's pages that no position
                # still to be computed can see (a mid-prompt sequence
                # computes its next slice's first position next, a running
                # one its last token's) go back to their pool.
                freed += bm.release_behind(
                    req, req.prefilled if req in self.prefilling
                    else req.num_tokens + req.pending - 1)
        if freed:
            self._tick_note["window_pages_freed"] += freed

    def _fetch(self, step: _Step) -> list:
        """Block until the device has run `step`; its results on the host."""
        from ray_tpu.runtime import metric_defs

        host = [np.asarray(a) for a in step.results]
        if self._picks_per_token:
            # Of the step's picks, the rows this program's held experts
            # computed and the busiest expert's, summed over the routed
            # layers: they come back with the samples, in the same wait.
            rows, busiest, met = (int(v)
                                  for v in np.asarray(step.expert_counts))
            self._tick_note.update(expert_rows=rows, expert_rows_max=busiest,
                                   experts_met=met)
            if rows:
                metric_defs.LLM_EXPERT_ROWS.inc(rows)
                metric_defs.LLM_EXPERT_LOAD_SKEW.set(
                    busiest * self._held_experts / rows)
        return host

    def _commit(self, step: _Step, host: list) -> List[RequestOutput]:
        """The part of a step's outcome that needs its results: the sampled
        tokens, what they finish, and the pages of what left."""
        from ray_tpu.runtime import metric_defs

        outputs: List[RequestOutput] = []
        entries = step.entries
        S, W = self.runner.batch_bucket(self.max_batch), self._spec_width
        if not step.host_sampled:
            acc, smp = host
        else:
            # No draft was proposed, so every row commits its slot 0: the
            # host's sample of the row that ends a context (a mid-prompt
            # slice's is never read).
            (logits,) = host
            acc = np.zeros((S, W), dtype=bool)
            smp = np.zeros((S, W), dtype=np.int32)
            for i, e in enumerate(entries):
                req = e["req"]
                if e["kv_len"] == req.num_tokens:
                    smp[i, 0] = sample(logits[i], req.params,
                                       np.asarray(req.context))
        discarded = freed = 0
        for i, e in enumerate(entries):
            req = e["req"]
            req.flying -= 1
            if e.get("yields"):
                req.pending -= 1
            if req.finished_reason is not None:
                # It ended at the commit before (a stop token) while this
                # step already carried it: the row's token is thrown away,
                # and this is the last step that writes its pages.
                discarded += bool(e.get("yields"))
                self._retire(req)
                continue
            if e["kind"] == "prefill":
                if not e.get("yields"):
                    continue   # mid-prompt, or recomputed: no sample is used
                accepted = [int(smp[i, 0])]
            else:
                prop = e["prop"]
                accepted = []
                for j, t in enumerate(prop):
                    if not bool(acc[i, j]):
                        break
                    accepted.append(int(t))
                # The model's own token after the agreed prefix (greedy
                # rows) or the residual/bonus sample (temperature rows).
                accepted.append(int(smp[i, len(accepted)]))
                room = req.params.max_tokens - len(req.output)
                accepted = accepted[:max(1, room)]
                stops = req.params.stop_token_ids or ()
                for j, t in enumerate(accepted):
                    if t in stops:
                        accepted = accepted[:j + 1]
                        break
                if prop:
                    self.spec_tokens_proposed += len(prop)
                    self.spec_tokens_accepted += len(accepted) - 1
                    metric_defs.LLM_SPEC_PROPOSED.inc(len(prop))
                    if len(accepted) > 1:
                        metric_defs.LLM_SPEC_ACCEPTED.inc(len(accepted) - 1)
            req.output.extend(accepted)
            outputs.append(self._emit(req, accepted))
            if req.finished_reason:
                if req in self.running:     # not foreseen by its length
                    self.running.remove(req)
                self._retire(req)
            elif e["prop"] and req.side_blocks:   # see _advance
                freed += self.block_manager.release_behind(
                    req, req.num_tokens - 1)
        if discarded:
            self.discarded_tokens += discarded
            self._tick_note["discarded_tokens"] = (
                self._tick_note.get("discarded_tokens", 0) + discarded)
        if freed:
            self._tick_note["window_pages_freed"] = (
                self._tick_note.get("window_pages_freed", 0) + freed)
        return outputs

    def _emit(self, req: _Request, new_tokens: List[int]) -> RequestOutput:
        from ray_tpu.runtime import metric_defs

        metric_defs.LLM_TOKENS_GENERATED.inc(len(new_tokens))
        now = time.time()
        first = req.timing["t_first_token"] is None
        if first:
            req.timing["t_first_token"] = now
        req.timing["t_last_token"] = now
        self._check_finished(req)
        done = req.finished_reason is not None
        if first:
            self._trace_first_token(req, done)
        if done:
            self._unpin_lora(req)
            self._finish_trace(req)
        return RequestOutput(
            req.id, req.prompt, list(req.output), done, req.finished_reason,
            self._detok(req.output) if done else None, new_tokens)

    def request_breakdown(self, req: _Request) -> Optional[Dict[str, float]]:
        """TTFT/ITL decomposition for one request from its lifecycle
        timestamps: queue_s (submit->admit), prefill_s (admit->first token,
        minus handoff time), handoff_s (disagg KV streams), decode_s
        (first->last token, minus stalls), stall_s (migration pauses)."""
        t = req.timing
        if t["t_first_token"] is None:
            return None
        t_submit = t["t_submit"]
        t_admit = t["t_admit"] if t["t_admit"] is not None else t_submit
        t_first = t["t_first_token"]
        t_last = (t["t_last_token"] if t["t_last_token"] is not None
                  else t_first)
        handoff_s = float(t.get("handoff_s") or 0.0)
        stall_s = float(t.get("pause_s") or 0.0)
        return {
            "queue_s": max(0.0, t_admit - t_submit),
            "prefill_s": max(0.0, t_first - t_admit),
            "handoff_s": handoff_s,
            "decode_s": max(0.0, t_last - t_first - handoff_s - stall_s),
            "stall_s": stall_s,
        }

    # Lifecycle spans and the latency histograms are written when the phase
    # they describe ENDS, so a request that is still decoding (or is aborted
    # later) has what is known of it: `llm:queue` at admission, `llm:prefill`
    # and the ttft phases at the first token, `llm:decode` and the itl phases
    # at the finish. The trace id derives from the rid, so these stitch with
    # the router's root span and the disagg handoff spans without any context
    # having crossed a process boundary.

    def _trace_admitted(self, req: _Request):
        """`llm:queue`: submit to the request's FIRST admission (a recompute
        after preemption re-admits without a second span)."""
        from ray_tpu.util import tracing

        t = req.timing
        if tracing.enabled() and t["t_admit"] > t["t_submit"]:
            with tracing.trace_context(tracing.request_trace_id(req.id),
                                       None):
                tracing.record_span("llm:queue", "llm", t["t_submit"],
                                    t["t_admit"], request_id=req.id)

    def _trace_first_token(self, req: _Request, done: bool):
        """The first token: `queue` and `prefill` of
        ray_tpu_llm_ttft_breakdown_ms and the `llm:prefill` span. An adopted
        request's first token came elsewhere (that replica recorded both;
        `adopt_request` observes `handoff`, the phase that ends there). A
        prefill-only engine leaves the span of a request it hands on to the
        prefill server, which closes it after the export."""
        from ray_tpu.runtime import metric_defs
        from ray_tpu.util import tracing

        bd = self.request_breakdown(req)
        metric_defs.LLM_TTFT_BREAKDOWN_MS.observe(
            bd["queue_s"] * 1e3, tags={"phase": "queue"})
        metric_defs.LLM_TTFT_BREAKDOWN_MS.observe(
            bd["prefill_s"] * 1e3, tags={"phase": "prefill"})
        if (not tracing.enabled() or req.adopted
                or (self.prefill_only and not done)):
            return
        t = req.timing
        with tracing.trace_context(tracing.request_trace_id(req.id), None):
            tracing.record_span(
                "llm:prefill", "llm", t["t_admit"], t["t_first_token"],
                request_id=req.id, tokens=len(req.prompt),
                **{k: t[k] for k in PREFILL_SPAN_ARGS})

    def _finish_trace(self, req: _Request):
        """Close out a finished request's latency attribution: the itl
        phases of ray_tpu_llm_itl_breakdown_ms and the `llm:decode` span,
        which carries the whole breakdown."""
        from ray_tpu.runtime import metric_defs
        from ray_tpu.util import tracing

        bd = self.request_breakdown(req)
        if bd is None:
            return
        # ITL phases are per inter-token gap: the mean decode gap, and the
        # stall share (migration pauses) amortized over the same gaps.
        gaps = max(1, len(req.output) - 1)
        metric_defs.LLM_ITL_BREAKDOWN_MS.observe(
            bd["decode_s"] * 1e3 / gaps, tags={"phase": "decode"})
        if bd["stall_s"]:
            metric_defs.LLM_ITL_BREAKDOWN_MS.observe(
                bd["stall_s"] * 1e3 / gaps, tags={"phase": "stall"})
        if not tracing.enabled():
            return
        t = req.timing
        with tracing.trace_context(tracing.request_trace_id(req.id), None):
            tracing.record_span(
                "llm:decode", "llm", t["t_first_token"], t["t_last_token"],
                request_id=req.id, tokens=len(req.output),
                finish_reason=req.finished_reason or "",
                **{k: round(v, 6) for k, v in bd.items()})

    def _check_finished(self, req: _Request):
        p = req.params
        if p.stop_token_ids and req.output and req.output[-1] in p.stop_token_ids:
            req.finished_reason = "stop"
        elif len(req.output) >= p.max_tokens:
            req.finished_reason = "length"
        elif req.num_tokens >= self._cap_tokens:
            req.finished_reason = "length"

    def _detok(self, token_ids: List[int]) -> Optional[str]:
        if self.tokenizer is None:
            return None
        try:
            return self.tokenizer.decode(token_ids)
        except Exception:
            return None
