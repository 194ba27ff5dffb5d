"""Disaggregated prefill/decode: KV-page streaming between LLM replicas.

Prefill and decode have opposite resource profiles — prefill is one long
compute-bound burst, decode is thousands of tiny latency-bound steps — so
co-scheduling them on one replica makes every chatty session's inter-token
latency hostage to whatever long prompt shares the batch. This module splits
the tiers: PrefillServer replicas run chunked prefill ONLY (engine built
with prefill_only=True), then stream the populated KV pages plus portable
request state to a decode replica, whose engine adopts the pages into its
own block table (BlockManager.adopt_blocks + ModelRunner.scatter_pages) and
enters decode directly.

Wire format (reuses the chunked raw-frame machinery the ring collectives
run on, collective/cpu_group.py):

    [u64 body len][u8 kind=3][KVHandoffMsg: state JSON + kv meta + trace ctx]
    [u64][u8 kind=1][97B _AMETA][raw k-page bytes]   x ceil(bytes/1MiB)
    [u64][u8 kind=1][97B _AMETA][raw v-page bytes]   x ceil(bytes/1MiB)
    <- [u64][u8 kind=2][JSON ack]

The head frame is the typed wire.KVHandoffMsg (kind 3): the portable
request state as JSON bytes plus the request's trace context, so the decode
replica's adopt span parent-links to the sender's handoff span and one
stitched trace covers PrefillServer -> decode replica -> migration target.
Receivers also still accept a bare JSON head frame (kind 2, the pre-trace
wire) — unknown-field/missing-field semantics match the TaskSpec wire.
Control frames are never pickle: the handoff hot path moves
zero pickled bytes end to end (counter-tested like the ring collectives),
and a decode replica never evals attacker-shaped pickles off a socket. Page
payloads ride kind-1 array frames straight out of / into the page buffers
via recv_into — no intermediate copies, dtype-agnostic (bf16 pages travel
as raw bytes; the logical dtype rides in the JSON meta).

Failure atomicity: one connection carries exactly one request, and the
receiver adopts only after BOTH arrays arrived whole. A prefill replica
dying mid-handoff just drops the connection — the decode engine adopts
nothing, and the router re-runs prefill on another replica (the sender only
reports success after the receiver's ack).
"""

from __future__ import annotations

import json
import socket
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.collective.cpu_group import (
    _AMETA, _HDR, _K_ARRAY, _chunks, _frame_views, _read_ameta, _read_hdr,
    _sock_recv_into, _sock_send)
from ray_tpu.core import serialization as _ser
from ray_tpu.llm.engine import PREFILL_SPAN_ARGS
from ray_tpu.runtime import wire
from ray_tpu.util import tracing

# Handoff control frame: JSON body (kinds 0/1 belong to cpu_group's wire).
_K_JSON = 2
# Typed control frame: wire.KVHandoffMsg body — the JSON request state plus
# the trace context that stitches the request's spans across the handoff.
_K_MSG = 3
_CHUNK_BYTES = 1 << 20


class HandoffError(RuntimeError):
    """KV handoff failed before the receiver acked adoption."""


def _send_json(sock: socket.socket, obj: dict,
               deadline: Optional[float] = None) -> None:
    body = json.dumps(obj).encode()
    _sock_send(sock, memoryview(_HDR.pack(len(body), _K_JSON) + body),
               None, deadline)


def _send_msg(sock: socket.socket, msg,
              deadline: Optional[float] = None) -> None:
    body = msg.encode()
    _sock_send(sock, memoryview(_HDR.pack(len(body), _K_MSG) + body),
               None, deadline)


def _recv_frame(sock: socket.socket, deadline: Optional[float] = None):
    """Receive one logical handoff message: ("json", dict) or a whole raw
    array reassembled across its chunk frames ("array", flat uint8)."""
    length, kind = _read_hdr(sock, None, deadline)
    if kind == _K_MSG:
        # Typed head frame: request state + trace context (KVHandoffMsg).
        # Decoded into the same meta dict shape the JSON frame carries so
        # everything downstream is agnostic to which head frame arrived.
        body = bytearray(length)
        _sock_recv_into(sock, memoryview(body), None, deadline)
        msg = wire.KVHandoffMsg.decode(bytes(body))
        meta = json.loads(msg.state_json.decode())
        meta["kv_dtype"] = msg.kv_dtype
        meta["kv_shape"] = list(msg.kv_shape)
        meta["kv_arrays"] = int(msg.n_arrays or 2)
        if msg.trace_id:
            meta["_trace"] = (msg.trace_id, msg.parent_span_id or None)
        return "json", meta
    if kind == _K_JSON:
        body = bytearray(length)
        _sock_recv_into(sock, memoryview(body), None, deadline)
        return "json", json.loads(bytes(body).decode())
    if kind != _K_ARRAY:
        raise HandoffError(f"handoff protocol error: unknown frame kind {kind}")
    dtype, shape, offset, nelems = _read_ameta(sock, None, deadline)
    out = np.empty(shape, dtype)
    flat = out.reshape(-1)
    total, got = flat.size, 0
    while True:
        if nelems:
            _sock_recv_into(
                sock, memoryview(flat[offset:offset + nelems]).cast("B"),
                None, deadline)
            got += nelems
        _ser.counters["deserialize_fast"] += 1
        if got >= total:
            return "array", flat
        length, kind = _read_hdr(sock, None, deadline)
        if kind != _K_ARRAY:
            raise HandoffError("handoff protocol error: truncated array stream")
        _, _, offset, nelems = _read_ameta(sock, None, deadline)


def _send_array(sock: socket.socket, arr: np.ndarray,
                deadline: Optional[float] = None) -> None:
    """Stream one array as chunked raw frames; dtype-agnostic (bytes view)."""
    flat = np.ascontiguousarray(arr).reshape(-1).view(np.uint8)
    for off, n in _chunks(0, flat.size, _CHUNK_BYTES):
        for view in _frame_views(flat[off:off + n], flat.shape, off):
            _sock_send(sock, view, None, deadline)


def send_handoff(address, state: dict, *pages,
                 timeout: float = 60.0) -> dict:
    """Stream one prefilled request to a decode replica's KVStreamServer:
    its state, then the cache's arrays as ModelRunner.gather_pages returned
    them (one dtype and one wire shape for all of a cache spec's arrays).

    Blocks until the receiver acks adoption — only then may the sender
    release its own pages and report success upstream (an unacked handoff
    is treated as never having happened; the router re-runs prefill)."""
    pages = [np.ascontiguousarray(p) for p in pages]
    first = pages[0]
    if any(p.dtype != first.dtype or p.shape != first.shape for p in pages):
        raise HandoffError("a handoff's page arrays differ in dtype or shape")
    migrated = bool(state.get("migrated"))
    with tracing.span("llm:kv_handoff", "llm",
                      request_id=str(state.get("id", "")),
                      migrated=migrated,
                      bytes=int(sum(p.nbytes for p in pages))):
        # The trace ids captured INSIDE the span: the receiver's adopt span
        # parent-links to this handoff span, not to the caller's.
        msg = wire.KVHandoffMsg(
            state_json=json.dumps(state).encode(),
            kv_dtype=str(first.dtype), kv_shape=list(first.shape),
            n_arrays=len(pages), migrated=migrated,
            trace_id=tracing.current_trace_id() or b"",
            parent_span_id=tracing.current_span_id() or b"")
        deadline = time.monotonic() + timeout
        with socket.create_connection(tuple(address), timeout=timeout) as sock:
            sock.settimeout(timeout)
            _send_msg(sock, msg, deadline)
            for p in pages:
                _send_array(sock, p, deadline)
            kind, ack = _recv_frame(sock, deadline)
    if kind != "json" or not ack.get("ok"):
        raise HandoffError(f"decode replica rejected handoff: {ack}")
    return ack


def migrate_session(address, state: dict, *pages,
                    timeout: float = 60.0) -> dict:
    """Replica->replica live session migration: the prefill->decode handoff
    wire generalized. `state` is an LLMEngine.export_session "kv" export —
    possibly mid-generation (output tokens + their KV pages ride along) —
    and the adopter resumes decode exactly where the exporter stopped, with
    zero re-prefill. Same whole-stream-or-discard atomicity and zero-pickle
    guarantees as send_handoff: an unacked migration never happened, and
    the caller falls back to seeded replay from the prompt."""
    meta = dict(state)
    meta["migrated"] = True
    return send_handoff(address, meta, *pages, timeout=timeout)


class KVStreamServer:
    """Decode-side handoff listener: adopts streamed KV pages atomically.

    One daemon thread accepts connections; each connection carries exactly
    one request. A connection that dies mid-stream is discarded whole —
    `adopt_fn(state, *pages) -> bool` runs only once every page array
    arrived intact, so partial prefill state can never enter the decode
    engine's block table."""

    def __init__(self, adopt_fn: Callable[..., bool],
                 host: str = "127.0.0.1", port: int = 0,
                 timeout: float = 60.0):
        self._adopt = adopt_fn
        self._timeout = timeout
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(16)
        self.address: Tuple[str, int] = self._sock.getsockname()
        self._closed = False
        self.handoffs_adopted = 0
        self.handoffs_rejected = 0
        self._thread = threading.Thread(
            target=self._serve, name="kv-handoff-listener", daemon=True)
        self._thread.start()

    def _serve(self):
        while not self._closed:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return  # closed
            threading.Thread(target=self._handle, args=(conn,),
                             daemon=True, name="kv-handoff-conn").start()

    def _handle(self, conn: socket.socket):
        with conn:
            conn.settimeout(self._timeout)
            try:
                kind, meta = _recv_frame(conn)
                if kind != "json":
                    raise HandoffError("handoff must start with a JSON frame")
                flats = [_recv_frame(conn)[1]
                         for _ in range(int(meta.pop("kv_arrays", 2)))]
            except Exception:
                # Partial stream (sender died / malformed): adopt NOTHING.
                self.handoffs_rejected += 1
                return
            trace_id, parent = meta.pop("_trace", (None, None))
            try:
                dtype = np.dtype(meta.pop("kv_dtype"))
                shape = tuple(meta.pop("kv_shape"))
                pages = [f.view(dtype).reshape(shape) for f in flats]
                # Adopt under the sender's trace context so this side of the
                # handoff — and any spans the adopt path opens — stitches
                # into the request's trace across the process boundary.
                with tracing.trace_context(trace_id, parent):
                    with tracing.span("llm:kv_adopt", "llm",
                                      request_id=str(meta.get("id", "")),
                                      migrated=bool(meta.get("migrated"))):
                        ok = bool(self._adopt(meta, *pages))
            except Exception as e:
                self.handoffs_rejected += 1
                try:
                    _send_json(conn, {"ok": False, "error": repr(e)})
                except Exception:
                    pass
                return
            if ok:
                self.handoffs_adopted += 1
                from ray_tpu.runtime import metric_defs

                metric_defs.LLM_KV_HANDOFFS.inc()
            else:
                self.handoffs_rejected += 1
            try:
                _send_json(conn, {"ok": ok, "id": meta.get("id")})
            except Exception:
                pass

    def close(self):
        self._closed = True
        try:
            self._sock.close()
        except OSError:
            pass


class PrefillServer:
    """Replica callable for the prefill tier.

    Runs chunked prefill ONLY (the engine's ticks carry no decode row), then
    exports the request — state + populated KV pages — and streams it to
    the decode replica named by the caller. Requests that finish AT prefill
    (max_tokens == 1, stop token on the first sample) complete here and
    return their response inline: there is nothing left to decode."""

    def __init__(self, llm_config):
        from ray_tpu.llm.serving import build_engine

        self.engine = build_engine(llm_config, prefill_only=True)
        self.tokenizer = llm_config.tokenizer
        self._lock = threading.Lock()
        # EWMA prefill throughput (tokens/s): the router's TTFT estimator
        # divides queued prefill tokens by this.
        self._prefill_tps = 0.0

    def _parse(self, request: Dict):
        from ray_tpu.llm.serving import LLMServer

        return LLMServer._parse(self, request)

    def prefill(self, request: Dict, decode_address) -> Dict:
        """Prefill one request and hand it to `decode_address` (a decode
        replica's KVStreamServer). Returns {"handoff": True, "rid": ...} on
        success; {"handoff": False, "response": ...} when the request
        finished during prefill."""
        prompt, params, lora_name, rid = self._parse(request)
        t0 = time.monotonic()
        t0_wall = time.time()
        with self._lock:
            # A router-assigned request_id rides through so the decode-side
            # stream keeps the router's name for the request (failover
            # replays re-derive the same sampling seed from it).
            rid = self.engine.add_request(prompt, params, request_id=rid,
                                          lora_name=lora_name)
            final = None
            while True:
                outs = self.engine.step()
                mine = [o for o in outs if o.request_id == rid]
                if any(o.finished for o in mine):
                    final = next(o for o in mine if o.finished)
                    break
                if mine:
                    break  # first token emitted: prefill complete
                if not self.engine.has_unfinished():
                    raise RuntimeError(f"request {rid} vanished mid-prefill")
            if final is not None:
                # Finished AT prefill: the engine recorded the lifecycle
                # spans when the request finished; nothing to hand off.
                return {"handoff": False, "rid": rid,
                        "response": _completion_response(final)}
            state = self.engine.export_request(rid)
            blocks = state.pop("blocks")
            pages = self.engine.runner.gather_pages(blocks)
            self.engine.block_manager.release_blocks(blocks)
            elapsed = max(time.monotonic() - t0, 1e-6)
            tps = len(prompt) / elapsed
            self._prefill_tps = (tps if self._prefill_tps == 0.0
                                 else 0.8 * self._prefill_tps + 0.2 * tps)
            t1_wall = time.time()
        # Stream outside the lock: the socket write must not serialize the
        # next request's prefill compute behind network time. The handoff
        # span (opened inside send_handoff) joins the request's trace — the
        # trace id is derived from the rid, so this stitches to the router's
        # root span without any context having crossed the RPC.
        with tracing.trace_context(tracing.request_trace_id(rid), None):
            tracing.record_span("llm:prefill", "llm", t0_wall, t1_wall,
                                request_id=rid, tokens=len(prompt),
                                tier="prefill",
                                **{k: state["timing"][k]
                                   for k in PREFILL_SPAN_ARGS})
            ack = send_handoff(decode_address, state, *pages)
        return {"handoff": True, "rid": rid, "ack": ack,
                "prefill_tokens_per_s": round(self._prefill_tps, 1)}

    def engine_stats(self) -> Dict:
        with self._lock:
            s = self.engine.stats()
        s["prefill_tokens_per_s"] = round(self._prefill_tps, 1)
        s["role"] = "prefill"
        return s


def _completion_response(out) -> Dict:
    """OpenAI-ish completion body from a finished RequestOutput (shared by
    LLMServer.completions and the prefill-finishes-everything path)."""
    return {
        "id": out.request_id,
        "object": "text_completion",
        "choices": [{
            "text": out.text,
            "token_ids": out.output_token_ids,
            "finish_reason": out.finish_reason,
        }],
        "usage": {
            "prompt_tokens": len(out.prompt_token_ids),
            "completion_tokens": len(out.output_token_ids),
        },
    }
