"""Serving-fleet resilience: replica failover, live KV session migration
over the drain plane, and drain-based scale-down.

Most coverage runs cluster-free against in-process engines (RouterCore and
FleetSupervisor are cluster-free by design; LLMServer + the raw-frame
migration wire work in-process), so ejection pruning, seeded replay
identity, migration atomicity, and the scale policy all run at unit-test
cost. The chaos churn test stands up a real Cluster for the drain plane
(NODE_DRAINING/NODE_PREEMPTED events from the GCS) and kills/drains
replica nodes under sustained load; the >60s sweep rides behind `slow`.
"""

import socket
import threading
import time

import pytest

import ray_tpu  # noqa: F401


def _tiny(vocab=128, max_seq=128):
    import jax.numpy as jnp

    from ray_tpu.models import llama

    return llama.LlamaConfig.tiny(vocab_size=vocab, max_seq=max_seq,
                                  dtype=jnp.float32)


def _cfg(config, **kw):
    from ray_tpu.llm.serving import LLMConfig

    base = dict(model_config=config, num_kv_blocks=64, block_size=8,
                max_batch_size=4, prefill_chunk=8, warmup_buckets="off",
                stream_timeout_s=30.0)
    base.update(kw)
    return LLMConfig(**base)


def _prompt(seed, n=17, vocab=128):
    return [(seed * 7 + 3 * i + seed) % vocab for i in range(n)]


@pytest.fixture(scope="module")
def setup(cpu_jax):
    return _tiny()


@pytest.fixture()
def captured_events(monkeypatch):
    """Record every events.emit this process makes (emit is a no-op send
    without a GCS, so capturing the records is the whole observable)."""
    from ray_tpu.runtime import events

    records = []
    real = events.make_event

    def emit(event_type, message, **kw):
        rec = real(event_type, message, **kw)
        records.append(rec)
        return rec

    monkeypatch.setattr(events, "emit", emit)
    return records


def _stats2(free=(64, 64)):
    return [{"running": 0, "waiting": 0, "prefilling": 0,
             "free_kv_blocks": f, "total_kv_blocks": 64} for f in free]


# ---------------------------------------------------------------------------
# RouterCore health: ejection prunes affinity eagerly (the leak fix),
# remap repoints it, exclusion drives failover picks.
# ---------------------------------------------------------------------------


def test_eject_prunes_affinity_and_stops_routing():
    from ray_tpu.llm.router import NoHealthyReplicasError, RouterCore

    core = RouterCore(2, block_size=8)
    p = _prompt(1, 33)
    # Pin both affinity kinds to replica 0.
    idx, _ = core.pick(p, session_id="s0", stats=_stats2())
    for _ in range(3):
        again, d = core.pick(p, session_id="s0", stats=_stats2())
        assert again == idx and d["reason"] in ("session", "prefix")

    pruned = core.eject(idx)
    assert pruned["prefix_pruned"] > 0 and pruned["sessions_pruned"] == 1
    # Eager prune: no owner entry for the corpse survives, so the session's
    # next turn routes to the survivor instead of leaking at the dead slot.
    assert idx not in core._prefix_owner.values()
    assert idx not in core._session_owner.values()
    other, _ = core.pick(p, session_id="s0", stats=_stats2())
    assert other != idx and core.is_healthy(other)

    # Idempotent; and with every replica down the router reports, not hangs.
    assert core.eject(idx) is None
    assert core.ejected_count == 1
    core.eject(other)
    with pytest.raises(NoHealthyReplicasError):
        core.pick(p, stats=_stats2())


def test_remap_repoints_affinity_to_adoptive_replica():
    from ray_tpu.llm.router import RouterCore

    core = RouterCore(3, block_size=8)
    p = _prompt(2, 33)
    src, _ = core.pick(p, session_id="sess", stats=[None] * 3)
    dst = (src + 1) % 3
    moved = core.remap(src, dst)
    assert moved["sessions_remapped"] == 1 and moved["prefix_remapped"] > 0
    core.set_draining(src)  # the drain path drains, THEN remaps
    idx, d = core.pick(p, session_id="sess", stats=[None] * 3)
    assert idx == dst and d["reason"] == "session"


def test_pick_exclude_and_draining_skips():
    from ray_tpu.llm.router import RouterCore

    core = RouterCore(3)
    core.set_draining(0)
    for _ in range(8):
        idx, _ = core.pick(_prompt(3), stats=[None] * 3, exclude={1})
        assert idx == 2  # 0 draining, 1 excluded by the failover attempt
    assert core.routable_count() == 2 and core.healthy_count() == 3


def test_note_failure_threshold_and_reset():
    from ray_tpu.llm.router import RouterCore

    core = RouterCore(2, fail_threshold=3)
    assert not core.note_failure(0)
    assert not core.note_failure(0)
    core.note_success(0)                     # a good probe resets the count
    assert not core.note_failure(0)
    assert not core.note_failure(0)
    assert core.note_failure(0)              # third consecutive: eject me
    assert core.note_failure(1, hard=True)   # hard failure: immediately


# ---------------------------------------------------------------------------
# Failover: dead replica -> ejection + seeded replay, token-identical,
# greedy AND sampled; orphan aborted server-side (no KV leak).
# ---------------------------------------------------------------------------


class _FlakyReplica:
    """Wraps a live LLMServer; fails `method` the first `fails` times —
    AFTER forwarding, when `submit_first` (the decode-died-mid-stream
    shape: the engine holds the orphan while the caller sees an error)."""

    def __init__(self, server, *, fails=1, method="completions",
                 submit_first=False):
        self._server = server
        self._fails = fails
        self._method = method
        self._submit_first = submit_first

    def __getattr__(self, name):
        return getattr(self._server, name)

    def completions(self, request):
        if self._method == "completions" and self._fails > 0:
            self._fails -= 1
            if self._submit_first:
                prompt, params, lora, rid = self._server._parse(request)
                self._server._submit(prompt, params, lora, rid)
            raise ConnectionError("replica connection lost")
        return self._server.completions(request)


@pytest.mark.parametrize("sampling", ["greedy", "temperature"])
def test_failover_replay_is_token_identical(setup, captured_events,
                                            sampling):
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import LLMServer
    from ray_tpu.runtime import events

    req = {"prompt": _prompt(4, 21), "max_tokens": 12,
           "request_id": f"failover-{sampling}", "session_id": "fo"}
    if sampling == "temperature":
        req.update(temperature=0.8, top_k=20)

    # Reference: the same request, same request_id, zero faults. The engine
    # seeds sampling from crc32(request_id), so this is the ground truth
    # any replay must reproduce bit-identically.
    ref = LLMServer(_cfg(setup)).completions(dict(req))

    victim = _FlakyReplica(LLMServer(_cfg(setup)))
    survivor = LLMServer(_cfg(setup))
    core = RouterCore(2, fail_threshold=1)
    sup = FleetSupervisor(core, [LocalReplica(victim, "victim"),
                                 LocalReplica(survivor, "survivor")])
    core._session_owner["fo"] = 0          # deterministic first pick

    resp = sup.completions(dict(req))
    assert "error" not in resp, resp        # the client never sees the fault
    assert resp["choices"][0]["token_ids"] == ref["choices"][0]["token_ids"]
    assert sup.failovers == 1 and core.healthy_count() == 1
    types = [e["type"] for e in captured_events]
    assert events.LLM_REQUEST_FAILOVER in types
    assert events.LLM_REPLICA_EJECTED in types


def test_spec_acceptance_failover_replay_token_identical(setup):
    """Speculative decoding at temperature>0 (unified-tick seeded acceptance
    sampling): accept/reject draws key on (crc32(request_id), absolute token
    index) and the n-gram drafts are pure functions of sequence history, so
    the survivor's replay reproduces the victim's trajectory bit-exactly."""
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import LLMServer

    spec = dict(speculative_ngram=3)
    # A cyclic prompt keeps the n-gram proposer firing, so the replayed
    # trajectory exercises real accept/reject draws, not just the spec-off
    # sampler.
    req = {"prompt": [5, 9, 13, 5, 9, 13, 5, 9, 13, 5, 9],
           "max_tokens": 14, "request_id": "spec-replay",
           "session_id": "sr", "temperature": 0.8, "top_k": 20}

    ref_server = LLMServer(_cfg(setup, **spec))
    ref = ref_server.completions(dict(req))
    assert ref_server.engine.spec_tokens_proposed > 0  # drafts actually ran

    victim = _FlakyReplica(LLMServer(_cfg(setup, **spec)))
    survivor = LLMServer(_cfg(setup, **spec))
    core = RouterCore(2, fail_threshold=1)
    sup = FleetSupervisor(core, [LocalReplica(victim, "victim"),
                                 LocalReplica(survivor, "survivor")])
    core._session_owner["sr"] = 0

    resp = sup.completions(dict(req))
    assert "error" not in resp, resp
    assert resp["choices"][0]["token_ids"] == ref["choices"][0]["token_ids"]
    assert survivor.engine.spec_tokens_proposed > 0


def test_spec_acceptance_migration_token_identical(setup):
    """A speculating temperature>0 session live-migrated mid-decode resumes
    on the target with its (seed, absolute-counter) sampling state carried
    in the portable state, so the collected output still equals the
    uninterrupted reference."""
    from ray_tpu.llm.serving import LLMServer

    spec = dict(speculative_ngram=3)
    req = {"prompt": [5, 9, 13, 5, 9, 13, 5, 9, 13, 5, 9],
           "max_tokens": 24, "request_id": "spec-mig",
           "temperature": 0.8, "top_k": 20}
    ref = LLMServer(_cfg(setup, **spec)).completions(dict(req))

    src, dst = LLMServer(_cfg(setup, **spec)), LLMServer(_cfg(setup, **spec))
    box = _bg_collect(src, req)
    assert _wait_running(src)
    summary = src.migrate_sessions(dst.handoff_address())
    box["thread"].join(15)
    if summary["migrated"] == ["spec-mig"]:
        resp = dst.completions_collect("spec-mig")
    else:
        # Raced to completion before the drain plane took it — the src
        # result must then already be the full (identical) stream.
        assert "resp" in box, box
        resp = box["resp"]
    assert resp["choices"][0]["token_ids"] == ref["choices"][0]["token_ids"]


def test_decode_failover_aborts_orphan_no_kv_leak(setup):
    """Decode replica 'dies' AFTER admitting the request: the failover path
    must abort the orphan server-side so it stops holding KV pages, and
    the replayed stream must still be identical."""
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import LLMServer

    req = {"prompt": _prompt(5, 21), "max_tokens": 48,
           "request_id": "orphan-abort", "session_id": "oa"}
    ref = LLMServer(_cfg(setup)).completions(dict(req))

    victim_server = LLMServer(_cfg(setup))
    victim = _FlakyReplica(victim_server, submit_first=True)
    survivor = LLMServer(_cfg(setup))
    core = RouterCore(2, fail_threshold=1)
    sup = FleetSupervisor(core, [LocalReplica(victim, "victim"),
                                 LocalReplica(survivor, "survivor")])
    core._session_owner["oa"] = 0

    resp = sup.completions(dict(req))
    assert resp["choices"][0]["token_ids"] == ref["choices"][0]["token_ids"]
    # The orphan was aborted on the failed replica: engine empty, every KV
    # page back in the free pool, stream table clean.
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        s = victim_server.engine_stats()
        if (s["running"] + s["waiting"] + s["prefilling"] == 0
                and s["free_kv_blocks"] == s["total_kv_blocks"]):
            break
        time.sleep(0.05)
    assert s["free_kv_blocks"] == s["total_kv_blocks"], s
    assert "orphan-abort" not in victim_server._streams


def test_stats_probe_staleness_ejects(setup, captured_events):
    """The fast-tier router-ejection leg: a replica that stops answering
    engine_stats gets ejected after fail_threshold consecutive misses —
    no request has to die first."""
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.runtime import events

    class DeafReplica:
        def engine_stats(self):
            raise TimeoutError("probe timed out")

    class FineReplica:
        def engine_stats(self):
            return _stats2()[0]

    core = RouterCore(2, fail_threshold=3)
    sup = FleetSupervisor(core, [LocalReplica(DeafReplica(), "deaf"),
                                 LocalReplica(FineReplica(), "fine")])
    for _ in range(3):
        sup.fresh_stats(force=True)
    assert not core.is_healthy(0) and core.is_healthy(1)
    assert any(e["type"] == events.LLM_REPLICA_EJECTED
               for e in captured_events)
    # Ejected replicas are never probed again (a dead actor must not cost
    # a timeout per stats refresh forever).
    stats = sup.fresh_stats(force=True)
    assert stats[0] is None and stats[1] is not None


def test_application_errors_propagate_without_ejection():
    """An error the replica RAISED while executing (validation failure,
    per-request stream timeout, remote TaskError) is not replica death:
    it must reach the client untouched, with no ejection and no replay —
    otherwise one malformed request walks the retry loop and ejects every
    healthy replica in the fleet."""
    from ray_tpu.core.exceptions import TaskError
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import RequestTimeoutError

    class AppErrorReplica:
        def __init__(self, exc):
            self._exc = exc

        def engine_stats(self):
            return _stats2()[0]

        def completions(self, request):
            raise self._exc

    cases = [
        (ValueError("string prompt requires a tokenizer"), ValueError),
        (RequestTimeoutError("no engine output within 30.0s"),
         RequestTimeoutError),
        # The actor-RPC shape: the replica executed and raised; get()
        # surfaces a TaskError wrapper. Still not transport death.
        (TaskError("completions", "Traceback ...\nValueError: bad params",
                   cause=ValueError("bad params")), TaskError),
    ]
    for exc, etype in cases:
        core = RouterCore(2, fail_threshold=1)
        sup = FleetSupervisor(core, [
            LocalReplica(AppErrorReplica(exc), "r0"),
            LocalReplica(AppErrorReplica(exc), "r1")])
        with pytest.raises(etype):
            sup.completions({"prompt": _prompt(2), "max_tokens": 2})
        assert core.healthy_count() == 2, exc
        assert sup.failovers == 0 and core.ejected_count == 0


def test_prefill_outage_never_ejects_decode_replicas():
    """A whole-tier prefill failure is reported as a 503, not attributed
    to the decode replica the router happened to pair with it — a
    transient prefill outage must not destroy the decode fleet."""
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore

    class Decode:
        def engine_stats(self):
            return _stats2()[0]

        def handoff_address(self):
            return ["127.0.0.1", 9]

    class DeadPrefill:
        def prefill(self, request, decode_address):
            raise ConnectionError("prefill node lost")

    core = RouterCore(2, fail_threshold=1)
    sup = FleetSupervisor(
        core, [LocalReplica(Decode(), "d0"), LocalReplica(Decode(), "d1")],
        prefill_replicas=[LocalReplica(DeadPrefill(), "p0"),
                          LocalReplica(DeadPrefill(), "p1")])
    resp = sup.completions({"prompt": _prompt(3), "max_tokens": 2,
                            "request_id": "pf-outage"})
    assert resp["error"]["code"] == 503
    assert resp["error"]["type"] == "prefill_unavailable"
    assert core.healthy_count() == 2
    assert sup.failovers == 0 and core.ejected_count == 0


def test_prefill_app_error_propagates_without_retry_or_503():
    """A deterministic error raised BY prefill executing the request (a
    malformed prompt failing validation) would fail identically on every
    replica: it must surface to the client immediately — no walk of the
    prefill tier, no 503 masking, no decode-replica ejection."""
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.core.exceptions import TaskError

    class Decode:
        def engine_stats(self):
            return _stats2()[0]

        def handoff_address(self):
            return ["127.0.0.1", 9]

    calls = []

    class BadRequestPrefill:
        def __init__(self, tag):
            self.tag = tag

        def prefill(self, request, decode_address):
            calls.append(self.tag)
            # As the real actor-RPC boundary would deliver a replica-side
            # ValueError from _parse.
            raise TaskError("prefill", "ValueError: prompt must be token ids",
                            cause=ValueError("prompt must be token ids"))

    core = RouterCore(2, fail_threshold=1)
    sup = FleetSupervisor(
        core, [LocalReplica(Decode(), "d0"), LocalReplica(Decode(), "d1")],
        prefill_replicas=[LocalReplica(BadRequestPrefill("p0"), "p0"),
                          LocalReplica(BadRequestPrefill("p1"), "p1")])
    with pytest.raises(TaskError, match="prompt must be token ids"):
        sup.completions({"prompt": _prompt(3), "max_tokens": 2,
                         "request_id": "pf-bad-req"})
    assert calls == ["p0"]  # no pointless retry across the tier
    assert core.healthy_count() == 2
    assert sup.failovers == 0 and core.ejected_count == 0
    assert core._inflight == [0, 0]


def test_kv_recollect_counts_inflight_on_target():
    """Re-collecting a migrated stream is the TARGET's work: it must ride
    the target's in-flight counter while it runs so pow2 scoring sees the
    adopted load, and release it afterwards."""
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import SessionMigratedError

    core = RouterCore(2, fail_threshold=1)
    seen = []

    class Drained:
        def engine_stats(self):
            return _stats2()[0]

        def completions(self, request):
            raise SessionMigratedError(request["request_id"], "kv")

    class Adopter:
        def engine_stats(self):
            return _stats2()[0]

        def completions_collect(self, rid):
            seen.append(core._inflight[1])
            return {"choices": [{"token_ids": [7], "text": "",
                                 "finish_reason": "stop"}]}

    sup = FleetSupervisor(core, [LocalReplica(Drained(), "drained"),
                                 LocalReplica(Adopter(), "adopter")])
    sup._drain_target[0] = 1
    core._session_owner["kv-acct"] = 0
    resp = sup.completions({"prompt": _prompt(4), "max_tokens": 2,
                            "request_id": "kv-acct",
                            "session_id": "kv-acct"})
    assert resp["choices"][0]["token_ids"] == [7]
    assert seen == [1]                  # counted while the collect ran
    assert core._inflight == [0, 0]     # and released afterwards


# ---------------------------------------------------------------------------
# Live migration: mid-decode KV export -> adopt, zero re-prefill,
# zero pickling; edge cases (partial stream, completion race, dead target).
# ---------------------------------------------------------------------------


def _bg_collect(server, req):
    """Submit via a thread like a real consumer; returns the result box."""
    box = {}

    def run():
        try:
            box["resp"] = server.completions(dict(req))
        except Exception as e:
            box["exc"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    box["thread"] = t
    return box


def _wait_running(server, n=1, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if server.engine_stats()["running"] >= n:
            return True
        time.sleep(0.01)
    return False


def test_migrate_session_zero_reprefill_zero_pickle(setup, pickle_sanitizer):
    from ray_tpu.llm.serving import LLMServer

    src, dst = LLMServer(_cfg(setup)), LLMServer(_cfg(setup))
    req = {"prompt": _prompt(6, 33), "max_tokens": 32,
           "request_id": "mig-zero"}
    ref = LLMServer(_cfg(setup)).completions(dict(req))

    box = _bg_collect(src, req)
    assert _wait_running(src)
    dst_prefill_before = dst.engine_stats()["prefill_tokens_computed"]
    with pickle_sanitizer.window() as w:
        summary = src.migrate_sessions(dst.handoff_address())
    assert summary["migrated"] == ["mig-zero"], summary
    box["thread"].join(15)
    # The blocked consumer is told where its stream went, typed + modal.
    assert "SESSION_MIGRATED kv" in repr(box["exc"])

    resp = dst.completions_collect("mig-zero")
    assert resp["choices"][0]["token_ids"] == ref["choices"][0]["token_ids"]
    # Zero re-prefill: the adopted sequence resumed decode directly.
    assert dst.engine_stats()["prefill_tokens_computed"] \
        == dst_prefill_before
    # Zero pickling: state rides JSON control frames, pages ride raw
    # array frames; a regression is attributed to its call site by the
    # sanitizer (same discipline as the collective wire).
    w.assert_zero_pickle()
    assert w.counters["deserialize_fast"] >= 2, \
        w.counters  # k + v page streams
    # And the exporter released the migrated pages.
    s = src.engine_stats()
    assert s["free_kv_blocks"] == s["total_kv_blocks"], s


def test_partial_kv_stream_discarded_whole(setup):
    """A sender dying mid-stream must leave NOTHING adopted: no stream
    entry, no engine state, no leaked pages on the target."""
    import json as json_mod

    from ray_tpu.collective.cpu_group import _HDR
    from ray_tpu.llm.serving import LLMServer

    dst = LLMServer(_cfg(setup))
    rejected_before = dst._handoff.handoffs_rejected
    meta = {"id": "torn", "prompt": _prompt(7), "output": [1, 2], "seed": 3,
            "params": {"max_tokens": 8}, "migrated": True,
            "kv_dtype": "float32", "kv_shape": [2, 4, 8, 2, 4],
            "block_ids": [0, 1]}
    body = json_mod.dumps(meta).encode()
    with socket.create_connection(tuple(dst.handoff_address()),
                                  timeout=5) as sock:
        sock.sendall(_HDR.pack(len(body), 2) + body)
        # Announce a K-page array but die before the bytes arrive.
        sock.sendall(_HDR.pack(10_000, 1))
    deadline = time.monotonic() + 10
    while (dst._handoff.handoffs_rejected == rejected_before
           and time.monotonic() < deadline):
        time.sleep(0.02)
    assert dst._handoff.handoffs_rejected == rejected_before + 1
    assert dst._handoff.handoffs_adopted == 0
    assert "torn" not in dst._streams
    s = dst.engine_stats()
    assert s["running"] + s["waiting"] + s["prefilling"] == 0
    assert s["free_kv_blocks"] == s["total_kv_blocks"]


def test_migration_races_completion_exactly_once(setup):
    """A request finishing while the drain starts is delivered exactly
    once: either its last tick delivered it and the consumer gets a normal
    finished response, or the migration exported it — never migrated AND
    completed."""
    from ray_tpu.llm.serving import LLMServer

    src, dst = LLMServer(_cfg(setup)), LLMServer(_cfg(setup))
    results = []
    for trial in range(6):
        rid = f"race-{trial}"
        req = {"prompt": _prompt(trial, 13), "max_tokens": 3,
               "request_id": rid}
        ref = LLMServer.completions  # noqa: F841  (doc: same path below)
        box = _bg_collect(src, req)
        # No barrier on purpose: across trials the drain lands at varying
        # points of this short request's life (queued, decoding, already
        # done).
        summary = src.migrate_sessions(dst.handoff_address())
        src._draining = False  # re-arm for the next trial
        box["thread"].join(15)
        placed = ([rid] == summary["migrated"]) + \
            ([rid] == summary["replayed"])
        done_at_src = "resp" in box
        if done_at_src:
            # Completed at the source: must NOT also have been exported.
            assert summary["migrated"] == [] and summary["replayed"] == []
            outcome = "finished"
        else:
            assert placed == 1, (summary, box)
            if summary["migrated"]:
                resp = dst.completions_collect(rid)
                outcome = "migrated"
            else:
                resp = dst.completions(dict(req))
                outcome = "replayed"
            ref_resp = LLMServer(_cfg(setup)).completions(dict(req)) \
                if trial == 0 else None
            if ref_resp is not None:
                assert resp["choices"][0]["token_ids"] \
                    == ref_resp["choices"][0]["token_ids"]
        results.append(outcome)
    # The race existed: not every trial resolved the same way, or at least
    # every trial resolved to exactly one delivery (asserted above).
    assert len(results) == 6


def test_target_dead_mid_migration_falls_back_to_replay(setup):
    """Whole-stream-or-discard: a dead target demotes every session to the
    replay path, and the seeded replay from the prompt is still identical."""
    from ray_tpu.llm.serving import LLMServer

    src = LLMServer(_cfg(setup))
    req = {"prompt": _prompt(8, 21), "max_tokens": 24,
           "request_id": "dead-target"}
    ref = LLMServer(_cfg(setup)).completions(dict(req))

    box = _bg_collect(src, req)
    assert _wait_running(src)
    # A dead port: connect refused -> migrate_session raises per session.
    sink = socket.socket()
    sink.bind(("127.0.0.1", 0))
    dead_addr = list(sink.getsockname())
    sink.close()
    summary = src.migrate_sessions(dead_addr, timeout=2.0)
    assert summary["migrated"] == [] and summary["replayed"] \
        == ["dead-target"]
    box["thread"].join(15)
    assert "SESSION_MIGRATED replay" in repr(box["exc"])

    healthy = LLMServer(_cfg(setup))
    resp = healthy.completions(dict(req))
    assert resp["choices"][0]["token_ids"] == ref["choices"][0]["token_ids"]


def test_draining_replica_rejects_new_admissions(setup):
    from ray_tpu.llm.serving import LLMServer, ReplicaDrainingError

    srv = LLMServer(_cfg(setup))
    srv.migrate_sessions(("127.0.0.1", 1))  # no sessions; flips draining
    with pytest.raises(ReplicaDrainingError, match="REPLICA_DRAINING"):
        srv.completions({"prompt": _prompt(9), "max_tokens": 2})
    assert srv.engine_stats()["draining"] is True


# ---------------------------------------------------------------------------
# Supervisor drain path end to end: the ROUTER moves the session and the
# client's in-flight call transparently resumes at the target.
# ---------------------------------------------------------------------------


def test_supervisor_drain_migrates_and_client_never_notices(
        setup, captured_events):
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import LLMServer
    from ray_tpu.runtime import events

    a, b = LLMServer(_cfg(setup)), LLMServer(_cfg(setup))
    req = {"prompt": _prompt(10, 33), "max_tokens": 48,
           "request_id": "drain-e2e", "session_id": "de"}
    ref = LLMServer(_cfg(setup)).completions(dict(req))

    core = RouterCore(2, fail_threshold=1)
    sup = FleetSupervisor(core, [LocalReplica(a, "a"), LocalReplica(b, "b")])
    core._session_owner["de"] = 0

    box = {}

    def client():
        box["resp"] = sup.completions(dict(req))

    t = threading.Thread(target=client, daemon=True)
    t.start()
    assert _wait_running(a)
    b_prefill_before = b.engine_stats()["prefill_tokens_computed"]
    summary = sup.drain_replica(0, reason="test-drain")
    assert summary["migrated"] == ["drain-e2e"] and summary["target"] == 1
    t.join(20)

    # The client saw ONE completed, identical response — no error, despite
    # its replica draining away mid-generation.
    resp = box["resp"]
    assert "error" not in resp
    assert resp["choices"][0]["token_ids"] == ref["choices"][0]["token_ids"]
    # Zero re-prefill on the adoptive replica, affinity remapped, metrics +
    # event emitted, and no failover was charged (planned move, not crash).
    assert b.engine_stats()["prefill_tokens_computed"] == b_prefill_before
    assert core._session_owner["de"] == 1
    assert sup.migrated_sessions == 1 and sup.failovers == 0
    assert any(e["type"] == events.LLM_SESSION_MIGRATED
               for e in captured_events)
    assert not core.is_routable(0) and core.is_healthy(0)


def test_drain_send_failure_aborts_potential_orphan_on_target():
    """A migration send that failed with a lost ack may have left the
    session fully adopted on the target (decoding with no consumer, KV
    pinned) while the router replays it from the prompt: the supervisor
    best-effort aborts those rids on the target before the replay."""
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore

    aborted = []

    class Drainee:
        def engine_stats(self):
            return _stats2()[0]

        def migrate_sessions(self, target_address):
            return {"migrated": [], "replayed": ["lost-ack"],
                    "send_failed": ["lost-ack"]}

    class Target:
        def engine_stats(self):
            return _stats2()[0]

        def handoff_address(self):
            return ["127.0.0.1", 9]

        def abort(self, rid):
            aborted.append(rid)
            return True

    core = RouterCore(2)
    sup = FleetSupervisor(core, [LocalReplica(Drainee(), "drainee"),
                                 LocalReplica(Target(), "target")])
    summary = sup.drain_replica(0, reason="lost-ack-test")
    assert summary["target"] == 1 and summary["replayed"] == ["lost-ack"]
    assert aborted == ["lost-ack"]


# ---------------------------------------------------------------------------
# Replica policy + scale-down-as-drain.
# ---------------------------------------------------------------------------


def test_replica_policy_watermarks_and_quiet_period():
    from ray_tpu.llm.replica_policy import ReplicaPolicy, ReplicaPolicyConfig

    pol = ReplicaPolicy(ReplicaPolicyConfig(
        min_replicas=1, max_replicas=4, kv_pressure_high=0.85,
        kv_pressure_low=0.5, scale_down_quiet_s=10.0, cooldown_s=0.0))

    def stats(free, depth=0):
        return [{"free_kv_blocks": free, "total_kv_blocks": 100,
                 "waiting": depth, "prefilling": 0,
                 "queued_prefill_tokens": depth * 64,
                 "tokens_per_s": 100.0}]

    # Hot KV -> scale up; capped at max.
    assert pol.desired(stats(free=5), 2, now=0.0) == 3
    assert pol.desired(stats(free=5), 4, now=1.0) == 4
    # Quiet must be SUSTAINED: below-low samples start the clock, a busy
    # sample resets it, and only a full quiet run shrinks the fleet.
    assert pol.desired(stats(free=90), 3, now=10.0) == 3
    assert pol.desired(stats(free=90), 3, now=15.0) == 3
    assert pol.desired(stats(free=5), 3, now=18.0) == 4     # busy: resets
    assert pol.desired(stats(free=90), 4, now=20.0) == 4
    assert pol.desired(stats(free=90), 4, now=31.0) == 3    # 10s quiet
    # Never below min; blind ticks (no stats) never act.
    assert pol.desired(stats(free=90), 1, now=100.0) == 1
    assert pol.desired([None], 3, now=200.0) == 3


def test_scale_down_drains_least_loaded_then_retires(setup, captured_events):
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import LLMServer
    from ray_tpu.runtime import events

    class ShrinkPolicy:
        def desired(self, stats, current, now):
            return current - 1

    servers = [LLMServer(_cfg(setup)) for _ in range(3)]
    retired = []
    core = RouterCore(3, fail_threshold=1)
    sup = FleetSupervisor(
        core, [LocalReplica(s, f"r{i}") for i, s in enumerate(servers)],
        policy=ShrinkPolicy(), retire_fn=retired.append)

    # Sustained load on replicas 0 and 1; replica 2 idles -> the victim.
    stop = threading.Event()
    failures = []

    def pressure(server, seed):
        while not stop.is_set():
            try:
                resp = server.completions(
                    {"prompt": _prompt(seed, 33), "max_tokens": 16})
                assert "choices" in resp
            except Exception as e:
                failures.append(e)
                return

    threads = [threading.Thread(target=pressure, args=(servers[i], s),
                                daemon=True)
               for i, s in ((0, 11), (0, 12), (1, 13), (1, 14))]
    for t in threads:
        t.start()
    assert _wait_running(servers[0]) and _wait_running(servers[1])

    action = sup.scale_tick()
    assert action == {"direction": "down", "from": 3, "to": 2,
                      "victim": 2, "drain": action["drain"]}
    assert retired == [2]
    assert not core.is_healthy(2)            # slot retired
    assert core.is_routable(0) and core.is_routable(1)
    types = [e["type"] for e in captured_events]
    assert events.LLM_REPLICAS_SCALED in types
    # Planned retirement: no shed, no crash-flavored events, and the loaded
    # replicas' requests never noticed.
    assert events.LLM_REQUEST_SHED not in types
    assert events.LLM_REPLICA_EJECTED not in types
    stop.set()
    for t in threads:
        t.join(30)
    # The loaded replicas' requests never noticed the retirement.
    assert not failures, failures[:2]


def test_scale_up_calls_through_and_emits(captured_events):
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.runtime import events

    class GrowPolicy:
        def desired(self, stats, current, now):
            return current + 2

    class Idle:
        def engine_stats(self):
            return _stats2()[0]

    grown = []
    core = RouterCore(1)
    sup = FleetSupervisor(core, [LocalReplica(Idle(), "r0")],
                          policy=GrowPolicy(), scale_up_fn=grown.append)
    action = sup.scale_tick()
    assert action == {"direction": "up", "from": 1, "to": 3}
    assert grown == [2]
    assert any(e["type"] == events.LLM_REPLICAS_SCALED
               and e["labels"]["direction"] == "up"
               for e in captured_events)
    # New capacity arrives as fresh append-only slots.
    idx = sup.add_replica(LocalReplica(Idle(), "r1"))
    assert idx == 1 and core.routable_count() == 2


def test_node_events_drive_drain_and_eject(setup):
    """The drain plane joined to the fleet: NODE_DRAINING drains the
    replicas whose engine_stats report that node; NODE_DEAD ejects them."""
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import LLMServer
    from ray_tpu.runtime import events

    a, b, c = (LLMServer(_cfg(setup)) for _ in range(3))
    node_of = {id(a): "aa" * 16, id(b): "bb" * 16, id(c): "cc" * 16}

    class NodeBound:
        def __init__(self, server):
            self._server = server

        def __getattr__(self, name):
            return getattr(self._server, name)

        def engine_stats(self):
            s = self._server.engine_stats()
            s["node_id"] = node_of[id(self._server)]
            return s

    core = RouterCore(3, fail_threshold=1)
    sup = FleetSupervisor(core, [LocalReplica(NodeBound(s), n)
                                 for s, n in ((a, "a"), (b, "b"), (c, "c"))])
    sup.fresh_stats(force=True)              # learn the node map

    feed = []
    handled = sup.check_events(list_events_fn=lambda limit: feed)
    assert handled == 0
    # Historical events (stamped before the supervisor existed) are never
    # replayed: a node that drained and recovered before this router
    # started must not drain the healthy replicas living there now.
    feed = [{"type": events.NODE_DEAD, "node_id": "cc" * 16, "time": 1.0}]
    assert sup.check_events(list_events_fn=lambda limit: feed) == 0
    assert core.is_routable(2)
    now = time.time()
    feed = [{"type": events.NODE_DRAINING, "node_id": "aa" * 16,
             "time": now + 1.0},
            {"type": events.NODE_DEAD, "node_id": "bb" * 16,
             "time": now + 2.0}]
    assert sup.check_events(list_events_fn=lambda limit: feed) == 2
    assert not core.is_routable(0) and core.is_healthy(0)   # draining
    assert not core.is_healthy(1)                            # dead
    assert core.is_routable(2)
    # Stale events never re-fire (the since-cursor advanced).
    assert sup.check_events(list_events_fn=lambda limit: feed) == 0


def test_resilience_metrics_roll_into_state_summary(setup):
    """ray_tpu_llm_failovers_total / _sessions_migrated_total /
    _replicas_healthy ride the generic llm_serving rollup
    (state.summary()["llm_serving"]) with no rollup-side changes."""
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.runtime import metric_defs as md
    from ray_tpu.state.api import _aggregate_llm_metrics

    class Idle:
        def engine_stats(self):
            return _stats2()[0]

    core = RouterCore(2)
    FleetSupervisor(core, [LocalReplica(Idle(), "x"),
                           LocalReplica(Idle(), "y")],
                    deployment="rollup-test")
    md.LLM_FAILOVERS.inc(tags={"deployment": "rollup-test"})
    md.LLM_SESSIONS_MIGRATED.inc(2, tags={"deployment": "rollup-test"})

    # The per-deployment series landed...
    assert any("rollup-test" in k and v == 2.0
               for k, v in md.LLM_REPLICAS_HEALTHY.snapshot()
               ["values"].items())
    # ...and the generic llm_serving aggregation picks all three up
    # (sums across every deployment/process; other tests in this run may
    # have contributed, so bounds, not equality).
    agg = _aggregate_llm_metrics([[m.snapshot() for m in md.ALL_METRICS]])
    assert agg["replicas_healthy"] >= 2.0
    assert agg["failovers_total"] >= 1.0
    assert agg["sessions_migrated_total"] >= 2.0


# ---------------------------------------------------------------------------
# Chaos: a real cluster's drain plane churns the fleet under load.
# ---------------------------------------------------------------------------


def _run_churn(setup, *, duration_s, notice_s, n_requests):
    """Shared body for the chaos churn test and the slow sweep: three
    'nodes' in a real Cluster each carry one in-process replica; the
    PreemptionKiller outright-kills one node and drains another with
    notice, while client threads sustain mixed load through the
    FleetSupervisor. Returns (responses, sup, servers, ref_fn)."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core import serialization as _ser
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import LLMServer
    from ray_tpu.state import list_cluster_events
    from ray_tpu.util.fault_injection import PreemptionKiller

    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=2)  # head (never a victim)
        nodes = [cluster.add_node(num_cpus=1) for _ in range(3)]
        ray_tpu.init(address=cluster.address)
        cluster.wait_for_nodes(4)

        servers = [LLMServer(_cfg(setup, num_kv_blocks=128))
                   for _ in range(3)]

        class NodeBound:
            """In-process replica pinned to a cluster node: calls fail if
            the node is down at call START or END (an actor RPC in flight
            when its node dies errors even though the work ran), and
            engine_stats reports the node id so drain events map here."""

            def __init__(self, server, node):
                self._server = server
                self._node = node

            def _dead(self):
                return self._node.proc.poll() is not None

            def __getattr__(self, name):
                if self._dead():
                    raise ConnectionError("replica node is dead")
                real = getattr(self._server, name)
                if not callable(real):
                    return real

                def guarded(*a, **kw):
                    out = real(*a, **kw)
                    if self._dead():
                        raise ConnectionError("replica node died mid-call")
                    return out

                return guarded

            def engine_stats(self):
                if self._dead():
                    raise ConnectionError("replica node is dead")
                s = self._server.engine_stats()
                s["node_id"] = self._node.node_id.hex()
                return s

        core = RouterCore(3, fail_threshold=1)
        sup = FleetSupervisor(
            core, [LocalReplica(NodeBound(s, n), f"replica-{i}")
                   for i, (s, n) in enumerate(zip(servers, nodes))])
        sup.fresh_stats(force=True)

        # Activity log: every drain/eject with its outcome, so a failed
        # invariant names what the supervisor actually did.
        sup.activity = []
        _drain0, _eject0 = sup.drain_replica, sup.eject_replica

        def _drain(idx, **kw):
            out = _drain0(idx, **kw)
            sup.activity.append(("drain", idx, kw.get("reason"), out))
            return out

        def _eject(idx, **kw):
            out = _eject0(idx, **kw)
            sup.activity.append(("eject", idx, kw.get("reason"), out))
            return out

        sup.drain_replica, sup.eject_replica = _drain, _eject

        # The router's control loop, inlined: poll the REAL drain plane.
        stop = threading.Event()

        def control():
            while not stop.is_set():
                try:
                    sup.check_events(
                        lambda limit: list_cluster_events(limit=limit))
                except Exception:
                    pass
                time.sleep(0.2)

        ctrl = threading.Thread(target=control, daemon=True)
        ctrl.start()

        # Sustained mixed load: short + long prompts, sessions, sampled +
        # greedy, every request router-named for replay identity.
        responses = {}
        errors = []
        ser_before = _ser.counter_snapshot()

        def make_req(i):
            req = {"prompt": _prompt(i % 7, 13 + 8 * (i % 3)),
                   "max_tokens": 8 + 8 * (i % 2),
                   "request_id": f"churn-{i}",
                   "session_id": f"sess-{i % 5}"}
            if i % 3 == 0:
                req.update(temperature=0.7, top_k=16)
            return req

        def client(lo, hi):
            for i in range(lo, hi):
                try:
                    responses[i] = sup.completions(make_req(i))
                except Exception as e:  # a client-visible error = failure
                    errors.append((i, e))
                time.sleep(duration_s / max(hi - lo, 1) * 0.5)

        n_threads = 4
        per = n_requests // n_threads
        clients = [threading.Thread(target=client,
                                    args=(t * per, (t + 1) * per),
                                    daemon=True)
                   for t in range(n_threads)]
        for t in clients:
            t.start()

        # Pinned pressure: sessions stuck to the victim replicas keep a
        # request in flight on each at the moment the chaos lands, so the
        # kill deterministically exercises failover and the drain
        # deterministically catches live sessions to migrate.
        core._session_owner["pin-kill"] = 0
        core._session_owner["pin-drain"] = 1
        pin_stop = threading.Event()
        seq = iter(range(1_000_000))

        def pinned(session):
            while not pin_stop.is_set():
                i = next(seq)
                try:
                    r = sup.completions(
                        {"prompt": _prompt(i % 5, 21), "max_tokens": 48,
                         "request_id": f"pin-{session}-{i}",
                         "session_id": session})
                    if "error" in r:
                        errors.append((f"pin-{session}-{i}", r))
                except Exception as e:
                    errors.append((f"pin-{session}-{i}", e))

        pins = [threading.Thread(target=pinned, args=(s,), daemon=True)
                for s in ("pin-kill", "pin-kill", "pin-drain", "pin-drain")]
        for t in pins:
            t.start()

        time.sleep(duration_s * 0.2)  # let load establish
        killer_hard = PreemptionKiller(cluster, notice_s=0.0, respawn=False,
                                       node_filter=lambda n: n in nodes)
        killer_soft = PreemptionKiller(cluster, notice_s=notice_s,
                                       respawn=False,
                                       node_filter=lambda n: n in nodes)
        assert killer_hard.strike(node=nodes[0].node_id.hex()) is not None
        time.sleep(1.0)  # let the dead-node event eject replica 0
        assert killer_soft.strike(node=nodes[1].node_id.hex()) is not None

        # Keep the pinned pressure up until the drain has been handled.
        deadline = time.monotonic() + notice_s
        while time.monotonic() < deadline and core.is_routable(1):
            time.sleep(0.1)
        time.sleep(0.5)
        pin_stop.set()
        for t in pins:
            t.join(30)
        for t in clients:
            t.join(duration_s * 4 + 60)
        stop.set()
        ctrl.join(5)
        killer_hard.stop()
        killer_soft.stop()
        ser_delta = _ser.counter_delta(ser_before)
        return responses, errors, sup, core, ser_delta, n_requests
    finally:
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
        cluster.shutdown()


@pytest.mark.chaos
def test_churn_kill_and_drain_under_load(setup):
    """One replica node dies outright, another drains with notice, under
    sustained mixed load: every request completes exactly once with no
    client-visible error, drained sessions moved with their KV, and the
    steady state moved zero pickled bytes."""
    from ray_tpu.runtime import metric_defs as md

    shed_before = sum(md.LLM_ROUTER_SHED.snapshot()["values"].values())
    # notice_s is generous here because this test REQUIRES the migration
    # to win the race against the drain deadline (migrated_sessions >= 1):
    # on a contended 1-core CI box, engine loops + fresh XLA compiles can
    # stretch migrate_sessions past a tight notice, and the deadline kill
    # landing mid-drain flips sessions to the (also-correct) replay path.
    # The slow sweep keeps the tight 8s notice — there the deadline kill
    # racing the drain is exactly the churn we want.
    responses, errors, sup, core, ser_delta, n = _run_churn(
        setup, duration_s=6.0, notice_s=20.0, n_requests=24)

    assert not errors, errors[:3]
    assert len(responses) == n                       # exactly once, all n
    for i, resp in responses.items():
        assert "error" not in resp, (i, resp)
        assert resp["choices"][0]["token_ids"], (i, resp)
    # The hard kill forced failovers; the drain caught live pinned
    # sessions and moved them with their KV.
    assert sup.failovers >= 1, sup.activity
    assert sup.migrated_sessions >= 1, sup.activity
    assert core.ejected_count >= 1, sup.activity
    assert core.healthy_count() >= 1
    # What must NOT happen under planned churn: shedding or drops.
    shed_after = sum(md.LLM_ROUTER_SHED.snapshot()["values"].values())
    assert shed_after == shed_before
    # Zero-pickle steady state: router + migration moved no pickled bytes.
    assert ser_delta["pickle"] == 0, ser_delta

    # Seeded replay identity spot-check: re-run a handful of the churned
    # requests on a fresh replica; same request_id -> same tokens, even
    # for the sampled ones.
    from ray_tpu.llm.serving import LLMServer

    fresh = LLMServer(_cfg(setup, num_kv_blocks=128))
    for i in list(responses)[:3]:
        req = {"prompt": _prompt(i % 7, 13 + 8 * (i % 3)),
               "max_tokens": 8 + 8 * (i % 2), "request_id": f"churn-{i}"}
        if i % 3 == 0:
            req.update(temperature=0.7, top_k=16)
        again = fresh.completions(req)
        assert again["choices"][0]["token_ids"] \
            == responses[i]["choices"][0]["token_ids"], i


@pytest.mark.chaos
@pytest.mark.slow
def test_churn_sweep_sustained(setup):
    """The long sweep: more load, longer window, same invariants."""
    responses, errors, sup, core, ser_delta, n = _run_churn(
        setup, duration_s=25.0, notice_s=8.0, n_requests=96)
    assert not errors, errors[:3]
    assert len(responses) == n
    assert all("error" not in r for r in responses.values())
    assert sup.failovers >= 1 and core.ejected_count >= 1
    assert ser_delta["pickle"] == 0, ser_delta
