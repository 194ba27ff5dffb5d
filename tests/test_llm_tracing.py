"""Per-request serving traces: end-to-end latency attribution that stays
stitched across handoff, failover, and migration (ISSUE acceptance).

The stitching invariant under test: the trace id is a pure function of the
request id (util/tracing.request_trace_id), so spans recorded by ANY
process — router, prefill tier, decode replica, migration source — join
one trace without trace context ever riding a pickled RPC. The only wire
bytes are the typed KVHandoffMsg's trace_id/parent_span_id raw-frame
fields, carried so the receiver's adopt span parent-links to the sender's
handoff span; the pickle sanitizer window proves the discipline held.

All coverage is cluster-free (LLMServer + PrefillServer + FleetSupervisor
run in-process), so every fault shape runs at unit-test cost.
"""

import json
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


@pytest.fixture(autouse=True)
def tracing_on():
    """Force tracing on for this module regardless of what another test
    (e.g. the microbenchmark's untraced leg) left behind."""
    from ray_tpu.util import tracing

    was = tracing.enabled()
    tracing.set_enabled(True)
    yield
    tracing.set_enabled(was)


def _trace(rid):
    from ray_tpu.state import api

    return api.request_trace(rid)


def _by_name(trace):
    out = {}
    for s in trace["spans"]:
        out.setdefault(s["name"], []).append(s)
    return out


# ---------------------------------------------------------------------------
# router -> engine lifecycle: one trace, parent-linked, decomposed
# ---------------------------------------------------------------------------


def test_request_trace_stitches_router_and_engine(setup, capsys, tmp_path):
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import LLMServer
    from ray_tpu.util import tracing

    rid = "trace-plain"
    server = LLMServer(_cfg(setup))
    sup = FleetSupervisor(RouterCore(1, block_size=8),
                          [LocalReplica(server, "r0")])
    resp = sup.completions({"prompt": _prompt(1, 21), "max_tokens": 6,
                            "request_id": rid})
    assert "error" not in resp, resp

    tr = _trace(rid)
    assert tr["trace_id"] == tracing.request_trace_id(rid).hex()
    names = _by_name(tr)
    # Router owns the root; admission, prefill, and decode are children of
    # the same trace (queue may be ~0-length and skipped — not asserted).
    for required in ("llm:request", "llm:admit", "llm:prefill", "llm:decode"):
        assert required in names, (required, sorted(names))
    root = names["llm:request"][0]
    assert "parent_span_id" not in root["args"]
    assert root["args"]["request_id"] == rid
    # llm:admit was recorded inside the root span's thread context.
    assert names["llm:admit"][0]["args"]["parent_span_id"] \
        == root["args"]["span_id"]
    assert names["llm:admit"][0]["args"]["admitted"] is True
    # Spans come back sorted by wall-clock start.
    ts = [s["ts"] for s in tr["spans"]]
    assert ts == sorted(ts)
    # The decode span carries the full breakdown as attributes.
    dec = names["llm:decode"][0]["args"]
    assert dec["tokens"] == 6 and "queue_s" in dec and "prefill_s" in dec

    # Flight recorder: the ticks that emitted this request's tokens are
    # attributable (batch composition + duration per tick).
    recs = server.flight_records(request_id=rid)
    assert recs and all("dur_ms" in r and rid in r["emitted"] for r in recs)
    assert server.engine_stats()["tick_records"] >= len(recs)

    # CLI surfacing: `scripts request <rid>` renders the local-ring trace
    # and --chrome exports a chrome://tracing file.
    from ray_tpu import scripts

    capsys.readouterr()
    chrome = tmp_path / "trace.json"
    scripts.main(["request", rid, "--chrome", str(chrome)])
    out = capsys.readouterr().out
    assert "llm:request" in out and "llm:decode" in out
    assert tr["trace_id"] in out
    dumped = json.loads(chrome.read_text())
    assert any(e["name"] == "llm:request" for e in dumped["traceEvents"])

    # Unknown request: empty trace, not an error.
    assert _trace("no-such-rid")["spans"] == []


def test_breakdown_metrics_roll_up_per_phase(setup):
    """ttft/itl breakdown histograms are observed per phase at finish, and
    the summary() rollup reports per-phase mean ms — not a meaningless sum
    of means across phases."""
    from ray_tpu.llm.serving import LLMServer
    from ray_tpu.runtime import metric_defs
    from ray_tpu.state.api import _aggregate_llm_metrics

    LLMServer(_cfg(setup)).completions(
        {"prompt": _prompt(2, 21), "max_tokens": 4, "request_id": "bd-1"})

    snap = metric_defs.LLM_TTFT_BREAKDOWN_MS.snapshot()
    phases = {dict(json.loads(k)).get("phase")
              for k in snap["histograms"]}
    assert {"queue", "prefill"} <= phases

    out = _aggregate_llm_metrics([[snap,
                                   metric_defs.LLM_ITL_BREAKDOWN_MS.snapshot()]])
    assert "ttft_breakdown_ms" in out and "itl_breakdown_ms" in out
    assert out["ttft_breakdown_ms"]["prefill"] > 0
    assert "decode" in out["itl_breakdown_ms"]
    # The phase map replaced the generic sum: no scalar leaked through.
    assert not isinstance(out["ttft_breakdown_ms"], float)


# ---------------------------------------------------------------------------
# disagg prefill -> decode: trace continuity across the raw-frame wire
# ---------------------------------------------------------------------------


def test_disagg_handoff_trace_stitched_zero_pickle(setup, pickle_sanitizer):
    from ray_tpu.llm.disagg import PrefillServer
    from ray_tpu.llm.serving import LLMServer

    rid = "trace-disagg"
    decode = LLMServer(_cfg(setup, disaggregate=1))
    prefill = PrefillServer(_cfg(setup))
    req = {"prompt": _prompt(3, 21), "max_tokens": 6, "request_id": rid}

    w = pickle_sanitizer.window()
    with w:
        res = prefill.prefill(req, decode.handoff_address())
        assert res["handoff"] and res["ack"]["ok"]
        out = decode.completions_collect(rid)
    assert len(out["choices"][0]["token_ids"]) == 6
    # Trace context rode the typed KVHandoffMsg raw frame — zero pickle.
    w.assert_zero_pickle()

    names = _by_name(_trace(rid))
    for required in ("llm:prefill", "llm:kv_handoff", "llm:kv_adopt",
                     "llm:decode"):
        assert required in names, (required, sorted(names))
    handoff = names["llm:kv_handoff"][0]["args"]
    adopt = names["llm:kv_adopt"][0]["args"]
    # The receiver's adopt span parent-links to the sender's handoff span:
    # the one cross-process edge, carried by the wire message itself.
    assert adopt["parent_span_id"] == handoff["span_id"]
    assert adopt["trace_id"] == handoff["trace_id"]
    assert not adopt["migrated"] and handoff["bytes"] > 0
    # Prefill happened on the prefill tier; the decode engine must not
    # have double-recorded it for the adopted request.
    assert names["llm:prefill"][0]["args"]["tier"] == "prefill"
    assert len(names["llm:prefill"]) == 1
    # No dangling time: decode starts after the prefill span started.
    assert names["llm:decode"][0]["ts"] >= names["llm:prefill"][0]["ts"]


# ---------------------------------------------------------------------------
# failover: the replay attempt is a named span in the same trace
# ---------------------------------------------------------------------------


class _FlakyReplica:
    def __init__(self, server, fails=1):
        self._server = server
        self._fails = fails

    def __getattr__(self, name):
        return getattr(self._server, name)

    def completions(self, request):
        if self._fails > 0:
            self._fails -= 1
            raise ConnectionError("replica connection lost")
        return self._server.completions(request)


def test_failover_replay_span_in_trace(setup):
    from ray_tpu.llm.router import FleetSupervisor, LocalReplica, RouterCore
    from ray_tpu.llm.serving import LLMServer

    rid = "trace-failover"
    core = RouterCore(2, fail_threshold=1)
    sup = FleetSupervisor(core, [
        LocalReplica(_FlakyReplica(LLMServer(_cfg(setup))), "victim"),
        LocalReplica(LLMServer(_cfg(setup)), "survivor")])
    core._session_owner["fo"] = 0  # deterministic first pick: the victim
    resp = sup.completions({"prompt": _prompt(4, 21), "max_tokens": 5,
                            "request_id": rid, "session_id": "fo"})
    assert "error" not in resp, resp
    assert sup.failovers == 1

    names = _by_name(_trace(rid))
    # The failed attempt is attributed inside the request's own trace —
    # TTFT inflation from a replica death is no longer unexplained.
    assert "llm:failover_replay" in names, sorted(names)
    fo = names["llm:failover_replay"][0]["args"]
    assert fo["replica"] == "0" and fo["error"] == "ConnectionError"
    assert fo["parent_span_id"] \
        == names["llm:request"][0]["args"]["span_id"]
    # The replay's engine lifecycle landed in the same trace too.
    assert "llm:decode" in names
    assert names["llm:decode"][0]["ts"] \
        >= names["llm:failover_replay"][0]["ts"]


# ---------------------------------------------------------------------------
# live migration: the pause is a first-class span, not a silent gap
# ---------------------------------------------------------------------------


def _bg_collect(server, req):
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


def test_migration_pause_is_a_span_not_a_gap(setup):
    from ray_tpu.llm.serving import LLMServer

    rid = "trace-mig"
    src, dst = LLMServer(_cfg(setup)), LLMServer(_cfg(setup))
    req = {"prompt": _prompt(5, 33), "max_tokens": 32, "request_id": rid}
    box = _bg_collect(src, req)
    assert _wait_running(src)
    summary = src.migrate_sessions(dst.handoff_address())
    box["thread"].join(15)
    if summary["migrated"] != [rid]:
        pytest.skip(f"request raced migration to completion: {summary}")
    resp = dst.completions_collect(rid)
    assert len(resp["choices"][0]["token_ids"]) == 32

    names = _by_name(_trace(rid))
    for required in ("llm:migration_pause", "llm:kv_handoff",
                     "llm:kv_adopt", "llm:decode"):
        assert required in names, (required, sorted(names))
    pause = names["llm:migration_pause"][0]
    assert pause["args"]["mode"] == "kv" and pause["dur"] > 0
    # The adopt side of the migration still parent-links across the wire.
    assert names["llm:kv_adopt"][0]["args"]["migrated"] is True
    assert names["llm:kv_adopt"][0]["args"]["parent_span_id"] \
        == names["llm:kv_handoff"][0]["args"]["span_id"]
    # "Not a gap": the decode span on the adopter books the pause into
    # stall_s instead of letting it masquerade as decode time.
    # Both run from the same stamp (the export's `t_handoff`) on one clock:
    # the adopter books up to its adoption, the pause ends at the source
    # when the adoption's acknowledgement is back, so the first lies inside
    # the second. How far inside is the machine's business (the adopter's
    # scatter compiles after its stamp: seconds on a loaded host), which is
    # why no distance between the two readings is held here.
    dec = names["llm:decode"][0]["args"]
    pause_s = pause["dur"] / 1e6
    assert 0 < dec["stall_s"] <= pause_s + 1e-5
    assert pause["ts"] / 1e6 == pytest.approx(
        next(r["t"] for r in src.flight_records()
             if r.get("kind") == "migration_pause"), abs=1e-5)
    # The source's flight recorder kept the synthetic pause record.
    assert any(r.get("kind") == "migration_pause"
               and r.get("request_id") == rid
               for r in src.flight_records())


# ---------------------------------------------------------------------------
# lifecycle spans are written when they END (PR 37): a request that is still
# decoding, or is aborted later, has its first two
# ---------------------------------------------------------------------------


def _engine_spans(rid):
    from ray_tpu.util import tracing

    out = {}
    for s in tracing.get_spans():
        if s["name"].startswith("llm:") and s["args"].get(
                "request_id") == rid:
            out.setdefault(s["name"], []).append(s)
    return out


def _until_first_token(engine, rid):
    for _ in range(64):
        if any(o.request_id == rid and o.new_token_ids
               for o in engine.step()):
            return
    raise AssertionError(f"{rid} got no token")


def _ttft_observed(phase):
    """Observations of one phase of ray_tpu_llm_ttft_breakdown_ms so far."""
    from ray_tpu.runtime import metric_defs

    snap = metric_defs.LLM_TTFT_BREAKDOWN_MS.snapshot()
    return sum(h["count"] for k, h in snap["histograms"].items()
               if dict(json.loads(k)).get("phase") == phase)


def _bare_engine(setup, **kw):
    from ray_tpu.llm.serving import build_engine

    return build_engine(_cfg(setup), **kw)


def test_a_request_still_decoding_has_its_queue_and_prefill_spans(setup):
    from ray_tpu.llm.engine import PREFILL_SPAN_ARGS
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.util import tracing

    rid = "span-early"
    engine = _bare_engine(setup)
    before = {p: _ttft_observed(p) for p in ("queue", "prefill")}
    engine.add_request(_prompt(6, 21), SamplingParams(max_tokens=12),
                       request_id=rid)
    engine.step()
    # admitted, no token yet: the queue span is there already
    assert engine.prefilling and sorted(_engine_spans(rid)) == ["llm:queue"]
    _until_first_token(engine, rid)
    assert engine.has_unfinished()
    spans = _engine_spans(rid)
    assert sorted(spans) == ["llm:prefill", "llm:queue"]
    queue, prefill = spans["llm:queue"][0], spans["llm:prefill"][0]
    # the same arguments and trace id as when both were written at the end
    assert set(prefill["args"]) >= {"request_id", "tokens",
                                    *PREFILL_SPAN_ARGS}
    assert prefill["args"]["tokens"] == 21 and prefill["args"]["slices"] == 3
    assert "tier" not in prefill["args"]
    for span in (queue, prefill):
        assert span["args"]["trace_id"] == tracing.request_trace_id(rid).hex()
    assert queue["ts"] + queue["dur"] == pytest.approx(prefill["ts"], abs=2)
    # the ttft histogram does not wait for the last token either
    assert {p: _ttft_observed(p) - before[p] for p in before} == {
        "queue": 1, "prefill": 1}
    while engine.has_unfinished():
        engine.step()
    spans = _engine_spans(rid)
    assert {k: len(v) for k, v in spans.items()} == {
        "llm:queue": 1, "llm:prefill": 1, "llm:decode": 1}
    dec = spans["llm:decode"][0]
    assert dec["args"]["tokens"] == 12 and dec["ts"] == pytest.approx(
        prefill["ts"] + prefill["dur"], abs=2)
    assert dec["args"]["prefill_s"] == pytest.approx(prefill["dur"] / 1e6,
                                                     abs=1e-4)
    assert {p: _ttft_observed(p) - before[p] for p in before} == {
        "queue": 1, "prefill": 1}


def test_an_aborted_request_keeps_its_first_two_spans(setup):
    from ray_tpu.llm.sampling import SamplingParams

    rid = "span-aborted"
    engine = _bare_engine(setup)
    engine.add_request(_prompt(7, 21), SamplingParams(max_tokens=50),
                       request_id=rid)
    _until_first_token(engine, rid)
    assert engine.abort_request(rid)
    while engine.has_unfinished():
        engine.step()
    assert {k: len(v) for k, v in _engine_spans(rid).items()} == {
        "llm:queue": 1, "llm:prefill": 1}


def test_an_adopted_request_gets_no_second_prefill_span(setup):
    """The first token came on the replica that prefilled; the adopter
    decodes and closes `llm:decode`, and observes the handoff's phase."""
    from ray_tpu.llm.sampling import SamplingParams

    rid = "span-adopted"
    src, dst = _bare_engine(setup), _bare_engine(setup)
    src.add_request(_prompt(8, 21), SamplingParams(max_tokens=8),
                    request_id=rid)
    _until_first_token(src, rid)
    assert len(_engine_spans(rid)["llm:prefill"]) == 1
    before = _ttft_observed("handoff")
    state = src.export_request(rid)
    blocks = state.pop("blocks")
    pages = src.runner.gather_pages(blocks)
    src.block_manager.release_blocks(blocks)
    assert dst.adopt_request(state, *pages)
    assert _ttft_observed("handoff") == before + 1
    while dst.has_unfinished():
        dst.step()
    assert {k: len(v) for k, v in _engine_spans(rid).items()} == {
        "llm:queue": 1, "llm:prefill": 1, "llm:decode": 1}
    assert _engine_spans(rid)["llm:decode"][0]["args"]["handoff_s"] > 0


def test_a_prefill_only_engine_leaves_the_span_to_the_prefill_server(setup):
    """A request handed on has ONE `llm:prefill`, the prefill server's (it
    covers the export); one that finishes at prefill has the engine's."""
    from ray_tpu.llm.sampling import SamplingParams

    engine = _bare_engine(setup, prefill_only=True)
    engine.add_request(_prompt(9, 21), SamplingParams(max_tokens=8),
                       request_id="span-pre")
    engine.add_request(_prompt(10, 21), SamplingParams(max_tokens=1),
                       request_id="span-pre-done")
    for _ in range(12):
        engine.step()
    engine.settle()
    assert sorted(_engine_spans("span-pre")) == ["llm:queue"]
    assert sorted(_engine_spans("span-pre-done")) == [
        "llm:decode", "llm:prefill", "llm:queue"]
