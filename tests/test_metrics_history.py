"""GCS metrics-history plane: time-series rings, windowed queries,
SLO burn-rate alerting, link attribution, and the windowed replica
policy.

Unit tests drive GcsServer's ingest/query/alert paths directly with
explicit timestamps (no sockets, no sleeps — the handlers take `now`),
so windowed aggregates are checked against exact synthetic references.
One end-to-end test pushes real flushes through a live cluster and reads
them back via `state.metrics_history` and `scripts metrics --json`.
"""

import asyncio
import json
import os
import time

import pytest

import ray_tpu
from ray_tpu.util.metrics import histogram_quantile


def _mk_server():
    from ray_tpu.runtime.gcs.server import GcsServer

    return GcsServer()


def _tkey(**tags) -> str:
    # Mirrors util.metrics._tag_key: sorted items, default separators.
    return json.dumps(sorted(tags.items()))


def _counter(name, value, tkey="[]"):
    return {"name": name, "type": "counter", "values": {tkey: value}}


def _gauge(name, value, tkey="[]"):
    return {"name": name, "type": "gauge", "values": {tkey: value}}


def _hist(name, boundaries, buckets, hsum, count, tkey="[]"):
    return {"name": name, "type": "histogram", "boundaries": boundaries,
            "histograms": {tkey: {"buckets": list(buckets), "sum": hsum,
                                  "count": count}}}


def _ingest(srv, snaps, now, node="aa" * 14, pid=1):
    srv._ingest_metrics_history(node, pid, json.dumps(snaps).encode(),
                                now=now)


# ---------------------------------------------------------------------------
# windowed queries vs synthetic references
# ---------------------------------------------------------------------------


def test_counter_rate_window_matches_straight_line():
    """A counter climbing 5/s flushed every second: the 30 s window rate
    must come out exactly 5.0, and the window delta exactly 150, with the
    pre-window point serving as baseline (the edge-crossing increment
    counts)."""
    srv = _mk_server()
    t0 = time.time() - 120.0
    for i in range(61):
        _ingest(srv, [_counter("ray_tpu_tasks_finished_total", 5.0 * i,
                               _tkey(outcome="ok"))], now=t0 + i)
    t_end = t0 + 60
    # Baseline is the LAST PRE-WINDOW point (t0+29, value 145), so the
    # increment that crossed the window edge counts: the straight-line
    # reference is 300 - 145 = 155 over the 30 s window.
    rate, by_node, _ = srv._mh_window("ray_tpu_tasks_finished_total",
                                      window_s=30.0, agg="rate", now=t_end)
    assert rate == pytest.approx(155.0 / 30.0)
    delta, _, _ = srv._mh_window("ray_tpu_tasks_finished_total",
                                 window_s=30.0, agg="delta", now=t_end)
    assert delta == pytest.approx(155.0)
    assert by_node == {"aa" * 14: pytest.approx(155.0)}
    # A reset (restart) clamps to zero instead of going negative.
    _ingest(srv, [_counter("ray_tpu_tasks_finished_total", 10.0,
                           _tkey(outcome="ok"))], now=t_end + 1)
    delta2, _, _ = srv._mh_window("ray_tpu_tasks_finished_total",
                                  window_s=30.0, agg="delta",
                                  now=t_end + 1)
    assert delta2 >= 0.0


def test_counter_idle_flushes_store_nothing():
    srv = _mk_server()
    t0 = time.time() - 60.0
    for i in range(20):
        _ingest(srv, [_counter("ray_tpu_tasks_submitted_total", 7.0)],
                now=t0 + i)
    recs = srv._mh_match("ray_tpu_tasks_submitted_total")
    assert len(recs) == 1
    assert len(recs[0]["points"]) == 1  # value never moved after flush 0


def test_histogram_quantile_window_matches_reference():
    """Quantiles must be reconstructed from the bucket deltas INSIDE the
    window: old traffic (all fast) falls out, and the p99 reflects only
    the recent slow observations."""
    srv = _mk_server()
    bounds = [1.0, 2.0, 5.0, 10.0, 100.0]
    name = "ray_tpu_llm_ttft_breakdown_ms"
    tk = _tkey(phase="prefill")
    t0 = time.time() - 400.0
    # Old regime: 1000 fast observations (bucket 0), outside the window.
    cum = [1000, 0, 0, 0, 0, 0]
    _ingest(srv, [_hist(name, bounds, cum, 500.0, 1000, tk)], now=t0)
    # In-window regime: 90 obs in (2,5], 10 in (10,100] per flush.
    for i in range(1, 4):
        cum = [1000, 0, 90 * i, 0, 10 * i, 0]
        _ingest(srv, [_hist(name, bounds, cum, 500.0 + 400.0 * i,
                            1000 + 100 * i, tk)], now=t0 + 370 + i * 10)
    window_buckets = [0, 0, 270, 0, 30, 0]
    expect_p99 = histogram_quantile(bounds, window_buckets, 0.99)
    p99, _, extras = srv._mh_window(name, window_s=60.0, agg="p99",
                                    now=t0 + 400)
    assert p99 == pytest.approx(expect_p99)
    assert extras["count"] == 300
    # 10% of window traffic sits in (10, 100] -> p99 interpolates there.
    assert 10.0 < p99 <= 100.0
    mean, _, _ = srv._mh_window(name, window_s=60.0, agg="mean",
                                now=t0 + 400)
    assert mean == pytest.approx(1200.0 / 300.0)
    # Tag filter: a non-matching subset finds nothing.
    none, _, _ = srv._mh_window(name, tags={"phase": "decode"},
                                window_s=60.0, agg="p99", now=t0 + 400)
    assert none is None


def test_gauge_window_mean_and_quiet_fallback():
    srv = _mk_server()
    t0 = time.time() - 300.0
    for i, v in enumerate([10.0, 20.0, 30.0]):
        _ingest(srv, [_gauge("ray_tpu_pending_leases", v)], now=t0 + i)
    # All samples are old; mean must fall back to the latest level, not
    # report "no samples" for a flat-but-alive gauge.
    val, _, _ = srv._mh_window("ray_tpu_pending_leases", window_s=30.0,
                               agg="mean", now=t0 + 290)
    assert val == pytest.approx(30.0)


# ---------------------------------------------------------------------------
# ring eviction under the byte budget
# ---------------------------------------------------------------------------


def test_ring_eviction_under_byte_cap():
    from ray_tpu import config as config_mod

    os.environ["RAY_TPU_METRICS_HISTORY_MAX_BYTES"] = "16384"
    os.environ["RAY_TPU_GCS_RING_SHARDS"] = "1"
    config_mod.reset_for_testing()
    try:
        srv = _mk_server()
        t0 = time.time() - 5000.0
        for i in range(2000):
            _ingest(srv, [_gauge("ray_tpu_owned_objects", float(i))],
                    now=t0 + i)
        shard = srv._mh_shards[0]
        assert shard["bytes"] <= shard["budget"]
        assert srv._mh_evicted_points > 0
        rec = srv._mh_match("ray_tpu_owned_objects")[0]
        # Oldest points evicted first: the surviving head moved forward.
        assert rec["points"][0][0] > t0
        assert rec["points"][-1][0] == pytest.approx(t0 + 1999)
        stats = asyncio.run(srv.handle_metrics_history_stats(None))
        assert stats["evicted_points"] == srv._mh_evicted_points
        assert stats["bytes"] <= stats["budget_bytes"]
    finally:
        os.environ.pop("RAY_TPU_METRICS_HISTORY_MAX_BYTES", None)
        os.environ.pop("RAY_TPU_GCS_RING_SHARDS", None)
        config_mod.reset_for_testing()


def test_stale_worker_purge_is_pid_exact():
    """A worker-death report purges exactly that pid's series — pid 123
    must not shadow pid 1234 — while a node death sweeps the node prefix."""
    srv = _mk_server()
    node = b"ab" * 7
    now = time.time()
    for pid in (123, 1234):
        srv._ingest_metrics_history(node.hex(), pid,
                                    json.dumps([_gauge("ray_tpu_owned_objects",
                                                       1.0)]).encode(),
                                    now=now)
        srv._kv[f"metrics:{node.hex()}:{pid}".encode()] = b"[]"
    asyncio.run(srv.handle_report_worker_death(None, node, b"w" * 14,
                                               pid=123))
    reporters = {r["reporter"]
                 for r in srv._mh_match("ray_tpu_owned_objects")}
    assert reporters == {f"{node.hex()}:1234"}
    assert f"metrics:{node.hex()}:123".encode() not in srv._kv
    assert f"metrics:{node.hex()}:1234".encode() in srv._kv
    # Node-prefix purge takes the rest.
    srv._mh_purge_reporter(f"{node.hex()}:")
    assert srv._mh_match("ray_tpu_owned_objects") == []


# ---------------------------------------------------------------------------
# burn-rate alerting: fire, dedup, resolve
# ---------------------------------------------------------------------------


def _ttft_flush(srv, now, cum_slow, cum_fast, node="cc" * 14):
    from ray_tpu.runtime import metric_defs

    bounds = list(metric_defs.LLM_TTFT_BREAKDOWN_MS._boundaries)
    # bucket 9 covers (1000, 5000] ms — every observation there breaches
    # the 1 s SLO; bucket 0 is well under it.
    buckets = [cum_fast] + [0] * 8 + [cum_slow, 0]
    count = cum_fast + cum_slow
    _ingest(srv, [_hist("ray_tpu_llm_ttft_breakdown_ms", bounds, buckets,
                        2000.0 * cum_slow + 10.0 * cum_fast, count,
                        _tkey(phase="prefill"))], now=now, node=node)


def _alert_events(srv, etype):
    return [e for e in getattr(srv, "_cluster_events", ())
            if e["type"] == etype]


def test_burn_rate_alert_fires_dedupes_and_resolves():
    from ray_tpu.runtime import events as events_mod

    srv = _mk_server()
    t0 = time.time() - 1000.0
    # 300 s of injected latency: every flush adds 10 breaching requests.
    for i in range(31):
        _ttft_flush(srv, t0 + i * 10, cum_slow=10 * (i + 1), cum_fast=0)
    t_bad = t0 + 300
    srv._alert_eval_tick(now=t_bad)
    firing = _alert_events(srv, events_mod.ALERT_FIRING)
    assert len(firing) == 1
    ev = firing[0]
    assert ev["labels"]["rule"] == "slo_burn_ttft"
    assert ev["labels"]["series"] == "ray_tpu_llm_ttft_breakdown_ms"
    assert ev["severity"] == "ERROR"
    assert ev["node_id"] == "cc" * 14  # top-contributor attribution
    assert float(ev["labels"]["value"]) >= 10.0
    # Ongoing condition: a second tick must NOT re-emit (signature dedup).
    srv._alert_eval_tick(now=t_bad + 2)
    assert len(_alert_events(srv, events_mod.ALERT_FIRING)) == 1
    alerts = asyncio.run(srv.handle_list_alerts(None))
    assert "slo_burn_ttft" in alerts["firing"]
    st = {r["name"]: r for r in alerts["rules"]}["slo_burn_ttft"]
    assert st["state"] == "firing" and st["since"] == pytest.approx(t_bad)
    # Recovery: 40 s of fast-only traffic empties the short window.
    slow = 310
    for i in range(1, 5):
        _ttft_flush(srv, t_bad + i * 10, cum_slow=slow, cum_fast=500 * i)
    srv._alert_eval_tick(now=t_bad + 40)
    resolved = _alert_events(srv, events_mod.ALERT_RESOLVED)
    assert len(resolved) == 1
    assert resolved[0]["labels"]["rule"] == "slo_burn_ttft"
    assert len(_alert_events(srv, events_mod.ALERT_FIRING)) == 1
    alerts = asyncio.run(srv.handle_list_alerts(None))
    assert alerts["firing"] == []
    assert {r["name"]: r for r in alerts["rules"]}["slo_burn_ttft"][
        "state"] == "ok"


def test_burn_rate_needs_both_windows():
    """A single-tick latency blip burns the short window but not the
    long one — the two-window guard must hold the alert back."""
    srv = _mk_server()
    t0 = time.time() - 1000.0
    # 300 s of healthy traffic...
    for i in range(31):
        _ttft_flush(srv, t0 + i * 10, cum_slow=0, cum_fast=100 * (i + 1))
    # ...then one bad flush right at the end — enough to burn the short
    # window (50/350 breaches -> 14x budget) but a rounding error to the
    # long one (50/3150 -> ~1.6x).
    _ttft_flush(srv, t0 + 305, cum_slow=50, cum_fast=3100)
    srv._alert_eval_tick(now=t0 + 306)
    from ray_tpu.runtime import events as events_mod

    assert _alert_events(srv, events_mod.ALERT_FIRING) == []


def test_silent_series_never_fires():
    srv = _mk_server()
    srv._alert_eval_tick(now=time.time())
    assert getattr(srv, "_alert_sigs", set()) == set()


# ---------------------------------------------------------------------------
# link utilization from tagged collective counters
# ---------------------------------------------------------------------------


def test_link_utilization_matrix():
    from ray_tpu.runtime.gcs.server import NodeRecord

    srv = _mk_server()
    ids = [b"n0" * 7, b"n1" * 7, b"h0" * 7]
    labels = [{"tpu-slice-name": "s0", "tpu-worker-id": "0"},
              {"tpu-slice-name": "s0", "tpu-worker-id": "1"},
              {}]
    for nid, lab in zip(ids, labels):
        srv._nodes[nid] = NodeRecord(nid, ("h", 1), {"CPU": 1.0}, "/s",
                                     False, lab)
    tk = _tkey(op="allreduce", algo="ring")
    now = time.time()
    for nid in ids:
        for metric in ("ray_tpu_collective_bytes_sent_total",
                       "ray_tpu_collective_bytes_recv_total"):
            _ingest(srv, [_counter(metric, 0.0, tk)], now=now - 20,
                    node=nid.hex())
            _ingest(srv, [_counter(metric, 3.0e6, tk)], now=now - 2,
                    node=nid.hex())
    out = asyncio.run(srv.handle_link_utilization(None, window_s=30.0))
    links = {l["link"]: l for l in out["links"]}
    # Slice nodes ride their ICI ring direction; the unlabeled node books
    # to its host link.
    assert f"host:{ids[2].hex()[:12]}" in links
    ici = [k for k in links if k.startswith("ici:s0:")]
    assert sorted(ici) == ["ici:s0:0->1", "ici:s0:1->0"]
    # worker 0 tx rides 0->1; worker 1's rx arrives on 0->1 too.
    fwd = links["ici:s0:0->1"]
    assert fwd["kind"] == "ici" and fwd["slice"] == "s0"
    assert fwd["tx_bytes_per_s"] == pytest.approx(1e5)
    assert fwd["rx_bytes_per_s"] == pytest.approx(1e5)
    assert fwd["by_op"]["allreduce/ring"] == pytest.approx(2e5)
    # Per-node totals come out regardless of attribution.
    assert out["nodes"][ids[0].hex()]["tx_bytes_per_s"] == \
        pytest.approx(1e5)


# ---------------------------------------------------------------------------
# windowed replica policy
# ---------------------------------------------------------------------------


_QUIET = {"waiting": 0, "prefilling": 0, "queued_prefill_tokens": 0,
          "total_kv_blocks": 100, "free_kv_blocks": 90}
_SPIKE = {"waiting": 10, "prefilling": 0, "queued_prefill_tokens": 0,
          "total_kv_blocks": 100, "free_kv_blocks": 0}


def test_replica_policy_windowed_ignores_one_tick_spike():
    from ray_tpu.llm.replica_policy import (ReplicaPolicy,
                                            ReplicaPolicyConfig)

    # Instantaneous mode scales on the very first spike tick...
    inst = ReplicaPolicy(ReplicaPolicyConfig())
    assert inst.desired([_SPIKE], current=1, now=1000.0) == 2
    # ...while windowed mode dilutes it against the quiet history.
    win = ReplicaPolicy(ReplicaPolicyConfig(signal_window_s=30.0))
    for i in range(6):
        assert win.desired([_QUIET], current=1, now=1000.0 + 5 * i) == 1
    assert win.desired([_SPIKE], current=1, now=1030.0) == 1
    # A SUSTAINED breach still scales once it dominates the window.
    for i in range(1, 8):
        got = win.desired([_SPIKE], current=1, now=1030.0 + 5 * i)
        if got == 2:
            break
    assert got == 2


def test_replica_policy_rejects_negative_window():
    from ray_tpu.llm.replica_policy import ReplicaPolicyConfig

    with pytest.raises(ValueError):
        ReplicaPolicyConfig(signal_window_s=-1.0)


# ---------------------------------------------------------------------------
# end to end: real flushes -> GCS rings -> state API + CLI
# ---------------------------------------------------------------------------


def test_metrics_history_end_to_end(capsys):
    from ray_tpu import scripts
    from ray_tpu.state import api as state
    from ray_tpu.runtime import alert_defs
    from ray_tpu.util import metrics as metrics_mod

    # A process's registry is cumulative since the process began, and under
    # xdist this one began with other files' tests. Its first flush would
    # hand this test's GCS an engine test's TTFT and ITL histograms as if
    # they were this cluster's traffic, and slo_burn_* fire on them ("none
    # firing here" below failed that way in one run of several, by which
    # files the worker had run before). Forget what the alert rules read.
    for rule in alert_defs.ALERT_RULES:
        metric = metrics_mod._REGISTRY.get(rule["series"])
        if metric is not None:
            with metric._lock:
                metric._values.clear()
                getattr(metric, "_hist", {}).clear()

    ray_tpu.init(num_cpus=2)
    try:
        addr = ray_tpu.get_runtime_context().gcs_address

        @ray_tpu.remote
        def one():
            return 1

        # Warmup establishes the counter's baseline point; without it the
        # first window point has no predecessor and the delta is zero.
        assert ray_tpu.get(one.remote(), timeout=60) == 1
        metrics_mod.flush()
        time.sleep(0.3)
        assert ray_tpu.get([one.remote() for _ in range(8)],
                           timeout=60) == [1] * 8
        metrics_mod.flush()
        deadline = time.time() + 10
        out = None
        while time.time() < deadline:
            out = state.metrics_history("ray_tpu_tasks_finished_total",
                                        window_s=120.0, agg="delta")
            if (out.get("value") or 0) >= 8:
                break
            time.sleep(0.3)
            metrics_mod.flush()
        assert out["value"] >= 8, out
        assert out["by_node"], "no per-node attribution"
        assert any(s["points"] for s in out["series"])

        # CLI twin returns the same payload as JSON.
        capsys.readouterr()
        scripts.main(["metrics", "ray_tpu_tasks_finished_total",
                      "--address", addr, "--window", "120",
                      "--agg", "delta", "--json"])
        cli = json.loads(capsys.readouterr().out)
        assert cli["value"] >= 8
        # Human rendering includes the sparkline lines.
        scripts.main(["metrics", "ray_tpu_tasks_finished_total",
                      "--address", addr, "--window", "120", "--rate"])
        txt = capsys.readouterr().out
        assert "value:" in txt and "ray_tpu_tasks_finished_total" in txt

        # Alerts surface in the summary rollup (none firing here).
        summ = state.summary()
        assert "alerts" in summ
        assert summ["alerts"]["rules"] >= 5
        assert summ["alerts"]["firing"] == []
    finally:
        ray_tpu.shutdown()
