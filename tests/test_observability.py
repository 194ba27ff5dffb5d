"""Cluster observability plane: event bus, trace aggregation, telemetry.

Acceptance surface of the observability PR: (1) life-or-death decisions
(slice loss, OOM kills, collective aborts, scale decisions, gang
restarts) leave typed events in the GCS ring, retrievable via
`state.list_cluster_events()` and `scripts events`; (2) `scripts
timeline --cluster` merges every process's span ring into one chrome
trace where submit -> execute -> nested submit stitch under one trace id;
(3) a Train run reports per-step phase breakdown and goodput through
`Result.telemetry`.
"""

import json
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.runtime import events as events_mod
from ray_tpu.runtime.tpu_topology import slice_labels
from ray_tpu.util import tracing


def _poll_events(deadline_s=15.0, **filters):
    from ray_tpu.state import list_cluster_events

    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        events = list_cluster_events(**filters)
        if events:
            return events
        time.sleep(0.2)
    return []


# ---------------------------------------------------------------------------
# Event record + bus plumbing
# ---------------------------------------------------------------------------

def test_event_record_shape_and_validation():
    ev = events_mod.make_event(
        events_mod.SLICE_LOST, "slice gone", severity=events_mod.ERROR,
        source="gcs", node_id=b"\xab" * 16, slice_name="trillium-0",
        labels={"hosts": "4"})
    assert ev["type"] == "SLICE_LOST" and ev["severity"] == "ERROR"
    assert ev["node_id"] == "ab" * 16 and ev["slice_name"] == "trillium-0"
    assert ev["labels"] == {"hosts": "4"} and ev["time"] > 0
    json.dumps(ev)  # must stay JSON-able end to end
    with pytest.raises(ValueError):
        events_mod.make_event("NOT_A_TYPE", "x")
    with pytest.raises(ValueError):
        events_mod.make_event(events_mod.NODE_DEAD, "x", severity="FATAL")
    # emit() outside any cluster is a silent no-op, never a crash.
    assert events_mod.emit(events_mod.NODE_DEAD, "no cluster") is not None


def test_event_bus_roundtrip_filters_and_cli(capsys):
    from ray_tpu import scripts
    from ray_tpu.state import list_cluster_events

    ray_tpu.init(num_cpus=1)
    try:
        addr = ray_tpu.get_runtime_context().gcs_address
        events_mod.emit(events_mod.AUTOSCALER_SCALE, "+1 launched",
                        source="autoscaler", labels={"launched": "1"})
        events_mod.emit(events_mod.NODE_DEAD, "synthetic node death",
                        severity=events_mod.ERROR, source="gcs")
        got = _poll_events(event_type="AUTOSCALER_SCALE")
        assert got and got[0]["message"] == "+1 launched"
        assert got[0]["labels"]["launched"] == "1"
        # Severity/source filters are exact.
        errors = _poll_events(severity="ERROR")
        assert errors and all(e["severity"] == "ERROR" for e in errors)
        assert list_cluster_events(event_type="SLICE_LOST") == []
        # Newest first.
        both = _poll_events()
        assert both[0]["time"] >= both[-1]["time"]

        scripts.main(["events", "--address", addr,
                      "--type", "AUTOSCALER_SCALE"])
        out = json.loads(capsys.readouterr().out)
        assert out and out[0]["type"] == "AUTOSCALER_SCALE"
        scripts.main(["events", "--address", addr, "--severity", "INFO",
                      "--source", "autoscaler", "--limit", "5"])
        out = json.loads(capsys.readouterr().out)
        assert all(e["source"] == "autoscaler" for e in out)
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Chaos: slice kill + OOM leave typed events
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_slice_kill_emits_typed_events_and_purges_metrics(capsys):
    from ray_tpu import scripts
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.core import worker as worker_mod
    from ray_tpu.util.fault_injection import SliceKiller

    cluster = Cluster()
    try:
        cluster.add_node(num_cpus=2)
        for i in range(2):
            cluster.add_node(num_cpus=1, resources={"slicehost": 1},
                             labels=slice_labels("trillium-0", "v5e-16", i))
        ray_tpu.init(address=cluster.address)
        cluster.wait_for_nodes(3)

        # Plant a metrics snapshot under a slice node's key: node death
        # must purge it (stale-metrics satellite, GCS side).
        from ray_tpu.state.api import list_nodes
        slice_node_hex = next(
            n["node_id"] for n in list_nodes()
            if n["labels"].get("tpu-slice-name") == "trillium-0")
        core = worker_mod.global_worker()
        stale_key = f"metrics:{slice_node_hex}:99999".encode()
        core.io.run(core.gcs.call("kv_put", key=stale_key, value=b"[]"))

        killer = SliceKiller(cluster, slice_name="trillium-0")
        assert killer.strike() is not None

        lost = _poll_events(event_type="SLICE_LOST")
        assert lost, "no SLICE_LOST event after slice strike"
        assert lost[0]["severity"] == "ERROR"
        assert lost[0]["source"] == "gcs"
        assert lost[0]["slice_name"] == "trillium-0"
        assert int(lost[0]["labels"]["hosts"]) == 2
        dead = _poll_events(event_type="NODE_DEAD")
        # Both slice hosts die (origin + fate-shared sibling).
        assert len(dead) >= 2
        assert all(e["node_id"] for e in dead)

        # Same events through the CLI surface.
        addr = ray_tpu.get_runtime_context().gcs_address
        scripts.main(["events", "--address", addr, "--type", "SLICE_LOST"])
        out = json.loads(capsys.readouterr().out)
        assert out and out[0]["slice_name"] == "trillium-0"

        # The dead node's metrics KV snapshot is gone.
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            keys = core.io.run(core.gcs.call(
                "kv_keys", prefix=b"metrics:"))["keys"]
            if stale_key not in keys:
                break
            time.sleep(0.2)
        assert stale_key not in keys
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


@pytest.mark.chaos
def test_oom_kill_emits_event(fake_memory_pressure):
    mem_file, marker = fake_memory_pressure
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(max_retries=2)
        def pressure(mem_file, marker):
            if not os.path.exists(marker):
                with open(mem_file, "w") as f:
                    f.write("0.99")
                with open(marker, "w") as f:
                    f.write(str(os.getpid()))
                time.sleep(120)
            return "survived retry"

        assert ray_tpu.get(pressure.remote(mem_file, marker),
                           timeout=120) == "survived retry"
        got = _poll_events(event_type="OOM_KILL")
        assert got, "no OOM_KILL event after memory-monitor kill"
        assert got[0]["severity"] == "ERROR"
        assert got[0]["source"] == "raylet"
        assert got[0]["node_id"]
        assert "killed worker" in got[0]["message"]
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Cluster-wide trace aggregation
# ---------------------------------------------------------------------------

def test_timeline_cluster_merges_and_stitches(tmp_path, capsys):
    """submit -> execute -> nested submit spans from >= 2 processes merge
    into one chrome trace under one trace id with correct parent links."""
    from ray_tpu import scripts

    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def obs_inner():
            return os.getpid()

        @ray_tpu.remote
        def obs_outer():
            return (os.getpid(), ray_tpu.get(obs_inner.remote(), timeout=60))

        with tracing.span("obs-driver-root", "test"):
            ref = obs_outer.remote()
        outer_pid, inner_pid = ray_tpu.get(ref, timeout=60)
        assert outer_pid != inner_pid != os.getpid()

        root = next(s for s in tracing.get_spans()
                    if s["name"] == "obs-driver-root")
        trace_id = root["args"]["trace_id"]

        out_path = str(tmp_path / "cluster_timeline.json")
        addr = ray_tpu.get_runtime_context().gcs_address
        scripts.main(["timeline", "--cluster", "--address", addr,
                      "--output", out_path])
        assert "process(es)" in capsys.readouterr().out
        with open(out_path) as f:
            events = json.load(f)["traceEvents"]

        # Lane metadata for every merged process.
        meta = [e for e in events if e.get("ph") == "M"]
        assert any(m["args"]["name"].startswith("driver:") for m in meta)
        assert any(m["args"]["name"].startswith("worker:") for m in meta)

        in_trace = [e for e in events if e.get("ph") == "X"
                    and e.get("args", {}).get("trace_id") == trace_id]
        # One trace spanning >= 2 distinct process lanes (driver + workers).
        assert len({e["pid"] for e in in_trace}) >= 2

        def execute_span(fn_name):
            matches = [e for e in in_trace if e["cat"] == "task:execute"
                       and fn_name in e["name"]]
            assert matches, f"no execute span for {fn_name} in merged trace"
            return matches[0]

        outer_span = execute_span("obs_outer")
        inner_span = execute_span("obs_inner")
        # Driver root -> outer execute -> inner execute, linked by id.
        assert outer_span["args"]["parent_span_id"] == root["args"]["span_id"]
        assert inner_span["args"]["parent_span_id"] == \
            outer_span["args"]["span_id"]
        assert inner_span["args"]["trace_id"] == trace_id
        # Spans from different processes landed on different lanes.
        assert outer_span["pid"] != inner_span["pid"]
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# Train step telemetry
# ---------------------------------------------------------------------------

def _telemetry_train_fn(config):
    from ray_tpu import train as rtrain
    from ray_tpu.train.checkpoint import Checkpoint

    ctx = rtrain.get_context()
    rank = ctx.get_world_rank()
    for step in range(config["steps"]):
        with rtrain.step_phase("data"):
            time.sleep(0.02)  # simulated input wait
        grads = {"w": np.full(8, float(rank + 1))}  # "compute"
        synced = rtrain.allreduce_gradients(grads)  # booked to "collective"
        metrics = {"step": step, "synced0": float(synced["w"][0])}
        if rank == 0 and step == config["steps"] - 1:
            d = os.path.join(ctx.get_storage_path(), f"ckpt_{step}")
            Checkpoint.save_pytree({"w": synced["w"]}, d)
            rtrain.report(metrics, checkpoint=Checkpoint(d))
        else:
            rtrain.report(metrics)


def test_train_telemetry_breakdown_and_goodput(tmp_path):
    from ray_tpu.train import (CollectiveTrainer, RunConfig, ScalingConfig,
                               TrainTelemetry)

    ray_tpu.init(num_cpus=4)
    try:
        trainer = CollectiveTrainer(
            _telemetry_train_fn,
            train_loop_config={"steps": 3},
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(name="telemetry-test",
                                 storage_path=str(tmp_path)))
        result = trainer.fit()
        assert result.error is None, result.error

        tel = result.telemetry
        assert isinstance(tel, TrainTelemetry)
        assert tel.run_name == "telemetry-test"
        assert tel.attempts == 1 and tel.gang_restarts == 0

        # Rank-0 per-step breakdown: every phase key present, data wait
        # and collective sync both attributed, residual is compute.
        assert len(tel.steps) == 3
        for rec in tel.steps:
            assert rec["rank"] == 0
            assert rec["total_s"] > 0
            assert rec["data_s"] >= 0.015  # the sleep in step_phase("data")
            assert rec["collective_s"] > 0
            assert rec["compute_s"] >= 0
            total_attributed = (rec["data_s"] + rec["collective_s"]
                                + rec["checkpoint_s"] + rec["compute_s"]
                                + rec["other_s"])
            assert total_attributed == pytest.approx(rec["total_s"],
                                                     rel=0.01)
        # The checkpointing step booked checkpoint time.
        assert tel.steps[-1]["checkpoint_s"] > 0

        # Goodput: productive over wall, wall includes worker placement.
        assert tel.wall_time_s > 0
        assert tel.productive_time_s == pytest.approx(
            sum(r["total_s"] for r in tel.steps))
        assert 0 < tel.goodput <= 1.0

        # Straggler attribution covers every rank, exactly one straggler.
        report = tel.straggler_report()
        assert [r["rank"] for r in report] == [0, 1]
        assert sum(1 for r in report if r["straggler"]) == 1
        assert all(r["steps"] == 3 for r in report)

        d = tel.to_dict()
        assert d["goodput"] == tel.goodput and len(d["stragglers"]) == 2
    finally:
        ray_tpu.shutdown()


def test_step_phase_noop_outside_session():
    from ray_tpu.train import step_phase

    with step_phase("data"):
        x = 1 + 1
    assert x == 2
