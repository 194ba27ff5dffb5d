"""Scaled-down scalability-envelope checks.

Reference analog: release/benchmarks/ (the published envelope — tasks
queued on one node, object args to a single task, returns from a single
task, many actors). Full-scale numbers need a cluster; these assert the
same MECHANISMS survive two orders of magnitude below the reference
envelope on one dev box, so regressions in queueing/arg-pinning/return
packaging surface in CI.
"""

import numpy as np
import pytest

import ray_tpu


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.mark.slow  # >60s measured: full-tier only
def test_many_queued_tasks(cluster):
    """100k trivial tasks queued at once all complete (reference row:
    1M+ queued on one node)."""

    @ray_tpu.remote
    def inc(x):
        return x + 1

    refs = [inc.remote(i) for i in range(100_000)]
    out = ray_tpu.get(refs, timeout=900)
    assert out[0] == 1 and out[-1] == 100_000
    assert len(out) == 100_000


def test_many_args_to_single_task(cluster):
    """5k object args resolve into one task (reference row: 10k+)."""

    @ray_tpu.remote
    def total(*parts):
        return sum(parts)

    parts = [ray_tpu.put(i) for i in range(5_000)]
    assert ray_tpu.get(total.remote(*parts), timeout=600) == \
        sum(range(5_000))


def test_many_returns_from_single_task(cluster):
    """1k returns from one task (reference row: 3k+)."""

    @ray_tpu.remote(num_returns=1000)
    def spread():
        return tuple(range(1000))

    refs = spread.remote()
    assert len(refs) == 1000
    vals = ray_tpu.get(refs, timeout=300)
    assert vals == list(range(1000))


def test_many_plasma_objects_in_one_get(cluster):
    """1k plasma-resident objects fetched in a single get (reference
    row: 10k+)."""
    refs = [ray_tpu.put(np.full(64_000, i, dtype=np.int32))
            for i in range(1_000)]
    out = ray_tpu.get(refs, timeout=600)
    assert len(out) == 1_000
    assert int(out[512][0]) == 512


@pytest.mark.slow  # >60s measured: full-tier only
def test_many_actors(cluster):
    """200 concurrent actors created and called (reference row: 40k+
    cluster-wide)."""

    @ray_tpu.remote
    class Cell:
        def __init__(self, v):
            self.v = v

        def get(self):
            return self.v

    actors = [Cell.remote(i) for i in range(200)]
    vals = ray_tpu.get([a.get.remote() for a in actors], timeout=600)
    assert vals == list(range(200))
    for a in actors:
        ray_tpu.kill(a)


# ---- full reference magnitudes (slow; run with -m slow) ------------------
#
# The rows above keep CI fast two orders of magnitude down; these are the
# REFERENCE-scale rows (release/benchmarks/README.md:27-31) on one box,
# gated behind the slow marker.

@pytest.mark.slow
def test_reference_scale_queued_tasks(cluster):
    """1,000,000 trivial tasks queued on one node all complete
    (release/benchmarks/README.md:30)."""

    @ray_tpu.remote
    def inc(x):
        return x + 1

    n = 1_000_000
    refs = [inc.remote(i) for i in range(n)]
    assert len(refs) == n
    # Sample-check completions across the whole range, then drain all.
    out = ray_tpu.get(refs, timeout=5400)
    assert len(out) == n
    assert out[0] == 1 and out[n // 2] == n // 2 + 1 and out[-1] == n


@pytest.mark.slow
def test_reference_scale_args_to_single_task(cluster):
    """10,000 object args resolve into one task
    (release/benchmarks/README.md:27)."""

    @ray_tpu.remote
    def total(*parts):
        return sum(parts)

    parts = [ray_tpu.put(i) for i in range(10_000)]
    assert ray_tpu.get(total.remote(*parts), timeout=1800) == \
        sum(range(10_000))


@pytest.mark.slow
def test_reference_scale_returns_from_single_task(cluster):
    """3,000 returns from one task (release/benchmarks/README.md:28)."""

    @ray_tpu.remote(num_returns=3000)
    def spread():
        return tuple(range(3000))

    refs = spread.remote()
    assert len(refs) == 3000
    vals = ray_tpu.get(refs, timeout=1800)
    assert vals == list(range(3000))


@pytest.mark.slow
def test_reference_scale_objects_in_one_get(cluster):
    """10,000 plasma-resident objects fetched in a single get
    (release/benchmarks/README.md:29)."""
    refs = [ray_tpu.put(np.full(16_000, i, dtype=np.int32))
            for i in range(10_000)]
    out = ray_tpu.get(refs, timeout=1800)
    assert len(out) == 10_000
    assert int(out[7777][0]) == 7777


# ---- control-plane scale envelope (batched leases, 1k fake nodes) --------

def test_time_to_first_lease_1k_fake_nodes():
    """Fast-tier control-plane envelope: with 1000 fake node records live
    in the GCS (full view synced to the raylet), the first lease of a
    64-entry LeaseBatchRequestMsg must still grant promptly — the path
    must be O(shard)/O(batch), not O(cluster). Anything approaching the
    60s line belongs behind the slow marker, so the bound asserts far
    below it. Shares the harness with the microbench suite so the test
    and the microbenchmark's legs measure the same thing."""
    from ray_tpu.util.microbenchmark import run_scale_envelope

    legs = run_scale_envelope(n_requests=64, fake_nodes=1000, trials=1)
    ttfl = legs["time_to_first_lease_1k_fake_nodes"]["value"]
    assert ttfl < 60.0, f"time to first lease {ttfl:.3f}s breaches envelope"
    # Batched leasing must not LOSE to per-item round-trips (generous
    # slack: this guards against the batch path breaking/falling back,
    # not against scheduler jitter on a loaded CI box).
    batched = legs["sched_tasks_per_s"]["value"]
    per_item = legs["sched_tasks_per_s_per_item"]["value"]
    assert batched > 0 and per_item > 0
    assert batched >= 0.5 * per_item, (batched, per_item)
