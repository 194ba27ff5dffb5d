"""Dataset tests. Reference test model: python/ray/data/tests."""

import numpy as np
import pandas as pd
import pytest

import ray_tpu
from ray_tpu import data as rdata


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


def test_range_count_take(cluster):
    ds = rdata.range(100, parallelism=4)
    assert ds.count() == 100
    rows = ds.take(5)
    assert [r["id"] for r in rows] == [0, 1, 2, 3, 4]


def test_map_batches(cluster):
    ds = rdata.range(32, parallelism=4).map_batches(
        lambda b: {"id": b["id"], "sq": b["id"] ** 2})
    rows = ds.take_all()
    assert all(r["sq"] == r["id"] ** 2 for r in rows)


def test_map_filter_fusion(cluster):
    from ray_tpu.data import plan as plan_mod

    ds = rdata.range(50, parallelism=2).map(
        lambda r: {"id": r["id"] * 2}).filter(lambda r: r["id"] % 4 == 0)
    optimized = plan_mod.optimize(ds._ops)
    # Read + one FusedMap (map+filter fused into one task stage).
    assert len(optimized) == 2
    assert optimized[1].name == "FusedMap"
    assert len(optimized[1].stages) == 2
    ids = sorted(r["id"] for r in ds.take_all())
    assert ids == [i * 2 for i in __import__("builtins").range(50) if (i * 2) % 4 == 0]


def test_limit_pushdown(cluster):
    from ray_tpu.data import plan as plan_mod

    ds = rdata.range(1000, parallelism=4).limit(10)
    optimized = plan_mod.optimize(ds._ops)
    assert len(optimized) == 1 and optimized[0].limit == 10
    assert ds.count() == 10


def test_iter_batches_rechunks(cluster):
    ds = rdata.range(100, parallelism=7)
    batches = list(ds.iter_batches(batch_size=32))
    sizes = [len(b["id"]) for b in batches]
    assert sizes == [32, 32, 32, 4]
    assert np.concatenate([b["id"] for b in batches]).tolist() == list(
        __import__("builtins").range(100))


def test_flat_map_and_sort(cluster):
    ds = rdata.from_items([{"x": 3}, {"x": 1}, {"x": 2}], parallelism=2)
    out = ds.flat_map(lambda r: [r, {"x": r["x"] + 10}]).sort("x", descending=True)
    xs = [r["x"] for r in out.take_all()]
    assert xs == sorted(xs, reverse=True)
    assert len(xs) == 6


def test_random_shuffle(cluster):
    ds = rdata.range(100, parallelism=4).random_shuffle(seed=0)
    ids = [r["id"] for r in ds.take_all()]
    assert sorted(ids) == list(__import__("builtins").range(100))
    assert ids != sorted(ids)


def test_repartition(cluster):
    ds = rdata.range(100, parallelism=2).repartition(5)
    blocks = list(ds.iter_blocks())
    assert len(blocks) == 5
    assert sum(b.num_rows for b in blocks) == 100


def test_tensor_columns(cluster):
    arrays = {"x": np.arange(48, dtype=np.float32).reshape(12, 4),
              "y": np.arange(12)}
    ds = rdata.from_numpy(arrays, parallelism=3)
    batch = next(iter(ds.iter_batches(batch_size=12)))
    assert batch["x"].shape == (12, 4)
    np.testing.assert_array_equal(batch["x"], arrays["x"])


def test_from_pandas_roundtrip(cluster):
    df = pd.DataFrame({"a": [1, 2, 3], "b": ["x", "y", "z"]})
    ds = rdata.from_pandas(df)
    out = ds.to_pandas()
    pd.testing.assert_frame_equal(out, df)


def test_read_write_files(cluster, tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    for i in __import__("builtins").range(3):
        pq.write_table(pa.table({"v": list(__import__("builtins").range(
            i * 10, (i + 1) * 10))}), str(tmp_path / f"part{i}.parquet"))
    ds = rdata.read_parquet(str(tmp_path))
    assert ds.count() == 30
    assert sorted(r["v"] for r in ds.take_all()) == list(
        __import__("builtins").range(30))


def test_streaming_split(cluster):
    ds = rdata.range(64, parallelism=4)
    its = ds.streaming_split(2)
    counts = [sum(len(b["id"]) for b in it.iter_batches(batch_size=8))
              for it in its]
    assert sum(counts) == 64
    assert all(c > 0 for c in counts)


def test_arrow_block_zero_copy_through_store(cluster):
    """Review item 9: Arrow blocks round-trip ZERO-COPY through the shm
    object store — the reconstructed table's column buffers point INTO the
    store's mapped arena (no copy at get), like reference plasma+Arrow."""
    import pyarrow as pa

    import ray_tpu
    from ray_tpu.core.worker import global_worker

    t = pa.table({"a": np.arange(200_000, dtype=np.int64),
                  "b": np.random.rand(200_000)})
    ref = ray_tpu.put(t)
    back = ray_tpu.get(ref, timeout=60)
    assert isinstance(back, pa.Table) and back.equals(t)

    store = global_worker().store
    base = pa.py_buffer(store._view).address
    size = len(store._view)
    for name in ("a", "b"):
        chunk = back.column(name).chunks[0]
        data_buf = chunk.buffers()[1]
        assert base <= data_buf.address < base + size, \
            f"column {name} was copied out of the store arena"


def test_numpy_fast_path_zero_copy_through_store(cluster):
    """Top-level ndarray put/get skips pickle and reconstructs as a view
    over the store arena."""
    import pyarrow as pa

    import ray_tpu
    from ray_tpu.core.worker import global_worker

    arr = np.arange(1 << 18, dtype=np.float32).reshape(512, 512)
    ref = ray_tpu.put(arr)
    back = ray_tpu.get(ref, timeout=60)
    np.testing.assert_array_equal(back, arr)
    assert back.dtype == arr.dtype and back.shape == arr.shape
    store = global_worker().store
    base = pa.py_buffer(store._view).address
    addr = back.__array_interface__["data"][0]
    assert base <= addr < base + len(store._view), "ndarray was copied"
