"""The grouped product's Pallas kernel (ops/grouped_dot.py), interpreted on
the CPU at small shapes, against a loop over the groups; its plan and its
sizes; and who takes it (`product`: the kernel on a TPU, `ragged_dot` off
it)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import grouped_dot as gd


def oracle(a, w, sizes):
    """A group at a time, float32; rows behind the last group zero."""
    a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
    out, r = np.zeros((a.shape[0], w.shape[2]), np.float32), 0
    for g, n in enumerate(np.asarray(sizes)):
        out[r:r + n] = a[r:r + n] @ w[g]
        r += n
    return out


# name -> (sizes, rows P, K, N, (row_tile, k_tile))
CASES = {
    "empty_groups_first": ([0, 0, 0, 5, 3, 9], 24, 32, 24, (8, 32)),
    "empty_groups_last": ([5, 3, 9, 0, 0, 0], 24, 32, 24, (8, 32)),
    "empty_groups_in_the_middle": ([5, 0, 0, 3, 0, 9], 24, 32, 24, (8, 16)),
    "every_row_in_one_group": ([0, 40, 0], 40, 32, 24, (16, 32)),
    "one_row_a_group": ([1] * 30, 37, 16, 8, (8, 16)),
    "sizes_off_the_sublane_tiles": ([13, 9, 11, 2, 7], 48, 32, 24, (16, 16)),
    "a_group_over_three_tiles": ([3, 21, 2], 32, 32, 24, (8, 32)),
    "no_pair_at_all": ([0, 0, 0, 0], 24, 32, 24, (8, 16)),
    "rows_behind_the_last_group": ([2, 0, 3], 64, 32, 24, (8, 32)),
    "rows_not_a_multiple_of_the_tile": ([7, 6, 9], 27, 32, 24, (16, 32)),
    # Nemotron-3-Super's 1024 x 2688 and 2688 x 1024 over 16: N, then K, are
    # 21 lane-tiles' worth and no multiple of the tile beside them
    "nemotron_w1_scaled": ([4, 0, 7, 1], 16, 64, 168, (8, 64)),
    "nemotron_w2_scaled": ([4, 0, 7, 1], 16, 168, 64, (8, 56)),
    # Kimi-Linear's 2304 x 1024 and 1024 x 2304 over 16 (18 lane-tiles)
    "kimi_up_scaled": ([0, 9, 2, 5], 16, 144, 64, (16, 48)),
    "kimi_down_scaled": ([0, 9, 2, 5], 16, 64, 144, (16, 64)),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_is_the_loop_over_groups(case, dtype):
    sizes, P, K, N, tiles = CASES[case]
    rng = np.random.default_rng(len(case))
    a = jnp.asarray(rng.standard_normal((P, K)), dtype)
    w = jnp.asarray(rng.standard_normal((len(sizes), K, N)) * K ** -0.5, dtype)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = gd.grouped_dot(a, w, sizes, tiles=gd.GroupedSizes(*tiles))
    assert got.shape == (P, N) and got.dtype == jnp.float32
    want = oracle(a, w, sizes)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
    total = int(sizes.sum())
    assert not np.asarray(got)[total:].any()          # zeros, not leftovers
    # and it is what XLA's own grouped product gives
    ragged = jax.lax.ragged_dot(a, w, sizes,
                                preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ragged),
                               rtol=1e-5, atol=1e-5)


def test_sizes_are_data_and_one_program_serves_them():
    """Group sizes never reach a shape: one trace for every draw."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((6, 32, 16)), jnp.float32)
    traces = []

    @jax.jit
    def f(a, w, sizes):
        traces.append(1)
        return gd.grouped_dot(a, w, sizes, tiles=gd.GroupedSizes(8, 16))

    for sizes in ([5, 0, 9, 1, 0, 4], [0, 0, 0, 0, 0, 32], [0] * 6):
        sizes = jnp.asarray(sizes, jnp.int32)
        np.testing.assert_allclose(np.asarray(f(a, w, sizes)),
                                   oracle(a, w, sizes), rtol=1e-5, atol=1e-5)
    assert len(traces) == 1


def test_plan_visits_only_experts_with_rows():
    """[3, 0, 0, 12, 0, 2] over tiles of 8: expert 0 in tile 0, expert 3 in
    tiles 0-1, expert 5 (rows 15-16) in tiles 1-2: 5 visits and no step for
    an empty expert, one step of zeros for tile 3, and the rest of the static
    grid idle on the last step's blocks."""
    sizes = jnp.asarray([3, 0, 0, 12, 0, 2], jnp.int32)
    expert, lo, hi, tile, a_tile, first = map(
        np.asarray, gd.visit_plan(sizes, 32, 8))
    assert len(expert) == 6 + 4
    assert expert.tolist() == [0, 3, 3, 5, 5] + [5] * 5
    assert list(zip(lo.tolist(), hi.tolist()))[:5] == [
        (0, 3), (3, 15), (3, 15), (15, 17), (15, 17)]
    assert not (hi[5:] - lo[5:]).any()
    assert tile.tolist() == [0, 0, 1, 1, 2] + [3] * 5
    assert a_tile.tolist() == [0, 0, 1, 1, 2] + [2] * 5
    assert first.tolist() == [1, 0, 1, 0, 1, 1, 0, 0, 0, 0]


# (rows, K, N, held): the five routed cells' products on a decode tick and a
# tick with a 128-token slice (benchmarks/configs/*.json)
PUBLISHED = {
    "nemotron3super": [(64 * 22, 1024, 2688, 128), (192 * 22, 2688, 1024, 128)],
    "kimilinear": [(64 * 8, 2304, 1024, 32), (192 * 8, 1024, 2304, 32)],
    "deepseekv2": [(32 * 6, 5120, 1536, 40), (160 * 6, 1536, 5120, 40)],
    "mimov2flash": [(32 * 8, 4096, 2048, 16), (160 * 8, 2048, 4096, 16)],
    "glm52": [(32 * 8, 6144, 2048, 8), (160 * 8, 2048, 6144, 8)],
}


@pytest.mark.parametrize("cell", sorted(PUBLISHED))
def test_sizes_fit_the_budget_at_the_published_shapes(cell):
    for rows, K, N, held in PUBLISHED[cell]:
        tiles = gd.grouped_sizes(rows, K, N, 2)
        assert tiles.row_tile == gd.ROW_TILE == 64
        assert K % tiles.k_tile == 0
        assert tiles.k_tile == K or tiles.k_tile % 128 == 0
        assert gd.grouped_vmem_bytes(N, 2, tiles.row_tile, tiles.k_tile,
                                     K) <= gd.GROUPED_VMEM_BUDGET
        # a small expert is one piece: a visit is a step
        assert (tiles.k_tile == K) == (2 * K * N * 2 < 12 << 20)


def test_few_rows_take_one_tile_of_whole_sublanes():
    assert gd.grouped_sizes(24, 256, 128, 2).row_tile == 32      # bfloat16: 16
    assert gd.grouped_sizes(24, 256, 128, 4).row_tile == 24      # float32: 8
    assert gd.grouped_sizes(4224, 256, 128, 2) == gd.GroupedSizes(64, 256)


def test_product_is_ragged_dot_off_the_chip():
    """On the CPU `held_expert_ffn`'s dot is XLA's: no Pallas call in the
    jaxpr."""
    sizes = jnp.asarray([3, 5], jnp.int32)
    a, w = jnp.ones((8, 16), jnp.bfloat16), jnp.ones((2, 16, 8), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda a, w: gd.product(sizes)(a, w))(a, w))
    assert "ragged_dot" in text and "pallas_call" not in text


def test_product_is_the_kernel_on_a_tpu_and_a_layer_shares_one_plan(
        monkeypatch):
    """Where the backend is a TPU every product is the kernel's, and the two
    or three products of a layer are launched over ONE plan (traced here, not
    run: the jaxpr holds the Pallas calls and no `ragged_dot`)."""
    monkeypatch.setattr("ray_tpu.ops.is_tpu_backend", lambda: True)
    plans = []
    plan = gd.visit_plan
    monkeypatch.setattr(gd, "visit_plan",
                        lambda *a: plans.append(a[1:]) or plan(*a))
    sizes = jnp.asarray([3, 5], jnp.int32)
    a = jnp.ones((128, 256), jnp.bfloat16)
    w1, w2 = (jnp.ones(s, jnp.bfloat16) for s in ((2, 256, 384), (2, 384, 256)))

    def layer(a, w1, w2):
        dot = gd.product(sizes)
        return dot(dot(a, w1).astype(a.dtype), w2)

    text = str(jax.make_jaxpr(layer)(a, w1, w2))
    assert text.count("pallas_call") == 2 and "ragged_dot" not in text
    assert plans == [(128, 64)]
