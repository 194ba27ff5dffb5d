"""The KV pool's boundary: whatever layout the pool has on the device, pages
leave and enter a runner in the wire view (L, K, n, page, hd).

The disaggregated hand-off, session migration, adopt_request / adopt_prefix
and the prefix store's codec all read and write that view, and stored or
in-flight pages must stay readable: this pins the view to values computed
without the pool, and a gather -> scatter round trip to the logits."""

import numpy as np
import pytest

import ray_tpu  # noqa: F401

PAGE = 8


def _i32(*values):
    return np.asarray(values, np.int32)


def _reference_kv(params, config, tokens):
    """K (after RoPE) and V of every layer for `tokens`, each (L, T, K, hd),
    from the training model's own layer: no pool, no pages."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.ops.layers import apply_rope, rms_norm, rope_frequencies

    T, K, hd = len(tokens), config.n_kv_heads, config.head_dim
    cos, sin = rope_frequencies(hd, config.max_seq, config.rope_theta)
    x = params["embed"][jnp.asarray([tokens])].astype(config.dtype)
    ks, vs = [], []
    for layer in range(config.n_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        h = rms_norm(x, lp["attn_norm"], config.norm_eps)
        k = (h @ lp["wk"]).reshape(1, T, K, hd)
        ks.append(apply_rope(k, cos, sin, jnp.arange(T)[None])[0])
        vs.append((h @ lp["wv"]).reshape(T, K, hd))
        x = llama._layer(config, x, lp, cos, sin)
    return np.asarray(jnp.stack(ks)), np.asarray(jnp.stack(vs))


def _tables(runner, ids):
    table = np.zeros((1, runner.max_blocks_per_seq), np.int32)
    table[0, :len(ids)] = ids
    return table


def _decode_logits(runner, ids, token, position):
    return np.asarray(runner.step(
        _i32([token]), _i32(position), _i32(position + 1), _i32(1),
        _tables(runner, ids)))


@pytest.mark.parametrize("tp", [1, 2])
def test_pages_cross_the_boundary_in_the_wire_view(cpu_jax, tp):
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    config = llama.LlamaConfig.tiny(vocab_size=128, max_seq=64,
                                    dtype=jnp.float32)
    params = llama.init_params(config, jax.random.key(3))
    mesh = (build_mesh(MeshConfig(tp=tp), devices=jax.devices()[:tp])
            if tp > 1 else None)

    def runner():
        return ModelRunner(config, params, num_blocks=16, block_size=PAGE,
                           mesh=mesh, attention_impl="reference")

    a, b = runner(), runner()
    prompt = [int(t) for t in
              np.random.default_rng(0).integers(1, 128, size=19)]
    ids_a, ids_b = [5, 2, 9], [1, 7, 3]
    # 12 tokens through the rectangular backbone (padded to 16 columns),
    # then 7 through the token-major one (padded to 8 rows): both write the
    # pool, and both drop their padding.
    chunk = np.zeros((1, 16), np.int32)
    chunk[0, :12] = prompt[:12]
    a.step(chunk, _i32(0), _i32(12), _i32(12), _tables(a, ids_a))
    flat = np.zeros(8, np.int32)
    flat[:7] = prompt[12:]
    zeros = np.zeros((1, 1), np.int32)
    a.step_mixed(flat, _i32(12), _i32(19), _i32(0, 7), _tables(a, ids_a),
                 zeros + 6, zeros, _i32(0), np.zeros(1, np.float32), _i32(0),
                 np.ones(1, np.float32), _i32(0), _i32(0))

    k, v = a.gather_pages(ids_a)
    L, K, hd = config.n_layers, config.n_kv_heads, config.head_dim
    assert k.shape == v.shape == (L, K, len(ids_a), PAGE, hd)
    ref_k, ref_v = _reference_kv(params, config, prompt)
    for got, ref in ((k, ref_k), (v, ref_v)):
        # (L, K, n, page, hd) -> (L, n * page, K, hd): token t of head kh
        # sits at [l, kh, t // page, t % page].
        by_token = got.transpose(0, 2, 3, 1, 4).reshape(L, -1, K, hd)
        np.testing.assert_allclose(by_token[:, :len(prompt)], ref,
                                   rtol=1e-5, atol=1e-5)
        # Slots past the prompt, and padding rows, were never written.
        assert not by_token[:, len(prompt):].any()
    untouched, _ = a.gather_pages([0, 15])
    assert not untouched.any()

    b.scatter_pages(ids_b, k, v)
    np.testing.assert_array_equal(
        _decode_logits(b, ids_b, 77, len(prompt)),
        _decode_logits(a, ids_a, 77, len(prompt)))
    k2, v2 = b.gather_pages(ids_b)
    np.testing.assert_array_equal(k2[:, :, :2], k[:, :, :2])
    np.testing.assert_array_equal(v2[:, :, :2], v[:, :, :2])
