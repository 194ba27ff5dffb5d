"""The latent cache (one pool, no head axis) through everything that carries
pages: `gather_pages` / `scatter_pages`, the prefix-store codec, the host
tier's spill and re-adoption, the cluster store, a `disagg` hand-off, and the
prefix cache. DeepSeek-V2 at tiny sizes on the CPU, float32, seeded weights:
"the same logits" and "the same tokens" below are bit for bit, because the
carried pages are copies and both sides run the same programs.
"""

import numpy as np
import pytest

import ray_tpu  # noqa: F401

PAGE = 8


def _i32(*values):
    return np.asarray(values, np.int32)


@pytest.fixture(scope="module")
def tiny(cpu_jax):
    from ray_tpu.models import deepseek_v2

    return deepseek_v2.DeepseekV2Config.tiny(experts_held=(0, 8))


def _params(config, seed=0):
    import jax

    from ray_tpu.models import deepseek_v2

    return deepseek_v2.init_params(config, jax.random.key(seed))


def _runner(config, params=None, num_blocks=16):
    from ray_tpu.llm.model_runner import ModelRunner

    return ModelRunner(config, params or _params(config),
                       num_blocks=num_blocks, block_size=PAGE, chunk_size=8,
                       attention_impl="reference")


def _tables(runner, ids):
    table = np.zeros((1, runner.max_blocks_per_seq), np.int32)
    table[0, :len(ids)] = ids
    return table


def _decode_logits(runner, ids, token, position):
    return np.asarray(runner.step(
        _i32([token]), _i32(position), _i32(position + 1), _i32(1),
        _tables(runner, ids)))


def _prompt(seed, n=17, vocab=256):
    return [(seed * 7 + 3 * i + seed) % vocab for i in range(n)]


def _cfg(config, **kw):
    from ray_tpu.llm.serving import LLMConfig

    base = dict(model_config=config, num_kv_blocks=64, block_size=PAGE,
                max_batch_size=4, prefill_chunk=8, warmup_buckets="off",
                stream_timeout_s=30.0)
    base.update(kw)
    return LLMConfig(**base)


def test_latent_pages_round_trip_in_the_wire_view(tiny):
    """One array, (L, 1, n, page, W): what `gather_pages` returns another
    runner's `scatter_pages` takes, and decodes the same logits from."""
    params = _params(tiny)
    a, b = _runner(tiny, params), _runner(tiny, params)
    assert [arr.name for arr in a.cache_arrays] == ["latent"]
    W = tiny.row_width
    assert a.cache["latent"].shape == (tiny.num_hidden_layers, 16, PAGE, W)
    prompt = _prompt(1, 19)
    ids_a, ids_b = [5, 2, 9], [1, 7, 3]
    chunk = np.zeros((1, 32), np.int32)
    chunk[0, :19] = prompt
    a.step(chunk, _i32(0), _i32(19), _i32(19), _tables(a, ids_a))
    pages = a.gather_pages(ids_a)
    assert len(pages) == 1
    (latent,) = pages
    assert latent.shape == (tiny.num_hidden_layers, 1, 3, PAGE, W)
    by_token = latent[:, 0].reshape(tiny.num_hidden_layers, -1, W)
    used = tiny.kv_lora_rank + tiny.qk_rope_head_dim
    assert by_token[:, :19, :used].any(axis=-1).all()
    assert not by_token[:, 19:].any()            # never written
    assert not by_token[..., used:].any()        # the row's padding
    (untouched,) = a.gather_pages([0, 15])
    assert not untouched.any()

    b.scatter_pages(ids_b, *pages)
    np.testing.assert_array_equal(
        _decode_logits(b, ids_b, 77, len(prompt)),
        _decode_logits(a, ids_a, 77, len(prompt)))
    (again,) = b.gather_pages(ids_b)
    np.testing.assert_array_equal(again[:, :, :2], latent[:, :, :2])
    with pytest.raises(ValueError, match="page arrays"):
        b.scatter_pages(ids_b, latent, latent)


def test_wire_helpers_and_codec_carry_any_number_of_arrays(tiny):
    from ray_tpu.llm.model_runner import (wire_concat, wire_nbytes,
                                          wire_page_count, wire_pages)
    from ray_tpu.llm.prefix_store import (decode_all, decode_pages,
                                          encode_pages)

    rng = np.random.RandomState(0)
    one = (rng.randn(3, 1, 4, PAGE, 128).astype(np.float32),)
    assert wire_page_count(one) == 4
    assert wire_nbytes(one) == one[0].nbytes
    head, tail = wire_pages(one, 0, 1), wire_pages(one, 1, 4)
    np.testing.assert_array_equal(wire_concat([head, tail])[0], one[0])
    meta, latent = decode_pages(encode_pages({"x": 1}, *one))
    assert meta["x"] == 1 and latent.dtype == np.float32
    np.testing.assert_array_equal(latent, one[0])
    # One-array and two-array records share a buffer and split apart again.
    k = rng.randn(2, 4, 1, PAGE, 16).astype(np.float32)
    records = decode_all(encode_pages({}, *one) + encode_pages({}, k, -k))
    assert [len(r) for r in records] == [2, 3]
    np.testing.assert_array_equal(records[1][2], -k)


def _engine(config, num_blocks=16, cluster_store=None):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.prefix_store import HostPrefixTier

    engine = LLMEngine(_runner(config, num_blocks=num_blocks),
                       max_batch_size=4, prefill_chunk=8,
                       enable_prefix_caching=True)
    tier = HostPrefixTier(8 << 20, low_watermark=0.8)
    engine.attach_prefix_store(host_tier=tier, cluster_store=cluster_store)
    return engine, tier


def test_host_tier_spills_and_readopts_latent_pages(tiny):
    """Pages evicted from the pool come back from host RAM: the re-admitted
    prompt decodes the same tokens and skips prefill for every promoted
    block."""
    from ray_tpu.llm.sampling import SamplingParams

    engine, tier = _engine(tiny)
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    system = _prompt(1, n=24)                       # 3 full blocks
    first = system + _prompt(2, n=6)
    ref = engine.generate([first], sp)[0].output_token_ids
    for s in range(3, 7):                           # churn the 16-page pool
        engine.generate([_prompt(s, n=40)], sp)
    assert len(tier) > 0 and tier.stats()["spills"] >= 3
    entry = tier.hottest(1)[0]
    assert entry["arrays"] == ["latent"] and "k" not in entry
    assert entry["latent"].shape[1:3] == (1, 1)
    before = engine.prefill_tokens_computed
    assert engine.generate([first], sp)[0].output_token_ids == ref
    assert engine.host_prefix_hits >= 3
    assert engine.prefill_tokens_computed - before <= len(first) + 1 - 24


def test_cluster_store_publishes_and_returns_latent_entries(tiny):
    """The codec's `arrays` names ride with a published entry, so a lookup
    returns it under the cache spec's names, not as a (K, V) pair."""
    import asyncio

    from ray_tpu.llm.prefix_store import ClusterPrefixStore, cluster_chain
    from ray_tpu.runtime.gcs.server import GcsServer

    srv = GcsServer()

    def transport(method, m, payload=b""):
        r = asyncio.run(getattr(srv, f"handle_{method}")(None, m, payload))
        return r.m, r.payload

    store = ClusterPrefixStore(PAGE, replica="a", transport=transport)
    tokens = list(range(1, PAGE + 1))
    latent = np.random.RandomState(1).randn(3, 1, 1, PAGE, 128).astype(
        np.float32)
    assert store.publish({"tokens": tokens, "arrays": ["latent"],
                          "latent": latent, "lora_name": "",
                          "weights_version": 0}, wait=True)
    got = ClusterPrefixStore(PAGE, replica="b", transport=transport) \
        .lookup_pages(cluster_chain(tokens, PAGE), weights_version=0)
    assert len(got) == 1 and tuple(got[0]["arrays"]) == ("latent",)
    np.testing.assert_array_equal(got[0]["latent"], latent)


def test_disagg_hand_off_of_a_latent_cache_decodes_the_same_tokens(tiny):
    from ray_tpu.llm.disagg import PrefillServer
    from ray_tpu.llm.serving import LLMServer

    decode = LLMServer(_cfg(tiny, disaggregate=1))
    prefill = PrefillServer(_cfg(tiny))
    single = LLMServer(_cfg(tiny))
    cold = None
    try:
        for req in ({"prompt": _prompt(1, 21), "max_tokens": 8},
                    {"prompt": _prompt(2, 21), "max_tokens": 8,
                     "temperature": 0.8, "top_k": 20, "seed": 1234}):
            res = prefill.prefill(req, decode.handoff_address())
            assert res["handoff"] and res["ack"]["ok"]
            out = decode.completions_collect(res["rid"])
            assert (out["choices"][0]["token_ids"]
                    == single.completions(req)["choices"][0]["token_ids"])
        assert decode.engine_stats()["handoffs_adopted"] == 2
        # The drain-time prefix push: cached latent pages to a replica that
        # has none of them.
        cold = LLMServer(_cfg(tiny))
        pushed = single.push_prefixes(cold.handoff_address())
        assert pushed["pushed"] >= 1 and "error" not in pushed
        assert len(cold.engine.block_manager.cached) >= pushed["pushed"]
    finally:
        for server in (decode, single, cold):
            if server is not None:
                server._handoff.close()


def test_a_prefix_hit_returns_the_uncached_runs_logits(tiny):
    """The second request with the same prompt attends over cached latent
    pages; its tokens and its last-position logits are the uncached run's."""
    from ray_tpu.llm.sampling import SamplingParams

    engine, _ = _engine(tiny, num_blocks=32)
    cold, _ = _engine(tiny, num_blocks=32)
    sp = SamplingParams(max_tokens=5, temperature=0.0)
    prompt = _prompt(4, n=29)
    first = engine.generate([prompt], sp)[0].output_token_ids
    saved = engine.block_manager.prefix_tokens_saved
    second = engine.generate([prompt], sp)[0].output_token_ids
    assert engine.block_manager.prefix_tokens_saved - saved >= 3 * PAGE
    assert second == first == cold.generate([prompt], sp)[0].output_token_ids
    # The logits behind it: decode one token over the cached pages and over
    # a fresh prefill of the same prompt.
    cached = engine.block_manager.cached
    hashes = engine.block_manager.prefix_hashes(prompt, 0)
    ids = [cached[h] for h in hashes[:3]]
    ids.append(next(i for i in range(32) if i not in ids))   # the new row's
    fresh = _runner(tiny)
    chunk = np.zeros((1, 32), np.int32)
    chunk[0, :24] = prompt[:24]
    fresh.step(chunk, _i32(0), _i32(24), _i32(24),
               _tables(fresh, [1, 2, 3, 4]))
    np.testing.assert_allclose(
        _decode_logits(engine.runner, ids, prompt[24], 24),
        _decode_logits(fresh, [1, 2, 3, 4], prompt[24], 24),
        rtol=0, atol=1e-5)


def test_the_tick_records_pairs_picks_and_expert_rows(tiny):
    """A unified tick's flight record: `attn_pairs`, `routed_rows` of the
    step the call DISPATCHED, `expert_rows`, `expert_rows_max` of the step it
    COMMITTED (the one dispatched a call earlier: one step of lookahead);
    `llm:prefill` gains `routed_rows`."""
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.util import tracing

    engine, _ = _engine(tiny, num_blocks=32)
    engine.generate([_prompt(5, n=11)], SamplingParams(max_tokens=3,
                                                       temperature=0.0))
    ticks = [t for t in engine.flight_records if t.get("kind") == "mixed"]
    picks = tiny.n_moe_layers * tiny.num_experts_per_tok
    first = ticks[0]                   # the prompt's first slice: 8 tokens
    assert first["attn_pairs"] == 8 * 9 // 2
    assert first["routed_rows"] == 8 * picks
    assert not first["lookahead"] and "expert_rows" not in first
    landed = ticks[1]                  # the call that commits that slice
    assert 0 <= landed["expert_rows_max"] <= landed["expert_rows"] \
        <= first["routed_rows"]
    # held experts that had a row, summed over the routed layers (PR 59)
    assert 0 <= landed["experts_met"] <= min(
        landed["expert_rows"], tiny.n_moe_layers * tiny.n_held)
    assert (landed["experts_met"] == 0) == (landed["expert_rows"] == 0)
    last = ticks[-2]                   # a decode row at context 13
    assert last["attn_pairs"] == last["kv_tokens"] == 13
    assert last["routed_rows"] == picks
    # the call that only lands the last step dispatches nothing
    assert ticks[-1]["routed_rows"] == 0 and ticks[-1]["expert_rows"] <= picks
    spans = [s for s in tracing.get_spans() if s["name"] == "llm:prefill"]
    assert spans and spans[-1]["args"]["routed_rows"] == 11 * picks
