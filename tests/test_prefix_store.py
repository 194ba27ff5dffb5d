"""Cluster-wide tiered KV prefix store (llm/prefix_store.py).

Tier 1 (host RAM spill pool) and tier 2 (the GCS-homed cluster prefix
table) are exercised cluster-free: the host tier against a real engine,
the cluster tier through a direct transport bridge onto a GcsServer
instance — the same handler code the wire hits, without sockets. The
proofs mirror the migration-wire suite: bit-identical tokens vs a fresh
prefill, zero re-prefill via the prefill-token counter, zero pickled
bytes via the sanitizer window, and whole-or-nothing on torn streams.
"""

import asyncio
import socket
import threading
import time

import numpy as np
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


def _engine(config, num_blocks=16, host_mb=8.0, cluster_store=None,
            low_watermark=0.8, host_capacity=None):
    """Fresh engine + tiers. Small pool so evictions (spills) happen."""
    import jax

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.prefix_store import HostPrefixTier
    from ray_tpu.models import llama

    params = llama.init_params(config, jax.random.key(0))
    runner = ModelRunner(config, params, num_blocks=num_blocks,
                         block_size=8, chunk_size=8)
    engine = LLMEngine(runner, max_batch_size=4, prefill_chunk=8,
                       enable_prefix_caching=True)
    tier = None
    if host_mb:
        cap = (host_capacity if host_capacity is not None
               else int(host_mb * (1 << 20)))
        tier = HostPrefixTier(cap, low_watermark=low_watermark)
    engine.attach_prefix_store(host_tier=tier, cluster_store=cluster_store)
    return engine, tier


def _gcs_bridge():
    """A GcsServer instance + a ClusterPrefixStore transport that calls
    its prefix handlers directly (the real table logic, no sockets)."""
    from ray_tpu.runtime.gcs.server import GcsServer

    srv = GcsServer()

    def transport(method, m, payload=b""):
        handler = getattr(srv, f"handle_{method}")
        r = asyncio.run(handler(None, m, payload))
        return r.m, r.payload

    return srv, transport


# --------------------------------------------------------------- page codec


def test_page_codec_roundtrip_and_truncation(cpu_jax):
    from ray_tpu.llm.prefix_store import (TruncatedSpillError, decode_all,
                                          decode_pages, encode_pages)

    rng = np.random.RandomState(0)
    k = rng.randn(2, 4, 1, 8, 16).astype(np.float32)
    v = rng.randn(2, 4, 1, 8, 16).astype(np.float32)
    buf = encode_pages({"x": 1}, k, v)
    meta, k2, v2 = decode_pages(buf)
    assert meta["x"] == 1
    assert np.array_equal(k, k2) and np.array_equal(v, v2)
    assert k2.dtype == k.dtype
    # Frames are self-delimiting: concatenated buffers split back apart.
    triples = decode_all(buf + encode_pages({}, v, k))
    assert len(triples) == 2
    assert np.array_equal(triples[1][1], v)
    # A torn buffer adopts nothing — whole-or-nothing.
    with pytest.raises(TruncatedSpillError):
        decode_all(buf[:-7])


# ---------------------------------------------------------------- host tier


def test_host_tier_lru_watermark_demotes(cpu_jax):
    from ray_tpu.llm.prefix_store import HostPrefixTier

    demoted = []
    one = np.zeros(256, dtype=np.float32)  # 1 KiB per array
    tier = HostPrefixTier(5 * 2048, low_watermark=0.5,
                          on_demote=demoted.append)
    for i in range(5):
        tier.put(bytes([i]) * 8, {"tokens": (i,), "k": one, "v": one,
                                  "lora_slot": 0, "lora_name": "",
                                  "weights_version": 0, "nbytes": 2048})
    assert not demoted and tier.bytes == 5 * 2048
    tier.get(bytes([0]) * 8)  # touch: 0 becomes MRU
    tier.put(b"\x09" * 8, {"tokens": (9,), "k": one, "v": one,
                           "lora_slot": 0, "lora_name": "",
                           "weights_version": 0, "nbytes": 2048})
    # Crossed the high watermark: demote LRU-first down to 50%.
    assert demoted and tier.bytes <= 3 * 2048
    assert [e["tokens"] for e in demoted[:2]] == [(1,), (2,)]
    assert tier.get(bytes([0]) * 8) is not None   # MRU survived
    assert tier.get(bytes([1]) * 8) is None       # demoted
    assert tier.stats()["demotions"] == len(demoted)


def test_host_tier_spill_readmit_bit_identical_zero_reprefill(
        setup, pickle_sanitizer):
    """The tier-1 tentpole proof: pages evicted from the device pool come
    back from host RAM — the re-admitted prompt decodes bit-identically to
    a fresh engine AND skips prefill for every promoted block, with zero
    pickled bytes anywhere on the spill/promote path."""
    from ray_tpu.llm.sampling import SamplingParams

    engine, tier = _engine(setup, num_blocks=16)
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    system = _prompt(1, n=24)                       # 3 full blocks
    a1 = system + _prompt(2, n=6)
    ref = engine.generate([a1], sp)[0].output_token_ids

    w = pickle_sanitizer.window()
    with w:
        # Unrelated traffic churns the 16-block pool until A's parked
        # blocks are evicted — which now spills them to the host tier.
        for s in range(3, 7):
            engine.generate([_prompt(s, n=40)], sp)
        assert len(tier) > 0 and tier.stats()["spills"] >= 3
        assert engine.block_manager.cached.get(
            engine.block_manager.prefix_hashes(system, 0)[-1]) is None

        computed_before = engine.prefill_tokens_computed
        out = engine.generate([a1], sp)[0].output_token_ids
    assert out == ref
    # All 3 system blocks promoted from host RAM: only the tail prefilled.
    assert engine.host_prefix_hits >= 3
    assert engine.host_prefix_tokens_saved >= 24
    assert engine.prefill_tokens_computed - computed_before \
        <= len(a1) + 1 - 24
    w.assert_zero_pickle()
    s = engine.stats()
    assert s["host_prefix_entries"] == len(tier)
    assert s["host_prefix_hits"] == engine.host_prefix_hits


def test_update_weights_clears_host_tier_and_bumps_version(setup):
    from ray_tpu.llm.sampling import SamplingParams

    engine, tier = _engine(setup, num_blocks=16)
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    engine.generate([_prompt(1, n=24)], sp)
    for s in range(3, 7):
        engine.generate([_prompt(s, n=40)], sp)
    assert len(tier) > 0
    v0 = engine.weights_version
    engine.update_weights(engine.runner.params)
    assert engine.weights_version == v0 + 1
    # Host-tier KV was computed under the old weights: gone, wholesale.
    assert len(tier) == 0 and tier.bytes == 0


# ------------------------------------- eviction spills: batched, off-thread
#
# Evicting records; one gather a tick is dispatched before the step that
# overwrites the victims' pages; a worker thread lands them in the tier
# (llm/engine.py, "eviction spills"; HostPrefixTier.reserve / land).


def _entry(i, nbytes=2048, arrays=True):
    one = np.full(nbytes // 8, i, dtype=np.float32)
    e = {"tokens": (i,), "lora_slot": 0, "lora_name": "",
         "weights_version": 0, "nbytes": nbytes}
    if arrays:
        e.update(arrays=["k", "v"], k=one, v=one)
    return e


def _tier_state(tier):
    return [(d, e["tokens"]) for d, e in tier._entries.items()], tier.bytes


@pytest.mark.parametrize("hook", [False, True], ids=["alone", "demoting"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reserve_and_land_leave_what_one_put_a_page_leaves(cpu_jax, seed,
                                                           hook):
    """The tier's rule, whichever way pages come in: random bursts through
    reserve + land (landing late, out of step with the gets between bursts)
    leave the entries, their LRU order, the bytes and the demoted set that
    one put a page leaves; what reserve does not ask for is exactly what a
    burst pushes out again with nobody to publish it."""
    from ray_tpu.llm.prefix_store import HostPrefixTier

    rng = np.random.RandomState(seed)
    out_a, out_b = [], []
    kw = dict(low_watermark=float(rng.choice([0.3, 0.8, 1.0])))
    a = HostPrefixTier(6 * 2048, on_demote=out_a.append if hook else None,
                       **kw)
    b = HostPrefixTier(6 * 2048, on_demote=out_b.append if hook else None,
                       **kw)
    late, asked, unasked, i = [], 0, 0, 0
    for _ in range(30):
        burst = [(bytes([i + j]) * 8, i + j)
                 for j in range(int(rng.randint(1, 12)))]
        i += len(burst)
        for d, j in burst:
            a.put(d, _entry(j))
        spills = b.reserve([(d, _entry(j, arrays=False)) for d, j in burst])
        asked += sum(s is not None for s in spills)
        unasked += sum(s is None for s in spills)
        if not hook:    # what is not asked for is gone when the burst ends
            assert [d for (d, _), s in zip(burst, spills) if s is None] == [
                d for d, _ in burst if d not in a._entries]
        late.extend((s, _entry(j)) for (_, j), s in zip(burst, spills)
                    if s is not None)
        assert _tier_state(a) == _tier_state(b)
        while late and rng.rand() < 0.6:        # land some, in order
            s, full = late.pop(0)
            b.land(s, {k: full[k] for k in ("arrays", "k", "v")})
        for d in [bytes([int(rng.randint(0, i))]) * 8 for _ in range(3)]:
            spill = b._inflight.get(d)
            if spill is not None:       # a get would wait for this one
                late.remove(next(x for x in late if x[0] is spill))
                b.land(spill, {k: _entry(spill.entry["tokens"][0])[k]
                               for k in ("arrays", "k", "v")})
            ea, eb = a.get(d), b.get(d)
            assert (ea is None) == (eb is None)
            if ea is not None:
                assert np.array_equal(ea["k"], eb["k"])
    for s, full in late:
        b.land(s, {k: full[k] for k in ("arrays", "k", "v")})
    assert b.drain(timeout=1.0) and b.stats()["inflight"] == 0
    assert _tier_state(a) == _tier_state(b)
    assert all(np.array_equal(a._entries[d]["v"], b._entries[d]["v"])
               for d in a._entries)
    assert a.stats()["demotions"] == b.stats()["demotions"]
    assert asked == b.stats()["spills"] and asked + unasked == i
    if hook:
        assert unasked == 0
        assert sorted(e["tokens"] for e in out_a) == sorted(
            e["tokens"] for e in out_b)
        assert all("k" in e for e in out_b)
    else:
        assert unasked > 0


def test_failed_landing_withdraws_the_entry(cpu_jax):
    from ray_tpu.llm.prefix_store import HostPrefixTier

    tier = HostPrefixTier(8 * 2048)
    s0, s1 = tier.reserve([(b"a" * 8, _entry(0, arrays=False)),
                           (b"b" * 8, _entry(1, arrays=False))])
    tier.land(s0, None)
    tier.land(s1, {"arrays": ["k", "v"], "k": 1, "v": 2})
    assert tier.get(b"a" * 8) is None and tier.bytes == 2048
    assert tier.get(b"b" * 8)["k"] == 1 and len(tier) == 1


def _spill_engine(setup, *, pages, hook=None, low=0.8):
    """A 16-page pool and a host tier of `pages` pages, every eviction
    recorded as the block manager hands it over."""
    import jax

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.prefix_store import HostPrefixTier
    from ray_tpu.models import llama

    params = llama.init_params(setup, jax.random.key(0))
    runner = ModelRunner(setup, params, num_blocks=16, block_size=8,
                         chunk_size=8)
    engine = LLMEngine(runner, max_batch_size=4, prefill_chunk=8,
                       enable_prefix_caching=True)
    tier = HostPrefixTier(pages * runner.page_nbytes, low_watermark=low,
                          on_demote=hook)
    engine.attach_prefix_store(host_tier=tier)
    evicted = []
    note = engine.block_manager.spill_fn

    def spill_fn(bid, h, meta):
        # The page as it is when it is evicted: what must reach the tier.
        evicted.append((h, meta, runner.gather_pages([bid])))
        note(bid, h, meta)

    engine.block_manager.spill_fn = spill_fn
    return engine, tier, evicted


def _park_then_burst(engine, sp):
    """Park 12 full pages of three finished prompts, then admit one prompt
    whose 13 pages evict nine of them in one allocation."""
    for s in (1, 2, 3):
        engine.generate([_prompt(s, n=33)], sp)
    assert len(engine.block_manager.reusable) == 12
    engine.flight_records.clear()
    engine.generate([_prompt(9, n=100)], sp)
    engine.settle_spills()
    return list(engine.flight_records)


def _per_page(engine, tier, evicted, hook=None):
    """The parent's path over the same evictions: one put a victim."""
    from ray_tpu.llm.prefix_store import HostPrefixTier

    ref = HostPrefixTier(tier.capacity_bytes, low_watermark=tier.low,
                         on_demote=hook)
    for h, (slot, name, prompt, length), pages in evicted:
        ref.put(h, {"tokens": prompt[:length],
                    **engine._entry_fields(pages),
                    "lora_slot": slot, "lora_name": name,
                    "weights_version": 0,
                    "nbytes": engine.runner.page_nbytes})
    return ref


def _same_entries(got, want):
    assert [e["digest"] for e in got] == [e["digest"] for e in want]
    for g, w in zip(got, want):
        assert g["arrays"] == w["arrays"] and g["tokens"] == w["tokens"]
        assert g["nbytes"] == w["nbytes"] and g["lora_name"] == w["lora_name"]
        for name in g["arrays"]:
            assert g[name].dtype == w[name].dtype
            assert np.array_equal(g[name], w[name])


def test_eviction_burst_larger_than_tier_keeps_newest_victims(setup):
    """(a) Nine pages evicted by one allocation into a tier of four: the tier
    ends holding what one put a page would have left (the newest victims,
    each page as gather_pages read it before the eviction), the rest were
    never read, and gathered + skipped = evictions."""
    from ray_tpu.llm.sampling import SamplingParams

    engine, tier, evicted = _spill_engine(setup, pages=4)
    ticks = _park_then_burst(engine, SamplingParams(max_tokens=2))
    assert len(evicted) >= 9
    ref = _per_page(engine, tier, evicted)
    _same_entries(list(tier._entries.values()), list(ref._entries.values()))
    assert [e["digest"] for e in tier._entries.values()] == [
        h for h, _, _ in evicted[-len(tier):]]
    assert tier.bytes == ref.bytes and 0 < len(tier) <= 4
    gathered = sum(t["spill_pages"] for t in ticks)
    skipped = sum(t["spill_skipped"] for t in ticks)
    assert gathered + skipped == len(evicted) and skipped >= 5
    st = engine.stats()
    assert st["host_prefix_spills"] == gathered == tier.stats()["spills"]
    assert st["host_prefix_spills_skipped"] == skipped
    assert st["host_prefix_spills_failed"] == 0
    assert st["host_prefix_spills_inflight"] == 0
    assert max(t["spill_ms"] for t in ticks) > 0.0


def test_eviction_burst_with_demotion_hook_publishes_every_victim(setup):
    """(b) With somewhere to demote to, nothing is skipped: every victim the
    burst pushes out of the tier reaches the hook with its pages."""
    from ray_tpu.llm.sampling import SamplingParams

    demoted, want = [], []
    engine, tier, evicted = _spill_engine(setup, pages=4, low=0.5,
                                          hook=demoted.append)
    ticks = _park_then_burst(engine, SamplingParams(max_tokens=2))
    ref = _per_page(engine, tier, evicted, hook=want.append)
    assert sum(t["spill_skipped"] for t in ticks) == 0
    assert sum(t["spill_pages"] for t in ticks) == len(evicted)
    _same_entries(list(tier._entries.values()), list(ref._entries.values()))
    assert len(demoted) == len(evicted) - len(tier) >= 5
    _same_entries(sorted(demoted, key=lambda e: e["digest"]),
                  sorted(want, key=lambda e: e["digest"]))


def test_readmit_while_spill_in_flight_promotes_bit_identical(setup):
    """(c) The spill -> re-admit proof, raced: the pages are still on their
    way to the host when the prompt comes back. It is promoted from the host
    tier all the same (the tier answers once they land): zero re-prefill of
    the promoted blocks, tokens bit-identical."""
    from ray_tpu.llm.sampling import SamplingParams

    engine, tier = _engine(setup, num_blocks=16)
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    system = _prompt(1, n=24)
    a1 = system + _prompt(2, n=6)
    ref = engine.generate([a1], sp)[0].output_token_ids
    land = engine._land_spills

    def slow_land(*args):
        time.sleep(0.25)
        land(*args)

    engine._land_spills = slow_land
    for s in range(3, 7):
        engine.generate([_prompt(s, n=40)], sp)
    assert engine.block_manager.cached.get(
        engine.block_manager.prefix_hashes(system, 0)[-1]) is None
    assert engine.stats()["host_prefix_spills_inflight"] > 0
    computed_before = engine.prefill_tokens_computed
    out = engine.generate([a1], sp)[0].output_token_ids
    assert out == ref
    assert engine.host_prefix_hits >= 3
    assert engine.prefill_tokens_computed - computed_before \
        <= len(a1) + 1 - 24
    engine.settle_spills()
    assert engine.stats()["host_prefix_spills_inflight"] == 0


@pytest.mark.parametrize("penalty", [1.0, 1.3],
                         ids=["device_sampled", "host_sampled"])
def test_one_spill_dispatch_a_tick_and_none_from_allocate(setup, penalty):
    """(d) A counting runner: whatever the number of evictions, allocating
    calls nothing on the device, a tick dispatches at most one gather, the
    synchronous gather_pages is never used, and no program that writes the
    pool (either head of the mixed step among them) is dispatched while a
    recorded victim is still unread."""
    from ray_tpu.llm.sampling import SamplingParams

    engine, tier, _ = _spill_engine(setup, pages=4)
    runner, bm = engine.runner, engine.block_manager
    bm.spill_fn = engine._note_eviction     # the recorder reads pages
    calls = {"async": 0, "sync": 0, "writes": 0}

    def counted(name, fn, key):
        def call(*a, **kw):
            calls[key] += 1
            if key == "writes":
                assert not engine._pending_spills, name
            return fn(*a, **kw)
        setattr(runner, name, call)

    counted("gather_pages_async", runner.gather_pages_async, "async")
    counted("gather_pages", runner.gather_pages, "sync")
    for name in ("step", "step_mixed", "step_mixed_logits", "scatter_pages"):
        counted(name, getattr(runner, name), "writes")
    sp = SamplingParams(max_tokens=3, repetition_penalty=penalty)
    for s in (1, 2, 3):
        engine.generate([_prompt(s, n=33)], sp)
    assert len(bm.reusable) == 12 and calls["async"] == 0
    engine.add_request(_prompt(9, n=100), sp)
    engine.add_request(_prompt(8, n=20), sp)
    most = 0
    while engine.has_unfinished():
        before = calls["async"]
        engine.step()
        most = max(most, calls["async"] - before)
    assert most == 1 and calls["sync"] == 0 and calls["writes"] > 0
    assert engine.stats()["host_prefix_spills_skipped"] >= 5
    # allocate() alone, evicting every parked page: not one device call.
    from ray_tpu.llm.engine import _Request

    engine.settle_spills()
    parked, before = len(bm.reusable), dict(calls)
    assert parked >= 8
    req = _Request("direct", _prompt(7, n=100), sp)
    assert bm.allocate(req, (len(bm.free) + parked) * 8)
    assert calls == before and len(engine._pending_spills) == parked
    engine.settle_spills()
    assert calls["async"] == before["async"] + 1 and calls["sync"] == 0


def test_update_weights_during_spill_leaves_no_old_version(setup):
    """(e) Pages on their way to the host when the weights are swapped were
    computed under the old ones: none of them lands in the tier."""
    from ray_tpu.llm.sampling import SamplingParams

    engine, tier = _engine(setup, num_blocks=16)
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    land = engine._land_spills

    def slow_land(*args):
        time.sleep(0.3)
        land(*args)

    engine._land_spills = slow_land
    engine.generate([_prompt(1, n=24)], sp)
    for s in range(3, 7):
        engine.generate([_prompt(s, n=40)], sp)
    assert engine.stats()["host_prefix_spills_inflight"] > 0
    engine.update_weights(engine.runner.params)
    assert len(tier) == 0 and tier.bytes == 0
    engine.settle_spills()
    assert len(tier) == 0 and tier.bytes == 0
    engine._land_spills = land
    for s in range(7, 11):
        engine.generate([_prompt(s, n=40)], sp)
    engine.settle_spills()
    assert len(tier) > 0
    assert {e["weights_version"] for e in tier._entries.values()} == {
        engine.weights_version}
    assert all("k" in e for e in tier._entries.values())


def test_failed_spill_is_counted_and_the_engine_goes_on(setup, caplog):
    """A spill that fails is a future cache miss: counted, logged, its
    entries withdrawn, and the requests are served as if nothing happened."""
    from ray_tpu.llm.sampling import SamplingParams

    engine, tier = _engine(setup, num_blocks=16)
    sp = SamplingParams(max_tokens=4, temperature=0.0)

    def broken(ids):
        raise RuntimeError("no staging memory")

    engine.runner.gather_pages_async = broken
    want = [engine.generate([_prompt(s, n=40)], sp)[0].output_token_ids
            for s in range(3, 7)]
    st = engine.stats()
    assert st["host_prefix_spills_failed"] > 0
    assert st["host_prefix_spills_failed"] == st["host_prefix_spills"]
    assert len(tier) == 0 and tier.bytes == 0
    assert st["host_prefix_spills_inflight"] == 0
    assert "evicted prefix pages" in caplog.text
    fresh, _ = _engine(setup, num_blocks=16, host_mb=0)
    assert want == [
        fresh.generate([_prompt(s, n=40)], sp)[0].output_token_ids
        for s in range(3, 7)]


# ------------------------------------------------- tier 2: the GCS table


def test_cluster_publish_lookup_roundtrip_zero_pickle(cpu_jax,
                                                      pickle_sanitizer):
    from ray_tpu.llm.prefix_store import ClusterPrefixStore, cluster_chain

    srv, transport = _gcs_bridge()
    store = ClusterPrefixStore(8, replica="owner-1", deployment="llm",
                               transport=transport)
    rng = np.random.RandomState(1)
    tokens = list(range(1, 17))                     # 2 blocks of 8
    chain = cluster_chain(tokens, 8)
    k = {}
    w = pickle_sanitizer.window()
    with w:
        for j in (0, 1):
            blk = tokens[:(j + 1) * 8]
            k[j] = rng.randn(2, 4, 1, 8, 16).astype(np.float32)
            assert store.publish(
                {"tokens": blk, "k": k[j], "v": k[j] * 2, "lora_name": "",
                 "weights_version": 0}, wait=True)
        adopter = ClusterPrefixStore(8, replica="survivor-2",
                                     deployment="llm", transport=transport)
        got = adopter.lookup_pages(chain, weights_version=0)
    assert len(got) == 2
    for j, e in enumerate(got):
        assert e["tokens"] == tokens[:(j + 1) * 8]
        assert np.array_equal(e["k"], k[j])
        assert np.array_equal(e["v"], k[j] * 2)
    w.assert_zero_pickle()
    assert w.counters["deserialize_fast"] >= 4    # k + v per block
    # The adopter now holds the pages hot: it becomes the live-owner hint.
    hit = store.lookup_owner(chain)
    assert hit and hit["owner_replica"] == "survivor-2"
    assert hit["n_blocks"] == 2


def test_cluster_stale_weights_never_adopted(cpu_jax):
    from ray_tpu.llm.prefix_store import ClusterPrefixStore, cluster_chain

    srv, transport = _gcs_bridge()
    store = ClusterPrefixStore(8, replica="r", transport=transport)
    tokens = list(range(8))
    pages = np.ones((2, 4, 1, 8, 16), dtype=np.float32)
    assert store.publish({"tokens": tokens, "k": pages, "v": pages,
                          "lora_name": "", "weights_version": 1}, wait=True)
    chain = cluster_chain(tokens, 8)
    # An engine on weights v2 must never see v1 KV: server-side exact gate.
    assert store.lookup_pages(chain, weights_version=2) == []
    # The metadata probe (version 0 = any) still sees the row...
    assert store.lookup_owner(chain)["owner_replica"] == "r"
    # ...and version-targeted GC drops it.
    store.purge(below_weights_version=2, wait=True)
    assert store.lookup_owner(chain) is None


def test_cluster_purge_owner_hint_vs_drop(cpu_jax):
    """Replica death blanks the live-owner HINT but the pages stay
    adoptable (they are GCS-homed — surviving the owner is the point);
    deployment deletion drops rows outright."""
    from ray_tpu.llm.prefix_store import ClusterPrefixStore, cluster_chain

    srv, transport = _gcs_bridge()
    store = ClusterPrefixStore(8, replica="dead-1", deployment="llm",
                               transport=transport)
    tokens = list(range(8))
    pages = np.ones((2, 4, 1, 8, 16), dtype=np.float32)
    assert store.publish({"tokens": tokens, "k": pages, "v": pages,
                          "lora_name": "", "weights_version": 0}, wait=True)
    chain = cluster_chain(tokens, 8)
    n = store.purge(owner_replica="dead-1", clear_owner_only=True,
                    wait=True)
    assert n == 1
    hit = store.lookup_owner(chain)
    assert hit is not None and hit["owner_replica"] == ""
    reader = ClusterPrefixStore(8, replica="", transport=transport)
    assert len(reader.lookup_pages(chain, weights_version=0)) == 1
    assert store.purge(deployment="llm", wait=True) == 1
    assert store.lookup_owner(chain) is None


def test_gcs_node_death_clears_owner_hints_same_tick(cpu_jax):
    """_mark_node_dead prunes the prefix table's owner hints exactly like
    dead-node metrics keys — same tick, same code path."""
    from ray_tpu.llm.prefix_store import ClusterPrefixStore, cluster_chain
    from ray_tpu.runtime import wire

    srv, transport = _gcs_bridge()
    store = ClusterPrefixStore(8, replica="r-on-node", transport=transport)
    pages = np.ones((2, 4, 1, 8, 16), dtype=np.float32)

    def publish(tokens, node):
        m = wire.PrefixEntryMsg(
            digest=cluster_chain(tokens, 8)[-1], lora_id="",
            weights_version=0, block_size=8, n_tokens=len(tokens),
            token_ids=tokens, nbytes=1, owner_replica="r-on-node",
            node_id=node, deployment="llm").encode()
        from ray_tpu.llm.prefix_store import encode_pages

        transport("prefix_upsert", m, encode_pages({}, pages, pages))

    publish(list(range(8)), b"nodeA")
    publish(list(range(8, 16)), b"nodeB")
    srv._purge_prefix_entries(node_id=b"nodeA", clear_owner_only=True)
    a = store.lookup_owner(cluster_chain(list(range(8)), 8))
    b = store.lookup_owner(cluster_chain(list(range(8, 16)), 8))
    assert a["owner_replica"] == "" and b["owner_replica"] == "r-on-node"
    # Both rows still adoptable.
    assert len(store.lookup_pages(cluster_chain(list(range(8)), 8),
                                  weights_version=0)) == 1


def test_gcs_table_byte_capacity_lru(cpu_jax):
    from ray_tpu.llm.prefix_store import ClusterPrefixStore, cluster_chain

    srv, transport = _gcs_bridge()
    # k+v = 2 x 2 KiB arrays + framing: ~4.4 KiB per entry; room for ~3.
    srv.PREFIX_STORE_CAPACITY = 13_500
    store = ClusterPrefixStore(8, replica="r", transport=transport)
    pages = np.ones((2, 4, 1, 8, 8), dtype=np.float32)
    chains = []
    for i in range(5):
        tokens = list(range(8 * i, 8 * i + 8))
        chains.append(cluster_chain(tokens, 8))
        assert store.publish({"tokens": tokens, "k": pages, "v": pages,
                              "lora_name": "", "weights_version": 0},
                             wait=True)
    assert srv._prefix_bytes <= srv.PREFIX_STORE_CAPACITY
    assert store.lookup_owner(chains[0]) is None      # LRU-evicted
    assert store.lookup_owner(chains[-1]) is not None  # newest survives


# ----------------------------------------- engine adoption from the store


def test_survivor_adopts_spilled_prefix_bit_identical(setup,
                                                      pickle_sanitizer):
    """The cross-replica proof at unit cost: the owner engine's working
    set demotes host-tier -> cluster table; a SEPARATE engine (fresh
    device pool, same weights) serves the shared prompt by adopting from
    the table — zero re-prefill for the prefix, bit-identical tokens,
    zero pickle on the wire path."""
    from ray_tpu.llm.prefix_store import ClusterPrefixStore
    from ray_tpu.llm.sampling import SamplingParams

    srv, transport = _gcs_bridge()
    owner_store = ClusterPrefixStore(8, replica="owner", deployment="llm",
                                     transport=transport)
    # Tiny host tier: watermark pressure demotes into the cluster table.
    owner, owner_tier = _engine(setup, num_blocks=16,
                                cluster_store=owner_store,
                                host_capacity=48 << 10, low_watermark=0.3)
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    system = _prompt(1, n=24)
    a1 = system + _prompt(2, n=6)
    ref = owner.generate([a1], sp)[0].output_token_ids
    for s in range(3, 8):
        owner.generate([_prompt(s, n=40)], sp)
    assert owner_store.published >= 3, owner_tier.stats()

    # The owner is dead now. A survivor with its own pool adopts.
    surv_store = ClusterPrefixStore(8, replica="survivor",
                                    deployment="llm", transport=transport)
    survivor, _ = _engine(setup, num_blocks=16, host_mb=0,
                          cluster_store=surv_store)
    w = pickle_sanitizer.window()
    with w:
        computed_before = survivor.prefill_tokens_computed
        out = survivor.generate([a1], sp)[0].output_token_ids
    assert out == ref
    assert survivor.cluster_prefix_hits >= 3
    assert survivor.cluster_prefix_tokens_saved >= 24
    assert survivor.prefill_tokens_computed - computed_before \
        <= len(a1) + 1 - 24
    w.assert_zero_pickle()
    s = survivor.stats()
    assert s["cluster_prefix_adopted_blocks"] >= 3


def test_forged_table_tokens_rejected_at_adoption(setup):
    """Token verification is the adoption-side anti-forgery check: a table
    row whose token_ids don't match the adopter's prompt bytes is skipped
    (the salt is fixed cluster-wide, so digests alone prove nothing)."""
    from ray_tpu.llm.prefix_store import ClusterPrefixStore, cluster_chain
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.runtime import wire
    from ray_tpu.llm.prefix_store import encode_pages

    srv, transport = _gcs_bridge()
    system = _prompt(1, n=8)
    # Forge: correct digest for `system`, but alien tokens + garbage KV.
    pages = np.zeros((2, 4, 1, 8, 16), dtype=np.float32)
    m = wire.PrefixEntryMsg(
        digest=cluster_chain(system, 8)[-1], lora_id="",
        weights_version=0, block_size=8, n_tokens=8,
        token_ids=[99] * 8, nbytes=1, owner_replica="evil",
        deployment="llm").encode()
    transport("prefix_upsert", m, encode_pages({}, pages, pages))

    store = ClusterPrefixStore(8, replica="victim", transport=transport)
    engine, _ = _engine(setup, num_blocks=16, host_mb=0,
                        cluster_store=store)
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    out = engine.generate([system + [5]], sp)[0].output_token_ids
    assert engine.cluster_prefix_hits == 0        # verification refused it
    plain, _ = _engine(setup, num_blocks=16, host_mb=0)
    assert out == plain.generate([system + [5]], sp)[0].output_token_ids


# ------------------------------------------- drain-plane prefix push wire


def test_push_prefixes_warms_target_zero_reprefill(setup, pickle_sanitizer):
    """Drain path: the victim streams its hottest parked prefix pages to
    the target over the handoff wire; the target then serves the shared
    prompt without re-prefilling the pushed blocks."""
    from ray_tpu.llm.serving import LLMServer

    src = LLMServer(_cfg(setup))
    dst = LLMServer(_cfg(setup))
    try:
        system = _prompt(1, n=24)
        req = {"prompt": system + _prompt(2, n=6), "max_tokens": 6}
        ref = src.completions(req)
        w = pickle_sanitizer.window()
        with w:
            pushed = src.push_prefixes(tuple(dst.handoff_address()))
            assert pushed["pushed"] >= 3, pushed
            computed_before = dst.engine_stats()["prefill_tokens_computed"]
            resp = dst.completions(req)
        assert resp["choices"][0]["token_ids"] \
            == ref["choices"][0]["token_ids"]
        stats = dst.engine_stats()
        assert stats["prefill_tokens_computed"] - computed_before \
            <= len(req["prompt"]) + 1 - 24
        assert stats["prefix_tokens_saved"] >= 24
        w.assert_zero_pickle()
        assert w.counters["deserialize_fast"] >= 2
    finally:
        src._handoff.close()
        dst._handoff.close()


def test_partial_prefix_push_discarded_whole(setup):
    """A pusher dying mid-stream leaves NOTHING adopted: no cached blocks,
    no leaked pages (ack-after-adoption, whole-or-nothing)."""
    import json as json_mod

    from ray_tpu.collective.cpu_group import _HDR
    from ray_tpu.llm.serving import LLMServer

    dst = LLMServer(_cfg(setup))
    try:
        rejected_before = dst._handoff.handoffs_rejected
        meta = {"prefix": True, "weights_version": 0,
                "entries": [{"tokens": _prompt(1, n=8), "lora": ""}],
                "kv_dtype": "float32", "kv_shape": [2, 4, 1, 8, 16]}
        body = json_mod.dumps(meta).encode()
        with socket.create_connection(tuple(dst.handoff_address()),
                                      timeout=5) as sock:
            sock.sendall(_HDR.pack(len(body), 2) + body)
            sock.sendall(_HDR.pack(10_000, 1))  # promised K pages... gone
        deadline = time.monotonic() + 10
        while (dst._handoff.handoffs_rejected == rejected_before
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert dst._handoff.handoffs_rejected == rejected_before + 1
        assert dst._handoff.handoffs_adopted == 0
        bm = dst.engine.block_manager
        assert not bm.cached
        s = dst.engine_stats()
        assert s["free_kv_blocks"] == s["total_kv_blocks"]
    finally:
        dst._handoff.close()


# ----------------------------------------------- router + fleet plumbing


class _FakeReplica:
    def __init__(self, tag):
        self.tag = tag
        self.key = f"fake:{tag}"
        self.name = tag
        self.calls = []

    def call(self, method, *args, **kwargs):
        kwargs.pop("_timeout", None)
        self.calls.append((method, args))
        if method == "engine_stats":
            return {"replica": self.tag, "running": 0, "waiting": 0,
                    "prefilling": 0, "free_kv_blocks": 64,
                    "total_kv_blocks": 64}
        return {}


class _FakeStore:
    def __init__(self, owner=None):
        self.owner = owner
        self.purges = []

    def purge(self, **kw):
        self.purges.append(kw)
        return -1

    def lookup_owner(self, digests, **kw):
        return ({"owner_replica": self.owner, "n_blocks": len(digests),
                 "n_tokens": 8} if self.owner else None)


def test_eject_blanks_cluster_owner_hint_same_tick():
    """The bugfix satellite: ejecting a replica purges its live-owner
    hints from the cluster table in the same tick as the router's own
    owner-LRU prune — clear_owner_only, because the pages must outlive
    the owner."""
    from ray_tpu.llm.router import FleetSupervisor, RouterCore

    store = _FakeStore()
    replicas = [_FakeReplica("rep-a"), _FakeReplica("rep-b")]
    sup = FleetSupervisor(RouterCore(2, block_size=8), replicas,
                          prefix_store=store)
    sup.fresh_stats(force=True)
    sup.eject_replica(0, reason="test")
    assert store.purges == [{"owner_replica": "rep-a",
                             "clear_owner_only": True}]
    assert not sup.core.is_healthy(0)
    # Idempotent: a second eject doesn't purge again.
    sup.eject_replica(0)
    assert len(store.purges) == 1


def test_router_cluster_fallback_restores_affinity():
    """Owner-LRU miss (fresh router / post-restart) + a live owner hint in
    the cluster table routes to that owner AND reseeds the local LRU."""
    from ray_tpu.llm.router import FleetSupervisor, RouterCore

    store = _FakeStore(owner="rep-b")
    replicas = [_FakeReplica("rep-a"), _FakeReplica("rep-b")]
    core = RouterCore(2, block_size=8)
    sup = FleetSupervisor(core, replicas, prefix_store=store)
    sup.fresh_stats(force=True)
    prompt = _prompt(1, n=16)
    idx = sup._cluster_affinity(prompt, {}, set())
    assert idx == 1
    # Local affinity reseeded: the next pick is a prefix hit, no probe.
    pick, decision = core.pick(prompt, stats=sup.fresh_stats())
    assert pick == 1 and decision["reason"] == "prefix"
    # Dead hint (no matching live replica tag): fall back to pow2.
    store.owner = "rep-gone"
    assert sup._cluster_affinity(prompt, {}, set()) is None


def test_drain_migrates_sessions_before_prefix_push():
    """drain_replica captures live sessions FIRST, then streams the
    victim's working set: migrate_sessions quiesces admission, so it must
    run the instant the drain lands — pushing prefixes first opened a
    window (hundreds of ms under load) in which fast-cycling sessions
    finished and their affinity-pinned successors were admitted
    mid-prefill, leaving nothing to migrate with KV."""
    from ray_tpu.llm.router import FleetSupervisor, RouterCore

    class _DrainReplica(_FakeReplica):
        def call(self, method, *args, **kwargs):
            kwargs.pop("_timeout", None)
            self.calls.append((method, args))
            if method == "engine_stats":
                return {"replica": self.tag, "running": 0, "waiting": 0,
                        "prefilling": 0, "free_kv_blocks": 64,
                        "total_kv_blocks": 64}
            if method == "handoff_address":
                return ("127.0.0.1", 1)
            if method == "migrate_sessions":
                return {"migrated": [], "replayed": []}
            return {}

    replicas = [_DrainReplica("rep-a"), _DrainReplica("rep-b")]
    sup = FleetSupervisor(RouterCore(2, block_size=8), replicas)
    sup.fresh_stats(force=True)
    summary = sup.drain_replica(0, target=1)
    assert summary["target"] == 1
    methods = [m for m, _ in replicas[0].calls]
    assert methods.index("migrate_sessions") < methods.index(
        "push_prefixes")


# --------------------------------------------------- LoRA pool scaling


def test_lora_resize_preserves_adapters_and_clamps(cpu_jax):
    import jax

    from ray_tpu.llm.lora import LoRAAdapter, LoRAManager
    from ray_tpu.models import llama

    config = _tiny()
    mgr = LoRAManager(config, n_slots=2, rank=4)
    rng = np.random.RandomState(0)

    def adapter(name):
        dims = {t: d for t, d in
                __import__("ray_tpu.llm.lora", fromlist=["target_dims"])
                .target_dims(config).items()}
        weights = {}
        for layer in range(config.n_layers):
            d_in, d_out = dims["wq"]
            weights[(layer, "wq")] = (
                rng.randn(d_in, 4).astype(np.float32),
                rng.randn(4, d_out).astype(np.float32))
        return LoRAAdapter(name=name, rank=4, alpha=8.0, weights=weights)

    s1 = mgr.load_adapter(adapter("a"))
    s2 = mgr.load_adapter(adapter("b"))
    before = {t: np.asarray(mgr.stacks[t][0]) for t in mgr.targets}
    grown = mgr.resize(6)
    assert grown == 6 and mgr.n_slots == 7
    for t in mgr.targets:
        a_stack = np.asarray(mgr.stacks[t][0])
        assert a_stack.shape[1] == 7
        assert np.array_equal(a_stack[:, :3], before[t][:, :3])
    assert mgr.slot_of("a") == s1 and mgr.slot_of("b") == s2
    assert mgr.name_of(s2) == "b"
    # Shrink clamps to the highest occupied slot — never orphans "b".
    assert mgr.resize(1) == max(s1, s2)
    assert mgr.slot_of("b") == s2


def test_lora_pool_policy_watermarks(cpu_jax):
    from ray_tpu.llm.lora import LoRAPoolPolicy, LoRAPoolPolicyConfig

    pol = LoRAPoolPolicy(LoRAPoolPolicyConfig(
        min_slots=1, max_slots=8, cooldown_s=10.0, quiet_s=30.0))
    full = {"lora_slots": 2, "lora_loaded": 2, "lora_evictions": 0}
    assert pol.desired(full, now=100.0) == 3      # occupancy grow
    assert pol.desired(full, now=105.0) is None   # cooldown
    # An eviction under cooldown-expired clock forces growth even at
    # moderate occupancy (occupancy can't see thrash once pinned full).
    thrash = {"lora_slots": 4, "lora_loaded": 2, "lora_evictions": 1}
    assert pol.desired(thrash, now=200.0) == 6
    # Shrink only after a sustained quiet window, never below loaded.
    idle = {"lora_slots": 8, "lora_loaded": 2, "lora_evictions": 1}
    assert pol.desired(idle, now=300.0) is None   # quiet clock starts
    assert pol.desired(idle, now=320.0) is None   # not quiet long enough
    assert pol.desired(idle, now=331.0) == 4
    assert pol.desired({"lora_slots": 0}, now=400.0) is None


# ------------------------------------------------------------ chaos proof


@pytest.mark.chaos
def test_owner_death_under_load_survivor_adopts_hottest(setup,
                                                        pickle_sanitizer):
    """ISSUE acceptance: kill the owning replica under mixed load; a
    survivor serves the dead owner's hottest prefix from the cluster
    table with ZERO re-prefill — prefill-token counter unchanged for the
    prefix, bit-identical tokens, zero pickle, no client errors."""
    from ray_tpu.llm.prefix_store import ClusterPrefixStore
    from ray_tpu.llm.serving import LLMServer

    srv, transport = _gcs_bridge()
    lock = threading.Lock()

    def locked_transport(method, m, payload=b""):
        with lock:  # concurrent requests share one bridge
            return transport(method, m, payload)

    # Owner replica: tiny host tier so watermark pressure demotes the
    # working set into the cluster table while it serves.
    owner = LLMServer(_cfg(setup, num_kv_blocks=16, host_prefix_mb=0.05,
                           host_prefix_low_watermark=0.3,
                           cluster_prefix_store=False))
    owner.engine.attach_prefix_store(
        host_tier=owner.engine.host_prefix_tier,
        cluster_store=ClusterPrefixStore(8, replica="owner",
                                         deployment="llm",
                                         transport=locked_transport))
    survivor = LLMServer(_cfg(setup, num_kv_blocks=16, host_prefix_mb=0,
                              cluster_prefix_store=False))
    survivor.engine.attach_prefix_store(
        cluster_store=ClusterPrefixStore(8, replica="survivor",
                                         deployment="llm",
                                         transport=locked_transport))
    try:
        hot = _prompt(1, n=24)                    # the hottest prefix
        ref = owner.completions({"prompt": hot + _prompt(2, n=6),
                                 "max_tokens": 6})
        owner.completions({"prompt": hot + _prompt(3, n=5),
                           "max_tokens": 6})      # hot traffic
        # Filler churn evicts the hot blocks from the 16-page device
        # pool into the host tier, whose watermark demotes them on into
        # the cluster table — the owner's working set is now durable.
        for s in range(4, 10):
            owner.completions({"prompt": _prompt(s, n=40),
                               "max_tokens": 6})
        assert owner.engine.cluster_store.published >= 3
        from ray_tpu.llm.prefix_store import cluster_chain
        assert owner.engine.cluster_store.lookup_owner(
            cluster_chain(hot, 8)) is not None

        errors = []
        results = {}

        def client(seed):
            try:
                results[seed] = survivor.completions(
                    {"prompt": hot + _prompt(seed, n=6),
                     "max_tokens": 6})["choices"][0]["token_ids"]
            except Exception as e:  # no client may ever see an error
                errors.append(e)

        w = pickle_sanitizer.window()
        with w:
            owner._handoff.close()                # the kill
            del owner
            computed_before = \
                survivor.engine_stats()["prefill_tokens_computed"]
            results[2] = survivor.completions(
                {"prompt": hot + _prompt(2, n=6),
                 "max_tokens": 6})["choices"][0]["token_ids"]
            threads = [threading.Thread(target=client, args=(s,))
                       for s in (9, 10)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        assert not errors
        assert results[2] == ref["choices"][0]["token_ids"]
        # Zero re-prefill for the hot prefix: its 24 tokens came from
        # the table, only the private tail was computed.
        stats = survivor.engine_stats()
        assert stats["cluster_prefix_tokens_saved"] >= 24
        first_cost = stats["prefill_tokens_computed"] - computed_before
        assert first_cost <= 3 * ((24 + 6 + 1) - 24)
        assert stats["cluster_prefix_adopted_blocks"] >= 3
        w.assert_zero_pickle()
    finally:
        survivor._handoff.close()
