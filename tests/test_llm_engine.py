"""LLM engine tests: paged decode must match naive full-forward decoding.

This is the correctness anchor for the serving engine (the reference
outsources all of this to vLLM; SURVEY §2.4/§3.5)."""

import numpy as np
import pytest

import ray_tpu  # noqa: F401


@pytest.fixture(scope="module")
def tiny_setup(cpu_jax):
    import jax

    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import llama

    import jax.numpy as jnp

    # fp32: greedy argmax must be noise-free for exact paged-vs-naive compare.
    config = llama.LlamaConfig.tiny(vocab_size=128, max_seq=64,
                                    dtype=jnp.float32)
    params = llama.init_params(config, jax.random.key(0))
    runner = ModelRunner(config, params, num_blocks=64, block_size=8)
    return config, params, runner


def naive_greedy_decode(params, config, prompt, n_steps):
    """Reference: full forward each step, greedy argmax."""
    import jax.numpy as jnp

    from ray_tpu.models import llama

    tokens = list(prompt)
    for _ in range(n_steps):
        logits = llama.forward(params, jnp.asarray([tokens], dtype=jnp.int32),
                               config)
        tokens.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return tokens[len(prompt):]


def test_paged_greedy_matches_naive(tiny_setup):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = tiny_setup
    engine = LLMEngine(runner, max_batch_size=4)
    prompt = [1, 5, 9, 2]
    n = 8
    out = engine.generate([prompt], SamplingParams(max_tokens=n))[0]
    expected = naive_greedy_decode(params, config, prompt, n)
    assert out.output_token_ids == expected
    assert out.finished and out.finish_reason == "length"


def test_continuous_batching_multiple_requests(tiny_setup):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = tiny_setup
    engine = LLMEngine(runner, max_batch_size=3)
    prompts = [[1, 2, 3], [7, 8], [11, 12, 13, 14], [21], [3, 1]]
    outs = engine.generate(prompts, SamplingParams(max_tokens=6))
    assert len(outs) == 5
    for prompt, out in zip(prompts, outs):
        expected = naive_greedy_decode(params, config, prompt, 6)
        assert out.output_token_ids == expected, (prompt, out.output_token_ids,
                                                  expected)


def test_stop_tokens(tiny_setup):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = tiny_setup
    prompt = [1, 5, 9, 2]
    first = naive_greedy_decode(params, config, prompt, 1)[0]
    engine = LLMEngine(runner)
    out = engine.generate([prompt], SamplingParams(
        max_tokens=10, stop_token_ids=[first]))[0]
    assert out.output_token_ids == [first]
    assert out.finish_reason == "stop"


def test_kv_block_reuse_across_requests(tiny_setup):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = tiny_setup
    engine = LLMEngine(runner, max_batch_size=2)
    free_before = len(engine.block_manager.free)
    for _ in range(3):
        engine.generate([[1, 2, 3, 4, 5]], SamplingParams(max_tokens=4))
    assert len(engine.block_manager.free) == free_before  # no page leaks


def test_sampling_params_temperature(tiny_setup):
    from ray_tpu.llm.sampling import SamplingParams, sample

    logits = np.array([0.0, 10.0, 0.0, 0.0])
    assert sample(logits, SamplingParams(temperature=0.0)) == 1
    # High temperature with a seed is reproducible.
    t1 = sample(logits, SamplingParams(temperature=5.0, seed=0))
    t2 = sample(logits, SamplingParams(temperature=5.0, seed=0))
    assert t1 == t2


# ------------------------------------------------------- prefix caching

def test_prefix_cache_identical_outputs_and_skip(tiny_setup):
    """Second request with a shared prompt prefix reuses cached KV blocks:
    prefill compute is skipped for the cached prefix AND greedy outputs
    match the uncached engine exactly (vLLM automatic-prefix-caching
    analog)."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = tiny_setup
    rng = np.random.RandomState(3)
    system = rng.randint(1, config.vocab_size, 24).tolist()  # 3 full blocks
    p1 = system + rng.randint(1, config.vocab_size, 6).tolist()
    p2 = system + rng.randint(1, config.vocab_size, 5).tolist()
    sp = SamplingParams(max_tokens=6, temperature=0.0)

    cached = LLMEngine(runner, enable_prefix_caching=True)
    out_a = cached.generate([p1], sp)[0].output_token_ids
    saved_before = cached.block_manager.prefix_tokens_saved
    out_b = cached.generate([p2], sp)[0].output_token_ids
    assert cached.block_manager.prefix_hits >= 1
    assert cached.block_manager.prefix_tokens_saved - saved_before == 24

    plain = LLMEngine(runner, enable_prefix_caching=False)
    assert plain.generate([p1], sp)[0].output_token_ids == out_a
    assert plain.generate([p2], sp)[0].output_token_ids == out_b


def test_prefix_cache_shared_blocks_not_corrupted(tiny_setup):
    """Two live sequences sharing cached prefix blocks decode
    concurrently; generated tokens must not corrupt the shared KV (writes
    only target private tail blocks)."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = tiny_setup
    rng = np.random.RandomState(5)
    system = rng.randint(1, config.vocab_size, 16).tolist()  # 2 full blocks
    p1 = system + [7]
    p2 = system + [9]
    sp = SamplingParams(max_tokens=8, temperature=0.0)

    engine = LLMEngine(runner, enable_prefix_caching=True)
    engine.add_request(p1, sp, request_id="a")
    outs = {}

    def pump(until_tokens_from_a):
        while engine.has_unfinished():
            for o in engine.step():
                if o.finished:
                    outs[o.request_id] = o.output_token_ids
            req_a = next((r for r in engine.running if r.id == "a"), None)
            if (until_tokens_from_a is not None and req_a is not None
                    and len(req_a.output) >= until_tokens_from_a):
                return

    # Let "a" prefill (registering the system blocks) and start decoding,
    # THEN admit "b": it must reuse a's still-live blocks (refcount 2)
    # while a keeps decoding into its own private tail.
    pump(until_tokens_from_a=2)
    engine.add_request(p2, sp, request_id="b")
    pump(until_tokens_from_a=None)
    # Both shared the system blocks.
    assert engine.block_manager.prefix_hits >= 1
    plain = LLMEngine(runner, enable_prefix_caching=False)
    assert plain.generate([p1], sp)[0].output_token_ids == outs["a"]
    assert plain.generate([p2], sp)[0].output_token_ids == outs["b"]


def test_prefix_cache_eviction_under_pressure(tiny_setup):
    """Parked cached blocks are evicted LRU when the pool runs dry; the
    engine keeps serving correctly afterwards."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = tiny_setup
    rng = np.random.RandomState(7)
    sp = SamplingParams(max_tokens=4, temperature=0.0)
    engine = LLMEngine(runner, enable_prefix_caching=True)
    # 64 blocks of 8 tokens; run many distinct 32-token prompts so parked
    # cached blocks must recycle.
    outs = []
    for i in range(12):
        p = rng.randint(1, config.vocab_size, 32).tolist()
        outs.append((p, engine.generate([p], sp)[0].output_token_ids))
    mgr = engine.block_manager
    assert len(mgr.free) + len(mgr.reusable) + len(mgr.refcount) <= 64
    # Re-run an early prompt (its blocks likely evicted): still correct.
    p0, o0 = outs[0]
    assert engine.generate([p0], sp)[0].output_token_ids == o0


def test_prefix_cache_stop_finish_release_accounting(tiny_setup):
    """A stop-token finish on a prompt whose blocks are cached releases
    them through the refcount-aware path (a release must not push shared
    cached blocks straight onto free)."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = tiny_setup
    rng = np.random.RandomState(11)
    prompt = rng.randint(1, config.vocab_size, 17).tolist()
    engine = LLMEngine(runner, enable_prefix_caching=True)
    first = engine.generate([prompt], SamplingParams(max_tokens=3))[0]
    # Finish a second run via stop_token on its own first token.
    stop = first.output_token_ids[0]
    out = engine.generate([prompt], SamplingParams(
        max_tokens=8, stop_token_ids=[stop]))[0]
    assert out.finish_reason == "stop"
    mgr = engine.block_manager
    # Every block either free, parked-reusable, or nothing: no leaks, and
    # no id is simultaneously free AND referenced.
    assert not mgr.refcount, mgr.refcount
    free_set = set(mgr.free)
    assert free_set.isdisjoint(mgr.reusable.keys())
    assert len(mgr.free) + len(mgr.reusable) == 64
    # The cached prefix still round-trips correctly afterwards.
    again = engine.generate([prompt], SamplingParams(max_tokens=3))[0]
    assert again.output_token_ids == first.output_token_ids


def test_prefix_cache_isolated_per_lora_slot(tiny_setup):
    """The hash chain seeds with the LoRA slot: identical prompts under
    different adapters must NOT share KV (adapters change wk/wv)."""
    from ray_tpu.llm.engine import BlockManager

    mgr = BlockManager(num_blocks=16, block_size=4)
    prompt = list(range(1, 13))
    base = mgr.prefix_hashes(prompt, lora_slot=0)
    lora = mgr.prefix_hashes(prompt, lora_slot=2)
    assert base != lora
    assert base == mgr.prefix_hashes(prompt, lora_slot=0)


# ------------------------------------------------- speculative decoding

def test_ngram_speculative_matches_naive(tiny_setup):
    """Prompt-lookup speculative decode must produce EXACTLY the plain
    greedy output (acceptance is exact-match on argmax), and accept extra
    tokens on repetitive sequences (vLLM ngram speculative analog)."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = tiny_setup
    # A strongly repetitive prompt so n-gram proposals hit.
    prompt = [5, 9, 13, 5, 9, 13, 5, 9, 13, 5, 9]
    n = 10
    sp = SamplingParams(max_tokens=n)
    plain = LLMEngine(runner, enable_prefix_caching=False)
    expected = plain.generate([prompt], sp)[0].output_token_ids

    spec = LLMEngine(runner, enable_prefix_caching=False,
                     speculative_ngram=4)
    got = spec.generate([prompt], sp)[0].output_token_ids
    assert got == expected, (got, expected)

    # Also exact on a non-repetitive prompt (graceful when proposals miss).
    prompt2 = [1, 7, 3, 11, 2]
    expected2 = plain.generate([prompt2], sp)[0].output_token_ids
    assert spec.generate([prompt2], sp)[0].output_token_ids == expected2


def test_ngram_speculative_accepts_on_repetition(tiny_setup):
    """On a cyclic-output regime the engine accepts speculative tokens
    (fewer verify steps than tokens)."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = tiny_setup
    prompt = [5, 9, 13, 5, 9, 13, 5, 9, 13, 5, 9]
    spec = LLMEngine(runner, enable_prefix_caching=False,
                     speculative_ngram=4)
    out = spec.generate([prompt], SamplingParams(max_tokens=12))[0]
    assert len(out.output_token_ids) == 12
    # The cyclic prompt makes n-gram proposals hit: acceptance MUST move
    # (a silently-disabled spec path would leave it at 0).
    assert spec.spec_tokens_accepted > 0, spec.spec_tokens_accepted


def test_warmup_precompiles_without_corrupting_state(tiny_setup):
    """warmup() must compile the token ladder via all-padding dummy steps
    that leave the KV pool / block manager untouched: generation after warmup
    must match the never-warmed engine token for token."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.sampling import SamplingParams

    config, params, runner = tiny_setup
    warmed = LLMEngine(runner, max_batch_size=4, speculative_ngram=3)
    n_shapes = warmed.warmup()
    assert n_shapes > 0
    assert not warmed.block_manager.refcount, "warmup leaked block state"
    prompt = [1, 5, 9, 2]
    out = warmed.generate([prompt], SamplingParams(max_tokens=8))[0]
    expected = naive_greedy_decode(params, config, prompt, 8)
    assert out.output_token_ids == expected
    # full adds the host-logits head over the same ladder, once
    assert warmed.warmup(full=True) == n_shapes - warmed._warm_spill_gather()
    assert warmed.warmup(full=True) == warmed._warm_spill_gather()
