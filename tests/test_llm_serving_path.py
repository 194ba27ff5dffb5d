"""Serving hot path: chunked prefill, preemption-recompute, TP, streaming.

Complements test_llm_engine.py (which anchors paged-vs-naive correctness);
this file exercises the round-2 serving features: bucketed chunked prefill,
preemption that preserves emitted tokens, tensor-parallel ModelRunner over a
CPU mesh, and token streaming end-to-end through serve.
"""

import numpy as np
import pytest

import ray_tpu  # noqa: F401


def _tiny(vocab=128, max_seq=64):
    import jax.numpy as jnp

    from ray_tpu.models import llama

    # fp32: greedy argmax must be noise-free for exact paged-vs-naive compare.
    return llama.LlamaConfig.tiny(vocab_size=vocab, max_seq=max_seq,
                                  dtype=jnp.float32)


def naive_greedy(params, config, prompt, n_steps):
    import jax.numpy as jnp

    from ray_tpu.models import llama

    tokens = list(prompt)
    for _ in range(n_steps):
        logits = llama.forward(params, jnp.asarray([tokens], dtype=jnp.int32),
                               config)
        tokens.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return tokens[len(prompt):]


@pytest.fixture(scope="module")
def setup(cpu_jax):
    import jax

    from ray_tpu.models import llama

    config = _tiny()
    params = llama.init_params(config, jax.random.key(0))
    return config, params


def test_chunked_prefill_matches_naive(setup):
    """A prompt longer than the chunk size prefills over several bucketed
    chunks and still greedy-decodes identically to the full forward."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    runner = ModelRunner(config, params, num_blocks=64, block_size=8,
                         chunk_size=8)
    engine = LLMEngine(runner, max_batch_size=4, prefill_chunk=8)
    prompt = [(7 * i + 3) % config.vocab_size for i in range(21)]  # 3 chunks
    out = engine.generate([prompt], SamplingParams(max_tokens=6))[0]
    assert out.output_token_ids == naive_greedy(params, config, prompt, 6)


def test_preemption_preserves_output(setup):
    """With a starved KV pool, the newest sequence is preempted and later
    recomputed (prompt + already-generated tokens); results are unchanged."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    # 10 pages x 4 tokens: two 10-token prompts + 8 generated tokens each
    # cannot fit simultaneously -> forced preemption mid-decode.
    runner = ModelRunner(config, params, num_blocks=10, block_size=4,
                         chunk_size=8)
    engine = LLMEngine(runner, max_batch_size=2, prefill_chunk=8)
    prompts = [[1, 5, 9, 2, 11, 3, 8, 4, 6, 10],
               [2, 7, 1, 12, 9, 5, 3, 13, 8, 6]]
    outs = engine.generate(prompts, SamplingParams(max_tokens=8))
    for prompt, out in zip(prompts, outs):
        assert out.output_token_ids == naive_greedy(params, config, prompt, 8)
    # All pages returned (cached prompt blocks park in the reusable pool;
    # nothing stays referenced).
    mgr = engine.block_manager
    assert len(mgr.free) + len(mgr.reusable) == 10
    assert not mgr.refcount


def test_engine_stream_yields_progressively(setup):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    runner = ModelRunner(config, params, num_blocks=64, block_size=8)
    engine = LLMEngine(runner, max_batch_size=2)
    prompt = [1, 5, 9, 2]
    toks = list(engine.stream(prompt, SamplingParams(max_tokens=5)))
    assert toks == naive_greedy(params, config, prompt, 5)


def test_tensor_parallel_runner_matches_naive(setup):
    """TP=2 over the CPU mesh: SERVE_RULES-sharded params + kv cache, the
    attention under shard_map — greedy output identical to single-device."""
    import jax

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    config, params = setup
    mesh = build_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    runner = ModelRunner(config, params, num_blocks=64, block_size=8,
                         mesh=mesh, chunk_size=8)
    engine = LLMEngine(runner, max_batch_size=2, prefill_chunk=8)
    prompt = [3, 14, 15, 9, 2, 6, 5]
    out = engine.generate([prompt], SamplingParams(max_tokens=6))[0]
    assert out.output_token_ids == naive_greedy(params, config, prompt, 6)


def test_no_recompiles_after_warmup(setup):
    """The bucketed runner must reuse compiled programs across requests of
    different prompt lengths within the same buckets."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    runner = ModelRunner(config, params, num_blocks=64, block_size=8,
                         chunk_size=8)
    engine = LLMEngine(runner, max_batch_size=2, prefill_chunk=8)
    # Warmup: prefill slices and decode rows in the 8-token bucket.
    engine.generate([[1, 2, 3], [4, 5, 6, 7]], SamplingParams(max_tokens=3))
    compiles = runner._step_mixed_jit._cache_size()
    assert compiles > 0
    # Different lengths, same token bucket: no new compiles.
    engine.generate([[9, 8], [2, 4, 6, 8]], SamplingParams(max_tokens=4))
    assert runner._step_mixed_jit._cache_size() == compiles


def test_serve_streaming_completions(cpu_jax):
    """End-to-end: tokens stream out of a serve replica before the request
    finishes (streaming actor method -> ObjectRefGenerator)."""
    import jax

    ray_tpu.init(num_cpus=2)
    try:
        from ray_tpu import serve
        from ray_tpu.llm.serving import LLMConfig, LLMServer, build_llm_deployment
        from ray_tpu.models import llama

        cfg = LLMConfig(model_config=_tiny(), num_kv_blocks=64,
                        block_size=8, max_batch_size=2)
        handle = serve.run(build_llm_deployment(cfg, name="llm"))
        # Non-streaming completions still work.
        resp = handle.options("completions").remote(
            {"prompt": [1, 5, 9, 2], "max_tokens": 4}).result(timeout=120)
        assert len(resp["choices"][0]["token_ids"]) == 4

        # Streaming: chunk events arrive token by token.
        gen = handle.options("completions_stream").remote_stream(
            {"prompt": [1, 5, 9, 2], "max_tokens": 5})
        events = [ray_tpu.get(ref, timeout=120) for ref in gen]
        toks = [e["token"] for e in events if not e["finished"]]
        assert len(toks) == 5
        assert events[-1]["finished"]
        assert events[-1]["token_ids"] == toks

        # Streamed greedy tokens match the non-streaming call.
        resp2 = handle.options("completions").remote(
            {"prompt": [1, 5, 9, 2], "max_tokens": 5}).result(timeout=120)
        assert resp2["choices"][0]["token_ids"] == toks
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
