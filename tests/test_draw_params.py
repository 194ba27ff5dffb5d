"""`models.draw_params` (PR 62): a replica's parameters by ONE jitted program a
configuration, the configuration static and the key an argument.

Two things are held here. The weights are the eager draw's: same tree, shapes
and dtypes, and EVERY leaf bit for bit, bfloat16 and float32 alike. No leaf
is allowed to differ: the leaves that did when the draw was first jitted
(`mimo_v2_flash` `sink`, `glm_dsa` `k_norm_b`, `phi4flash` `lambda_*`, a
`llama` stack whose scale is no power of two, `kimi_linear` `conv_w` of a
one-layer kind on the chip) are each drawn by one program of their own in the
eager draw too, so both round once. And the key is an argument: one start
compiles at most three programs for its parameters (the key's two and the
draw), and a second seed in the same process traces and compiles nothing.
"""

import importlib

import numpy as np
import pytest

import ray_tpu  # noqa: F401

FAMILIES = [
    ("llama", "LlamaConfig"), ("deepseek_v2", "DeepseekV2Config"),
    ("mimo_v2_flash", "MimoV2FlashConfig"), ("phi4flash", "Phi4FlashConfig"),
    ("brumby", "BrumbyConfig"), ("kimi_linear", "KimiLinearConfig"),
    ("glm_dsa", "GlmDsaConfig"), ("nemotron_h", "NemotronHConfig"),
    ("minicpm_sala", "MiniCPMSALAConfig"), ("afmoe", "AfmoeConfig"),
    ("lfm2_moe", "Lfm2MoeConfig")]


def _bits(x):
    a = np.asarray(x)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


@pytest.mark.parametrize("module,cls", FAMILIES, ids=[f[0] for f in FAMILIES])
def test_the_jitted_draw_is_the_eager_draw(cpu_jax, module, cls):
    import jax
    import jax.numpy as jnp

    from ray_tpu import models

    family = importlib.import_module("ray_tpu.models." + module)
    config = getattr(family, cls).tiny(dtype=jnp.bfloat16)
    key = jax.random.key(2100000517 % (2**31 - 1))
    eager = family.init_params(config, key)
    drawn = models.draw_params(config, key)
    assert jax.tree.structure(drawn) == jax.tree.structure(eager)
    bf16 = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(eager),
                            jax.tree.leaves(drawn)):
        name = jax.tree_util.keystr(path)
        assert (a.shape, a.dtype) == (b.shape, b.dtype), name
        assert a.dtype in (jnp.bfloat16, jnp.float32), name
        bf16 += a.dtype == jnp.bfloat16
        assert np.array_equal(_bits(a), _bits(b)), name
    assert bf16 >= 8        # the weights themselves are bfloat16 leaves


def _params_span(replica):
    from ray_tpu.util import tracing

    spans = tracing.get_spans()
    top = [s for s in spans if s["name"] == "llm:startup"
           and s["args"].get("replica") == replica]
    assert len(top) == 1
    mine = [s for s in spans if s["name"] == "llm:startup:params"
            and s["args"].get("parent_span_id") == top[0]["args"]["span_id"]]
    assert len(mine) == 1
    return mine[0]["args"]


@pytest.mark.parametrize("module,cls", [FAMILIES[0], FAMILIES[5]],
                         ids=["llama", "kimi_linear"])
def test_the_key_is_an_argument_of_one_program(cpu_jax, module, cls):
    """A start's `llm:startup:params` span counts at most three backend
    compiles (an eager draw of the tiny Kimi-Linear counts 70), and another
    seed in the same process draws other weights with no trace and no compile:
    the seed is no constant of the program."""
    import jax

    from ray_tpu.llm.serving import LLMConfig, build_engine
    from ray_tpu.util import tracing

    was = tracing.enabled()
    tracing.set_enabled(True)
    jax.clear_caches()      # the test above drew this configuration too
    try:
        family = importlib.import_module("ray_tpu.models." + module)
        config = getattr(family, cls).tiny()
        kw = dict(model_config=config, num_kv_blocks=32, block_size=4,
                  prefill_chunk=16, max_batch_size=2, warmup_buckets="off")
        first = build_engine(LLMConfig(seed=11, **kw), replica=module + "-11")
        span = _params_span(module + "-11")
        assert span["source"] == "init" and 1 <= span["compiles"] <= 3
        before = tracing.compile_totals()
        second = build_engine(LLMConfig(seed=12, **kw), replica=module + "-12")
        again = _params_span(module + "-12")
        assert again["compiles"] == 0
        assert again["trace_s"] == again["lower_s"] == again["compile_s"] == 0
        gained = tracing.compile_since(before)
        assert gained["cache_misses"] == 0
        a = jax.tree.leaves(first.runner.params)
        b = jax.tree.leaves(second.runner.params)
        assert any(not np.array_equal(x, y) for x, y in zip(a, b))
        eager = family.init_params(config, jax.random.key(12))
        for x, y in zip(jax.tree.leaves(eager), b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    finally:
        tracing.set_enabled(was)
