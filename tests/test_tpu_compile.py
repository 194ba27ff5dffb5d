"""Compile the main path's kernels and sharded steps for a described TPU.

No chip is attached here: the installed TPU compiler compiles for a
`v5e:2x2` topology that is only described, at Llama-3-8B widths. That finds
what interpret mode cannot (tiling, VMEM, kernels GSPMD cannot partition,
missing vma under shard_map) at no chip time. Nothing runs, so nothing here
says anything about results or speed — `chip_smoke.py` does that on the chip.

All in ONE file and compiled in the test's own process: only one process may
hold the TPU library at a time, so the topology is described inside a
module-scoped fixture (never at import), and the xdist worker that is handed
this file is the only one that loads it.
"""

import os
import re
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import llama
from ray_tpu.ops import attention as att
from ray_tpu.ops import paged_attention as pa
from ray_tpu.parallel import sharding as sharding_mod
from ray_tpu.parallel.mesh import AXES

# Llama-3-8B attention widths (LlamaConfig.llama3_8b).
H, K, HD = 32, 8, 128
PAGE, POOL, MAX_PAGES = 16, 2048, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep these out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """The dispatchers ask is_tpu_backend() and would see the CPU here:
    answer for the described chip, so "auto" resolves as it does there."""
    monkeypatch.setattr("ray_tpu.ops.is_tpu_backend", lambda: True)


KERNEL = 'custom_call_target="tpu_custom_call"'


def _compiled_kernels(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(KERNEL)


def _paged_args(sh, q_shape, kv_heads=K):
    """The K/V kernel's operands: both pools whole, as they lie, (L, P, page,
    K, hd), and the layer's index."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    S = 8
    pool = sds((4, POOL, PAGE, kv_heads, HD), jnp.bfloat16)
    return (sds(q_shape, jnp.bfloat16), pool, pool, sds((), jnp.int32),
            sds((S, MAX_PAGES), jnp.int32), sds((S,), jnp.int32),
            sds((S,), jnp.int32))


def _whole_pool_kernels(fn, *args) -> int:
    """Kernels in the compiled program, which must take the pools where they
    lie: no copy and no re-layout of a pool on the way into the kernel."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    pool = "bf16[%s]" % ",".join(map(str, args[1].shape))
    assert pool in text
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= %s\S* (copy|transpose|fusion)\(" % re.escape(pool),
                          line)]
    assert not moved, moved
    return text.count(KERNEL)


# (T, q heads, kv heads): a tick's largest and smallest bucket at Llama-3-8B's
# widths, then what one chip sees under tensor_parallel 4 and at G = 1.
@pytest.mark.parametrize("T,heads,kv_heads", [
    (512, H, K), (8, H, K), (160, H // 4, K // 4), (160, 8, 8)],
    ids=["T512", "T8", "tp4_shard", "G1"])
def test_unified_paged_kernel_compiles(one_chip, T, heads, kv_heads):
    args = _paged_args(one_chip, (T, heads, HD), kv_heads)
    cu = jax.ShapeDtypeStruct((9,), jnp.int32, sharding=one_chip)
    assert _whole_pool_kernels(
        lambda *a: pa.ragged_paged_attention_unified(*a, interpret=False),
        *args, cu) == 1


# Decode rows, and the benchmark check's 128-token chunks (q_block-token
# query blocks).
@pytest.mark.parametrize("Bq", [1, 128])
def test_rectangular_paged_kernel_compiles(one_chip, Bq):
    args = _paged_args(one_chip, (8, Bq, H, HD))
    assert _whole_pool_kernels(
        lambda *a: pa.ragged_paged_attention(*a, interpret=False),
        *args) == 1


def _qkv(sh, seq):
    def sds(heads):
        return jax.ShapeDtypeStruct((1, seq, heads, HD), jnp.bfloat16,
                                    sharding=sh)

    return sds(H), sds(K), sds(K)


# One resident size, then one each side of the resident/tiled switch.
@pytest.mark.parametrize(
    "seq", [2048, att._FWD_RESIDENT_MAX_ROWS, 2 * att._FWD_RESIDENT_MAX_ROWS])
def test_flash_forward_compiles(one_chip, seq):
    assert _compiled_kernels(
        lambda q, k, v: att.flash_attention_fwd(q, k, v, interpret=False),
        *_qkv(one_chip, seq)) == 1


def _flash_loss(q, k, v):
    out = att.flash_attention(q, k, v, interpret=False)
    return out.astype(jnp.float32).sum()


_flash_grad = jax.grad(_flash_loss, argnums=(0, 1, 2))


@pytest.mark.parametrize(
    "seq", [2048, att._BWD_RESIDENT_MAX_ROWS, 2 * att._BWD_RESIDENT_MAX_ROWS])
def test_flash_grad_compiles(one_chip, seq):
    # forward + dQ + dK/dV
    assert _compiled_kernels(_flash_grad, *_qkv(one_chip, seq)) == 3


def _abstract(tree, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def _mesh(topo, **axes):
    shape = tuple(axes.get(a, 1) for a in AXES)
    return Mesh(np.array(topo.devices).reshape(shape), AXES)


def test_sharded_train_step_compiles_for_four_chips(topo, on_tpu):
    """fsdp=2 x tp=2, attention_impl="auto": GSPMD cannot partition a Mosaic
    kernel, so the flash call must sit in a shard_map — and must still be
    there, not traded for the reference."""
    import optax

    from ray_tpu.parallel import fsdp

    mesh = _mesh(topo, fsdp=2, tp=2)
    cfg = llama.LlamaConfig.llama3_8b(n_layers=2, remat_policy="dots")
    opt = optax.adamw(1e-4)
    axes = llama.param_logical_axes(cfg)
    _, make_step = fsdp.build_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh, axes,
        {"tokens": ("batch", None)})
    state = jax.eval_shape(
        lambda: fsdp.init_train_state(
            llama.init_params(cfg, jax.random.key(0)), opt))
    specs = sharding_mod.tree_specs(axes, sharding_mod.TRAIN_RULES)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        {"params": specs,
         "opt_state": fsdp._spec_like_params(state["opt_state"],
                                             state["params"], specs),
         "step": P()},
        is_leaf=lambda x: isinstance(x, P))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (2, 2049), jnp.int32,
        sharding=NamedSharding(mesh, P(("dp", "fsdp", "ep"), None)))}
    # As the benchmark and chip_smoke.py compile it (stable cache keys): no
    # name stack in the locations, so XLA calls a kernel inside shard_map
    # `shard_map.<n>` and nothing else so. `flash_kernel_ms.train` reads
    # the profile by that name (benchmarks/tick_phases.py).
    locations = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        compiled = make_step(shardings).lower(
            _abstract(state, shardings), batch).compile()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations",
                          locations)
    text = compiled.as_text()
    assert KERNEL in text
    kernels = re.findall(r"%([\w.\-]+) = [^%]*?custom-call\([^)]*\), "
                         + KERNEL, text)
    assert len(kernels) == text.count(KERNEL) >= 3
    assert all(k.startswith("shard_map.") for k in kernels), kernels
    assert set(re.findall(r"%(shard_map[\w.\-]*) = ", text)) == set(kernels)
    assert "all-gather" in text and "reduce-scatter" in text
    # Parameters and moments are spread: a quarter of the state per device.
    total = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert compiled.memory_analysis().argument_size_in_bytes < 0.3 * total


def test_tensor_parallel_serve_backbone_compiles_for_four_chips(topo, on_tpu):
    """tensor_parallel=4: the runner wraps the paged kernel in shard_map,
    whose check_vma needs the kernel's out_shape to say how it varies. The
    sampling head is left out: it is plain XLA and most of a step's compile
    time."""
    from ray_tpu.llm.model_runner import ModelRunner, pool_partition_spec

    mesh = _mesh(topo, tp=4)
    cfg = llama.LlamaConfig.llama3_8b(n_layers=2)
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0)))
    # Placement needs a device to put arrays on; there is none.
    with mock.patch.object(ModelRunner, "_place_params", lambda self, p: p), \
            mock.patch.object(ModelRunner, "_place_cache",
                              lambda self, c: c):
        runner = ModelRunner(cfg, params, num_blocks=POOL, block_size=PAGE,
                             mesh=mesh, attention_impl="pallas")
    specs = sharding_mod.tree_specs(llama.param_logical_axes(cfg),
                                    sharding_mod.SERVE_RULES)
    aparams = _abstract(params, jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P)))
    kv = NamedSharding(mesh, pool_partition_spec())
    acache = _abstract(runner.cache, {"k": kv, "v": kv})
    rep = NamedSharding(mesh, P())
    T, S = 512, 8

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)

    compiled = jax.jit(runner._backbone_mixed, donate_argnums=(1,)).lower(
        aparams, acache, i32(T), i32(S), i32(S), i32(S + 1),
        i32(S, runner.max_blocks_per_seq)).compile()
    text = compiled.as_text()
    assert KERNEL in text
    assert "all-reduce" in text
    one_layer_pool = 2 * cfg.n_layers * K * POOL * PAGE * HD * 2
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    assert (compiled.memory_analysis().argument_size_in_bytes
            < 0.3 * (weights + one_layer_pool))


@pytest.mark.parametrize("backbone", ["mixed", "rect"])
def test_step_backbone_does_not_copy_the_pool(one_chip, on_tpu, backbone):
    """The donated KV pool goes through a step program in the layout it came
    in (llm/model_runner.py, "The KV pool's layout"): no copy of a whole K
    or V pool, no pool-sized temporary, the result aliased to the parameter.
    Any other layout costs four such copies and 1.0 x the K + V pool of
    temporaries at any depth. Nor is a LAYER's share of the pool sliced out
    or transposed (until PR 32: (K + V pool) / n_layers of temporaries, an
    eighth here): the kernel takes the pools whole, so what a step allocates
    is its rows and query blocks, under 2% of the pools."""
    from ray_tpu.llm import model_runner
    from ray_tpu.llm.model_runner import ModelRunner, pool_shape

    # Mistral-7B-v0.3's widths (benchmarks/configs/mistral-7b-v0.3-l16.json)
    # are Llama-3-8B's with another vocabulary and rope base.
    cfg = llama.LlamaConfig.llama3_8b(n_layers=8, vocab_size=32768,
                                      max_seq=4096, rope_theta=1e6)
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0)))
    init = model_runner.init_kv_cache
    with mock.patch.object(model_runner, "init_kv_cache",
                           lambda *a: jax.eval_shape(lambda: init(*a))):
        runner = ModelRunner(cfg, params, num_blocks=POOL, block_size=PAGE,
                             attention_impl="pallas")

    def on_chip(tree):
        return _abstract(tree, jax.tree.map(lambda _: one_chip, tree))

    aparams, acache = on_chip(params), on_chip(runner.cache)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    S = 32
    tables = i32(S, runner.max_blocks_per_seq)
    if backbone == "mixed":     # the closed cell's unified tick: T = 160
        fn, args = runner._backbone_mixed, (
            i32(160), i32(S), i32(S), i32(S + 1), tables)
    else:                       # a decode step of the split path
        fn, args = runner._backbone, (
            i32(S, 1), i32(S), i32(S), i32(S), tables)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        aparams, acache, *args).compile()
    text = compiled.as_text()
    assert KERNEL in text
    one_pool = "bf16[%s]" % ",".join(map(str, pool_shape(cfg, POOL, PAGE)))
    assert one_pool in text
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= %s\S* copy\(" % re.escape(one_pool), line)]
    assert not copies, copies
    L, P, page, kv, hd = pool_shape(cfg, POOL, PAGE)
    layer_pages = [line.strip()[:160] for line in text.splitlines()
                   if re.search(r"= bf16\[(%d,%d,%d,%d|%d,%d,%d,%d)\]"
                                % (kv, P, page, hd, P, page, kv, hd), line)]
    assert not layer_pages, layer_pages
    pools = 2 * L * P * page * kv * hd * 2                      # K + V, bf16
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pools
    assert mem.temp_size_in_bytes < 0.02 * pools


def test_spill_gather_reads_its_pages_and_nothing_pool_sized(one_chip):
    """The eviction spills' one gather (ModelRunner.gather_pages_async;
    llm/engine.py, "eviction spills") at the closed Mistral cell's shapes:
    3584 pages, 16 layers, n = 128. The pool is read where it lies (not
    donated, not copied), and what the program allocates is of the order of
    the n pages it stages in the wire view, not of the pool."""
    from ray_tpu.llm import model_runner
    from ray_tpu.llm.model_runner import ModelRunner, pool_shape

    pool_pages, n = 3584, 128
    cfg = llama.LlamaConfig.llama3_8b(n_layers=16, vocab_size=32768,
                                      max_seq=4096, rope_theta=1e6)
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.key(0)))
    init = model_runner.init_kv_cache
    with mock.patch.object(model_runner, "init_kv_cache",
                           lambda *a: jax.eval_shape(lambda: init(*a))):
        runner = ModelRunner(cfg, params, num_blocks=pool_pages,
                             block_size=PAGE, attention_impl="reference")
    assert runner.page_nbytes == 2 * 16 * PAGE * K * HD * 2     # 1 MiB
    acache = _abstract(runner.cache,
                       jax.tree.map(lambda _: one_chip, runner.cache))
    ids = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    compiled = runner._gather_jit.lower(acache, ids).compile()
    text = compiled.as_text()
    shape = pool_shape(cfg, pool_pages, PAGE)
    one_pool = "bf16[%s]" % ",".join(map(str, shape))
    assert one_pool in text
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= %s\S* copy\(" % re.escape(one_pool), line)]
    assert not copies, copies
    staged = n * runner.page_nbytes
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 0             # nothing donated
    assert staged <= mem.output_size_in_bytes < 1.01 * staged
    print("spill gather temporaries", mem.temp_size_in_bytes, "of", staged)
    assert mem.temp_size_in_bytes <= 2 * staged     # the pool is 3.5 GiB


@pytest.mark.parametrize("tier_first", [True, False],
                         ids=["tier_then_warmup", "warmup_then_tier"])
def test_warmup_compiles_the_spill_gather_ladder(tier_first):
    """Nothing compiles at the first eviction of a run (a compile inside a
    benchmark window makes the run incorrect; PR 26's 730 ms compose tick
    was the per-page gather compiling there): a unified engine's warm-up
    covers the gather at each of its sizes, whether the host tier is
    attached before warmup() or, as LLMServer does it, after. On the CPU, at
    a tiny size: what is asserted is the count of compiles."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.prefix_store import HostPrefixTier
    from ray_tpu.llm.sampling import SamplingParams

    cfg = llama.LlamaConfig.tiny(vocab_size=128, max_seq=128,
                                 dtype=jnp.float32)
    runner = ModelRunner(cfg, llama.init_params(cfg, jax.random.key(0)),
                         num_blocks=48, block_size=8, chunk_size=8)
    engine = LLMEngine(runner, max_batch_size=4, prefill_chunk=8)
    tier = HostPrefixTier(30 * runner.page_nbytes)
    if tier_first:
        engine.attach_prefix_store(host_tier=tier)
    shapes = engine.warmup()
    if not tier_first:
        engine.attach_prefix_store(host_tier=tier)
        shapes = engine.warmup_shapes
    assert engine._spill_sizes == (8, 32)    # up to the tier's 30 pages
    assert shapes == len(engine._warm_mixed) + len(engine._spill_sizes)
    compiles = engine.stats()["step_compiles"]
    sp = SamplingParams(max_tokens=2)
    for s in range(8):      # 8 x 12 pages through a 48-page pool
        engine.generate([[(s * 7 + 3 * i) % 128 for i in range(90)]], sp)
    engine.settle_spills()
    st = engine.stats()
    assert st["host_prefix_spills"] >= 40 and st["step_compiles"] == compiles
    assert not any(t["recompile"] for t in engine.flight_records)


# What a TPU profile shows for a Pallas kernel is its custom call's HLO text,
# which keeps frontend_attributes and drops pallas_call's `name` (PR 26).
@pytest.mark.parametrize("names,build", [
    # One K/V paged kernel behind both entry points (PR 32); its name keeps
    # the `paged_attention_` prefix the benchmark's reduction matches.
    (("paged_attention_unified",), lambda sh: (
        lambda *a: pa.ragged_paged_attention_unified(*a, interpret=False),
        _paged_args(sh, (8, H, HD))
        + (jax.ShapeDtypeStruct((9,), jnp.int32, sharding=sh),))),
    (("paged_attention_unified",), lambda sh: (
        lambda *a: pa.ragged_paged_attention(*a, interpret=False),
        _paged_args(sh, (8, 1, H, HD)))),
    (("flash_fwd_tiled",), lambda sh: (
        lambda q, k, v: att.flash_attention_fwd(q, k, v, interpret=False),
        _qkv(sh, 2 * att._FWD_RESIDENT_MAX_ROWS))),
    (("flash_fwd", "flash_bwd_dq_resident", "flash_bwd_dkv_resident"),
     lambda sh: (_flash_grad, _qkv(sh, 2048))),
    (("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
     lambda sh: (_flash_grad, _qkv(sh, 2 * att._BWD_RESIDENT_MAX_ROWS))),
], ids=["paged_attention_unified", "paged_attention_unified:rect",
        "flash_fwd_tiled", "flash_fwd+flash_bwd_dq_resident+flash_bwd_dkv_resident",
        "flash_fwd+flash_bwd_dq+flash_bwd_dkv"])
def test_kernel_tag_reaches_the_compiled_hlo(one_chip, names, build):
    fn, args = build(one_chip)
    text = jax.jit(fn).lower(*args).compile().as_text()
    flat = text.replace("\n", "").replace("\\", "")   # JAX indents the JSON
    for name in names:
        assert 'kernel_metadata={"kernel":"%s"}' % name in flat, name
    assert text.count(KERNEL) == len(names)


def test_paged_kernel_is_named_where_tracebacks_are_stripped(one_chip):
    """The benchmark (and chip_smoke.py) run with
    JAX_INCLUDE_FULL_TRACEBACKS_IN_LOCATIONS=0, where a profile's event is
    named by the HLO instruction alone. The K/V kernel is called through a
    jitted function, and inlined it takes that function's name: the
    benchmark's reduction finds its kernels by `tpu_custom_call` or
    `paged_attention_` (benchmarks/tick_phases.py), so the name has to hold
    one of them (PR 32: `_kv_call.11` made every traced run incorrect)."""
    args = _paged_args(one_chip, (160, H, HD)) + (
        jax.ShapeDtypeStruct((9,), jnp.int32, sharding=one_chip),)
    was = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        text = jax.jit(lambda *a: pa.ragged_paged_attention_unified(
            *a, interpret=False)).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
    names = re.findall(r"%(\S+) = \S+ custom-call\(.*" + re.escape(KERNEL),
                       text)
    assert len(names) == 1, names
    assert names[0].startswith("tpu_custom_call") or \
        "paged_attention_" in names[0], names


# ---- DeepSeek-V2 (models/deepseek_v2.py) at the benchmark cell's shapes ----

@pytest.mark.parametrize("backbone", ["mixed160", "mixed32", "rect128"])
def test_deepseek_step_compiles_without_copying_the_latent_pool(
        one_chip, on_tpu, backbone):
    """The step programs of `deepseekv2-docqa-closed32` (1 dense + 4 expert
    layers at the published widths, 40 of 160 experts, a 16384-page latent
    pool; benchmarks/configs/deepseek-v2-l5-e40.json) compile for the chip:
    the donated pool goes through in the layout it came in (no pool-sized
    copy, the result aliased to the parameter), the kernel takes the whole
    pool, so no layer's pages are sliced out either, and no expert stack is
    (they are parameters of their own). Temporaries (the query blocks and the
    expert rows) are held under 40% of the cell's 1.5625 GiB pool. The Pallas kernels are of ONE kind, the latent paged attention, one
    a layer; XLA's own grouped products are `ragged-dot` custom calls and
    carry no kernel_metadata."""
    from ray_tpu.llm import model_runner
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import deepseek_v2 as ds

    # One dense + one expert layer: every kind of layer the cell's five have,
    # at a third of the compile (which takes every core of the machine the
    # suite runs on). At the cell's depth the same programs hold 0.13 GiB of
    # temporaries (compiled by hand, PR 29).
    layers = 2
    cfg = ds.DeepseekV2Config(vocab_size=25600, num_hidden_layers=layers,
                              experts_held=(0, 40),
                              max_position_embeddings=16384)
    params = jax.eval_shape(lambda: ds.init_params(cfg, jax.random.key(0)))
    init = model_runner.init_cache
    with mock.patch.object(model_runner, "init_cache",
                           lambda *a: jax.eval_shape(lambda: init(*a))):
        runner = ModelRunner(cfg, params, num_blocks=16384, block_size=PAGE,
                             attention_impl="pallas")

    def on_chip(tree):
        return _abstract(tree, jax.tree.map(lambda _: one_chip, tree))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    S = 32
    tables = i32(S, runner.max_blocks_per_seq)
    fn, args = {
        "mixed160": (runner._backbone_mixed,
                     (i32(160), i32(S), i32(S), i32(S + 1), tables)),
        "mixed32": (runner._backbone_mixed,
                    (i32(32), i32(S), i32(S), i32(S + 1), tables)),
        "rect128": (runner._backbone, (
            i32(2, 128), i32(2), i32(2), i32(2),
            i32(2, runner.max_blocks_per_seq))),
    }[backbone]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(runner.cache), *args).compile()
    text = compiled.as_text()
    shape = (layers, 16384, PAGE, 640)
    assert runner.cache["latent"].shape == shape
    pool = "bf16[%s]" % ",".join(map(str, shape))
    assert pool in text
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= %s\S* copy\(" % re.escape(pool), line)]
    assert not copies, copies
    pool_bytes = int(np.prod(shape)) * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 0.4 * 5 / layers * pool_bytes
    flat = text.replace("\n", "").replace("\\", "")
    tagged = flat.count(
        'kernel_metadata={"kernel":"paged_attention_latent_unified"}')
    # the held experts' products are the Pallas weight stream on a TPU
    # (ops/grouped_dot.product): three a routed layer
    grouped = flat.count('kernel_metadata={"kernel":"grouped_dot"}')
    assert grouped == 3 * (layers - 1)
    assert tagged >= 2 and flat.count("kernel_metadata=") == tagged + grouped
    others = [line.strip()[:80] for line in text.splitlines()
              if KERNEL in line and "kernel_metadata" not in line.replace(
                  "\\", "") and "ragged-dot" not in line]
    assert not others, others
    assert "ragged-dot" not in text


def _latent_args(sh, shape):
    """The latent kernel's operands at the cell's widths (128 heads, rows of
    640 lanes, a 16384-page pool of five layers, a 1024-page table)."""
    def sds(s, dtype):
        return jax.ShapeDtypeStruct(s, dtype, sharding=sh)

    S = 32 if len(shape) == 3 else shape[0]
    args = [sds(shape, jnp.bfloat16), sds((5, 16384, PAGE, 640), jnp.bfloat16),
            sds((), jnp.int32), sds((S, 1024), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.int32)]
    if len(shape) == 3:
        args.append(sds((S + 1,), jnp.int32))
    return args


@pytest.mark.parametrize("shape", [(160, 128, 640), (2, 128, 128, 640)],
                         ids=["unified", "rect"])
def test_latent_kernel_compiles_and_carries_its_tag(one_chip, shape):
    args = _latent_args(one_chip, shape)
    fn = (pa.latent_paged_attention_unified if len(shape) == 3
          else pa.latent_paged_attention)
    text = jax.jit(lambda *a: fn(*a, scale=0.1, lat=512, interpret=False)
                   ).lower(*args).compile().as_text()
    flat = text.replace("\n", "").replace("\\", "")
    assert 'kernel_metadata={"kernel":"paged_attention_latent_unified"}' \
        in flat
    assert text.count(KERNEL) == 1


def test_latent_kernel_is_named_where_tracebacks_are_stripped(one_chip):
    """Compiled the benchmark's way, the latent kernel's HLO instruction is
    named after its jitted entry, `paged_attention_latent_call.<n>`, which
    the benchmark's readers take for a paged kernel
    (benchmarks/tick_phases.py, `is_custom_call`), and not for the window
    form's."""
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "benchmarks")
    sys.path.insert(0, bench)
    try:
        import tick_phases
    finally:
        sys.path.remove(bench)
    was = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        text = jax.jit(lambda *a: pa.latent_paged_attention_unified(
            *a, scale=0.1, lat=512, interpret=False)).lower(
                *_latent_args(one_chip, (160, 128, 640))).compile().as_text()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
    names = re.findall(r"%(\S+) = \S+ custom-call\(.*" + re.escape(KERNEL),
                       text)
    assert len(names) == 1, names
    assert tick_phases.is_custom_call(names[0], tick_phases.PAGED_KERNELS)
    assert "paged_attention_latent" in names[0], names
    assert "paged_attention_window" not in names[0], names


@pytest.mark.parametrize("tokens", [32, 160, 504])
def test_latent_kernel_leaves_room_under_the_scoped_vmem_limit(
        one_chip, tokens):
    """At the sizes `latent_q_block` / `latent_kv_pages` choose for the
    published widths the kernel compiles with 14 MiB of VMEM, under the 16 the
    compiler scopes to a kernel on the v5e: an overrun of the limit itself
    may show only on the chip (PR 33). 504 tokens: more than 64 query blocks,
    where the compiler takes 2 MB more (looked at by hand, PR 36)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call

    def limited(*a, **kw):
        return call(*a, compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=14 * 2 ** 20), **kw)

    TQ = pa.latent_q_block(128, 640)
    pages = pa.latent_kv_pages(128, 640, 512, PAGE)
    assert pa.latent_vmem_bytes(128, 640, 512, PAGE, TQ, *pages) \
        <= pa.LATENT_VMEM_BUDGET
    jax.clear_caches()      # the jitted entry may hold an unlimited trace
    try:
        with mock.patch.object(pl, "pallas_call", limited):
            text = jax.jit(lambda *a: pa.latent_paged_attention_unified(
                *a, scale=0.1, lat=512, interpret=False)).lower(
                    *_latent_args(one_chip, (tokens, 128, 640))
                ).compile().as_text()
    finally:
        jax.clear_caches()
    assert text.count(KERNEL) == 1


# ---- MiMo-V2-Flash (models/mimo_v2_flash.py) at the benchmark cell's shapes --

def _mimo_kernel_args(sh, window: bool, q_shape):
    """The K/V kernel's operands at MiMo-V2-Flash's widths, the pools ROW
    POOLS as the model declares them: q 256 lanes (a head's 128 + its 64 in
    its half of a lane tile), a K row 192 lanes a kv head laid SPLIT with
    nothing padded (`pa.KRow`: 768 lanes at 4 kv heads, 1,536 at 8) and a V
    row 128, 64 query heads; a full layer's 4 kv heads under the cell's
    2,304-page table, or a window layer's 8 under an 18-page ring with a
    sink logit a head. -> (operands, sink, kv heads)."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    S = 32 if len(q_shape) == 3 else q_shape[0]
    kv, width, pages, layers = (8, 18, 1152, 5) if window else (
        4, 2304, 49152, 2)
    args = [sds(q_shape, jnp.bfloat16),
            sds((layers, pages, PAGE, pa.k_row(kv, 192).lanes), jnp.bfloat16),
            sds((layers, pages, PAGE, kv * 128), jnp.bfloat16),
            sds((), jnp.int32), sds((S, width), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.int32)]
    if len(q_shape) == 3:
        args.append(sds((S + 1,), jnp.int32))
    return args, (sds((64,), jnp.float32) if window else None), kv


def _mimo_kernel_text(args, sink, kv, **kw):
    fn = (pa.ragged_paged_attention_unified if len(args[0].shape) == 3
          else pa.ragged_paged_attention)
    kw = dict(kw, scale=192 ** -0.5, interpret=False, kv_heads=kv)
    if sink is not None:
        return jax.jit(lambda sink, *a: fn(*a, window=128, sink=sink, **kw)
                       ).lower(sink, *args).compile().as_text()
    return jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile().as_text()


@pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("q_shape", [(160, 64, 256), (32, 64, 256),
                                     (2, 128, 64, 256), (2, 1, 64, 256)],
                         ids=["unified", "decode_rows", "rect128", "rect1"])
def test_kv_kernel_compiles_at_two_widths_and_in_its_window_form(
        one_chip, window, q_shape):
    """The kernel of row pools at 64 / 4 | 8 heads, K rows of 768 | 1,536
    lanes laid split (a page DMA of whole lane tiles, two products a head of
    128 + 128 deep) and V rows of 512 | 1,024, decode rows and slices, under
    two names: a full layer's is
    `paged_attention_unified`, a window layer's `paged_attention_window`
    (its jitted entry `paged_attention_window_call`; benchmarks/
    layer_metrics/window_kernel_ms.tick.py finds it by that). The full
    layer's 2,304-page table (295 KB of scalar prefetch) fits SMEM; both
    pools go in where they lie."""
    args, sink, kv = _mimo_kernel_args(one_chip, window, q_shape)
    text = _mimo_kernel_text(args, sink, kv)
    flat = text.replace("\n", "").replace("\\", "")
    name = "paged_attention_window" if window else "paged_attention_unified"
    assert 'kernel_metadata={"kernel":"%s"}' % name in flat
    assert text.count(KERNEL) == 1
    for pool in args[1:3]:
        shape = "bf16[%s]" % ",".join(map(str, pool.shape))
        moved = [line.strip()[:160] for line in text.splitlines()
                 if re.search(r"= %s\S* (copy|transpose|fusion)\("
                              % re.escape(shape), line)]
        assert shape in text and not moved, moved


@pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
def test_kv_rows_kernel_leaves_room_under_the_scoped_vmem_limit(
        one_chip, window):
    """At the sizes `kv_sizes` chooses for MiMo-V2-Flash's widths the row
    kernel compiles with KV_VMEM_BUDGET of VMEM, under the 16 MB the compiler
    scopes to a kernel on the v5e, and with what the hand reckoning counts:
    the reckoning is not under the compiler's own count."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call
    args, sink, kv = _mimo_kernel_args(one_chip, window, (160, 64, 256))
    sizes = pa.kv_sizes(64, kv, 192, 128, PAGE, 2, rows=True,
                        window=128 if window else None)
    assert sizes == pa.KVSizes(32, *((16, 16) if window else (64, 64)), True,
                               (128, 64))
    reckoned = pa.kv_vmem_bytes(64, kv, 192, 128, PAGE, 2, True,
                                sizes.q_block, sizes.pages_one,
                                sizes.pages_many)
    assert reckoned <= pa.KV_VMEM_BUDGET

    def compiles_under(limit):
        def limited(*a, **kw):
            return call(*a, compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=limit), **kw)

        jax.clear_caches()  # the jitted entry may hold an unlimited trace
        try:
            with mock.patch.object(pl, "pallas_call", limited):
                return _mimo_kernel_text(args, sink, kv).count(KERNEL) == 1
        except Exception as e:
            assert "vmem" in str(e).lower(), e
            return False
        finally:
            jax.clear_caches()

    assert compiles_under(reckoned)
    assert not compiles_under(reckoned // 2)


def test_window_kernel_is_named_where_tracebacks_are_stripped(one_chip):
    """Compiled the benchmark's way (no call stack in source locations) the
    window form's HLO instruction is named after its jitted entry, and the
    full form's after its own: a trace tells the two apart by
    `paged_attention_window` (PR 32 found the jitted function's name is the
    kernel's instruction)."""
    names = {}
    was = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        for window in (False, True):
            args, sink, kv = _mimo_kernel_args(one_chip, window,
                                               (160, 64, 256))
            kw = dict(scale=192 ** -0.5, interpret=False, kv_heads=kv)
            if window:
                kw.update(window=128)
            text = jax.jit(
                lambda sink, *a: pa.ragged_paged_attention_unified(
                    *a, sink=sink, **kw)).lower(
                        sink, *args).compile().as_text()
            names[window] = re.findall(
                r"%(\S+) = \S+ custom-call\(.*" + re.escape(KERNEL), text)
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
    assert len(names[False]) == len(names[True]) == 1
    assert "paged_attention_window" in names[True][0], names
    assert "paged_attention_" in names[False][0], names
    assert "paged_attention_window" not in names[False][0], names


@pytest.mark.parametrize("backbone", ["mixed160", "rect128"])
def test_mimo_step_compiles_with_both_groups_pools_in_place(
        one_chip, on_tpu, backbone):
    """The step programs of `mimov2flash-longdoc-closed32` at the published
    widths (benchmarks/configs/mimo-v2-flash-l7-e16.json) compile for the
    chip with one layer of each kind (full + dense, window + experts, full +
    experts): all four donated pools go through in the layout they came in
    (no pool-sized copy, the results aliased to the parameters), and the
    Pallas kernels are the K/V kernel under its two names, one a layer."""
    from ray_tpu.llm import model_runner
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import mimo_v2_flash as mm

    cfg = mm.MimoV2FlashConfig(
        vocab_size=19072, hybrid_layer_pattern=(0, 1, 0),
        moe_layer_freq=(0, 1, 1), experts_held=(0, 16),
        max_position_embeddings=36864)
    params = jax.eval_shape(lambda: mm.init_params(cfg, jax.random.key(0)))
    init = model_runner.init_cache
    with mock.patch.object(model_runner, "init_cache",
                           lambda *a: jax.eval_shape(lambda: init(*a))):
        runner = ModelRunner(cfg, params, num_blocks=49152, block_size=PAGE,
                             attention_impl="pallas", max_batch=32)
    assert runner.group_pages == {"all": 49152, "window": 1152}
    assert runner.table_widths == {"all": 2304, "window": 18}

    def on_chip(tree):
        return _abstract(tree, jax.tree.map(lambda _: one_chip, tree))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    S = 32
    fn, args = {
        "mixed160": (runner._backbone_mixed, (
            i32(160), i32(S), i32(S), i32(S + 1),
            {"all": i32(S, 2304), "window": i32(S, 18)})),
        # the benchmark's check: it gives `step` ONE table, and `step` lays
        # the window ring on the host before the program runs
        "rect128": (runner._backbone, (
            i32(2, 128), i32(2), i32(2), i32(2),
            {"all": i32(2, 2304), "window": i32(2, 18)})),
    }[backbone]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(runner.cache), *args).compile()
    text = compiled.as_text()
    pool_bytes = 0
    for a in runner.cache_arrays:
        pool = "bf16[%s]" % ",".join(map(str, a.shape))
        assert pool in text
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= %s\S* copy\(" % re.escape(pool), line)]
        assert not copies, copies
        pool_bytes += int(np.prod(a.shape)) * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 1 << 30
    flat = text.replace("\n", "").replace("\\", "")
    full = flat.count('kernel_metadata={"kernel":"paged_attention_unified"}')
    window = flat.count('kernel_metadata={"kernel":"paged_attention_window"}')
    assert (full, window) == (2, 1), (full, window)
    # three grouped products each of this cut's two routed layers
    # (ops/grouped_dot.py)
    assert flat.count('kernel_metadata={"kernel":"grouped_dot"}') == 6
    assert flat.count("kernel_metadata=") == 3 + 6
    assert "ragged-dot" not in text


# ---- Trinity-Large-Preview (models/afmoe.py, PR 59) at its cell's shapes -----

from chip_smoke import AFMOE_CUT  # noqa: E402


def _afmoe_kernel_args(sh, window: bool, q_shape):
    """The row kernel's operands at Trinity-Large-Preview's widths: 48 query
    heads over 8 kv heads of 128 (SIX query heads a kv head: every other
    configuration has a power of two), K and V rows 1,024 lanes; the one full
    layer under the cell's 576-page table, or a window layer under the
    266-page ring of a 4,096-token window."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    S = 32 if len(q_shape) == 3 else q_shape[0]
    width, layers = (266, 4) if window else (576, 1)
    args = [sds(q_shape, jnp.bfloat16),
            sds((layers, 12288, PAGE, 1024), jnp.bfloat16),
            sds((layers, 12288, PAGE, 1024), jnp.bfloat16),
            sds((), jnp.int32), sds((S, width), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.int32)]
    if len(q_shape) == 3:
        args.append(sds((S + 1,), jnp.int32))
    return args


def _afmoe_kernel_text(args, window: bool):
    fn = (pa.ragged_paged_attention_unified if len(args[0].shape) == 3
          else pa.ragged_paged_attention)
    kw = dict(scale=128 ** -0.5, interpret=False, kv_heads=8,
              window=4096 if window else None)
    return jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile().as_text()


@pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("q_shape", [(160, 48, 128), (32, 48, 128),
                                     (2, 128, 48, 128), (2, 1, 48, 128)],
                         ids=["unified", "decode_rows", "rect128", "rect1"])
def test_kv_kernel_compiles_at_six_query_heads_a_kv_head(one_chip, window,
                                                         q_shape):
    """The kernel of row pools at 48 / 8 heads of 128 lanes, decode rows and
    slices, full and under a window of 4,096 tokens (a ring of 266 pages),
    at the sizes `kv_sizes` chooses: one kernel under the form's name, both
    pools where they lie."""
    args = _afmoe_kernel_args(one_chip, window, q_shape)
    text = _afmoe_kernel_text(args, window)
    flat = text.replace("\n", "").replace("\\", "")
    name = "paged_attention_window" if window else "paged_attention_unified"
    assert 'kernel_metadata={"kernel":"%s"}' % name in flat
    assert text.count(KERNEL) == 1
    for pool in args[1:3]:
        shape = "bf16[%s]" % ",".join(map(str, pool.shape))
        moved = [line.strip()[:160] for line in text.splitlines()
                 if re.search(r"= %s\S* (copy|transpose|fusion)\("
                              % re.escape(shape), line)]
        assert shape in text and not moved, moved


@pytest.mark.parametrize("window", [False, True], ids=["full", "window"])
def test_kv_rows_kernel_fits_its_reckoning_at_a_window_of_4096(one_chip,
                                                               window):
    """At the sizes `kv_sizes` chooses for 48 / 8 heads of 128 lanes, with
    and without a 4,096-token window (whose tile is no longer a block of one
    token's: `WINDOW_TILES`), the row kernel compiles with what the hand
    reckoning counts of VMEM, under KV_VMEM_BUDGET."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call
    sizes = pa.kv_sizes(48, 8, 128, 128, PAGE, 2, rows=True,
                        window=4096 if window else None)
    reckoned = pa.kv_vmem_bytes(48, 8, 128, 128, PAGE, 2, True,
                                sizes.q_block, sizes.pages_one,
                                sizes.pages_many)
    assert reckoned <= pa.KV_VMEM_BUDGET

    def limited(*a, **kw):
        return call(*a, compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=reckoned), **kw)

    jax.clear_caches()      # the jitted entry may hold an unlimited trace
    try:
        with mock.patch.object(pl, "pallas_call", limited):
            text = _afmoe_kernel_text(
                _afmoe_kernel_args(one_chip, window, (160, 48, 128)), window)
    finally:
        jax.clear_caches()
    assert text.count(KERNEL) == 1


@pytest.mark.parametrize("backbone", ["mixed160", "rect128"])
def test_afmoe_step_compiles_with_both_groups_pools_in_place(one_chip, on_tpu,
                                                             backbone):
    """The step programs of `trinitylarge-docqa-closed32` at the published
    widths and the cell's cut (benchmarks/configs/trinity-large-l5-e32.json:
    five layers, 32 held experts, an eighth of the vocabulary): all four
    donated row pools go through the layers where they lie (no pool-sized
    copy, the results aliased), the Pallas kernels are the K/V kernel under
    its two names, one a layer, and three grouped products a routed layer,
    and the whole tick's arguments and temporaries fit the chip."""
    from ray_tpu.llm import model_runner
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import afmoe

    cfg = afmoe.AfmoeConfig(max_position_embeddings=9216, **AFMOE_CUT)
    params = jax.eval_shape(lambda: afmoe.init_params(cfg, jax.random.key(0)))
    init = model_runner.init_cache
    with mock.patch.object(model_runner, "init_cache",
                           lambda *a: jax.eval_shape(lambda: init(*a))):
        runner = ModelRunner(cfg, params, num_blocks=12288, block_size=PAGE,
                             attention_impl="pallas", max_batch=32)
    assert runner.group_pages == {"all": 12288, "window": 12288}
    assert runner.table_widths == {"all": 576, "window": 266}
    assert [(a.name, a.shape) for a in runner.cache_arrays] == [
        ("k_all", (1, 12288, 16, 1024)), ("v_all", (1, 12288, 16, 1024)),
        ("k_window", (4, 12288, 16, 1024)),
        ("v_window", (4, 12288, 16, 1024))]

    def on_chip(tree):
        return _abstract(tree, jax.tree.map(lambda _: one_chip, tree))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def tables(S):
        return {"all": i32(S, 576), "window": i32(S, 266)}

    S, T = 32, 160
    fn, args = {
        # the whole tick: backbone, head and sampler at 16 x 25,024
        "mixed160": (runner._step_mixed, (
            i32(T), i32(S, 1), i32(T), i32(S), i32(S), i32(S + 1), tables(S),
            i32(S, 1), i32(S, 1), i32(S), f32(S), i32(S), f32(S), i32(S),
            i32(S))),
        "rect128": (runner._step, (
            i32(2, 128), i32(2), i32(2), i32(2), tables(2))),
    }[backbone]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(runner.cache), *args).compile()
    text = compiled.as_text()
    pool_bytes = 0
    for a in runner.cache_arrays:
        pool = "bf16[%s]" % ",".join(map(str, a.shape))
        assert pool in text
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= %s\S* copy\(" % re.escape(pool), line)]
        assert not copies, copies
        pool_bytes += int(np.prod(a.shape)) * 2
    mem = compiled.memory_analysis()
    assert pool_bytes <= mem.alias_size_in_bytes < pool_bytes + (1 << 20)
    assert mem.temp_size_in_bytes < 1 << 28
    # 12.67 GB of arguments + 0.05 GB of temporaries (PR 59)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12.9e9
    flat = text.replace("\n", "").replace("\\", "")
    count = lambda name: flat.count('kernel_metadata={"kernel":"%s"}' % name)
    assert (count("paged_attention_unified"),
            count("paged_attention_window")) == (1, 4)
    assert count("grouped_dot") == 12
    assert flat.count("kernel_metadata=") == 17
    assert "ragged-dot" not in text


# ---- Phi-4-mini-flash (PR 35): the scan kernel, the pair form, the step ------

def test_scan_kernel_compiles_at_the_published_widths(one_chip):
    """The selective scan over a tick's ragged rows at 5,120 channels x 16
    (192 rows of 64 sequences, 128 slots of 9 layers): one Pallas call, named
    after its jitted entry `ssm_scan_call` where tracebacks are stripped (so
    that no reader of `tpu_custom_call*` counts it), the donated state
    written where it lies (no state-sized copy, the result aliased)."""
    from ray_tpu.ops import ssm_scan as ss

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    R, d_i, N, S = 192, 5120, 16, 64
    state = ss.state_shape(9, 128, N, d_i)
    args = (sds((R, d_i)), sds((R, d_i)), sds((R, N)), sds((R, N)),
            sds((N, d_i)), sds(state), sds((), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.int32), sds((S,), jnp.int32),
            sds((S,), jnp.bool_))
    was = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    try:
        compiled = jax.jit(
            lambda *a: ss.ssm_scan(*a, impl="pallas", interpret=False),
            donate_argnums=(5,)).lower(*args).compile()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
    text = compiled.as_text()
    names = re.findall(r"%(\S+) = .* custom-call\(.*" + re.escape(KERNEL),
                       text)     # (y, state): a tuple-typed instruction
    assert len(names) == 1 and names[0].startswith("ssm_scan_call"), names
    flat = text.replace("\n", "").replace("\\", "")
    assert 'kernel_metadata={"kernel":"ssm_scan"}' in flat
    shape = "f32[%s]" % ",".join(map(str, state))
    copies = [line.strip()[:160] for line in text.splitlines()
              if re.search(r"= %s\S* copy\(" % re.escape(shape), line)]
    assert shape in text and not copies, copies
    assert compiled.memory_analysis().alias_size_in_bytes \
        == int(np.prod(state)) * 4


@pytest.mark.parametrize("window", [None, 512], ids=["full", "window"])
@pytest.mark.parametrize("q_shape", [(192, 40, 128), (64, 40, 128),
                                     (2, 128, 40, 128), (2, 1, 40, 128)],
                         ids=["tick", "narrowed", "rect128", "rect1"])
def test_pair_form_compiles_over_row_pools(one_chip, window, q_shape):
    """The K/V kernel over ROW POOLS of 10 kv pairs of 128 lanes (40 query
    heads of 64 as half-zero 128-lane rows, 20 kv heads of 64 paired), full
    and window form (window 512, a 42-page ring), for a tick's rows, the
    narrowed rows of the cross-decoder and the check's rectangles: both pools
    go in where they lie, 1,280 lanes a token with no padding."""
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S = 64 if len(q_shape) == 3 else 2
    layers, pages, width = (8, 5376, 42) if window else (1, 20480, 512)
    pool = sds((layers, pages, PAGE, 1280))
    args = [sds(q_shape), pool, pool, sds((), jnp.int32),
            sds((S, width), jnp.int32), sds((S,), jnp.int32),
            sds((S,), jnp.int32)]
    if len(q_shape) == 3:
        args.append(sds((S + 1,), jnp.int32))
    fn = (pa.ragged_paged_attention_unified if len(q_shape) == 3
          else pa.ragged_paged_attention)
    text = jax.jit(lambda *a: fn(*a, scale=0.125, window=window, kv_heads=10,
                                 interpret=False)).lower(
        *args).compile().as_text()
    assert text.count(KERNEL) == 1
    shape = "bf16[%s]" % ",".join(map(str, pool.shape))
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= %s\S* (copy|transpose|fusion)\("
                          % re.escape(shape), line)]
    assert shape in text and not moved, moved
    # as it lies: a token's 1,280 lanes, no sublane padding of the heads
    assert re.search(re.escape(shape) + r"\{3,2,1,0:T\(8,128\)\(2,1\)\}",
                     text), "the row pool is not laid out whole on the lanes"


@pytest.mark.parametrize("backbone", ["mixed192", "rect128"])
def test_phi4flash_step_compiles_with_every_pool_in_place(
        one_chip, on_tpu, backbone):
    """The step programs of `phi4flash-reason-closed64` at the published
    widths (benchmarks/configs/phi-4-mini-flash-l32.json) with 8 of 32 layers
    (one (Mamba, window) pair, the pair (memory, full), one (GMU, cross)
    pair): the four K/V row pools and the scan state go through where they
    lie (no pool-sized copy; the convolution tail, 3 x 5,120 a slot, is the
    one array XLA re-lays), and the Pallas kernels are the scan's (one a
    Mamba layer), the window form and the full form (layer L/2 + 1 and the
    cross layer)."""
    from ray_tpu.llm import model_runner
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import phi4flash as pm

    cfg = pm.Phi4FlashConfig(num_hidden_layers=8,
                             max_position_embeddings=8192)
    params = jax.eval_shape(lambda: pm.init_params(cfg, jax.random.key(0)))
    init = model_runner.init_cache
    with mock.patch.object(model_runner, "init_cache",
                           lambda *a: jax.eval_shape(lambda: init(*a))):
        runner = ModelRunner(cfg, params, num_blocks=20480, block_size=PAGE,
                             attention_impl="pallas", max_batch=64)
    assert runner.group_pages == {"all": 20480, "window": 5376, "state": 128}
    assert runner.table_widths == {"all": 512, "window": 42, "state": 1}

    def on_chip(tree):
        return _abstract(tree, jax.tree.map(lambda _: one_chip, tree))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def tables(S):
        return {"all": i32(S, 512), "window": i32(S, 42), "state": i32(S, 1)}

    S = 64
    fn, args = {
        "mixed192": (runner._backbone_mixed, (
            i32(192), i32(S), i32(S), i32(S + 1), tables(S), None, None,
            i32(S))),
        "rect128": (runner._backbone, (
            i32(2, 128), i32(2), i32(2), i32(2), tables(2))),
    }[backbone]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(runner.cache), *args).compile()
    text = compiled.as_text()
    pool_bytes = 0
    for a in runner.cache_arrays:
        kind = "f32" if a.dtype == jnp.float32 else "bf16"
        pool = "%s[%s]" % (kind, ",".join(map(str, a.shape)))
        assert pool in text
        pool_bytes += int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        if a.name == "conv_tail":
            continue
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= %s\S* copy\(" % re.escape(pool), line)]
        assert not copies, copies
    mem = compiled.memory_analysis()
    # every array aliased (the tail's 3 rows a slot lie padded to 4)
    assert pool_bytes <= mem.alias_size_in_bytes < pool_bytes + (1 << 20)
    assert mem.temp_size_in_bytes < 1 << 30
    flat = text.replace("\n", "").replace("\\", "")
    count = lambda name: flat.count('kernel_metadata={"kernel":"%s"}' % name)
    assert (count("ssm_scan"), count("paged_attention_window"),
            count("paged_attention_unified")) == (2, 1, 2)
    assert flat.count("kernel_metadata=") == 5


# ---- power retention (ops/power_retention.py, models/brumby.py) -------------

@pytest.mark.parametrize("rows", [16, 144], ids=["decode16", "rows16+128"])
def test_retention_kernel_compiles_at_brumbys_widths(one_chip, rows):
    """The kernel at Brumby-14B's widths (40 / 8 heads of 128: a state block
    of 65 x 128 x 128 float32 a kv head) over the cell's 16 sequences and 33
    slots of 6 layers: Mosaic takes the dynamic lane rotations, the 128 x
    128 transposes, the DMAs from a multiple of 8 rows and the state's and
    the buffer's own DMAs across grid steps (two sets of 4.26 MB in VMEM),
    and S, z and the buffered rows are aliased in and out (6.91 GB: nothing
    is copied)."""
    from ray_tpu.ops import power_retention as pr

    K, G, hd, S, L, slots = 8, 5, 128, 16, 6, 32
    P = rows + 8 * S + pr.CHUNK

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state, norm, buf = (pr.state_shape(L, slots, K, hd),
                        pr.norm_shape(L, slots, K, hd),
                        pr.buffer_shape(L, slots, K, hd))
    compiled = jax.jit(
        lambda *a: pr.power_retention_call(*a, eps=1e-6, interpret=False),
        donate_argnums=(2, 3, 4)).lower(
        sd((K, P, G * hd)), sd((K, P, 4 * hd)), sd(state), sd(norm), sd(buf),
        sd((), jnp.int32), *[sd((S,), jnp.int32)] * 5).compile()
    mem = compiled.memory_analysis()
    held = 4 * (int(np.prod(state)) + int(np.prod(norm)))
    assert held == 33 * 6 * 8 * 65 * 128 * 129 * 4
    held += 4 * int(np.prod(buf))
    assert buf == (6, 33, 8, 2 * pr.FOLD + 8, 128)
    assert held <= mem.alias_size_in_bytes < held + (1 << 20)
    assert mem.temp_size_in_bytes < 1 << 20
    assert compiled.as_text().count(KERNEL) == 1


@pytest.mark.parametrize("backbone", ["mixed144", "rect128"])
def test_brumby_step_compiles_with_the_state_in_place(one_chip, on_tpu,
                                                      backbone):
    """The step programs of `brumby14b-longgen-closed16` at the published
    widths, 6 layers, the whole vocabulary (benchmarks/configs/
    brumby-14b-l6.json): the cache is the state group's four arrays (S, z,
    the buffered rows, their count) and nothing else, all go through the
    layer scan where they lie (no copy of any), the one Pallas kernel is the
    retention's, and arguments and temporaries fit the chip (14.0 GB of
    16)."""
    from ray_tpu.llm import model_runner
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import brumby as bm

    cfg = bm.BrumbyConfig(num_hidden_layers=6)
    params = jax.eval_shape(lambda: bm.init_params(cfg, jax.random.key(0)))
    init = model_runner.init_cache
    with mock.patch.object(model_runner, "init_cache",
                           lambda *a: jax.eval_shape(lambda: init(*a))):
        runner = ModelRunner(cfg, params, num_blocks=18432, block_size=PAGE,
                             attention_impl="pallas", max_batch=16)
    assert runner.group_pages == {"all": 18432, "state": 32}
    assert runner.table_widths == {"all": 2048, "state": 1}
    assert [a.name for a in runner.cache_arrays] == [
        "ret_state", "ret_norm", "ret_rows", "ret_fill"]

    def on_chip(tree):
        return _abstract(tree, jax.tree.map(lambda _: one_chip, tree))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def tables(S):
        return {"all": i32(S, 2048), "state": i32(S, 1)}

    S, T = 16, 144
    fn, args = {
        # the whole tick: backbone, head and sampler at 16 x 151,936
        "mixed144": (runner._step_mixed, (
            i32(T), i32(S, 1), i32(T), i32(S), i32(S), i32(S + 1), tables(S),
            i32(S, 1), i32(S, 1), i32(S), f32(S), i32(S), f32(S), i32(S),
            i32(S))),
        "rect128": (runner._step, (
            i32(2, 128), i32(2), i32(2), i32(2), tables(2))),
    }[backbone]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(runner.cache), *args).compile()
    text = compiled.as_text()
    held = 0
    for a in runner.cache_arrays:
        pool = "%s[%s]" % ("s32" if a.dtype == jnp.int32 else "f32",
                           ",".join(map(str, a.shape)))
        assert pool in text
        held += 4 * int(np.prod(a.shape))
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= %s\S* copy\(" % re.escape(pool), line)]
        assert not copies, copies
    mem = compiled.memory_analysis()
    assert held <= mem.alias_size_in_bytes < held + (1 << 20)
    assert mem.temp_size_in_bytes < 1 << 28
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.2e9
    flat = text.replace("\n", "").replace("\\", "")
    assert flat.count('kernel_metadata={"kernel":"power_retention"}') == 1
    assert flat.count("kernel_metadata=") == 1


def test_brumby_snapshot_copy_touches_one_slot(one_chip):
    """`copy_state` for the 6.91 GB state group: one slot's S, z, buffered
    rows and their count move (0.2 GB), in place; no program copies an
    array."""
    from ray_tpu.ops import power_retention as pr

    shapes = {"ret_state": pr.state_shape(6, 32, 8, 128),
              "ret_norm": pr.norm_shape(6, 32, 8, 128),
              "ret_rows": pr.buffer_shape(6, 32, 8, 128),
              "ret_fill": pr.fill_shape(6, 32)}
    cache = {k: jax.ShapeDtypeStruct(
        v, jnp.int32 if k == "ret_fill" else jnp.float32, sharding=one_chip)
        for k, v in shapes.items()}
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda c, src, dst: {k: v.at[:, dst].set(v[:, src])
                             for k, v in c.items()},
        donate_argnums=(0,)).lower(cache, slot, slot).compile()
    mem = compiled.memory_analysis()
    held = sum(4 * int(np.prod(v)) for v in shapes.values())
    assert held <= mem.alias_size_in_bytes < held + (1 << 20)
    assert mem.temp_size_in_bytes < 1 << 28         # a slot is 0.2 GB


# ---- Kimi Delta Attention (ops/kda.py, models/kimi_linear.py) --------------

# The cell's cut of the model, stated once (chip_smoke.py holds it to the
# configuration's file in tests/test_chip_smoke.py).
from chip_smoke import KIMI_CUT  # noqa: E402


@pytest.mark.parametrize("fold", [8, 16])
@pytest.mark.parametrize("rows", [64, 192], ids=["decode64", "rows64+128"])
def test_kda_kernel_compiles_at_kimi_linears_widths(one_chip, rows, fold):
    """The kernel at Kimi-Linear's widths (32 heads of 128 x 128 float32, 8 to
    a grid step) over the cell's 64 sequences and 129 slots of 9 layers, its
    rows TOKEN-MAJOR as the layer makes them: Mosaic takes the 128 x 128
    transposes, a decode row's block `(None, 8, 640)` at any row by scalar
    prefetch, the buffer's tile `(fold x 8, 384)` beside the state's block,
    the joining row's store at a traced multiple of 8, a fold's masked
    product and the state's own DMA out, a chunk's DMA of `(64, 8, 640)`
    from a row that is no multiple of 8 (the row is the untiled leading
    axis), the heads read out of it, and the triangular solve's products; S
    and the buffer are aliased in and out (2.43 GB + 0.46 GB at a fold of 8:
    nothing is copied)."""
    from ray_tpu.ops import kda

    Hk, hd, S, L, slots = 32, 128, 64, 9, 128

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = kda.state_shape(L, slots, Hk, hd, hd)
    buf = kda.buffer_shape(L, slots, Hk, hd, hd, fold)
    assert buf == (9, 129, 4, fold * 8, 384)
    compiled = jax.jit(
        lambda *a: kda.kda_call(*a, dk=hd, chunk=kda.CHUNK, sub=kda.SUB,
                                interpret=False),
        donate_argnums=(1, 2)).lower(
        sd((rows + kda.CHUNK, Hk, 5 * hd)), sd(state), sd(buf),
        sd((), jnp.int32), *[sd((S,), jnp.int32)] * 5).compile()
    mem = compiled.memory_analysis()
    held = 4 * (int(np.prod(state)) + int(np.prod(buf)))
    assert held == 129 * 9 * 32 * 4 * (128 * 128 + fold * 384)
    assert held <= mem.alias_size_in_bytes < held + (1 << 20)
    assert mem.temp_size_in_bytes < 1 << 20
    assert compiled.as_text().count(KERNEL) == 1


@pytest.mark.parametrize("backbone", ["mixed192", "rect128"])
def test_kimi_linear_step_compiles_with_both_caches_in_place(one_chip, on_tpu,
                                                             backbone):
    """The step programs of `kimilinear-longout-closed64` at the published
    widths, 12 layers, 32 held experts, the vocabulary's eighth (benchmarks/
    configs/kimi-linear-48b-l12-e32.json): the latent pool of the 3 MLA
    layers AND the state group's S, buffered rows, fills and tails of the 9
    KDA layers go through the layers where they lie (no copy of any: a
    decode row's S is an input block and leaves by the kernel's own DMA),
    the Pallas kernels are the latent one and the KDA one, and arguments and
    temporaries fit the chip (11.44 GB + 0.26 GB of 16.9: the buffer is 0.46
    GB of it)."""
    from ray_tpu.llm import model_runner
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import kimi_linear as km

    cfg = km.KimiLinearConfig(max_position_embeddings=16384, **KIMI_CUT)
    params = jax.eval_shape(lambda: km.init_params(cfg, jax.random.key(0)))
    init = model_runner.init_cache
    with mock.patch.object(model_runner, "init_cache",
                           lambda *a: jax.eval_shape(lambda: init(*a))):
        runner = ModelRunner(cfg, params, num_blocks=32768, block_size=PAGE,
                             attention_impl="pallas", max_batch=64)
    assert runner.group_pages == {"all": 32768, "state": 128}
    assert runner.table_widths == {"all": 1024, "state": 1}
    assert runner.page_nbytes == 3 * 16 * 640 * 2
    assert [(a.name, a.shape) for a in runner.cache_arrays] == [
        ("latent", (3, 32768, 16, 640)),
        ("kda_state", (9, 129, 32, 128, 128)),
        ("kda_rows", (9, 129, 4, 64, 384)),
        ("kda_fill", (9, 129)),
        ("kda_tail", (9, 129, 288, 128))]

    def on_chip(tree):
        return _abstract(tree, jax.tree.map(lambda _: one_chip, tree))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def tables(S):
        return {"all": i32(S, 1024), "state": i32(S, 1)}

    S, T = 64, 192
    fn, args = {
        # the whole tick: backbone, head and sampler at 64 x 20,480
        "mixed192": (runner._step_mixed, (
            i32(T), i32(S, 1), i32(T), i32(S), i32(S), i32(S + 1), tables(S),
            i32(S, 1), i32(S, 1), i32(S), f32(S), i32(S), f32(S), i32(S),
            i32(S))),
        "rect128": (runner._step, (
            i32(2, 128), i32(2), i32(2), i32(2), tables(2))),
    }[backbone]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(runner.cache), *args).compile()
    text = compiled.as_text()
    held = 0
    for a in runner.cache_arrays:
        pool = "%s[%s]" % ({"bfloat16": "bf16", "float32": "f32",
                            "int32": "s32"}[jnp.dtype(a.dtype).name],
                           ",".join(map(str, a.shape)))
        assert pool in text
        held += jnp.dtype(a.dtype).itemsize * int(np.prod(a.shape))
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= %s\S* copy\(" % re.escape(pool), line)]
        assert not copies, copies
    mem = compiled.memory_analysis()
    assert held <= mem.alias_size_in_bytes < held + (1 << 20)
    assert mem.temp_size_in_bytes < 1 << 29
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12.0e9
    flat = text.replace("\n", "").replace("\\", "")
    count = lambda name: flat.count('kernel_metadata={"kernel":"%s"}' % name)
    assert count("kda") == 9
    # three grouped products each of the eleven routed layers
    assert count("grouped_dot") == 33
    assert flat.count("kernel_metadata=") == 12 + 33
    assert "ragged-dot" not in text


# ---- GLM-5.2: sparse latent attention (ops/sparse_latent.py) ---------------

from chip_smoke import GLM_CUT  # noqa: E402


@pytest.mark.parametrize("T", [32, 160], ids=["decode32", "rows32+128"])
def test_dsa_entries_compile_at_glm52s_widths(one_chip, T):
    """The three entries at the cell's sizes (32 index heads of 128 over a
    block table of 36,864 positions, a selection of 2,048 of them, 64 heads
    over 640-lane rows): Mosaic takes the index kernel's page walk, the
    selection kernel's 32 passes over a (8, 36,864) block and the attention
    kernel's (2,048, 640) rows a token, a lane block of the (T, 2,048, 2,560)
    operand that ONE gather a selection group fetches, beside the step's own
    rows; each carries its tag."""
    from ray_tpu.ops import sparse_latent as sl

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, pages, width = 32, 20480, 2304
    scalars = (sds((S, width), jnp.int32), sds((S,), jnp.int32),
               sds((S,), jnp.int32), sds((S + 1,), jnp.int32))
    index = jax.jit(lambda q, w, pool, li, *a: sl.dsa_index(
        q, w, pool, li, *a, impl="pallas", interpret=False)).lower(
        sds((T, 32, 128), jnp.bfloat16), sds((T, 32), jnp.float32),
        sds((2, pages, PAGE, 128), jnp.bfloat16), sds((), jnp.int32),
        *scalars).compile().as_text()
    select = jax.jit(lambda s, n: sl.dsa_select(
        s, n, topk=2048, impl="pallas", interpret=False)).lower(
        sds((T, width * PAGE), jnp.float32),
        sds((T,), jnp.int32)).compile().as_text()
    def attend_fn(q, p, c, pool, g, tb, seq, at, first, own):
        picked = sl.gather_selection(
            pool, g, sl.pool_rows(p, tb, seq, PAGE, impl="pallas"))
        cached, mask = sl.step_rows(p, c, seq, at, first, at >= 0)
        return sl.dsa_attend(q, picked, c, cached, own, mask, place=3,
                             scale=0.0625, lat=512, impl="pallas",
                             interpret=False)

    attend = jax.jit(attend_fn).lower(
        sds((T, 64, 640), jnp.bfloat16), sds((T, 2048), jnp.int32),
        sds((T,), jnp.int32), sds((2, pages, PAGE, 2560), jnp.bfloat16),
        sds((), jnp.int32), scalars[0], sds((T,), jnp.int32),
        sds((T,), jnp.int32), sds((T,), jnp.int32),
        sds((T, 640), jnp.bfloat16)).compile().as_text()
    for name, text in (("dsa_index", index), ("dsa_select", select),
                       ("dsa_attend", attend)):
        flat = text.replace("\n", "").replace("\\", "")
        assert flat.count('kernel_metadata={"kernel":"%s"}' % name) >= 1
    assert " sort(" not in select
    # the pool is gathered from where it lies: no group of it is copied, and
    # the kernel reads its lane block of the gathered operand where it lies
    pool = "bf16[2,%d,16,2560]" % pages
    assert pool in attend and not re.search(
        r"= %s\S* (copy|dynamic-slice)\(" % re.escape(pool), attend)
    assert "bf16[%d,2048,2560]" % T in attend
    assert "bf16[%d,2048,640]" % T not in attend
    # and a position's row by products: no gather of single elements
    assert "slice_sizes={1,1}," not in attend


@pytest.mark.parametrize("backbone", ["mixed160", "mixed32", "rect128"])
def test_glm_dsa_step_compiles_with_both_pools_in_place(one_chip, on_tpu,
                                                        backbone):
    """The step programs of `glm52-longdoc-closed32` at the published widths,
    8 layers, 8 held experts, the vocabulary's eighth (benchmarks/configs/
    glm-5.2-l8-e8.json): the latent pool, two selection groups of four
    layers' rows side by side, AND the index-key pool of the 2 "full" layers
    go through the layers where they lie (no copy of either: the lane-window
    scatter writes in place), every layer holds the dense latent kernel and
    the sparse entries under one branch, only the "full" layers score AND
    GATHER (two gathers of (T, 2048, 2560) a step, none of (T, 2048, 640)),
    and arguments and temporaries fit the chip."""
    from ray_tpu.llm import model_runner
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import glm_dsa as gd

    cfg = gd.GlmDsaConfig(**GLM_CUT)
    params = jax.eval_shape(lambda: gd.init_params(cfg, jax.random.key(0)))
    init = model_runner.init_cache
    with mock.patch.object(model_runner, "init_cache",
                           lambda *a: jax.eval_shape(lambda: init(*a))):
        runner = ModelRunner(cfg, params, num_blocks=20480, block_size=PAGE,
                             attention_impl="pallas", max_batch=32)
    assert runner.table_widths == {"all": 2304}
    assert runner.page_nbytes == 16 * (8 * 640 + 2 * 128) * 2
    assert [(a.name, a.shape) for a in runner.cache_arrays] == [
        ("latent", (2, 20480, 16, 2560)), ("index", (2, 20480, 16, 128))]

    def on_chip(tree):
        return _abstract(tree, jax.tree.map(lambda _: one_chip, tree))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    S = 32
    T = {"mixed160": 160, "mixed32": 32, "rect128": 2 * 128}[backbone]
    fn, args = (runner._step, (
        i32(2, 128), i32(2), i32(2), i32(2), {"all": i32(2, 2304)})
    ) if backbone == "rect128" else (runner._step_mixed, (
        i32(T), i32(S, 1), i32(T), i32(S), i32(S), i32(S + 1),
        {"all": i32(S, 2304)}, i32(S, 1), i32(S, 1), i32(S), f32(S),
        i32(S), f32(S), i32(S), i32(S)))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(runner.cache), *args).compile()
    text = compiled.as_text()
    held = 0
    for a in runner.cache_arrays:
        pool = "bf16[%s]" % ",".join(map(str, a.shape))
        assert pool in text
        held += 2 * int(np.prod(a.shape))
        # nor written a token at a time: a scatter whose window is SOME lanes
        # of a row becomes a loop of `dynamic-update-slice` (0.5 ms a layer
        # on the chip, PR 50), which is why `pool_write_rows` writes the
        # tokens' whole rows back
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= %s\S* (copy|dynamic-update-slice)\("
                               % re.escape(pool), line)]
        assert not copies, copies
    mem = compiled.memory_analysis()
    assert held <= mem.alias_size_in_bytes < held + (1 << 20)
    print(backbone, "arguments", mem.argument_size_in_bytes / 1e9,
          "temporaries", mem.temp_size_in_bytes / 1e9)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9
    # no temporary the size of the latent pool (3.36 GB), and the selected
    # rows gathered once a selection group, all four layers' at once
    assert mem.temp_size_in_bytes < 3.3e9
    wide, narrow = ("bf16[%d,2048,%d]" % (T, w) for w in (2560, 640))
    gathers = [line for line in text.splitlines()
               if re.search(r"= %s\S* (gather|fusion)\(" % re.escape(wide),
                            line) and "gather" in line]
    assert len(gathers) == 2, gathers
    assert narrow not in text
    flat = text.replace("\n", "").replace("\\", "")
    count = lambda name: flat.count('kernel_metadata={"kernel":"%s"}' % name)
    assert (count("dsa_select"), count("dsa_attend"),
            count("paged_attention_latent_unified")) == (2, 8, 8)
    assert count("dsa_index") in (2, 4)     # the text names it once or twice
    # three grouped products each of the seven routed layers
    assert count("grouped_dot") == 21 and "ragged-dot" not in text
    # Compiled the benchmark's way (tracebacks stripped) every kernel's
    # instruction is named after its jitted entry, which is how a trace's
    # readers find it: none stands under a conditional, where it would be
    # `tpu_custom_call.<n>` (PR 49's first traced run: five readers None).
    was = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.clear_caches()      # the entries' lowerings above hold their stacks
    try:
        stripped = jax.jit(fn, donate_argnums=(1,)).lower(
            on_chip(params), on_chip(runner.cache), *args).compile().as_text()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
        jax.clear_caches()
    names = re.findall(r"%(\S+) = \S+ custom-call\(.*" + re.escape(KERNEL),
                       stripped)
    by_entry = {e: sum(n.startswith(e + ".") or n == e for n in names)
                for e in ("dsa_index_call", "dsa_select_call",
                          "dsa_attend_call", "paged_attention_latent_call")}
    # `dsa_index_call` stays ONE kernel a "full" layer (2) since PR 51: a
    # walk that several sequences share and a sequence's own walk are blocks
    # of the same grid, told apart by the scalars `index_walks` lays (whose
    # table, from which tile, up to which row), not two kernels; what the
    # plan adds around it is XLA's, under the same entry.
    assert by_entry == {"dsa_index_call": 2, "dsa_select_call": 2,
                        "dsa_attend_call": 8,
                        "paged_attention_latent_call": 8}, names


# ---- Nemotron-3-Super: Mamba-2 (ops/ssd.py, models/nemotron_h.py) ----------

from chip_smoke import NEMOTRON_CUT  # noqa: E402


@pytest.mark.parametrize("rows", [64, 192], ids=["decode64", "rows64+128"])
def test_ssd_kernel_compiles_at_nemotron_3_supers_widths(one_chip, rows):
    """The kernel at the published widths (128 heads of 64 x 128 float32 in 8
    groups, one group's 16 heads to a grid step) over the cell's 64 sequences
    and 129 slots of 5 layers, its rows TOKEN-MAJOR as the layer makes them:
    Mosaic takes the 128 x 128 transposes, a decode row's block `(None, 16,
    128)` and its groups' `(None, 8, 256)` at any row by scalar prefetch, a
    chunk's DMAs of `(128, 16, 128)` and `(128, 8, 256)` from a row that is
    no multiple of 8, a head and a group read out of them at a traced index,
    and the chunked form's products; since PR 56 a slot's buffer tile `(88,
    128)` as a block in and out, a row stored into it at a traced multiple
    of 8, the `(8, 128)(128, 128)` product of C_t with the buffered B, the
    fold's pair of heads read at a traced index and the state's own DMA out
    of scratch. S and the buffer are aliased in and out (2.7 GB + 0.23 GB:
    nothing is copied)."""
    from ray_tpu.ops import ssd

    Hm, P, G, N, S, L, slots = 128, 64, 8, 128, 64, 5, 128

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = ssd.state_shape(L, slots, Hm, P, N)
    buf = ssd.buffer_shape(L, slots, Hm, G, P, N)
    compiled = jax.jit(
        lambda *a: ssd.ssd_call(*a, chunk=ssd.CHUNK, interpret=False),
        donate_argnums=(2, 3)).lower(
        sd((rows + ssd.CHUNK, Hm, 2 * P)), sd((rows + ssd.CHUNK, G, 2 * N)),
        sd(state), sd(buf), sd((), jnp.int32),
        *[sd((S,), jnp.int32)] * 5).compile()
    mem = compiled.memory_analysis()
    held = 4 * (int(np.prod(state)) + int(np.prod(buf)))
    assert held == 129 * 5 * (128 * 64 * 128 + 8 * 88 * 128) * 4
    assert held <= mem.alias_size_in_bytes < held + (1 << 20)
    assert mem.temp_size_in_bytes < 1 << 20
    text = compiled.as_text()
    assert text.count(KERNEL) == 1
    assert 'kernel_metadata={"kernel":"ssd"}' in text.replace(
        "\n", "").replace("\\", "")


@pytest.mark.parametrize("rows", [32, 160], ids=["decode32", "rows32+128"])
def test_ssd_kernel_compiles_with_a_heads_own_keys(one_chip, rows):
    """The same kernel where every head is a group of its own (G = H), at
    MiniCPM-SALA's lightning widths: 32 heads of 128 x 128 float32, 16 to a
    grid step, over `minicpm-sala-l16`'s 32 sequences and 65 slots of 12
    layers. Mosaic takes a decode row's `(None, 16, 256)` blocks of x and of
    the heads' own [B | C], the tile `(144, 256)` with a row of packed B
    stored at a traced multiple of 8, a chunk's two DMAs of `(128, 16, 256)`
    and a head's B | C read out of the second at a traced index. S and the
    buffer are aliased in and out."""
    from ray_tpu.ops import ssd

    Hm, P, N, S, L, slots = 32, 128, 128, 32, 12, 64

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = ssd.state_shape(L, slots, Hm, P, N)
    buf = ssd.buffer_shape(L, slots, Hm, Hm, P, N)
    assert buf == (L, slots + 1, 2, 144, 256)
    compiled = jax.jit(
        lambda *a: ssd.ssd_call(*a, chunk=ssd.CHUNK, interpret=False),
        donate_argnums=(2, 3)).lower(
        sd((rows + ssd.CHUNK, Hm, 2 * P)), sd((rows + ssd.CHUNK, Hm, 2 * N)),
        sd(state), sd(buf), sd((), jnp.int32),
        *[sd((S,), jnp.int32)] * 5).compile()
    mem = compiled.memory_analysis()
    held = 4 * (int(np.prod(state)) + int(np.prod(buf)))
    assert held <= mem.alias_size_in_bytes < held + (1 << 20)
    assert mem.temp_size_in_bytes < 1 << 20
    assert compiled.as_text().count(KERNEL) == 1


@pytest.mark.parametrize("q_shape", [(192, 32, 128), (64, 32, 128),
                                     (2, 128, 32, 128), (2, 1, 32, 128)],
                         ids=["unified", "decode_rows", "rect128", "rect1"])
def test_kv_rows_kernel_compiles_at_32_query_and_2_kv_heads(one_chip,
                                                            q_shape):
    """The kernel of row pools at Nemotron-3-Super's attention layer: 32 query
    heads over 2 kv heads (SIXTEEN query heads a kv head, a new point for
    `kv_sizes`), K and V rows of 2 x 128 = 256 lanes, one layer under the
    cell's 512-page table; both pools go in where they lie, and the sizes fit
    the stated VMEM budget."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S = 64 if len(q_shape) == 3 else q_shape[0]
    sizes = pa.kv_sizes(32, 2, 128, 128, PAGE, 2, rows=True)
    assert pa.kv_vmem_bytes(32, 2, 128, 128, PAGE, 2, True, sizes.q_block,
                            sizes.pages_one,
                            sizes.pages_many) <= pa.KV_VMEM_BUDGET
    args = [sds(q_shape, jnp.bfloat16),
            sds((1, 32768, PAGE, 256), jnp.bfloat16),
            sds((1, 32768, PAGE, 256), jnp.bfloat16),
            sds((), jnp.int32), sds((S, 512), jnp.int32),
            sds((S,), jnp.int32), sds((S,), jnp.int32)]
    fn = pa.ragged_paged_attention
    if len(q_shape) == 3:
        args.append(sds((S + 1,), jnp.int32))
        fn = pa.ragged_paged_attention_unified
    text = jax.jit(lambda *a: fn(*a, scale=128 ** -0.5, interpret=False,
                                 kv_heads=2)).lower(*args).compile().as_text()
    assert text.count(KERNEL) == 1
    for pool in args[1:3]:
        shape = "bf16[%s]" % ",".join(map(str, pool.shape))
        moved = [line.strip()[:160] for line in text.splitlines()
                 if re.search(r"= %s\S* (copy|transpose|fusion)\("
                              % re.escape(shape), line)]
        assert shape in text and not moved, moved


# ---- MiniCPM-SALA: block-sparse attention (ops/block_sparse.py) ------------

@pytest.mark.parametrize("T", [192], ids=["unified"])
def test_block_sparse_entries_compile_at_minicpm_salas_widths(one_chip, T):
    """The three entries of ops/block_sparse.py at the published widths (32
    query / 2 kv heads of 128, pages of 16, blocks of 64, 64 kept) under
    `minicpm-sala-l16`'s 2,304-page table and 24,576-page pools of 4 layers:
    the first stage's kernel (a sequence's 2,304 page means as one block, the
    pair means by a lane roll, the sum over a kv head's 16 heads), the row
    kernel as ONE kv head of 256 lanes under a table a (row, kv head), and
    the row kernel with its block mask; the pools go in where they lie."""
    from ray_tpu.ops import block_sparse as bs

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, pages, width = 32, 24576, 2304
    g = bs.Geometry(page=PAGE)
    pool = sds((4, pages, PAGE, 256), jnp.bfloat16)
    means = sds((4, pages, 256), jnp.bfloat16)
    q = sds((T, 32, 128), jnp.bfloat16)
    ragged = [sds((S, width)), sds((S,)), sds((S,)), sds((S + 1,))]
    kw = dict(kv_heads=2, scale=128 ** -0.5, geometry=g, impl="pallas",
              interpret=False)

    def select(q, means, layer, *ragged):
        return bs.block_scores(q, means, layer, *ragged, **kw)

    def attend(q, k_pool, v_pool, layer, tables, kv_lens, q_pos, cu, blocks,
               count):
        return bs.block_attend(q, k_pool, v_pool, layer, tables, kv_lens,
                               q_pos, cu, blocks, count, **kw)

    text = jax.jit(select).lower(q, means, sds(()), *ragged).compile(
        ).as_text()
    assert text.count(KERNEL) == 1
    text = jax.jit(attend).lower(
        q, pool, pool, sds(()), *ragged, sds((T, 2, g.topk)),
        sds((T, 2))).compile().as_text()
    assert text.count(KERNEL) == 2
    shape = "bf16[%s]" % ",".join(map(str, pool.shape))
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= %s\S* (copy|transpose|fusion)\("
                          % re.escape(shape), line)]
    assert shape in text and not moved, moved


@pytest.mark.parametrize("shape", ["mimo_full", "trinity_window", "table"])
def test_kv_rows_kernel_starts_a_run_of_pages_a_loop_step(one_chip, capsys,
                                                          shape):
    """The row kernel under the compiler's own scoped VMEM limit at
    MiMo-V2-Flash's full shape (64 pages a step of a block of one token),
    Trinity-Large-Preview's window shape (16) and the table form's
    (`block_attend_call`: 16 query heads over ONE kv head of 256 lanes, 64):
    a block of one token starts its tiles' pages in runs (`pa.start_counted`;
    PR 65), so the Mosaic module holds, at the first tile's start and at a
    step's, 2 PAGE_RUN page DMAs side by side and the two of a page started
    alone, beside the block's fetch of q (the parent's whole module held ten
    starts, the table form's five)."""
    from jax.experimental import pallas as pl

    from ray_tpu.ops import block_sparse as bs

    call = pl.pallas_call

    def debug(*a, **kw):        # prints the kernel's jaxpr and Mosaic module
        return call(*a, debug=True, **kw)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if shape == "table":
        S, T, pool = 32, 64, sds((4, 24576, PAGE, 256), jnp.bfloat16)
        args = (sds((T, 32, 128), jnp.bfloat16), pool, pool, sds(()),
                sds((S, 2304)), sds((S,)), sds((S,), jnp.bool_), sds((S,)),
                sds((S, 2, 64)), sds((S, 2)))
        lower = lambda: jax.jit(lambda *a: bs.block_attend_call(
            *a, kv_heads=2, scale=128 ** -0.5, block=64,
            interpret=False)).lower(*args)
        assert pa.kv_sizes(16, 1, 256, 256, PAGE, 2,
                           rows=True).pages_one == 64
    elif shape == "mimo_full":
        args, sink, kv = _mimo_kernel_args(one_chip, False, (32, 64, 256))
        lower = lambda: jax.jit(lambda *a: pa.ragged_paged_attention_unified(
            *a, scale=192 ** -0.5, interpret=False, kv_heads=kv)).lower(*args)
    else:
        args = _afmoe_kernel_args(one_chip, True, (32, 48, 128))
        lower = lambda: jax.jit(lambda *a: pa.ragged_paged_attention_unified(
            *a, scale=128 ** -0.5, interpret=False, kv_heads=8,
            window=4096)).lower(*args)
    jax.clear_caches()      # the jitted entry may hold a trace without debug
    try:
        with mock.patch.object(pl, "pallas_call", debug):
            capsys.readouterr()
            text = lower().compile().as_text()
            module = capsys.readouterr().out
    finally:
        jax.clear_caches()
    assert text.count(KERNEL) == 1
    module = module[module.index("module @"):]
    starts = module.count("tpu.enqueue_dma")
    a_site = 2 * pa.PAGE_RUN + 2
    assert starts >= 1 + 2 * a_site, starts
    # a run's starts lie in ONE loop body: between two of its neighbours no
    # region opens or closes
    bodies = re.split(r"scf\.(?:for|while|if|yield|condition)\b", module)
    assert max(body.count("tpu.enqueue_dma") for body in bodies) \
        >= 2 * pa.PAGE_RUN


@pytest.mark.parametrize("backbone", ["mixed192", "rect128"])
def test_nemotron_h_step_compiles_with_both_caches_in_place(one_chip, on_tpu,
                                                            backbone):
    """The step programs of `nemotron3super-longout-closed64` at the
    published widths, published layers 0-10, 128 held experts, the
    vocabulary's quarter (benchmarks/configs/nemotron-3-super-l11-e128.json):
    the K/V row pools of the one attention layer AND the state group's S,
    buffered rows, their count and the tails of the 5 Mamba-2 layers go
    through the layers where they lie (no copy of any), the Pallas kernels
    are the K/V one and five SSD ones, and arguments and temporaries fit the
    chip (12.81 GB + 0.07-0.10 GB of 16.9)."""
    from ray_tpu.llm import model_runner
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import nemotron_h as nh

    cfg = nh.NemotronHConfig(max_position_embeddings=8192, **NEMOTRON_CUT)
    params = jax.eval_shape(lambda: nh.init_params(cfg, jax.random.key(0)))
    init = model_runner.init_cache
    with mock.patch.object(model_runner, "init_cache",
                           lambda *a: jax.eval_shape(lambda: init(*a))):
        runner = ModelRunner(cfg, params, num_blocks=32768, block_size=PAGE,
                             attention_impl="pallas", max_batch=64)
    assert runner.group_pages == {"all": 32768, "state": 128}
    assert runner.table_widths == {"all": 512, "state": 1}
    assert [(a.name, a.shape) for a in runner.cache_arrays] == [
        ("k_all", (1, 32768, 16, 256)), ("v_all", (1, 32768, 16, 256)),
        ("ssd_state", (5, 129, 128, 64, 128)),
        ("ssd_rows", (5, 129, 8, 88, 128)), ("ssd_fill", (5, 129)),
        ("conv_tail", (5, 129, 240, 128))]
    assert runner.kv_kernels["all"]["layout"] == "rows"

    def on_chip(tree):
        return _abstract(tree, jax.tree.map(lambda _: one_chip, tree))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def tables(S):
        return {"all": i32(S, 512), "state": i32(S, 1)}

    S, T = 64, 192
    fn, args = {
        # the whole tick: backbone, head and sampler at 64 x 32,768
        "mixed192": (runner._step_mixed, (
            i32(T), i32(S, 1), i32(T), i32(S), i32(S), i32(S + 1), tables(S),
            i32(S, 1), i32(S, 1), i32(S), f32(S), i32(S), f32(S), i32(S),
            i32(S))),
        "rect128": (runner._step, (
            i32(2, 128), i32(2), i32(2), i32(2), tables(2))),
    }[backbone]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(runner.cache), *args).compile()
    text = compiled.as_text()
    held = 0
    for a in runner.cache_arrays:
        pool = "%s[%s]" % ({"bfloat16": "bf16", "float32": "f32",
                            "int32": "s32"}[jnp.dtype(a.dtype).name],
                           ",".join(map(str, a.shape)))
        assert pool in text
        held += jnp.dtype(a.dtype).itemsize * int(np.prod(a.shape))
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= %s\S* copy\(" % re.escape(pool), line)]
        assert not copies, copies
    mem = compiled.memory_analysis()
    assert held <= mem.alias_size_in_bytes < held + (1 << 20)
    assert mem.temp_size_in_bytes < 1 << 28
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.05e9
    flat = text.replace("\n", "").replace("\\", "")
    count = lambda name: flat.count('kernel_metadata={"kernel":"%s"}' % name)
    assert count("ssd") == 5
    # the five expert layers' two products each are the Pallas weight stream
    # (ops/grouped_dot.product), none XLA's ragged-dot
    assert count("grouped_dot") == 10
    assert flat.count("kernel_metadata=") == 16
    assert "ragged-dot" not in text


# ---- the held experts' grouped product (ops/grouped_dot.py) -----------------

@pytest.mark.parametrize("rows,K,N,held", [
    (64 * 22, 1024, 2688, 128), (192 * 22, 2688, 1024, 128),    # Nemotron
    (64 * 8, 2304, 1024, 32), (192 * 8, 1024, 2304, 32),        # Kimi-Linear
    (160 * 6, 5120, 1536, 40),          # DeepSeek-V2's: K walked in pieces
    (160 * 4, 3072, 3072, 32),          # Trinity's: the largest experts
    # LFM2-MoE's, EVERY expert held: 13.25 and 13.375 MiB of the 14 MiB
    # budget by `grouped_vmem_bytes`, the nearest any configuration has come
    (64 * 4, 2048, 1536, 64), (192 * 4, 1536, 2048, 64),
])
def test_grouped_dot_compiles_and_is_named_for_its_reader(one_chip, rows, K,
                                                          N, held):
    """The weight-stream kernel at the published widths, tiles by
    `grouped_sizes`: Mosaic takes the blocks under the scoped VMEM default,
    and where tracebacks are stripped (the benchmark's setting) the
    instruction is named after the jitted entry, `grouped_dot_call.<n>`:
    what `expert_product_ms.tick` keys on, and NOT what the paged kernels'
    readers sum (`tpu_custom_call*`, `paged_attention_*`)."""
    from ray_tpu.ops import grouped_dot as gd

    sh = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = (sh((rows, K), jnp.bfloat16), sh((held, K, N), jnp.bfloat16),
            sh((held,), jnp.int32))
    was = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.clear_caches()
    try:
        text = jax.jit(lambda *a: gd.grouped_dot(
            *a, interpret=False)).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", was)
        jax.clear_caches()
    names = re.findall(r"%(\S+) = \S+ custom-call\(.*" + re.escape(KERNEL),
                       text)
    assert len(names) == 1, names
    assert names[0].startswith("grouped_dot_call"), names
    assert "paged_attention_" not in names[0]
    flat = text.replace("\n", "").replace("\\", "")
    assert 'kernel_metadata={"kernel":"grouped_dot"}' in flat


# ---- LFM2-24B-A2B: the pair form by runs, a tail in a slot, every expert ----

@pytest.mark.parametrize("q_shape", [(192, 32, 64), (64, 32, 64),
                                     (2, 128, 32, 64), (2, 1, 32, 64)],
                         ids=["unified", "decode_rows", "rect128", "rect1"])
def test_pair_form_by_runs_compiles_over_row_pools(one_chip, q_shape):
    """The K/V kernel over ROW POOLS of 4 kv pairs of 128 lanes at
    LFM2-24B-A2B's attention layers: 32 query heads of 64 as half-zero
    128-lane rows in runs of four (`pair_queries(q, 4)`), 8 kv heads of 64
    paired, each head's own half of the 128-wide value sum kept
    (`pair_outputs`), two layers under the cell's 512-page table; both pools
    go in where they lie, 512 lanes a token with no padding, and the sizes
    fit the stated VMEM budget."""
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S = 64 if len(q_shape) == 3 else q_shape[0]
    sizes = pa.kv_sizes(32, 4, 128, 128, PAGE, 2, rows=True)
    assert pa.kv_vmem_bytes(32, 4, 128, 128, PAGE, 2, True, sizes.q_block,
                            sizes.pages_one,
                            sizes.pages_many) <= pa.KV_VMEM_BUDGET
    pool = sds((2, 32768, PAGE, 512))
    args = [sds(q_shape), pool, pool, sds((), jnp.int32),
            sds((S, 512), jnp.int32), sds((S,), jnp.int32),
            sds((S,), jnp.int32)]
    fn = pa.ragged_paged_attention
    if len(q_shape) == 3:
        args.append(sds((S + 1,), jnp.int32))
        fn = pa.ragged_paged_attention_unified
    lowered = jax.jit(lambda q, *a: pa.pair_outputs(fn(
        pa.pair_queries(q, 4), *a, scale=0.125, kv_heads=4,
        interpret=False), 4)).lower(*args)
    assert lowered.out_info.shape == q_shape
    text = lowered.compile().as_text()
    assert text.count(KERNEL) == 1
    shape = "bf16[%s]" % ",".join(map(str, pool.shape))
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"= %s\S* (copy|transpose|fusion)\("
                          % re.escape(shape), line)]
    assert shape in text and not moved, moved
    assert re.search(re.escape(shape) + r"\{3,2,1,0:T\(8,128\)\(2,1\)\}",
                     text), "the row pool is not laid out whole on the lanes"


from chip_smoke import LFM2_CUT  # noqa: E402


@pytest.mark.parametrize("backbone", ["mixed192", "rect128"])
def test_lfm2_moe_step_compiles_with_pools_and_tails_in_place(one_chip, on_tpu,
                                                              backbone):
    """The step programs of `lfm2moe-longout-closed64` at the published
    widths, published layers 1-9, ALL 64 experts of 8 routed layers, the whole
    vocabulary (benchmarks/configs/lfm2-24b-a2b-l9.json): the K/V pair pools
    of the two attention layers AND the seven conv layers' tails go through
    the layers where they lie (aliased in and out, no copy of any), the
    Pallas kernels are two K/V ones and three grouped products an expert
    layer, and arguments and temporaries fit the chip (12.51 GB + 0.03-0.06 GB of 16.9)."""
    from ray_tpu.llm import model_runner
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import lfm2_moe as lm

    cfg = lm.Lfm2MoeConfig(max_position_embeddings=8192, **LFM2_CUT)
    params = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.key(0)))
    init = model_runner.init_cache
    with mock.patch.object(model_runner, "init_cache",
                           lambda *a: jax.eval_shape(lambda: init(*a))):
        runner = ModelRunner(cfg, params, num_blocks=32768, block_size=PAGE,
                             attention_impl="pallas", max_batch=64)
    assert runner.group_pages == {"all": 32768, "state": 128}
    assert runner.table_widths == {"all": 512, "state": 1}
    assert [(a.name, a.shape) for a in runner.cache_arrays] == [
        ("k_all", (2, 32768, 16, 512)), ("v_all", (2, 32768, 16, 512)),
        ("conv_tail", (7, 129, 32, 128))]
    assert runner.kv_kernels["all"] == {
        "layout": "rows", "decode": "per_head", "q_block": 64,
        "pages": [32, 64], "k_lanes": [128, 0]}

    def on_chip(tree):
        return _abstract(tree, jax.tree.map(lambda _: one_chip, tree))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def tables(S):
        return {"all": i32(S, 512), "state": i32(S, 1)}

    S, T = 64, 192
    fn, args = {
        # the whole tick: backbone, head and sampler at 64 x 65,536
        "mixed192": (runner._step_mixed, (
            i32(T), i32(S, 1), i32(T), i32(S), i32(S), i32(S + 1), tables(S),
            i32(S, 1), i32(S, 1), i32(S), f32(S), i32(S), f32(S), i32(S),
            i32(S))),
        "rect128": (runner._step, (
            i32(2, 128), i32(2), i32(2), i32(2), tables(2))),
    }[backbone]
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        on_chip(params), on_chip(runner.cache), *args).compile()
    text = compiled.as_text()
    held = 0
    for a in runner.cache_arrays:
        pool = "bf16[%s]" % ",".join(map(str, a.shape))
        assert pool in text
        held += 2 * int(np.prod(a.shape))
        copies = [line.strip()[:160] for line in text.splitlines()
                  if re.search(r"= %s\S* copy\(" % re.escape(pool), line)]
        assert not copies, copies
    mem = compiled.memory_analysis()
    assert held <= mem.alias_size_in_bytes < held + (1 << 20)
    assert mem.temp_size_in_bytes < 1 << 29
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 13.2e9
    flat = text.replace("\n", "").replace("\\", "")
    count = lambda name: flat.count('kernel_metadata={"kernel":"%s"}' % name)
    # the eight expert layers' three products each are the Pallas weight
    # stream (ops/grouped_dot.product), none XLA's ragged-dot
    assert count("grouped_dot") == 24
    assert flat.count("kernel_metadata=") == 26
    assert "ragged-dot" not in text
