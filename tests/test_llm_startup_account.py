"""The start-up account (PR 55): the compile ledger of `util/tracing.py`, the
spans `serving.build_engine` writes on a replica's way to ready, and
`engine.stats()["startup"]`: an observer only. PR 54's account was refused
for what it did to the start (three kinds of new waits, the prefix tiers
attached before the warm-up); `test_the_start_waits_once_as_the_parent_does`
and `test_a_server_attaches_its_tiers_after_the_warm_up` hold that none of it
comes back.

No test compares two measured times: the ledger's arithmetic runs on stages
laid by hand, and what is measured is only held to add up.
"""

import json
import os
import subprocess
import sys

import pytest

import ray_tpu  # noqa: F401

STAGES = ("trace_s", "lower_s", "compile_s", "cache_read_s")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(autouse=True)
def tracing_on():
    from ray_tpu.util import tracing

    was = tracing.enabled()
    tracing.set_enabled(True)
    yield
    tracing.set_enabled(was)


@pytest.fixture()
def ledger(monkeypatch):
    """The ledger with totals of the test's own."""
    from ray_tpu.util import tracing

    monkeypatch.setattr(tracing, "_compile_totals",
                        dict.fromkeys(tracing._compile_totals, 0))
    return tracing


def _config(**kw):
    import jax.numpy as jnp

    from ray_tpu.llm.serving import LLMConfig
    from ray_tpu.models import llama

    base = dict(model_config=llama.LlamaConfig.tiny(
        vocab_size=128, max_seq=128, dtype=jnp.float32),
        num_kv_blocks=32, block_size=8, max_batch_size=2, prefill_chunk=8,
        warmup_buckets="light")      # the ladder: 8 and 16 tokens
    base.update(kw)
    return LLMConfig(**base)


def _startup_spans(replica):
    """The spans of the start-up tagged `replica`, by name."""
    from ray_tpu.util import tracing

    spans = [s for s in tracing.get_spans() if s["name"].startswith("llm:")]
    top = [s for s in spans if s["name"] == "llm:startup"
           and s["args"].get("replica") == replica]
    assert len(top) == 1, [s["args"] for s in top]
    out = {"llm:startup": top}
    for s in spans:
        if s["args"].get("parent_span_id") == top[0]["args"]["span_id"]:
            out.setdefault(s["name"], []).append(s)
    return out


@pytest.fixture(scope="module")
def built(cpu_jax):
    from ray_tpu.llm.serving import build_engine
    from ray_tpu.util import tracing

    tracing.set_enabled(True)
    engine = build_engine(_config(), replica="account-test")
    return engine, _startup_spans("account-test")


# ---- the ledger ---------------------------------------------------------------


def test_ledger_counts_a_jitted_functions_stages(cpu_jax, ledger):
    import jax
    import jax.numpy as jnp

    assert ledger.watch_compiles()

    @jax.jit
    def startup_account_probe(x):
        return jnp.tanh(x) @ x.T

    x = jnp.ones((8, 8))        # an eager program of its own
    before = ledger.compile_totals()
    startup_account_probe(x).block_until_ready()
    gained = ledger.compile_since(before)
    assert gained["compiles"] == 1
    assert all(gained[key] > 0 for key in ("trace_s", "lower_s", "compile_s"))
    # run again: the program is there, nothing compiles, nothing is added
    before = ledger.compile_totals()
    startup_account_probe(x).block_until_ready()
    assert not any(ledger.compile_since(before).values())


def test_a_stage_nested_in_another_counts_once(ledger):
    """A jitted function traced inside another's trace, and a helper traced
    inside a lowering: the outer stage's own seconds are its extent less
    what nests inside it, whatever the stages."""
    ledger._on_stage_start(TRACE, 100.0, fun_name="outer")
    ledger._on_stage_start(TRACE, 100.1, fun_name="inner")
    ledger._on_stage_end(TRACE, 100.1, 100.4, fun_name="inner")
    ledger._on_stage_end(TRACE, 100.0, 101.0, fun_name="outer")
    ledger._on_stage_start(LOWER, 101.0, fun_name="jit(outer)")
    ledger._on_stage_start(TRACE, 101.5, fun_name="helper")
    ledger._on_stage_end(TRACE, 101.5, 101.75, fun_name="helper")
    ledger._on_stage_end(LOWER, 101.0, 103.0, fun_name="jit(outer)")
    totals = ledger.compile_totals()
    assert totals["trace_s"] == pytest.approx(0.3 + 0.7 + 0.25)
    assert totals["lower_s"] == pytest.approx(2.0 - 0.25)
    assert sum(totals[key] for key in STAGES) == pytest.approx(3.0)
    # a stage an error never closed does not swallow the next one
    ledger._on_stage_start(TRACE, 200.0, fun_name="raises")
    ledger._on_stage_start(LOWER, 201.0, fun_name="jit(next)")
    ledger._on_stage_end(LOWER, 201.0, 201.5, fun_name="jit(next)")
    assert ledger.compile_totals()["lower_s"] == pytest.approx(1.75 + 0.5)


def test_a_cache_hits_read_is_not_its_compile(ledger):
    ledger._on_stage_start(COMPILE, 10.0, fun_name="jit(f)")
    ledger._on_cache_event("/jax/compilation_cache/cache_misses")
    ledger._on_stage_end(COMPILE, 10.0, 14.0, fun_name="jit(f)")
    ledger._on_stage_start(COMPILE, 20.0, fun_name="jit(f)")
    ledger._on_cache_event("/jax/compilation_cache/cache_hits")
    ledger._on_cache_read("/jax/compilation_cache/cache_retrieval_time_sec",
                          0.75)
    ledger._on_stage_end(COMPILE, 20.0, 21.0, fun_name="jit(f)")
    ledger._on_stage_start(COMPILE, 30.0, fun_name="jit(tiny)")
    ledger._on_stage_end(COMPILE, 30.0, 30.01, fun_name="jit(tiny)")
    totals = ledger.compile_totals()
    assert totals["compile_s"] == pytest.approx(4.0 + 0.25 + 0.01)
    assert totals["cache_read_s"] == pytest.approx(0.75)
    assert (totals["compiles"], totals["cache_hits"],
            totals["cache_misses"]) == (3, 1, 1)


@pytest.mark.parametrize("hits,misses,compiles,expected", [
    (1, 0, 1, True),        # read from the cache, nothing written
    (0, 1, 1, False),       # compiled and written
    (0, 0, 1, False),       # compiled under the cache's floor: not asked
    (0, 0, 0, False),       # nothing compiled at all
    (5, 1, 6, False),       # an interval of six programs, one written
    (6, 0, 9, True),        # six read, three small ones beside them
])
def test_stage_args_call_it_a_hit_only_where_nothing_was_written(
        ledger, hits, misses, compiles, expected):
    """A span's arguments from an interval of the ledger: the four stages
    rounded, the backend's compiles, and `cache_hit`."""
    before = ledger.compile_totals()
    for i in range(compiles):
        ledger._on_stage_start(COMPILE, 10.0 + i)
        if i < hits:
            ledger._on_cache_event("/jax/compilation_cache/cache_hits")
            ledger._on_cache_read(
                "/jax/compilation_cache/cache_retrieval_time_sec", 0.125)
        elif i < hits + misses:
            ledger._on_cache_event("/jax/compilation_cache/cache_misses")
        ledger._on_stage_end(COMPILE, 10.0 + i, 10.5 + i)
    args = ledger.stage_args(ledger.compile_since(before))
    assert args == {"trace_s": 0.0, "lower_s": 0.0,
                    "compile_s": pytest.approx(0.5 * compiles - 0.125 * hits),
                    "cache_read_s": pytest.approx(0.125 * hits),
                    "compiles": compiles, "cache_hit": expected}


_CACHE_PROBE = """
import json, sys
import jax, jax.numpy as jnp
from ray_tpu.util import tracing
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
tracing.watch_compiles()
@jax.jit
def cached_probe(x):
    return jnp.cos(x) @ x.T
x = jnp.ones((16, 16))
before = tracing.compile_totals()
cached_probe(x).block_until_ready()
gained = tracing.compile_since(before)
gained["hit"] = tracing.stage_args(gained)["cache_hit"]
print("LEDGER " + json.dumps(gained))
"""


def test_ledger_tells_a_persistent_cache_hit_from_a_miss(tmp_path):
    """The same function in two fresh processes over one cache directory."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + sys.path))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    runs = []
    for _ in range(2):
        out = subprocess.run(
            [sys.executable, "-c", _CACHE_PROBE, str(tmp_path / "cache")],
            env=env, capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        line = [l for l in out.stdout.splitlines() if l.startswith("LEDGER ")]
        runs.append(json.loads(line[-1][len("LEDGER "):]))
    cold, warm = runs
    assert (cold["cache_hits"], cold["cache_misses"]) == (0, 1)
    assert cold["hit"] is False and cold["cache_read_s"] == 0
    assert (warm["cache_hits"], warm["cache_misses"]) == (1, 0)
    assert warm["hit"] is True and warm["cache_read_s"] > 0
    for run in runs:    # traced and lowered again, hit or not
        assert run["compiles"] == 1
        assert run["trace_s"] > 0 and run["lower_s"] > 0


# ---- the spans and the statistic ------------------------------------------------


def test_build_engine_writes_the_account_as_spans(built):
    engine, spans = built
    assert set(spans) >= {"llm:startup", "llm:startup:params",
                          "llm:startup:place", "llm:startup:warmup",
                          "llm:step_compile"}
    top = spans["llm:startup"][0]
    assert "parent_span_id" not in top["args"]
    for group in spans.values():    # one trace; the links were the filter
        assert all(s["args"]["trace_id"] == top["args"]["trace_id"]
                   for s in group)
    for name in ("llm:startup:params", "llm:startup:place",
                 "llm:startup:warmup"):
        assert len(spans[name]) == 1
        assert top["ts"] <= spans[name][0]["ts"]
        assert (spans[name][0]["ts"] + spans[name][0]["dur"]
                <= top["ts"] + top["dur"])
    params = spans["llm:startup:params"][0]["args"]
    place = spans["llm:startup:place"][0]["args"]
    assert params["source"] == "init" and params["bytes"] > 0
    assert all(key in params for key in STAGES)
    assert place["param_bytes"] == params["bytes"]
    assert place["cache_bytes"] == sum(
        a.nbytes for a in engine.runner.cache.values())
    assert (place["pages"], place["slots"]) == (32, 0)
    warm = spans["llm:startup:warmup"][0]["args"]
    assert (warm["programs"], warm["full"]) == (2, False)
    assert all(key in warm for key in STAGES)


def test_the_account_adds_up(built):
    engine, spans = built
    up = spans["llm:startup"][0]["args"]
    assert up["model"] == "LlamaConfig" and up["replica"] == "account-test"
    assert up["total_s"] == pytest.approx(
        up["params_s"] + up["place_s"] + up["warmup_s"] + up["other_s"],
        abs=2e-3)
    assert up["other_s"] >= 0
    assert up["total_s"] == pytest.approx(
        spans["llm:startup"][0]["dur"] / 1e6, abs=0.05)
    for name, key in (("llm:startup:params", "params_s"),
                      ("llm:startup:place", "place_s"),
                      ("llm:startup:warmup", "warmup_s")):
        assert up[key] == pytest.approx(spans[name][0]["dur"] / 1e6,
                                        abs=2e-3)
    # a warmed program is the `llm:step_compile` span its dispatch wrote
    programs = spans["llm:step_compile"]
    assert [(p["args"]["entry_point"], p["args"]["shapes"][0])
            for p in programs] == [("mixed", [8]), ("mixed", [16])]
    for p in programs:      # the stages lie inside the dispatch's extent
        parts = [p["args"][key] for key in STAGES]
        assert 0 < sum(parts) <= p["dur"] / 1e6 + 1e-3
        assert all(part >= 0 for part in parts)
        assert p["args"]["trace_s"] > 0 and p["args"]["lower_s"] > 0
        assert p["args"]["cache_hit"] in (True, False)
    # the one wait of the start: the warm-up's closing one
    warm = spans["llm:startup:warmup"][0]
    assert 0 <= warm["args"]["device_tail_s"] <= warm["dur"] / 1e6
    assert up["device_tail_s"] == pytest.approx(
        warm["args"]["device_tail_s"], abs=1e-3)
    # the stages nest: the programs' in the warm-up's in the whole call's
    # (which holds the eager draw's too)
    for key in STAGES:
        inside = sum(p["args"][key] for p in programs)
        assert up[key] >= warm["args"][key] - 2e-3 >= inside - 4e-3
    stages = sum(warm["args"][key] for key in STAGES)
    assert stages + warm["args"]["device_tail_s"] <= warm["dur"] / 1e6 + 1e-3
    # the programs lie inside the warm-up, one after another
    ends = [warm["ts"]]
    for p in programs:
        assert p["ts"] >= ends[-1] - 1 and p["dur"] > 0
        ends.append(p["ts"] + p["dur"])
    assert ends[-1] <= warm["ts"] + warm["dur"] + 1


def test_stats_hold_the_account_and_build_nothing(built):
    engine, spans = built
    stats = engine.stats()
    up = stats["startup"]
    assert up is engine.startup and up is engine.stats()["startup"]
    assert up["warmup_s"] == stats["warmup_s"] > 0
    assert up["programs"] == stats["warmup_shapes"] == 2
    args = spans["llm:startup"][0]["args"]
    assert {k: v for k, v in args.items()
            if k not in ("trace_id", "span_id")} == up
    json.dumps(up)      # `engine_stats()` travels


def test_a_compile_after_ready_has_an_extent(built):
    from ray_tpu.util import tracing

    engine, _ = built
    before = engine.stats()["step_compiles"]
    seen = len(tracing.get_spans())
    # the host-logits head: a light warm-up leaves it cold
    engine.runner.warm_mixed_logits(8, 2)
    assert engine.stats()["step_compiles"] == before + 1
    new = [s for s in tracing.get_spans()[seen:]
           if s["name"] == "llm:step_compile"]
    assert len(new) == 1
    span = new[0]
    assert span["dur"] > 0
    assert span["args"]["entry_point"] == "mixed_logits"
    assert span["args"]["compile_index"] == before + 1
    assert span["args"]["trace_s"] > 0 and span["args"]["lower_s"] > 0
    stages = sum(span["args"][key] for key in STAGES)
    assert 0 < stages <= span["dur"] / 1e6 + 1e-3
    # the same shape again: no compile, no span
    engine.runner.warm_mixed_logits(8, 2)
    assert engine.stats()["step_compiles"] == before + 1
    assert not [s for s in tracing.get_spans()[seen:]
                if s["name"] == "llm:step_compile"][1:]
    # and the account of the start has not moved
    assert engine.stats()["startup"]["programs"] == 2


def test_the_start_waits_once_as_the_parent_does(cpu_jax, monkeypatch):
    """What PR 54 was refused for: the account watches the parent's start,
    and the parent waits for the device ONCE, at the end of `warmup()`. The
    same programs in the same order, counted as the parent counts them."""
    import jax

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.serving import build_engine

    waits, order = [], []
    real = jax.block_until_ready

    def counted(x):
        waits.append(order[-1] if order else None)
        return real(x)

    def noting(name, fn):
        def wrapped(self, *args, **kw):
            order.append(name)
            return fn(self, *args, **kw)
        return wrapped

    monkeypatch.setattr(jax, "block_until_ready", counted)
    for name in ("warm_mixed", "gather_pages_async", "copy_state"):
        monkeypatch.setattr(ModelRunner, name,
                            noting(name, getattr(ModelRunner, name)))
    monkeypatch.setattr(LLMEngine, "attach_prefix_store", noting(
        "attach_prefix_store", LLMEngine.attach_prefix_store))
    engine = build_engine(_config(), replica="waits-once")
    assert waits == ["warm_mixed"]      # once, after the last program
    assert order == ["warm_mixed", "warm_mixed"]
    assert engine.warmup_shapes == 2
    programs = _startup_spans("waits-once")["llm:step_compile"]
    assert [p["args"]["entry_point"] for p in programs] == ["mixed", "mixed"]


# The frames beneath a dispatch, as large as at the parent of PR 55 (`aa640ee`):
# (locals + cells + free variables, stack depth) of each function that is on
# the Python stack while a step program is traced.
_FRAMES_UNDER_A_TRACE = [
    ("ray_tpu.llm.serving", "build_engine", (20, 10)),
    ("ray_tpu.llm.serving", "LLMServer.__init__", (13, 10)),
    ("ray_tpu.llm.engine", "LLMEngine.warmup", (10, 6)),
    ("ray_tpu.llm.engine", "LLMEngine._warm_spill_gather", (3, 5)),
    ("ray_tpu.llm.model_runner", "ModelRunner.warm_mixed", (6, 17)),
    ("ray_tpu.llm.model_runner", "ModelRunner.step_mixed", (21, 21)),
    ("ray_tpu.llm.model_runner", "ModelRunner.step_mixed_logits", (11, 12)),
    ("ray_tpu.llm.model_runner", "ModelRunner.step", (10, 11)),
    ("ray_tpu.llm.model_runner", "ModelRunner.gather_pages_async", (5, 5)),
]


@pytest.mark.skipif(sys.version_info[:2] != (3, 12),
                    reason="stack depths are this interpreter's")
@pytest.mark.parametrize("module,name,size", _FRAMES_UNDER_A_TRACE,
                         ids=[f[1] for f in _FRAMES_UNDER_A_TRACE])
def test_the_account_adds_no_word_to_a_frame_beneath_a_trace(
        module, name, size):
    """The account watches from an object of its own and from attributes:
    it adds no local and no stack slot to a frame that lies under the trace
    of a step program. CPython 3.12 maps a 16 KiB chunk of its frame stack at
    the call that does not fit and unmaps it at that call's return, so a word
    more here moves which hot call of the trace pays that every time, and a
    warm-up reads seconds longer or shorter for it (PERF.md section 6, PR 55:
    +1.5-1.9 s with a `with` and a dozen locals, every instrument off).

    A PR that changes one of these functions for its own reasons updates the
    pair here, and knows from this that `warmup_s` against its parent will
    move by what the frame stack does, in either direction."""
    import importlib

    obj = importlib.import_module(module)
    for part in name.split("."):
        obj = getattr(obj, part)
    code = obj.__code__
    held = code.co_nlocals + len(code.co_cellvars) + len(code.co_freevars)
    assert (held, code.co_stacksize) == size


def test_a_server_attaches_its_tiers_after_the_warm_up(cpu_jax, monkeypatch):
    """As `LLMServer` did before the account: the engine is built and warmed,
    THEN the prefix tiers are hung on it, and the spill gather's sizes are
    warmed at that point. They are compiles after ready (`llm:step_compile`
    spans with an extent), counted in `warmup_s` / `warmup_shapes` as ever
    and not in `startup`, which was built once at ready."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.serving import LLMServer
    from ray_tpu.util import tracing

    at_attach = {}
    attach = LLMEngine.attach_prefix_store

    def noting(self, **kw):
        at_attach.update(shapes=self.warmup_shapes, startup=self.startup,
                         spans=len(tracing.get_spans()))
        return attach(self, **kw)

    monkeypatch.setattr(LLMEngine, "attach_prefix_store", noting)
    server = LLMServer(_config(host_prefix_mb=1.0))
    try:
        stats = server.engine_stats()
        up = stats["startup"]
        assert server.engine.host_prefix_tier is not None
        assert at_attach["shapes"] == 2 and at_attach["startup"] is up
        assert up["replica"] == server._replica_tag and up["programs"] == 2
        gathers = len(server.engine._spill_sizes)
        assert gathers > 0 and stats["warmup_shapes"] == 2 + gathers
        assert stats["warmup_s"] >= up["warmup_s"]
        entries = [p["args"]["entry_point"] for p in _startup_spans(
            server._replica_tag)["llm:step_compile"]]
        assert entries == ["mixed", "mixed"]
        late = [s for s in tracing.get_spans()[at_attach["spans"]:]
                if s["name"] == "llm:step_compile"]
        assert [s["args"]["entry_point"] for s in late] == ["gather"] * gathers
        assert all(s["dur"] > 0 for s in late)
    finally:
        server._handoff.close()


def test_with_tracing_off_nothing_is_registered(cpu_jax):
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.serving import build_engine
    from ray_tpu.util import tracing

    assert tracing.watch_compiles()
    tracing.set_enabled(False)
    assert not tracing._compile_watched and not tracing.watch_compiles()
    for listeners in (jax._src.monitoring.get_event_listeners(),
                      jax._src.monitoring.get_event_duration_listeners(),
                      jax._src.monitoring.get_event_time_span_listeners(),
                      jax._src.monitoring.get_scalar_listeners()):
        assert not [f for f in listeners
                    if getattr(f, "__module__", "") == tracing.__name__]
    before, seen = tracing.compile_totals(), len(tracing.get_spans())
    jax.jit(lambda x: jnp.sinh(x) + 3)(jnp.ones(5)).block_until_ready()
    engine = build_engine(_config(warmup_buckets="light"), replica="off")
    assert tracing.compile_totals() == before
    assert len(tracing.get_spans()) == seen
    stats = engine.stats()
    assert stats["warmup_s"] > 0 and stats["warmup_shapes"] == 2
    up = stats["startup"]
    assert up["warmup_s"] == stats["warmup_s"] and up["replica"] == "off"
    assert up["total_s"] == pytest.approx(
        up["params_s"] + up["place_s"] + up["warmup_s"] + up["other_s"],
        abs=2e-3)
    assert all(up[key] == 0 for key in STAGES)
    tracing.set_enabled(True)       # and on again: the next use registers
    assert tracing.watch_compiles() and tracing._compile_watched
