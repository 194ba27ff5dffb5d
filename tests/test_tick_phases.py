"""The clock inside the tick (PR 26): phase times and counters in the flight
record, starvation counts in the `llm:prefill` span, one PhaseClock helper,
and a stable name on every Pallas kernel.

The scenario is small enough to work out by hand. `token_budget` 8 equals
`prefill_chunk` 8, so a tick's budget holds one full chunk: prompt A (24
tokens) prefills in ticks 1-3 while prompt B (24 tokens) waits admitted
without a slice; from tick 4 on A decodes (1 token of budget) and B gets the
other 7."""

import time

import pytest

import ray_tpu  # noqa: F401

PHASES = ("compose_ms", "dispatch_ms", "wait_ms", "commit_ms")
NEW_FIELDS = ("admit_ms", "since_prev_ms") + PHASES + (
    "kv_tokens", "prefill_tokens", "starved", "q_blocks", "kv_pages_walked",
    "kv_pages_unrolled")


def _engine(**kw):
    import jax.numpy as jnp

    from ray_tpu.llm.serving import LLMConfig, build_engine
    from ray_tpu.models import llama

    base = dict(
        model_config=llama.LlamaConfig.tiny(vocab_size=128, max_seq=128,
                                            dtype=jnp.float32),
        num_kv_blocks=64, block_size=8, max_batch_size=4, prefill_chunk=8,
        token_budget=8, warmup_buckets="off")
    base.update(kw)
    return build_engine(LLMConfig(**base))


def _drive(engine):
    """A then B, both 24-token prompts; A stops after 2 tokens, B after 3."""
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.util import tracing

    was = tracing.enabled()
    tracing.set_enabled(True)
    try:
        engine.add_request(list(range(1, 25)), SamplingParams(max_tokens=2),
                           request_id="tick-a")
        engine.add_request(list(range(40, 64)), SamplingParams(max_tokens=3),
                           request_id="tick-b")
        while engine.has_unfinished():
            engine.step()
        spans = {s["args"]["request_id"]: s["args"]
                 for s in tracing.get_spans() if s["name"] == "llm:prefill"
                 and s["args"].get("request_id") in ("tick-a", "tick-b")}
    finally:
        tracing.set_enabled(was)
    return engine.tick_records(), spans


@pytest.fixture(scope="module")
def unified(cpu_jax):
    return _drive(_engine())


@pytest.mark.parametrize("field", NEW_FIELDS)
def test_unified_tick_record_holds_the_new_field(unified, field):
    records, _ = unified
    assert len(records) >= 7 and {r["kind"] for r in records} == {"mixed"}
    for r in records:
        assert isinstance(r[field], (int, float)) and r[field] >= 0, (field, r)


def test_phases_partition_the_tick(unified):
    records, _ = unified
    for r in records:
        # the four phases partition [t, t + dur_ms]; each is rounded to 1 us
        assert sum(r[p] for p in PHASES) == pytest.approx(r["dur_ms"],
                                                          abs=0.005), r
    assert records[0]["since_prev_ms"] == 0.0
    for prev, r in zip(records, records[1:]):
        gap = (r["t"] - r["admit_ms"] / 1e3) - (prev["t"] + prev["dur_ms"] / 1e3)
        assert r["since_prev_ms"] == pytest.approx(gap * 1e3, abs=0.01)


def test_a_record_holds_two_steps_and_says_which(unified):
    """One step of lookahead (ISSUE 34): the first call only dispatches, the
    last only lands, every call between dispatches with a step in flight;
    `emitted` is the step COMMITTED, the row counters the one DISPATCHED."""
    records, _ = unified
    assert [r["lookahead"] for r in records] == (
        [False] + [True] * (len(records) - 2) + [False])
    assert all(r["settled"] == "idle" for r in (records[0], records[-1]))
    assert all("settled" not in r for r in records[1:-1])
    assert records[0]["wait_ms"] < 0.1 and records[0]["commit_ms"] < 0.1
    assert records[-1]["used"] == 0 and records[-1]["dispatch_ms"] < 0.1
    # A's first token is sampled by the step call 3 dispatched (its last
    # chunk) and emitted by call 4, which dispatches A's decode row
    assert [r["emitted"] for r in records[:5]] == [
        {}, {}, {}, {"tick-a": 1}, {"tick-a": 2}]


def test_wait_intervals_of_successive_records_do_not_overlap(unified):
    """`benchmarks/tick_phases.py` sums the device's idle time inside the
    records' wait intervals, `t + compose + dispatch` to `+ wait`: they must
    stay disjoint with a step in flight."""
    records, _ = unified
    end = 0.0
    for r in records:
        start = r["t"] + (r["compose_ms"] + r["dispatch_ms"]) / 1e3
        assert start >= end - 1e-5, r
        end = start + r["wait_ms"] / 1e3


def _fields_the_readers_read():
    """Flight-record fields named by a file under benchmarks/layer_metrics/
    or by what they share (`tick_phases.py`, `serve_cell.host_intervals`)."""
    import os
    import re

    root = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    files = [os.path.join(root, "layer_metrics", f)
             for f in sorted(os.listdir(os.path.join(root, "layer_metrics")))
             if f.endswith(".py")] + [os.path.join(root, "tick_phases.py"),
                                      os.path.join(root, "time_account.py")]
    fields = {"t", "dur_ms", "kind", "prefill_rows"}      # host_intervals
    for path in files:
        text = open(path).read()
        fields.update(re.findall(r"""\bt(?:\.get\(|\[)["'](\w+)["']""", text))
        for call in re.findall(r"window_median\(run,([^)]*)\)", text):
            fields.update(re.findall(r"""["'](\w+)["']""", call))
    return fields


@pytest.mark.parametrize("which", ["lookahead", "settled"])
def test_every_field_a_reader_reads_is_in_the_record(unified, which):
    """Of the fields the benchmark's readers take from a tick, those this
    engine's records keep at all are in a record that ran ahead and in one
    that did not (a model without experts or window layers keeps none of
    theirs; the readers leave such ticks out)."""
    records, _ = unified
    fields = _fields_the_readers_read()
    assert {"wait_ms", "since_prev_ms", "decode_rows", "kv_tokens",
            "lookahead", "settled", "spill_ms"} <= fields
    kept = {f for f in fields if any(f in r for r in records)}
    assert {"admit_ms", "compose_ms", "dispatch_ms", "commit_ms", "wait_ms",
            "since_prev_ms", "decode_rows", "kv_tokens", "attn_pairs"} <= kept
    chosen = [r for r in records if r["lookahead"] == (which == "lookahead")]
    assert chosen
    if which == "lookahead":
        kept.discard("settled")     # why not: only where it did not
    for r in chosen:
        assert kept <= set(r), (kept - set(r), r)


@pytest.mark.parametrize("field,expected", [
    # ticks 1-3: A's chunks of 8 at contexts 8, 16, 24, B starved; tick 4:
    # A decodes at context 25 and B prefills 7; tick 5: A finished, B's
    # context reaches 7 + 8
    ("kv_tokens", [8, 16, 24, 25 + 7, 15]),
    ("prefill_tokens", [8, 8, 8, 7, 8]),
    ("starved", [1, 1, 1, 0, 0]),
    ("prefill_rows", [1, 1, 1, 1, 1]),
    ("decode_rows", [0, 0, 0, 1, 0]),
    # the paged kernel's walk (PR 32), pages of 8: a chunk of 8 is one query
    # block that walks its 1, 2, 3 pages; tick 4 is A's decode row over 25
    # tokens (4 pages) and B's 7 (1 page)
    ("q_blocks", [1, 1, 1, 2, 1]),
    ("kv_pages_walked", [1, 2, 3, 4 + 1, 2]),
    # the row kernel's unrolled starts (PR 65): a 5-D pool has none
    ("kv_pages_unrolled", [0, 0, 0, 0, 0]),
])
def test_counters_match_the_hand_built_batch(unified, field, expected):
    records, _ = unified
    assert [r[field] for r in records[:5]] == expected


def test_a_slice_of_several_query_blocks_walks_its_context_once_a_block(
        cpu_jax):
    """A 72-token prompt in one slice is ceil(72 / q_block) query blocks, and
    block j walks the pages up to its own last token (the causal exit), so
    pages walked over kv_tokens / page says how often a context is read."""
    from ray_tpu.llm.sampling import SamplingParams
    engine = _engine(prefill_chunk=72, token_budget=80)
    Q_BLOCK = engine.runner.block.q_block
    assert engine.stats()["kv_kernels"]["all"]["q_block"] == Q_BLOCK < 72
    engine.add_request(list(range(1, 73)), SamplingParams(max_tokens=2),
                       request_id="walk")
    while engine.has_unfinished():
        engine.step()
    first, second = list(engine.flight_records)[:2]
    ends = list(range(Q_BLOCK, 72, Q_BLOCK)) + [72]
    assert first["kv_tokens"] == 72 and first["q_blocks"] == len(ends)
    assert first["kv_pages_walked"] == sum(-(-e // 8) for e in ends)
    assert (second["q_blocks"], second["kv_pages_walked"]) == (1, 73 // 8 + 1)


def test_kv_pages_unrolled_is_the_row_kernels_rule_on_a_composed_tick():
    """Host only: of the pages that blocks of ONE token walk through a row
    pool's full form, `_kernel_walk` counts those the kernel starts unrolled,
    runs of `pa.PAGE_RUN` in every tile of `pages_one` (the rest of a ragged
    tile is started one by one); a 5-D pool's kernel and a latent one's start
    none that way."""
    from types import SimpleNamespace

    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.ops import paged_attention as pa

    def walk(kernels, entries):
        stub = SimpleNamespace(
            runner=SimpleNamespace(block=SimpleNamespace(q_block=32),
                                   kv_kernels=kernels),
            block_size=16, block_manager=SimpleNamespace(side={}))
        return LLMEngine._kernel_walk(stub, entries)

    row = lambda n, ctx: {"tokens": [0] * n, "kv_len": ctx, "q_pos": ctx - n}
    # decode rows of 2,119 pages (33 tiles of 64 and 7 more), of two whole
    # tiles and of 7 pages; a slice of 33 tokens is a block of 32 and a block
    # of ONE token that walks 313 pages (4 tiles, then 57: seven runs)
    tick = [row(1, 33900), row(1, 2 * 64 * 16), row(33, 5000), row(1, 100)]
    assert pa.PAGE_RUN == 8
    rows = {"all": pa.KVSizes(32, 64, 64, True, (128, 64)).describe()}
    assert walk(rows, tick) == {
        "q_blocks": 5, "kv_pages_walked": 2119 + 128 + 313 + 313 + 7,
        "kv_pages_unrolled": 2112 + 128 + (256 + 56) + 0}
    # tiles of 16 pages hold two runs each
    small = {"all": pa.KVSizes(32, 16, 64, True).describe()}
    assert walk(small, [row(1, 100 * 16)])["kv_pages_unrolled"] == 96
    for other in ({"all": pa.KVSizes(64, 16, 16, False).describe()}, {}):
        counted = walk(other, tick)
        assert counted["kv_pages_unrolled"] == 0
        assert counted["kv_pages_walked"] == 2880


@pytest.mark.parametrize("rid,arg,expected", [
    ("tick-a", "slices", 3), ("tick-a", "starved_ticks", 0),
    ("tick-a", "cached_tokens", 0),
    # B: 7 beside A's decode, then 8, 8 and the last token
    ("tick-b", "slices", 4), ("tick-b", "starved_ticks", 3),
    ("tick-b", "cached_tokens", 0), ("tick-b", "tokens", 24),
])
def test_prefill_span_counts_slices_and_starved_ticks(unified, rid, arg,
                                                      expected):
    _, spans = unified
    assert spans[rid][arg] == expected


def test_cached_tokens_in_the_span_are_the_prefix_hit(cpu_jax):
    from ray_tpu.llm.sampling import SamplingParams
    from ray_tpu.util import tracing

    engine = _engine(token_budget=None)
    prompt = list(range(1, 25))
    was = tracing.enabled()
    tracing.set_enabled(True)
    try:
        for rid in ("hit-1", "hit-2"):
            engine.add_request(prompt, SamplingParams(max_tokens=1),
                               request_id=rid)
            while engine.has_unfinished():
                engine.step()
        args = {s["args"]["request_id"]: s["args"]
                for s in tracing.get_spans() if s["name"] == "llm:prefill"}
    finally:
        tracing.set_enabled(was)
    assert args["hit-1"]["cached_tokens"] == 0
    # two full blocks of 8 are cached; the third holds the last token, which
    # must be recomputed for its logits
    assert args["hit-2"]["cached_tokens"] == 16
    assert args["hit-2"]["slices"] == 1


@pytest.fixture(scope="module")
def host_sampled(cpu_jax):
    """One 24-token prompt with a repetition penalty, 2 tokens out: three
    prefill ticks and one decode tick, all sampled on the host."""
    from ray_tpu.llm.sampling import SamplingParams

    engine = _engine()
    engine.add_request(list(range(1, 25)),
                       SamplingParams(max_tokens=2, repetition_penalty=1.2),
                       request_id="pen-a")
    while engine.has_unfinished():
        engine.step()
    return engine.tick_records()


@pytest.mark.parametrize("field", ("admit_ms", "since_prev_ms") + PHASES)
def test_host_sampled_ticks_keep_the_same_record(host_sampled, field):
    """A tick the host samples is a mixed tick like any other: the same
    phases on the same clock."""
    assert len(host_sampled) == 4
    for r in host_sampled:
        assert r["kind"] == "mixed" and r["host_sampled"]
        assert isinstance(r[field], (int, float)) and r[field] >= 0, (field, r)


def test_phase_clock_marks_are_the_hosts_clock(cpu_jax):
    from ray_tpu.util import tracing

    before = time.time()
    with tracing.PhaseClock("test:tick") as clock:
        a = clock.mark("one")
        b = clock.mark("two")
        c = clock.mark(None)
    assert before <= a <= b <= c <= time.time()
    with pytest.raises(ValueError):     # a raising body still closes it
        with tracing.PhaseClock("test:tick") as clock:
            clock.mark("one")
            raise ValueError("boom")
    assert clock._phase is None


def test_server_loop_records_the_gap_between_ticks(cpu_jax):
    import jax.numpy as jnp

    from ray_tpu.llm.serving import LLMConfig, LLMServer
    from ray_tpu.models import llama

    server = LLMServer(LLMConfig(
        model_config=llama.LlamaConfig.tiny(vocab_size=128, max_seq=128,
                                            dtype=jnp.float32),
        num_kv_blocks=64, block_size=8, max_batch_size=4, prefill_chunk=8,
        warmup_buckets="off", stream_timeout_s=60.0))
    try:
        out = server.completions({"prompt": list(range(1, 12)),
                                  "max_tokens": 4, "request_id": "loop-1"})
        assert len(out["choices"][0]["token_ids"]) == 4
        records = server.flight_records()
        assert len(records) >= 4
        # the loop's stream puts lie between one tick's end and the next
        assert all(r["since_prev_ms"] > 0 for r in records[1:])
    finally:
        server._handoff.close()


# ---- kernel names -----------------------------------------------------------

def _flash_args(seq):
    import jax
    import jax.numpy as jnp

    return tuple(jax.ShapeDtypeStruct((1, seq, h, 128), jnp.bfloat16)
                 for h in (4, 2, 2))


def _flash_fwd(seq):
    from ray_tpu.ops import attention as att

    return (lambda q, k, v: att.flash_attention_fwd(q, k, v)), _flash_args(seq)


def _flash_grad(seq):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import attention as att

    def loss(q, k, v):
        return att.flash_attention(q, k, v).astype(jnp.float32).sum()

    return jax.grad(loss, argnums=(0, 1, 2)), _flash_args(seq)


def _paged(unified):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    pool = sds((2, 16, 8, 2, 128), jnp.bfloat16)
    rest = (pool, pool, sds(()), sds((4, 8)), sds((4,)), sds((4,)))
    if unified:
        return (pa.ragged_paged_attention_unified,
                (sds((8, 4, 128), jnp.bfloat16),) + rest + (sds((5,)),))
    return (pa.ragged_paged_attention,
            (sds((4, 1, 4, 128), jnp.bfloat16),) + rest)


def _site(name):
    from ray_tpu.ops import attention as att

    fwd_tiled = 2 * att._FWD_RESIDENT_MAX_ROWS
    bwd_tiled = 2 * att._BWD_RESIDENT_MAX_ROWS
    return {
        "flash_fwd": lambda: _flash_fwd(256),
        "flash_fwd_tiled": lambda: _flash_fwd(fwd_tiled),
        "flash_bwd_dq_resident": lambda: _flash_grad(256),
        "flash_bwd_dkv_resident": lambda: _flash_grad(256),
        "flash_bwd_dq": lambda: _flash_grad(bwd_tiled),
        "flash_bwd_dkv": lambda: _flash_grad(bwd_tiled),
        # one K/V paged kernel behind both entry points (PR 32)
        "paged_attention_unified": lambda: _paged(True),
        "paged_attention_unified:rect": lambda: _paged(False),
    }[name]()


@pytest.mark.parametrize("site", [
    "flash_fwd", "flash_fwd_tiled", "flash_bwd_dq_resident",
    "flash_bwd_dkv_resident", "flash_bwd_dq", "flash_bwd_dkv",
    "paged_attention_unified", "paged_attention_unified:rect"])
def test_pallas_call_site_is_named_in_the_jaxpr(cpu_jax, site):
    import re

    fn, args = _site(site)
    name = site.split(":")[0]
    text = str(cpu_jax.make_jaxpr(fn)(*args))
    assert re.search(rf"\bname={name}\b", text), re.findall(r"name=\w+", text)
    assert f"'kernel': '{name}'" in text or f'"kernel": "{name}"' in text


# ---- the time account (PR 37) ------------------------------------------------

SEVEN = ("admit", "compose", "dispatch", "wait", "commit", "loop", "idle")


def _serve_one(engine, rid, n=24, out=20):
    from ray_tpu.llm.sampling import SamplingParams

    engine.add_request(list(range(1, n + 1)), SamplingParams(max_tokens=out),
                       request_id=rid)
    while engine.has_unfinished():
        engine.step()


def _slow_fetch(engine, every_s, once_s):
    """The device, made late: every wait lasts `every_s` more, and the
    first wait after `arm()` `once_s` more: what a step that ran long on the
    device looks like from the host."""
    real, state = engine._fetch, {"once": 0.0}

    def fetch(step):
        delay, state["once"] = every_s + state["once"], 0.0
        time.sleep(delay)
        return real(step)

    engine._fetch = fetch
    return lambda: state.update(once=once_s)


def test_the_seven_phases_add_up_to_the_records_wall_time(cpu_jax):
    engine = _engine()
    assert engine.stats()["time"]["ticks"] == 0
    for k in range(3):
        _serve_one(engine, f"sum-{k}", out=6)
        time.sleep(0.05)                     # an idle engine between two
    acc, records = engine.stats()["time"], engine.tick_records()
    first, last = records[0], records[-1]
    wall = (last["t"] + last["dur_ms"] / 1e3) - (
        first["t"] - first["admit_ms"] / 1e3)
    assert sum(acc[p] for p in SEVEN) == pytest.approx(wall, abs=1e-3)
    assert acc["t_last"] - acc["t_first"] == pytest.approx(wall, abs=1e-3)
    assert acc["ticks"] == len(records) and acc["idle"] >= 0.1
    # by phase, the account is the records' sum (each rounded to 1 us)
    for phase in ("admit", "compose", "dispatch", "wait", "commit"):
        assert acc[phase] == pytest.approx(
            sum(r[phase + "_ms"] for r in records) / 1e3, abs=1e-3), phase
    assert acc["loop"] + acc["idle"] == pytest.approx(
        sum(r["since_prev_ms"] for r in records) / 1e3, abs=1e-3)
    assert acc["cpu"] == pytest.approx(
        sum(r["cpu_ms"] for r in records) / 1e3, abs=1e-3)
    assert 0 < acc["cpu"] <= wall and acc["spill"] == 0.0


def test_gc_ms_sees_a_pass_on_another_thread_inside_a_tick_only(cpu_jax):
    """The collector fires on whichever thread allocates; the engine thread
    waits for the device meanwhile. A pass while the engine is idle lies in
    nobody's period."""
    import gc
    import threading

    from ray_tpu.util import tracing

    engine = _engine()
    ballast = [[i] for i in range(400_000)]      # a pass of well over 1 ms
    real, passes = engine._fetch, []
    was_on = gc.isenabled()
    gc.disable()           # no pass but the forced ones, for the test's sake

    def fetch(step):
        if len(engine.flight_records) == 5:      # once, inside a wait phase
            before = len(tracing.collector_passes())
            worker = threading.Thread(target=gc.collect)
            worker.start()
            worker.join()
            passes.extend(tracing.collector_passes()[before:])
        return real(step)

    engine._fetch = fetch
    try:
        _gc_inside_and_outside(engine, passes)
    finally:
        if was_on:
            gc.enable()
    del ballast


def _gc_inside_and_outside(engine, passes):
    import gc

    from ray_tpu.util import tracing

    _serve_one(engine, "gc-in", out=8)
    assert passes and passes[-1][2] == 2         # a full pass was kept
    hit = [r for r in engine.tick_records() if r["gc_ms"] > 0]
    assert len(hit) == 1 and hit[0] is engine.tick_records()[5]
    assert hit[0]["gc_ms"] == pytest.approx(
        1e3 * sum(stop - start for start, stop, _ in passes), abs=0.01)
    assert hit[0]["gc_ms"] <= hit[0]["wait_ms"]
    assert engine.stats()["time"]["gc"] == pytest.approx(
        hit[0]["gc_ms"] / 1e3, abs=1e-5)
    # outside any period: the engine is idle while this one runs
    n = len(engine.tick_records())
    gc.collect()
    start, stop, _ = tracing.collector_passes()[-1]
    assert tracing.collector_seconds(start, stop) == pytest.approx(
        stop - start)
    assert tracing.collector_seconds(stop, stop + 1.0) == 0.0
    earlier = tracing.collector_passes()[-2][1]      # the pass before it
    assert tracing.collector_seconds(earlier, start) == 0.0
    assert tracing.collector_seconds(earlier, start + 1e-4) == (
        pytest.approx(1e-4, abs=1e-6))
    _serve_one(engine, "gc-out", out=4)
    assert all(r["gc_ms"] == 0 for r in engine.tick_records()[n:])


def test_the_collectors_clock_is_installed_once(cpu_jax):
    import gc

    from ray_tpu.util import tracing

    _engine(), _engine()
    assert gc.callbacks.count(tracing._on_collection) == 1


def _long(**fields):
    """A long record of a 17 ms engine: 3 ms of host phases, 12 of wait,
    2 of loop, plus what a case adds."""
    record = dict(admit_ms=0.5, compose_ms=1.5, dispatch_ms=0.7, wait_ms=12.0,
                  commit_ms=0.8, since_prev_ms=2.0, spill_ms=0.0, gc_ms=0.0,
                  cpu_ms=3.5, lookahead=True, recompile=False)
    record.update(fields)
    return record


@pytest.mark.parametrize("cause,record,after", [
    ("recompile", _long(recompile=True, compose_ms=2000.0, gc_ms=150.0), {}),
    # a full pass on another thread while this one waited for the device
    ("gc", _long(wait_ms=142.0, gc_ms=128.0), {"wait_ms": 0.4}),
    ("host_work:admit", _long(admit_ms=120.0, cpu_ms=119.0), {}),
    ("host_work:spill", _long(compose_ms=131.0, spill_ms=128.0,
                              cpu_ms=90.0), {}),
    # LLMServer._lock handed to a burst of submitters between two calls
    ("host_blocked:loop", _long(since_prev_ms=95.0, cpu_ms=4.0), {}),
    # a pause of the machine: the next result was there already
    ("host_late", _long(wait_ms=125.0), {"wait_ms": 1.2}),
    ("device", _long(wait_ms=2900.0), {"wait_ms": 11.0}),
    ("wait", _long(wait_ms=130.0, lookahead=False, settled="draft"),
     {"wait_ms": 12.0}),
])
def test_a_long_records_excess_is_put_down_to_one_cause(cause, record, after):
    from ray_tpu.llm.engine import long_tick_excess, stall_cause

    period = record["since_prev_ms"] + record["admit_ms"] + sum(
        record[p] for p in PHASES)
    excess = long_tick_excess(period, [17.4] * 128)
    assert excess == pytest.approx(period - 17.4)
    assert stall_cause(record, after, excess, 12.0) == cause


def test_which_periods_are_long():
    from ray_tpu.llm.engine import long_tick_excess

    history = [17.4] * 64 + [22.6] * 16 + [130.0] * 2   # the median holds
    assert long_tick_excess(1.3 * 17.4, history) is None   # two prefill rows
    assert long_tick_excess(2 * 17.4 + 19.9, history) is None
    assert long_tick_excess(2 * 17.4 + 20.0, history) == pytest.approx(37.4)
    assert long_tick_excess(127.0, history) == pytest.approx(127.0 - 17.4)
    assert long_tick_excess(127.0, [17.4] * 15) is None    # too few to know
    # an engine of sub-millisecond ticks: the floor, not the factor, decides
    assert long_tick_excess(15.0, [0.7] * 128) is None
    # since_prev_ms after an idle engine is no loop: never the host's phase
    from ray_tpu.llm.engine import stall_cause

    waking = _long(since_prev_ms=5000.0, compose_ms=60.0, cpu_ms=58.0,
                   lookahead=False, settled="idle")
    assert stall_cause(waking, {}, 45.0, 12.0, idle=True) == (
        "host_work:compose")


@pytest.fixture(scope="module")
def stalled(cpu_jax):
    """An engine whose every wait lasts 5 ms, and ONE 250 ms: 20 tokens
    before it, an idle engine of 300 ms, then 30 tokens with the late step
    in their middle."""
    from ray_tpu.llm.sampling import SamplingParams

    engine = _engine()
    arm = _slow_fetch(engine, 0.005, 0.25)
    _serve_one(engine, "calm", out=20)
    time.sleep(0.3)
    before = engine.stats()["time"]
    n = len(engine.flight_records)
    engine.add_request(list(range(1, 25)), SamplingParams(max_tokens=30),
                       request_id="late")
    while engine.has_unfinished():
        if len(engine.flight_records) == n + 12:
            arm()
        engine.step()
    return engine, before, n


def test_an_idle_gap_is_not_a_stall(stalled):
    engine, before, n = stalled
    assert before["stalls"] == {} and before["ticks"] == n
    waking = engine.tick_records()[n]
    assert waking["since_prev_ms"] >= 300.0 and "stall" not in waking
    after = engine.stats()["time"]
    assert after["idle"] - before["idle"] == pytest.approx(
        waking["since_prev_ms"] / 1e3, abs=1e-3)
    assert after["loop"] < 0.1


def test_a_late_step_is_a_stall_with_a_cause_and_the_pair_is_kept(stalled):
    engine, before, n = stalled
    long = [r for r in engine.tick_records() if "stall" in r]
    assert len(long) == 1
    record = long[0]
    # the step was queued behind another and the next wait was no shorter
    assert record["lookahead"] and record["wait_ms"] >= 250.0
    assert record["stall"]["cause"] == "device"
    period = record["since_prev_ms"] + record["admit_ms"] + record["dur_ms"]
    assert 240.0 <= record["stall"]["ms"] < period
    assert record["cpu_ms"] < 50.0 and record["gc_ms"] == 0.0
    stalls = engine.stats()["time"]["stalls"]
    assert stalls == {"device": {"ticks": 1, "seconds": pytest.approx(
        record["stall"]["ms"] / 1e3, abs=1e-5)}}
    # the pair outlives the tick ring, without `emitted`
    pairs = engine.tick_records(stalls=True)
    assert len(pairs) == 1 and engine.tick_records(stalls=True, limit=1)
    kept, after = pairs[0]
    records = engine.tick_records()
    at = records.index(record)
    assert "emitted" in record and "emitted" not in kept
    assert kept == {k: v for k, v in record.items() if k != "emitted"}
    assert after == {k: v for k, v in records[at + 1].items()
                     if k != "emitted"}
    engine.flight_records.clear()
    assert engine.tick_records(stalls=True) == pairs


def test_the_server_publishes_stall_seconds_by_cause(cpu_jax):
    import jax.numpy as jnp

    from ray_tpu.llm.serving import LLMConfig, LLMServer
    from ray_tpu.models import llama
    from ray_tpu.runtime import metric_defs

    def seconds():
        snap = metric_defs.LLM_STALL_SECONDS.snapshot()
        return sum(v for k, v in snap.get("values", {}).items()
                   if "device" in k)

    server = LLMServer(LLMConfig(
        model_config=llama.LlamaConfig.tiny(vocab_size=128, max_seq=128,
                                            dtype=jnp.float32),
        num_kv_blocks=64, block_size=8, max_batch_size=4, prefill_chunk=8,
        warmup_buckets="off", stream_timeout_s=60.0))
    try:
        base = seconds()
        with server._lock:
            arm = _slow_fetch(server.engine, 0.005, 0.25)
        stream = server.completions_stream({
            "prompt": list(range(1, 12)), "max_tokens": 40,
            "request_id": "pub-1"})
        for k, event in enumerate(stream):
            if k == 25:
                arm()
        stalls = server.engine_stats()["time"]["stalls"]
        assert stalls["device"]["ticks"] == 1
        pairs = server.flight_records(stalls=True)
        assert pairs[0][0]["stall"]["cause"] == "device"
        server._publish_gauges()        # the loop's once-a-second call
        assert seconds() - base == pytest.approx(
            stalls["device"]["seconds"], abs=1e-5)
        server._publish_gauges()        # growth only: nothing twice
        assert seconds() - base == pytest.approx(
            stalls["device"]["seconds"], abs=1e-5)
    finally:
        server._handoff.close()
