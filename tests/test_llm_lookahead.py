"""One step of lookahead in the mixed tick (ISSUE 34): `step()` composes and
dispatches step n+1 before it waits for step n, the sampled tokens fed device
to device.

The contract is that nothing a client sees moves. Every scenario is driven
twice on fresh engines over the same parameters: by `step()` alone (one step
in flight between calls) and with `settle()` after every `step()`, which is
the synchronous schedule; the token streams must be equal, token for token,
for the three blocks (llama K/V, latent, window + full layer groups).
"""

import numpy as np
import pytest

import ray_tpu  # noqa: F401

BLOCKS = ("llama", "latent", "window")


@pytest.fixture(scope="module", params=BLOCKS)
def block(request, cpu_jax):
    """(name, config, params, runner keywords) of a tiny float32 model."""
    import jax
    import jax.numpy as jnp

    name = request.param
    if name == "llama":
        from ray_tpu.models import llama as model

        config = model.LlamaConfig.tiny(vocab_size=128, max_seq=256,
                                        dtype=jnp.float32)
        shape = dict(block_size=8, chunk_size=8)
    elif name == "latent":
        from ray_tpu.models import deepseek_v2 as model

        config = model.DeepseekV2Config.tiny(experts_held=(0, 8))
        shape = dict(block_size=8, chunk_size=8)
    else:
        from ray_tpu.models import mimo_v2_flash as model

        config = model.MimoV2FlashConfig.tiny()     # window 8
        shape = dict(block_size=4, chunk_size=16)
    return name, config, model.init_params(config, jax.random.key(0)), shape


def _engine(block, *, num_blocks=64, max_batch=4, params=None, **kw):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner

    _, config, own, shape = block
    runner = ModelRunner(config, own if params is None else params,
                         num_blocks=num_blocks, max_batch=max_batch, **shape)
    engine = LLMEngine(runner, max_batch_size=max_batch,
                       prefill_chunk=shape["chunk_size"], **kw)
    # The release rule, held in every scenario: no request's pages go back
    # while a step in flight still carries it.
    release = engine.block_manager.release

    def checked(req):
        assert req.flying == 0, f"{req.id} released with a step in flight"
        release(req)

    engine.block_manager.release = checked
    return engine


def _prompt(seed, n, vocab=128):
    return np.random.default_rng([34, seed]).integers(1, vocab, n).tolist()


class Streams:
    """What the clients of an engine saw: tokens by request, as emitted."""

    def __init__(self):
        self.tokens, self.reason = {}, {}

    def take(self, outs):
        for o in outs:
            self.tokens.setdefault(o.request_id, []).extend(o.new_token_ids)
            assert self.tokens[o.request_id] == o.output_token_ids
            if o.finished:
                assert o.request_id not in self.reason
                self.reason[o.request_id] = o.finish_reason


def _drive(engine, sync, script=None, streams=None):
    """Step to the end; `script(engine, n)` runs before the n-th step() call.
    `sync`: settle after every call, which is the synchronous schedule."""
    streams = streams or Streams()
    n = 0
    while True:
        more = script(engine, n) if script else False
        if not (more or engine.has_unfinished()):
            break
        streams.take(engine.step())
        if sync:
            engine.settle()
        # has_unfinished() is false only on a settled engine
        if not engine.has_unfinished():
            assert engine._flight is None and not engine._stash
        if engine._flight is not None:
            assert engine.has_unfinished()
        n += 1
        assert n < 2000
    return streams


def _both(block, script=None, **kw):
    """The scenario by step() alone and by the synchronous schedule."""
    ahead, sync = _engine(block, **kw), _engine(block, **kw)
    got = _drive(ahead, False, script and script())
    want = _drive(sync, True, script and script())
    assert got.tokens == want.tokens and got.reason == want.reason
    assert not sync.lookahead_ticks
    return ahead, got


def _pages_back(engine):
    """Every group's pages are free or parked, none live, none lost."""
    for name, c in engine.stats()["kv_groups"].items():
        assert c["live"] == 0 and c["free"] + c["parked"] == c["total"], (
            name, c)


def _arrivals(sampling):
    """Two short prompts first; while they decode, prompts that span several
    slices arrive, one of them for a single token."""
    from ray_tpu.llm.sampling import SamplingParams

    def params(max_tokens, rid):
        if sampling == "greedy":
            return SamplingParams(max_tokens=max_tokens)
        return SamplingParams(max_tokens=max_tokens, temperature=0.8,
                              top_k=20, seed=len(rid) + max_tokens)

    def script():
        def at(engine, n):
            if n == 0:
                engine.add_request(_prompt(1, 5), params(12, "a"),
                                   request_id="a")
                engine.add_request(_prompt(2, 11), params(7, "b"),
                                   request_id="b")
            elif n == 3:
                engine.add_request(_prompt(3, 45), params(9, "long"),
                                   request_id="long")
                engine.add_request(_prompt(4, 38), params(1, "one"),
                                   request_id="one")
            elif n == 6:
                engine.add_request(_prompt(5, 21), params(5, "late"),
                                   request_id="late")
            return n <= 6
        return at
    return script


@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
def test_step_alone_equals_the_synchronous_schedule(block, sampling):
    """Prompts that span several slices beside live decode rows, finishes by
    `max_tokens`: same tokens, and the steady ticks ran ahead."""
    engine, got = _both(block, _arrivals(sampling))
    assert {r: len(t) for r, t in got.tokens.items()} == {
        "a": 12, "b": 7, "long": 9, "one": 1, "late": 5}
    assert set(got.reason.values()) == {"length"}
    stats = engine.stats()
    records = engine.tick_records()
    assert stats["lookahead_ticks"] == sum(r["lookahead"] for r in records)
    assert stats["lookahead_ticks"] >= len(records) - 2
    assert stats["discarded_tokens"] == 0
    # a finish by max_tokens is known a step ahead: no row outlives its
    # request, so the rows dispatched are the tokens emitted
    assert sum(r["decode_rows"] for r in records) == sum(
        len(t) - 1 for t in got.tokens.values())
    assert set(stats["settled_ticks"]) <= {"idle"}
    _pages_back(engine)


def test_greedy_tokens_are_the_plain_forward_pass(block):
    """Against the model's own forward pass, not a second engine."""
    import jax.numpy as jnp

    from ray_tpu.llm.sampling import SamplingParams

    name, config, params, _ = block
    if name != "llama":
        pytest.skip("the families' references are held to in their own files")
    from ray_tpu.models import llama

    engine = _engine(block)
    prompts = [_prompt(7, 19), _prompt(8, 6)]
    outs = engine.generate(prompts, SamplingParams(max_tokens=6))
    for prompt, out in zip(prompts, outs):
        tokens = list(prompt)
        for _ in range(6):
            logits = llama.forward(params, jnp.asarray([tokens], jnp.int32),
                                   config)
            tokens.append(int(np.argmax(np.asarray(logits[0, -1]))))
        assert out.output_token_ids == tokens[len(prompt):]
    assert engine.lookahead_ticks and not engine.has_unfinished()


def test_prefix_hit_rides_a_step_in_flight(block):
    """A prompt that shares another's prefix arrives while that one's last
    slice is in flight: its blocks are addressable from the dispatch on."""
    from ray_tpu.llm.sampling import SamplingParams

    shared = _prompt(9, 32)

    def script():
        def at(engine, n):
            if n == 0:
                engine.add_request(shared + _prompt(10, 3),
                                   SamplingParams(max_tokens=6),
                                   request_id="first")
            elif n == 3:
                engine.add_request(shared + _prompt(11, 9),
                                   SamplingParams(max_tokens=6),
                                   request_id="second")
            return n <= 3
        return at

    engine, got = _both(block, script)
    assert engine.stats()["prefix_tokens_saved"] >= 24
    assert len(got.tokens["second"]) == 6
    _pages_back(engine)


def _stop_case(block):
    """A greedy request, and the first token of its stream from the third on
    that no earlier one equals: the stop token of the scenarios below."""
    from ray_tpu.llm.sampling import SamplingParams

    prompt = _prompt(12, 13)
    free = _engine(block).generate(
        [prompt], SamplingParams(max_tokens=24))[0].output_token_ids
    k = next(i for i in range(2, 20) if free[i] not in free[:i])
    return prompt, free, k


def test_stop_token_found_one_step_late(block):
    """Step n+1 already carries the row when commit n finds the stop token:
    its token is thrown away, the client sees today's tokens, and the pages
    come back at the commit of that last step, not before."""
    from ray_tpu.llm.sampling import SamplingParams

    prompt, free, k = _stop_case(block)

    def script():
        def at(engine, n):
            if n == 0:
                engine.add_request(prompt, SamplingParams(
                    max_tokens=24, stop_token_ids=[free[k]]),
                    request_id="stop")
                engine.add_request(_prompt(13, 9),
                                   SamplingParams(max_tokens=16),
                                   request_id="other")
            return n == 0
        return at

    engine, got = _both(block, script)
    assert got.tokens["stop"] == free[:k + 1]
    assert got.reason == {"stop": "stop", "other": "length"}
    assert engine.stats()["discarded_tokens"] == 1
    assert sum(r.get("discarded_tokens", 0)
               for r in engine.tick_records()) == 1
    _pages_back(engine)


def test_abort_with_the_request_in_flight(block):
    """abort_request settles the step in flight, then frees: the other
    request's tokens are unchanged and every page is accounted for."""
    from ray_tpu.llm.sampling import SamplingParams

    def script():
        def at(engine, n):
            if n == 0:
                engine.add_request(_prompt(14, 21),
                                   SamplingParams(max_tokens=40),
                                   request_id="gone")
                engine.add_request(_prompt(15, 10),
                                   SamplingParams(max_tokens=10),
                                   request_id="stays")
            elif n == 6:
                live = engine.stats()["kv_groups"]["all"]["live"]
                assert live and engine.abort_request("gone")
                assert engine._flight is None
                assert engine.stats()["kv_groups"]["all"]["live"] < live
                assert not engine.abort_request("gone")
            return n <= 6
        return at

    engine, got = _both(block, script)
    assert len(got.tokens["stays"]) == 10 and "gone" not in got.reason
    assert engine.stats()["settled_ticks"].get("call") == 1
    _pages_back(engine)


def test_page_pressure_settles_before_it_preempts(block):
    """A pool that cannot hold both sequences to their ends: the tick that
    would preempt settles first (`settled: pressure`), the victim's pages
    are released with nothing in flight, and it recomputes to the same
    tokens."""
    from ray_tpu.llm.sampling import SamplingParams

    page = block[3]["block_size"]

    def script():
        def at(engine, n):
            if n == 0:
                for i in range(2):
                    engine.add_request(
                        _prompt(16 + i, 3 * page - 1),
                        SamplingParams(max_tokens=7 * page),
                        request_id=f"p{i}")
            return n == 0
        return at

    # ten pages a sequence by its end; a window group wants two rings' room
    engine, got = _both(block, script, max_batch=2,
                        num_blocks=16 if block[0] == "window" else 11)
    assert [len(got.tokens[f"p{i}"]) for i in range(2)] == [7 * page] * 2
    assert engine.stats()["settled_ticks"].get("pressure", 0) >= 1
    assert engine.stats()["discarded_tokens"] == 0
    _pages_back(engine)


def test_update_weights_between_two_steps(block):
    """update_weights settles the step in flight: the tokens before the swap
    are the old weights', those after the new ones', as in the synchronous
    schedule."""
    import jax

    from ray_tpu.llm.sampling import SamplingParams

    name, config, params, _ = block
    other = jax.tree.map(lambda a: a * 1.25 if a.ndim > 1 else a, params)

    def script():
        def at(engine, n):
            if n == 0:
                engine.add_request(_prompt(18, 12),
                                   SamplingParams(max_tokens=10),
                                   request_id="swap")
            elif n == 5:
                info = engine.update_weights(other, force=True)
                assert info["version"] == 1 and engine._flight is None
            return n <= 5
        return at

    engine, got = _both(block, script)
    plain = _engine(block).generate(
        [_prompt(18, 12)], SamplingParams(max_tokens=10))[0].output_token_ids
    assert len(got.tokens["swap"]) == 10 and got.tokens["swap"] != plain
    assert got.tokens["swap"][:2] == plain[:2]


def test_export_request_between_two_steps(block):
    """A prefill-only engine's request is exported while the step that holds
    its last slice is in flight: export_request settles, the first token is
    in the state and the pages are written."""
    from ray_tpu.llm.sampling import SamplingParams

    if block[0] == "window":
        pytest.skip("no page travels for a block of two layer groups")
    prompt = _prompt(19, 27)
    want = _engine(block).generate(
        [prompt], SamplingParams(max_tokens=7))[0].output_token_ids
    pre = _engine(block, prefill_only=True)
    dec = _engine(block)
    pre.add_request(prompt, SamplingParams(max_tokens=7), request_id="x")
    while not pre.running:
        assert pre.step() == []
    assert pre._flight is not None and pre.running[0].pending == 1
    state = pre.export_request("x")
    assert pre._flight is None and state["output"] == want[:1]
    blocks = state.pop("blocks")
    pages = pre.runner.gather_pages(blocks)
    pre.block_manager.release_blocks(blocks)
    assert dec.adopt_request(state, *pages)
    assert [o.new_token_ids for o in pre.step()] == [want[:1]]
    assert not pre.has_unfinished()
    got = Streams()
    got.tokens["x"] = list(state["output"])
    _drive(dec, False, streams=got)
    assert got.tokens["x"] == want and dec.prefill_tokens_computed == 0


def test_drop_all_forgets_the_step_in_flight(block):
    """The server's failure path: every request released, the ones that had
    left the queues with the step in flight too, and the handle dropped."""
    from ray_tpu.llm.sampling import SamplingParams

    engine = _engine(block)
    engine.add_request(_prompt(25, 9), SamplingParams(max_tokens=3),
                       request_id="short")
    engine.add_request(_prompt(26, 30), SamplingParams(max_tokens=9),
                       request_id="long")
    while not engine._leaving:
        engine.step()
    assert engine._flight is not None and engine.has_unfinished()
    engine.drop_all()
    assert not engine.has_unfinished() and not engine._leaving
    assert engine.stats()["kv_groups"]["all"]["parked"] == 0
    _pages_back(engine)
    out = engine.generate([_prompt(27, 7)], SamplingParams(max_tokens=4))
    assert len(out[0].output_token_ids) == 4


def test_a_draft_settles_first(block):
    """With an n-gram proposer every tick that carries a decode row runs
    whole inside its call (`settled: draft`), to today's tokens."""
    from ray_tpu.llm.sampling import SamplingParams

    cyclic = [5, 9, 13] * 4

    def script():
        def at(engine, n):
            if n == 0:
                engine.add_request(cyclic, SamplingParams(max_tokens=12),
                                   request_id="cyc")
                engine.add_request(_prompt(20, 30),
                                   SamplingParams(max_tokens=3),
                                   request_id="other")
            return n == 0
        return at

    engine, got = _both(block, script, speculative_ngram=3)
    want = _engine(block).generate(
        [cyclic], SamplingParams(max_tokens=12))[0].output_token_ids
    assert got.tokens["cyc"] == want
    records = engine.tick_records()
    assert all(r.get("settled") == "draft" and not r["lookahead"]
               for r in records if r["decode_rows"])
    assert engine.stats()["spec_tokens_proposed"] > 0
    assert engine.stats()["settled_ticks"]["draft"] >= 1
    _pages_back(engine)


def test_a_host_sampled_tick_settles_first(block):
    """A repetition-penalty request arrives while the pipeline runs: the
    ticks that carry it land inside their calls (`settled: host_sampled`),
    the ones before and after run ahead, and no token moves."""
    from ray_tpu.llm.sampling import SamplingParams

    def script():
        def at(engine, n):
            if n == 0:
                engine.add_request(_prompt(21, 9),
                                   SamplingParams(max_tokens=20),
                                   request_id="plain")
            elif n == 4:
                engine.add_request(_prompt(22, 6), SamplingParams(
                    max_tokens=4, repetition_penalty=1.3), request_id="pen")
            return n <= 4
        return at

    engine, got = _both(block, script)
    assert len(got.tokens["plain"]) == 20 and len(got.tokens["pen"]) == 4
    records = engine.tick_records()
    host = [r for r in records if r["host_sampled"]]
    assert host and all(r["settled"] == "host_sampled" and not r["lookahead"]
                        for r in host)
    assert records[2]["lookahead"] and records[-2]["lookahead"]
    _pages_back(engine)


def test_the_sampled_tokens_stay_on_the_device(block):
    """A decode row behind a step in flight names its row of that step's
    samples and carries no token of its own."""
    from ray_tpu.llm.sampling import SamplingParams

    engine = _engine(block)
    seen = []
    mixed = engine.runner.step_mixed

    def spy(tokens, *args, prev_samples=None, token_src=None, **kw):
        if token_src is not None:       # not a warm-up of a new bucket
            seen.append((np.array(tokens), prev_samples,
                         np.array(token_src)))
        return mixed(tokens, *args, prev_samples=prev_samples,
                     token_src=token_src, **kw)

    engine.runner.step_mixed = spy
    engine.generate([_prompt(23, 5), _prompt(24, 6)],
                    SamplingParams(max_tokens=5))
    first, *rest = seen
    assert first[1] is None and (first[2] == -1).all()
    fed = [s for s in rest if (s[2] >= 0).any()]
    assert len(fed) >= 4
    for tokens, prev, src in fed:
        assert not isinstance(prev, np.ndarray)     # as it lies on the device
        assert (tokens[src >= 0] == 0).all() and set(src[src >= 0]) <= {0, 1}
