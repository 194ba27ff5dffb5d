"""The sampling head's filter (`ModelRunner._filter_logits`): temperature,
top-k and top-p over a row of the vocabulary, its thresholds found by
selection, not by a sort.

  * the contract, row by row, against a float64 numpy reference: the top-k
    keep set exactly (an order statistic has one value), the top-p keep set
    outside the band where float32 summation decides, ties together, a kept
    value `scaled`'s own float and a dropped one `NEG_INF`;
  * no sort over the vocabulary in the lowered program;
  * a seeded sampled request's tokens through an engine are those of the
    sort-based filter this file keeps as the reference (`sort_filter`, the
    function as it stood until ISSUE 39);
  * the host's sampler (`llm/sampling.py`, requests with a repetition
    penalty) keeps the same nucleus as the device's.
"""

import numpy as np
import pytest

import ray_tpu  # noqa: F401

NEG_INF = -1e30
MASS_BAND = 1e-5


def sort_filter(self, logits, temps, top_ks, top_ps):
    """`_filter_logits` as it stood before the selection: two sorts over the
    vocabulary, a gather and a cumulative sum. The reference of the engine
    case below and of the stand-alone timing (chip_smoke.py)."""
    import jax
    import jax.numpy as jnp

    V = logits.shape[-1]
    scaled = logits / jnp.maximum(temps[:, None], 1e-6)
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    k_eff = jnp.where(top_ks > 0, top_ks, V)
    kth = jnp.take_along_axis(
        sorted_desc, jnp.clip(k_eff - 1, 0, V - 1)[:, None], axis=1)
    scaled = jnp.where(scaled >= kth, scaled, NEG_INF)
    probs = jax.nn.softmax(scaled, axis=-1)
    sp = -jnp.sort(-probs, axis=-1)
    csum = jnp.cumsum(sp, axis=-1)
    keep_sorted = (csum - sp) < top_ps[:, None]
    cutoff = jnp.min(jnp.where(keep_sorted, sp, jnp.inf), axis=-1,
                     keepdims=True)
    return jnp.where(probs >= cutoff, scaled, NEG_INF)


def reference_row(logits, temp, top_k, top_p):
    """Steps 1-3 of the contract for one row, in numpy. -> (scaled float32,
    the top-k keep set, and for the top-p step every token's mass STRICTLY
    ABOVE it among the survivors, float64: a token stays where that mass is
    under `top_p`)."""
    V = logits.shape[0]
    scaled = logits / np.maximum(np.float32(temp), np.float32(1e-6))
    assert scaled.dtype == np.float32
    k = V if top_k <= 0 else min(top_k, V)
    kth = np.sort(scaled)[V - k]
    keep_k = scaled >= kth
    x = np.where(keep_k, scaled, np.float32(NEG_INF)).astype(np.float64)
    e = np.exp(x - x.max())
    probs = e / e.sum()
    # Mass strictly above: over distinct values, descending.
    order = np.argsort(-x, kind="stable")
    xs, ps = x[order], probs[order]
    before = np.concatenate([[0.0], np.cumsum(ps)[:-1]])
    first_of_tie = np.concatenate([[True], xs[1:] != xs[:-1]])
    above_sorted = np.maximum.accumulate(np.where(first_of_tie, before, 0.0))
    above = np.empty(V)
    above[order] = above_sorted
    return scaled, keep_k, above


def check_rows(got, logits, temps, top_ks, top_ps):
    got = np.asarray(got)
    for r in range(logits.shape[0]):
        scaled, keep_k, above = reference_row(
            logits[r], temps[r], int(top_ks[r]), float(top_ps[r]))
        kept = got[r] != np.float32(NEG_INF)
        # a kept value is scaled's own float, a dropped one NEG_INF
        np.testing.assert_array_equal(
            got[r][kept].view(np.uint32), scaled[kept].view(np.uint32))
        if top_ps[r] >= 1.0:
            np.testing.assert_array_equal(kept, keep_k, err_msg=f"row {r}")
            continue
        assert not (kept & ~keep_k).any(), f"row {r}: kept below the k-th"
        must_stay = keep_k & (above < top_ps[r] - MASS_BAND)
        must_go = above >= top_ps[r] + MASS_BAND
        assert kept[must_stay].all(), f"row {r}: dropped inside the nucleus"
        assert not kept[must_go].any(), f"row {r}: kept outside the nucleus"
        # ties stay together
        order = np.argsort(scaled, kind="stable")
        tied = scaled[order][1:] == scaled[order][:-1]
        assert (kept[order][1:][tied] == kept[order][:-1][tied]).all(), (
            f"row {r}: a tie was split")


@pytest.fixture(scope="module")
def runner(cpu_jax):
    """`_filter_logits` reads nothing of a runner but `NEG_INF`."""
    from ray_tpu.llm.model_runner import ModelRunner

    assert ModelRunner.NEG_INF == NEG_INF
    return object.__new__(ModelRunner)


def _filter(runner, logits, temps, top_ks, top_ps):
    import jax
    import jax.numpy as jnp

    return jax.jit(runner._filter_logits)(
        jnp.asarray(logits, jnp.float32), jnp.asarray(temps, jnp.float32),
        jnp.asarray(top_ks, jnp.int32), jnp.asarray(top_ps, jnp.float32))


def _draw(V, rows, seed):
    rng = np.random.default_rng([V, seed])
    return (rng.normal(size=(rows, V)) * 4).astype(np.float32)


VOCABS = {256: 5, 1000: 5, 19072: 4, 200064: 2}       # V: rows


@pytest.mark.parametrize("V", list(VOCABS))
@pytest.mark.parametrize("mix", ["top_k", "top_p", "both", "neither"])
def test_filter_keeps_what_the_reference_keeps(runner, V, mix):
    """Every row its own parameters: `top_k` through 0, 1, 50, V and past V,
    `top_p` through 1.0, 0.9, 0.5 and 1e-6, alone and together."""
    rows = VOCABS[V]
    logits = _draw(V, rows, 1)
    temps = np.linspace(0.5, 1.3, rows).astype(np.float32)
    ks = np.array([1, 50, V, V + 7, 0][:rows])
    ps = np.array([0.9, 0.5, 1e-6, 1.0, 0.9][:rows], np.float32)
    top_ks = ks if mix in ("top_k", "both") else np.zeros(rows, int)
    top_ps = ps if mix in ("top_p", "both") else np.ones(rows, np.float32)
    got = _filter(runner, logits, temps, top_ks, top_ps)
    check_rows(got, logits, temps, top_ks, top_ps)
    if mix == "neither":        # the scaled logits as they are
        np.testing.assert_array_equal(
            np.asarray(got), logits / temps[:, None])


def _tied(V, seed):
    """Rows whose thresholds fall ON ties: the 50th largest value held by
    many tokens, and a cutoff probability shared by several."""
    rng = np.random.default_rng([V, seed, 9])
    logits = (rng.normal(size=(4, V)) * 2).astype(np.float32)
    top = np.argsort(-logits, axis=1)
    for r in range(4):
        logits[r, top[r, 40:70]] = logits[r, top[r, 40]]     # around k = 50
        logits[r, top[r, 2:6]] = logits[r, top[r, 2]]        # inside the mass
    return logits


@pytest.mark.parametrize("case", [
    "ties_at_kth", "ties_at_cutoff", "equal_row", "infinities",
    "coldest", "quantised"])
def test_filter_edges(runner, case):
    V = 1000
    temps = np.full(4, 0.8, np.float32)
    top_ks = np.array([50, 50, 0, 45])
    top_ps = np.array([1.0, 0.7, 0.7, 0.95], np.float32)
    if case in ("ties_at_kth", "ties_at_cutoff"):
        logits = _tied(V, 3)
        if case == "ties_at_kth":       # the 41st to the 70th are one value
            top_ks, top_ps = np.array([50, 45, 41, 70]), np.ones(4, np.float32)
    elif case == "equal_row":       # one value: every threshold is a tie
        logits = np.full((4, V), 1.25, np.float32)
    elif case == "infinities":      # masked-out tokens arrive as -inf / -1e30
        logits = _draw(V, 4, 5)
        logits[:, ::3] = -np.inf
        logits[:, 1::7] = NEG_INF
        top_ks = np.array([50, 700, 0, 950])    # the k-th IS -1e30 / -inf
    elif case == "coldest":         # temperature at the clamp: one-hot rows
        logits = _draw(V, 4, 6)
        logits[1, 17] = logits[1].max()         # ... and a tie at the top
        temps = np.array([1e-6, 1e-6, 0.0, 1e-7], np.float32)
    else:                           # bf16-like logits: ties everywhere
        logits = np.round(_draw(V, 4, 7) * 2) / 2
    got = _filter(runner, logits, temps, top_ks, top_ps)
    check_rows(got, logits, temps, top_ks, top_ps)
    if case == "ties_at_kth":
        assert ((np.asarray(got) != np.float32(NEG_INF)).sum(1) == 70).all()
    if case == "equal_row":
        assert (np.asarray(got) != np.float32(NEG_INF)).all()


def test_a_row_that_asks_for_nothing_rides_the_passes_unharmed(runner):
    """Shapes are fixed: a row with `top_k` 0 and `top_p` 1 runs the passes
    of its neighbours and keeps its scaled logits as they are."""
    logits = _draw(256, 4, 8)
    temps = np.ones(4, np.float32)
    got = np.asarray(_filter(runner, logits, temps, [0, 5, 0, 0],
                             [1.0, 1.0, 0.3, 1.0]))
    np.testing.assert_array_equal(got[0], logits[0])
    np.testing.assert_array_equal(got[3], logits[3])
    assert (got[1] != np.float32(NEG_INF)).sum() == 5


def test_the_step_sorts_nothing_as_long_as_the_vocabulary(runner):
    """The lowered `_filter_sampled` holds no sort at all, so none over an
    axis of the vocabulary's length, and no cumulative sum along it."""
    import re

    import jax
    import jax.numpy as jnp

    n, V = 16, 4099
    text = jax.jit(runner._filter_sampled).lower(
        jax.ShapeDtypeStruct((n, V), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.float32)).as_text()
    assert f"{n}x{V}" in text
    assert not re.search(r"stablehlo\.sort|top_k", text)
    # `nonzero` sums along the rows; nothing sums along the vocabulary
    assert not [line for line in text.splitlines()
                if "cumsum" in line and str(V) in line]


# ---- through an engine -------------------------------------------------------

def _served_tokens(config, params):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.sampling import SamplingParams

    engine = LLMEngine(
        ModelRunner(config, params, num_blocks=64, block_size=8,
                    chunk_size=8),
        max_batch_size=4, prefill_chunk=8)
    sp = SamplingParams(max_tokens=12, temperature=0.8, top_k=50)
    for i, prompt in enumerate([[(5 * i + 1) % 128 for i in range(11)],
                                [2, 7, 1, 12, 9, 5, 3, 13]]):
        engine.add_request(prompt, sp, request_id=f"seeded-{i}")
    done = {}
    while engine.has_unfinished():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out.output_token_ids
    return done, engine


def test_a_seeded_request_draws_what_it_drew_behind_the_sort(cpu_jax,
                                                             monkeypatch):
    """Two seeded sampled requests (top-k 50 of 128) return, token for token,
    what the sort-based filter gives them: the kept set is the same and the
    draw's keys are untouched. The flight records count the rows that sample
    and what they ask of the filter."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.models import llama

    config = llama.LlamaConfig.tiny(vocab_size=128, max_seq=64,
                                    dtype=jnp.float32)
    params = llama.init_params(config, jax.random.key(0))
    got, engine = _served_tokens(config, params)
    monkeypatch.setattr(ModelRunner, "_filter_logits", sort_filter)
    want, _ = _served_tokens(config, params)
    assert got == want
    assert all(len(tokens) == 12 for tokens in got.values())
    assert got["seeded-0"] != got["seeded-1"]
    stats = engine.stats()
    assert stats["sampled_rows"] == stats["topk_rows"] > 0
    assert stats["topp_rows"] == 0
    records = [r for r in engine.flight_records if r.get("sampled_rows")]
    assert records and all(
        r["topk_rows"] == r["sampled_rows"] and r["topp_rows"] == 0
        and not r["host_sampled"] for r in records)
    assert sum(r["sampled_rows"] for r in records) == stats["sampled_rows"]


# ---- the host's sampler keeps the same nucleus --------------------------------

@pytest.mark.parametrize("top_p", [0.9, 0.5, 0.05])
@pytest.mark.parametrize("top_k", [0, 20])
def test_host_nucleus_is_the_devices(runner, top_k, top_p):
    """`sampling.sample` (a request with a repetition penalty) keeps the
    crossing token, as the device's filter does: over many draws its tokens
    cover the device's keep set and nothing else."""
    from ray_tpu.llm.sampling import SamplingParams, nucleus

    rng = np.random.default_rng([top_k, int(top_p * 100)])
    logits = (rng.normal(size=(1, 64)) * 2).astype(np.float32)
    logits[0, 5] = logits[0, 9] = np.sort(logits[0])[-3]       # a tie inside
    temp = 0.7
    device = np.asarray(_filter(runner, logits, [temp], [top_k], [top_p]))[0]
    kept = set(np.flatnonzero(device != np.float32(NEG_INF)).tolist())
    host = nucleus(logits[0].astype(np.float64),
                   SamplingParams(temperature=temp, top_k=top_k, top_p=top_p))
    assert set(np.flatnonzero(np.isfinite(host)).tolist()) == kept
    # the crossing token is in: the kept mass reaches top_p
    e = np.exp(logits[0].astype(np.float64) / temp)
    if top_k:
        e[np.argsort(-logits[0])[top_k:]] = 0.0
    assert e[sorted(kept)].sum() / e.sum() >= top_p
