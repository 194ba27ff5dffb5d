"""Unified ragged ticks: one mixed prefill+decode launch per engine step.

Anchors the tentpole's correctness contract at three layers:

  * kernel — the token-major unified reference is BIT-identical per row to
    the rectangular per-sequence reference (same math, different layout),
    and the Pallas unified kernel (interpret mode on CPU) matches it
    numerically;
  * engine — a mixed tick's greedy output is bit-identical to the naive
    full-forward reference, seeded temperature sampling replays, a request
    the host has to sample (repetition penalty) and a prefill-only engine
    ride the same tick, with zero pickling on the hot loop;
  * speculation — n-gram drafts verified by seeded acceptance sampling
    replay deterministically (same request id -> same tokens), and the
    warmed T-bucket ladder holds steady state at zero recompiles.
"""

import numpy as np
import pytest

import ray_tpu  # noqa: F401


def _tiny(vocab=128, max_seq=64):
    import jax.numpy as jnp

    from ray_tpu.models import llama

    # fp32: greedy argmax must be noise-free for exact unified-vs-split.
    return llama.LlamaConfig.tiny(vocab_size=vocab, max_seq=max_seq,
                                  dtype=jnp.float32)


@pytest.fixture(scope="module")
def setup(cpu_jax):
    import jax

    from ray_tpu.models import llama

    config = _tiny()
    params = llama.init_params(config, jax.random.key(0))
    return config, params


def _engine(config, params, *, spec=0, **kw):
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner

    runner = ModelRunner(config, params, num_blocks=64, block_size=8,
                         chunk_size=8)
    return LLMEngine(runner, max_batch_size=4, prefill_chunk=8,
                     speculative_ngram=spec, **kw)


def naive_greedy(params, config, prompt, n_steps):
    import jax.numpy as jnp

    from ray_tpu.models import llama

    tokens = list(prompt)
    for _ in range(n_steps):
        logits = llama.forward(params, jnp.asarray([tokens], dtype=jnp.int32),
                               config)
        tokens.append(int(np.argmax(np.asarray(logits[0, -1]))))
    return tokens[len(prompt):]


# ---------------------------------------------------------------------------
# Kernel layer: token-major ragged layout vs rectangular per-sequence.
# ---------------------------------------------------------------------------


def _ragged_case(seed=0, q_lens=(1, 3, 8), kv_lens=(9, 11, 8), T=16, K=2,
                 H=4, hd=8, ps=4, L=3, dtype=np.float32, vd=None):
    """A mixed batch over pools as they lie, (L, P, ps, K, hd), whose layers
    and pages all differ and whose tables are shuffled. Default: one decode
    row (1 token), one spec-verify-sized chunk (3 rows), one prefill slice
    (8 rows), and flat-tail padding."""
    rng = np.random.default_rng(seed)
    S = len(q_lens)
    max_pages = max(-(-int(n) // ps) for n in kv_lens) + 1
    cu = np.zeros(S + 1, np.int32)
    cu[1:] = np.cumsum(q_lens)
    assert cu[-1] <= T
    kv_lens = np.asarray(kv_lens, np.int32)      # context incl. new tokens
    q_positions = np.maximum(kv_lens - np.asarray(q_lens, np.int32), 0)
    P = 1 + S * max_pages
    k_pool = rng.standard_normal((L, P, ps, K, hd)).astype(dtype)
    v_pool = rng.standard_normal((L, P, ps, K, vd or hd)).astype(dtype)
    block_tables = rng.permutation(S * max_pages).astype(np.int32).reshape(
        S, max_pages) + 1
    q = rng.standard_normal((T, H, hd)).astype(dtype)
    return q, k_pool, v_pool, block_tables, kv_lens, q_positions, cu


def _on_device(case, layer):
    import jax.numpy as jnp

    q, kp, vp, bt, kv_lens, q_pos, cu = case
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.int32(layer), jnp.asarray(bt), jnp.asarray(kv_lens),
            jnp.asarray(q_pos), jnp.asarray(cu))


def test_unified_reference_matches_rectangular_per_sequence(cpu_jax):
    """Each sequence's rows through the token-major layout equal the same
    rows pushed through the rectangular per-sequence reference. Tolerance
    is last-ulp only: XLA reduction order differs between batch shapes,
    the math does not. (The token-level identity with the plain forward
    pass is enforced at the engine layer below.)"""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    case = _ragged_case()
    q, kp, vp, bt, kv_lens, q_pos, cu = case
    out = np.asarray(pa.ragged_paged_attention_unified_reference(
        *_on_device(case, 1)))
    S = len(kv_lens)
    for s in range(S):
        rect = pa.ragged_paged_attention_reference(
            jnp.asarray(q[cu[s]:cu[s + 1]][None]), jnp.asarray(kp),
            jnp.asarray(vp), jnp.int32(1), jnp.asarray(bt[s:s + 1]),
            jnp.asarray(kv_lens[s:s + 1]), jnp.asarray(q_pos[s:s + 1]))
        np.testing.assert_allclose(out[cu[s]:cu[s + 1]], np.asarray(rect[0]),
                                   rtol=2e-6, atol=2e-7,
                                   err_msg=f"row block {s} diverged")
    # Padding rows (beyond cu[-1]) are exact zeros, not garbage.
    assert np.array_equal(out[cu[S]:], np.zeros_like(out[cu[S]:]))


def test_unified_pallas_matches_reference(cpu_jax):
    """The Pallas kernel (interpret mode on CPU) computes the same online
    softmax as the reference within fp32 accumulation noise."""
    from ray_tpu.ops import paged_attention as pa

    case = _ragged_case(seed=7)
    cu = case[-1]
    args = _on_device(case, 2)
    ref = np.asarray(pa.ragged_paged_attention_unified_reference(*args))
    out = np.asarray(pa.ragged_paged_attention_unified(*args))
    np.testing.assert_allclose(out[:cu[-1]], ref[:cu[-1]],
                               rtol=1e-5, atol=1e-5)
    assert np.array_equal(out[cu[-1]:], np.zeros_like(out[cu[-1]:]))


# The kernel's walk (PR 32) at tiny sizes: pages of 4, tiles of 2 pages (8
# context tokens), query blocks of 4 tokens. A case is (q_lens, kv_lens, T).
_WALKS = {
    "decode_only": ((1, 1, 1), (9, 1, 20), 8),
    # the slice is 3 query blocks (4 + 4 + 1) beside two decode rows
    "slice_of_several_blocks": ((1, 9, 1), (12, 21, 5), 16),
    "spec_verify_rows": ((3, 3, 1), (11, 7, 9), 8),
    # a padding sequence (kv_len 0) between real ones, padding tokens after
    "padding_sequence_and_tokens": ((1, 0, 2), (6, 0, 9), 16),
    # contexts of one token, exactly a tile, a tile + a page, and a length
    # that is no multiple of the page
    "context_edges": ((1, 1, 1, 1), (1, 8, 12, 7), 8),
    "slice_from_position_zero": ((9,), (9,), 16),
}


def _ring_tables(bt, kv_lens, q_pos, window, ps, width):
    """The window form's block tables: logical page p of a sequence at slot
    p % width, for the pages its first query token's window starts in up to
    its last token's; every other slot names page 0, which nothing may
    read."""
    ring = np.zeros((len(kv_lens), width), np.int32)
    for s, (n, p0) in enumerate(zip(kv_lens, q_pos)):
        for p in range(max(0, int(p0) - (window - 1)) // ps, -(-int(n) // ps)):
            ring[s, p % width] = bt[s, p]
    return ring


def _dense_attention(case, layer, window, sink, scale):
    """The flat rows' attention written out a token at a time from the FULL
    tables: a window's lower edge, a sink column and a narrower V, with no
    paging trick to share with the functions under test."""
    q, kp, vp, bt, kv_lens, q_pos, cu = case
    ps, K = kp.shape[2], kp.shape[3]
    G = q.shape[1] // K
    out = np.zeros(q.shape[:2] + (vp.shape[-1],), np.float64)
    for s in range(len(kv_lens)):
        for t in range(cu[s], cu[s + 1]):
            i = int(q_pos[s]) + t - cu[s]
            js = [j for j in range(min(i + 1, int(kv_lens[s])))
                  if window is None or i - j < window]
            for h in range(q.shape[1]):
                k = np.stack([kp[layer, bt[s, j // ps], j % ps, h // G]
                              for j in js]).astype(np.float64)
                v = np.stack([vp[layer, bt[s, j // ps], j % ps, h // G]
                              for j in js]).astype(np.float64)
                sc = k @ q[t, h].astype(np.float64) * scale
                top = max(sc.max(), sink[h] if sink is not None else -np.inf)
                w = np.exp(sc - top)
                denom = w.sum() + (np.exp(sink[h] - top)
                                   if sink is not None else 0.0)
                out[t, h] = (w / denom) @ v
    return out


def _check_rectangular_entry(q, q_lens, cu, args, kw, out):
    """The rectangular entry, every sequence padded to the longest row,
    against its reference and against the token-major entry's `out`."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    Bq = max(q_lens)
    rect = np.zeros((len(q_lens), Bq) + q.shape[1:], q.dtype)
    for s, n in enumerate(q_lens):
        rect[s, :n] = q[cu[s]:cu[s + 1]]
    rargs = (jnp.asarray(rect),) + args[1:7]
    rref = np.asarray(pa.ragged_paged_attention_reference(*rargs, **kw))
    rout = np.asarray(pa.ragged_paged_attention(*rargs, **kw))
    for s, n in enumerate(q_lens):
        np.testing.assert_allclose(rout[s, :n], rref[s, :n], rtol=1e-5,
                                   atol=1e-5, err_msg=f"sequence {s}")
        np.testing.assert_allclose(rout[s, :n], out[cu[s]:cu[s + 1]],
                                   rtol=1e-5, atol=1e-5)


# (kv heads, query heads), the pool's layer, and the kernel's form: none, or
# a window over a ring table with a sink logit a head and a V pool narrower
# than K (query and key width 8, value width 4).
_WINDOW = dict(window=5, vd=4, ring=5)


@pytest.mark.parametrize("heads,layer,form", [
    ((2, 2), 0, None), ((2, 8), 0, None), ((2, 8), 2, None),
    ((1, 8), 2, None), ((2, 8), 1, _WINDOW), ((4, 4), 2, _WINDOW),
    ((1, 8), 0, dict(vd=4))],
    ids=["G1-layer0", "G4-layer0", "G4-last_layer",
         "G8_one_kv_head-last_layer", "G4-window_sink_widths",
         "G1-window_sink_widths", "G8-two_widths"])
@pytest.mark.parametrize("walk", sorted(_WALKS))
def test_kv_kernel_matches_reference(cpu_jax, monkeypatch, walk, heads,
                                     layer, form):
    """One K/V kernel behind both entry points, against the jnp references:
    the token-major entry on the case as given, and the rectangular entry
    on each sequence's own rows. The pools' layers differ, so a wrong layer
    offset fails; so do the shuffled tables. In the window form the tables
    are rings that name page 0 wherever a page has left every window, the
    references are held to the attention written out from the full tables,
    and a kernel that read a page behind a window would read page 0."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "kv_sizes",
                        lambda *a, **kw: pa.KVSizes(4, 2, 2, False))
    q_lens, kv_lens, T = _WALKS[walk]
    K, H = heads
    form = dict(form or {})
    case = _ragged_case(seed=len(walk) + H, q_lens=q_lens, kv_lens=kv_lens,
                        T=T, K=K, H=H, vd=form.pop("vd", None))
    q, kp, vp, bt, kvl, q_pos, cu = case
    args = _on_device(case, layer)
    kw = {}
    if form:
        window = form["window"]
        sink = np.random.default_rng(H).standard_normal(H).astype(np.float32)
        ring = _ring_tables(bt, kvl, q_pos, window, kp.shape[2],
                            form["ring"])
        args = args[:4] + (jnp.asarray(ring),) + args[5:]
        kw = dict(window=window, sink=jnp.asarray(sink))
    ref = np.asarray(pa.ragged_paged_attention_unified_reference(*args, **kw))
    out = np.asarray(pa.ragged_paged_attention_unified(*args, **kw))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    if form:
        dense = _dense_attention(case, layer, window, sink,
                                 1.0 / np.sqrt(q.shape[-1]))
        np.testing.assert_allclose(ref[:cu[-1]], dense[:cu[-1]], rtol=1e-4,
                                   atol=1e-5)
    _check_rectangular_entry(q, q_lens, cu, args, kw, out)


# The kernel of ROW POOLS (PR 46) at small shapes of MiMo-V2-Flash's kind: a
# full layer's 4 kv heads under 64 query heads (G = 16) with V narrower than
# K; a window layer's 8 kv heads with a ring table and a sink logit a head.
# Sizes (query tokens a block, pages a step of a block of one token and of a
# block of many), then the walk. With pages of 4 and 3 | 2 pages a step a
# context of 70 has whole tiles that every row sees whole (the FAST walk),
# and a block of 32 tokens at G = 16 is 512 rows a kv head: two passes of a
# step, and four groups of tokens written out.
_ROW_FORMS = {
    "G16_K4_two_widths": dict(K=4, H=64, vd=4),
    "G2_K8_window_ring_sink": dict(K=8, H=16, vd=4, window=5, ring=5),
    "G8_K8_window_ring_sink": dict(K=8, H=64, vd=4, window=9, ring=6),
}
# Trinity-Large-Preview's kind (PR 59): SIX query heads a kv head (a kv head's
# rows are no whole sublane tile), K and V the same width. Run from
# tests/test_llm_afmoe.py, a worker of their own: this file's worker stands
# near the kernel's limit of mapped programs as it is (the verify skill's
# note on `vm.max_map_count`; 28 more kernels here killed it, PR 59).
ROW_FORMS_G6 = {
    "G6_K2_one_width": dict(K=2, H=12, vd=None),
    "G6_K2_window_ring_sink": dict(K=2, H=12, vd=None, window=9, ring=6),
}
_ROW_WALKS = dict(
    {name: ((4, 3, 2),) + walk for name, walk in _WALKS.items()},
    # decode rows at contexts of many tiles, one ending mid-tile, one
    # mid-page, and a padding sequence between them
    long_decode_rows=((4, 3, 2), (1, 1, 0, 1), (70, 48, 0, 61), 8),
    # a slice of two blocks (32 + 8 tokens) over a long context beside a
    # decode row; the first block's rows take two passes a step
    slice_of_two_passes=((32, 3, 2), (1, 40), (30, 70), 48),
    mixed_tick_padding_block=((8, 2, 3), (1, 17, 0, 1), (33, 40, 0, 5), 32),
)


@pytest.mark.parametrize("form", sorted(_ROW_FORMS))
@pytest.mark.parametrize("walk", sorted(_ROW_WALKS))
def test_kv_rows_kernel_matches_reference(cpu_jax, monkeypatch, walk, form):
    """`_kv_rows_kernel` behind both entry points against the jnp references
    (which read a row pool as the same rows, (K, width) apart), the way
    test_kv_kernel_matches_reference holds the 5-D kernel: the token-major
    entry on the case as given, the rectangular entry on each sequence's own
    rows, and with a window the references against the attention written out
    from the full tables."""
    rows_kernel_case(monkeypatch, walk, _ROW_FORMS[form])


def rows_kernel_case(monkeypatch, walk, form):
    """One case of test_kv_rows_kernel_matches_reference: `walk` a name of
    `_ROW_WALKS`, `form` a dict as `_ROW_FORMS` holds them."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    sizes, q_lens, kv_lens, T = _ROW_WALKS[walk]
    monkeypatch.setattr(pa, "kv_sizes",
                        lambda *a, **kw: pa.KVSizes(*sizes, True))
    f = dict(form)
    K, H = f["K"], f["H"]
    case = _ragged_case(seed=len(walk) + H, q_lens=q_lens, kv_lens=kv_lens,
                        T=T, K=K, H=H, vd=f["vd"])
    q, kp, vp, bt, kvl, q_pos, cu = case
    layer = 1
    args = _on_device(case, layer)
    args = (args[0],) + tuple(
        p.reshape(*p.shape[:3], -1) for p in args[1:3]) + args[3:]
    kw = dict(kv_heads=K)
    window, sink = f.get("window"), None
    if window:
        sink = np.random.default_rng(H).standard_normal(H).astype(np.float32)
        ring = _ring_tables(bt, kvl, q_pos, window, kp.shape[2], f["ring"]
                            + -(-max(q_lens) // kp.shape[2]))
        args = args[:4] + (jnp.asarray(ring),) + args[5:]
        kw.update(window=window, sink=jnp.asarray(sink))
    ref = np.asarray(pa.ragged_paged_attention_unified_reference(*args, **kw))
    out = np.asarray(pa.ragged_paged_attention_unified(*args, **kw))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    dense = _dense_attention(case, layer, window, sink,
                             1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(ref[:cu[-1]], dense[:cu[-1]], rtol=1e-4,
                               atol=1e-5)
    _check_rectangular_entry(q, q_lens, cu, args, kw, out)


# (H, K, hd, vd, rows, window): the shapes the K/V kernels meet in the
# benchmark's cells, pages of 16 tokens, bfloat16.
_KV_SHAPES = {
    "mistral": (32, 8, 128, 128, False, None),
    "phi_pairs": (40, 10, 128, 128, True, None),
    "phi_pairs_window": (40, 10, 128, 128, True, 512),
    "mimo_full": (64, 4, 192, 128, True, None),
    "mimo_window": (64, 8, 192, 128, True, 128),
    "afmoe_full": (48, 8, 128, 128, True, None),
    "afmoe_window": (48, 8, 128, 128, True, 4096),
}

# What `kv_sizes` returned for the shapes above BEFORE the window form's tile
# became a function of the window's pages (PR 59), and returns still; and
# what the v5e sweep chose at Trinity-Large-Preview's (chip_smoke.py --phase
# afmoe_kernels; `kv_sizes`' docstring has the readings). MiMo's two since
# PR 64 at the widths its K rows lie at, 192 lanes a head split 128 + 64 (a
# page 40 | 80 KB where the rows padded to 256 lanes a head made it 48 | 96):
# `chip_smoke.py`'s `mimo_kernel_timing` swept them again, and over split rows
# a full layer's block of one token takes 64 pages a step too (32 before).
_KV_CHOSEN = {
    "mistral": (64, 16, 16), "phi_pairs": (48, 16, 32),
    "phi_pairs_window": (48, 16, 16), "mimo_full": (32, 64, 64),
    "mimo_window": (32, 16, 16), "afmoe_full": (40, 16, 32),
    "afmoe_window": (40, 16, 48),
}


@pytest.mark.parametrize("shape", sorted(_KV_CHOSEN))
def test_kv_sizes_of_every_cell_are_what_was_swept(shape):
    from ray_tpu.ops import paged_attention as pa

    H, K, hd, vd, rows, window = _KV_SHAPES[shape]
    split = (128, 64) if shape.startswith("mimo") else (hd, 0)
    assert pa.kv_sizes(H, K, hd, vd, 16, 2, rows=rows, window=window) \
        == pa.KVSizes(*_KV_CHOSEN[shape], rows, split if rows else None)


@pytest.mark.parametrize("window,many", [
    (128, 16), (512, 16), (1024, 16), (2048, 16), (4096, 48), (8192, 64),
    (32768, 64)])
def test_a_window_tile_is_a_fifth_of_the_windows_pages_at_most(window, many):
    """The window form's tile of a block of many tokens, at a shape whose
    budget leaves it alone (16 / 8 heads of 128): a block of one token's 16
    pages while the window's walk is a few such tiles (MiMo's 128 tokens,
    Phi's 512), then whole multiples of 16 pages up to a fifth of the
    window's (`WINDOW_TILES`), and never over the full form's tile."""
    from ray_tpu.ops import paged_attention as pa

    sizes = pa.kv_sizes(16, 8, 128, 128, 16, 2, rows=True, window=window)
    full = pa.kv_sizes(16, 8, 128, 128, 16, 2, rows=True)
    assert (sizes.pages_one, sizes.pages_many) == (16, many)
    assert sizes.pages_many <= full.pages_many


@pytest.mark.parametrize("shape", sorted(_KV_SHAPES))
def test_kv_sizes_fit_the_stated_budget(shape):
    """`kv_sizes` is the one place a K/V kernel's block and tile sizes live:
    at every shape a cell runs, what it returns reckons under the budget, and
    the query block does not depend on the page size (a block states it once,
    before it knows its pages)."""
    from ray_tpu.ops import paged_attention as pa

    H, K, hd, vd, rows, window = _KV_SHAPES[shape]
    sizes = pa.kv_sizes(H, K, hd, vd, 16, 2, rows=rows, window=window)
    assert sizes.rows == rows
    assert pa.kv_vmem_bytes(H, K, hd, vd, 16, 2, rows, sizes.q_block,
                            sizes.pages_one, sizes.pages_many) \
        <= pa.KV_VMEM_BUDGET
    assert sizes.q_block % 8 == 0 and min(sizes[1:3]) >= 1
    for ps in (4, 8, 32):
        assert pa.kv_sizes(H, K, hd, vd, ps, 2, rows=rows,
                           window=window).q_block == sizes.q_block
    assert sizes.describe()["layout"] == ("rows" if rows else "5d")


def test_kv_sizes_at_known_shapes():
    """Mistral's 5-D pools keep what PR 32 swept (64 tokens a block, 16
    pages a step, the masked all-heads decode form); MiMo's two groups cut a
    slice into the same blocks (the engine counts a tick's walk with one
    `q_block`); and 32 pages of 5-D tiles at MiMo's full widths, whose four
    kv heads pad to a sublane tile of 16, reckon OVER the budget: the 17.2 MB
    that PR 33 met on the chip only."""
    from ray_tpu.ops import paged_attention as pa

    assert pa.kv_sizes(32, 8, 128, 128, 16, 2) == pa.KVSizes(64, 16, 16,
                                                            False)
    assert pa.kv_sizes(32, 8, 128, 128, 16, 2).describe()["decode"] \
        == "masked_all_heads"
    full = pa.kv_sizes(64, 4, 192, 128, 16, 2, rows=True)
    window = pa.kv_sizes(64, 8, 192, 128, 16, 2, rows=True, window=128)
    assert full == pa.KVSizes(32, 64, 64, True, (128, 64))
    assert window == pa.KVSizes(32, 16, 16, True, (128, 64))
    assert full.describe() == {"layout": "rows", "decode": "per_head",
                               "q_block": 32, "pages": [64, 64],
                               "k_lanes": [128, 64]}
    # a K tile is reckoned at its true lanes: the rows padded to 256 lanes a
    # head held a sixth more
    padded = pa.kv_vmem_bytes(64, 4, 256, 128, 16, 2, True, 32, 32, 64)
    assert padded - pa.kv_vmem_bytes(64, 4, 192, 128, 16, 2, True, 32, 32,
                                     64) == 2 * 64 * 16 * 4 * 64 * 2
    five_d = lambda pages: pa.kv_vmem_bytes(64, 4, 256, 128, 16, 2, False,
                                            32, pages, pages)
    assert five_d(16) <= pa.KV_VMEM_BUDGET < five_d(32)
    assert pa.kv_sizes(64, 4, 256, 128, 16, 2) == pa.KVSizes(32, 16, 16,
                                                            False)
    # as rows the same 32 pages are a quarter the bytes
    assert pa.kv_vmem_bytes(64, 4, 256, 128, 16, 2, True, 32, 32, 32) \
        <= pa.KV_VMEM_BUDGET


# ---------------------------------------------------------------------------
# Engine layer: the mixed tick against the plain forward pass.
# ---------------------------------------------------------------------------


def _drive(engine):
    """Step until nothing is left; the finished outputs by request id."""
    done = {}
    while engine.has_unfinished():
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out.output_token_ids
    return done


def test_mixed_tick_matches_naive_greedy(setup):
    """Mixed batches (several prompts of different lengths, decode rows and
    prefill slices sharing launches) greedy-decode bit-identically to the
    naive full-forward reference, and every tick is a mixed tick."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    prompts = [[(7 * i + 3) % 128 for i in range(21)],      # 3 chunks
               [1, 5, 9, 2, 11, 3, 8],                      # 1 chunk
               [(3 * i + 2) % 128 for i in range(13)]]      # 2 chunks
    eng = _engine(config, params)
    outs = eng.generate(prompts, SamplingParams(max_tokens=6))
    assert {sig[0] for sig in eng.runner._seen_shapes} == {"mixed"}
    records = eng.tick_records()
    assert records and {r["kind"] for r in records} == {"mixed"}
    assert any(r["decode_rows"] and r["prefill_rows"] for r in records)
    for p, out in zip(prompts, outs):
        assert out.output_token_ids == naive_greedy(params, config, p, 6)


def test_seeded_sampling_replays_identically(setup, pickle_sanitizer):
    """temperature>0: each token's draw is keyed on (seed, absolute
    position) and the seed derives from the request id, so the same request
    id on a fresh engine draws the same tokens, another id does not have
    to — and the steady-state loop never pickles."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    prompts = [[(5 * i + 1) % 128 for i in range(11)],
               [2, 7, 1, 12, 9, 5, 3, 13]]
    sp = SamplingParams(max_tokens=8, temperature=0.8, top_k=20)
    runs = []
    with pickle_sanitizer.window() as w:
        for _ in range(2):
            eng = _engine(config, params)
            for i, p in enumerate(prompts):
                eng.add_request(p, sp, request_id=f"seeded-{i}")
            runs.append(_drive(eng))
    assert runs[0] == runs[1]
    assert all(len(toks) == 8 for toks in runs[0].values())
    assert runs[0]["seeded-0"] != runs[0]["seeded-1"]
    w.assert_zero_pickle()


def naive_host_sampled(params, config, prompt, sp, n_steps):
    """The plain reference of a host-sampled request: the full forward
    pass's last-row logits through `sampling.sample`."""
    import jax.numpy as jnp

    from ray_tpu.llm.sampling import sample
    from ray_tpu.models import llama

    tokens = list(prompt)
    for _ in range(n_steps):
        logits = llama.forward(params, jnp.asarray([tokens], dtype=jnp.int32),
                               config)
        tokens.append(sample(np.asarray(logits[0, -1], np.float32), sp,
                             np.asarray(tokens)))
    return tokens[len(prompt):]


def test_repetition_penalty_rides_the_mixed_tick(setup):
    """A repetition penalty needs host logits: the request rides mixed
    ticks with the logits head, and returns what `sampling.sample` over the
    reference's logits returns."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    prompt = [3, 14, 15, 9, 2, 6, 5]
    sp = SamplingParams(max_tokens=6, repetition_penalty=1.3)
    eng = _engine(config, params)
    out = eng.generate([prompt], sp)[0]
    assert out.output_token_ids == naive_host_sampled(params, config, prompt,
                                                      sp, 6)
    # the penalty changed something: this is not the plain greedy stream
    assert out.output_token_ids != naive_greedy(params, config, prompt, 6)
    records = eng.tick_records()
    assert records and all(r["kind"] == "mixed" and r["host_sampled"]
                           for r in records)
    assert {sig[0] for sig in eng.runner._seen_shapes} == {"mixed_logits"}


def test_penalty_request_beside_plain_requests(setup):
    """One tick carries a penalty request and plain greedy ones: the host
    samples every row of it, and the plain requests' tokens are what they
    are without the neighbour."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    plain = [[1, 5, 9, 2, 11, 3, 8], [(3 * i + 2) % 128 for i in range(13)]]
    pen_prompt = [3, 14, 15, 9, 2, 6, 5]
    pen = SamplingParams(max_tokens=4, repetition_penalty=1.3)
    eng = _engine(config, params)
    for i, p in enumerate(plain):
        eng.add_request(p, SamplingParams(max_tokens=8), request_id=f"plain-{i}")
    eng.add_request(pen_prompt, pen, request_id="pen")
    done = _drive(eng)
    for i, p in enumerate(plain):
        assert done[f"plain-{i}"] == naive_greedy(params, config, p, 8)
    assert done["pen"] == naive_host_sampled(params, config, pen_prompt,
                                             pen, 4)
    records = eng.tick_records()
    assert any(r["host_sampled"] and r["decode_rows"] == 3 for r in records)
    # once the penalty request has finished the device samples again
    assert not records[-1]["host_sampled"]
    assert {r["kind"] for r in records} == {"mixed"}


def test_tick_with_a_penalty_request_proposes_no_drafts(setup):
    """Drafts are verified by the device sampler's head; a tick the host
    samples proposes none, and proposals resume with the next plain tick."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    cyclic = [5, 9, 13, 5, 9, 13, 5, 9, 13, 5, 9]
    eng = _engine(config, params, spec=3)
    eng.add_request(cyclic, SamplingParams(max_tokens=12), request_id="plain")
    eng.add_request(cyclic[1:], SamplingParams(
        max_tokens=3, repetition_penalty=1.3), request_id="pen")
    done = _drive(eng)
    records = eng.tick_records()
    host = [r for r in records if r["host_sampled"]]
    assert host and all(r["spec_tokens"] == 0 for r in host)
    assert sum(r["spec_tokens"] for r in records) > 0
    assert eng.stats()["spec_tokens_proposed"] == sum(
        r["spec_tokens"] for r in records)
    # exact acceptance: the drafts changed no token
    assert done["plain"] == naive_greedy(params, config, cyclic, 12)


@pytest.fixture(scope="module", params=["llama", "latent"])
def family(request, setup):
    """(config, params, greedy reference) of a K/V block and of the latent
    (DeepSeek-V2) block, both tiny and float32."""
    if request.param == "llama":
        config, params = setup
        return config, params, lambda p, n: naive_greedy(params, config, p, n)
    import jax

    from test_llm_deepseek_v2 import sizes_of

    from ray_tpu.models import deepseek_v2
    from ray_tpu.models import deepseek_v2_reference as ref

    config = deepseek_v2.DeepseekV2Config.tiny(experts_held=(0, 8))
    params = deepseek_v2.init_params(config, jax.random.key(0))
    sizes = sizes_of(config)

    def greedy(prompt, n_steps):
        tokens = list(prompt)
        for _ in range(n_steps):
            logits, _ = ref.logits_at(
                params, np.asarray([tokens], np.int32), [len(tokens) - 1],
                sizes)
            tokens.append(int(np.argmax(np.asarray(logits)[0, -1])))
        return tokens[len(prompt):]

    return config, params, greedy


def test_prefill_only_engine_rides_the_mixed_tick(family):
    """The disaggregated prefill tier's engine: every tick is a mixed tick
    with no decode row, finished prefills park in `running`, and what
    export_request hands over adopt_request decodes to the reference's
    greedy tokens."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params, greedy = family
    pre = _engine(config, params, prefill_only=True)
    dec = _engine(config, params)
    prompts = {"po-0": [(7 * i + 3) % 128 for i in range(21)],
               "po-1": [(3 * i + 2) % 128 for i in range(13)]}
    first = {}
    for rid, p in prompts.items():
        pre.add_request(p, SamplingParams(max_tokens=6), request_id=rid)
    while pre.waiting or pre.prefilling:
        for out in pre.step():
            first[out.request_id] = out.output_token_ids
    # The step that holds the last slice is in flight (one step of
    # lookahead): with nothing left to compose, the next call lands it.
    assert pre.has_unfinished() and pre.running[-1].pending == 1
    for out in pre.step():
        first[out.request_id] = out.output_token_ids
    records = pre.tick_records()
    assert records and all(r["kind"] == "mixed" and r["decode_rows"] == 0
                           for r in records)
    assert not records[-1]["lookahead"] and records[-1]["settled"] == "idle"
    assert sorted(r.id for r in pre.running) == sorted(prompts)
    assert pre.step() == [] and len(pre.tick_records()) == len(records)
    for rid in prompts:
        state = pre.export_request(rid)
        blocks = state.pop("blocks")
        pages = pre.runner.gather_pages(blocks)
        pre.block_manager.release_blocks(blocks)
        assert dec.adopt_request(state, *pages)
    assert not pre.has_unfinished() and not pre.block_manager.refcount
    done = _drive(dec)
    for rid, p in prompts.items():
        assert done[rid] == greedy(p, 6) and done[rid][:1] == first[rid]
    assert dec.prefill_tokens_computed == 0
    assert {r["kind"] for r in dec.tick_records()} == {"mixed"}


@pytest.mark.parametrize("stage", ["mid_prefill", "mid_decode"])
def test_abort_frees_the_pages_at_once(setup, stage):
    """abort_request settles the step in flight between two ticks, so an
    aborted request's pages are free (or parked for their prefix) when it
    returns."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    eng = _engine(config, params)
    bm = eng.block_manager
    total = bm._available()
    rid = eng.add_request([(7 * i + 3) % 128 for i in range(21)],
                          SamplingParams(max_tokens=8))
    eng.step()
    if stage == "mid_prefill":
        assert [r.id for r in eng.prefilling] == [rid]
        assert 0 < eng.prefilling[0].prefilled < 21
    else:
        while not eng.running:
            eng.step()
        eng.step()
        eng.step()      # commits lag their dispatch by one call
        assert len(eng.running[0].output) >= 2 and eng.running[0].flying
    assert bm.refcount and bm._available() < total
    assert eng.abort_request(rid)
    assert not bm.refcount and bm._available() == total
    assert not eng.has_unfinished() and not eng.abort_request(rid)


def test_plain_tick_dispatches_the_mixed_program_alone(setup):
    """A tick without a repetition-penalty request runs `_step_mixed_jit`
    with the operands it has had since the mixed tick was written, and the
    two that feed a step's tokens from the samples of the one before (ISSUE
    34), and never the logits head: the program the benchmark's cells run."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    eng = _engine(config, params, spec=2)
    runner = eng.runner
    seen = []
    mixed = runner._step_mixed_jit

    def spy(*args):
        seen.append(args)
        return mixed(*args)

    def never(*args):
        raise AssertionError("a plain tick dispatched the logits head")

    runner._step_mixed_jit = spy
    runner._step_mixed_logits_jit = never
    eng.generate([[1, 5, 9, 2, 11, 3, 8], [(3 * i + 2) % 128
                                           for i in range(13)]],
                 SamplingParams(max_tokens=4, temperature=0.7, seed=3))
    assert seen
    S, W, M = 4, 3, runner.max_blocks_per_seq
    i32, f32 = np.int32, np.float32
    for args in seen:
        assert len(args) == 19
        assert args[0] is runner.params and args[17] == {} \
            and args[18] is None
        Tb = args[2].shape[0]
        assert list(args[8]) == ["all"]     # one block table a layer group
        args = args[:8] + (args[8]["all"],) + args[9:]
        assert [(a.shape, a.dtype) for a in args[2:17]] == [
            ((Tb,), i32),           # tokens
            ((S, W), i32),          # prev_samples
            ((Tb,), i32),           # token_src
            ((S,), i32),            # q_positions
            ((S,), i32),            # kv_lens
            ((S + 1,), i32),        # cu_q_lens
            ((S, M), i32),          # block_tables
            ((S, W), i32),          # out_rows
            ((S, W), i32),          # proposals
            ((S,), i32),            # prop_lens
            ((S,), f32),            # temps
            ((S,), i32),            # top_ks
            ((S,), f32),            # top_ps
            ((S,), i32),            # seeds
            ((S,), i32),            # counters
        ]


@pytest.mark.parametrize("where,keyword", [
    ("LLMConfig", "unified_ticks"), ("LLMConfig", "decode_multi_step"),
    ("LLMEngine", "unified_ticks"), ("LLMEngine", "decode_multi_step"),
    ("LLMEngine", "pipeline_depth")])
def test_removed_options_are_refused_by_name(setup, where, keyword):
    """The options that selected the split path are gone (PR 31): passing
    one is a TypeError that names it, not a silent no-op."""
    from ray_tpu.llm.engine import LLMEngine
    from ray_tpu.llm.model_runner import ModelRunner
    from ray_tpu.llm.serving import LLMConfig

    config, params = setup
    with pytest.raises(TypeError, match=keyword):
        if where == "LLMConfig":
            LLMConfig(model_config=config, **{keyword: 1})
        else:
            LLMEngine(ModelRunner(config, params, num_blocks=8, block_size=8),
                      **{keyword: 1})


# ---------------------------------------------------------------------------
# Speculation: seeded acceptance sampling replays deterministically.
# ---------------------------------------------------------------------------


def test_spec_acceptance_sampling_replays_identically(setup):
    """n-gram drafts + temperature>0 acceptance sampling: accept/reject
    draws key on (crc32-derived seed, absolute token index) and drafts are
    a pure function of sequence history, so a fresh engine replaying the
    same request reproduces the trajectory token for token."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    prompt = [5, 9, 13, 5, 9, 13, 5, 9, 13, 5, 9]
    sp = SamplingParams(max_tokens=12, temperature=0.7, seed=42)
    runs = []
    for _ in range(2):
        eng = _engine(config, params, spec=3)
        out = eng.generate([prompt], sp)[0]
        runs.append((out.output_token_ids, eng.stats()))
    assert runs[0][0] == runs[1][0]
    s = runs[0][1]
    assert s["spec_tokens_proposed"] > 0, s    # drafts actually launched
    assert s["spec_tokens_proposed"] == runs[1][1]["spec_tokens_proposed"]
    assert s["spec_tokens_accepted"] == runs[1][1]["spec_tokens_accepted"]


def test_spec_greedy_accepts_model_continuation(setup):
    """Force-feed the verifier the model's own greedy continuation as the
    draft: every draft token must be accepted (greedy accept rule is
    proposal == argmax), proving the accept branch end to end."""
    import zlib

    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    prompt = [1, 5, 9, 2, 11, 3, 8]
    cont = naive_greedy(params, config, prompt, 4)
    eng = _engine(config, params, spec=3)
    eng._ngram_propose = lambda context, k, n=3: list(
        cont[len(context) - len(prompt):len(context) - len(prompt) + k])
    out = eng.generate([prompt], SamplingParams(max_tokens=4))[0]
    assert out.output_token_ids == cont
    s = eng.stats()
    assert s["spec_tokens_accepted"] > 0, s
    assert s["spec_tokens_accepted"] <= s["spec_tokens_proposed"]
    # Seed bookkeeping: derived from crc32(request_id) when not supplied.
    rid = out.request_id
    assert isinstance(zlib.crc32(rid.encode()) & 0x7FFFFFFF, int)


# ---------------------------------------------------------------------------
# Compile discipline: warmed T-ladder, zero steady-state recompiles.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("penalty", [1.0, 1.3],
                         ids=["device_sampled", "host_sampled"])
def test_steady_state_zero_recompiles_after_warmup(setup, penalty):
    """warmup(full=True) precompiles the token-bucket ladder under both
    heads; serving traffic that stays inside warmed buckets, a request with
    a repetition penalty among it, must never trigger another compile (the
    silent-recompile stall the step_compiles counter exists to catch)."""
    from ray_tpu.llm.sampling import SamplingParams

    config, params = setup
    eng = _engine(config, params)
    eng.warmup(full=True)
    warm = eng.stats()["step_compiles"]
    assert warm > 0
    eng.generate([[(7 * i + 3) % 128 for i in range(21)],
                  [1, 5, 9, 2], [2, 7, 1, 12, 9]],
                 SamplingParams(max_tokens=6, repetition_penalty=penalty))
    eng.generate([[4, 4, 8], [9, 1, 1, 2, 3, 5, 8, 13]],
                 SamplingParams(max_tokens=4, temperature=0.9, seed=7))
    assert eng.stats()["step_compiles"] == warm, \
        "steady-state traffic recompiled after warmup"
    assert not any(r["recompile"] for r in eng.tick_records())


def test_spec_counters_roll_into_summary(setup):
    """ray_tpu_llm_spec_* counters ride the standard metric defs, so the
    cluster summary's llm_serving rollup picks them up without plumbing."""
    from ray_tpu.runtime import metric_defs as md

    names = {m._name for m in md.ALL_METRICS}
    assert "ray_tpu_llm_spec_proposed_total" in names
    assert "ray_tpu_llm_spec_accepted_total" in names
    assert "ray_tpu_llm_step_compiles_total" in names
