"""How `_kv_rows_kernel` starts a tile's page DMAs (ops/paged_attention.py,
`start_tile`; PR 65): a block of one token starts PAGE_RUN pages a loop step,
their starts unrolled, then the rest one by one, whole tile or ragged. Decode
rows whose contexts lie on and around the tile boundary, through the
interpreted kernel against the jnp references, in the four forms the
benchmark's cells run: plain rows, MiMo-V2-Flash's split rows with a sink, the
window form over a ring table, and `block_sparse.block_attend_call`'s table
form (every (row, kv head) a sequence of its own over its kept pages).

The guard: wherever a table names no page of the row (behind its last page,
outside its window's ring, a block it does not keep) the kernel's pool holds
a page of NaN. The scores of a tile's rows past the context are masked, but
the second product multiplies them by zero: a NaN row in scratch shows. So a
run of starts that overshoots a tile's last real page fails here.

A file of its own and not beside the row kernel's other cases: their worker
(tests/test_llm_unified.py) stands near `vm.max_map_count` as it is (the
verify skill's note), as PR 59's cases run from tests/test_llm_afmoe.py.

Pages of 8 tokens, float32. Tolerance as the row kernel's other cases."""

import numpy as np
import pytest

import ray_tpu  # noqa: F401

PS, POOL, LAYERS, LAYER = 8, 96, 2, 1
NAN_PAGE = 0        # what a table names where the row has no page

# kv heads, query heads, head widths, pages a step of a block of one token;
# with `window` (pages) the tables are rings `ring` pages wide.
FORMS = {
    "plain_rows": dict(K=8, H=16, hd=128, vd=128, pages_one=16),
    "split_rows_sink": dict(K=4, H=8, hd=192, vd=128, pages_one=64,
                            sink=True),
    "window_ring": dict(K=2, H=4, hd=128, vd=128, pages_one=16, window=41,
                        ring=45),
    "table": dict(K=2, H=4, hd=128, vd=128, pages_one=64),
}
BLOCK = 4 * PS      # the table form's block: four pages
TOPK = 64           # and the most blocks a row keeps: 256 pages


def contexts(tiles: int, pages_one: int, form: str):
    """Context lengths in tokens: `tiles` whole tiles and that -1 token, +1
    token, -1 page, +1 page; a context under one tile that ends inside a
    page; 8 and 9 pages (the counted loop's run, and its rest). The table
    form also 256 kept pages (four whole tiles of 64) and 253."""
    whole = tiles * pages_one * PS
    out = [whole, whole - 1, whole + 1, whole - PS, whole + PS,
           5 * PS - 3, 8 * PS, 9 * PS]
    if form == "table":
        out += [256 * PS, 253 * PS]
    return out


def _pools(rng, f):
    """(K pool as the row lies, V pool, the same with NaN_PAGE finite): row
    pools of POOL pages."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    row = pa.k_row(f["K"], f["hd"])
    k = rng.standard_normal((LAYERS, POOL, PS, f["K"], f["hd"]))
    v = rng.standard_normal((LAYERS, POOL, PS, f["K"] * f["vd"]))
    k = np.asarray(row.lay(jnp.asarray(k, jnp.float32)))
    v = v.astype(np.float32)
    bad_k, bad_v = k.copy(), v.copy()
    bad_k[:, NAN_PAGE] = bad_v[:, NAN_PAGE] = np.nan
    return row, (k, v), (bad_k, bad_v)


def _unified(monkeypatch, f, lens, rng):
    """The token-major entry over decode rows of contexts `lens`: (kernel's
    output over the pool with the NaN page, the reference's over the same
    tables and a finite page there)."""
    import jax.numpy as jnp

    from ray_tpu.ops import paged_attention as pa

    monkeypatch.setattr(pa, "kv_sizes", lambda *a, **kw: pa.KVSizes(
        8, f["pages_one"], f["pages_one"], True))
    S = len(lens)
    row, clean, bad = _pools(rng, f)
    lens = np.asarray(lens, np.int32)
    q_pos = lens - 1
    pages = -(-lens // PS)
    window = f.get("window") and f["window"] * PS - 3
    width = f.get("ring") or int(pages.max()) + 2
    tables = np.full((S, width), NAN_PAGE, np.int32)
    for s in range(S):
        first = 0 if not window else max(0, int(q_pos[s]) - (window - 1)) // PS
        for p in range(first, int(pages[s])):
            tables[s, p % width] = rng.integers(1, POOL)
    q = row.queries(jnp.asarray(
        rng.standard_normal((S, f["H"], f["hd"])), jnp.float32))
    kw = dict(kv_heads=f["K"], scale=f["hd"] ** -0.5)
    if window:
        kw["window"] = window
    if f.get("sink"):
        kw["sink"] = jnp.asarray(rng.standard_normal(f["H"]), jnp.float32)
    rest = (jnp.int32(LAYER), jnp.asarray(tables), jnp.asarray(lens),
            jnp.asarray(q_pos), jnp.arange(S + 1, dtype=jnp.int32))
    out = pa.ragged_paged_attention_unified(
        q, *map(jnp.asarray, bad), *rest, **kw)
    ref = pa.ragged_paged_attention_unified_reference(
        q, *map(jnp.asarray, clean), *rest, **kw)
    return np.asarray(out), np.asarray(ref)


def _table(f, lens, rng):
    """`block_attend_call` over rows that keep ceil(n / BLOCK) blocks each,
    their own the last and n tokens in all: as `_unified`."""
    import jax.numpy as jnp

    from ray_tpu.ops import block_sparse as bs
    from ray_tpu.ops import paged_attention as pa

    K, H, hd = f["K"], f["H"], f["hd"]
    assert pa.kv_sizes(H // K, 1, K * hd, K * f["vd"], PS, 4,
                       rows=True).pages_one == f["pages_one"]
    S, B = len(lens), BLOCK // PS
    _, clean, bad = _pools(rng, f)
    count = -(-np.asarray(lens) // BLOCK)               # blocks a row keeps
    own = count + 5                                     # its own block
    positions = own * BLOCK + (np.asarray(lens) - 1) % BLOCK
    NBLK = int(own.max()) + 2                           # the last: no page
    tables = rng.integers(1, POOL, (S, NBLK * B)).astype(np.int32)
    tables[:, -B:] = NAN_PAGE
    blocks = np.full((S, K, TOPK), NBLK - 1, np.int32)
    for s in range(S):
        tables[s, positions[s] // PS + 1:] = NAN_PAGE   # behind its last page
        for kh in range(K):
            blocks[s, kh, :count[s] - 1] = np.sort(rng.choice(
                own[s], count[s] - 1, replace=False))
            blocks[s, kh, count[s] - 1] = own[s]
    counts = np.repeat(count[:, None], K, axis=1).astype(np.int32)
    q = jnp.asarray(rng.standard_normal((S, H, hd)), jnp.float32)
    positions = jnp.asarray(positions, jnp.int32)
    kw = dict(kv_heads=K, scale=hd ** -0.5, block=BLOCK)
    out = bs.block_attend_call(
        q, *map(jnp.asarray, bad), jnp.int32(LAYER), jnp.asarray(tables),
        jnp.arange(S, dtype=jnp.int32), jnp.ones((S,), bool), positions,
        jnp.asarray(blocks), jnp.asarray(counts), interpret=True, **kw)
    keep = bs.keep_bits(jnp.asarray(blocks), jnp.asarray(counts), positions,
                        jnp.ones((S,), bool), BLOCK, NBLK)
    ref = bs.block_attend_reference(
        q, *map(jnp.asarray, clean), jnp.int32(LAYER), jnp.asarray(tables),
        positions + 1, positions, jnp.arange(S + 1, dtype=jnp.int32), keep,
        **kw)
    return np.asarray(out), np.asarray(ref)


@pytest.mark.parametrize("tiles", [1, 2, 3])
@pytest.mark.parametrize("form", sorted(FORMS))
def test_decode_rows_across_the_tile_boundary(cpu_jax, monkeypatch, form,
                                              tiles):
    f = FORMS[form]
    rng = np.random.default_rng(tiles + len(form))
    lens = contexts(tiles, f["pages_one"], form)
    out, ref = (_table(f, lens, rng) if form == "table"
                else _unified(monkeypatch, f, lens, rng))
    assert np.isfinite(ref).all()
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
