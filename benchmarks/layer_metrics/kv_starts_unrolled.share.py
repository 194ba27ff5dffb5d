"""Model step, paged kernels: the share of the pages a tick's query blocks
walk whose DMAs the row kernel starts UNROLLED, a run of eight a loop step
before the step's wait (ops/paged_attention.py, `start_counted` under
`_kv_rows_kernel.start_tile`, PR 65): `kv_pages_unrolled` (of the pages that
blocks of ONE token walk through a row pool's full form, those started in
runs: all but the last few of a ragged tile) over `kv_pages_walked` (every
block's pages, a slice's blocks of many too, which start a page a turn), both
by the kernel's own arithmetic in `LLMEngine._kernel_walk`; summed over the
window's ticks, in percent. `samples` are the ticks' own shares, for the
run's notes. None where the program keeps no such count (older than PR 65):
no `per_layer` entry yet, a listed reader of a field its own PR adds fails the
parent's runs (PERF.md section 7 has the entry that waits)."""


def _ticks(run):
    return [t for t in run.window_ticks()
            if "kv_pages_unrolled" in t and t.get("kv_pages_walked")]


def samples(run):
    return [100.0 * t["kv_pages_unrolled"] / t["kv_pages_walked"]
            for t in _ticks(run)]


def read(run):
    ticks = _ticks(run)
    if not ticks:
        return None
    return (100.0 * sum(t["kv_pages_unrolled"] for t in ticks)
            / sum(t["kv_pages_walked"] for t in ticks))
