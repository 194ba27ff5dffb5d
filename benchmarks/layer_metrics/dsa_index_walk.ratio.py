"""Model step: how often the indexer fetches an index key it must read once:
`dsa_index_walked_rows` (the index keys a "full" layer's walks fetch under the
index kernel's plan: a page run that several of a step's rows hold in the same
places of their tables is walked once for all of them; a slice's blocks each
walk their own part again) over `dsa_index_rows` (the sum of the rows'
contexts: every key once a ROW), both counted by the block's `tick_counts`,
summed over the window's ticks. 1.0: every row walks its context alone, once;
under 1 where rows share a document. None where the program keeps no such
count: which is why PR 51 handed this file in with no `per_layer` entry (the
parent has no such count, and `run.py` calls a run incorrect where a listed
reader finds nothing); a PR whose parent keeps the count can list it
(`PERF.md` section 7 has the entry)."""


def read(run):
    ticks = [t for t in run.window_ticks()
             if "dsa_index_walked_rows" in t and t.get("dsa_index_rows")]
    rows = sum(t["dsa_index_rows"] for t in ticks)
    return (sum(t["dsa_index_walked_rows"] for t in ticks) / rows
            if rows else None)
