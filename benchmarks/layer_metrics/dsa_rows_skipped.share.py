"""Model step: the share of a tick's query-context pairs that the selection
keeps out of the latent layers: 100 x (1 - `dsa_pairs` / `attn_pairs`), both
counted by `_mixed_tick` (`attn_pairs`: the causal pairs a dense layer would
cover; `dsa_pairs`: min(position + 1, index_topk) a query token, the block's
`tick_counts`), summed over the window's ticks. 0 where every context fits
index_topk; ~94% at 33k-35k rows of context and a selection of 2,048. None
where the program keeps no such count."""


def read(run):
    ticks = [t for t in run.window_ticks()
             if "dsa_pairs" in t and t.get("attn_pairs")]
    dense = sum(t["attn_pairs"] for t in ticks)
    return (100.0 * (1.0 - sum(t["dsa_pairs"] for t in ticks) / dense)
            if dense else None)
