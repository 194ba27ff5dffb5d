"""Model step: the share of a tick's query-context pairs that the selection
keeps out of the sparse layers: 100 x (1 - `block_pairs` / `attn_pairs`),
both counted by `_mixed_tick` (`attn_pairs`: the causal pairs a dense layer
would cover; `block_pairs`: a token's pairs inside min(its blocks, topk)
blocks, or all where it sees no more than `dense_len`: the block's
`tick_counts`), summed over the window's ticks. 0 where every context fits
`dense_len`; ~88% at 33k-35k tokens of context and 64 blocks of 64 kept.
None where the program keeps no such count."""


def read(run):
    ticks = [t for t in run.window_ticks()
             if "block_pairs" in t and t.get("attn_pairs")]
    dense = sum(t["attn_pairs"] for t in ticks)
    return (100.0 * (1.0 - sum(t["block_pairs"] for t in ticks) / dense)
            if dense else None)
