"""Model step: the share of its rows' pages a window layer's walk leaves
unread. 1 - `window_pages_walked` (pages one window layer's walk starts a DMA
for: a query block starts at the page that holds its first token's oldest
visible position) over `kv_pages_walked` (the pages the same query blocks
walk in a layer that sees every token), both by the kernel's own arithmetic in
`_mixed_tick`; mean over the window's ticks, in percent. None where the
program keeps no such count (a model without window layers, an older
program)."""


def read(run):
    xs = [100.0 * (1.0 - t["window_pages_walked"] / t["kv_pages_walked"])
          for t in run.window_ticks()
          if "window_pages_walked" in t and t.get("kv_pages_walked")]
    return sum(xs) / len(xs) if xs else None
