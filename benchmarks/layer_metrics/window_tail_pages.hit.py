"""Engine admission: window-group pages a prefix hit attaches. The window's
sum of `window_tail_pages` (the pages of every window group that hits
attached since the record before: `BlockManager.match_prefix` holds a hit's
tail, the pages that cover [b - window, b) behind its page boundary b) over
the prefix hits in the window (`prefix_hits` of `engine.stats()` at both
ends). A page of a group holds all its layers, so a whole tail is window /
page size pages: 256 at a 4,096-token window, fewer where a prompt is shorter
than the window; a hit whose tail was recycled is cut short
(`prefix_hits_cut_short`) and attaches less. None where the program keeps no
such count (no window group, an older program) or the window saw no hit."""


def read(run):
    ticks = [t for t in run.window_ticks() if "window_tail_pages" in t]
    hits = (run.stats_after.get("prefix_hits", 0)
            - run.stats_before.get("prefix_hits", 0))
    if not ticks or hits <= 0:
        return None
    return sum(t["window_tail_pages"] for t in ticks) / hits
