"""Engine admission: the most the window groups' pools held at once, over
their size. `window_pool_used` of a tick (the window groups' pages live or
parked when the tick was composed), its maximum over the window's ticks, over
the groups' `total` in `engine.stats()["kv_groups"]`, in percent. The pool
is sized by a rule of the program (`model_runner.window_group_pages`: what
`max_batch` rings hold live and as much again for the tails cached prefixes
park, never more than the `all` group has): this says what the rule is worth
at a window where it costs gigabytes. Parked pages count as used: a pool
that reads 100% recycles parked tails to serve live rings. None where the
program keeps no such count (no window group, an older program)."""


def read(run):
    used = [t["window_pool_used"] for t in run.window_ticks()
            if "window_pool_used" in t]
    groups = run.stats_after.get("kv_groups", {})
    total = sum(g["total"] for name, g in groups.items()
                if name not in ("all", "state"))
    return 100.0 * max(used) / total if used and total else None
