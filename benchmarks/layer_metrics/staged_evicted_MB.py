"""Device bytes the staged-column cache's LRU evicted inside the window, in
MB: `staged_cache.evicted_bytes` of /status/kernels (one increment a column
`ops/stage._evict_over_budget_locked` pops to get back under the budget;
summed over a tree). With `upload_MB` beside it: a working set larger than
the budget cycles through it. Nothing where the program has no such
counter."""
from benchmarks.lib import readers as R


def read(ctx):
    b = R.delta(ctx, "staged_cache", "evicted_bytes")
    return None if b is None else b / 1e6
