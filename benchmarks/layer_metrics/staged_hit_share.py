"""Staged-cache hits over hits + misses, inside the window."""
from benchmarks.lib import readers as R


def read(ctx):
    h = R.delta(ctx, "staging", "cache_hits")
    m = R.delta(ctx, "staging", "cache_misses")
    return 100.0 * h / (h + m) if h is not None and m is not None and h + m else None
