"""Queries per fused group of the search batch window, inside the window."""
from benchmarks.lib import readers as R


def read(ctx):
    groups = R.delta(ctx, "batching", "search", "groups")
    queries = R.delta(ctx, "batching", "search", "queries")
    return queries / groups if groups else None
