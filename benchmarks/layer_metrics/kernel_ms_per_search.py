"""Device time of the search path's modules in the traced interval, over the
judged searches completed in it."""
from benchmarks.lib import readers as R


def read(ctx):
    secs = R.family_seconds(ctx, "search_path")
    n = len(R.in_trace(ctx, R.by_role(ctx, "search")))
    return secs * 1e3 / n if secs is not None and n else None
