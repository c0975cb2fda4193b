"""Spans of the blocks each correctly answered judged search covered (by the
corpus manifest), summed over those completed in the window, per second."""
from benchmarks.lib import readers as R


def read(ctx):
    res = R.completed_in_window(ctx, R.by_role(ctx, "search"))
    if not res:
        return None
    return sum(ctx["env"].spans_covered(r["op"]) for r in res
               if R.good(r)) / ctx["seconds"]
