"""Median latency (send to last byte) of the judged search-class requests
completed in the window."""
from benchmarks.lib import readers as R


def read(ctx):
    return R.pct_ms(ctx, "search", 0.5, from_due=False, completed_only=True)
