"""Median latency of the one closed-loop search client beside the finds."""
from benchmarks.lib import readers as R


def read(ctx):
    return R.pct_ms(ctx, "search_beside", 0.5, from_due=False, completed_only=True)
