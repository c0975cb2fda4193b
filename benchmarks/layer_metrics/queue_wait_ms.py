"""Median self time of the frontend's queue-wait spans (search and metrics
queries), read back from the self tenant."""
from benchmarks.lib import readers as R, stats

ROOTS = ("frontend.search", "frontend.metrics_query_range")


def read(ctx):
    return stats.percentile([ms for _, ms in R.spans_named(ctx, "queue-wait", ROOTS)], 0.5)
