"""Jobs the frontend built per request over the blocklist, inside the window:
`range.jobs` over `range.searches` of /status/kernels (`record_range`: one
call a search or `rate()` request, with the block-batch, row-group-shard or
time-shard jobs built for it; the ingester leg is not counted). Nothing where
the program has no such counter."""
from benchmarks.lib import readers as R


def read(ctx):
    jobs = R.delta(ctx, "range", "jobs")
    searches = R.delta(ctx, "range", "searches")
    return jobs / searches if jobs is not None and searches else None
