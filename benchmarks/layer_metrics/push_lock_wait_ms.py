"""Time push handlers waited for the ingester instance's lock
(`ingest:lock_wait`) per push acknowledged in the window: what the
acknowledgements behind a cut or a decode were waiting on."""
from benchmarks.lib import readers as R, stages


def read(ctx):
    d = stages.delta(ctx, "ingest:lock_wait")
    acked = sum(1 for r in R.by_role(ctx, "ingest") if r["status"] == 200)
    return d[0] * 1e3 / acked if d is not None and acked else None
