"""Host time in the ingest `decode` stage per MB acknowledged in the window."""
from benchmarks.lib import readers as R


def read(ctx, stage="decode"):
    s = R.delta(ctx, "ingest", "stages", stage, "seconds")
    mb = sum(r["body_bytes"] for r in R.by_role(ctx, "ingest")
             if r["status"] == 200) / 1e6
    return s * 1e3 / mb if s is not None and mb else None
