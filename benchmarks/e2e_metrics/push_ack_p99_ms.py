"""99th percentile of the OTLP push acknowledgement, from the instant each
request was due (open loop); a refused or failed push is beyond every
percentile."""
from benchmarks.lib import readers as R


def read(ctx):
    return R.pct_ms(ctx, "ingest", 0.99, from_due=True, completed_only=False)
