"""95th percentile of the OTLP push acknowledgement, from the instant each
request was due (open loop); a refused or failed push is beyond every
percentile. The highest percentile a window supports (~611 pushes leave 30
samples beyond it, `stats.supported_percentile`: at least ten; the 99th
leaves 6); it still spreads 19-37 % from run to run (PERF.md section 2)."""
from benchmarks.lib import readers as R


def read(ctx):
    return R.pct_ms(ctx, "ingest", 0.95, from_due=True, completed_only=False)
