"""99th percentile of the OTLP push acknowledgement, from the instant each
request was due (open loop); a refused or failed push is beyond every
percentile. The 6th-worst of ~611 pushes a window: it spreads 18-35 % from
run to run (PERF.md section 2), which is why the cell is judged by the
median (`push_ack_p50_ms`) and by `push_within_1s_share`, and this tail is
read beside them (PR 40)."""
from benchmarks.lib import readers as R


def read(ctx):
    return R.pct_ms(ctx, "ingest", 0.99, from_due=True, completed_only=False)
