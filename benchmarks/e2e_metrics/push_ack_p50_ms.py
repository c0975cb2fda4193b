"""Median of the OTLP push acknowledgement, from the instant each request
was due (open loop); a refused or failed push is beyond every percentile.
What an exporter's ordinary push waits: the handler's decode, the instance
lock, the WAL append and its group fsync. It does not see the pushes beside
the cut (a tenth of a window's): `push_within_1s_share` is judged beside it
for a stall, `push_ack_p95_ms` / `push_ack_p99_ms` are read for the tail,
which spreads too widely between runs to hold a bound (PERF.md section 2)."""
from benchmarks.lib import readers as R


def read(ctx):
    return R.pct_ms(ctx, "ingest", 0.5, from_due=True, completed_only=False)
