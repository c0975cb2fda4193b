"""90th percentile of trace-by-id latency, from the instant each request was
due (open loop); a failed request is beyond every percentile. In a traced
run: of the finds due a second or more before the profiler's session
(readers.untraced)."""
from benchmarks.lib import readers as R


def read(ctx):
    return R.pct_ms(ctx, "find", 0.9, from_due=True, completed_only=False,
                    untraced_only=True)
