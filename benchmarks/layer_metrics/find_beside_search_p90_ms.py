"""p90, from the due time, of the open-loop find stream that runs beside the
judged stream of the cell."""
from benchmarks.lib import readers as R


def read(ctx):
    return R.pct_ms(ctx, "find_beside", 0.9, from_due=True, completed_only=False)
