"""Share of the window's search_block and metrics routing decisions that
chose the device."""
from benchmarks.lib import readers as R


def read(ctx):
    rows = {k: v for k, v in R.routing_delta(ctx).items()
            if k[0] in ("search_block", "metrics")}
    total = sum(rows.values())
    if not total:
        return None
    return 100.0 * sum(v for k, v in rows.items() if k[1] == "device") / total
