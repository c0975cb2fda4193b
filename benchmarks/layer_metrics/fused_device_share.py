"""Share of the window's block decisions on the multi-block path that chose
the device: the `search_fused` rows (one a block of a block-batch job,
db/route.route_fused) and the `metrics` rows (one a block of a `rate()` time
shard, route_metrics) of the `routing` counter. `device_route_share` reads
`search_block` + `metrics` and so cannot see a fused group leave the chip.
A `fallback` row (the group went back to block-by-block search) is not a
block decision and is left out. Nothing where no such decision was made."""
from benchmarks.lib import readers as R


def read(ctx):
    rows = {k: v for k, v in R.routing_delta(ctx).items()
            if k[0] in ("search_fused", "metrics") and k[1] != "fallback"}
    total = sum(rows.values())
    if not total:
        return None
    return 100.0 * sum(v for k, v in rows.items() if k[1] == "device") / total
