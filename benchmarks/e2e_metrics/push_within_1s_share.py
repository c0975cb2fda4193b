"""Share of the window's OTLP pushes acknowledged with 200 within 1 s of the
instant each was due (open loop): the limit `find_within_1s_share` holds the
reads of the same deployment to. A refused, failed or unacknowledged push is
not within it. It sees acknowledgements stalling (one cut under the
instance lock held every push for ~4 s until PR 37: 10-12 % of a window's
pushes were later than 1 s); it does not see a tail that doubles below 1 s:
`push_ack_p95_ms` / `push_ack_p99_ms` are read beside it, and the median
(`push_ack_p50_ms`) is judged beside it."""
from benchmarks.lib import readers as R

WITHIN_S = 1.0


def read(ctx):
    res = R.by_role(ctx, "ingest")
    if not res:
        return None
    met = sum(1 for r in res if R.good(r) and r["t_done"] - r["t_due"] <= WITHIN_S)
    return 100.0 * met / len(res)
