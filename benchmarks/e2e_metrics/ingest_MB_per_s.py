"""OTLP/HTTP protobuf bytes acknowledged with 200 inside the window, per
second of window."""
from benchmarks.lib import readers as R


def read(ctx):
    res = R.completed_in_window(ctx, R.by_role(ctx, "ingest"))
    if not res:
        return None
    return sum(r["body_bytes"] for r in res if r["status"] == 200) / 1e6 / ctx["seconds"]
