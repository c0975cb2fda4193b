"""Device time of the trace-by-id programs (mesh_find on a mesh, the device
find kernel) per judged find completed in the traced interval. Nothing where
routing answered every find on the host."""
from benchmarks.lib import readers as R


def read(ctx):
    secs = R.family_seconds(ctx, "find_path")
    n = len(R.in_trace(ctx, R.by_role(ctx, "find")))
    return secs * 1e3 / n if secs and n else None
