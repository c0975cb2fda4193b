"""Programs the XLA compiler had to build inside the window (misses of the
persistent compile cache): must be 0."""
from benchmarks.lib import readers as R


def read(ctx):
    return R.delta(ctx, "compile_cache", "disk_misses")
