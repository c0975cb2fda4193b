"""What a staged lookup that missed costs the host: wall time, inside the
window, of the stages that bring a block's columns from the backend to the
device -- `stage:read_columns` (ranged reads and decompression),
`stage:assemble`, `stage:upload` of ops/stage and, where a job streamed its
block in chunks, `stream:fetch` + `stream:decompress` around them -- over the
lookups that missed (`staging.cache_misses`). A hit's lookup adds only its
view's `stage:assemble`, microseconds. Nothing where no lookup missed."""
from benchmarks.lib import readers as R
from benchmarks.lib import stages

NAMES = ("stage:read_columns", "stage:assemble", "stage:upload",
         "stream:fetch", "stream:decompress")


def read(ctx):
    misses = R.delta(ctx, "staging", "cache_misses")
    parts = [p for p in (stages.delta(ctx, n) for n in NAMES) if p is not None]
    if not misses or not parts:
        return None
    return sum(p[0] for p in parts) * 1e3 / misses
