"""Of the columns a staged lookup missed on the device inside the window,
the share the host chunk pool gave back: `caching.chunk_pool.hits` over
`hits` + `misses` of /status/kernels (`ops/chunkpool.restage`: a hit is a
column decompressed and uploaded from the pool, a miss one that fell through
to the backend read, assemble and upload). What demoting evicted columns to
host memory buys. Nothing where no column was missed."""
from benchmarks.lib import readers as R


def read(ctx):
    hits = R.delta(ctx, "caching", "chunk_pool", "hits")
    misses = R.delta(ctx, "caching", "chunk_pool", "misses")
    if hits is None or misses is None or hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
