"""Of the time a search's jobs held a worker thread, the share that thread
was on a CPU: `cpu_seconds` over `seconds` of the `run:search_*` and
`run:metrics_query_range` rows in the window. The rest the thread was
runnable or blocked: waiting for the GIL, a lock, a read, the device (sync
timing waits for a launch inside the stage) or the pool threads it handed
blocks to (a `rate()`, a block-batch job). Read it against itself: between
two runs of one traffic the structure is the same and the difference is
contention (benchmarks/INTERP.md)."""
from benchmarks.lib import cpu


def read(ctx):
    return cpu.oncpu_share(ctx, cpu.SEARCH_RUNS)
