"""What one search costs its own threads: CPU (thread_time) of the outermost
stages of a search's own threads -- the handlers' `http:search` /
`http:metrics`, every job's `run:search_*` / `run:metrics_query_range` in
the process that ran it, and on a tree the wire's `job:encode` /
`job:decode` -- over the searches served in the window (`http:search` +
`http:metrics` counts). It is NOT a capacity of one interpreter: most of it
is native code that released the GIL (zstd, ranged reads: several cores at
once in one process), it grows with contention (120 -> 176 ms from 1 client
to 4 on one traffic, PERF.md section 6, PR 38), and work a job hands to a
pool (a `rate()`'s `block:metrics`) is in those stages' own rows, not here.
Whether the interpreter is the limit is read from `gil_wait_ms` and
`job_oncpu_share`."""
from benchmarks.lib import cpu


def read(ctx):
    return cpu.cpu_ms_per(ctx, cpu.SEARCH_HTTP + cpu.SEARCH_RUNS + cpu.WIRE,
                          cpu.SEARCH_HTTP)
