"""Host work an eviction costs the lookup beside it: wall time of the
`stage:demote` stage (ops/stage._drain_demotions: the device -> host pull and
the codec of every column an eviction pass parked for the host chunk pool, on
the thread of whichever lookup admitted a column) inside the window, over the
columns evicted in it (`staged_cache.evictions`). Nothing where the program
has neither, or where nothing was evicted."""
from benchmarks.lib import readers as R
from benchmarks.lib import stages


def read(ctx):
    demote = stages.delta(ctx, "stage:demote")
    evictions = R.delta(ctx, "staged_cache", "evictions")
    if demote is None or not evictions:
        return None
    return demote[0] * 1e3 / evictions
