"""How long one block cut holds the ingester instance's lock: the
`ingest:swap` stage (PR 37: the cut set moves to `flushing`, the WAL head
rotates, the carried live traces are fsynced into the new head) per flush in
the window. Every push acknowledgement and every find's ingester leg waits
on that lock. Nothing on a program without the stage: the parent of PR 37
held the lock for all of `ingest:cut` and says so nowhere."""
from benchmarks.lib import stages


def read(ctx):
    return stages.ms_per(ctx, ("ingest:swap",), "ingest:flush")
