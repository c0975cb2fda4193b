"""Host time of one block cut: the `ingest:cut` stage (the cut snapshot
decoded to traces) plus `ingest:flush` (build + write the block; the `cut:*`
stages nest inside it), per flush in the window. Since PR 37 both run
outside the instance lock, beside the pushes; what a cut still does under
the lock is `ingest:swap` (`cut_lock_hold_ms`)."""
from benchmarks.lib import stages


def read(ctx):
    return stages.ms_per(ctx, ("ingest:cut", "ingest:flush"), "ingest:flush")
