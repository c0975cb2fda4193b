"""Host time of one block cut: the `ingest:cut` stage (cut set -> traces, WAL
rotation, under the instance lock) plus `ingest:flush` (build + write the
block; the `cut:*` stages nest inside it), per flush in the window."""
from benchmarks.lib import stages


def read(ctx):
    return stages.ms_per(ctx, ("ingest:cut", "ingest:flush"), "ingest:flush")
