"""Host time in the ingest `wal_append` stage per MB acknowledged in the window."""
from benchmarks.layer_metrics import decode_ms_per_MB


def read(ctx):
    return decode_ms_per_MB.read(ctx, stage="wal_append")
