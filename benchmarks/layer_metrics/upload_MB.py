"""Bytes moved host -> device by staging, inside the window."""
from benchmarks.lib import readers as R


def read(ctx):
    b = R.delta(ctx, "staging", "transfer_bytes_total")
    return None if b is None else b / 1e6
