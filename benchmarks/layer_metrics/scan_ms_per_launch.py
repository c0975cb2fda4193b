"""Device time of one `filter` scan: the XLA modules that the runtime's flow
ids lead to from `tempo/kernel:launch{op=filter}` annotations in the traced
interval, per launch (benchmarks/lib/launches.py reads the trace file in a
process of its own). Nothing where the program writes no such annotation."""
from benchmarks.lib import launches


def read(ctx, op="filter"):
    row = (launches.reduce_cell(ctx) or {}).get(op)
    return row["seconds"] * 1e3 / row["launches"] if row and row["launches"] else None
