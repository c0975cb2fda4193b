"""What a block cut's seconds are made of: of the wall time of `ingest:cut`
+ `ingest:flush` in the window (`cut_ms_per_flush`), the share the cutting
thread was on a CPU. The rest it stood in line for the interpreter behind
the push handlers, or waited for the disk or the device."""
from benchmarks.lib import cpu


def read(ctx):
    return cpu.oncpu_share(ctx, ("ingest:cut", "ingest:flush"))
