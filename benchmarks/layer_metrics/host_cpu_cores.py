"""CPU cores the system under test kept busy inside the window: the
process's CPU seconds (`interp.cpu_seconds`, time.process_time: every
thread, the runtime's native ones too) over the seconds between the two
snapshots on the program's own clock (`interp.wall_seconds`). A tree's
snapshot sums both over its processes, so the wall is divided by their
number and the result is the tree's cores. One process is NOT held to one
core: its decoders and reads run with the GIL released (3.1-3.3 cores in
`chip1-read-mix`, PERF.md section 6, PR 38), so a reading near 1 does not say the
interpreter is saturated and one above 1 does not say it is not:
`gil_wait_ms` and `job_oncpu_share` say that."""
from benchmarks.lib import cpu


def read(ctx):
    busy = cpu.interp(ctx, "cpu_seconds")
    wall = cpu.interp(ctx, "wall_seconds")
    if busy is None or not wall or wall <= 0:
        return None
    return busy / (wall / cpu.instances(ctx))
