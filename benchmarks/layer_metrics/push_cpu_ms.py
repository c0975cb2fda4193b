"""What one OTLP push costs its handler's thread: CPU of the `http:push`
stage (route entry to the acknowledgement: WAL append, live-head insert;
the decode runs off this path, in the generator's tap and in the cut) over
the pushes handled in the window. The wall clock of the same stage also
holds the waits (`push_lock_wait_ms`, the GIL beside a cut). Not a
capacity by itself: the handler's native parts release the GIL, and on the
chip's host the thread clock ticks in 10 ms steps, so only the sum over a
window's hundreds of pushes means anything."""
from benchmarks.lib import cpu


def read(ctx):
    return cpu.cpu_ms_per(ctx, ("http:push",), ("http:push",))
