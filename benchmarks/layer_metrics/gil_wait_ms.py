"""How long a thread that becomes runnable waits for the interpreter: the
mean lateness of the program's always-on 19 Hz sampler inside the window
(`interp.probe` of /status/kernels: `late_seconds` over `ticks`). The
sampler sleeps one period and then needs the GIL back; a handler or a worker
pays the same wait after every device wait, read or lock. On a tree: the
mean over every process's ticks. Nothing with the sampler off or on a
program without the probe. benchmarks/INTERP.md says what else it holds."""
from benchmarks.lib import cpu


def read(ctx):
    late = cpu.interp(ctx, "probe", "late_seconds")
    ticks = cpu.interp(ctx, "probe", "ticks")
    return max(0.0, late) * 1e3 / ticks if late is not None and ticks else None
