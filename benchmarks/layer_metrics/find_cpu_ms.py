"""What one trace-by-id request costs its own threads: CPU of `http:find`
and of its jobs' `run:find_recent` / `run:find_blocks` over the finds served
(`http:find` count); in a traced run up to the start of the profiler's
session, as `find_server_ms` takes the wall clock of the same requests. A
row fetch the job hands to the db's pool is CPU of the pool's threads
(`rows:materialize`'s own row), not in this sum."""
from benchmarks.lib import cpu


def read(ctx):
    return cpu.cpu_ms_per(ctx, cpu.FIND_HTTP + cpu.FIND_RUNS, cpu.FIND_HTTP,
                          before_session=True)
