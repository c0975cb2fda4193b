"""Row fetch (`find:fetch`: rows -> wire trace, rows:materialize inside it)
per trace-by-id request served in the window (before the profiler's
session in a traced run, as find_server_ms); bloom + index lookup are
find_server_ms less this and the `http:encode` stage."""
from benchmarks.lib import stages


def read(ctx):
    return stages.ms_per(ctx, ("find:fetch",), "http:find", before_session=True)
