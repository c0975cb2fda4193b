"""Mean time of a trace-by-id request inside the server: the `http:find`
stage, route entry to last byte written, seconds over count in the window;
in a traced run, in the part of the window before the profiler's session
(lib/stages.delta), as find_p50_ms takes its finds.
What is left to find_p50_ms is the connection's and the client's."""
from benchmarks.lib import stages


def read(ctx):
    return stages.ms_per(ctx, ("http:find",), "http:find", before_session=True)
