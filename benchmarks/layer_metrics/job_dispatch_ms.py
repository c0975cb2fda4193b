"""What crossing the frontend -> worker boundary costs a search: the extent of
its jobs' `job:dispatch` spans (enqueue -> a worker has the job in hand: the
queue wait and, for a remote querier, the poll's way back) plus `job:result`
(result posted -> merged at the frontend), summed over the search's jobs, per
traced search. Nothing where the program writes no such span."""
from benchmarks.lib import stages

ROOTS = ("frontend.search",)


def read(ctx):
    return stages.span_ms_per_root(ctx, ("job:dispatch", "job:result"), ROOTS,
                                   extent=True)
