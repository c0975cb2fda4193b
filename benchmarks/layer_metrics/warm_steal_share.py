"""Share of the window's stolen jobs that did not wait for the steal clock:
`affinity.warm_steals` of /status/kernels (steals a cache domain took at
once because it had reported the job's block among the blocks it holds
staged columns for) over `affinity.jobs.steal` (every block-carrying job a
non-owner ran). Nothing where the program has no such counter, or where
nothing was stolen."""
from benchmarks.lib import readers as R


def read(ctx):
    warm = R.delta(ctx, "affinity", "warm_steals")
    steal = R.delta(ctx, "affinity", "jobs", "steal")
    if warm is None or not steal:
        return None
    return 100.0 * warm / steal
