"""Share of the window's block-carrying jobs that their block's owner ran
(`affinity.jobs` of /status/kernels: `own` over `own` + `steal`; jobs that
carry no block are `unowned` and not counted)."""
from benchmarks.lib import readers as R


def read(ctx):
    own = R.delta(ctx, "affinity", "jobs", "own") or 0
    steal = R.delta(ctx, "affinity", "jobs", "steal") or 0
    return 100.0 * own / (own + steal) if own + steal else None
