"""Share of the window's trace-by-id requests answered correctly within 1 s
of the instant each was due (open loop). 1 s is the program's own default
objective for the `traces` class (`TEMPO_SLO_TRACES_P99_S`,
tempo_tpu/util/profiler.py). A 5xx, a timeout, a wrong span set and a miss
answered 200 are not within it."""
from benchmarks.lib import readers as R

WITHIN_S = 1.0


def read(ctx):
    res = R.by_role(ctx, "find")
    if not res:
        return None
    met = sum(1 for r in res if R.good(r) and r["t_done"] - r["t_due"] <= WITHIN_S)
    return 100.0 * met / len(res)
