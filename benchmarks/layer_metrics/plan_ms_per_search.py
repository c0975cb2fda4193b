"""What planning costs a search: wall seconds of the `plan:compile` stage
(`db/search.plan_job`, `db/metrics_exec`: once a job) in the window, over
the searches served in it (`http:search` + `http:metrics` counts). The one
time "Plan + route" has from inside the program. Needs the `http:*` rows'
counts only, but is reported where the CPU clock is (None on the parent
of PR 38), so that a line has the layer's metrics together or not at all."""
from benchmarks.lib import cpu, stages


def read(ctx):
    plan = stages.delta(ctx, "plan:compile")
    searches = cpu.total(ctx, cpu.SEARCH_HTTP)
    if plan is None or searches is None or searches["count"] <= 0:
        return None
    return plan[0] * 1e3 / searches["count"]
