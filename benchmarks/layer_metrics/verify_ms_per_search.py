"""Self time of exact-verify spans over the search queries that were traced."""
from benchmarks.lib import readers as R

ROOTS = ("frontend.search",)


def read(ctx):
    n = sum(1 for spans in ctx.get("selftrace") or []
            if any(not s["parent"] and s["name"] in ROOTS for s in spans))
    if not n:
        return None
    return sum(ms for _, ms in R.spans_named(ctx, "verify", ROOTS)) / n
