"""Hedge twins a search: jobs the frontend enqueued a second time because
they were older than `hedge_after_s` (`/status/kernels` `hedging`: one count
a hedged job, by how its twin ended -- win, lose, unneeded), inside the
window, over the requests that built block jobs in it (`range.searches`:
searches and `rate()` requests). On the single binary a twin landed on the
process that already ran the original; since PR 42 a job is hedged only
where another cache domain could take the twin, so there it reads 0.
Nothing where the program has no such section."""
from benchmarks.lib import readers as R


def read(ctx):
    before = ctx["kernels_before"].get("hedging")
    after = ctx["kernels_after"].get("hedging")
    searches = R.delta(ctx, "range", "searches")
    if not isinstance(after, dict) or not searches:
        return None
    twins = sum(after.values()) - sum((before or {}).values())
    return twins / searches
