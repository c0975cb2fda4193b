"""How unevenly the window's jobs kept the workers busy: the busiest
worker's busy seconds over the mean (`dispatch.by_worker` of /status/kernels:
a remote worker's run from hand-off to result, so two queued pulls overlap;
`local` is the serving process's own threads and, since PR 38, the `seconds`
of their `run:*` stages). 1.0 is an
even load; the worker count is the ceiling (one worker did everything)."""


def read(ctx):
    def table(snap):
        return (snap.get("dispatch") or {}).get("by_worker")

    before, after = table(ctx["kernels_before"]), table(ctx["kernels_after"])
    if not after:
        return None
    busy = [row["busy_seconds"] - ((before or {}).get(w) or {}).get("busy_seconds", 0.0)
            for w, row in after.items()]
    mean = sum(busy) / len(busy)
    return max(busy) / mean if mean > 0 else None
