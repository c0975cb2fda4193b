"""What the program's one span primitive (kerneltel `TEL.stage`) publishes,
as the readers of PR 23 take it: the cumulative `stages` table of
/status/kernels (name -> {count, seconds and, since PR 38, cpu_seconds:
`lib/cpu.py` reads that one; each read as the difference of the two
snapshots around the window) and the same stages as self-trace spans.
A program without the table or the span (the parent of PR 23) gives None
everywhere, and the metric is left out of the line."""

from __future__ import annotations

from . import readers as R


def delta(ctx: dict, name: str, before_session: bool = False) -> tuple[float, int] | None:
    """(seconds, count) of one stage inside the window. `before_session`:
    in a traced run, only up to the start of the profiler's session, from
    the copy of the table the program keeps then (`stages_at_session`;
    the last session's, if the harness had to take it again): stopping a
    session costs seconds of CPU beside serving (15-27 s on four chips),
    and what was served then says how the profiler stops, not how the
    system runs."""
    end = (ctx["kernels_after"].get("stages_at_session")
           if before_session and ctx.get("trace_span") else None)
    if end:
        a = (ctx["kernels_before"].get("stages") or {}).get(name, {})
        b = end.get(name)
        if b is None:
            return None
        return (float(b["seconds"] - a.get("seconds", 0.0)),
                int(b["count"] - a.get("count", 0)))
    s = R.delta(ctx, "stages", name, "seconds")
    n = R.delta(ctx, "stages", name, "count")
    if s is None or n is None:
        return None
    return float(s), int(n)


def ms_per(ctx: dict, names, per: str, before_session: bool = False) -> float | None:
    """Seconds of the stages `names` inside the window, in ms, over how
    often the stage `per` ran in it."""
    parts = [delta(ctx, n, before_session) for n in names]
    den = delta(ctx, per, before_session)
    if den is None or den[1] <= 0 or all(p is None for p in parts):
        return None
    return sum(p[0] for p in parts if p is not None) * 1e3 / den[1]


def span_ms_per_root(ctx: dict, names, roots, extent: bool = False) -> float | None:
    """Self time (ms) of the self-trace spans called one of `names`, in
    traces rooted at one of `roots`, over the number of those traces.
    `extent`: each span's whole duration instead, the stages it nests
    included (for a stage that never nests in itself)."""
    n = sum(1 for spans in ctx.get("selftrace") or []
            if any(not s["parent"] and s["name"] in roots for s in spans))
    ms = [(s["end"] - s["start"]) * 1e3 if extent else self_ms
          for name in names for s, self_ms in R.spans_named(ctx, name, roots)]
    return sum(ms) / n if n and ms else None
