"""What the program says of its interpreter (PR 38), as the "Host
interpreter" readers take it (benchmarks/INTERP.md):

  * a row of the `stages` table of /status/kernels filled by a
    `with TEL.stage(...)` body carries `cpu_seconds` beside `seconds`: the
    stage's own thread on a CPU (time.thread_time, native code that
    released the GIL included) against the wall clock;
  * `run:<kind>` rows: every job of a search or a find, timed in the
    process and on the thread that ran it. With `http:search|metrics|find`
    (and, on a tree, the wire handlers' `job:encode` / `job:decode`) they
    are the outermost stages of a request's own threads and never nest in
    each other, so their CPU adds up without counting anything twice. Work
    a job hands to a pool (a `rate()`'s `block:metrics`, a find's row
    fetch) is CPU of the pool's threads: in those stages' own rows and in
    `interp.cpu_seconds`, not in this sum (benchmarks/INTERP.md);
  * `interp`: the process's CPU and wall seconds and the always-on
    sampler's lateness probe (`ticks`, `late_seconds`).

Everything is read as the difference of the two snapshots around the
window; on a tree both are sums over the instances. A program without a
field (the parent of PR 38) gives None and the metric is left out."""

from __future__ import annotations

from . import readers as R

SEARCH_HTTP = ("http:search", "http:metrics")
SEARCH_RUNS = ("run:search_recent", "run:search_blocks",
               "run:search_block_shard", "run:metrics_query_range")
FIND_HTTP = ("http:find",)
FIND_RUNS = ("run:find_recent", "run:find_blocks")
WIRE = ("job:encode", "job:decode")

_KEYS = ("count", "seconds", "cpu_seconds")


def row(ctx: dict, name: str, before_session: bool = False) -> dict | None:
    """{count, seconds, cpu_seconds} of one stage inside the window; None
    where the row is not there or has no CPU clock. `before_session` as
    lib/stages.delta: in a traced run, only up to the start of the
    profiler's session (`stages_at_session`)."""
    after = ctx["kernels_after"]
    end = (after.get("stages_at_session")
           if before_session and ctx.get("trace_span") else None)
    b = (end or after.get("stages") or {}).get(name)
    if b is None or "cpu_seconds" not in b:
        return None
    a = (ctx["kernels_before"].get("stages") or {}).get(name, {})
    return {k: b[k] - a.get(k, 0) for k in _KEYS}


def total(ctx: dict, names, before_session: bool = False) -> dict | None:
    """The sum of `row` over `names`; None when none of them is there."""
    rows = [r for r in (row(ctx, n, before_session) for n in names) if r]
    if not rows:
        return None
    return {k: sum(r[k] for r in rows) for k in _KEYS}


def cpu_ms_per(ctx: dict, names, per, before_session: bool = False) -> float | None:
    """CPU (ms) of the stages `names` over how often the stages `per` ran."""
    num = total(ctx, names, before_session)
    den = total(ctx, per, before_session)
    if num is None or den is None or den["count"] <= 0:
        return None
    return num["cpu_seconds"] * 1e3 / den["count"]


def oncpu_share(ctx: dict, names) -> float | None:
    """Of the wall seconds the stages `names` took, the share (%) their
    own threads were on a CPU."""
    t = total(ctx, names)
    if t is None or t["seconds"] <= 0:
        return None
    return 100.0 * t["cpu_seconds"] / t["seconds"]


def interp(ctx: dict, *path) -> float | None:
    return R.delta(ctx, "interp", *path)


def instances(ctx: dict) -> int:
    """Processes whose `interp` the snapshot sums (1 but on a tree)."""
    rows = ctx["kernels_after"].get("instances")
    return sum(1 for r in rows if r.get("alive", True)) if rows else 1
