"""Helpers the metric readers share. A reader is
`benchmarks/<e2e_metrics|layer_metrics>/<name>.py` with `read(ctx)`; it
returns a number, or None when there is nothing to read (the harness then
leaves the metric out of the line).

ctx keys: seconds, t0, t_end, streams {name: {"spec", "results"}}, config,
mix, manifest, setup_s, kernels_before, kernels_after, cost_after,
blocks_compacted_in_window (the served compactor's, by either of its
drivers; None where the process runs none), selftrace (list of traces, each
a list of spans) or None, trace (the reduced device trace) or None,
trace_span (t_start, t_end of the traced interval on the client's clock) or
None, extras (what after-window checks measured), device (the server's own
word: platform, device_kind, count), module_ops (lib/module_ops.json: which
device modules belong to which op), env.
"""

from __future__ import annotations

from . import stats


def by_role(ctx: dict, role: str) -> list[dict]:
    out = []
    for st in ctx["streams"].values():
        if st["spec"].get("role") == role:
            out += st["results"]
    return out


def good(r: dict) -> bool:
    return bool(r["ok"])


def latencies_ms(results: list[dict], from_due: bool, cap_ms: float) -> list[float]:
    """Latency of every request; a failed one counts as beyond every
    percentile (the cap: its stream's timeout)."""
    out = []
    for r in results:
        t_from = r["t_due"] if from_due else r["t_send"]
        out.append((r["t_done"] - t_from) * 1e3 if good(r) else cap_ms)
    return out


def completed_in_window(ctx: dict, results: list[dict]) -> list[dict]:
    return [r for r in results if r["t_done"] <= ctx["t_end"]]


def untraced(ctx: dict, results: list[dict]) -> list[dict]:
    """Requests that were due at least a second before the profiler's
    session began (all of them in a run without one): /debug/profile/device
    slows the server's Python ~14x while it records and freezes it when it
    stops, so a latency taken from then on is the profiler's, not the
    system's."""
    if not ctx.get("trace_span"):
        return results
    began = ctx["trace_span"][0]
    return [r for r in results if r["t_due"] < began - 1.0]


def pct_ms(ctx: dict, role: str, p: float, from_due: bool,
           completed_only: bool, untraced_only: bool = False) -> float | None:
    res = by_role(ctx, role)
    if completed_only:
        res = completed_in_window(ctx, res)
    if untraced_only:
        res = untraced(ctx, res)
    return stats.percentile(latencies_ms(res, from_due, 120_000.0), p)


def delta(ctx: dict, *path, source: str = "kernels"):
    """Difference of a cumulative counter between the two snapshots."""
    def dig(d):
        for k in path:
            if not isinstance(d, dict) or k not in d:
                return None
            d = d[k]
        return d
    a, b = dig(ctx[f"{source}_before"]), dig(ctx[f"{source}_after"])
    if a is None and b is not None:
        a = 0
    if b is None:
        return None
    return b - a


def metrics_family_total(text: str, family: str) -> int | None:
    """Sum of a counter family's samples in a `/metrics` page (labels or
    none); None where the page does not have the family."""
    total = None
    for line in text.splitlines():
        name, _, rest = line.partition(" ")
        if name.split("{")[0] == family and rest:
            total = (total or 0) + int(float(rest.split()[0]))
    return total


def routing_delta(ctx: dict) -> dict:
    """(layer, engine, reason) -> decisions inside the window."""
    def table(snap):
        return {(r["layer"], r["engine"], r["reason"]): r["count"]
                for r in snap.get("routing", [])}
    a, b = table(ctx["kernels_before"]), table(ctx["kernels_after"])
    return {k: v - a.get(k, 0) for k, v in b.items() if v - a.get(k, 0)}


def spans_named(ctx: dict, name: str, roots=None) -> list[tuple[dict, float]]:
    """(span, self time in ms) for every self-trace span called `name`, in
    traces whose root is one of `roots` (any root when None)."""
    out = []
    for spans in ctx.get("selftrace") or []:
        root = next((s for s in spans if not s["parent"]), None)
        if roots is not None and (root is None or root["name"] not in roots):
            continue
        st = stats.self_times(spans)
        out += [(s, st[s["id"]] * 1e3) for s in spans if s["name"] == name]
    return out


def family_seconds(ctx: dict, key: str) -> float | None:
    """Device seconds, in the traced interval, of the op families that
    module_ops.json lists under `key`."""
    tr = ctx.get("trace")
    if not tr or not tr.get("devices"):
        return None
    return sum(tr["families"].get(f, {}).get("seconds", 0.0)
               for f in ctx["module_ops"][key])


def in_trace(ctx: dict, results: list[dict]) -> list[dict]:
    """Requests that completed inside the traced interval."""
    if not ctx.get("trace_span"):
        return []
    a, b = ctx["trace_span"]
    return [r for r in results if a <= r["t_done"] <= b and good(r)]
