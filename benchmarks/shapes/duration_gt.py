"""{ duration > X } limit 20, X between the mix's two millisecond marks and
written in microseconds (90,000 operands a block, so none repeats): nearly
every trace matches, so the answer is any 20 of them: 20 distinct ids, every
one a member of the oracle's set."""
from benchmarks.lib import shapeutil as U

KIND = "search"
SCAN = {"S": ["span.trace_sid", "span.dur_us"],
        "T": ["trace.span_off", "trace.start_ms"]}  # staged columns the scan reads


def build(rnd, env, params):
    b = U.draw_block(rnd, env)
    lo, hi = params.get("ms", [900, 990])
    us = lo * 1000 + U.draw_unique(rnd, env, ("duration_gt", b), (hi - lo) * 1000)
    return {"block": b, "us": us, **U.window(env, b)}


def request(op, env):
    return U.get("/api/search", {
        "q": f'{{ duration > {op["us"]}us }}', "limit": 20,
        "start": op["start"], "end": op["end"]})


def check(op, status, body, env):
    got, why = U.search_ids(status, body)
    if got is None:
        return False, why
    want = U.union(env, op["start"], op["end"],
                   lambda o: o.traces_duration_gt(op["us"]))
    if len(set(got)) != min(20, len(want)):
        return False, f"got {len(set(got))} of limit 20, {len(want)} match"
    extra = set(got) - want
    return (not extra), f"{len(extra)} ids do not match" if extra else ""
