"""{ span.k = "v" } >> { duration > Yms }: structural descendant. Exact set."""
from benchmarks.lib import shapeutil as U

KIND = "search"
SCAN = {"A": ["sattr.span", "sattr.key_id", "sattr.vtype", "sattr.str_id"],
        "S": ["span.trace_sid", "span.dur_us", "span.parent_idx"],
        "T": ["trace.span_off", "trace.start_ms"]}  # staged columns the scan reads


def build(rnd, env, params):
    b = U.draw_block(rnd, env)
    lo, hi = params.get("ms", [300, 700])
    return {"block": b, "key": f"attr.key{rnd.randrange(1, 100):03d}",
            "val": f"value-{rnd.randrange(5000):05d}",
            "ms": rnd.randrange(lo, hi), **U.window(env, b)}


def request(op, env):
    q = (f'{{ span.{op["key"]} = "{op["val"]}" }} >> '
         f'{{ duration > {op["ms"]}ms }}')
    return U.get("/api/search", {"q": q, "limit": 5000,
                                 "start": op["start"], "end": op["end"]})


def check(op, status, body, env):
    got, why = U.search_ids(status, body)
    if got is None:
        return False, why
    want = U.union(env, op["start"], op["end"],
                   lambda o: o.traces_descendant(op["key"], op["val"],
                                                 op["ms"] * 1000))
    return U.equal_sets(got, want)
