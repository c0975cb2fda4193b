"""{ span.attr.keyNNN = "value-NNNNN" }: the north-star query. Exact set."""
from benchmarks.lib import shapeutil as U

KIND = "search"
# staged columns the scan reads (ops/filter.required_columns for the query's
# conditions, plus the trace-start column every search stages)
SCAN = {"A": ["sattr.span", "sattr.key_id", "sattr.vtype", "sattr.str_id"],
        "S": ["span.trace_sid"], "T": ["trace.span_off", "trace.start_ms"]}


def build(rnd, env, params):
    b = U.draw_block(rnd, env)
    return {"block": b, "key": f"attr.key{rnd.randrange(1, 100):03d}",
            "val": f"value-{rnd.randrange(5000):05d}", **U.window(env, b)}


def request(op, env):
    return U.get("/api/search", {
        "q": f'{{ span.{op["key"]} = "{op["val"]}" }}', "limit": 5000,
        "start": op["start"], "end": op["end"]})


def check(op, status, body, env):
    got, why = U.search_ids(status, body)
    if got is None:
        return False, why
    want = U.union(env, op["start"], op["end"],
                   lambda o: o.traces_attr(op["key"], op["val"]))
    return U.equal_sets(got, want)
