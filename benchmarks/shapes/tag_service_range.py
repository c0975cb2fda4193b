"""`tag_service` over the newest N whole blocks (lib/rangeutil.py). Exact
set; `limit` is the traces the range covers + 100, so nothing is cut."""
from benchmarks.lib import rangeutil as R, shapeutil as U
from benchmarks.shapes import tag_service as one

KIND = one.KIND
check = one.check


def build(rnd, env, params):
    n, v, win = R.draw(rnd, env, params, "tag_service_range", 64)
    return {"block": 0, "n": n, "svc": f"svc-{v:03d}", **win}


def request(op, env):
    return U.get("/api/search", {"tags": f'service.name={op["svc"]}',
                                 "limit": R.traces_covered(env, op) + 100,
                                 "start": op["start"], "end": op["end"]})
