"""Tag search service.name=svc-NNN over one block. Exact set."""
from benchmarks.lib import shapeutil as U

KIND = "search"


def build(rnd, env, params):
    b = U.draw_block(rnd, env)
    return {"block": b,
            "svc": f"svc-{U.draw_unique(rnd, env, ('tag_service', b), 64):03d}",
            **U.window(env, b)}


def request(op, env):
    n = env.manifest["blocks"][op["block"]]["n_traces"]
    return U.get("/api/search", {"tags": f'service.name={op["svc"]}',
                                 "limit": n + 100,
                                 "start": op["start"], "end": op["end"]})


def check(op, status, body, env):
    got, why = U.search_ids(status, body)
    if got is None:
        return False, why
    want = U.union(env, op["start"], op["end"],
                   lambda o: o.traces_service(op["svc"]))
    if not want:
        return False, "the oracle finds no trace: a bad operand"
    return U.equal_sets(got, want)
