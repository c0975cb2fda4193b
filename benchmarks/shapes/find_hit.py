"""GET /api/traces/{id} for an id one block holds: 200 and the exact spans."""
import json

from benchmarks.lib import shapeutil as U
from benchmarks.lib.oracle import spans_of_otlp_json

KIND = "find"


def build(rnd, env, params):
    b = U.draw_block(rnd, env)
    return {"block": b,
            "sid": rnd.randrange(env.manifest["blocks"][b]["n_traces"])}


def request(op, env):
    hex_id = env.block_ids(op["block"])[op["sid"]].tobytes().hex()
    return "GET", f"/api/traces/{hex_id}", None, {}


def check(op, status, body, env):
    if status != 200:
        return False, f"HTTP {status} for an id block {op['block']} holds"
    try:
        got = spans_of_otlp_json(json.loads(body))
    except (ValueError, KeyError, TypeError) as e:
        return False, f"unreadable answer: {e}"
    ok = got == env.oracle(op["block"]).trace_spans(op["sid"])
    return ok, "" if ok else "span set differs"
