"""GET /api/traces/{id} for a trace this run pushed and the server
acknowledged at least `min_age_s` ago: 200 and the exact spans, whether it
sits in the live head, in a block being cut or in a cut block."""
import json

from benchmarks.lib.oracle import spans_of_otlp_json

KIND = "find"


def build(rnd, env, params):
    return {"u": rnd.random(), "v": rnd.random(),
            "min_age_s": params.get("min_age_s", 1.0)}


def request(op, env):
    acked = env.push_log.older_than(op["min_age_s"])
    if not acked:
        return None  # nothing acknowledged yet: nothing to ask
    op["index"], op["base_ns"], _ = acked[int(op["u"] * len(acked))]
    op["trace"] = int(op["v"] * env.push_template.T)
    return ("GET", "/api/traces/"
            + env.push_template.trace_id(op["index"], op["trace"]), None, {})


def check(op, status, body, env):
    if status != 200:
        return False, f"HTTP {status} for an acknowledged trace"
    try:
        got = spans_of_otlp_json(json.loads(body))
    except (ValueError, KeyError, TypeError) as e:
        return False, f"unreadable answer: {e}"
    ok = got == env.push_template.expected_spans(op["index"], op["trace"],
                                                 op["base_ns"])
    return ok, "" if ok else "span set differs"
