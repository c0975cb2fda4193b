"""{ resource.service.name = "svc-NNN" } | rate(), step 60 s: one series,
every bucket equal to the oracle's count."""
import json

from benchmarks.lib import shapeutil as U

KIND = "search"
SCAN = {"S": ["span.trace_sid", "span.res_idx", "span@res.service_id",
              "span.start_ms"],
        "T": ["trace.span_off"]}  # staged columns the scan reads
STEP_S = 60


def build(rnd, env, params):
    b = U.draw_block(rnd, env)
    return {"block": b,
            "svc": f"svc-{U.draw_unique(rnd, env, ('rate_service', b), 64):03d}",
            **U.window(env, b)}


def request(op, env):
    return U.get("/api/metrics/query_range", {
        "q": f'{{ resource.service.name = "{op["svc"]}" }} | rate()',
        "start": op["start"], "end": op["end"], "step": STEP_S})


def check(op, status, body, env):
    if status != 200:
        return False, f"HTTP {status}: {body[:200]!r}"
    try:
        series = json.loads(body)["data"]["result"]
    except (ValueError, KeyError, TypeError) as e:
        return False, f"unreadable answer: {e}"
    step_ms = STEP_S * 1000
    start_ms = (op["start"] * 1000 // step_ms) * step_ms
    nb = -(-(op["end"] * 1000 - start_ms) // step_ms)
    want = sum(env.oracle(b).rate_counts(op["svc"], start_ms, step_ms, nb)
               for b in U.blocks_overlapping(env, op["start"], op["end"]))
    if len(series) != 1:
        return False, f"{len(series)} series"
    got = [0] * nb
    for ts, v in series[0]["values"]:
        got[int(round((float(ts) * 1000 - start_ms) / step_ms))] = int(
            round(float(v) * STEP_S))
    if int(want.sum()) == 0:
        return False, "the oracle counts no span: a bad operand"
    ok = got == [int(x) for x in want]
    return ok, "" if ok else f"total got {sum(got)} want {int(want.sum())}"
