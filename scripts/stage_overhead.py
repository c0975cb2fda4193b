#!/usr/bin/env python3
"""Microbenchmark: what one `with TEL.stage(...)` costs when nothing listens
(no self-trace parked, no profiler session) -- the always-on price of the
span primitive. Prints one JSON line; host-clock numbers of the machine it
runs on (run it where the server runs: `chiprun -- python scripts/stage_overhead.py`).

    us_per_stage_nojax   stage() in a process that never imported jax
                         (two clock reads + one locked dict add)
    us_per_stage         the same once jax is loaded: + an inert TraceAnnotation
    us_per_find_stages   the five stages one trace-by-id request passes
                         (http:find > find:bloom, find:lookup, find:fetch >
                         rows:materialize; http:encode, http:write)
    us_per_stage_traced  with a self-trace parked (a span per stage)
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tempo_tpu.util.kerneltel import TEL  # noqa: E402

N = 200_000


def per_call_us(body, n=N) -> float:
    body()  # first call creates the table row
    t0 = time.perf_counter()
    for _ in range(n):
        body()
    return (time.perf_counter() - t0) / n * 1e6


def one():
    with TEL.stage("bench:stage", rows=5):
        pass


def find_path():
    with TEL.stage("http:find"):
        with TEL.stage("find:bloom", blocks=4):
            pass
        with TEL.stage("find:lookup", blocks=4):
            pass
        with TEL.stage("find:fetch", hits=1):
            with TEL.stage("rows:materialize", rows=1):
                pass
        with TEL.stage("http:encode", spans=69):
            pass
        with TEL.stage("http:write", bytes=40_000, status=200):
            pass


def main() -> int:
    out = {"n": N}
    assert "jax" not in sys.modules
    out["us_per_stage_nojax"] = per_call_us(one)
    import jax

    dev = jax.devices()[0]
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": jax.device_count()}
    out["us_per_stage"] = per_call_us(one)
    out["us_per_find_stages"] = per_call_us(find_path, N // 10)

    from tempo_tpu.services.selftrace import SelfTracer

    tracer = SelfTracer(push=lambda tenant, rs: None)
    with tracer.trace("frontend.search", {}) as t:
        tok = TEL.set_active_trace(t)
        out["us_per_stage_traced"] = per_call_us(one, 20_000)
        TEL.reset_active_trace(tok)
        t.spans.clear()  # 20,000 spans are not a trace to ship
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
