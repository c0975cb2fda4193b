#!/usr/bin/env python3
"""Find a knee once: several fixed rates of one open-loop stream of a cell's
mix against ONE server in one process, the mix's other streams beside it.

    python benchmarks/sweep.py --workload chip1-find --stream find \
        --rates 4,8,16,32 --seconds 15 [--seed 1]

For each rate it prints one JSON line: offered and completed requests per
second, p50/p90/max latency from the due time, how late the generator sent,
and the backlog (requests due but unanswered) at the end of the window. The
highest rate whose backlog does not grow and whose p90 stays near the lower
rates' is the knee; the rate written into the mix file is a fixed share of
it (PERF.md section 6 records both). Not a metric and never run by the
driver; the parent stays off jax like run.py.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--stream", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, per second")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--scale", choices=("full", "tiny"), default="full")
    args = ap.parse_args(argv)
    from benchmarks.lib import cell as C, corpus, harness as H, stats

    bench, cell, config, mix = C.load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    C.ensure_native()
    manifest = corpus.ensure(config, args.scale, args.seed, log=H.log)
    # one list long enough for every rate, drawn from behind one cursor
    mix = copy.deepcopy(mix)
    spec = next(s for s in mix["streams"] if s["name"] == args.stream)
    spec["ops"] = int(sum(rates) * args.seconds * 1.5) + 200
    mix["events"], mix["after_window"] = [], []
    p = C.CellPass(cell, config, mix, manifest, args.seed, args.seconds, False,
                   args.allow_cpu, f"sweep-s{args.seed}")
    rc = 1
    try:
        p.start()
        p.warm_up()
        st = p.streams[args.stream]
        for rate in rates:
            # the same list, re-timed at this rate from where the cursor is
            spec["rate_per_s"] = rate
            st.dues = H.due_times(mix["name"] + f"-r{rate}", spec, args.seed,
                                  len(st.ops))
            before = len(st.results)
            first = st.cursor
            p.window("window")
            res = st.results[before:]
            lat = [(r["t_done"] - r["t_due"]) * 1e3 for r in res]
            late = [(r["t_send"] - r["t_due"]) * 1e3 for r in res]
            in_window = sum(r["t_done"] <= p.t_end for r in res)
            print(json.dumps({
                "rate_per_s": rate, "offered": st.cursor - first,
                "completed_in_window_per_s": in_window / args.seconds,
                "backlog_at_end": (st.cursor - first) - in_window,
                "bad_status": sum(r["status"] not in (200, 404) for r in res),
                "p50_ms": stats.percentile(lat, 0.5),
                "p90_ms": stats.percentile(lat, 0.9),
                "p99_ms": stats.percentile(lat, 0.99),
                "max_ms": max(lat, default=None),
                "late_p99_ms": stats.percentile(late, 0.99),
                "device": p.device["device_kind"]}), flush=True)
            time.sleep(2)
        rc = p.stop() or 0
    finally:
        p.kill()
    return rc


if __name__ == "__main__":
    sys.exit(main())
