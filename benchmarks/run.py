#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of `workloads` in BENCHMARK.json: a configuration
(benchmarks/configs/<config>.json) under a traffic mix
(benchmarks/mixes/<traffic>.json). The run starts the system under test,
`python -m tempo_tpu.services.app --target=all`, as its ONE child, warms up
the shapes the mix uses, measures for --seconds from the client's side of
the HTTP API, checks every answer against a numpy oracle off the clock, and
prints as the LAST line of stdout one JSON object with the keys `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` with --trace 1),
then `checks`: each number `correct` was decided from, beside its limit (the
same as the last lines of stderr).
Everything else goes to stderr, to earlier stdout lines and to chiprun_out/.

This process never imports jax: the server child owns the chip. A server
that is not on a TPU whose device_kind benchmarks/lib/peaks.json knows ends
the run with a non-zero exit and nothing on stdout. `--allow-cpu --scale
tiny` is the rehearsal: the same code end to end on the CPU backend, marked
`CPU DRY RUN`, with counts and correctness and no timing under a metric's
name. See benchmarks/README.md.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up counts from process start

import argparse  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (BENCHMARK.json's "
                         "run_seconds by default)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: run the server on the CPU backend")
    ap.add_argument("--scale", choices=("full", "tiny"), default="full",
                    help="tiny exists only for the rehearsal")
    ap.add_argument("--deadline", type=float, default=1150.0,
                    help="give up (exit 3, server killed) after this long")
    args = ap.parse_args(argv)
    if args.scale == "tiny" and not args.allow_cpu:
        print("run.py: --scale tiny is for --allow-cpu only", file=sys.stderr)
        return 2
    try:
        import tempo_tpu.backend.local  # noqa: F401  (the system under test is here)
        from benchmarks.lib import cell as C, corpus, harness as H, readers as R
        from benchmarks.lib.server import ServerFailure
    except ImportError as e:
        print(f"run.py: not inside a tempo-tpu checkout: {e}", file=sys.stderr)
        return 2
    try:
        bench, cell, config, mix = C.load_cell(args.workload)
    except (KeyError, OSError, ValueError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    seconds = args.seconds or float(bench["run_seconds"])
    tag = f"s{args.seed}-t{args.trace}"
    current: dict = {"pass": None}

    def give_up() -> None:
        print(f"run.py: no result after {args.deadline:.0f} s", file=sys.stderr,
              flush=True)
        if current["pass"] is not None:
            current["pass"].kill()
        os._exit(3)

    watchdog = C.start_watchdog(args.deadline, give_up)
    try:
        C.ensure_native()
        manifest = corpus.ensure(config, args.scale, args.seed, log=H.log)

        def new_pass(trace: bool):
            p = C.CellPass(cell, config, mix, manifest, args.seed, seconds,
                           trace, args.allow_cpu, tag)
            current["pass"] = p
            return p

        # the first run of a cell in a checkout: the whole window once,
        # unmeasured, so that every program the traffic can reach is in the
        # persistent cache before anything is timed
        # (the marker lives with the cache it vouches for, and carries the
        # mix's digest: changed traffic reaches other programs)
        digest = hashlib.sha1(json.dumps(mix, sort_keys=True).encode()).hexdigest()[:10]
        marker = os.path.join(C.compile_cache_dir(),
                              f"benchmark-compiled-{cell['name']}-{args.scale}-{digest}")
        if mix.get("compile_pass") and not os.path.exists(marker):
            H.log("compile pass: the window once, unmeasured")
            p = new_pass(trace=False)
            p.start()
            p.warm_up()
            p.window("compile")
            rc = p.stop()
            H.log(f"compile pass done, server exit {rc}")
            os.makedirs(os.path.dirname(marker), exist_ok=True)
            open(marker, "w").close()

        p = new_pass(bool(args.trace))
        p.start()
        p.warm_up()
        setup_s = time.perf_counter() - T_START
        H.log(f"set-up {setup_s:.1f}s; measuring {seconds:.0f}s")
        p.window("window")
        after = p.after_window()
        rc = p.stop()
        window_results = [r for st in p.streams.values() for r in st.results
                          if r["phase"] == "window"]
        H.check_all(p.warm + window_results + after, p.env)
        trace = p.reduce_trace() if args.trace else None
        ctx = p.context(setup_s, trace)
    except C.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    except ServerFailure as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 1
    finally:
        watchdog.cancel()
        if current["pass"] is not None:
            current["pass"].kill()
    if "jax" in sys.modules:
        print("run.py: the parent process imported jax", file=sys.stderr)
        return 1

    # attempted: every request the window took from its lists, plus the
    # after-window checks; warm-up answers are checked too and any failure
    # there makes the run incorrect
    taken = sum(st.cursor - st.window_from - st.skipped for st in p.streams.values())
    judged = window_results + after
    ok = sum(1 for r in judged if r["ok"])
    attempted = taken + len(after)
    failed = attempted - ok
    problems = [f"{r['op']['shape']}#{r['op'].get('i')}: {r['detail']}"
                for r in p.warm + judged if not r["ok"]]
    problems += [f"event {e['event']['path']}: HTTP {e['status']}"
                 for e in p.events if not e["ok"]]
    unanswered = attempted - len(judged)
    if unanswered:
        problems.append(f"{unanswered} requests had no answer {H.DRAIN_S} s "
                        "after the window")
    if rc != 0:
        problems.append(f"server exit code {rc} on SIGTERM")
    # a window holds no compaction (ROADMAP A12: a `rate()` counts double
    # after one) and, untraced, no compile: either makes the run's numbers
    # another system's. Blocks the served compactor merged away count, by
    # either of its drivers, and the pipeline's own job counter beside them;
    # a mix whose deployment compacts while it serves says `compactions_allowed`.
    # A traced run compiles what self-tracing's own pushes reach, and a mix
    # may say why its programs' sizes follow the traffic (`compiles_allowed`)
    compactions = max(ctx["blocks_compacted_in_window"] or 0,
                      R.delta(ctx, "compaction", "jobs") or 0)
    if compactions and not mix.get("compactions_allowed"):
        problems.append(f"{compactions} blocks were compacted inside the window")
    compiles = R.delta(ctx, "compile_cache", "disk_misses")
    if compiles and not args.trace and not mix.get("compiles_allowed"):
        problems.append(f"{compiles} programs compiled inside the window")
    on_chip = p.device["platform"] == "tpu"
    # every number `correct` is decided from, beside its limit: answers are
    # compared with the oracle one by one and exactly, so every limit is 0
    # (compactions and compiles have no limit where the mix allows them,
    # compiles none in a traced run either)
    bad = [r for r in p.warm + judged if not r["ok"]]
    checks = {
        "answers_compared": {"value": len(p.warm) + len(judged), "limit": None},
        "answers_wrong_or_failed": {"value": len(bad), "limit": 0},
        "of_them_in_warm_up": {"value": sum(r["phase"] == "warm" for r in bad), "limit": 0},
        "requests_without_answer": {"value": unanswered, "limit": 0},
        "events_failed": {"value": sum(not e["ok"] for e in p.events), "limit": 0},
        "server_exit_code": {"value": rc, "limit": 0},
        "compactions_in_window": {"value": compactions, "limit": None if (
            mix.get("compactions_allowed")) else 0},
        "compiles_in_window": {"value": compiles or 0, "limit": None if (
            args.trace or mix.get("compiles_allowed")) else 0},
    }

    group = "per_layer" if args.trace else "end_to_end"
    metrics = C.read_metrics(bench, cell, group, ctx)
    peaks = [d.get("peak_bytes_in_use") or 0 for d in
             (ctx["cost_after"].get("hbm", {}).get("per_device_memory_stats") or [])]
    line = C.result_line(problems, attempted, failed, metrics, p.device,
                         max(peaks, default=0), trace if args.trace else None,
                         checks)
    info = {
        "workload": cell["name"], "seed": args.seed, "seconds": seconds,
        "trace": args.trace, "setup_s": setup_s,
        "total_s": time.perf_counter() - T_START,
        "window": C.summarize(ctx), "problems": problems[:20],
        "window_compiles": compiles, "compactions_in_window": compactions,
        "routing_in_window": [[*k, v] for k, v in sorted(R.routing_delta(ctx).items())],
        "link_rtt_ms": ctx["kernels_after"]["device"].get("link_rtt_ms"),
        "launches": {f"{k['op']}/{k['bucket']}": k["calls"]
                     for k in ctx["kernels_after"]["kernels"]},
        "compile_cache": ctx["kernels_after"].get("compile_cache"),
        "staged_cache": {k: ctx["kernels_after"].get("staged_cache", {}).get(k)
                         for k in ("entries", "bytes", "budget_bytes")},
        "extras": p.extras, "trace_devices": (trace or {}).get("devices"),
        "trace_families": (trace or {}).get("families"),
        "selftraces": len(ctx["selftrace"] or []),
        "skipped": {n: st.skipped for n, st in p.streams.items()},
        "warm_ms": [[r["op"]["shape"], r["op"].get("block"),
                     round((r["t_done"] - r["t_send"]) * 1e3)] for r in p.warm],
    }
    C.write_json(os.path.join(C.out_dir(), f"bench-{cell['name']}-{tag}.json"),
                 {"line": line, "info": info})
    if compiles:
        H.log(f"{compiles} programs compiled inside the window")
    print(json.dumps({"info": info}, default=str), flush=True)
    if not on_chip:
        # a rehearsal: which metrics a reader found something for, never a value
        print("CPU DRY RUN -- not a chip result; metrics a reader could fill: "
              + ", ".join(sorted(metrics)), flush=True)
        line["metrics"] = {}
        line.pop("breakdown", None)
        line = {**{k: v for k, v in line.items() if k != "checks"},
                "cpu_dry_run": True, "checks": line["checks"]}
    print(json.dumps(line), flush=True)
    for why in problems[:5]:
        print(f"not correct: {why[:300]}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    return 0 if line["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
