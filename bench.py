"""BASELINE-config benchmarks, one JSON line each; the LAST line is the
headline END-TO-END search (IO + zstd decode + device staging + filter +
verify), the honest comparable to the reference's 0.18 s vParquet
full-block search that *includes* local-SSD IO
(docs/design-proposals/2022-04 Parquet.md:233-241 => 57.8 M spans/s).

Lines, in order:
  1. traceql_filter_kernel_spans_per_sec_per_chip -- device-resident
     filter kernel only (ceiling metric; no IO/staging).
  1b. search_mesh_1x1_overhead -- the stacked shard_map search program
     vs the plain kernel on a 1x1 mesh, both legs on device-resident
     columns (ROADMAP 2a): the fixed smap/stacking price mesh routing
     must amortize, with the costmodel walker's per-collective comm
     bytes attached (all zero on 1x1 by the ring model).
  2. find_trace_by_id_p50_ms -- BASELINE config #1: trace-ID lookup on a
     local-disk block via the production device Find path (bloom read +
     batched bisection kernel + row materialization).
  2b. find_auto_crossover_rows -- the committed device-vs-host find
     race (ops/find.calibrate_find): both engines timed on the same
     block set, the crossover written to a CostLedger artifact, and the
     `auto` policy proven to route from it (reason ledger_crossover).
  3. compaction_mb_per_sec -- BASELINE config #4 shape: level-0->1
     columnar compaction of many small blocks, MB/s of input consumed.
  4. ingest_otlp_mb_per_sec -- raw-bytes OTLP write path (native scan +
     splice + columnar WAL windows), vs the reference's 15 MB/s
     per-tenant rate-limit default; the row carries a per-stage
     breakdown (decode / wal_append / stage_delta / cut / flush ms)
     read from the kerneltel ingest ledger.
  5. spanmetrics_reduce_spans_per_sec -- BASELINE config #5: span-metrics
     segmented reduce (calls + latency sum + histogram) on device.
  5a. spanmetrics_streaming_spans_per_sec / service_graph_edges_per_sec
     -- the streaming metrics-generator plane (PR-17): coded windows
     through push_window (packed-key series assembly + device reduce)
     and client/server pairing through the coded edge store + fused
     edge reduce; the edge row's tel proves the distributor tap costs
     zero extra proto decodes (columnar cache counters).
  5b. search_concurrent_p50_ms -- Q parallel identical-shape queries on
     one hot block through the cross-query batching executor
     (db/batchexec): p50/p95 latency, launches-per-query, occupancy.
  5b2. search_mesh_batched_virtual_cpu -- COUNTS ONLY, from an
     8-virtual-CPU-device subprocess (no timing: virtual devices share
     the host's cores): one admission window's 16 queries as ONE
     Q-programs x sharded-rows mesh launch (parallel/multiquery) --
     launches/query, occupancy and walker comm bytes/query;
     search_struct_comm_shrink rides along -- the walker-priced
     per-struct-node collective before/after the bit-packed + hoisted
     gathers (>= 5x is the acceptance gate).
  5c. search_affinity_p99_ms -- the cache-affinity differential: 3
     simulated querier workers (each its own TempoDB = its own staged-
     cache domain), 4 tenants, 50 concurrent Zipf-mixed searches, HBM
     budget pinched to ~1.35x one fleet copy; p99 + staged-cache hit
     rate with affinity routing on vs off, and the re-upload bytes
     affinity avoided.
  6. search_block_e2e_cold_spans_per_sec -- BASELINE config #2, fresh
     reader each query: every byte from disk + staged to device through
     the cold-read streaming pipeline (ops/stream); the row carries
     per-stage ms and the overlap ratio.
  6b. search_block_e2e_cold_find_p50_ms -- trace-ID lookup with fresh
     readers per query: bloom shard, trace index and the trace's
     row-group chunks all come from disk through the pipeline's
     plan -> ranged-fetch -> threaded-decode stages.
  7. search_block_e2e_spans_per_sec -- BASELINE config #2 (headline):
     hot immutable block, staged device arrays cached (the production
     querier pattern; the reference's hot path re-decodes parquet from
     the OS page cache each query).

vs_baseline semantics: for the kernel and e2e search lines it is the
ratio to the reference's 57.8 M spans/s (IO-inclusive), passed
explicitly. Every OTHER row resolves against BASELINE.json's
"published" map -- committed values from prior bench rounds (the
reference publishes no figures for find p50 / compaction MB/s /
span-metrics, so the committed round IS the comparable; direction-aware
so >1 always means improvement). Rows with a null published value
(calibration rows, rows awaiting their first committed round) report
0.0; a row MISSING from the map warns on stderr so it can't ship
baseline-less forever.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from tempo_tpu.util.testdata import synth_block

BASELINE_SPANS_PER_SEC = 10.4e6 / 0.18  # reference vParquet search, IO incl.

# committed per-metric baselines (BASELINE.json "published"): rows whose
# comparable is a prior committed bench round rather than a reference
# paper figure resolve vs_baseline here. direction says which way is
# better ("higher" throughput vs "lower" latency) so the ratio always
# reads >1 = improvement. A null value = "intentionally no baseline yet"
# (calibration rows); a MISSING metric key warns on stderr, so a new
# bench row can't silently ship with vs_baseline 0.0 forever.
def _load_published() -> dict:
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BASELINE.json")) as f:
            return json.load(f).get("published", {})
    except Exception as e:
        print(f"bench: BASELINE.json unreadable ({e}); "
              "all unpublished rows report vs_baseline 0.0", file=sys.stderr)
        return {}


_PUBLISHED = _load_published()


def _baseline_ratio(metric: str, value: float) -> float:
    ent = _PUBLISHED.get(metric)
    if ent is None:
        print(f"bench: WARNING metric {metric!r} has no BASELINE.json "
              "published entry (add one, or a null-value placeholder)",
              file=sys.stderr)
        return 0.0
    base = ent.get("value")
    if not base or value <= 0:
        return 0.0
    return (value / base if ent.get("direction", "higher") == "higher"
            else base / value)

# the device every row ran on (resolved once in main; stamped by _emit)
_DEVICE: dict = {}


def adaptive_min(sample, base: int, cap: int) -> float:
    """ONE definition of the stop policy every metric shares: take at
    least `base` samples, keep sampling while the minimum improves >2%
    (a noisy patch squeezes real windows out), stop at `cap`.
    sample() -> seconds for one run."""
    times: list[float] = []
    for i in range(cap):
        dt = sample()
        improved = not times or dt < min(times) * 0.98
        times.append(dt)
        if i + 1 >= base and not improved:
            break
    return min(times)


def best_window(fn, windows: int = 3, max_windows: int | None = None):
    """Best (minimum) wall time of fn() runs -- timeit's rationale: a
    one-chip machine shares its host's cores, whose other tenants can
    eat an entire timing window; contention only ever adds time, so the
    best window measures the engine and the others the neighbors."""

    def sample() -> float:
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    return adaptive_min(sample, windows, max_windows or 2 * windows)


def _tel_mark() -> tuple[int, float, float]:
    """Kernel-telemetry mark: (compiles, device_seconds, wall_t0). Take
    one per measured section; _emit(tel=mark) folds the deltas into the
    bench row so the perf trajectory separates compile cost from
    steady-state device time."""
    from tempo_tpu.util.kerneltel import TEL

    c, d = TEL.totals()
    return c, d, time.perf_counter()


def _tel_close(mark: tuple[int, float, float], workers: int = 1) -> dict:
    """Close a telemetry section at its end (call BEFORE unrelated work
    runs): compile count + share of the section's wall time the device
    spent executing (under sync timing; dispatch share otherwise) --
    distinguishes "slow because recompiling" from "slow kernel".

    `workers`: concurrent threads driving the device inside the section.
    Device seconds accumulate ACROSS threads while wall time doesn't, so
    a Q-wide concurrent section must divide by Q x wall or the share
    reads as Q-ish (BENCH_r06's search_concurrent reported 3.85)."""
    from tempo_tpu.util.kerneltel import TEL

    c0, d0, t0 = mark
    c1, d1 = TEL.totals()
    wall = (time.perf_counter() - t0) * max(1, workers)
    return {"compiles": c1 - c0,
            "device_time_share": round((d1 - d0) / wall, 4) if wall > 0 else 0.0}


def _emit(metric: str, value: float, unit: str,
          vs_baseline: float | None = None,
          tel: dict | tuple | None = None) -> None:
    if vs_baseline is None:  # resolve from the committed published map
        vs_baseline = _baseline_ratio(metric, float(value))
    row = {
        "metric": metric,
        "value": round(float(value), 4),
        "unit": unit,
        "vs_baseline": round(float(vs_baseline), 3),
        "device": _DEVICE,
    }
    if tel is not None:
        row.update(_tel_close(tel) if isinstance(tel, tuple) else tel)
    print(json.dumps(row), flush=True)


# ------------------------------------------------------------ benchmarks
def bench_analysis() -> None:
    """Static-checker cost, tracked beside kernel perf: the tier-1 gate
    runs on every CI pass, so its wall time is part of the build budget.
    The row carries rule and file counts so a scan-scope regression
    (rules silently skipping files) shows up as a trend break."""
    from tempo_tpu.analysis import RULES, default_root, run_analysis

    t0 = time.perf_counter()
    report = run_analysis(default_root())
    wall_ms = (time.perf_counter() - t0) * 1e3
    _emit("static_analysis_ms", wall_ms, "ms",
          tel={"rules": len(RULES), "files_scanned": report.files_scanned,
               "findings": len(report.findings),
               "suppressed": report.suppressed,
               "family_ms": {k: round(v, 1)
                             for k, v in sorted(report.family_ms.items())}})


def bench_kernel() -> None:
    import jax
    import jax.numpy as jnp

    from tempo_tpu.ops.filter import Cond, Operands, T_RES, T_SATTR, T_SPAN, eval_block

    rng = np.random.default_rng(42)
    N_SPANS, N_TRACES, N_RES = 1 << 22, 1 << 17, 1 << 10
    N_SATTR = N_SPANS * 2
    cols = {
        "span.trace_sid": rng.integers(0, N_TRACES, size=N_SPANS).astype(np.int32),
        "span.dur_us": rng.integers(0, 1_000_000, size=N_SPANS).astype(np.int32),
        "span.res_idx": rng.integers(0, N_RES, size=N_SPANS).astype(np.int32),
        "res.service_id": rng.integers(0, 64, size=N_RES).astype(np.int32),
        "sattr.span": np.sort(rng.integers(0, N_SPANS, size=N_SATTR)).astype(np.int32),
        "sattr.key_id": rng.integers(0, 100, size=N_SATTR).astype(np.int32),
        "sattr.vtype": np.zeros(N_SATTR, dtype=np.int32),
        "sattr.str_id": rng.integers(0, 5_000, size=N_SATTR).astype(np.int32),
    }
    dcols = {k: jax.device_put(jnp.asarray(v)) for k, v in cols.items()}
    conds = (
        Cond(target=T_RES, col="res.service_id", op="eq"),
        Cond(target=T_SPAN, col="span.dur_us", op="ge"),
        Cond(target=T_SATTR, col="str", op="eq"),
    )
    tree = ("and", ("cond", 0), ("cond", 1), ("cond", 2))

    def run(svc, dur, key, val):
        operands = Operands.build(
            [(0, svc, 0, 0.0, 0.0), (0, dur, 0, 0.0, 0.0), (key, val, 0, 0.0, 0.0)]
        )
        return eval_block((tree, conds), dcols, operands, N_SPANS, N_TRACES,
                          N_SPANS, N_RES, N_TRACES)

    mark = _tel_mark()
    jax.block_until_ready(run(1, 500_000, 3, 17))
    iters = 10

    def window():
        for i in range(iters):
            out = run(i % 64, 400_000 + i, i % 100, i % 5_000)
        jax.block_until_ready(out)

    # windows are ~0.1 s here, so sample generously: the kernel line is
    # the ceiling metric and must not record a neighbor's timeslice
    dt = best_window(window, windows=6, max_windows=15)
    tel = _tel_close(mark)
    sps = N_SPANS * iters / dt
    _emit("traceql_filter_kernel_spans_per_sec_per_chip", sps, "spans/s",
          sps / BASELINE_SPANS_PER_SEC, tel=tel)
    # roofline accounting: unique input column bytes the query touches
    # per iteration / kernel time, as a fraction of the chip's peak HBM
    # bandwidth -- says whether the kernel is near the memory roofline
    # or leaving headroom (the spans/s line alone has no denominator)
    bytes_touched = sum(v.nbytes for v in cols.values())
    bps = bytes_touched * iters / dt
    # the fraction CAN exceed 1.0: the bytes model counts every input
    # column per iteration, but across back-to-back queries XLA keeps
    # hot columns on-chip, so the kernel reads HBM less than once per
    # query. On the CPU (asked for explicitly) there is no roofline.
    from tempo_tpu.util.costmodel import HBM_PEAK_BPS

    peak = HBM_PEAK_BPS.get(_DEVICE["device_kind"])
    _emit("traceql_filter_kernel_bytes_per_sec", bps, "B/s",
          bps / peak if peak else 0.0, tel=tel)


def bench_mesh_1x1_overhead() -> None:
    """ROADMAP item 2a: what the stacked shard_map search program COSTS
    over the plain single-block kernel when the mesh buys nothing (a
    1x1 mesh = one device, no collectives). The value is the wall-time
    ratio mesh/plain (>1 = overhead; the fixed price of smap dispatch,
    operand stacking and the block axis), so mesh routing below this
    block count is pure loss. The row carries the per-collective comm
    bytes the PR-10 jaxpr walker priced for the mesh program -- on a
    1x1 mesh every ring term is x(k-1)=0, and the row PROVES that:
    nonzero bytes here would mean the walker is charging collectives
    that cannot move wire data."""
    import jax
    import jax.numpy as jnp

    from tempo_tpu.ops.filter import Cond, Operands, T_RES, T_SPAN, eval_block
    from tempo_tpu.parallel.mesh import make_mesh
    from tempo_tpu.parallel.search import sharded_search
    from tempo_tpu.util import costmodel

    rng = np.random.default_rng(21)
    N, NT, R = 1 << 20, 1 << 15, 1 << 10
    flat = {
        "span.trace_sid": rng.integers(0, NT, size=N).astype(np.int32),
        "span.dur_us": rng.integers(0, 1_000_000, size=N).astype(np.int32),
        "span.res_idx": rng.integers(0, R, size=N).astype(np.int32),
        "res.service_id": rng.integers(0, 64, size=R).astype(np.int32),
    }
    conds = (
        Cond(target=T_RES, col="res.service_id", op="eq"),
        Cond(target=T_SPAN, col="span.dur_us", op="ge"),
    )
    tree = ("and", ("cond", 0), ("cond", 1))
    operands = Operands.build([(0, 3, 0, 0.0, 0.0),
                               (0, 500_000, 0, 0.0, 0.0)])

    # plain kernel: the single-block device path (trace mask + counts)
    dcols = {k: jax.device_put(jnp.asarray(v)) for k, v in flat.items()}
    mark = _tel_mark()
    run_plain = lambda: eval_block(  # noqa: E731
        (tree, conds), dcols, operands, N, NT, N, R, NT, span_out=False)
    jax.block_until_ready(run_plain())
    iters = 8
    plain_s = best_window(
        lambda: jax.block_until_ready([run_plain() for _ in range(iters)]),
        windows=4) / iters

    # stacked mesh program on a 1x1 mesh: same rows as one (B=1) block.
    # Columns are device-put ONCE (sharded_search's jnp.asarray is a
    # no-op on resident arrays), matching the plain leg's staged dcols
    # -- the ratio must price the smap/stacking program overhead, not a
    # per-call host->device transfer the production staged-column path
    # never pays.
    mesh = make_mesh(1)
    stacked = {k: jax.device_put(jnp.asarray(v[None]))
               for k, v in flat.items()}
    n_spans = np.asarray([N], dtype=np.int32)
    tm, sc = sharded_search(mesh, tree, conds, operands, stacked, n_spans,
                            nt=NT)
    # correctness anchor: both engines agree on the trace verdicts
    ptm, psc = (np.asarray(x) for x in run_plain())
    assert (tm[0] == ptm).all() and (sc[0] == psc).all(), \
        "mesh and plain kernels disagree on a 1x1 mesh"
    mesh_s = best_window(
        lambda: [sharded_search(mesh, tree, conds, operands, stacked,
                                n_spans, nt=NT) for _ in range(iters)],
        windows=4) / iters
    tel = _tel_close(mark)

    # per-collective comm bytes from the costmodel's static jaxpr
    # walker (captured in the background on the program's first
    # compile); a 1x1 mesh must price every ring collective at 0 bytes
    costmodel.COST.drain(timeout=10.0)
    comm = costmodel.COST.comm_for("mesh_search", str(N))
    tel.update({
        "plain_ms": round(plain_s * 1e3, 3),
        "mesh_ms": round(mesh_s * 1e3, 3),
        "comm_bytes_per_launch": {c: int(b) for c, b in sorted(comm.items())},
        "comm_bytes_total": int(sum(comm.values())),
    })
    _emit("search_mesh_1x1_overhead", mesh_s / plain_s, "ratio", tel=tel)


def bench_find_and_search(tmp: str) -> tuple[float, float, dict, dict]:
    """BASELINE config #2 shape: a 10-block local backend holding the
    reference's own dataset size (~150 K traces / 10.4 M spans total,
    docs/design-proposals/2022-04 Parquet.md:211-218), searched through
    the PRODUCTION engine (TempoDB.search -> search_blocks_fused: the
    same path the frontend's block-batch jobs execute)."""
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest, search_block

    rng = np.random.default_rng(7)
    backend = LocalBackend(tmp + "/store")
    n_blocks, n_traces, spans_per = 10, 1 << 15, 32  # 10 x 1.05 M = 10.5 M spans
    metas, ids_per = [], []
    for _ in range(n_blocks):
        meta, ids = synth_block(backend, "bench", rng, n_traces, spans_per)
        metas.append(meta)
        ids_per.append(ids)
    total_spans = n_blocks * n_traces * spans_per

    db = TempoDB(TempoDBConfig(wal_path=tmp + "/wal"), backend=backend)
    db.poll_now()

    # --- find p50 (bloom gates + batched lookup + row materialization
    # across the 10-block backend). Steady-state: warm each block's
    # row-group chunk cache first (the production querier's long-lived
    # readers sit on hot caches; the reference's 0.18 s figure likewise
    # rides the OS page cache)
    mark = _tel_mark()
    group_traces = (1 << 16) // spans_per  # traces per 64Ki-span row group
    for b in range(n_blocks):
        for sid in range(0, n_traces, group_traces):
            assert db.find_trace_by_id("bench", ids_per[b][sid].tobytes()) is not None
    picks = rng.integers(0, n_traces, size=120)
    lat = []
    for i, p in enumerate(picks[20:]):
        tid = ids_per[i % n_blocks][int(p)].tobytes()
        t0 = time.perf_counter()
        got = db.find_trace_by_id("bench", tid)
        lat.append(time.perf_counter() - t0)
        assert got is not None
    _emit("find_trace_by_id_p50_ms", float(np.median(lat) * 1e3), "ms",
          tel=_tel_close(mark))

    # --- batched lookup, production auto path (the frontend ID-shard /
    # multi-block unit): on one chip this is the host vectorized
    # searchsorted engine (each device dispatch+fetch costs a full link
    # RTT); on a mesh the device kernel takes over (parallel/find.py)
    from tempo_tpu.ops.find import lookup_ids_blocks_cached

    blocks = [db.open_block(m) for m in metas]
    mark = _tel_mark()
    Q = 256
    qidx = rng.integers(0, n_traces, size=Q)
    qcodes = (ids_per[0][qidx].view(">u4").astype(np.int64) - 0x80000000).astype(np.int32).reshape(Q, 4)
    sids = lookup_ids_blocks_cached(blocks, qcodes)  # warm
    assert (sids[0] >= 0).all()
    iters_f = 10
    dt = best_window(
        lambda: [lookup_ids_blocks_cached(blocks, qcodes) for _ in range(iters_f)],
        windows=3)
    # ids RESOLVED per second (each call answers Q ids against all 10
    # blocks' indexes); the per-block bisection work is 10x that
    _emit("find_batched_device_ids_per_sec", Q * iters_f / dt, "ids/s",
          tel=_tel_close(mark))

    # --- find calibration race (ops/find.calibrate_find): measure both
    # engines over the same 10-block index set, commit the crossover to
    # a CostLedger artifact, then PROVE the `auto` policy consults it
    # (routing reason ledger_crossover). The row's value is the modeled
    # id-row count where the device engine starts winning.
    from tempo_tpu.ops.find import calibrate_find
    from tempo_tpu.util import costledger
    from tempo_tpu.util.kerneltel import TEL as _TEL

    costledger.configure(tmp + "/cost_ledger.json")
    mark = _tel_mark()
    entry = calibrate_find(blocks, qcodes, repeats=3)
    r0 = _TEL.routing_counts()
    auto_sids = lookup_ids_blocks_cached(blocks, qcodes, mode="auto")
    assert (auto_sids == sids).all(), "auto policy changed find results"
    r1 = _TEL.routing_counts()
    routed = [k for k, n in r1.items()
              if k[0] == "find" and n > r0.get(k, 0)]
    import jax as _jax

    want = "ledger_crossover" if len(_jax.devices()) == 1 else "mesh"
    assert any(k[2] == want for k in routed), (want, routed)
    tel = _tel_close(mark)
    tel.update({"winner": entry["winner"],
                "host_ms": round(entry["host_s"] * 1e3, 3),
                "device_ms": round(entry["device_s"] * 1e3, 3),
                "rows": entry["rows"], "queries": entry["queries"],
                "ledger": costledger.ledger().path})
    _emit("find_auto_crossover_rows", entry["crossover_rows"], "rows",
          tel=tel)

    # --- e2e search over the 10-block backend through TempoDB.search.
    # Correctness gate first: the fused device engine must agree with a
    # per-block host-engine scan.
    mark = _tel_mark()
    req = SearchRequest(tags={"service.name": "svc-003"},
                        min_duration_ms=100, limit=50)
    # touch 1 = host engine; touch 2 = staging upload; touch 3+ = pure
    # device (search_blocks_fused promote-on-second-touch policy)
    for _ in range(3):
        resp = db.search("bench", req)
    assert resp.inspected_spans == total_spans
    assert len(resp.traces) == req.limit
    # correctness gate: every trace the device engine returned must be a
    # REAL match -- materialize it and check the predicate holds on the
    # wire form (a span whose resource is svc-003, trace duration >=
    # min_duration) -- and the per-block host engine must agree on the
    # global newest-first frontier (within the 1 s device key granularity)
    for t in resp.traces:
        tr = db.find_trace_by_id("bench", bytes.fromhex(t.trace_id))
        assert tr is not None
        assert t.duration_ms >= req.min_duration_ms
        svcs = {rs.resource.attrs.get("service.name") for rs in tr.resource_spans}
        assert "svc-003" in svcs, svcs
    host_newest = []
    for m in metas:
        r = search_block(db.open_block(m), req, mode="host")
        host_newest.extend(r.traces)
    host_newest.sort(key=lambda t: -t.start_time_unix_nano)
    got_ids = {t.trace_id for t in resp.traces}
    cutoff = min(t.start_time_unix_nano for t in resp.traces)
    missed = [t for t in host_newest[: req.limit]
              if t.trace_id not in got_ids
              and t.start_time_unix_nano > cutoff + 1_000_000_000]
    assert not missed, f"device engine missed {len(missed)} strictly-newer matches"

    # cold: a fresh TempoDB + readers every iteration => every byte from
    # disk + zstd decode + filter. MIN per-iteration time (timeit's
    # methodology): the host's cores are shared and contention swings
    # individual iterations 2-3x; external noise only ever ADDS time.
    iters = 6
    smark = _stream_mark()
    n_cold = {"n": 0}

    def cold_sample() -> float:
        n_cold["n"] += 1
        dbc = TempoDB(TempoDBConfig(wal_path=tmp + "/wal"), backend=backend)
        dbc.poll_now()
        t0 = time.perf_counter()
        resp = dbc.search("bench", req)
        dt = time.perf_counter() - t0
        assert resp.inspected_spans == total_spans
        dbc.close()
        return dt

    cold = total_spans / adaptive_min(cold_sample, iters, 2 * iters)
    cold_tel = {**_tel_close(mark), **_stream_close(smark, per=n_cold["n"])}

    # cold find p50: fresh readers per lookup, so the bloom shard, the
    # trace index and the trace's row-group chunks all come off disk
    # through the pipeline's plan -> ranged-fetch -> threaded-decode
    # stages (colio plan_fetch/_run_plan)
    mark = _tel_mark()
    smark = _stream_mark()
    fpicks = rng.integers(0, n_traces, size=9)
    flat = []
    for i, p in enumerate(fpicks):
        dbf = TempoDB(TempoDBConfig(wal_path=tmp + "/wal"), backend=backend)
        dbf.poll_now()
        tid = ids_per[i % n_blocks][int(p)].tobytes()
        t0 = time.perf_counter()
        got = dbf.find_trace_by_id("bench", tid)
        flat.append(time.perf_counter() - t0)
        assert got is not None
        dbf.close()
    _emit("search_block_e2e_cold_find_p50_ms", float(np.median(flat) * 1e3),
          "ms",
          tel={**_tel_close(mark), **_stream_close(smark, per=len(flat))})
    mark = _tel_mark()

    # hot: long-lived readers (the production querier pattern over
    # immutable blocks) => staged device arrays cached; ~one device sync
    # per query. The reference's analog hot path still re-decodes
    # parquet pages from the OS page cache each query.
    def warm_sample() -> float:
        t0 = time.perf_counter()
        resp = db.search("bench", req)
        dt = time.perf_counter() - t0
        assert resp.inspected_spans == total_spans
        return dt

    warm = total_spans / adaptive_min(warm_sample, 2 * iters, 4 * iters)
    warm_tel = _tel_close(mark)

    # --- TraceQL metrics range query over the same 10-block backend
    # (db/metrics_exec): fused filter->bucketize->fold per block, device
    # for blocks whose staged columns are already hot. No reference
    # figure exists (the reference's traceql-metrics shipped unbenched),
    # so vs_baseline stays 0.0.
    from tempo_tpu.db.metrics_exec import align_params

    mark = _tel_mark()
    base_s = 1_700_000_000
    mreq = align_params(
        '{ span.http.status_code >= 200 } | rate() by(resource.service.name)',
        base_s, base_s + 3600, 60)
    mresp = db.metrics_query_range("bench", mreq)
    assert mresp.series, "metrics bench query matched nothing"
    total_counted = sum(int(s["count"].sum()) for s in mresp.series.values())
    assert total_counted > 0

    def metrics_sample() -> float:
        t0 = time.perf_counter()
        r = db.metrics_query_range("bench", mreq)
        dt = time.perf_counter() - t0
        assert r.inspected_spans == total_spans
        return dt

    msec = adaptive_min(metrics_sample, 4, 10)
    _emit("metrics_query_range_spans_per_sec", total_spans / msec, "spans/s",
          tel=_tel_close(mark))

    db.close()
    return cold, warm, cold_tel, warm_tel


def _stream_mark() -> dict:
    """Cold-read stream-pipeline telemetry mark (kerneltel stream stats)."""
    from tempo_tpu.util.kerneltel import TEL

    return TEL.stream_stats()


def _stream_close(mark: dict, per: int = 1) -> dict:
    """Close a cold-read section: per-query stage ms (fetch/decompress/
    assemble/upload) and the overlap ratio (stage seconds / pipeline
    wall seconds; >1 = stages of different units genuinely ran at the
    same time) -- the "where did the cold time go" row extension."""
    from tempo_tpu.util.kerneltel import TEL

    now = TEL.stream_stats()
    per = max(1, per)
    stage_s = {k: v - mark["stage_seconds"].get(k, 0.0)
               for k, v in now["stage_seconds"].items()}
    wall = now["wall_seconds"] - mark["wall_seconds"]
    return {"stream": {
        "runs": now["runs"] - mark["runs"],
        "units": now["units"] - mark["units"],
        "stage_ms_per_query": {k: round(v * 1000 / per, 2)
                               for k, v in stage_s.items()},
        "overlap_ratio": round(sum(stage_s.values()) / wall, 3) if wall > 0 else 0.0,
    }}


def _compact_mark() -> dict:
    """Compaction-pipeline telemetry mark (kerneltel compaction stats)."""
    from tempo_tpu.util.kerneltel import TEL

    return TEL.compaction_stats()


def _compact_close(mark: dict) -> dict:
    """Close a compaction section: PER-RUN averages (a section times the
    same job set over several best_window repetitions, so totals would
    be ~windows x the headline run) -- per-stage ms, overlap ratio
    (stage seconds / wall seconds; >1 = stages genuinely overlapped),
    peak jobs in flight and prefetch outcomes: the "where did the time
    go" row extension for the compaction metrics."""
    from tempo_tpu.util.kerneltel import TEL

    now = TEL.compaction_stats()
    runs = max(1, now["runs"] - mark["runs"])
    stage_s = {k: v - mark["stage_seconds"].get(k, 0.0)
               for k, v in now["stage_seconds"].items()}
    wall = now["wall_seconds"] - mark["wall_seconds"]
    return {"pipeline": {
        "runs": runs,
        "jobs_per_run": round((now["jobs"] - mark["jobs"]) / runs, 2),
        # run-scoped peak (reset per pipeline run): every window in a
        # section runs the same job set, so the last run's peak IS the
        # section's -- the lifetime max would leak across sections
        "max_jobs_inflight": now["run_max_jobs_inflight"],
        "stage_ms_per_run": {k: round(v * 1000 / runs, 1)
                             for k, v in stage_s.items()},
        "overlap_ratio": round(sum(stage_s.values()) / wall, 3) if wall > 0 else 0.0,
        "prefetch_per_run": {
            k: round((now["prefetch"].get(k, 0) - mark["prefetch"].get(k, 0)) / runs, 2)
            for k in now["prefetch"]},
    }}


def bench_compaction(tmp: str) -> None:
    """Two shapes, both through the pipelined concurrent executor
    (db/compact_pipeline; TEMPO_COMPACT_CONCURRENCY workers, >= 4 here):
    the realistic level-1 job (8 mid-size blocks, the compactor's
    steady-state diet) is the headline compaction_mb_per_sec; the
    adversarial many-tiny-blocks shape (per-block fixed costs dominate)
    runs as the production compactor sees it -- select_jobs-size batches
    of max_input_blocks executing concurrently through the admission
    gate, with concat part copies as backend-side hardlinks. Rows carry
    pipeline stats (jobs in flight, per-stage ms, overlap ratio,
    prefetch outcomes) so the snapshot shows where the time goes.
    Single-core-friendly host work by design -- the TPU plays no role in
    compaction."""
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db.compact_pipeline import CompactionPipeline, resolve_concurrency
    from tempo_tpu.db.compactor import CompactionJob, CompactorConfig

    rng = np.random.default_rng(11)
    cfg = CompactorConfig()
    # the canonical env parser, floored at the acceptance shape's >= 4
    conc = max(4, resolve_concurrency(cfg))

    backend = LocalBackend(tmp + "/cstore-realistic")
    metas = [synth_block(backend, "bench", rng, 1 << 14, 24, n_res=256)[0]
             for _ in range(8)]
    total = sum(m.size_bytes for m in metas)
    mark = _compact_mark()
    # best of 3 (same min-under-noise rationale as the search timings;
    # one run of this job is ~2 s, and any window can catch a neighbor)
    def job():
        outs = CompactionPipeline(backend, cfg, concurrency=conc).run(
            {"bench": [CompactionJob("bench", metas)]})
        assert outs[0].error is None, outs[0].error
        assert outs[0].result.traces_out == 8 * (1 << 14)

    best = best_window(job, windows=3)
    _emit("compaction_mb_per_sec", total / best / 1e6, "MB/s",
          tel=_compact_close(mark))

    backend2 = LocalBackend(tmp + "/cstore-small")
    metas2 = [synth_block(backend2, "bench", rng, 200, 8, n_res=16)[0]
              for _ in range(100)]
    total2 = sum(m.size_bytes for m in metas2)
    k = cfg.max_input_blocks
    jobs2 = [CompactionJob("bench", metas2[i:i + k])
             for i in range(0, len(metas2), k)]
    mark2 = _compact_mark()

    def job2():
        outs = CompactionPipeline(backend2, cfg, concurrency=conc).run(
            {"bench": jobs2})
        errs = [o.error for o in outs if o.error is not None]
        assert not errs, errs
        assert sum(o.result.traces_out for o in outs) == 100 * 200

    best2 = best_window(job2, windows=2)
    _emit("compaction_small_blocks_mb_per_sec", total2 / best2 / 1e6, "MB/s",
          tel=_compact_close(mark2))


def bench_ingest(tmp: str) -> None:
    """OTLP raw-bytes ingest through the production write path
    (push_raw: native structural scan + byte splice -> rate limit ->
    WAL append + live map), distributor-role shape (no generator tap --
    the tap is async and in production runs on other cores/hosts).
    vs_baseline is the ratio to the reference's 15 MB/s per-tenant
    ingest rate-limit default (modules/overrides/limits.go:92-99): >= 1
    means one tenant at the default limit can't saturate this path."""
    from tempo_tpu.services.app import App, AppConfig, IngesterConfig
    from tempo_tpu.util.testdata import make_traces
    from tempo_tpu.wire import otlp_pb

    cfg = AppConfig(
        target="all", http_port=0, storage_path=tmp + "/ingest-store",
        ingester=IngesterConfig(max_trace_idle_s=9999, max_block_age_s=9999,
                                flush_check_period_s=9999),
    )
    app = App(cfg)
    app.start()
    try:
        app.distributor.generator_forward = None
        app.distributor.generator_ring = None
        tenant = app.tenant_of({})
        traces = make_traces(200, seed=3, n_spans=20)
        payloads = [otlp_pb.encode_trace(t) for _, t in traces]
        raw_bytes = sum(len(p) for p in payloads)
        # collectors batch: one export request carries many traces
        # (concatenated Export payloads are protobuf-valid), and the
        # columnar WAL turns each window into ONE framed record
        per_window = 40
        windows = [b"".join(payloads[i:i + per_window])
                   for i in range(0, len(payloads), per_window)]
        app.distributor.push_raw(tenant, windows[0])  # warm
        iters = 2

        def window():
            for _ in range(iters):
                for p in windows:
                    app.distributor.push_raw(tenant, p)

        dt = best_window(window, windows=3)
        mbs = raw_bytes * iters / dt / 1e6

        # per-stage breakdown (ISSUE 16): one more measured pass, then a
        # staging refresh + forced cut/flush so every write-path stage
        # records into the kerneltel ingest ledger
        from tempo_tpu.util.kerneltel import TEL

        def _stage_s(stats: dict) -> dict:
            return {k: v["seconds"] for k, v in stats["stages"].items()}

        inst = app.ingester.instance(tenant)
        if inst.live_engine is not None:  # drain the timing passes' backlog
            inst.live_engine.maybe_refresh()
        app.ingester.sweep_all(force=True)
        s0 = _stage_s(TEL.ingest_stats())
        window()
        if inst.live_engine is not None:
            inst.live_engine.maybe_refresh()
        app.ingester.sweep_all(force=True)
        s1 = _stage_s(TEL.ingest_stats())
        tel = {f"{st}_ms": round((s1.get(st, 0.0) - s0.get(st, 0.0)) * 1e3, 2)
               for st in ("decode", "wal_append", "stage_delta", "cut", "flush")}
        _emit("ingest_otlp_mb_per_sec", mbs, "MB/s", mbs / 15.0, tel=tel)
    finally:
        app.stop()


def bench_search_concurrent(tmp: str) -> None:
    """Cross-query batching executor (db/batchexec): Q parallel
    identical-shape queries against ONE hot staged block. Reports
    per-query p50/p95 latency plus launches-per-query and batch
    occupancy from kernel telemetry -- the sequential comparable is 2
    launches per query (filter + select); a healthy batcher lands well
    under 1."""
    from concurrent.futures import ThreadPoolExecutor

    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest
    from tempo_tpu.util.kerneltel import TEL

    rng = np.random.default_rng(23)
    backend = LocalBackend(tmp + "/store-conc")
    meta, _ = synth_block(backend, "bench", rng, 1 << 15, 32)  # 1.05 M spans
    db = TempoDB(
        TempoDBConfig(wal_path=tmp + "/wal-conc", device_promote_touches=1),
        backend=backend)
    db.poll_now()
    req = SearchRequest(query="{ duration > 100ms }", limit=20)
    Q, iters = 16, 3

    def one(_):
        t0 = time.perf_counter()
        r = db.search_blocks("bench", [meta], req)
        assert r.traces
        return time.perf_counter() - t0

    with ThreadPoolExecutor(Q) as ex:  # warm: staging + both compiles
        list(ex.map(one, range(Q)))
    mark = _tel_mark()
    l0 = TEL.launch_count()
    s0 = TEL.batch_stats().get("search", {"groups": 0, "queries": 0})
    lats: list[float] = []
    for _ in range(iters):
        with ThreadPoolExecutor(Q) as ex:
            lats.extend(ex.map(one, range(Q)))
    launches = TEL.launch_count() - l0
    s1 = TEL.batch_stats().get("search", {"groups": 0, "queries": 0})
    groups = s1["groups"] - s0.get("groups", 0)
    queries = s1["queries"] - s0.get("queries", 0)
    tel = _tel_close(mark, workers=Q)
    tel.update({
        "p95_ms": round(float(np.percentile(lats, 95)) * 1e3, 3),
        "launches_per_query": round(launches / (Q * iters), 3),
        "batch_occupancy": round(queries / groups, 2) if groups else 0.0,
    })

    # tracing-on overhead on the SAME warm batched shape: the timeline
    # spine's hot-path cost is clock reads + locked appends, so this
    # ratio must stay ~1.0 (the test suite asserts < 1.05). Off and on
    # legs are INTERLEAVED round by round (the test_selftrace median
    # scheme): this shared box drifts minute to minute, and back-to-back
    # homogeneous legs read the drift as overhead (BENCH_r06 shipped
    # ratios of 0.64 and 0.44 -- "tracing speeds you up" is a timing
    # artifact, not a result).
    from tempo_tpu.services.selftrace import SelfTracer

    st = SelfTracer(lambda tenant, rss: None)

    def one_traced(_):
        with st.trace("bench") as t:
            token = TEL.set_active_trace(t)
            t0 = time.perf_counter()
            try:
                db.search_blocks("bench", [meta], req)
            finally:
                TEL.reset_active_trace(token)
            return time.perf_counter() - t0

    def batch(fn) -> list[float]:
        with ThreadPoolExecutor(Q) as ex:
            return list(ex.map(fn, range(Q)))

    def interleaved_ratio(off_fn, on_fn, rounds: int = 4) -> float:
        offs: list[float] = []
        ons: list[float] = []
        for _ in range(rounds):
            offs.extend(batch(off_fn))
            ons.extend(batch(on_fn))
        return round(
            float(np.median(ons)) / max(float(np.median(offs)), 1e-9), 4)

    tel["selftrace_overhead_ratio"] = interleaved_ratio(one, one_traced)

    # always-on profiler overhead on the same warm batched shape: the
    # background sampler is ~19 Hz of raw stack walks, so this ratio
    # must stay under the 1.02x gate. Same interleaving: the sampler
    # starts and stops around each ON leg so off legs in the same round
    # are the true contemporaneous comparable.
    from tempo_tpu.util.profiler import PROF

    def batch_profiled(_i):
        return one(_i)

    def profiled_round() -> list[float]:
        PROF.start(hz=19.0)
        try:
            return batch(batch_profiled)
        finally:
            PROF.stop()

    offs_p: list[float] = []
    ons_p: list[float] = []
    for _ in range(4):
        offs_p.extend(batch(one))
        ons_p.extend(profiled_round())
    tel["profile_overhead_ratio"] = round(
        float(np.median(ons_p)) / max(float(np.median(offs_p)), 1e-9), 4)
    _emit("search_concurrent_p50_ms", float(np.median(lats)) * 1e3, "ms",
          tel=tel)
    db.close()


def bench_search_live(tmp: str) -> None:
    """Live-head device engine (db/live_engine): N live traces in one
    ingester instance, C concurrent searches -- device engine vs the
    host index walk (the differential oracle), plus the staging-lag
    stat (push -> device-visible ms) from kernel telemetry."""
    import os
    import random as _random
    from concurrent.futures import ThreadPoolExecutor

    from tempo_tpu.backend import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest
    from tempo_tpu.db.wal import WAL
    from tempo_tpu.services.ingester import Ingester, IngesterConfig
    from tempo_tpu.services.overrides import Overrides
    from tempo_tpu.util.kerneltel import TEL
    from tempo_tpu.util.testdata import make_trace, make_trace_id
    from tempo_tpu.wire.segment import segment_for_write

    db = TempoDB(TempoDBConfig(wal_path=tmp + "/wal-live-db"),
                 backend=MemBackend())
    ing = Ingester(WAL(tmp + "/wal-live"), db, Overrides(), IngesterConfig())
    inst = ing.instance("bench")
    rng = _random.Random(17)
    n_traces, C, iters = 2000, 8, 3
    lag0 = TEL.livestage_stats()
    for i in range(n_traces):
        tid = make_trace_id(rng)
        tr = make_trace(rng, trace_id=tid, n_spans=4,
                        base_time_ns=1_700_000_000_000_000_000 + i * 10**9)
        lo, hi = tr.time_range_nanos()
        s, e = lo // 10**9, hi // 10**9 + 1
        inst.push_segments([(tid, s, e, segment_for_write(tr, s, e))])
    reqs = [SearchRequest(tags={"service.name": "db"}, limit=20),
            SearchRequest(tags={"name": "GET /api"}, limit=20),
            SearchRequest(min_duration_ms=200, limit=20)]

    def run_engine(engine: str) -> list[float]:
        prev = os.environ.get("TEMPO_LIVE_ENGINE")
        os.environ["TEMPO_LIVE_ENGINE"] = engine
        try:
            inst.search_live(reqs[0])  # warm: staging upload + compiles

            def one(i):
                t0 = time.perf_counter()
                r = inst.search_live(reqs[i % len(reqs)])
                assert r.traces
                return time.perf_counter() - t0

            lats: list[float] = []
            for _ in range(iters):
                with ThreadPoolExecutor(C) as ex:
                    lats.extend(ex.map(one, range(C)))
            return lats
        finally:
            if prev is None:  # restore whatever the operator forced
                del os.environ["TEMPO_LIVE_ENGINE"]
            else:
                os.environ["TEMPO_LIVE_ENGINE"] = prev

    mark = _tel_mark()
    dev = run_engine("device")
    host = run_engine("index")
    lag1 = TEL.livestage_stats()
    lag_ms = 0.0
    if lag1["lag_count"] > lag0["lag_count"]:
        lag_ms = ((lag1["lag_avg_s"] * lag1["lag_count"]
                   - lag0["lag_avg_s"] * lag0["lag_count"])
                  / (lag1["lag_count"] - lag0["lag_count"]) * 1e3)
    tel = _tel_close(mark, workers=C)
    tel.update({
        "host_index_p50_ms": round(float(np.median(host)) * 1e3, 3),
        "p95_ms": round(float(np.percentile(dev, 95)) * 1e3, 3),
        "staging_lag_ms": round(lag_ms, 2),
        "live_traces": n_traces,
        "crossover_rows": inst.live_engine.stats()["crossover_rows"],
    })
    _emit("search_live_p50_ms", float(np.median(dev)) * 1e3, "ms",
          tel=tel)
    db.close()


def bench_search_affinity(tmp: str) -> None:
    """Cache-affinity scheduling differential (services/frontend): a
    dispatcher-only frontend + 3 simulated remote querier workers, each
    with its OWN TempoDB over one shared backend -- its own staged-cache
    domain, the in-process analog of 3 chips' HBM. 4 tenants' blocks,
    50 concurrent mixed-tenant searches with Zipf skew, and the staged
    device budget pinched to ~1.35x ONE fleet copy of the working set,
    so placement-blind dequeue (affinity off) duplicates staged columns
    across workers and thrashes the cache while block->querier affinity
    keeps each block staged on exactly one worker. Reports p99 and
    fleet staged-cache hit rate for both modes plus the re-upload bytes
    affinity avoided -- the differential soak gate's numbers."""
    import gc
    import threading as th
    from concurrent.futures import ThreadPoolExecutor

    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest
    from tempo_tpu.ops import stage as stage_mod
    from tempo_tpu.services.frontend import Frontend
    from tempo_tpu.services.querier import Querier
    from tempo_tpu.services.worker import execute_job
    from tempo_tpu.util.kerneltel import TEL

    rng = np.random.default_rng(31)
    backend = LocalBackend(tmp + "/store-aff")
    fleet, n_tenants, concurrency, n_queries = 3, 4, 50, 150
    tenants = [f"tenant-{i}" for i in range(n_tenants)]
    for t in tenants:
        for _ in range(2):
            synth_block(backend, t, rng, 1 << 12, 8, n_res=64)
    req = SearchRequest(query="{ duration > 100ms }", limit=10)

    def new_db():
        db = TempoDB(TempoDBConfig(wal_path=tmp + "/wal-aff",
                                   device_promote_touches=1), backend=backend)
        db.poll_now()
        return db

    # measure ONE fleet copy of the staged working set, then pinch the
    # budget: without pressure, placement-blind routing eventually warms
    # every worker and the differential vanishes -- with it, off-mode
    # duplication evicts and re-uploads forever (the million-user shape,
    # where the working set never fits every chip)
    old_budget = stage_mod.staged_cache_stats()["budget_bytes"]
    stage_mod.set_staged_cache_budget(0)  # drop earlier benches' entries
    stage_mod.set_staged_cache_budget(old_budget)
    probe = new_db()
    base = stage_mod.staged_cache_stats()["bytes"]
    for t in tenants:
        probe.search(t, req)  # stages both of t's blocks (promote=1)
    footprint = stage_mod.staged_cache_stats()["bytes"] - base
    probe.close()
    del probe
    gc.collect()
    budget = max(1 << 20, int(footprint * 1.35))
    stage_mod.set_staged_cache_budget(budget)

    zipf = np.array([1.0 / (i + 1) ** 1.1 for i in range(n_tenants)])
    q_tenants = rng.choice(n_tenants, size=n_queries, p=zipf / zipf.sum())

    def run_mode(affinity: bool) -> dict:
        fe_db = new_db()
        fe = Frontend(Querier(fe_db, ring=None, client_for=lambda a: None),
                      n_workers=0, hedge_after_s=0.0,
                      affinity=affinity, affinity_steal_ms=75.0)
        worker_dbs = [new_db() for _ in range(fleet)]
        queriers = [Querier(db, ring=None, client_for=lambda a: None)
                    for db in worker_dbs]
        stop = th.Event()

        def wloop(wid: int):
            qr = queriers[wid]
            while not stop.is_set():
                job = fe.poll_job(wait_s=0.25, worker_id=f"w{wid}")
                if job is None:
                    continue
                tok = TEL.set_affinity_placement(job.get("placement", ""))
                try:
                    try:
                        res = execute_job(qr, job.get("tenant", ""),
                                          job["kind"], job["payload"])
                        fe.complete_job(job["id"], ok=True, result=res)
                    except Exception as e:  # noqa: BLE001 - frontend retries
                        fe.complete_job(job["id"], ok=False, error=str(e),
                                        retryable=True)
                finally:
                    TEL.reset_affinity_placement(tok)

        threads = [th.Thread(target=wloop, args=(i,), daemon=True)
                   for i in range(fleet)]
        for t in threads:
            t.start()
        h0, m0 = TEL.staged_cache_hits.get(), TEL.staged_cache_misses.get()
        b0 = TEL.transfer_bytes.get()
        lats: list[float] = []
        lat_lock = th.Lock()

        def one(i: int):
            tenant = tenants[int(q_tenants[i])]
            t0 = time.perf_counter()
            r = fe.search(tenant, req)
            dt = time.perf_counter() - t0
            assert r.traces
            with lat_lock:
                lats.append(dt)

        with ThreadPoolExecutor(concurrency) as ex:
            list(ex.map(one, range(n_queries)))
        stop.set()
        for t in threads:
            t.join(timeout=5)
        fe.stop()
        hits = TEL.staged_cache_hits.get() - h0
        misses = TEL.staged_cache_misses.get() - m0
        upload = TEL.transfer_bytes.get() - b0
        fe_db.close()
        for db in worker_dbs:
            db.close()
        gc.collect()  # free this fleet's staged entries before the next
        return {
            "p50_ms": round(float(np.median(lats)) * 1e3, 2),
            "p99_ms": round(float(np.percentile(lats, 99)) * 1e3, 2),
            "staged_hit_rate": round(hits / (hits + misses), 4)
                               if hits + misses else 0.0,
            "upload_bytes": int(upload),
        }

    a0 = TEL.affinity_stats()["jobs"]
    on = run_mode(True)
    a1 = TEL.affinity_stats()["jobs"]
    off = run_mode(False)
    stage_mod.set_staged_cache_budget(old_budget)
    tel = {
        "affinity_on": on,
        "affinity_off": off,
        "placements_on": {k: a1.get(k, 0) - a0.get(k, 0)
                          for k in sorted(set(a0) | set(a1))},
        "reupload_bytes_avoided": max(
            0, off["upload_bytes"] - on["upload_bytes"]),
        "workers": fleet, "tenants": n_tenants, "concurrency": concurrency,
        "staged_budget_bytes": budget,
    }
    _emit("search_affinity_p99_ms", on["p99_ms"], "ms", tel=tel)


# mesh-batched probe: runs in a FRESH interpreter with 8 virtual CPU
# devices (a CPU child never needs the chip this process holds) and
# reports COUNTS ONLY -- virtual devices share the host's cores, so a
# wall time there says nothing about chips. (1) one admission window's
# 16 queries as ONE Q-programs x sharded-rows mesh launch
# (parallel/multiquery): launches/query, occupancy and the walker's
# comm bytes/query; (2) the struct-op collective shrink: the
# walker-priced per-node comm bytes of the packed '>' struct program vs
# the legacy triple-gather program.
_MESH_BATCH_PROBE = r"""
import json, os
import numpy as np
import tempfile
from tempo_tpu.util.testdata import synth_block
from tempo_tpu.backend.mem import MemBackend
from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
from tempo_tpu.db.search import SearchRequest, search_block, _plan_for_block
from tempo_tpu.db.batchexec import batched_search_block_many
from tempo_tpu.ops.filter import Cond, Operands, T_SPAN, required_columns
from tempo_tpu.ops.multiquery import _p2, lower_plan, pack_queries
from tempo_tpu.ops.stage import stage_block
from tempo_tpu.parallel import make_mesh
from tempo_tpu.parallel.multiquery import mesh_eval_multiquery
from tempo_tpu.parallel.search import sharded_search
from tempo_tpu.util import costmodel
from tempo_tpu.util.kerneltel import TEL

rng = np.random.default_rng(37)
backend = MemBackend()
meta, _ = synth_block(backend, "bench", rng, 1 << 14, 16)  # 256Ki spans
db = TempoDB(TempoDBConfig(wal_path=tempfile.mkdtemp(),
                           device_promote_touches=1), backend=backend)
db.poll_now()
blk = db.open_block(meta)
mesh = make_mesh()
assert mesh.devices.size > 1, "probe needs the virtual-device mesh"
Q = 16
reqs = [SearchRequest(query="{ duration > %dms }" % (100 + i), limit=20)
        for i in range(Q)]

# end-to-end identity + occupancy through the REAL admission window
warm = batched_search_block_many(db.batchers.search, [(blk, reqs[0], None)],
                                 promote_touches=1)
outs = batched_search_block_many(db.batchers.search,
                                 [(blk, r, None) for r in reqs],
                                 promote_touches=1)
d = lambda r: [{**t.to_dict(), "matchedSpans": t.matched_spans}
               for t in r.traces]
for r, o in zip(reqs, outs):
    assert d(o) == d(search_block(blk, r)), "mesh-batched != sequential"
occupancy = TEL.mesh_batch_stats()["occupancy"]

# kernel-level leg: the SAME 16 programs as one batched mesh launch
lowered = [lower_plan(_plan_for_block(blk, r)) for r in reqs]
assert all(lq is not None for lq in lowered)
p0 = _plan_for_block(blk, reqs[0])
needed = required_columns(p0.conds) + list(p0.extra_cols)
staged = stage_block(blk, needed + ["trace.start_ms"])
q_b = _p2(Q, lo=1)
progs = pack_queries(lowered, q_b)
l0 = TEL.launch_count()
mesh_eval_multiquery(mesh, lowered, staged, progs)
batched_launches = TEL.launch_count() - l0
costmodel.COST.drain(30.0)
comm = costmodel.COST.comm_for("mesh_multiquery", str(staged.n_spans_b))

# struct-op collective shrink: '>' node, packed vs legacy walker bytes
B, S, NT = 2, 1 << 15, 1 << 10
scols = {
    "span.trace_sid": np.sort(
        rng.integers(0, NT, size=(B, S)).astype(np.int32), axis=1),
    "span.dur_us": rng.integers(0, 1000, size=(B, S)).astype(np.int32),
    "span.parent_idx": np.where(
        np.arange(S)[None, :] % 8 == 0, -1,
        np.arange(S, dtype=np.int32)[None, :] - 1) * np.ones((B, 1), np.int32),
}
n_spans = np.asarray([S, S - 1000], np.int32)
sconds = (Cond(target=T_SPAN, col="span.dur_us", op="lt"),
          Cond(target=T_SPAN, col="span.dur_us", op="ge"))
sops = Operands.build([(0, 900, 0, 0.0, 0.0), (0, 50, 0, 0.0, 0.0)])
stree = ("struct", ">", ("cond", 0), ("cond", 1))
os.environ["TEMPO_STRUCT_PACK"] = "1"
tm1, sc1 = sharded_search(mesh, stree, sconds, sops, scols, n_spans, nt=NT)
os.environ["TEMPO_STRUCT_PACK"] = "0"
tm0, sc0 = sharded_search(mesh, stree, sconds, sops, scols, n_spans, nt=NT)
assert (tm1 == tm0).all() and (sc1 == sc0).all(), "struct shrink changed results"
del os.environ["TEMPO_STRUCT_PACK"]
drained = costmodel.COST.drain(30.0)
packed = costmodel.COST.comm_for("mesh_search", str(S))
legacy = costmodel.COST.comm_for("mesh_search_nopack", str(S))
db.close()
# comm rows may be absent (TEMPO_COSTMODEL=0 kill switch, or a drain
# timeout on a loaded box): report 0.0 rather than aborting the bench
shrink = (legacy["all_gather"] / packed["all_gather"]
          if drained and packed.get("all_gather") and legacy.get("all_gather")
          else 0.0)
print(json.dumps({
    "devices": int(mesh.devices.size),
    "launches_per_query": batched_launches / Q,
    "occupancy": occupancy,
    "comm_bytes_per_query": sum(comm.values()) / Q,
    "comm_bytes_per_launch": {c: int(b) for c, b in sorted(comm.items())},
    "struct_before": {c: int(b) for c, b in sorted(legacy.items())},
    "struct_after": {c: int(b) for c, b in sorted(packed.items())},
    "struct_node_shrink": shrink,
}))
"""


def bench_mesh_batched(tmp: str) -> None:
    """search_mesh_batched_virtual_cpu: launches per query when one
    admission window's 16 queries ride ONE batched mesh launch (1/16 =
    fully batched), with occupancy and walker comm bytes attached.
    search_struct_comm_shrink: walker-priced per-struct-node comm bytes
    before/after the bit-packed + hoisted gathers (the acceptance gate
    is >= 5x). Counts only, from a subprocess with 8 virtual CPU
    devices; the timed mesh rows wait for the four-chip host (ROADMAP
    A4/B8)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = " ".join(f for f in env.get("XLA_FLAGS", "").split()
                     if not f.startswith("--xla_force_host_platform_device_count"))
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
    proc = subprocess.run(
        [sys.executable, "-c", _MESH_BATCH_PROBE],
        capture_output=True, text=True, timeout=1200, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    assert proc.returncode == 0, proc.stderr[-3000:]
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    virtual = {"platform": "cpu", "device_kind": "virtual cpu",
               "count": row["devices"]}
    _emit("search_mesh_batched_virtual_cpu", row["launches_per_query"],
          "launches/query",
          tel={"device": virtual,
               "occupancy": row["occupancy"],
               "comm_bytes_per_query": round(row["comm_bytes_per_query"], 1),
               "comm_bytes_per_launch": row["comm_bytes_per_launch"]})
    _emit("search_struct_comm_shrink", row["struct_node_shrink"], "ratio",
          tel={"device": virtual,
               "comm_before": row["struct_before"],
               "comm_after": row["struct_after"]})


def bench_spanmetrics() -> None:
    import jax

    from tempo_tpu.ops.reduce import span_metrics_reduce

    rng = np.random.default_rng(13)
    N, S = 1 << 22, 4096
    sid = rng.integers(0, S, size=N).astype(np.int32)
    dur = rng.random(N).astype(np.float32) * 10.0
    edges = tuple(float(2.0 ** (i - 6)) for i in range(14))
    mark = _tel_mark()
    span_metrics_reduce(sid, dur, S, edges)  # compile
    iters = 5
    dt = best_window(
        lambda: [span_metrics_reduce(sid, dur, S, edges) for _ in range(iters)],
        windows=3)
    _emit("spanmetrics_reduce_spans_per_sec", N * iters / dt, "spans/s",
          tel=_tel_close(mark))


def bench_generator_tap(tmp: str) -> None:
    """Streaming metrics-generator plane (services/generator): the
    PR-17 device reduction path the distributor tap feeds with the
    ingest decode's own coded columns. Two rows:

    - spanmetrics_streaming_spans_per_sec: push_window end to end over
      one coded window -- vectorized packed-key series assembly against
      the LiveDict, device segmented reduce, registry fold.
    - service_graph_edges_per_sec: client/server windows paired through
      the coded edge store ((trace, span/parent) keys), batched through
      the fused edge_metrics_reduce kernel.

    The tel on the edge row carries the zero-extra-decode proof: a real
    App window pushed through distributor -> tap -> generator with the
    columnar cache's decode counter unchanged beyond the ingest decode
    itself (the tap re-uses cached SegFeatures; extra_decodes must be
    0)."""
    from tempo_tpu.ingest.columnar import LiveDict, SpanColumns
    from tempo_tpu.services.generator import MetricsGenerator
    from tempo_tpu.services.overrides import Overrides

    rng = np.random.default_rng(41)
    ld = LiveDict()
    svc_codes = np.asarray([ld.code(f"svc-{i:03d}") for i in range(32)],
                           np.int32)
    name_codes = np.asarray([ld.code(f"op-{i:03d}") for i in range(128)],
                            np.int32)

    # --- span-metrics leg: one realistic coded window per push
    N = 1 << 16
    cols_sm = SpanColumns(
        svc_code=rng.choice(svc_codes, size=N).astype(np.int32),
        name_code=rng.choice(name_codes, size=N).astype(np.int32),
        kind=rng.integers(1, 6, size=N).astype(np.int32),
        status=(rng.random(N) < 0.05).astype(np.int32) * 2,
        dur_s=(rng.random(N).astype(np.float32) * 2.0),
        edge_key=np.zeros(N, np.uint64),
        tid_hex="00" * 16)
    gen = MetricsGenerator(Overrides())
    gen.push_window("bench", [cols_sm], ld)  # warm: compiles + series
    iters = 4
    mark = _tel_mark()
    dt = best_window(
        lambda: [gen.push_window("bench", [cols_sm], ld)
                 for _ in range(iters)], windows=3)
    _emit("spanmetrics_streaming_spans_per_sec", N * iters / dt, "spans/s",
          tel=_tel_close(mark))

    # --- service-graph leg: every window completes E edges (the client
    # part opens them, the server part in the same window closes them,
    # so the pending store drains back to empty each push)
    E = 1 << 14
    ekeys = np.arange(1, E + 1, dtype=np.uint64)
    cols_client = SpanColumns(
        svc_code=rng.choice(svc_codes, size=E).astype(np.int32),
        name_code=rng.choice(name_codes, size=E).astype(np.int32),
        kind=np.full(E, 3, np.int32), status=np.zeros(E, np.int32),
        dur_s=(rng.random(E).astype(np.float32) * 2.0),
        edge_key=ekeys, tid_hex="00" * 16)
    cols_server = SpanColumns(
        svc_code=rng.choice(svc_codes, size=E).astype(np.int32),
        name_code=rng.choice(name_codes, size=E).astype(np.int32),
        kind=np.full(E, 2, np.int32),
        status=(rng.random(E) < 0.05).astype(np.int32) * 2,
        dur_s=(rng.random(E).astype(np.float32) * 2.0),
        edge_key=ekeys, tid_hex="00" * 16)
    gen2 = MetricsGenerator(Overrides())
    gen2.push_window("bench", [cols_client, cols_server], ld)  # warm
    sg = gen2._procs("bench")["service-graphs"]
    assert not sg.pending, "paired window left edges pending"
    mark = _tel_mark()
    dt = best_window(
        lambda: [gen2.push_window("bench", [cols_client, cols_server], ld)
                 for _ in range(iters)], windows=3)
    tel = _tel_close(mark)

    # --- zero-extra-decode proof through the REAL tap (App write path)
    from tempo_tpu.services.app import App, AppConfig, IngesterConfig
    from tempo_tpu.util.testdata import make_traces
    from tempo_tpu.wire import otlp_pb

    cfg = AppConfig(
        target="all", http_port=0, storage_path=tmp + "/gen-store",
        ingester=IngesterConfig(max_trace_idle_s=9999, max_block_age_s=9999,
                                flush_check_period_s=9999),
    )
    app = App(cfg)
    app.start()
    try:
        tenant = app.tenant_of({})
        for _, tr in make_traces(16, seed=5, n_spans=8):
            app.distributor.push_raw(tenant, otlp_pb.encode_trace(tr))
        app.distributor.flush_generator_tap()
        st = app.ingester.instance(tenant).columnar.stats()
        series = sum(1 for line in app.generator.metrics_text()
                     if line.startswith("traces_spanmetrics_calls_total"))
        extra = st["decodes"] - st["cached"]
        assert extra == 0, f"tap cost {extra} extra decodes: {st}"
        assert series > 0, "tap produced no generated series"
        tel.update({"tap_segments": st["cached"],
                    "tap_decodes": st["decodes"],
                    "tap_extra_decodes": extra,
                    "tap_series": series})
    finally:
        app.stop()
    _emit("service_graph_edges_per_sec", E * iters / dt, "edges/s", tel=tel)


def bench_caching(tmp: str) -> None:
    """The tiered cache plane, two rows:

    - search_result_cache_hit_p50_ms: p50 of a repeated search through
      the frontend once the result cache holds the entry -- the
      dashboard-refresh hot path, admitted AHEAD of the QoS queue. The
      tel carries the zero-work proof: device launches during the
      measured hits must be 0.
    - chunk_cache_restage_speedup: stage_block served from the host
      chunk pool (a demoted, recompressed HBM eviction victim) vs the
      cold path (backend ranged read + decode + pad + upload) on the
      same (block, columns) entry. The acceptance bar is >= 3x.
    """
    from tempo_tpu.services.app import App, AppConfig, IngesterConfig
    from tempo_tpu.util.kerneltel import TEL
    from tempo_tpu.util.testdata import make_traces
    from tempo_tpu.wire import otlp_pb

    cfg = AppConfig(
        target="all", http_port=0, storage_path=tmp + "/cache-store",
        compaction_cycle_s=9999,
        ingester=IngesterConfig(max_trace_idle_s=0.0, max_block_age_s=0.0,
                                flush_check_period_s=9999),
    )
    app = App(cfg)
    app.start()
    try:
        from tempo_tpu.db.search import SearchRequest

        tenant = app.tenant_of({})
        for _, tr in make_traces(64, seed=7, n_spans=8):
            app.distributor.push_raw(tenant, otlp_pb.encode_trace(tr))
        app.ingester.flush_all()
        app.db.poll_now()
        req = SearchRequest(query="{ true }", limit=20)
        r0 = app.frontend.search(tenant, req)  # miss: executes + stores
        assert r0.traces, "bench corpus not searchable"
        app.frontend.search(tenant, req)  # warm: first hit
        rc = app.frontend.result_cache
        assert rc is not None and rc.stats_hits >= 1, \
            "result cache did not hit on the repeat"
        l0 = TEL.launch_count()
        lats: list[float] = []
        for _ in range(400):
            t0 = time.perf_counter()
            app.frontend.search(tenant, req)
            lats.append(time.perf_counter() - t0)
        launches = TEL.launch_count() - l0
        assert launches == 0, f"cache hits launched {launches} kernels"
    finally:
        app.stop()
    _emit("search_result_cache_hit_p50_ms",
          float(np.percentile(lats, 50)) * 1e3, "ms",
          tel={"hits": len(lats),
               "p95_ms": round(float(np.percentile(lats, 95)) * 1e3, 4),
               "device_launches_during_hits": launches})

    # --- chunk-tier restage vs cold stage, same entry
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.ops import chunkpool
    from tempo_tpu.ops.filter import Cond, required_columns
    from tempo_tpu.ops.stage import (
        pool_holds,
        set_staged_cache_budget,
        stage_block,
    )

    rng = np.random.default_rng(29)
    backend = LocalBackend(tmp + "/store-chunk")
    meta_a, _ = synth_block(backend, "bench", rng, 1 << 14, 24)
    meta_b, _ = synth_block(backend, "bench", rng, 1 << 14, 24)
    db = TempoDB(TempoDBConfig(wal_path=tmp + "/wal-chunk"), backend=backend)
    db.poll_now()
    blk_a, blk_b = db.open_block(meta_a), db.open_block(meta_b)
    needed = required_columns(
        (Cond(target="res", col="res.service_id", op="eq"),))

    # cold leg: a FRESH reader per sample (the pack object keeps its
    # own decoded-chunk/column caches, which a warm reader would serve
    # from) and cache=False to skip the HBM store and the pool probe --
    # every sample pays footer + ranged reads + decode + pad + upload,
    # the exact work a pool hit skips
    from tempo_tpu.block.versioned import open_block_versioned

    stage_block(blk_a, needed, cache=False)  # compile/warm the upload
    cold_dt = best_window(
        lambda: stage_block(open_block_versioned(backend, meta_a),
                            needed, cache=False), windows=3)

    chunkpool.clear()
    pool_hits0 = chunkpool.stats()["hits"]
    restage_lats: list[float] = []
    for _ in range(6):
        # park A in the pool: stage A then B (A becomes the LRU head),
        # squeeze the HBM budget so A demotes, restore the budget
        stage_block(blk_a, needed)
        stage_block(blk_b, needed)
        set_staged_cache_budget(1)
        set_staged_cache_budget(4 << 30)
        assert pool_holds(blk_a, needed, None), "demotion missed"
        t0 = time.perf_counter()
        stage_block(blk_a, needed)
        restage_lats.append(time.perf_counter() - t0)
    pool_hits = chunkpool.stats()["hits"] - pool_hits0
    assert pool_hits >= len(restage_lats), \
        f"only {pool_hits} pool hits across {len(restage_lats)} restages"
    restage_dt = min(restage_lats)
    set_staged_cache_budget(4 << 30)
    _emit("chunk_cache_restage_speedup", cold_dt / restage_dt, "x",
          tel={"cold_ms": round(cold_dt * 1e3, 3),
               "restage_ms": round(restage_dt * 1e3, 3),
               "codec": chunkpool.codec_name(),
               "pool_hits": pool_hits})


def bench_fleet() -> None:
    """`python bench.py --fleet`: multi-process fleet certification.

    Delegates to tempo_tpu.fleet.harness (QPS scaling 1->4 queriers +
    rolling ingester restart at RF=2 under vulture) and emits the two
    headline numbers as bench rows alongside the FLEET_SCALE.json
    artifact. Kept out of the default run: it spawns ~8 processes and
    owns its own wall-clock budget."""
    from tempo_tpu.fleet import harness as fleet_harness

    base = tempfile.mkdtemp(prefix="tempo-fleet-bench-")
    try:
        artifact = fleet_harness.certify("FLEET_SCALE.json", base,
                                         quick="--quick" in sys.argv)
    finally:
        shutil.rmtree(base, ignore_errors=True)
    qps = artifact["qps_scaling"]
    _emit("fleet_qps_scaling_ratio_4q", qps["ratio"], "x",
          qps["ratio"] / qps["target_ratio"])
    rolling = artifact["rolling_restart"]
    _emit("fleet_rolling_restart_miss_free_cycles",
          float(rolling["cycles"]), "cycles",
          1.0 if rolling["pass"] else 0.0)


def main() -> None:
    if "--fleet" in sys.argv:
        bench_fleet()
        return
    # name the device before any row: no chip (and no explicit
    # JAX_PLATFORMS=cpu) or a TPU the peaks table lacks is an error,
    # never a silent CPU run or a roofline of 0
    from tempo_tpu.util import costmodel

    try:
        _DEVICE.update(costmodel.resolve_device())
    except costmodel.NoAcceleratorError as e:
        sys.exit(f"bench: {e}")
    if (_DEVICE["platform"] != "cpu"
            and _DEVICE["device_kind"] not in costmodel.DEVICE_PEAKS):
        sys.exit(f"bench: device_kind {_DEVICE['device_kind']!r} is not in "
                 "tempo_tpu.util.costmodel.DEVICE_PEAKS; add its published "
                 "peaks (with their source) before benchmarking on it")
    bench_analysis()
    bench_kernel()
    bench_mesh_1x1_overhead()
    tmp = tempfile.mkdtemp(prefix="tempo-tpu-bench-")
    try:
        cold, warm, cold_tel, warm_tel = bench_find_and_search(tmp)
        bench_compaction(tmp)
        bench_ingest(tmp)
        bench_spanmetrics()
        bench_generator_tap(tmp)
        bench_search_concurrent(tmp)
        bench_mesh_batched(tmp)
        bench_search_live(tmp)
        bench_search_affinity(tmp)
        bench_caching(tmp)
        _emit("search_block_e2e_cold_spans_per_sec", cold, "spans/s",
              cold / BASELINE_SPANS_PER_SEC, tel=cold_tel)
        # headline LAST: hot-block search (cached device staging), the
        # production querier pattern; cold line above is the every-byte-
        # from-disk comparable to the reference's 0.18 s figure
        _emit("search_block_e2e_spans_per_sec", warm, "spans/s",
              warm / BASELINE_SPANS_PER_SEC, tel=warm_tel)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
