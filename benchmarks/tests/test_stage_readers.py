"""The readers PR 23 added, against what a rehearsal recorded
(fixtures/stages_ctx.json: the `stages` tables of the two /status/kernels
snapshots, three self-traces and each stream's statuses, per cell; a CPU
rehearsal at the tiny scale, so counts and shapes are real and the seconds
are not a chip's), against a program without the table (the parent), and
the join of launch annotations to device modules on plain data and on a
recorded chip trace."""
import json
import os

import pytest

from benchmarks.layer_metrics import (
    cut_ms_per_flush, find_fetch_ms, find_server_ms, materialize_ms_per_search,
    push_lock_wait_ms, scan_ms_per_launch, stage_ms_per_search,
    verify_ms_per_search)
from benchmarks.lib import launches, stages, stats, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def table():
    with open(os.path.join(os.path.dirname(HERE), "lib", "module_ops.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixtures", "stages_ctx.json")) as f:
        return json.load(f)


def _d(ctx, name, key):
    a, b = ctx["kernels_after"]["stages"], ctx["kernels_before"]["stages"]
    return a[name][key] - b.get(name, {key: 0})[key]


def test_find_readers_on_the_recorded_cell(recorded):
    ctx = recorded["chip1-find"]
    n = _d(ctx, "http:find", "count")
    assert n > 50
    server = find_server_ms.read(ctx)
    fetch = find_fetch_ms.read(ctx)
    assert server == pytest.approx(_d(ctx, "http:find", "seconds") * 1e3 / n)
    assert fetch == pytest.approx(_d(ctx, "find:fetch", "seconds") * 1e3 / n)
    assert 0 < fetch <= server  # the fetch is inside the handler


def test_write_readers_on_the_recorded_cell(recorded):
    ctx = recorded["chip1-write-live"]
    acked = sum(r["status"] == 200 for r in ctx["streams"]["push"]["results"])
    assert acked > 100 and _d(ctx, "ingest:flush", "count") == 1
    assert push_lock_wait_ms.read(ctx) == pytest.approx(
        _d(ctx, "ingest:lock_wait", "seconds") * 1e3 / acked)
    whole = (_d(ctx, "ingest:cut", "seconds") + _d(ctx, "ingest:flush", "seconds")) * 1e3
    assert cut_ms_per_flush.read(ctx) == pytest.approx(whole)
    # the cut:* stages nest inside ingest:flush: they explain it, not add to it
    inside = sum(_d(ctx, n, "seconds") for n in ctx["kernels_after"]["stages"]
                 if n.startswith("cut:"))
    assert 0 < inside <= _d(ctx, "ingest:flush", "seconds")


def test_span_readers_on_the_recorded_cell(recorded):
    ctx = recorded["chip1-read-mix"]
    assert len(ctx["selftrace"]) == 3
    mat = materialize_ms_per_search.read(ctx)
    ver = verify_ms_per_search.read(ctx)
    # rows:materialize is verify's sibling, inside its extent: verify keeps
    # its whole self time and the new metric says how much of it is rows
    assert 0 < mat <= ver
    extents = sum(s["end"] - s["start"] for spans in ctx["selftrace"]
                  for s in spans if s["name"] == "rows:materialize")
    assert mat == pytest.approx(extents * 1e3 / 3)
    for spans in ctx["selftrace"]:
        st = stats.self_times(spans)
        for v in (s for s in spans if s["name"] == "verify"):
            assert st[v["id"]] == pytest.approx(v["end"] - v["start"])
            inner = [s for s in spans if s["name"] == "rows:materialize"
                     and s["parent"] == v["parent"]
                     and v["start"] <= s["start"] and s["end"] <= v["end"]]
            assert inner
    staged = stage_ms_per_search.read(ctx)
    names = ("stage:read_columns", "stage:assemble", "stage:upload")
    total = sum(stats.self_times(spans)[s["id"]] for spans in ctx["selftrace"]
                for s in spans if s["name"] in names)
    assert staged == pytest.approx(total * 1e3 / 3) and staged > 0


@pytest.mark.parametrize("reader", [
    find_server_ms, find_fetch_ms, push_lock_wait_ms, cut_ms_per_flush,
    materialize_ms_per_search, stage_ms_per_search, scan_ms_per_launch])
def test_a_program_without_the_table_or_the_spans_gives_nothing(reader):
    """The parent of PR 23: no `stages` in /status/kernels, no stage spans,
    no scope in the trace. A reader returns None and does not raise."""
    ctx = {"kernels_before": {"ingest": {}}, "kernels_after": {"ingest": {}},
           "selftrace": [[{"id": "a", "parent": "", "name": "frontend.search",
                           "start": 0.0, "end": 1.0},
                          {"id": "b", "parent": "a", "name": "verify",
                           "start": 0.1, "end": 0.9}]],
           "streams": {"push": {"spec": {"role": "ingest"},
                                "results": [{"status": 200, "ok": True}]}},
           "trace_span": None}
    assert reader.read(ctx) is None
    assert reader.read(dict(ctx, selftrace=None)) is None


def test_ms_per_needs_the_denominator():
    ctx = {"kernels_before": {"stages": {"http:find": {"count": 2, "seconds": 1.0}}},
           "kernels_after": {"stages": {"http:find": {"count": 2, "seconds": 1.0},
                                        "find:fetch": {"count": 1, "seconds": 0.5}}}}
    assert stages.delta(ctx, "find:fetch") == (0.5, 1)  # new since the first snapshot
    assert stages.ms_per(ctx, ("find:fetch",), "http:find") is None  # no find in the window


@pytest.mark.parametrize("traced, server, fetch", [(False, 100.0, 40.0), (True, 30.0, 10.0)])
def test_find_readers_stop_at_the_session_in_a_traced_run(traced, server, fetch):
    """Stopping a session costs seconds of CPU beside serving: a traced run
    reads the two find metrics from the window's start to the session's
    start (the table the program kept then), an untraced one from the whole
    window; a program that keeps no such table (the parent) is read whole."""
    row = lambda n, s: {"count": n, "seconds": s}
    ctx = {"kernels_before": {"stages": {"http:find": row(10, 0.3), "find:fetch": row(10, 0.1)}},
           "kernels_after": {"stages": {"http:find": row(30, 2.3), "find:fetch": row(30, 0.9)},
                             "stages_at_session": {"http:find": row(20, 0.6),
                                                   "find:fetch": row(20, 0.2)}},
           "trace_span": (21.5, 29.5) if traced else None}
    assert find_server_ms.read(ctx) == pytest.approx(server)
    assert find_fetch_ms.read(ctx) == pytest.approx(fetch)
    del ctx["kernels_after"]["stages_at_session"]
    assert find_server_ms.read(ctx) == pytest.approx(100.0)
    # a session that began before the window (the write cell's): nothing to read
    ctx["kernels_after"]["stages_at_session"] = ctx["kernels_before"]["stages"]
    assert find_server_ms.read(ctx) == (None if traced else pytest.approx(100.0))


def _chain(line, t, op_line, run_id, n):
    """One dispatch as the runtime records it: the call's linkage event on
    the caller's line, the execute it links to (same line, later), and the
    enqueue issued from a runtime thread (`line`) 30 ms later."""
    k = str(n)
    return [(op_line, t + 1 * MS, t + 1 * MS, "call", "14/" + k),
            (op_line, t + 2 * MS, t + 4 * MS, "exec", "14/" + k),
            (op_line, t + 3 * MS, t + 3 * MS, "issue", "7/" + k),
            (line, t + (30 + n) * MS, t + (30.5 + n) * MS, "issued", "7/" + k),
            (line, t + (30.2 + n) * MS, t + (30.2 + n) * MS, "enqueue", run_id)]


def test_launch_join_follows_the_flow_ids_not_the_clocks():
    """Two `run` programs, one launched as filter and one as timeseries on
    another thread at the same time; the launches do not wait for their
    outputs, so every module runs after both annotations ended and an
    enqueue on a shared runtime thread lies in neither: the ids still name
    each. A dispatch outside every launch, and one whose chain the session's
    start cut, are left out, not guessed."""
    t = 1_000 * MS
    launched = [(1, t, t + 5 * MS, "filter"), (2, t, t + 5 * MS, "timeseries"),
                (1, t + 500 * MS, t + 505 * MS, "filter")]
    events = (_chain(9, t, 1, "41", 1) + _chain(9, t, 2, "42", 2)
              + _chain(9, t + 500 * MS, 1, "43", 3)
              + _chain(9, t + 700 * MS, 1, "44", 4)       # no launch around the call
              + _chain(9, t + 900 * MS, 1, "45", 5)[2:])  # the call was not recorded
    modules = [(t + 100 * MS, 100 * MS, "jit_run(1)", "41"),
               (t + 200 * MS, 150 * MS, "jit_run(2)", "42"),
               (t + 600 * MS, 180 * MS, "jit_run(1)", "43"),
               (t + 800 * MS, 1 * MS, "jit_sel(3)", "44"),
               (t + 950 * MS, 1 * MS, "jit_run(1)", "45")]
    assert launches.ops_by_run(launched, events) == {
        "41": "filter", "42": "timeseries", "43": "filter"}
    out = launches.group(launched, events, modules)
    assert out == {"filter": {"seconds": pytest.approx(0.280), "launches": 2,
                              "programs": ["jit_run(1)"]},
                   "timeseries": {"seconds": pytest.approx(0.150), "launches": 1,
                                  "programs": ["jit_run(2)"]}}
    # cut at the seconds asked for: the second filter module starts after 0.4 s
    assert launches.group(launched, events, modules, asked_s=0.4)["filter"]["launches"] == 1
    assert launches.group([], [], []) == {}


def test_launch_join_on_a_recorded_chip_trace():
    with open(os.path.join(HERE, "fixtures", "launches_trace.json")) as f:
        fx = json.load(f)
    out = launches.group(*([tuple(x) for x in fx[k]]
                           for k in ("launches", "events", "modules")), 8.0)
    assert {k: v["launches"] for k, v in out.items()} == \
        {k: v["launches"] for k, v in fx["expected"].items()}
    assert out["filter"]["launches"] == 17 and out["timeseries"]["launches"] == 3
    # 56 of the session's 59 modules ran inside a launch; none is guessed
    assert sum(v["launches"] for v in out.values()) == 56 and len(fx["modules"]) == 59
    for op, exp in fx["expected"].items():
        assert out[op]["seconds"] == pytest.approx(exp["seconds"], rel=1e-4)
    ctx = {"_launches": out}  # what reduce_cell caches
    assert scan_ms_per_launch.read(ctx) == pytest.approx(
        out["filter"]["seconds"] * 1e3 / 17)
    assert 50 < scan_ms_per_launch.read(ctx) < 200  # a scan is ~100 ms, not ~1


def test_an_idle_gap_is_owned_by_the_innermost_tempo_annotation(table):
    """lib/xplane.py as PR 22 left it, fed the planes this PR's program
    writes: a layer annotation owns the gap, not a frame."""
    t = 1_000 * MS
    dev = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ("fusion.1", t, 10 * MS), ("fusion.2", t + 90 * MS, 10 * MS)]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "t", "events": [
        ("tempo/http:search", t, 100 * MS),
        ("tempo/topk:collect", t + 8 * MS, 85 * MS),
        ("tempo/rows:materialize", t + 10 * MS, 80 * MS),
        ("tempo/verify:eval", t + 50 * MS, 2 * MS)]}]}
    out = xplane.reduce_planes([dev, host], table)
    assert out["idle_gaps"][0][0] == "tempo/rows:materialize"
    assert out["idle_gaps"][0][1] == pytest.approx(0.080)
