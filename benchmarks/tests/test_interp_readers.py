"""The "Host interpreter" readers of PR 38 (benchmarks/INTERP.md), against
what a rehearsal recorded (fixtures/interp_ctx.json: the `stages` and
`interp` sections of the two /status/kernels snapshots and each stream's
statuses, for three one-process cells and the four-process tree; a CPU
rehearsal at the tiny scale, so counts and shapes are real and the seconds
are not a chip's), against a program without the fields (the parent), on a
tree's sums and up to a traced session's start."""
import copy
import json
import os

import pytest

from benchmarks.layer_metrics import (
    cut_oncpu_share, find_cpu_ms, gil_wait_ms, host_cpu_cores, job_oncpu_share,
    plan_ms_per_search, push_cpu_ms, search_cpu_ms)
from benchmarks.lib import cpu

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = {
    "gil_wait_ms": gil_wait_ms, "host_cpu_cores": host_cpu_cores,
    "search_cpu_ms": search_cpu_ms, "job_oncpu_share": job_oncpu_share,
    "plan_ms_per_search": plan_ms_per_search, "find_cpu_ms": find_cpu_ms,
    "push_cpu_ms": push_cpu_ms, "cut_oncpu_share": cut_oncpu_share,
}
SEARCH = ("gil_wait_ms", "host_cpu_cores", "search_cpu_ms", "job_oncpu_share",
          "plan_ms_per_search")


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "fixtures", "interp_ctx.json")) as f:
        return json.load(f)


def _d(ctx, name, key):
    a, b = ctx["kernels_after"]["stages"], ctx["kernels_before"]["stages"]
    return a[name].get(key, 0) - b.get(name, {}).get(key, 0)


def _sum(ctx, names, *keys):
    return sum(_d(ctx, n, k) for n in names if n in ctx["kernels_after"]["stages"]
               for k in keys)


def _interp(ctx, *path):
    def dig(d):
        for k in path:
            d = d[k]
        return d
    return dig(ctx["kernels_after"]["interp"]) - dig(ctx["kernels_before"]["interp"])


def _untraced(ctx):
    return dict(ctx, trace_span=None)


@pytest.mark.parametrize("cell", ["chip1-read-mix", "host4-scalable-read-mix"])
def test_search_readers_on_the_recorded_cells(recorded, cell):
    ctx = recorded[cell]
    searches = _d(ctx, "http:search", "count") + _d(ctx, "http:metrics", "count")
    assert searches > 50
    roots = cpu.SEARCH_HTTP + cpu.SEARCH_RUNS + cpu.WIRE
    both = _sum(ctx, roots, "cpu_seconds")
    assert search_cpu_ms.read(ctx) == pytest.approx(both * 1e3 / searches)
    # a rate() evaluates its block on the db's pool: that CPU is in the
    # pool thread's own `block:metrics` row, not in the job's
    assert _d(ctx, "block:metrics", "cpu_seconds") > 0
    share = job_oncpu_share.read(ctx)
    assert share == pytest.approx(100 * _sum(ctx, cpu.SEARCH_RUNS, "cpu_seconds")
                                  / _sum(ctx, cpu.SEARCH_RUNS, "seconds"))
    assert 0 < share <= 100
    assert plan_ms_per_search.read(ctx) == pytest.approx(
        _d(ctx, "plan:compile", "seconds") * 1e3 / searches)
    ticks = _interp(ctx, "probe", "ticks")
    assert ticks > 100
    assert gil_wait_ms.read(ctx) == pytest.approx(
        _interp(ctx, "probe", "late_seconds") * 1e3 / ticks)
    # every job of the window ran in exactly one run:* stage
    jobs = _sum(ctx, cpu.SEARCH_RUNS + cpu.FIND_RUNS, "count")
    assert jobs >= 2 * searches
    # the balance: the outermost stages' CPU fits in the process's
    finds = _sum(ctx, cpu.FIND_HTTP + cpu.FIND_RUNS, "cpu_seconds")
    assert both + finds <= _interp(ctx, "cpu_seconds")
    wire = _sum(ctx, cpu.WIRE, "count")
    assert (wire > 0) is (cell == "host4-scalable-read-mix")


def test_a_trees_cores_are_the_sum_over_its_processes(recorded):
    one, tree = recorded["chip1-read-mix"], recorded["host4-scalable-read-mix"]
    assert cpu.instances(one) == 1 and cpu.instances(tree) == 4
    for ctx, n in ((one, 1), (tree, 4)):
        cores = host_cpu_cores.read(ctx)
        wall = _interp(ctx, "wall_seconds")
        assert cores == pytest.approx(_interp(ctx, "cpu_seconds") / (wall / n))
        assert 0 < cores <= (os.cpu_count() or 64)
        # the snapshots bracket the window on every process's own clock
        assert ctx["seconds"] <= wall / n < ctx["seconds"] + 30
    # the same counters read as one process would be 4 x too few cores
    flat = copy.deepcopy(tree)
    del flat["kernels_after"]["instances"]
    assert host_cpu_cores.read(flat) == pytest.approx(host_cpu_cores.read(tree) / 4)
    # a querier that died is not a process whose clock was summed
    flat["kernels_after"]["instances"] = [{"index": 0, "alive": True},
                                          {"index": 1, "alive": False}]
    assert cpu.instances(flat) == 1


def test_find_reader_on_the_recorded_cell(recorded):
    ctx = recorded["chip1-find"]
    whole = _untraced(ctx)
    n = _d(ctx, "http:find", "count")
    assert n > 50
    roots = cpu.FIND_HTTP + cpu.FIND_RUNS
    assert find_cpu_ms.read(whole) == pytest.approx(
        _sum(ctx, roots, "cpu_seconds") * 1e3 / n)
    # traced: only up to the session's start, from the table kept then
    at, before = ctx["kernels_after"]["stages_at_session"], ctx["kernels_before"]["stages"]
    n_at = at["http:find"]["count"] - before["http:find"]["count"]
    assert 0 < n_at < n
    cpu_at = sum(at[r]["cpu_seconds"] - before.get(r, {}).get("cpu_seconds", 0)
                 for r in roots if r in at)
    assert find_cpu_ms.read(ctx) == pytest.approx(cpu_at * 1e3 / n_at)
    # the search readers have nothing to say in a cell that does not list
    # them, but do not break on it
    assert search_cpu_ms.read(ctx) > 0


def test_write_readers_on_the_recorded_cell(recorded):
    ctx = recorded["chip1-write-live"]
    acked = sum(r["status"] == 200 for r in ctx["streams"]["push"]["results"])
    pushes = _d(ctx, "http:push", "count")
    assert pushes >= acked > 100 and _d(ctx, "ingest:flush", "count") >= 1
    assert push_cpu_ms.read(ctx) == pytest.approx(
        _d(ctx, "http:push", "cpu_seconds") * 1e3 / pushes)
    cut = ("ingest:cut", "ingest:flush")
    share = cut_oncpu_share.read(ctx)
    assert share == pytest.approx(100 * _sum(ctx, cut, "cpu_seconds")
                                  / _sum(ctx, cut, "seconds"))
    assert 0 < share <= 100
    # the push's own thread cannot have been on a CPU longer than it took
    assert _d(ctx, "http:push", "cpu_seconds") <= _d(ctx, "http:push", "seconds")


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_fields_gives_nothing(recorded, name):
    """The parent of PR 38: rows of {count, seconds}, no `run:*` rows, no
    `interp`. A reader returns None and does not raise; so does one that
    finds no /status/kernels section at all."""
    cell = {"find_cpu_ms": "chip1-find", "push_cpu_ms": "chip1-write-live",
            "cut_oncpu_share": "chip1-write-live"}.get(name, "chip1-read-mix")
    ctx = copy.deepcopy(recorded[cell])
    for snap in (ctx["kernels_before"], ctx["kernels_after"]):
        del snap["interp"]
        for key in ("stages", "stages_at_session"):
            rows = snap.get(key) or {}
            for n in [n for n in rows if n.startswith("run:")]:
                del rows[n]
            for row in rows.values():
                row.pop("cpu_seconds", None)
    assert READERS[name].read(ctx) is None
    assert READERS[name].read(_untraced(ctx)) is None
    bare = {"kernels_before": {}, "kernels_after": {}, "trace_span": None,
            "seconds": 51.0, "streams": {}}
    assert READERS[name].read(bare) is None


def test_the_sampler_off_leaves_the_probe_out(recorded):
    """TEMPO_PROFILE_HZ=0: the probe's fields stay 0 and gil_wait_ms is left
    out, while the CPU clock still reads."""
    ctx = copy.deepcopy(recorded["chip1-read-mix"])
    for snap in (ctx["kernels_before"], ctx["kernels_after"]):
        snap["interp"]["probe"] = {"ticks": 0, "late_seconds": 0.0,
                                   "late_over_5ms": 0, "late_over_20ms": 0}
    assert gil_wait_ms.read(ctx) is None
    assert host_cpu_cores.read(ctx) > 0


def test_rows_new_in_the_window_and_rows_that_did_not_run():
    row = lambda n, s, c: {"count": n, "seconds": s, "cpu_seconds": c}
    ctx = {"trace_span": None,
           "kernels_before": {"stages": {"http:search": row(4, 2.0, 0.2),
                                         "http:find": row(3, 0.3, 0.1)}},
           "kernels_after": {"stages": {
               "http:search": row(6, 3.0, 0.3), "http:find": row(3, 0.3, 0.1),
               "run:search_blocks": row(2, 1.5, 0.5),
               "job:dispatch": {"count": 2, "seconds": 0.1}}}}
    # new since the first snapshot: counted from zero; no CPU clock: no row
    assert cpu.row(ctx, "run:search_blocks") == {
        "count": 2, "seconds": 1.5, "cpu_seconds": 0.5}
    assert cpu.row(ctx, "job:dispatch") is None and cpu.row(ctx, "http:metrics") is None
    assert search_cpu_ms.read(ctx) == pytest.approx((0.1 + 0.5) * 1e3 / 2)
    assert job_oncpu_share.read(ctx) == pytest.approx(100 * 0.5 / 1.5)
    assert find_cpu_ms.read(ctx) is None  # no find in the window
    assert plan_ms_per_search.read(ctx) is None  # no plan:compile row


def test_contract_lists_the_eight_under_their_layers():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        bench = json.load(f)
    got = {m["name"]: m for m in bench["per_layer"] if m["name"] in READERS}
    assert set(got) == set(READERS)
    # the eight by name, in the order PR 38 added them: later PRs append
    # their own entries after them
    assert [m["name"] for m in bench["per_layer"] if m["name"] in READERS] == [
        "gil_wait_ms", "host_cpu_cores", "search_cpu_ms", "job_oncpu_share",
        "plan_ms_per_search", "find_cpu_ms", "push_cpu_ms", "cut_oncpu_share"]
    search_cells = ["chip1-read-mix", "host4-scalable-read-mix", "chip1-range-mix"]
    for name in SEARCH:
        assert got[name]["workloads"] == search_cells
    assert got["find_cpu_ms"]["workloads"] == ["chip1-find", "host4-find"]
    assert got["push_cpu_ms"]["workloads"] == got["cut_oncpu_share"]["workloads"] == [
        "chip1-write-live"]
    assert {m["layer"] for n, m in got.items() if n != "plan_ms_per_search"} == {
        "Host interpreter"}
    assert got["plan_ms_per_search"]["layer"] == "Plan + route"
    assert all(m["source"] == "program_counter" for m in got.values())
