"""The five per-layer readers of PR 34 on synthetic contexts: what each
computes from what the program publishes, and that a program without the
counter or span (the parent), or a run without a trace, gives None and does
not raise."""
import pytest

from benchmarks.layer_metrics import (
    fused_device_share, jobs_per_search, merge_ms_per_search,
    range_scan_roofline, select_ms_per_launch)
from benchmarks.lib import opcost
from benchmarks.tests.test_range_shapes import fake_env


def routing(rows):
    return [{"layer": l, "engine": e, "reason": r, "count": n} for l, e, r, n in rows]


def kernels(before, after):
    return {"kernels_before": before, "kernels_after": after}


EMPTY = {"kernels_before": {}, "kernels_after": {}, "selftrace": None,
         "streams": {}, "trace_span": None, "trace": None}


# ------------------------------------------------------- fused_device_share
def test_fused_device_share_reads_fused_and_metrics_rows_only():
    ctx = kernels(
        {"routing": routing([("search_fused", "device", "staged_hit", 100),
                             ("search_fused", "host", "host_scan_cheaper", 50)])},
        {"routing": routing([("search_fused", "device", "staged_hit", 700),
                             ("search_fused", "device", "promoted", 20),
                             ("search_fused", "host", "host_scan_cheaper", 90),
                             ("search_fused", "host", "cold_block", 10),
                             ("search_fused", "fallback", "pre_io_budget", 5),
                             ("metrics", "device", "hot_block", 330),
                             ("search_block", "host", "host_scan_cheaper", 999),
                             ("verify", "skip", "exact_plan", 999)])})
    # device: 600 + 20 + 330 = 950; host: 40 + 10 = 50
    assert fused_device_share.read(ctx) == pytest.approx(95.0)


@pytest.mark.parametrize("after", [
    {}, {"routing": []},
    {"routing": routing([("search_block", "device", "hot_block", 7)])}])
def test_fused_device_share_gives_nothing_without_a_fused_decision(after):
    assert fused_device_share.read(kernels({}, after)) is None


# ---------------------------------------------------------- jobs_per_search
def test_jobs_per_search_is_jobs_over_requests_in_the_window():
    ctx = kernels({"range": {"searches": 10, "jobs": 30, "job_blocks": 90}},
                  {"range": {"searches": 110, "jobs": 380, "job_blocks": 1000}})
    assert jobs_per_search.read(ctx) == pytest.approx(3.5)


@pytest.mark.parametrize("before,after", [
    ({}, {}),  # the parent: no `range` section
    ({"range": {"searches": 4, "jobs": 9}}, {"range": {"searches": 4, "jobs": 9}}),
], ids=["no_counter", "no_request"])
def test_jobs_per_search_gives_nothing_where_nothing_counted(before, after):
    assert jobs_per_search.read(kernels(before, after)) is None


# ------------------------------------------------------ merge_ms_per_search
def span(i, parent, name, start, end):
    return {"id": i, "parent": parent, "name": name, "start": start, "end": end}


def test_merge_ms_is_self_time_of_merge_and_collect_per_search_root():
    search = [span("r", "", "frontend.search", 0.0, 1.0),
              span("j", "r", "job:search_blocks", 0.0, 0.8),
              span("c", "j", "topk:collect", 0.1, 0.5),      # 0.4 s ...
              span("k", "c", "kernel:launch", 0.2, 0.3),     # ... less 0.1 s
              span("m1", "r", "search:merge", 0.80, 0.81),
              span("m2", "r", "search:merge", 0.90, 0.92)]
    other = [span("r", "", "frontend.search", 0.0, 0.5),
             span("m", "r", "search:merge", 0.4, 0.41)]
    metrics = [span("r", "", "frontend.metrics_query_range", 0.0, 1.0),
               span("c", "r", "topk:collect", 0.0, 0.9)]  # another root: out
    ctx = {"selftrace": [search, other, metrics]}
    # (0.3 + 0.01 + 0.02 + 0.01) s over two search roots
    assert merge_ms_per_search.read(ctx) == pytest.approx(170.0)


def test_merge_ms_gives_nothing_where_the_program_has_no_merge_span():
    parent = [span("r", "", "frontend.search", 0.0, 1.0),
              span("c", "r", "topk:collect", 0.1, 0.5)]
    assert merge_ms_per_search.read({"selftrace": [parent]}) is None
    assert merge_ms_per_search.read({"selftrace": None}) is None
    assert merge_ms_per_search.read({}) is None


# ----------------------------------------------------- select_ms_per_launch
@pytest.mark.parametrize("rows,want", [
    ({"select": {"seconds": 0.03, "launches": 40, "programs": ["jit_sel(1)"]},
      "filter": {"seconds": 0.5, "launches": 5, "programs": ["jit_run(1)"]}}, 0.75),
    ({"filter": {"seconds": 0.5, "launches": 5, "programs": ["jit_run(1)"]}}, None),
    ({"select": {"seconds": 0.0, "launches": 0, "programs": []}}, None),
    ({}, None), (None, None),
], ids=["per_launch", "no_select_launch", "zero_launches", "empty", "no_trace"])
def test_select_ms_per_launch(rows, want):
    got = select_ms_per_launch.read({"_launches": rows})
    assert got == (pytest.approx(want) if want is not None else None)


# ------------------------------------------------------ range_scan_roofline
def roofline_ctx(share_rows, results, secs=0.010):
    env = fake_env(1)
    return {
        **kernels({}, {"routing": routing(share_rows)}),
        "streams": {"search": {"spec": {"role": "search"}, "results": results}},
        "trace_span": (100.0, 108.0), "env": env, "manifest": env.manifest,
        "config": env.config, "device": {"device_kind": "TPU v5 lite"},
        "trace": {"devices": [{}], "families": {
            "scan(filter|multiquery|timeseries|live_filter)": {"seconds": secs},
            "select(select|mq_select)": {"seconds": 9.0}}},
        "module_ops": {"scan": ["scan(filter|multiquery|timeseries|live_filter)"]},
    }


def result(shape, n, t_done=104.0, ok=True):
    env = fake_env(1)
    b = env.manifest["blocks"]
    return {"op": {"shape": shape, "block": 0, "n": n,
                   "start": b[n - 1]["start_s"] - 5, "end": b[0]["end_s"]},
            "t_done": t_done, "ok": ok}


def test_range_scan_roofline_prices_every_block_at_its_own_bucket():
    from benchmarks.shapes import attr_eq, rate_service

    rows = [("search_fused", "device", "staged_hit", 60),
            ("search_fused", "host", "host_scan_cheaper", 20),
            ("metrics", "device", "hot_block", 20)]
    res = [result("attr_eq_range", 3), result("rate_service_range", 24),
           result("tag_service_range", 6),               # no span-axis scan
           result("attr_eq_range", 12, t_done=120.0),    # outside the trace
           result("attr_eq_range", 12, ok=False)]        # not a good answer
    ctx = roofline_ctx(rows, res)
    one_attr = opcost.scan_cost(attr_eq.SCAN, 18750 * 69, 18750, 2)["bytes"]
    one_rate = opcost.scan_cost(rate_service.SCAN, 18750 * 69, 18750, 2)["bytes"]
    # 2^21 span rows, 2^22 attribute rows, 2^15 traces a block
    assert one_attr == (4 * 2**22 + 2**21 + 2 * 2**15 + 2**15) * 4
    need = (3 * one_attr + 24 * one_rate) * 0.80
    want = 100.0 * need / 819e9 / 0.010
    assert range_scan_roofline.read(ctx) == pytest.approx(want)
    assert 0 < want < 105


def test_range_scan_roofline_scales_with_the_device_share():
    res = [result("duration_gt_range", 6)]
    all_dev = roofline_ctx([("search_fused", "device", "staged_hit", 10)], res)
    half = roofline_ctx([("search_fused", "device", "staged_hit", 5),
                         ("search_fused", "host", "host_scan_cheaper", 5)], res)
    assert range_scan_roofline.read(half) == pytest.approx(
        range_scan_roofline.read(all_dev) / 2)


def test_range_scan_roofline_gives_nothing_without_a_trace_or_a_decision():
    res = [result("duration_gt_range", 6)]
    ctx = roofline_ctx([("search_fused", "device", "staged_hit", 10)], res)
    assert range_scan_roofline.read(dict(ctx, trace=None)) is None
    assert range_scan_roofline.read(dict(ctx, trace_span=None)) is None
    assert range_scan_roofline.read(roofline_ctx([], res)) is None
    assert range_scan_roofline.read(roofline_ctx(
        [("search_fused", "device", "staged_hit", 10)], res, secs=0.0)) is None


@pytest.mark.parametrize("mod", [fused_device_share, jobs_per_search,
                                 merge_ms_per_search, select_ms_per_launch,
                                 range_scan_roofline],
                         ids=lambda m: m.__name__.rsplit(".", 1)[-1])
def test_an_untraced_run_of_the_parent_gives_nothing(mod):
    assert mod.read(dict(EMPTY)) is None
