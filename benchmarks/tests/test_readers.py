from benchmarks.e2e_metrics import find_within_1s_share
from benchmarks.layer_metrics import find_p50_ms, find_p90_ms
from benchmarks.lib import readers as R


def _find(due, took, ok=True):
    return {"ok": ok, "t_due": due, "t_send": due, "t_done": due + took}


def _ctx(results, trace_span=None):
    return {"streams": {"find": {"spec": {"role": "find"}, "results": results}},
            "t_end": 100.0, "trace_span": trace_span}


def test_share_within_a_second_counts_from_the_due_time_and_failures_against():
    res = [_find(i, 0.03) for i in range(7)]
    res += [_find(7, 1.0), _find(8, 1.2), _find(9, 0.03, ok=False)]
    assert find_within_1s_share.read(_ctx(res)) == 80.0
    assert find_within_1s_share.read(_ctx([])) is None


def test_percentiles_of_a_traced_run_leave_out_what_the_profiler_touched():
    res = [_find(i, 0.02) for i in range(20)] + [_find(20 + i, 9.0) for i in range(10)]
    assert find_p90_ms.read(_ctx(res)) == 9000.0
    ctx = _ctx(res, trace_span=(20.5, 28.5))
    assert len(R.untraced(ctx, res)) == 20  # due before 19.5 s
    assert abs(find_p90_ms.read(ctx) - 20.0) < 1e-6
    assert abs(find_p50_ms.read(ctx) - 20.0) < 1e-6


def test_a_counter_family_is_summed_from_the_metrics_page():
    page = ("# TYPE tempo_compactor_runs_total counter\n"
            "tempo_compactor_runs_total 4\n"
            "tempo_compactor_blocks_compacted_total 6\n"
            "tempo_compactor_blocks_compacted_totals 99\n"
            'tempo_x_total{tenant="a"} 2\ntempo_x_total{tenant="b"} 3.0\n# EOF\n')
    assert R.metrics_family_total(page, "tempo_compactor_blocks_compacted_total") == 6
    assert R.metrics_family_total(page, "tempo_x_total") == 5
    assert R.metrics_family_total(page, "tempo_absent_total") is None
