"""The four "Job dispatch" readers (PR 26) on plain data shaped as the
program publishes it (`dispatch` and `affinity` of /status/kernels, the
`job:dispatch` / `job:result` self-trace spans), and on a program that
publishes none of it (the parent of PR 26): each then returns None."""
import pytest

from benchmarks.layer_metrics import (
    affinity_hit_share, job_dispatch_ms, querier_load_spread, remote_job_share)


def snap(local, remote, own, steal, unowned, busy):
    return {"dispatch": {"jobs": {"local": local, "remote": remote},
                         "by_worker": {w: {"jobs": 1, "busy_seconds": s}
                                       for w, s in busy.items()},
                         "wire_bytes": 0},
            "affinity": {"jobs": {"own": own, "steal": steal, "unowned": unowned}}}


def span(i, parent, name, start, end):
    return {"id": i, "parent": parent, "name": name, "start": start, "end": end}


@pytest.fixture
def ctx():
    return {
        "kernels_before": snap(10, 30, 20, 10, 10,
                               {"local": 1.0, "querier-1": 2.0, "querier-2": 2.0}),
        "kernels_after": snap(30, 90, 50, 40, 30,
                              {"local": 3.0, "querier-1": 8.0, "querier-2": 3.0,
                               "querier-3": 2.0}),  # a worker new in the window
        "selftrace": [
            [span("r", "", "frontend.search", 0.0, 1.0),
             span("j1", "r", "job:search_blocks", 0.0, 0.9),
             span("q1", "j1", "queue-wait", 0.0, 0.010),
             span("d1", "j1", "job:dispatch", 0.0, 0.012),
             span("x1", "j1", "job:result", 0.880, 0.900),
             span("j2", "r", "job:search_recent", 0.0, 0.1),
             span("d2", "j2", "job:dispatch", 0.0, 0.004)],
            [span("r", "", "frontend.search", 0.0, 1.0),
             span("j1", "r", "job:search_blocks", 0.0, 0.9),
             span("d1", "j1", "job:dispatch", 0.0, 0.008)],
            # not a search: its spans are another metric's
            [span("r", "", "frontend.find_trace_by_id", 0.0, 1.0),
             span("d", "r", "job:dispatch", 0.0, 0.5)]],
    }


def test_readers_take_the_windows_difference(ctx):
    assert remote_job_share.read(ctx) == pytest.approx(100.0 * 60 / 80)
    assert affinity_hit_share.read(ctx) == pytest.approx(100.0 * 30 / 60)
    busy = [2.0, 6.0, 1.0, 2.0]
    assert querier_load_spread.read(ctx) == pytest.approx(6.0 / (sum(busy) / 4))
    # (12 + 20 + 4) ms in the first search, 8 ms in the second
    assert job_dispatch_ms.read(ctx) == pytest.approx((36.0 + 8.0) / 2)


def test_no_job_in_the_window_is_nothing_to_read(ctx):
    ctx["kernels_after"] = ctx["kernels_before"]
    assert remote_job_share.read(ctx) is None
    assert affinity_hit_share.read(ctx) is None
    assert querier_load_spread.read(ctx) is None


@pytest.mark.parametrize("reader", [affinity_hit_share, job_dispatch_ms,
                                    querier_load_spread, remote_job_share])
def test_a_program_without_the_counters_gives_none(reader):
    parent = {"kernels_before": {"affinity": {"jobs": {}}, "stages": {}},
              "kernels_after": {"affinity": {"jobs": {}}, "stages": {}},
              "selftrace": [[span("r", "", "frontend.search", 0.0, 1.0),
                             span("j", "r", "job:search_blocks", 0.0, 0.9)]]}
    assert reader.read(parent) is None
    assert reader.read({"kernels_before": {}, "kernels_after": {},
                        "selftrace": None}) is None
