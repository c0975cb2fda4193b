"""The five readers `chip1-12block-read-cold` brought (benchmarks/COLD.md), each
on a recorded `ctx`: the two /status/kernels snapshots around a window as
the program publishes them (found -> the formula's value) and as a program
without the counter or the span publishes them (absent -> None, the metric
is left out of the line; a reader never raises)."""

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.layer_metrics import (  # noqa: E402
    demote_ms_per_eviction, hedge_twins_per_search, miss_stage_ms,
    pool_restage_share, staged_evicted_MB)


def _snapshot(evictions, evicted_bytes, pool_hits, pool_misses, cache_misses,
              stages, hedging, searches):
    return {
        "staged_cache": {"entries": 70, "bytes": 4_200_000_000,
                         "budget_bytes": 4 << 30, "evictions": evictions,
                         "evicted_bytes": evicted_bytes},
        "caching": {"chunk_pool": {"enabled": True, "hits": pool_hits,
                                   "misses": pool_misses, "demotions": 9}},
        "staging": {"cache_hits": 500, "cache_misses": cache_misses},
        "stages": {n: {"count": c, "seconds": s} for n, (c, s) in stages.items()},
        "hedging": hedging,
        "range": {"searches": searches, "jobs": 2 * searches},
    }


@pytest.fixture
def ctx():
    before = _snapshot(
        100, 5_000_000_000, 10, 300, 120,
        {"stage:read_columns": (120, 60.0), "stage:assemble": (700, 30.0),
         "stage:upload": (120, 12.0), "stage:demote": (40, 2.0)},
        {"lose": 3}, 200)
    after = _snapshot(
        400, 20_000_000_000, 25, 885, 200,
        {"stage:read_columns": (200, 140.0), "stage:assemble": (1400, 70.0),
         "stage:upload": (200, 28.0), "stage:demote": (160, 8.0)},
        {"lose": 10, "win": 2, "unneeded": 1}, 500)
    return {"kernels_before": before, "kernels_after": after}


def _without(ctx, *path):
    out = copy.deepcopy(ctx)
    for snap in (out["kernels_before"], out["kernels_after"]):
        d = snap
        for k in path[:-1]:
            d = d[k]
        d.pop(path[-1], None)
    return out


CASES = [
    (staged_evicted_MB, 15_000.0, ("staged_cache", "evicted_bytes")),
    (pool_restage_share, 100.0 * 15 / 600, ("caching", "chunk_pool")),
    (miss_stage_ms, (80.0 + 40.0 + 16.0) * 1e3 / 80, ("staging", "cache_misses")),
    (demote_ms_per_eviction, 6.0 * 1e3 / 300, ("stages", "stage:demote")),
    (hedge_twins_per_search, 10 / 300, ("hedging",)),
]


@pytest.mark.parametrize("reader,want,_path", CASES,
                         ids=[c[0].__name__.rsplit(".", 1)[-1] for c in CASES])
def test_found_gives_the_formulas_value(ctx, reader, want, _path):
    assert reader.read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("reader,_want,path", CASES,
                         ids=[c[0].__name__.rsplit(".", 1)[-1] for c in CASES])
def test_absent_gives_none(ctx, reader, _want, path):
    assert reader.read(_without(ctx, *path)) is None


def test_the_parents_status_reads_none_where_the_counters_are_new(ctx):
    """The parent of PR 42 has `staged_cache` without the two counters and no
    `stage:demote` row; its pool and hedging sections are there."""
    parent = _without(_without(_without(
        ctx, "staged_cache", "evictions"), "staged_cache", "evicted_bytes"),
        "stages", "stage:demote")
    assert staged_evicted_MB.read(parent) is None
    assert demote_ms_per_eviction.read(parent) is None
    assert pool_restage_share.read(parent) == pytest.approx(2.5)
    assert miss_stage_ms.read(parent) == pytest.approx(1700.0)
    assert hedge_twins_per_search.read(parent) == pytest.approx(10 / 300)


def test_nothing_evicted_missed_or_hedged(ctx):
    """A window in which the cache held everything: no miss, no eviction."""
    still = copy.deepcopy(ctx)
    still["kernels_after"] = copy.deepcopy(still["kernels_before"])
    still["kernels_after"]["range"]["searches"] += 100
    assert staged_evicted_MB.read(still) == 0.0
    assert pool_restage_share.read(still) is None
    assert miss_stage_ms.read(still) is None
    assert demote_ms_per_eviction.read(still) is None
    assert hedge_twins_per_search.read(still) == 0.0
    # a process that has not hedged yet publishes an empty section: 0 twins
    still["kernels_before"]["hedging"] = {}
    still["kernels_after"]["hedging"] = {}
    assert hedge_twins_per_search.read(still) == 0.0
