import pytest

from benchmarks.lib import stats


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 0.5) == 50
    assert stats.percentile(v, 0.9) == 90
    assert stats.percentile(v, 0.99) == 99
    assert stats.percentile([7.0], 0.9) == 7.0
    assert stats.percentile([], 0.5) is None
    assert stats.percentile([3, 1, 2], 0.5) == 2


@pytest.mark.parametrize("n,p", [(19, 0.5), (40, 0.75), (100, 0.9), (250, 0.95),
                                 (1000, 0.99), (10_000, 0.999)])
def test_highest_percentile_with_ten_samples_beyond(n, p):
    assert stats.supported_percentile(n) == p
    assert p == 0.5 or round(n * (1 - p), 9) >= 10


def test_union_counts_overlap_once():
    assert stats.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert stats.union_length([]) == 0


def test_self_time_subtracts_what_children_cover():
    spans = [
        {"id": "r", "parent": "", "start": 0.0, "end": 10.0},
        {"id": "a", "parent": "r", "start": 1.0, "end": 4.0},
        {"id": "b", "parent": "r", "start": 3.0, "end": 6.0},   # overlaps a
        {"id": "c", "parent": "a", "start": 1.5, "end": 2.0},
        {"id": "late", "parent": "r", "start": 9.0, "end": 12.0},  # clipped
    ]
    st = stats.self_times(spans)
    assert st["r"] == pytest.approx(10 - 5 - 1)
    assert st["a"] == pytest.approx(2.5)
    assert st["c"] == pytest.approx(0.5)
