"""`fold_ms_per_launch` (PR 32): the `timeseries` rows of the launch join, on
the recorded chip trace, on plain data, and on a program or a run that gives
it nothing to read."""
import json
import os

import pytest

from benchmarks.layer_metrics import fold_ms_per_launch, scan_ms_per_launch
from benchmarks.lib import launches

HERE = os.path.dirname(os.path.abspath(__file__))


def test_fold_reader_on_a_recorded_chip_trace():
    with open(os.path.join(HERE, "fixtures", "launches_trace.json")) as f:
        fx = json.load(f)
    out = launches.group(*([tuple(x) for x in fx[k]]
                           for k in ("launches", "events", "modules")), 8.0)
    ctx = {"_launches": out}  # what reduce_cell caches
    assert out["timeseries"]["launches"] == 3
    got = fold_ms_per_launch.read(ctx)
    assert got == pytest.approx(out["timeseries"]["seconds"] * 1e3 / 3)
    assert got != scan_ms_per_launch.read(ctx)  # another op's modules
    assert got > 50  # the recorded program scattered 2^24 rows


@pytest.mark.parametrize("rows,want", [
    ({"timeseries": {"seconds": 0.012, "launches": 4, "programs": ["jit_run(2)"]}}, 3.0),
    ({"filter": {"seconds": 0.5, "launches": 5, "programs": ["jit_run(1)"]}}, None),
    ({"timeseries": {"seconds": 0.0, "launches": 0, "programs": []}}, None),
    ({}, None),
    (None, None),
], ids=["per_launch", "no_timeseries_launch", "zero_launches", "empty", "no_trace"])
def test_fold_reader_gives_nothing_where_there_is_nothing(rows, want):
    got = fold_ms_per_launch.read({"_launches": rows})
    assert got == (pytest.approx(want) if want is not None else None)


def test_a_program_without_the_annotation_gives_nothing():
    """An untraced run, or a program older than the launch annotations: None,
    and no raise."""
    ctx = {"kernels_before": {}, "kernels_after": {}, "selftrace": None,
           "streams": {}, "trace_span": None}
    assert fold_ms_per_launch.read(ctx) is None
