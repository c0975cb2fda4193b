"""`cut_lock_hold_ms` (PR 37): seconds of `ingest:swap` over the flushes of
the window, and nothing on a stage table without the stage (the parent)."""
import pytest

from benchmarks.layer_metrics import cut_lock_hold_ms, cut_ms_per_flush


def _ctx(before: dict, after: dict, **more) -> dict:
    return {"kernels_before": {"stages": before}, "kernels_after": {"stages": after},
            "selftrace": None, "streams": {}, "trace_span": None, **more}


def _row(count, seconds):
    return {"count": count, "seconds": seconds}


PARENT = {"ingest:cut": _row(1, 2.14), "ingest:flush": _row(1, 2.25),
          "ingest:lock_wait": _row(612, 98.0)}


@pytest.mark.parametrize("before,after,want", [
    ({}, PARENT, None),
    ({}, {**PARENT, "ingest:swap": _row(1, 0.012)}, 12.0),
    # two flushes in the window, one before it
    ({"ingest:swap": _row(1, 0.010), "ingest:cut": _row(1, 2.0), "ingest:flush": _row(1, 2.0)},
     {"ingest:swap": _row(3, 0.050), "ingest:cut": _row(3, 6.0), "ingest:flush": _row(3, 6.5)},
     20.0),
    # a swap whose write has not landed inside the window: no flush to divide by
    ({}, {"ingest:swap": _row(1, 0.012), "ingest:cut": _row(1, 2.0)}, None),
    ({}, {}, None),
], ids=["parent_has_no_swap", "per_flush", "window_delta", "no_flush_in_window", "empty"])
def test_cut_lock_hold_reads_the_swap_per_flush(before, after, want):
    got = cut_lock_hold_ms.read(_ctx(before, after))
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_swap_is_not_added_to_the_cut_time():
    """`cut_ms_per_flush` keeps reading `ingest:cut` + `ingest:flush`."""
    ctx = _ctx({}, {**PARENT, "ingest:swap": _row(1, 0.012)})
    assert cut_ms_per_flush.read(ctx) == pytest.approx(4390.0)


def test_no_status_table_gives_nothing():
    """An older program publishes no `stages` at all: None, and no raise."""
    ctx = {"kernels_before": {}, "kernels_after": {}, "selftrace": None,
           "streams": {}, "trace_span": None}
    assert cut_lock_hold_ms.read(ctx) is None
