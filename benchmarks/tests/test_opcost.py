import pytest

from benchmarks.lib import opcost
from benchmarks.shapes import attr_eq, duration_gt, rate_service, tag_service


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        opcost.peaks_for("TPU v9 imaginary")
    assert opcost.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_scan_cost_is_columns_times_padded_rows():
    c = opcost.scan_cost(duration_gt.SCAN, 10_350_000, 150_000, 2)
    assert opcost.bucket(10_350_000) == 16_777_216
    assert c["bytes"] == (2 * 16_777_216 + 2 * 262_144 + 262_144) * 4
    assert c["bound"] == "bandwidth"
    assert not hasattr(tag_service, "SCAN")  # answered from the resource index
    a = opcost.scan_cost(attr_eq.SCAN, 10_350_000, 150_000, 2)
    assert a["bytes"] > c["bytes"]  # the attribute axis is twice the span axis
    t = opcost.timeseries_cost(rate_service.SCAN, 10_350_000, 150_000, 60)
    assert t["bytes"] == (4 * 16_777_216 + 262_144 + 262_144 + 60) * 4
    assert opcost.mesh_find_cost(150_000, 4, 1)["bound"] == "latency"
    assert opcost.select_cost(150_000, 20)["bytes"] == (2 * 262_144 + 40) * 4
