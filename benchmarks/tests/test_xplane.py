"""The trace reduction: busy union, idle share, per-module time, gap owner --
on planes written by the tests' own XSpace writer and read back through
jax.profiler (the path a run takes), and on the recorded fixture."""
import json
import os

import pytest

from benchmarks.lib import xplane
from benchmarks.tests.xspace_writer import encode_xspace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000  # ns


@pytest.fixture(scope="module")
def table():
    with open(os.path.join(os.path.dirname(HERE), "lib", "module_ops.json")) as f:
        return json.load(f)


def synthetic():
    t = 1_000 * MS
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_run(111)", t + 10 * MS, 20 * MS),
            ("jit_sel(222)", t + 30 * MS, 5 * MS),
            ("jit_run(111)", t + 60 * MS, 20 * MS)]},
        {"name": "XLA Ops", "events": [
            ("fusion.1", t + 10 * MS, 12 * MS),
            ("fusion.2", t + 20 * MS, 10 * MS),      # overlaps fusion.1 by 2 ms
            ("sort.3", t + 30 * MS, 5 * MS),
            ("fusion.1", t + 60 * MS, 20 * MS)]}]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            ("$threading.py:1 _bootstrap", t, 100 * MS),           # a wait name
            ("$search.py:10 verify_rows", t + 35 * MS, 24 * MS),   # owns 35..60
            ("$hosteval.py:5 inner", t + 40 * MS, 10 * MS),
            ("$app.py:3 parse", t + 80 * MS, 20 * MS)]}]}         # owns 80..100
    return [dev, host]


def check_synthetic(out):
    assert out["window_s"] == pytest.approx(0.100)
    assert len(out["devices"]) == 1
    # busy: 10..35 and 60..80 ms
    assert out["busy_s"] == pytest.approx(0.045)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.55)
    assert out["modules"]["jit_run(111)"]["seconds"] == pytest.approx(0.040)
    assert out["modules"]["jit_run(111)"]["count"] == 2
    fam = out["families"]
    assert fam["scan(filter|multiquery|timeseries|live_filter)"]["seconds"] == pytest.approx(0.040)
    assert fam["select(select|mq_select)"]["seconds"] == pytest.approx(0.005)
    assert out["device_ops"][0][0].endswith("jit_run(111)")
    gaps = out["idle_gaps"]
    assert [round(g[1], 3) for g in gaps] == [0.025, 0.02, 0.01]
    assert gaps[0][0] == "search.py:10 verify_rows"   # the longest overlap wins
    assert gaps[1][0] == "app.py:3 parse"
    assert gaps[2][0] == "unattributed"               # only a wait frame covers 0..10


def test_reduction_on_plain_planes(table):
    check_synthetic(xplane.reduce_planes(synthetic(), table))


def test_reduction_through_the_profilers_reader(table, tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(encode_xspace(synthetic()))
    check_synthetic(xplane.reduce_planes(xplane.read_planes(str(path)), table))


def test_window_ends_where_the_profiler_was_asked_to_stop(table):
    """What the file holds after the asked seconds is the profiler stopping
    (a freeze, then a tail): neither window nor busy time."""
    planes = synthetic()
    t = 1_000 * MS
    # the tail after the freeze: one more launch and one more host event
    planes[0]["lines"][0]["events"].append(("jit_run(111)", t + 900 * MS, 20 * MS))
    planes[0]["lines"][1]["events"].append(("fusion.1", t + 900 * MS, 20 * MS))
    planes[1]["lines"][0]["events"].append(("$app.py:3 parse", t + 890 * MS, 40 * MS))
    whole = xplane.reduce_planes(planes, table)
    assert whole["window_s"] == pytest.approx(0.930)
    assert whole["busy_s"] == pytest.approx(0.065)
    check_synthetic(xplane.reduce_planes(planes, table, asked_s=0.100))
    out = xplane.reduce_planes(planes, table, asked_s=0.070)
    assert out["session_s"] == pytest.approx(0.930)
    assert out["window_s"] == pytest.approx(0.070)
    assert out["busy_s"] == pytest.approx(0.035)   # 10..35 and 60..70 ms
    assert out["modules"]["jit_run(111)"]["seconds"] == pytest.approx(0.030)
    assert [round(g[1], 3) for g in out["idle_gaps"]] == [0.025, 0.01]


def test_no_device_plane_gives_nothing_to_read(table):
    out = xplane.reduce_planes([synthetic()[1]], table)
    assert out["devices"] == [] and out["busy_s"] == 0.0 and out["idle_gaps"] == []


def test_a_session_without_a_device_plane_is_told_without_jax(tmp_path):
    """The harness takes such a session again: it has to see, off jax,
    whether the profiler wrote a TPU plane."""
    import io
    import zipfile

    from benchmarks.lib.cell import xspace_has_device

    def zipped(planes):
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w") as z:
            z.writestr("plugins/profile/x/host.xplane.pb", encode_xspace(planes))
            z.writestr("plugins/profile/x/host.trace.json.gz", b"/device:TPU:0")
        return buf.getvalue()

    assert xspace_has_device(zipped(synthetic()))
    assert not xspace_has_device(zipped([synthetic()[1]]))


def test_recorded_fixture(table):
    """Recorded on the v5e by this benchmark (chip1-find traced run, PR 22):
    the first 60 events of every line of device 0 and of the four busiest
    host threads, written with xspace_writer. Truncated, so its XLA Ops line
    ends long before its XLA Modules line: a fixture for the reduction, not a
    reading. The expected numbers are the reduction of the same events as
    plain data, spot-checked by hand (four 153.7 ms launches of one jit_run
    = 0.6149 s)."""
    pb = os.path.join(HERE, "fixtures", "small.xplane.pb")
    with open(os.path.join(HERE, "fixtures", "small.expected.json")) as f:
        want = json.load(f)
    out = xplane.reduce_planes(xplane.read_planes(pb), table)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert len(out["devices"]) == want["n_devices"]
    for fam, secs in want["families"].items():
        assert out["families"][fam]["seconds"] == pytest.approx(secs, rel=1e-6)
    assert [g[0] for g in out["idle_gaps"][:3]] == want["gap_owners"][:3]
