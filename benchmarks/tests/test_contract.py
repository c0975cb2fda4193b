"""BENCHMARK.json against the contract's limits, and what it names exists."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_lines(bench):
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for entry in bench["configs"] + bench["workloads"]:
        assert NAME.match(entry["name"])
        assert 1 <= len(entry["why"]) <= 200 and "\t" not in entry["why"]
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    sources = [c["source"] for c in bench["configs"]]
    assert len(sources) == len(set(sources))


def test_cells_and_the_files_they_name(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    configs = {c["name"]: c for c in bench["configs"]}
    assert {w["config"] for w in cells} == set(configs)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        cfg_file = os.path.join(ROOT, configs[w["config"]]["file"])
        with open(cfg_file) as f:
            cfg = json.load(f)
        assert cfg["chips"] == w["chips"]
        assert cfg["source"] == configs[w["config"]]["source"]
        for key in configs[w["config"]]["reduced"]:
            assert key in cfg
        with open(os.path.join(BENCH, "mixes", w["traffic"] + ".json")) as f:
            mix = json.load(f)
        for s in mix["streams"]:
            for sh in s["shapes"]:
                assert os.path.exists(os.path.join(BENCH, "shapes", sh["shape"] + ".py"))
        for chk in mix.get("after_window", []):
            assert os.path.exists(os.path.join(BENCH, "checks", chk["check"] + ".py"))


def test_every_metric_has_its_reader_and_every_cell_its_metrics(bench):
    for m in bench["end_to_end"]:
        assert os.path.exists(os.path.join(BENCH, "e2e_metrics", m["name"] + ".py"))
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
    for w in bench["workloads"]:
        e2e = {m["name"] for m in bench["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in bench["per_layer"]
                 if w["name"] in m.get("workloads", [w["name"]])]
        assert layer and all(m["moves"] in e2e for m in layer)


def test_last_line_has_the_contracts_keys():
    from benchmarks.lib.cell import result_line

    device = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}
    line = result_line([], 10, 0, {"setup_s": {"value": 1.5, "unit": "s"}},
                       device, 123, None)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    trace = {"devices": [{}], "busy_s": 0.5, "window_s": 8.0,
             "device_ops": [["a", 0.1]], "idle_gaps": [["b", 0.2]]}
    checks = {"answers_wrong_or_failed": {"value": 1, "limit": 0}}
    line = result_line(["x"], 10, 1, {}, device, 123, trace, checks)
    assert line["correct"] is False
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device",
                         "breakdown", "checks"}
    assert list(line)[-1] == "checks" and line["checks"] == checks  # last, as compared
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    json.dumps(line)
