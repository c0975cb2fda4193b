"""Conformance: the bench's synthetic block is indistinguishable from a
builder-produced block to the read path (same column set, working find +
search), so bench numbers measure the real format."""

import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))  # bench.py

from tempo_tpu.backend import MemBackend  # noqa: E402
from tempo_tpu.block import build_block_from_traces  # noqa: E402
from tempo_tpu.block.reader import BackendBlock, open_block  # noqa: E402
from tempo_tpu.db.search import SearchRequest, search_block  # noqa: E402
from tempo_tpu.util.testdata import make_traces, synth_block  # noqa: E402


def test_synth_block_matches_builder_columns():
    be = MemBackend()
    rng = np.random.default_rng(1)
    meta, ids = synth_block(be, "t", rng, 64, 4, n_res=8)
    synth_names = set(BackendBlock(be, meta).pack.names())

    be2 = MemBackend()
    m2 = build_block_from_traces(be2, "t", make_traces(8, seed=2))
    built_names = set(BackendBlock(be2, m2).pack.names())
    assert synth_names == built_names


def test_synth_block_find_and_search():
    be = MemBackend()
    rng = np.random.default_rng(3)
    meta, ids = synth_block(be, "t", rng, 128, 8, n_res=16)
    blk = open_block(be, "t", meta.block_id)
    # find every 10th id
    for i in range(0, 128, 10):
        t = blk.find_trace_by_id(ids[i].tobytes())
        assert t is not None and t.span_count() == 8
    assert blk.find_trace_by_id(b"\x00" * 16) is None
    # search on the dedicated service column
    resp = search_block(blk, SearchRequest(tags={"service.name": "svc-003"}, limit=1000))
    svc_col = blk.pack.read("res.service_id")
    res_idx = blk.pack.read("span.res_idx")
    sid_col = blk.pack.read("span.trace_sid")
    code = blk.dictionary.lookup("svc-003")
    expect = {ids[s].tobytes().hex()
              for s in np.unique(sid_col[svc_col[res_idx] == code])}
    assert {r.trace_id for r in resp.traces} == expect


def test_tel_close_workers_normalizes_device_time_share(monkeypatch):
    """Concurrent sections accumulate device seconds across Q threads
    while wall time doesn't: without the workers divisor the share reads
    ~Q (BENCH_r06 search_concurrent reported 3.85). With it, a section
    whose threads were device-busy the whole time reads <= ~1."""
    import time as _time

    from bench import _tel_close
    from tempo_tpu.util import kerneltel as kt

    mark = (0, 0.0, _time.perf_counter() - 0.1)  # section wall ~0.1s
    # 4 threads x ~0.09s device time each inside that 0.1s wall
    monkeypatch.setattr(kt.TEL, "totals", lambda: (0, 0.36))
    raw = _tel_close(mark)
    assert raw["device_time_share"] > 2.0  # the r06 artifact, reproduced
    share = _tel_close(mark, workers=4)["device_time_share"]
    assert 0.0 < share <= 1.05
    assert abs(share - raw["device_time_share"] / 4) < 0.05
