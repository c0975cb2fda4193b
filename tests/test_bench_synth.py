"""Conformance: the synthetic block (util/testdata.synth_block) is
indistinguishable from a builder-produced block to the read path (same
column set, working find + search), so numbers taken over synthetic
corpora measure the real format."""

import numpy as np

from tempo_tpu.backend import MemBackend
from tempo_tpu.block import build_block_from_traces
from tempo_tpu.block.reader import BackendBlock, open_block
from tempo_tpu.db.search import SearchRequest, search_block
from tempo_tpu.util.testdata import make_traces, synth_block


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
