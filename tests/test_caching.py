"""Tiered cache plane tests (result cache + host chunk pool).

Tier A (services/resultcache): the frontend result cache must be
invisible to correctness -- a cache-on frontend answers every query
with the same payload a cache-off frontend computes fresh, across
pushes, flushes and compactions (the generation pair does the
invalidation); incremental extension (cached immutable prefix + tail
re-execution) must equal a full fresh execution; a hit must run zero
device launches and never reach the executor.

Tier B (ops/chunkpool): a demote -> restage round trip must rebuild
the StagedBlock bit-identically under every codec, serve it without
touching the backend read path, and keep the pool inside its
compressed-byte budget with consistent counters.

Differential corpora are pushed with now-stamped spans: the cache's
documented arrival model is "spans arrive within the live window of
their start time" -- backdated arrivals into an already-cached
historical range are accepted staleness, bounded by the TTL, and are
NOT what these tests exercise.
"""

import time

import numpy as np
import pytest

from tempo_tpu.backend import MemBackend
from tempo_tpu.block import build_block_from_traces, open_block
from tempo_tpu.db.metrics_exec import align_params
from tempo_tpu.db.metrics_exec import response_to_dict as metrics_to_dict
from tempo_tpu.db.search import SearchRequest, response_to_dict
from tempo_tpu.ops import chunkpool
from tempo_tpu.ops.filter import Cond, required_columns
from tempo_tpu.ops.stage import stage_block
from tempo_tpu.util.testdata import make_traces
from tempo_tpu.wire import otlp_pb

TENANT = "t"


@pytest.fixture(autouse=True)
def _clean_chunkpool():
    chunkpool.clear()
    yield
    chunkpool.clear()


def _mk_app(tmp_path, name):
    from tempo_tpu.services.app import App, AppConfig, IngesterConfig

    cfg = AppConfig(
        target="all", http_port=0, storage_path=str(tmp_path / name),
        compaction_cycle_s=9999,
        ingester=IngesterConfig(max_trace_idle_s=0.0, max_block_age_s=0.0,
                                flush_check_period_s=9999),
    )
    app = App(cfg)
    app.start()
    return app


def _canon_search(resp) -> list:
    """The result content, order-normalized; inspected* telemetry is
    execution cost, not result, and legitimately differs between a
    cached answer and a fresh scan."""
    return sorted(response_to_dict(resp)["traces"], key=lambda t: t["traceID"])


def _canon_metrics(resp) -> dict:
    d = metrics_to_dict(resp)
    return {
        "fn": d["fn"], "start_ms": d["start_ms"], "step_ms": d["step_ms"],
        "n_buckets": d["n_buckets"], "label_names": d["label_names"],
        "series": sorted(d["series"], key=lambda s: tuple(s["labels"])),
    }


# ---------------------------------------------------- Tier A: result cache
def test_result_cache_differential_on_off(tmp_path, monkeypatch):
    """Cache-on and cache-off frontends fed the identical
    push/flush/compact interleaving answer every query identically at
    every checkpoint -- with the cache-on app asked twice, so both the
    store path and the hit/extend path are compared."""
    monkeypatch.setenv("TEMPO_RESULT_CACHE", "1")
    on = _mk_app(tmp_path, "on")
    monkeypatch.setenv("TEMPO_RESULT_CACHE", "0")
    off = _mk_app(tmp_path, "off")
    try:
        assert on.frontend.result_cache is not None
        assert off.frontend.result_cache is None
        t_on, t_off = on.tenant_of({}), off.tenant_of({})
        seed = [0]

        def push(n):
            seed[0] += 1
            now_ns = time.time_ns()
            for _, tr in make_traces(n, seed=100 + seed[0], n_spans=4,
                                     base_time_ns=now_ns):
                blob = otlp_pb.encode_trace(tr)
                on.distributor.push_raw(t_on, blob)
                off.distributor.push_raw(t_off, blob)

        def flush():
            for app, ten in ((on, t_on), (off, t_off)):
                app.ingester.flush_all()
                app.db.poll_now()

        def compact():
            for app, ten in ((on, t_on), (off, t_off)):
                app.db.cfg.compaction.min_input_blocks = 2
                app.db.compact_once(ten)
                app.db.poll_now()

        grid0 = (int(time.time()) // 5) * 5 - 300

        def check():
            now = int(time.time())
            sreqs = [
                SearchRequest(query="{ true }", limit=500),
                SearchRequest(query="{ true }", start=now - 300, end=now + 5,
                              limit=500),
            ]
            for req in sreqs:
                fresh = _canon_search(off.frontend.search(t_off, req))
                first = _canon_search(on.frontend.search(t_on, req))
                again = _canon_search(on.frontend.search(t_on, req))
                assert first == fresh
                assert again == fresh
            mreq = align_params("{ true } | count_over_time()",
                                grid0, now + 5, 5.0)
            mfresh = _canon_metrics(off.frontend.metrics_query_range(t_off, mreq))
            mfirst = _canon_metrics(on.frontend.metrics_query_range(t_on, mreq))
            magain = _canon_metrics(on.frontend.metrics_query_range(t_on, mreq))
            assert mfirst == mfresh
            assert magain == mfresh

        push(6); check()
        flush(); check()
        push(6); check()
        flush(); check()
        compact(); check()
        rc = on.frontend.result_cache
        # the repeats were served by the cache, and the mutation
        # checkpoints actually invalidated (not just missed)
        assert rc.stats_hits >= 1
        assert rc.stats_invalidations >= 1
    finally:
        on.stop()
        off.stop()


def test_extension_matches_fresh_execution(tmp_path, monkeypatch):
    """A moving now-edge repeat (cached immutable prefix + re-executed
    tail) must equal a full fresh execution, for search and metrics."""
    monkeypatch.setenv("TEMPO_RESULT_CACHE", "1")
    monkeypatch.setenv("TEMPO_RESULT_CACHE_LIVE_WINDOW_S", "2.0")
    app = _mk_app(tmp_path, "ext")
    try:
        tenant = app.tenant_of({})
        rc = app.frontend.result_cache
        # batch A: stamped 30s back, flushed to the backend -- the
        # immutable prefix content
        for _, tr in make_traces(10, seed=1, n_spans=4,
                                 base_time_ns=time.time_ns() - 30 * 10**9):
            app.distributor.push_raw(tenant, otlp_pb.encode_trace(tr))
        app.ingester.flush_all()
        app.db.poll_now()

        t1 = int(time.time())
        sreq1 = SearchRequest(query="{ true }", start=t1 - 60, end=t1, limit=500)
        app.frontend.search(tenant, sreq1)  # miss: stores exact + prefix
        mreq1 = align_params("{ true } | count_over_time()",
                             t1 - 300, t1, 5.0)
        app.frontend.metrics_query_range(tenant, mreq1)

        # batch B: now-stamped, lives in the ingester head -- only the
        # tail slice can see it
        for _, tr in make_traces(8, seed=2, n_spans=4,
                                 base_time_ns=time.time_ns()):
            app.distributor.push_raw(tenant, otlp_pb.encode_trace(tr))

        ext0 = rc.stats_extensions
        t2 = int(time.time()) + 1
        sreq2 = SearchRequest(query="{ true }", start=t1 - 60, end=t2, limit=500)
        got = _canon_search(app.frontend.search(tenant, sreq2))
        want = _canon_search(app.frontend._search_exec(tenant, sreq2))
        assert got == want
        assert any(True for _ in got), "extension corpus not searchable"

        mreq2 = align_params("{ true } | count_over_time()",
                             t1 - 300, t2 + 5, 5.0)
        mgot = _canon_metrics(app.frontend.metrics_query_range(tenant, mreq2))
        mwant = _canon_metrics(app.frontend._metrics_exec(tenant, mreq2))
        assert mgot == mwant
        assert rc.stats_extensions > ext0, \
            "repeat did not take the extension path"
    finally:
        app.stop()


def test_generation_invalidation(tmp_path, monkeypatch):
    """Push (live gen), flush+poll and compaction (blocklist gen) must
    each invalidate, with fresh data visible immediately after."""
    monkeypatch.setenv("TEMPO_RESULT_CACHE", "1")
    app = _mk_app(tmp_path, "gen")
    try:
        tenant = app.tenant_of({})
        rc = app.frontend.result_cache

        def push(n, seed):
            tids = []
            for tid, tr in make_traces(n, seed=seed, n_spans=4,
                                       base_time_ns=time.time_ns()):
                app.distributor.push_raw(tenant, otlp_pb.encode_trace(tr))
                tids.append(tid)
            return tids

        tids = push(6, 11)
        req = SearchRequest(query="{ true }", limit=500)
        r1 = app.frontend.search(tenant, req)
        h0 = rc.stats_hits
        r2 = app.frontend.search(tenant, req)
        assert rc.stats_hits == h0 + 1
        assert _canon_search(r2) == _canon_search(r1)

        # by-id rides the same generations
        b0 = rc.stats_hits
        tr1 = app.frontend.find_trace_by_id(tenant, tids[0])
        tr2 = app.frontend.find_trace_by_id(tenant, tids[0])
        assert tr1 is not None and tr2 == tr1
        assert rc.stats_hits == b0 + 1

        # push -> live generation bump: the new trace must be visible
        inv0 = rc.stats_invalidations
        new_tids = push(2, 12)
        r3 = app.frontend.search(tenant, req)
        assert new_tids[0].hex() in {t["traceID"] for t in _canon_search(r3)}
        assert rc.stats_invalidations > inv0

        # flush + poll -> blocklist generation bump; the trace set is
        # unchanged (same corpus, different placement -- presentation
        # fields like rootTraceName are leg-dependent), entry re-keyed
        def ids(resp):
            return sorted((t["traceID"], t["startTimeUnixNano"])
                          for t in response_to_dict(resp)["traces"])

        inv1 = rc.stats_invalidations
        app.ingester.flush_all()
        app.db.poll_now()
        r4 = app.frontend.search(tenant, req)
        assert ids(r4) == ids(r3)
        assert rc.stats_invalidations > inv1

        # second block, then compaction -> blocklist generation bump
        push(2, 13)
        app.ingester.flush_all()
        app.db.poll_now()
        r5 = app.frontend.search(tenant, req)
        inv2 = rc.stats_invalidations
        app.db.cfg.compaction.min_input_blocks = 2
        assert app.db.compact_once(tenant), "compaction did not run"
        app.db.poll_now()
        r6 = app.frontend.search(tenant, req)
        assert ids(r6) == ids(r5)
        assert rc.stats_invalidations > inv2
    finally:
        app.stop()


def test_result_cache_hit_zero_work(tmp_path, monkeypatch):
    """An exact hit is answered entirely at the cache layer: zero
    device launches, and the executor is provably never entered."""
    from tempo_tpu.util.kerneltel import TEL

    monkeypatch.setenv("TEMPO_RESULT_CACHE", "1")
    app = _mk_app(tmp_path, "zero")
    try:
        tenant = app.tenant_of({})
        for _, tr in make_traces(8, seed=3, n_spans=4,
                                 base_time_ns=time.time_ns()):
            app.distributor.push_raw(tenant, otlp_pb.encode_trace(tr))
        req = SearchRequest(query="{ true }", limit=500)
        r1 = app.frontend.search(tenant, req)
        assert r1.traces

        def boom(*a, **k):
            raise AssertionError("cache hit reached the executor")

        monkeypatch.setattr(app.frontend, "_search_exec", boom)
        l0 = TEL.launch_count()
        r2 = app.frontend.search(tenant, req)
        assert TEL.launch_count() - l0 == 0
        assert _canon_search(r2) == _canon_search(r1)
    finally:
        app.stop()


def test_result_cache_kill_switch(tmp_path, monkeypatch):
    """TEMPO_RESULT_CACHE=0 skips construction entirely -- every
    request executes fresh through the pre-cache path."""
    monkeypatch.setenv("TEMPO_RESULT_CACHE", "0")
    app = _mk_app(tmp_path, "off2")
    try:
        assert app.frontend.result_cache is None
        tenant = app.tenant_of({})
        for _, tr in make_traces(4, seed=4, n_spans=4,
                                 base_time_ns=time.time_ns()):
            app.distributor.push_raw(tenant, otlp_pb.encode_trace(tr))
        req = SearchRequest(query="{ true }", limit=500)
        r1 = app.frontend.search(tenant, req)
        r2 = app.frontend.search(tenant, req)
        assert r1.traces and _canon_search(r2) == _canon_search(r1)
    finally:
        app.stop()


# ---------------------------------------------------- Tier B: chunk pool
def _block(n_traces=120, seed=5):
    backend = MemBackend()
    traces = make_traces(n_traces, seed=seed, n_spans=10)
    meta = build_block_from_traces(backend, TENANT, traces, row_group_spans=256)
    return backend, meta, open_block(backend, TENANT, meta.block_id)


_NEEDED = required_columns((Cond(target="res", col="res.service_id", op="eq"),))


def _pool_keys(blk, needed=_NEEDED, groups=None):
    """The one-column pool keys of a request, as ops/stage spells them."""
    from tempo_tpu.ops.stage import _pool_key, column_keys

    return [_pool_key(k) for k in column_keys(blk, needed, groups).values()]


def _demote_all(meta, blk, staged):
    """Demote every device column of a staged request, one entry each."""
    keys = _pool_keys(blk)
    assert sorted(k[0][0] for k in keys) == sorted(staged.cols)
    return keys, [chunkpool.demote(meta.block_id, k, staged.cols[k[0][0]])
                  for k in keys]


@pytest.mark.parametrize("codec", ["none", "lz4", "snappy", "zstd"])
def test_chunkpool_roundtrip_bit_identity(codec, monkeypatch):
    """demote -> restage rebuilds every column of a StagedBlock
    bit-identically under every codec (same dtypes/shapes/bytes), and a
    fresh reader's view over the restaged columns carries the same
    padded-shape fields."""
    monkeypatch.setenv("TEMPO_CHUNK_CACHE", "1")
    monkeypatch.setenv("TEMPO_CHUNK_CACHE_CODEC", codec)
    monkeypatch.setenv("TEMPO_CHUNK_CACHE_MIN_REUSE", "1")
    backend, meta, blk = _block()
    staged = stage_block(blk, _NEEDED)
    ref = {k: np.asarray(v).copy() for k, v in staged.cols.items()}
    shape_ref = (staged.n_spans, staged.n_traces, staged.n_res,
                 staged.n_spans_b, staged.n_traces_b, staged.n_res_b,
                 staged.span_base)
    keys, admitted = _demote_all(meta, blk, staged)
    assert all(admitted)
    got = chunkpool.restage(meta.block_id, keys)
    assert set(got) == set(keys)
    for key, dev in got.items():
        arr = np.asarray(dev)
        assert arr.dtype == ref[key[0][0]].dtype
        np.testing.assert_array_equal(arr, ref[key[0][0]])
    view = stage_block(open_block(backend, TENANT, meta.block_id), _NEEDED)
    assert set(view.cols) == set(ref)
    assert (view.n_spans, view.n_traces, view.n_res, view.n_spans_b,
            view.n_traces_b, view.n_res_b, view.span_base) == shape_ref
    assert chunkpool.stats()["codec"] == codec


def test_chunkpool_restage_skips_backend_read(monkeypatch):
    """A fresh reader staging pooled columns must be served from the
    pool: the backend read/decode/assemble path is provably never
    entered."""
    monkeypatch.setenv("TEMPO_CHUNK_CACHE", "1")
    monkeypatch.setenv("TEMPO_CHUNK_CACHE_CODEC", "none")
    monkeypatch.setenv("TEMPO_CHUNK_CACHE_MIN_REUSE", "1")
    backend, meta, blk = _block()
    staged = stage_block(blk, _NEEDED)
    ref = {k: np.asarray(v).copy() for k, v in staged.cols.items()}
    keys, admitted = _demote_all(meta, blk, staged)
    assert all(admitted)

    def boom(*a, **k):
        raise AssertionError("restage fell through to the backend read path")

    monkeypatch.setattr("tempo_tpu.ops.stage.read_stage_columns", boom)
    h0 = chunkpool.stats()["hits"]
    fresh_blk = open_block(backend, TENANT, meta.block_id)
    warm = stage_block(fresh_blk, _NEEDED)
    assert chunkpool.stats()["hits"] == h0 + len(keys)
    assert set(warm.cols) == set(ref)
    for name in ref:
        np.testing.assert_array_equal(np.asarray(warm.cols[name]), ref[name])


def test_chunkpool_budget_and_admission(monkeypatch):
    """The pool stays inside its compressed-byte budget (LRU-oldest
    evicted, counters consistent) and the per-entry/reuse admission
    gates reject what they should."""
    monkeypatch.setenv("TEMPO_CHUNK_CACHE", "1")
    monkeypatch.setenv("TEMPO_CHUNK_CACHE_CODEC", "none")
    monkeypatch.setenv("TEMPO_CHUNK_CACHE_MIN_REUSE", "1")
    key = (("span.trace_sid",), None)
    blocks = []
    for i in range(4):
        _, meta, blk = _block(n_traces=60, seed=20 + i)
        blocks.append(
            (meta, stage_block(blk, _NEEDED, cache=False).cols["span.trace_sid"]))

    # size one entry, then budget for two-and-a-half of them
    s0 = chunkpool.stats()
    assert chunkpool.demote(blocks[0][0].block_id, key, blocks[0][1])
    one = chunkpool.stats()["compressed_bytes"]
    assert one > 0
    monkeypatch.setenv("TEMPO_CHUNK_CACHE_BUDGET", str(one * 5 // 2))
    for meta, col in blocks[1:]:
        assert chunkpool.demote(meta.block_id, key, col)
    st = chunkpool.stats()
    assert st["compressed_bytes"] <= one * 5 // 2
    assert st["entries"] == 2
    assert st["demotions"] - s0["demotions"] == 4
    assert st["evictions"] - s0["evictions"] == 2
    # LRU order: the oldest two went, the newest two stayed
    assert not chunkpool.probe(blocks[0][0].block_id, key)
    assert not chunkpool.probe(blocks[1][0].block_id, key)
    assert chunkpool.probe(blocks[2][0].block_id, key)
    assert chunkpool.probe(blocks[3][0].block_id, key)

    # per-entry admission cap: an oversized column is refused
    chunkpool.clear()
    raw = int(np.asarray(blocks[0][1]).nbytes)
    monkeypatch.setenv("TEMPO_CHUNK_CACHE_MAX_ENTRY", str(raw // 2))
    assert not chunkpool.demote(blocks[0][0].block_id, key, blocks[0][1])
    assert chunkpool.stats()["entries"] == 0
    monkeypatch.delenv("TEMPO_CHUNK_CACHE_MAX_ENTRY")

    # reuse admission: one staging is not worth host RAM at MIN_REUSE=2
    monkeypatch.setenv("TEMPO_CHUNK_CACHE_MIN_REUSE", "2")
    assert not chunkpool.demote(blocks[0][0].block_id, key, blocks[0][1])
    chunkpool.note_stage(blocks[0][0].block_id, key)
    chunkpool.note_stage(blocks[0][0].block_id, key)
    assert chunkpool.demote(blocks[0][0].block_id, key, blocks[0][1])


def test_chunk_cache_kill_switch(monkeypatch):
    """TEMPO_CHUNK_CACHE=0 restores discard-on-evict exactly: nothing
    is admitted, probed or restaged."""
    monkeypatch.setenv("TEMPO_CHUNK_CACHE", "0")
    _, meta, blk = _block(n_traces=40, seed=30)
    staged = stage_block(blk, _NEEDED, cache=False)
    d0 = chunkpool.stats()["demotions"]
    keys, admitted = _demote_all(meta, blk, staged)
    assert not any(admitted)
    st = chunkpool.stats()
    assert not st["enabled"]
    assert st["entries"] == 0 and st["demotions"] == d0
    assert not any(chunkpool.probe(meta.block_id, k) for k in keys)
    assert chunkpool.restage(meta.block_id, keys) == {}
