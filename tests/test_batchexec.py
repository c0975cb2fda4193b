"""Cross-query batching executor: differential equivalence, launch
accounting, fairness, queue pruning, staged-LRU accounting."""

from __future__ import annotations

import gc
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from tempo_tpu.backend.mem import MemBackend
from tempo_tpu.db.search import SearchRequest, search_block
from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
from tempo_tpu.util.kerneltel import TEL
from tempo_tpu.util.testdata import make_traces

TENANT = "batch-t"


def _mkdb(**over) -> TempoDB:
    cfg = TempoDBConfig(
        wal_path=tempfile.mkdtemp(prefix="tempo-batch-wal"),
        batch_window_ms=over.pop("batch_window_ms", 200.0),
        batch_max=over.pop("batch_max", 16),
        device_promote_touches=over.pop("device_promote_touches", 1),
        **over,
    )
    return TempoDB(cfg, backend=MemBackend())


def _dicts(resp):
    return [{**t.to_dict(), "matchedSpans": t.matched_spans} for t in resp.traces]


# ---------------------------------------------------------- lowering


def test_lower_plan_eligibility():
    from tempo_tpu.db.search import _plan_for_block
    from tempo_tpu.ops.multiquery import lower_plan

    db = _mkdb()
    m = db.write_block(TENANT, make_traces(30, seed=21, n_spans=6))
    blk = db.open_block(m)

    def lowered(q):
        p = _plan_for_block(blk, SearchRequest(query=q))
        return None if p.prune else lower_plan(p)

    # eligible: dedicated-column scalar compares, and/or combinations
    assert lowered('{ name = "db.query" }') is not None
    assert lowered('{ duration > 100ms }') is not None
    assert lowered('{ status = error && kind = server }') is not None
    assert lowered('{ name = "GET /" || duration < 1ms }') is not None
    assert lowered('{ span.http.status_code >= 500 }') is not None
    # span + res mix is eligible (res conds ride span@ materialization)
    assert lowered(
        '{ name = "db.query" && resource.service.name = "auth" }') is not None
    # ineligible: generic attr table, regex, structural relation
    assert lowered('{ span.component = "grpc" }') is None
    assert lowered('{ name =~ "GET.*" }') is None
    assert lowered('{ name = "GET /" } >> { name = "db.query" }') is None


# ------------------------------------------------- differential equivalence


# mix of batcher-eligible and fallback queries; every one must come out
# identical to the sequential single-query engine
_QUERIES = [
    '{ name = "db.query" }',
    '{ name != "render" }',
    '{ duration > 500ms }',
    '{ status = error }',
    '{ kind = server }',
    '{ span.http.method = "GET" && duration > 10ms }',
    '{ span.http.status_code >= 500 }',
    '{ name = "GET /api" || name = "cache.get" }',
    '{ name = "db.query" && resource.service.name = "db" }',
    '{ span.http.status_code = 200 && status != error }',
    # fallback paths (ineligible for the fused kernel)
    '{ span.component = "grpc" }',
    '{ name =~ "GET .*" }',
]


def test_differential_batched_vs_sequential():
    """N random TraceQL queries concurrently through the batcher vs
    sequentially through db/search.py: bit-identical result sets."""
    db = _mkdb()
    m = db.write_block(TENANT, make_traces(120, seed=7, n_spans=8))
    blk = db.open_block(m)
    # limit >= total traces: no truncation, so fallback engines with a
    # different (exact) candidate selection order converge too
    reqs = [SearchRequest(query=q, limit=200) for q in _QUERIES] * 2
    expected = [_dicts(search_block(blk, r)) for r in reqs]
    with ThreadPoolExecutor(len(reqs)) as ex:
        futs = [ex.submit(db.search_blocks, TENANT, [m], r) for r in reqs]
        got = [_dicts(f.result()) for f in futs]
    for q, e, g in zip([r.query for r in reqs], expected, got):
        assert e == g, f"batched != sequential for {q!r}"


def test_batched_launch_reduction_and_identity():
    """16 concurrent identical-shape queries against one staged block:
    >= 8x fewer device launches than the sequential device path, with
    bit-identical results (the ISSUE acceptance criterion)."""
    db = _mkdb()
    m = db.write_block(TENANT, make_traces(150, seed=9, n_spans=8))
    blk = db.open_block(m)
    req = SearchRequest(query='{ name != "zzz" && duration > 1ms }', limit=10)

    from tempo_tpu.db.batchexec import batched_search_block_many

    # warm: stages the block + compiles both fused and sequential programs
    warm = batched_search_block_many(db.batchers.search, [(blk, req, None)])
    assert warm[0] is not None
    seq_ref = search_block(blk, req, mode="device")
    assert _dicts(warm[0]) == _dicts(seq_ref)

    l0 = TEL.launch_count()
    outs = batched_search_block_many(
        db.batchers.search, [(blk, req, None)] * 16)
    batched_launches = TEL.launch_count() - l0
    assert all(o is not None for o in outs)
    for o in outs:
        assert _dicts(o) == _dicts(seq_ref)

    l1 = TEL.launch_count()
    for _ in range(16):
        search_block(blk, req, mode="device")
    seq_launches = TEL.launch_count() - l1
    assert batched_launches > 0
    assert seq_launches >= 8 * batched_launches, (
        f"batched={batched_launches} sequential={seq_launches}")

    # the same 16 queries from real concurrent threads also coalesce
    stats0 = TEL.batch_stats().get("search", {})
    with ThreadPoolExecutor(16) as ex:
        futs = [ex.submit(db.search_blocks, TENANT, [m], req)
                for _ in range(16)]
        for f in futs:
            assert _dicts(f.result()) == _dicts(seq_ref)
    stats1 = TEL.batch_stats()["search"]
    assert stats1["max_occupancy"] >= 2  # threads actually shared launches
    assert stats1["queries"] > stats0.get("queries", 0)


def test_find_batched_equivalence():
    """Concurrent trace-by-ID lookups coalesce through the find batcher
    and return the same traces as the sequential path."""
    traces = make_traces(60, seed=11, n_spans=5)
    db = _mkdb()
    m = db.write_block(TENANT, traces)
    ids = [tid for tid, _ in traces[:10]]
    seq = [db._device_find(db.find_candidates(TENANT, i), i) for i in ids]
    with ThreadPoolExecutor(10) as ex:
        futs = [ex.submit(db.find_trace_by_id, TENANT, i) for i in ids]
        got = [f.result() for f in futs]
    for i, (s, g) in enumerate(zip(seq, got)):
        assert (g is not None) == bool(s)
        if s:
            from tempo_tpu.wire.combine import combine_traces
            from tempo_tpu.wire import otlp_json

            assert otlp_json.dumps(g) == otlp_json.dumps(combine_traces(s))
    assert TEL.batch_stats().get("find", {}).get("queries", 0) >= 10


def test_lone_query_skips_window():
    """A lone query on an idle executor must not pay the admission
    window (and can never be delayed past it)."""
    db = _mkdb(batch_window_ms=500.0)
    traces = make_traces(40, seed=13, n_spans=4)
    m = db.write_block(TENANT, traces)
    req = SearchRequest(query='{ name != "zzz" }', limit=5)
    db.search_blocks(TENANT, [m], req)  # warm: staging + compiles
    t0 = time.perf_counter()
    db.search_blocks(TENANT, [m], req)
    assert time.perf_counter() - t0 < 0.5  # ran without the 500 ms window
    # back-to-back sequential traffic (search and find) must not pay the
    # window either: only a concurrent submitter holds it open
    db.find_trace_by_id(TENANT, traces[0][0])  # warm find path
    t0 = time.perf_counter()
    for tid, _ in traces[1:5]:
        assert db.find_trace_by_id(TENANT, tid) is not None
    assert time.perf_counter() - t0 < 4 * 0.5  # 4 lookups, no 500 ms waits


# --------------------------------------------------------------- fairness


def test_tenant_fairness_under_flood():
    """Tenant B's job is dequeued fairly (and joins batches) while
    tenant A floods the queue; B is never starved past one rotation."""
    from tempo_tpu.services.frontend import RequestQueue, _Job

    q = RequestQueue()
    for i in range(50):
        q.enqueue("A", _Job(kind="search_blocks", payload={}, fn=None,
                            args=(), batch_key=("k", "A")))
    q.enqueue("B", _Job(kind="search_blocks", payload={}, fn=None,
                        args=(), batch_key=("k", "B")))
    seen_tenants = []
    for _ in range(2):
        tenant, job, extras = q.dequeue_batch(
            timeout=0.1, max_batch=8, key_fn=lambda j: j.batch_key)
        seen_tenants.append(tenant)
    assert "B" in seen_tenants  # one rotation at most, despite A's flood


def test_batch_executor_cross_tenant_group():
    """Items from different submitters under one key demux correctly,
    and per-item runner errors only fail their own submitter."""
    from tempo_tpu.db.batchexec import BatchExecutor

    def runner(key, items):
        return [ValueError("boom") if it == "bad" else f"ok:{it}"
                for it in items]

    ex = BatchExecutor("test", runner, window_s=0.05, max_batch=8)
    results = {}
    errs = {}

    def submit(item):
        try:
            results[item] = ex.submit("k", item)
        except ValueError as e:
            errs[item] = e

    ts = [threading.Thread(target=submit, args=(it,))
          for it in ("a", "bad", "c")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert results == {"a": "ok:a", "c": "ok:c"}
    assert "bad" in errs


# ---------------------------------------------------------- queue pruning


def test_request_queue_prunes_drained_tenants():
    """Regression: tenants were appended to the rotation on first
    enqueue but never removed when their deque drained."""
    from tempo_tpu.services.frontend import RequestQueue, _Job

    q = RequestQueue()
    for i in range(100):
        tenant = f"churn-{i}"
        q.enqueue(tenant, _Job(kind="x", payload={}, fn=None, args=()))
        assert q.dequeue(timeout=0.1) is not None
    with q.lock:
        assert len(q.order) == 0
        assert len(q.queues) == 0
    # interleaved: live tenants stay, drained ones go
    q.enqueue("live", _Job(kind="x", payload={}, fn=None, args=()))
    q.enqueue("live", _Job(kind="x", payload={}, fn=None, args=()))
    q.enqueue("dead", _Job(kind="x", payload={}, fn=None, args=()))
    got = {q.dequeue(timeout=0.1)[0] for _ in range(2)}
    assert got == {"live", "dead"}
    with q.lock:
        assert list(q.order) == ["live"]
    assert q.dequeue(timeout=0.1)[0] == "live"
    with q.lock:
        assert len(q.order) == 0 and len(q.queues) == 0


# ------------------------------------------------------ staged-LRU sweep


def test_staged_lru_sweeps_dead_weakrefs():
    """An entry whose block weakref died must release its nbytes from
    the global staged-cache accounting on the next insert/evict."""
    from tempo_tpu.block.builder import build_block_from_traces
    from tempo_tpu.block.reader import BackendBlock
    from tempo_tpu.ops import stage
    from tempo_tpu.ops.filter import required_columns
    from tempo_tpu.ops.stage import stage_block

    backend = MemBackend()
    m1 = build_block_from_traces(backend, TENANT, make_traces(20, seed=31))
    m2 = build_block_from_traces(backend, TENANT, make_traces(20, seed=32))
    blk1 = BackendBlock(backend, m1)
    blk2 = BackendBlock(backend, m2)
    cols = ["span.name_id", "trace.span_off", "span.trace_sid"]
    stage_block(blk1, cols)
    key1 = id(blk1)
    with stage._lru_lock:
        assert any(k[0] == key1 for k in stage._lru)  # entry accounted
    del blk1
    gc.collect()
    with stage._lru_lock:  # dead weakref still resident until a sweep
        dead = [k for k, e in stage._lru.items() if e[0]() is None]
    assert dead  # blk1's entry died with its arrays
    # the next insert sweeps the dead entry: accounted bytes must equal
    # the sum of LIVE entries' nbytes exactly, with no dead keys left
    stage_block(blk2, cols)
    with stage._lru_lock:
        assert stage._lru_bytes == sum(
            e[1] for e in stage._lru.values() if e[0]() is not None)
        assert all(e[0]() is not None for e in stage._lru.values())
    del blk2
    gc.collect()


# --------------------------------------------------- frontend multi wire


def test_frontend_poll_merges_same_key_jobs():
    """poll_job hands a remote worker ONE `multi` wire job for same-key
    queued jobs; complete_job demuxes the result list."""
    from tempo_tpu.db.search import SearchResponse, response_to_dict
    from tempo_tpu.services.frontend import Frontend, _Job
    from tempo_tpu.services.querier import Querier

    db = _mkdb()
    m = db.write_block(TENANT, make_traces(10, seed=41, n_spans=3))
    querier = Querier(db, ring=None, client_for=lambda a: None)
    fe = Frontend(querier, n_workers=0)
    try:
        jobs = []
        for i in range(3):
            j = _Job(kind="search_blocks",
                     payload={"req": {"limit": 5}, "block_ids": [m.block_id]},
                     fn=None, args=(),
                     batch_key=("search_blocks", TENANT, (m.block_id,)))
            jobs.append(j)
            fe.queue.enqueue(TENANT, j)
        wire = fe.poll_job(wait_s=1.0)
        assert wire is not None and wire["kind"] == "multi"
        assert wire["payload"]["kind"] == "search_blocks"
        assert len(wire["payload"]["jobs"]) == 3
        resp = SearchResponse()
        fe.complete_job(wire["id"], ok=True, result={
            "results": [response_to_dict(resp)] * 3})
        for j in jobs:
            assert j.done.is_set()
            assert j.error is None
            assert j.result is not None
    finally:
        fe.stop()


def test_frontend_multi_failure_fails_every_leased_job():
    """A worker posting ok=False (or a short results list) for a multi
    lease must fail/retry EVERY leased job -- a short list must never
    strand window-mates until the dispatch deadline."""
    from tempo_tpu.services.frontend import Frontend, _Job
    from tempo_tpu.services.querier import Querier

    db = _mkdb()
    m = db.write_block(TENANT, make_traces(10, seed=42, n_spans=3))
    querier = Querier(db, ring=None, client_for=lambda a: None)
    fe = Frontend(querier, n_workers=0)
    try:
        for bad_result in (None, {"results": []}):
            jobs = []
            for i in range(3):
                j = _Job(kind="search_blocks",
                         payload={"req": {"limit": 5}, "block_ids": [m.block_id]},
                         fn=None, args=(),
                         batch_key=("search_blocks", TENANT, (m.block_id,)))
                j.tries = 99  # exhaust retries: failure must surface now
                jobs.append(j)
                fe.queue.enqueue(TENANT, j)
            wire = fe.poll_job(wait_s=1.0)
            assert wire is not None and wire["kind"] == "multi"
            fe.complete_job(wire["id"], ok=bad_result is not None,
                            result=bad_result, error="worker exploded",
                            retryable=True)
            for j in jobs:
                assert j.done.is_set()  # not stranded
                assert j.error is not None
    finally:
        fe.stop()


def test_worker_executes_multi_wire_job():
    from tempo_tpu.db.search import request_to_dict
    from tempo_tpu.services.querier import Querier
    from tempo_tpu.services.worker import execute_job

    db = _mkdb()
    m = db.write_block(TENANT, make_traces(30, seed=43, n_spans=4))
    db.poll_now()
    querier = Querier(db, ring=None, client_for=lambda a: None)
    req = SearchRequest(query='{ name != "zzz" }', limit=5)
    payload = {"kind": "search_blocks",
               "tenants": [TENANT, TENANT],
               "jobs": [{"req": request_to_dict(req),
                         "block_ids": [m.block_id]}] * 2}
    out = execute_job(querier, TENANT, "multi", payload)
    assert len(out["results"]) == 2
    blk = db.open_block(m)
    expect = _dicts(search_block(blk, req))
    for r in out["results"]:
        assert r["traces"] == expect
