"""Storage-engine tests: WAL, blocklist/poller, search, compaction, facade."""

import os

import numpy as np
import pytest

from tempo_tpu.backend import MemBackend
from tempo_tpu.block import build_block_from_traces
from tempo_tpu.db import TempoDB, TempoDBConfig
from tempo_tpu.db import compactor as comp
from tempo_tpu.db.blocklist import Blocklist, Poller
from tempo_tpu.db.search import SearchRequest
from tempo_tpu.db.wal import WAL, WALBlock
from tempo_tpu.util.testdata import make_trace, make_traces
from tempo_tpu.wire import segment
from tempo_tpu.wire.combine import combine_traces

TENANT = "t1"


def _db(tmp_path, backend=None):
    cfg = TempoDBConfig(wal_path=str(tmp_path / "wal"))
    return TempoDB(cfg, backend=backend or MemBackend())


# ---------------------------------------------------------------- WAL


def test_wal_append_replay(tmp_path):
    wal = WAL(str(tmp_path))
    blk = wal.new_block(TENANT)
    traces = make_traces(5, seed=1)
    for tid, t in traces:
        seg = segment.segment_for_write(t, 100, 200)
        blk.append(tid, 100, 200, seg)
    blk.flush()

    replayed = wal.rescan_blocks()
    assert len(replayed) == 1
    rb = replayed[0]
    assert rb.tenant == TENANT and rb.clean
    assert [r.trace_id for r in rb.records] == [tid for tid, _ in traces]
    got = segment.segment_to_trace(rb.records[0].segment)
    assert got.span_count() == traces[0][1].span_count()


def test_wal_torn_tail(tmp_path):
    wal = WAL(str(tmp_path))
    blk = wal.new_block(TENANT)
    traces = make_traces(3, seed=2)
    for tid, t in traces:
        blk.append(tid, 1, 2, segment.segment_for_write(t, 1, 2))
    blk.close()
    # simulate crash mid-append: chop bytes off the tail
    with open(blk.path, "r+b") as f:
        f.truncate(os.path.getsize(blk.path) - 7)
    replayed = wal.rescan_blocks()
    assert not replayed[0].clean
    assert len(replayed[0].records) == 2  # last record dropped
    # file is truncated to a clean boundary: re-open and append works
    # (same format class the block was written with -- w2 by default)
    blk2 = type(blk)(str(tmp_path), TENANT, replayed[0].block_id)
    tid, t = make_traces(1, seed=9)[0]
    blk2.append(tid, 1, 2, segment.segment_for_write(t, 1, 2))
    blk2.flush()
    again = [rb for rb in wal.rescan_blocks() if rb.block_id == replayed[0].block_id]
    assert len(again[0].records) == 3 and again[0].clean


# ------------------------------------------------------- blocklist/poller


def test_poller_and_blocklist():
    backend = MemBackend()
    m1 = build_block_from_traces(backend, TENANT, make_traces(5, seed=3))
    m2 = build_block_from_traces(backend, "t2", make_traces(4, seed=4))
    poller = Poller(backend)
    metas, compacted = poller.poll()
    assert {m.block_id for m in metas[TENANT]} == {m1.block_id}
    assert {m.block_id for m in metas["t2"]} == {m2.block_id}

    bl = Blocklist()
    bl.apply_poll_results(metas, compacted)
    assert len(bl.metas(TENANT)) == 1

    # tenant index was written and round-trips without re-listing
    consumer = Poller(backend, build_index=False)
    metas2, _ = consumer.poll()
    assert {m.block_id for m in metas2[TENANT]} == {m1.block_id}

    # in-flight updates survive a poll (ApplyPollResults patching)
    m3 = build_block_from_traces(backend, TENANT, make_traces(3, seed=5))
    bl.update(TENANT, add=[m3])
    stale_metas = {TENANT: [m for m in metas[TENANT]]}  # poll without m3
    bl.apply_poll_results(stale_metas, {})
    assert {m.block_id for m in bl.metas(TENANT)} == {m1.block_id, m3.block_id}


# ------------------------------------------------------------- facade


def test_find_across_blocks(tmp_path):
    db = _db(tmp_path)
    all_traces = make_traces(40, seed=6, n_spans=6)
    db.write_block(TENANT, all_traces[:20])
    db.write_block(TENANT, all_traces[20:])
    for tid, original in all_traces[::7]:
        got = db.find_trace_by_id(TENANT, tid)
        assert got is not None
        assert got.span_count() == original.span_count()
    assert db.find_trace_by_id(TENANT, b"\x01" * 16) is None


def test_find_combines_partials(tmp_path):
    """Same trace id in two blocks (replicated flush) -> combined, deduped."""
    db = _db(tmp_path)
    tid = b"\x42" * 16
    t1 = make_trace(1, trace_id=tid, n_spans=4)
    t2 = make_trace(2, trace_id=tid, n_spans=5)
    filler1 = make_traces(3, seed=7)
    filler2 = make_traces(3, seed=8)
    db.write_block(TENANT, sorted(filler1 + [(tid, t1)], key=lambda p: p[0]))
    db.write_block(TENANT, sorted(filler2 + [(tid, t2)], key=lambda p: p[0]))
    got = db.find_trace_by_id(TENANT, tid)
    assert got.span_count() == 9


def test_search_end_to_end(tmp_path):
    db = _db(tmp_path)
    traces = make_traces(60, seed=10, n_spans=8)
    db.write_block(TENANT, traces)

    # tag search on a service that exists
    resp = db.search(TENANT, SearchRequest(tags={"service.name": "db"}, limit=100))
    # oracle: traces with any span whose resource service == "db"
    expect = {
        tid.hex()
        for tid, t in traces
        if any(res.service_name == "db" for res, _, _ in t.all_spans())
    }
    assert {r.trace_id for r in resp.traces} == expect

    # absent value prunes everything
    assert db.search(TENANT, SearchRequest(tags={"service.name": "nope"})).traces == []

    # min duration filters (trace-level, exact)
    resp2 = db.search(TENANT, SearchRequest(min_duration_ms=1, limit=1000))
    for r in resp2.traces:
        assert r.duration_ms >= 1

    # attribute search
    resp3 = db.search(TENANT, SearchRequest(tags={"http.method": "GET"}, limit=1000))
    expect3 = {
        tid.hex()
        for tid, t in traces
        if any(sp.attrs.get("http.method") == "GET" for _, _, sp in t.all_spans())
    }
    assert {r.trace_id for r in resp3.traces} == expect3

    # tag discovery
    tags = db.search_tags(TENANT)
    assert "http.method" in tags and "k8s.cluster.name" in tags
    vals = db.search_tag_values(TENANT, "http.method")
    assert set(vals) <= {"GET", "POST", "PUT", "DELETE"} and vals


def test_compaction_roundtrip(tmp_path):
    db = _db(tmp_path)
    db.cfg.compaction.min_input_blocks = 2
    all_traces = make_traces(30, seed=12, n_spans=5)
    db.write_block(TENANT, all_traces[:10])
    db.write_block(TENANT, all_traces[10:20])
    db.write_block(TENANT, all_traces[20:])
    assert len(db.blocklist.metas(TENANT)) == 3

    results = db.compact_once(TENANT)
    assert results and sum(len(r.new_blocks) for r in results) >= 1
    metas = db.blocklist.metas(TENANT)
    assert all(m.compaction_level >= 1 for m in metas)
    # every trace still findable, spans preserved
    for tid, original in all_traces[::5]:
        got = db.find_trace_by_id(TENANT, tid)
        assert got is not None
        assert got.span_count() == original.span_count()

    # compacted originals are marked in the backend
    _, compacted = db.poller.poll()
    assert len(compacted[TENANT]) == 3


def test_compaction_dedupes_across_blocks(tmp_path):
    db = _db(tmp_path)
    tid = b"\x99" * 16
    shared = make_trace(5, trace_id=tid, n_spans=6)
    import copy

    db.write_block(TENANT, sorted(make_traces(4, seed=13) + [(tid, shared)], key=lambda p: p[0]))
    db.write_block(TENANT, sorted(make_traces(4, seed=14) + [(tid, copy.deepcopy(shared))], key=lambda p: p[0]))
    db.compact_once(TENANT)
    got = db.find_trace_by_id(TENANT, tid)
    assert got.span_count() == 6  # replicas deduped, not doubled


def test_retention(tmp_path):
    db = _db(tmp_path)
    db.cfg.compaction.retention_s = 10  # everything is ancient vs 2023 test data
    db.write_block(TENANT, make_traces(5, seed=15))
    res = db.retention_once(TENANT)
    assert len(res.marked) == 1
    assert db.blocklist.metas(TENANT) == []
    db.poll_now()
    assert db.blocklist.metas(TENANT) == []


def test_select_jobs_windows():
    cfg = comp.CompactorConfig()
    now = 1_700_000_000.0
    metas = []
    for i in range(4):
        m = build_block_from_traces(MemBackend(), TENANT, make_traces(2, seed=i))
        m.size_bytes = 100
        metas.append(m)
    jobs = comp.select_jobs(TENANT, metas, cfg, now=1_700_100_000.0)
    assert jobs and all(len(j.blocks) >= 2 for j in jobs)
    assert jobs[0].hash.startswith(f"{TENANT}-0-")


def test_streamed_search_matches_unstreamed(tmp_path):
    """A many-row-group block takes the streaming path and returns the
    same results as the single-stage path."""
    from tempo_tpu.backend import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest, search_block
    from tempo_tpu.util.testdata import make_traces

    db = TempoDB(
        TempoDBConfig(wal_path=str(tmp_path / "w"), row_group_spans=32),
        backend=MemBackend(),
    )
    traces = make_traces(120, seed=13, n_spans=6)  # 720 spans -> ~23 groups
    meta = db.write_block("t", traces)
    assert len(meta.row_groups) > 8  # streaming threshold crossed

    blk = db.open_block(meta)
    req = SearchRequest(query='{ resource.service.name = "db" }', limit=1000)
    resp = search_block(blk, req)
    expect = {
        tid.hex() for tid, t in traces
        if any(r.service_name == "db" for r, _, _ in t.all_spans())
    }
    assert {r.trace_id for r in resp.traces} == expect
    assert resp.inspected_spans == 720
    # sharded path (explicit group range) still agrees on its shard
    half = search_block(blk, req, groups_range=list(range(0, len(meta.row_groups) // 2)))
    assert {r.trace_id for r in half.traces} <= expect
    db.close()


def test_streamed_search_cross_chunk_and(tmp_path):
    """AND of two tracify legs whose matching spans land in DIFFERENT
    chunks must still match the trace (per-leaf cross-chunk combine)."""
    from tempo_tpu.backend import MemBackend
    from tempo_tpu.db import TempoDB, TempoDBConfig
    from tempo_tpu.db.search import SearchRequest, search_block
    from tempo_tpu.wire.model import Resource, ResourceSpans, Scope, ScopeSpans, Span, Trace

    base = 1_700_000_000_000_000_000
    # one giant trace whose "a" span is at the start and "b" span at the
    # end, padded with enough filler spans to span many row groups
    tid = bytes([7]) * 16
    spans = [Span(trace_id=tid, span_id=(1).to_bytes(8, "big"), name="start",
                  attrs={"a": "v"}, start_unix_nano=base, end_unix_nano=base + 10)]
    for i in range(300):
        spans.append(Span(trace_id=tid, span_id=(i + 2).to_bytes(8, "big"),
                          name="filler", start_unix_nano=base, end_unix_nano=base + 10))
    spans.append(Span(trace_id=tid, span_id=(999).to_bytes(8, "big"), name="end",
                      attrs={"b": "v"}, start_unix_nano=base, end_unix_nano=base + 10))
    tr = Trace(resource_spans=[ResourceSpans(
        resource=Resource(attrs={"service.name": "s"}),
        scope_spans=[ScopeSpans(scope=Scope(), spans=spans)])])
    # second trace with only "a" (must NOT match)
    tid2 = bytes([8]) * 16
    tr2 = Trace(resource_spans=[ResourceSpans(
        resource=Resource(attrs={"service.name": "s"}),
        scope_spans=[ScopeSpans(scope=Scope(), spans=[
            Span(trace_id=tid2, span_id=(1).to_bytes(8, "big"), name="x",
                 attrs={"a": "v"}, start_unix_nano=base, end_unix_nano=base + 10)])])])

    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w"), row_group_spans=16),
                 backend=MemBackend())
    meta = db.write_block("t", [(tid, tr), (tid2, tr2)])
    assert len(meta.row_groups) > 8  # streaming engages
    blk = db.open_block(meta)
    # tag search: per-tag tracify groups ANDed at trace level
    resp = search_block(blk, SearchRequest(tags={"a": "v", "b": "v"}, limit=10))
    assert {r.trace_id for r in resp.traces} == {tid.hex()}
    db.close()


def test_device_paths_run_mesh_programs(tmp_path):
    """The service-layer Find and search run the sharded mesh programs
    (the same kernels the driver dryrun validates) and match the host
    fallback path exactly."""
    from tempo_tpu.parallel import find as pf
    from tempo_tpu.parallel import search as ps

    db = _db(tmp_path)
    for seed in (21, 22, 23):
        db.write_block(TENANT, make_traces(8, seed=seed))
    assert db.mesh.devices.size == 8  # conftest forces the virtual mesh

    fi = pf.make_sharded_find_rows.cache_info()
    f_before = fi.hits + fi.misses
    si = ps.make_sharded_search.cache_info()
    s_before = si.hits + si.misses

    tid, t = make_traces(8, seed=22)[3]
    got = db.find_trace_by_id(TENANT, tid)
    assert got is not None and got.span_count() == t.span_count()
    fi = pf.make_sharded_find_rows.cache_info()
    assert fi.hits + fi.misses > f_before, "find did not run the mesh program"

    req = SearchRequest(tags={"service.name": "auth"}, limit=100)
    resp = db.search(TENANT, req)
    si = ps.make_sharded_search.cache_info()
    assert si.hits + si.misses > s_before, "search did not run the mesh program"

    db.cfg.device_find = False
    db.cfg.device_search = False
    resp_host = db.search(TENANT, req)
    assert sorted(r.trace_id for r in resp.traces) == sorted(
        r.trace_id for r in resp_host.traces
    )
    got_host = db.find_trace_by_id(TENANT, tid)
    assert got_host.span_count() == got.span_count()


def test_device_search_generic_attr_on_mesh(tmp_path):
    """Arbitrary {span.foo = "bar"} / mixed generic-attr queries run the
    stacked MESH program (attr rows sharded over sp) and match the host
    path -- previously the generic-attr tables forced the per-block
    fallback."""
    from tempo_tpu.parallel import search as ps

    db = _db(tmp_path)
    for seed in (31, 32, 33):
        db.write_block(TENANT, make_traces(10, seed=seed))

    for q in (
        '{ span.component = "grpc" }',          # sattr str eq
        '{ .component =~ "gr.*" }',             # EITHER scope + regex table
        '{ span.latency.weight > 0.25 }',       # float attr (needs_verify)
        '{ span.component != nil && duration > 1ms }',  # exists + span col
    ):
        si = ps.make_sharded_search.cache_info()
        before = si.hits + si.misses
        req = SearchRequest(query=q, limit=100)
        resp = db.search(TENANT, req)
        si = ps.make_sharded_search.cache_info()
        assert si.hits + si.misses > before, f"{q} did not run the mesh program"
        assert resp.traces, q
        db.cfg.device_search = False
        resp_host = db.search(TENANT, req)
        db.cfg.device_search = True
        assert sorted(r.trace_id for r in resp.traces) == sorted(
            r.trace_id for r in resp_host.traces
        ), q


def test_device_find_combines_partials(tmp_path):
    """Device Find returns per-block hit rows so replicated partial
    traces still combine (not a single elected winner)."""
    db = _db(tmp_path)
    tid = b"\x43" * 16
    t1 = make_trace(41, trace_id=tid, n_spans=4)
    t2 = make_trace(42, trace_id=tid, n_spans=5)
    db.write_block(TENANT, sorted(make_traces(3, seed=43) + [(tid, t1)], key=lambda p: p[0]))
    db.write_block(TENANT, sorted(make_traces(3, seed=44) + [(tid, t2)], key=lambda p: p[0]))
    assert db.cfg.device_find
    got = db.find_trace_by_id(TENANT, tid)
    assert got.span_count() == 9


def test_compaction_unions_blooms_on_device(tmp_path, monkeypatch):
    """Compaction must produce the output bloom via the device OR-union
    when input geometries match -- never by re-inserting ids."""
    from tempo_tpu.block.bloom import ShardedBloom

    db = _db(tmp_path)
    db.cfg.compaction.concat_small_input_bytes = 0  # force the real merge
    a = make_traces(8, seed=31)
    b = make_traces(8, seed=32)
    db.write_block(TENANT, a)
    db.write_block(TENANT, b)
    m1, m2 = db.blocklist.metas(TENANT)
    assert (m1.bloom_shards, m1.bloom_shard_bits) == (m2.bloom_shards, m2.bloom_shard_bits)

    def no_rebuild(self, ids):
        raise AssertionError("bloom rebuilt key-by-key; union path not taken")

    monkeypatch.setattr(ShardedBloom, "add_many", no_rebuild)
    results = db.compact_once(TENANT)
    assert results and results[0].new_blocks
    (out,) = db.blocklist.metas(TENANT)
    assert (out.bloom_shards, out.bloom_shard_bits) == (m1.bloom_shards, m1.bloom_shard_bits)
    blk = db.open_block(out)
    for tid, _ in a + b:
        assert blk.bloom_test(tid)


def _canon_trace(t):
    """Canonical comparable form of a wire trace: every span with its
    resource/scope context, attrs, events, links -- order-independent."""
    out = []
    for res, scope, sp in t.all_spans():
        out.append((
            sp.span_id, sp.name, sp.kind, sp.start_unix_nano, sp.end_unix_nano,
            sp.status_code, sp.parent_span_id, tuple(sorted(sp.attrs.items())),
            tuple(sorted(res.attrs.items())), (scope.name, scope.version),
            tuple((e.name, e.time_unix_nano, tuple(sorted(e.attrs.items()))) for e in sp.events),
            tuple((ln.trace_id, ln.span_id, tuple(sorted(ln.attrs.items()))) for ln in sp.links),
        ))
    return sorted(out)


def test_columnar_compaction_golden_vs_wire(tmp_path):
    """The columnar fast path and the wire-model merge produce
    byte-equivalent traces (golden equality), including a collision."""
    tid = b"\x77" * 16
    shared1 = make_trace(51, trace_id=tid, n_spans=4)
    shared2 = make_trace(52, trace_id=tid, n_spans=5)
    inputs = [
        sorted(make_traces(12, seed=53, n_spans=6) + [(tid, shared1)], key=lambda p: p[0]),
        sorted(make_traces(12, seed=54, n_spans=6) + [(tid, shared2)], key=lambda p: p[0]),
        make_traces(12, seed=55, n_spans=6),
    ]
    dbs = {}
    for mode in ("columnar", "wire"):
        db = _db(tmp_path / mode)
        db.cfg.compaction.columnar = mode == "columnar"
        for batch in inputs:
            db.write_block(TENANT, batch)
        res = db.compact_once(TENANT)
        assert res and res[0].new_blocks
        dbs[mode] = db

    all_ids = sorted({tid} | {t for batch in inputs for t, _ in batch})
    for t in all_ids:
        a = dbs["columnar"].find_trace_by_id(TENANT, t)
        b = dbs["wire"].find_trace_by_id(TENANT, t)
        assert a is not None and b is not None, t.hex()
        assert _canon_trace(a) == _canon_trace(b), t.hex()
    # search parity too
    req = SearchRequest(tags={"service.name": "auth"}, limit=1000)
    ra = dbs["columnar"].search(TENANT, req)
    rb = dbs["wire"].search(TENANT, req)
    assert sorted(r.trace_id for r in ra.traces) == sorted(r.trace_id for r in rb.traces)


def test_columnar_compaction_size_cuts(tmp_path):
    """A small target_block_bytes cuts compaction output into multiple
    id-disjoint blocks, all traces intact."""
    db = _db(tmp_path)
    db.cfg.compaction.target_block_bytes = 1  # force per-trace-ish cuts
    all_traces = make_traces(24, seed=61, n_spans=5)
    db.write_block(TENANT, all_traces[:12])
    db.write_block(TENANT, all_traces[12:])
    res = db.compact_once(TENANT)
    assert res
    outs = res[0].new_blocks
    assert len(outs) > 1, "size target did not cut the output"
    # id ranges are disjoint and ordered (merge emits sorted runs)
    ranges = sorted((m.min_id, m.max_id) for m in outs)
    for (lo1, hi1), (lo2, hi2) in zip(ranges, ranges[1:]):
        assert hi1 < lo2
    for t, original in all_traces:
        got = db.find_trace_by_id(TENANT, t)
        assert got is not None and got.span_count() == original.span_count()


def test_block_codec_config(tmp_path):
    """TempoDBConfig.block_codec writes ingest blocks with the chosen
    chunk codec; find/search read them back transparently."""
    from tempo_tpu.backend.mem import MemBackend
    from tempo_tpu.db.search import SearchRequest
    from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
    from tempo_tpu.util.testdata import make_traces

    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w"), block_codec="gzip"),
                 backend=MemBackend())
    traces = make_traces(10, seed=6, n_spans=3)
    meta = db.write_block("t", sorted(traces, key=lambda t: t[0]))
    blk = db.open_block(meta)
    codecs = {rec[3] for col in blk.pack._cols.values() for rec in col["chunks"]
              if rec[2] >= 128}
    assert "gzip" in codecs and "zstd" not in codecs
    tid, tr = traces[2]
    got = db.find_trace_by_id("t", tid)
    assert got is not None and got.span_count() == tr.span_count()
    assert db.search("t", SearchRequest(limit=50)).traces
    db.close()


def test_cli_block_ops(tmp_path, capsys):
    """gen-bloom / dump-columns / rewrite-block (tempo-cli's bloom
    regen, column dump and convert roles)."""
    import glob
    import os

    from tempo_tpu.cli.__main__ import main as cli

    store = str(tmp_path / "store")
    cli(["--backend.path", store, "gen", "t1", "--traces", "20", "--spans", "3"])
    bid = capsys.readouterr().out.split()[2].rstrip(":")

    cli(["--backend.path", store, "dump-columns", "t1", bid])
    out = capsys.readouterr().out
    assert "span.trace_sid" in out and "TOTAL" in out and "zstd" in out

    # nuke the bloom; regen restores find
    for f in glob.glob(os.path.join(store, "t1", bid, "bloom-*")):
        os.remove(f)
    cli(["--backend.path", store, "gen-bloom", "t1", bid])
    assert "regenerated bloom" in capsys.readouterr().out
    from tempo_tpu.backend.local import LocalBackend
    from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig

    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w")),
                 backend=LocalBackend(store))
    db.poll_now()
    blk = db.open_block(db.blocklist.metas("t1")[0])
    tid = blk.trace_index["trace.id"][3].tobytes()
    before = db.find_trace_by_id("t1", tid)
    assert before is not None

    cli(["--backend.path", store, "rewrite-block", "t1", bid, "--codec", "gzip"])
    assert "rewrote" in capsys.readouterr().out
    db2 = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w2")),
                  backend=LocalBackend(store))
    db2.poll_now()
    metas = db2.blocklist.metas("t1")
    # the freshly-compacted original stays listed for the swap-window
    # grace (blocklist.COMPACTED_GRACE_S); exactly one LIVE replacement
    live = [m for m in metas if not m.compacted_at_unix]
    assert len(live) == 1 and live[0].block_id != bid
    assert all(m.block_id == bid for m in metas if m.compacted_at_unix)
    got = db2.find_trace_by_id("t1", tid)
    assert got is not None and got.span_count() == before.span_count()
    # attributes survive the lossless conversion
    def attr_sets(t):
        return sorted((sp.name, tuple(sorted(sp.attrs.items())))
                      for _, _, sp in t.all_spans())
    assert attr_sets(got) == attr_sets(before)
    db.close()
    db2.close()


def test_tres_membership_axis(tmp_path):
    """The tres axis (builder.build_tres) is consistent with the span
    axis, drives the res-only host fast path to identical results, and
    survives compaction with remapped res indices."""
    from tempo_tpu.block.builder import build_tres
    from tempo_tpu.db.route import host_plan
    from tempo_tpu.db.search import SearchRequest, _plan_for_block, search_block

    db = _db(tmp_path)
    db.cfg.compaction.min_input_blocks = 2
    all_traces = make_traces(40, seed=21, n_spans=6)
    db.write_block(TENANT, all_traces[:20])
    db.write_block(TENANT, all_traces[20:])
    metas = db.blocklist.metas(TENANT)
    blk = db.open_block(metas[0])

    # tres columns match a recompute from the span axis
    sid = blk.pack.read("span.trace_sid")
    ri = blk.pack.read("span.res_idx")
    want = build_tres(sid, ri, blk.meta.total_traces)
    for n in ("tres.res", "tres.nspans", "trace.tres_off"):
        np.testing.assert_array_equal(blk.pack.read(n), want[n])

    # res-only queries take the tres plan and agree with a span-axis run
    svc = None
    d = blk.dictionary
    for code in blk.pack.read("res.service_id"):
        if code >= 0:
            svc = d.string(int(code))
            break
    assert svc is not None
    req = SearchRequest(tags={"service.name": svc}, limit=100)
    p = _plan_for_block(blk, req)
    host_needed, tres_mode = host_plan(blk, p, None)
    assert tres_mode and "tres.res" in host_needed
    got = search_block(blk, req, mode="host")

    class _NoTresPack:
        def __init__(self, pack):
            self._p = pack
        def has(self, name):
            return False if name.startswith("tres.") else self._p.has(name)
        def __getattr__(self, a):
            return getattr(self._p, a)

    blk2 = db.open_block(metas[0])
    blk2.__dict__["pack"] = _NoTresPack(blk2.pack)  # cached_property slot
    base = search_block(blk2, req, mode="host")
    assert {(t.trace_id, t.matched_spans) for t in got.traces} == \
           {(t.trace_id, t.matched_spans) for t in base.traces}
    assert len(got.traces) > 0

    # compaction: merged tres equals a recompute from merged span columns
    db.compact_once(TENANT)
    db.poll_now()
    cmeta = [m for m in db.blocklist.metas(TENANT) if m.compaction_level >= 1]
    assert cmeta
    cblk = db.open_block(cmeta[0])
    want2 = build_tres(cblk.pack.read("span.trace_sid"),
                       cblk.pack.read("span.res_idx"), cblk.meta.total_traces)
    for n in ("tres.res", "tres.nspans", "trace.tres_off"):
        np.testing.assert_array_equal(cblk.pack.read(n), want2[n])


def test_grace_listed_blocks_not_reprocessed(tmp_path):
    """Freshly-compacted blocks stay searchable for the grace window but
    must NOT be re-selected as compaction inputs or re-marked by
    retention (their data already lives in an output block)."""
    import time as _time

    backend = MemBackend()
    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w1")), backend=backend)
    db.cfg.compaction.min_input_blocks = 2
    all_traces = make_traces(20, seed=51, n_spans=4)
    db.write_block(TENANT, all_traces[:10])
    db.write_block(TENANT, all_traces[10:])
    db.compact_once(TENANT)
    # a DIFFERENT process's poller (fresh db) sees the graced inputs --
    # the compacting process removes them locally and immediately
    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w2")), backend=backend)
    db.poll_now()
    metas = db.blocklist.metas(TENANT)
    graced = [m for m in metas if m.compacted_at_unix]
    assert graced, "grace window should keep the inputs listed"

    # compaction sweep: graced blocks are never inputs again
    jobs = comp.select_jobs(TENANT, metas, db.cfg.compaction)
    for j in jobs:
        assert not any(m.compacted_at_unix for m in j.blocks)

    # retention sweep over grace-listed metas must not crash or re-mark
    db.cfg.compaction.retention_s = 0  # everything "expired"
    res = db.retention_once(TENANT)
    assert all(m.block_id not in res.marked for m in graced)

    # idempotent mark: double-marking is a no-op, not DoesNotExist
    db.backend.mark_compacted(TENANT, graced[0].block_id)


def test_concat_compound_compaction(tmp_path):
    """Level-0 small blocks concat into a compound block (no-decode
    verbatim copies); the poller expands it into part blocks that serve
    find + search unchanged; the next level's columnar rewrite merges
    the parts for real; a fully-consumed compound ages out whole."""
    backend = MemBackend()
    db = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w1")), backend=backend)
    db.cfg.compaction.min_input_blocks = 2
    db.cfg.compaction.max_input_blocks = 16
    all_traces = make_traces(40, seed=61, n_spans=5)
    for i in range(8):
        db.write_block(TENANT, all_traces[i * 5:(i + 1) * 5])
    db.poll_now()

    res = db.compact_once(TENANT)
    assert res and all("/" in m.block_id for r in res for m in r.new_blocks), \
        "small level-0 inputs must take the concat path (parts have cid/pN ids)"
    assert sum(r.traces_out for r in res) == 40

    # a fresh process's poll expands the compound into parts
    db2 = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w2")), backend=backend)
    db2.poll_now()
    parts = [m for m in db2.blocklist.metas(TENANT) if "/" in m.block_id]
    assert len(parts) == 8 and all(m.compaction_level == 1 for m in parts)

    for tid, original in all_traces[::7]:
        got = db2.find_trace_by_id(TENANT, tid)
        assert got is not None and got.span_count() == original.span_count()
    resp = db2.search(TENANT, SearchRequest(limit=100))
    assert len(resp.traces) == 40

    # the next level merges parts with the real columnar rewrite
    res2 = db2.compact_once(TENANT)
    merged = [m for r in res2 for m in r.new_blocks]
    assert merged and all("/" not in m.block_id for m in merged)
    # freshly-consumed parts keep their searchable grace: the compound
    # does NOT collapse to a whole yet
    db3 = TempoDB(TempoDBConfig(wal_path=str(tmp_path / "w3")), backend=backend)
    db3.poll_now()
    assert not [m for m in db3.blocklist.compacted_metas(TENANT)
                if m.version == "vtpu1c"]
    import tempo_tpu.db.blocklist as BL

    _g = BL.COMPACTED_GRACE_S
    BL.COMPACTED_GRACE_S = 0.0  # grace lapsed: whole-collapse kicks in
    db3.poll_now()
    assert len(db3.search(TENANT, SearchRequest(limit=100)).traces) == 40
    for tid, original in all_traces[::11]:
        assert db3.find_trace_by_id(TENANT, tid) is not None

    # every part consumed -> the compound lists as ONE compacted whole
    wholes = [m for m in db3.blocklist.compacted_metas(TENANT)
              if m.version == "vtpu1c"]
    assert wholes, "fully-consumed compound should age out as a whole"

    # retention deletes whole compounds (never individual parts)
    try:
        db3.cfg.compaction.compacted_retention_s = 0
        res3 = db3.retention_once(TENANT)
        assert wholes[0].block_id in res3.deleted
        assert not any("/" in b for b in res3.deleted)
        # the bytes are truly gone (recursive delete incl. parts)
        assert not any(bid.startswith(wholes[0].block_id)
                       for bid in backend.blocks(TENANT))
    finally:
        BL.COMPACTED_GRACE_S = _g


def _old_layout_block(backend, traces):
    """Write a round-3-layout block: today's builder output minus the
    columns that joined in round 4 (tres axis, span.parent_idx). The
    single definition both compat tests share."""
    from tempo_tpu.block.builder import BlockBuilder, write_block

    b = BlockBuilder(TENANT)
    for tid, t in sorted(traces, key=lambda p: p[0]):
        b.add_trace(tid, t)
    fin = b.finalize()
    for name in list(fin.cols):
        if name.startswith("tres.") or name in ("trace.tres_off", "span.parent_idx"):
            del fin.cols[name]
    return write_block(backend, fin)


def test_pre_upgrade_block_compat(tmp_path):
    """A physically OLD-format block (no tres axis, no span.parent_idx --
    the round-3 layout) must keep working end to end: find by id, tag
    search, structural-TraceQL planning without the parent column, and
    compaction MIXED with a current-format block (differing column sets
    force the columnar merge's UnsupportedColumnar fallback to the
    wire-level merge)."""
    from tempo_tpu.backend import MemBackend
    from tempo_tpu.db.compactor import CompactionJob, CompactorConfig, compact
    from tempo_tpu.db.search import search_block

    backend = MemBackend()
    old_traces = make_traces(20, seed=21, n_spans=4)
    old_meta = _old_layout_block(backend, old_traces)

    new_traces = make_traces(20, seed=22, n_spans=4)
    new_meta = build_block_from_traces(backend, TENANT, new_traces)

    db = _db(tmp_path, backend)
    db.poll_now()

    # the old block reads fine: find every id, search without tres/struct
    blk = db.open_block(old_meta)
    assert not blk.pack.has("tres.res") and not blk.pack.has("span.parent_idx")
    for tid, t in old_traces:
        got = db.find_trace_by_id(TENANT, tid)
        assert got is not None and got.span_count() == t.span_count()
    svc = next(iter(old_traces[0][1].resource_spans[0].resource.attrs.values()))
    r = search_block(blk, SearchRequest(tags={"service.name": str(svc)}, limit=100),
                     mode="host")
    assert r.inspected_spans == blk.meta.total_spans
    assert any(hit.trace_id == old_traces[0][0].hex() for hit in r.traces)
    # structural TraceQL must plan WITHOUT the parent column (host path);
    # testdata traces have server->client edges, so hits are guaranteed
    r2 = search_block(
        blk, SearchRequest(query='{ kind = server } > { kind = client }', limit=10),
        mode="host")
    assert r2.inspected_spans == blk.meta.total_spans

    # mixed-format compaction: columnar merge refuses (differing column
    # sets) and the wire fallback produces one complete modern block.
    # concat is disabled so the small level-0 inputs don't take the
    # compound-block shortcut (which legitimately keeps old layouts).
    res = compact(backend, CompactionJob(TENANT, [old_meta, new_meta]),
                  CompactorConfig(concat_small_input_bytes=0))
    assert res.traces_out == 40
    db.poll_now()
    merged = [m for m in db.blocklist.metas(TENANT) if m.compaction_level >= 1]
    assert len(merged) >= 1
    mblk = db.open_block(merged[0])
    assert mblk.pack.has("tres.res") and mblk.pack.has("span.parent_idx")
    for tid, t in old_traces + new_traces:
        got = db.find_trace_by_id(TENANT, tid)
        assert got is not None and got.span_count() == t.span_count()


def test_compound_block_mixed_layout_compat(tmp_path):
    """The no-decode CONCAT compaction path applied to a rolling-upgrade
    mix (one old-layout sub-block without tres/parent_idx, one current)
    must yield a compound block that still answers find and search."""
    from tempo_tpu.backend import MemBackend
    from tempo_tpu.db.compactor import CompactionJob, CompactorConfig, compact

    backend = MemBackend()
    old_traces = make_traces(15, seed=31, n_spans=4)
    old_meta = _old_layout_block(backend, old_traces)
    new_traces = make_traces(15, seed=32, n_spans=4)
    new_meta = build_block_from_traces(backend, TENANT, new_traces)

    res = compact(backend, CompactionJob(TENANT, [old_meta, new_meta]),
                  CompactorConfig())  # small level-0 inputs -> concat path
    assert res.traces_out == 30

    db = _db(tmp_path, backend)
    db.poll_now()
    merged = [m for m in db.blocklist.metas(TENANT) if m.compaction_level >= 1]
    assert merged
    for tid, t in old_traces + new_traces:
        got = db.find_trace_by_id(TENANT, tid)
        assert got is not None and got.span_count() == t.span_count()
    svc = old_traces[0][1].resource_spans[0].resource.attrs["service.name"]
    r = db.search(TENANT, SearchRequest(tags={"service.name": str(svc)}, limit=100))
    assert r.inspected_spans >= 30 * 4
    # the OLD-layout sub-block's matching trace must be among the hits
    assert any(hit.trace_id == old_traces[0][0].hex() for hit in r.traces)
