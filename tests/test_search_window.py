"""The request's time window: who settles it, and that every engine does.

The device compares `trace.start_ms` (block-relative milliseconds) against
bounds widened by 1 ms, so a windowed plan over-matches at the edges. That
is NOT a reason to host-verify (PlannedQuery.needs_verify is about the
query's own conditions, and hosteval never evaluates the window):
db/search._candidates re-checks the window exactly on `trace.start_ns`, and
every path from selected sids to results goes through it. These tests hold
each engine to that: exact edges at second granularity, escalation when the
edge rows fall out, zero materialised traces for exact plans, hosteval still
run for lossy ones, the routing counter, and a structural guard for engines
yet to be written.
"""

from __future__ import annotations

import ast
import pathlib
import tempfile

import pytest

from tempo_tpu.backend.mem import MemBackend
from tempo_tpu.block.reader import BackendBlock
from tempo_tpu.db import route as route_mod
from tempo_tpu.db import search as search_mod
from tempo_tpu.db.search import (
    SearchRequest,
    SearchResponse,
    _plan_for_block,
    search_block,
    search_blocks_device,
    search_blocks_fused,
)
from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig
from tempo_tpu.traceql.hosteval import trace_matches
from tempo_tpu.traceql.parser import parse
from tempo_tpu.util.kerneltel import TEL
from tempo_tpu.util.testdata import make_traces, restart_trace

NS = 10**9
BASE_S = 1_700_000_000
START, END = BASE_S + 2, BASE_S + 4  # the window, unix seconds
ONE, TWO = "win-one", "win-two"  # tenants: the corpus as one block / as two


def _starts() -> list[int]:
    """Trace start times (ns) around [START, END]. The block's base is not
    millisecond-aligned (…+123,457 ns), so `trace.start_ms` and the plan's
    bounds floor differently: the reason the device bounds carry +-1 ms."""
    lo, hi = START * NS, END * NS
    out = [BASE_S * NS + 123_457, (BASE_S + 1) * NS + 400_000_123,
           (BASE_S + 5) * NS + 7, (BASE_S + 6) * NS + 999]  # far outside
    out += [lo - d for d in (1, 499_999, 999_999, 1_000_000)]  # < 1 ms early
    out += [lo, hi]  # exactly on the edges: inside
    out += [lo + 1, lo + 999_999, hi - 1, hi - 1_000_001]
    out += [lo + i * 37_000_003 + 17 for i in range(1, 40)]  # inside, sub-ms offsets
    # <= 2 ms late: the newest rows the device lets through, more of them
    # than the first escalation k (32) even in half a block
    out += [hi + 1 + i * 9_989 for i in range(100)]
    out += [hi + 1_500_000, hi + 1_999_999]
    assert len(set(out)) == len(out)
    return out


def _in_window(start_ns: int) -> bool:
    return START * NS <= start_ns <= END * NS


@pytest.fixture(scope="module")
def world():
    starts = _starts()
    traces = [(tid, restart_trace(t, s)) for (tid, t), s in
              zip(make_traces(len(starts), seed=27, n_spans=4), starts)]
    for i, (_, t) in enumerate(traces):
        # a generic int attribute past int32: the device column clamps it
        sp = next(t.all_spans())[2]
        sp.attrs["bytes.sent"] = (5_000_000_000, 3_000_000_000, 7)[i % 3]
    db = TempoDB(
        TempoDBConfig(wal_path=tempfile.mkdtemp(prefix="tempo-win-wal"),
                      row_group_spans=32, device_promote_touches=1,
                      batch_window_ms=50.0),
        backend=MemBackend())
    db.write_block(ONE, traces)
    db.write_block(TWO, traces[0::2])
    db.write_block(TWO, traces[1::2])
    start_of = {tid.hex(): t.time_range_nanos()[0] for tid, t in traces}
    assert sorted(start_of.values()) == sorted(starts)
    yield db, traces, start_of
    db.close()


def _blocks(db, tenant):
    return [db.open_block(m) for m in db.blocklist.metas(tenant)]


def _merged(resps, limit):
    out = SearchResponse()
    for r in resps:
        out.merge(r, 10**9)
    out.traces.sort(key=lambda t: -t.start_time_unix_nano)
    out.traces = out.traces[:limit]
    return out


def _per_block(db, tenant, req, **kw):
    return _merged([search_block(b, req, **kw) for b in _blocks(db, tenant)],
                   req.limit)


def _sharded(db, tenant, req, mode="auto"):
    resps = []
    for b in _blocks(db, tenant):
        g = list(range(len(b.meta.row_groups)))
        assert len(g) >= 4
        resps += [search_block(b, req, groups_range=half, mode=mode)
                  for half in (g[: len(g) // 2], g[len(g) // 2:])]
    return _merged(resps, req.limit)


def _fused(rtt_ms):
    def run(db, tenant, req, monkeypatch):
        # the router weighs a host scan against one link round trip: a
        # huge estimate keeps every block on the host engine, a negative
        # one sends every block to the device (the db promotes at 1 touch)
        monkeypatch.setattr(route_mod, "link_rtt_ms", lambda: rtt_ms)
        got = search_blocks_fused(_blocks(db, tenant), req)
        assert got is not None
        return got
    return run


def _batchexec(db, tenant, req, monkeypatch=None):
    """Two same-shape requests from one caller: one fused multi-query
    launch (db/batchexec); an ineligible plan falls back as TempoDB does."""
    from dataclasses import replace

    from tempo_tpu.db.batchexec import batched_search_block_many

    resps = []
    for b in _blocks(db, tenant):
        mate = replace(req, limit=(req.limit or 20) + 1)
        got = batched_search_block_many(
            db.batchers.search, [(b, req, None), (b, mate, None)])[0]
        if isinstance(got, Exception):
            raise got
        resps.append(got if got is not None else search_block(b, req))
    return _merged(resps, req.limit)


def _mesh(db, tenant, req, monkeypatch=None):
    got = search_blocks_device(_blocks(db, tenant), req, db.mesh)
    assert got is not None
    return got


def _streamed(db, tenant, req, monkeypatch):
    monkeypatch.setattr(route_mod, "JOB_STAGE_BUDGET_BYTES", 0)
    return _per_block(db, tenant, req, mode="device")


# every way db/ turns a selection into results; a new engine is added here
ENGINES = {
    "host": lambda db, t, req, mp: _per_block(db, t, req, mode="host"),
    "device": lambda db, t, req, mp: _per_block(db, t, req, mode="device"),
    "streamed": _streamed,
    "sharded-host": lambda db, t, req, mp: _sharded(db, t, req, "host"),
    "sharded-device": lambda db, t, req, mp: _sharded(db, t, req, "device"),
    "fused-host": _fused(1e9),
    "fused-device": _fused(-1.0),
    "batchexec": _batchexec,
    "mesh": _mesh,
    "tempodb": lambda db, t, req, mp: db.search(t, req),
}
# engines whose top-k key is the block's millisecond column: with distinct
# milliseconds inside the window they return THE newest `limit`; the fused
# and mesh selections order by whole seconds and return any `limit` of the
# newest second (as they do without a window)
_MS_KEYED = ("host", "device", "streamed", "batchexec")

EXACT_QUERIES = ['{ duration > 1us }', '{ name = "GET /api" }',
                 '{ span.component = "grpc" }', '{ span.http.method = "GET" }']


def _rows(resp):
    return [(t.trace_id, t.start_time_unix_nano, t.matched_spans) for t in resp.traces]


def _oracle(traces, start_of, query="", tags=None, windowed=True):
    q = parse(query) if query else None
    want = set()
    for tid, t in traces:
        if windowed and not _in_window(start_of[tid.hex()]):
            continue
        if q is not None and not trace_matches(q, t):
            continue
        if tags and not all(
                any(str(sp.attrs.get(k)) == v or rs_res.attrs.get(k) == v
                    for rs_res, _, sp in t.all_spans()) for k, v in tags.items()):
            continue
        want.add(tid.hex())
    return want


def _count_calls(monkeypatch, name, log):
    """Append the positional arguments of every call of db/search.<name>
    to `log`, and let the call through."""
    orig = getattr(search_mod, name)

    def spy(*a, **kw):
        log.append(a)
        return orig(*a, **kw)

    monkeypatch.setattr(search_mod, name, spy)


@pytest.fixture
def materialized(monkeypatch):
    """Number of traces handed to BackendBlock.materialize_traces."""
    calls: list[int] = []
    orig = BackendBlock.materialize_traces

    def spy(self, sids, *a, **kw):
        calls.append(len(sids))
        return orig(self, sids, *a, **kw)

    monkeypatch.setattr(BackendBlock, "materialize_traces", spy)
    return calls


# --------------------------------------------------------------- (a)
@pytest.mark.parametrize("engine", ["host", "device", "fused-host", "fused-device",
                                    "batchexec", "sharded-host", "sharded-device"])
def test_exact_plan_under_window_materializes_nothing(world, engine, monkeypatch,
                                                      materialized):
    """A windowed search whose query conditions are exact makes zero
    materialize_traces calls and returns what the unwindowed plan returns,
    filtered on start_ns: ids, order, matched_spans."""
    db, traces, start_of = world
    run = ENGINES[engine]
    for q in EXACT_QUERIES:
        for blk in _blocks(db, TWO):
            p = _plan_for_block(blk, SearchRequest(query=q, start=START, end=END))
            assert p.prune or not p.needs_verify, q
        free = _rows(run(db, TWO, SearchRequest(query=q, limit=1000), monkeypatch))
        materialized.clear()
        got = _rows(run(db, TWO, SearchRequest(query=q, limit=1000, start=START,
                                               end=END), monkeypatch))
        assert sum(materialized) == 0, (q, materialized)
        assert got == [r for r in free if _in_window(r[1])], q
        assert {r[0] for r in got} == _oracle(traces, start_of, q), q
        assert got, q


# --------------------------------------------------------------- (b)
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_window_edges_exact_and_escalate(world, engine, monkeypatch):
    """Traces within 1 ms outside start / end are excluded, those exactly
    on them included, at second granularity of req.start / req.end; with a
    small limit the dropped edge rows (the newest the device lets through)
    leave the first selection short and the collect widens k."""
    db, traces, start_of = world
    inside = sorted((s for s in start_of.values() if _in_window(s)), reverse=True)
    assert START * NS in inside and END * NS in inside and len(inside) == 45

    collects, settled = [], []
    _count_calls(monkeypatch, "_collect_topk", collects)
    _count_calls(monkeypatch, "_collect_topk_multi", collects)
    _count_calls(monkeypatch, "_candidates", settled)

    q = '{ duration > 1us }'  # exact on the device, matches every trace
    small = ENGINES[engine](db, ONE, SearchRequest(query=q, limit=5, start=START,
                                                   end=END), monkeypatch)
    got = [t.start_time_unix_nano for t in small.traces]
    assert len(set(t.trace_id for t in small.traces)) == 5, got
    assert all(_in_window(s) for s in got), got
    assert got == sorted(got, reverse=True)
    if engine in _MS_KEYED:
        assert got == inside[:5]
    # one block: a collect settles once per selection, so more settles
    # than collects means a selection came back short and k was widened
    assert collects and len(settled) > len(collects), (collects, settled)

    full = ENGINES[engine](db, ONE, SearchRequest(query=q, limit=1000, start=START,
                                                  end=END), monkeypatch)
    assert [t.start_time_unix_nano for t in full.traces] == inside
    # one edge alone, and the window one second narrower on each side
    only_end = ENGINES[engine](db, ONE, SearchRequest(query=q, limit=1000, end=START),
                               monkeypatch)
    assert sorted(t.start_time_unix_nano for t in only_end.traces) == sorted(
        s for s in start_of.values() if s <= START * NS)
    only_start = ENGINES[engine](db, ONE, SearchRequest(query=q, limit=1000, start=END),
                                 monkeypatch)
    assert sorted(t.start_time_unix_nano for t in only_start.traces) == sorted(
        s for s in start_of.values() if s >= END * NS)


# --------------------------------------------------------------- (c)
LOSSY = [
    ('{ span.latency.weight > 0.25 }', "host", "lossy_cond"),  # float attribute
    ('{ span.latency.weight > 0.25 }', "device", "lossy_cond"),
    ('{ span.bytes.sent > 4000000000 }', "device", "lossy_cond"),  # clamped int
    ('{ span.bytes.sent > 4000000000 }', "batchexec", "lossy_cond"),
    ('{ name = "GET /api" } ~ { true }', "device", "lossy_cond"),  # sibling tree
    ('{ name = "GET /api" } ~ { true }', "fused-host", "lossy_cond"),
    ('{ true } >> { name = "db.query" }', "sharded-device", "struct_on_shard"),
    ('{ true } >> { name = "db.query" }', "sharded-host", "struct_on_shard"),
    ('{ span.latency.weight > 0.25 } | count() > 1', "fused-device", "lossy_cond"),
]


@pytest.mark.parametrize("query,engine,reason", LOSSY)
def test_lossy_plan_under_window_still_verifies(world, query, engine, reason,
                                                monkeypatch, materialized):
    """Float attribute, clamped int, `~`, struct on a shard, a pipeline:
    a condition of the query is conservative, so hosteval still settles
    every candidate -- and the window is still exact."""
    db, traces, start_of = world
    verified = []  # (blk, req, sids, verify) of every _verify_candidates call
    _count_calls(monkeypatch, "_verify_candidates", verified)
    before = TEL.routing_counts().get(("verify", "hosteval", reason), 0)
    got = ENGINES[engine](db, TWO, SearchRequest(query=query, limit=1000, start=START,
                                                 end=END), monkeypatch)
    assert any(verify and len(sids) for _, _, sids, verify in verified)
    assert sum(materialized) > 0
    assert TEL.routing_counts().get(("verify", "hosteval", reason), 0) > before
    want = _oracle(traces, start_of, query)
    assert {t.trace_id for t in got.traces} == want
    assert want and len(want) < len(_oracle(traces, start_of, query, windowed=False))


# --------------------------------------------------------------- (d)
def test_verify_routing_counter(world, monkeypatch):
    """("verify", "skip" | "hosteval", reason): one decision per collected
    block, in /status/kernels `routing` like every routing decision."""
    db, _, _ = world
    blk = _blocks(db, ONE)[0]
    groups = list(range(len(blk.meta.row_groups)))

    def delta(fn):
        a = TEL.routing_counts()
        fn()
        b = TEL.routing_counts()
        return {k[1:]: v - a.get(k, 0) for k, v in b.items()
                if k[0] == "verify" and v - a.get(k, 0)}

    win = dict(start=START, end=END, limit=50)
    assert delta(lambda: search_block(blk, SearchRequest(
        query='{ duration > 1us }', **win), mode="host")) == {("skip", "exact_plan"): 1}
    assert delta(lambda: search_block(blk, SearchRequest(
        query='{ span.latency.weight > 0.25 }', **win), mode="host")) == {
            ("hosteval", "lossy_cond"): 1}
    assert delta(lambda: search_block(blk, SearchRequest(
        tags={"http.method": "GET"}, **win), mode="host")) == {("skip", "no_query"): 1}
    struct = SearchRequest(query='{ true } >> { name = "db.query" }', **win)
    assert delta(lambda: search_block(blk, struct, mode="host")) == {
        ("skip", "exact_plan"): 1}  # the whole block keeps its struct node
    assert delta(lambda: search_block(blk, struct, groups_range=groups[:2],
                                      mode="host")) == {("hosteval", "struct_on_shard"): 1}
    # the fused engine: one decision per block of the global collect
    monkeypatch.setattr(route_mod, "link_rtt_ms", lambda: 1e9)
    assert delta(lambda: search_blocks_fused(_blocks(db, TWO), SearchRequest(
        query='{ duration > 1us }', **win))) == {("skip", "exact_plan"): 2}
    rows = [r for r in TEL.snapshot()["routing"] if r["layer"] == "verify"]
    assert {(r["engine"], r["reason"]) for r in rows} >= {
        ("skip", "exact_plan"), ("skip", "no_query"), ("hosteval", "lossy_cond"),
        ("hosteval", "struct_on_shard")}


# ------------------------------------------------------ the invariant
REQUESTS = {
    "tags": dict(tags={"http.method": "GET"}),  # never verified: _candidates alone
    "exact": dict(query='{ name = "GET /api" }'),
    "lossy": dict(query='{ span.latency.weight > 0.25 }'),
    "struct": dict(query='{ true } >> { name = "db.query" }'),
    "match-all": dict(query='{ true }'),
}


@pytest.mark.parametrize("kind", sorted(REQUESTS))
@pytest.mark.parametrize("engine", sorted(ENGINES))
def test_every_engine_settles_the_window(world, engine, kind, monkeypatch):
    """Every function in db/ that turns selected sids into results ends in
    _candidates: a window that cuts the blocks (most of each lies outside,
    half of that within the device's +-1 ms) returns exactly the oracle's
    set, for requests that verify and for those that never do."""
    db, traces, start_of = world
    spec = REQUESTS[kind]
    got = ENGINES[engine](db, TWO, SearchRequest(limit=1000, start=START, end=END,
                                                 **spec), monkeypatch)
    ids = [t.trace_id for t in got.traces]
    assert len(ids) == len(set(ids))
    assert all(_in_window(t.start_time_unix_nano) for t in got.traces)
    want = _oracle(traces, start_of, spec.get("query", ""), spec.get("tags"))
    assert set(ids) == want and want


def test_results_are_built_only_behind_candidates():
    """Static half of the invariant: in db/, SearchResult objects are built
    by _materialize from the records _candidates made (and by the live
    head, which settles its own per-trace index), and _materialize is
    called only by the collects and the fused merge that takes their records.
    A new engine that builds results itself has to come here and say who
    re-checks its window."""
    root = pathlib.Path(search_mod.__file__).parent
    builders, callers = set(), set()
    for path in sorted(root.glob("*.py")):
        tree = ast.parse(path.read_text())
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                    if node.func.id == "SearchResult":
                        builders.add(f"{path.stem}.{fn.name}")
                    elif node.func.id == "_materialize":
                        callers.add(f"{path.stem}.{fn.name}")
    assert builders == {"search._materialize", "search.response_from_dict",
                        "live_engine._collect"}, builders
    assert callers == {"search._collect_topk", "search._collect_topk_multi",
                       "search._fused_eval"}, callers  # search_blocks_fused past its routing
    src = (root / "search.py").read_text()
    for fn in ("_collect_topk", "_collect_topk_multi"):
        body = src.split(f"def {fn}(")[1].split("\ndef ")[0]
        assert "_candidates(" in body, fn
