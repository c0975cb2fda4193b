"""The staged cache holds device columns, not column sets (ops/stage).

A request resolves each column it names against the block's store and
stages only what is missing; what it gets back is a fresh view that
equals an uncached staging field for field. Counts and equality only:
CPU, tiny blocks."""

from __future__ import annotations

import gc
import threading

import numpy as np
import pytest

from tempo_tpu.backend import MemBackend
from tempo_tpu.block import build_block_from_traces, open_block
from tempo_tpu.db.search import SearchRequest, _plan_for_block
from tempo_tpu.ops import chunkpool, stage
from tempo_tpu.ops.filter import Operands, eval_block, required_columns
from tempo_tpu.ops.stage import has_staged, is_staged, stage_block
from tempo_tpu.util.kerneltel import TEL
from tempo_tpu.util.testdata import make_traces

TENANT = "t"
SHARD = [1, 2]  # a row-group range, as the frontend's shard jobs ask


@pytest.fixture(autouse=True)
def _clean():
    chunkpool.clear()
    budget = stage.staged_cache_stats()["budget_bytes"]
    yield
    stage.set_staged_cache_budget(budget)
    chunkpool.clear()
    gc.collect()


def _open(n_traces=120, seed=41):
    backend = MemBackend()
    meta = build_block_from_traces(
        backend, TENANT, make_traces(n_traces, seed=seed, n_spans=10),
        row_group_spans=256)
    blk = open_block(backend, TENANT, meta.block_id)
    assert blk.pack.axes["span"].n_groups >= 4
    return backend, meta, blk


# ---- the five shapes of benchmarks/mixes/read-mix.json, as the routes
# spell their column lists (db/search, db/batchexec, db/metrics_exec)

_SEARCHES = {
    "attr_eq": SearchRequest(query='{ span.component = "grpc" }'),
    "duration_gt": SearchRequest(query="{ duration > 900ms }"),
    "struct_desc": SearchRequest(
        query='{ span.component = "grpc" } >> { duration > 500ms }'),
    "tag_service": SearchRequest(tags={"service.name": "db"}),
}
SHAPES = [*sorted(_SEARCHES), "rate_service"]


def _shape(blk, name):
    """-> (needed, launch): the column list a route stages for the shape
    and a kernel launch over a StagedBlock of it."""
    if name == "rate_service":
        from tempo_tpu.db.metrics_exec import parse_metrics_query
        from tempo_tpu.ops.timeseries import eval_timeseries_device
        from tempo_tpu.traceql.plan import plan_metrics_filter

        p = plan_metrics_filter(
            parse_metrics_query('{ resource.service.name = "db" } | rate()'),
            blk.dictionary)
        needed = [n for n in required_columns(p.conds)
                  if n != "trace.span_off"] + ["span.start_ms"]
        operands = Operands.build(p.rows, p.tables or None)

        def launch(st):
            return eval_timeseries_device(
                (p.tree, p.conds), st, operands,
                np.zeros(st.n_spans, np.int32), None, None, 0, 1000, 4, 1)
    else:
        p = _plan_for_block(blk, _SEARCHES[name])
        needed = (required_columns(p.conds) + list(p.extra_cols)
                  + ["trace.start_ms"])
        operands = Operands.build(p.rows, p.tables or None)

        def launch(st):
            return eval_block(
                (p.tree, p.conds), st.cols, operands, st.n_spans, st.n_traces,
                st.n_spans_b, st.n_res_b, st.n_traces_b, span_out=False)
    return needed, launch


def _fields(st):
    return (st.n_spans, st.n_traces, st.n_res, st.n_spans_b, st.n_traces_b,
            st.n_res_b, st.span_base)


def _assert_same(view, ref):
    assert _fields(view) == _fields(ref)
    assert set(view.cols) == set(ref.cols)
    for name, arr in ref.cols.items():
        got = view.cols[name]
        assert (got.dtype, got.shape) == (arr.dtype, arr.shape), name
        np.testing.assert_array_equal(np.asarray(got), np.asarray(arr), name)


def _staging():
    return TEL.snapshot()["staging"]


def _record_reads(monkeypatch) -> list[list[str]]:
    """The pack columns each host read phase asks for, from here on."""
    reads: list[list[str]] = []
    real_read = stage.read_stage_columns

    def recording(blk, plan, groups):
        reads.append(list(plan.read_names))
        return real_read(blk, plan, groups)

    monkeypatch.setattr(stage, "read_stage_columns", recording)
    return reads


# ------------------------------------------------------- (a) the view


@pytest.mark.parametrize("groups", [None, SHARD], ids=["block", "shard"])
@pytest.mark.parametrize("shape", SHAPES)
def test_warm_view_equals_uncached(shape, groups):
    """A view from a cache that other shapes and ranges have filled is
    the uncached staging, field for field and array for array, and a
    kernel launched on it compiles nothing the uncached one did not."""
    _, _, blk = _open()
    for other in SHAPES:  # every shape, both ranges: the store is shared
        needed, _ = _shape(blk, other)
        stage_block(blk, needed, None)
        stage_block(blk, needed, SHARD)
    needed, launch = _shape(blk, shape)
    ref = stage_block(blk, needed, groups, cache=False)
    out_ref = launch(ref)
    before, timed = _staging(), TEL.stage_stats()["stage:assemble"]["count"]
    view = stage_block(blk, needed, groups)
    after = _staging()
    # a hit is timed too (as assembling its view): the staging time of
    # a traced search is then ~0, not absent
    assert TEL.stage_stats()["stage:assemble"]["count"] == timed + 1
    assert after["cache_hits"] == before["cache_hits"] + 1
    assert after["column_misses"] == before["column_misses"]
    assert after["transfer_bytes_total"] == before["transfer_bytes_total"]
    _assert_same(view, ref)
    compiles = TEL.totals()[0]
    out = launch(view)
    assert TEL.totals()[0] == compiles
    for a, b in zip(out, out_ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------- (b) overlapping requests


@pytest.mark.parametrize("groups", [None, SHARD], ids=["block", "shard"])
def test_overlap_stages_only_missing(groups, monkeypatch):
    """The second of two overlapping requests reads, assembles and
    uploads its missing columns only, and shares the rest by identity."""
    _, _, blk = _open()
    reads = _record_reads(monkeypatch)
    first, _ = _shape(blk, "attr_eq")
    second, _ = _shape(blk, "struct_desc")
    shared = [n for n in second if n in first]
    extra = [n for n in second if n not in first]
    assert len(shared) >= 4 and extra

    s0 = _staging()
    a = stage_block(blk, first, groups)
    s1 = _staging()
    assert sorted(reads.pop()) == sorted(first)
    assert s1["column_misses"] - s0["column_misses"] == len(a.cols)
    assert s1["bytes_reused"] == s0["bytes_reused"]

    b = stage_block(blk, second, groups)
    s2 = _staging()
    assert sorted(reads.pop()) == sorted(extra)
    assert (s2["cache_hits"], s2["cache_misses"]) == (
        s1["cache_hits"], s1["cache_misses"] + 1)
    assert s2["column_hits"] - s1["column_hits"] == len(shared)
    assert s2["column_misses"] - s1["column_misses"] == len(extra)
    new_bytes = sum(b.cols[n].nbytes for n in extra)
    assert s2["transfer_bytes_total"] - s1["transfer_bytes_total"] == new_bytes
    assert s2["bytes_reused"] - s1["bytes_reused"] == sum(
        arr.nbytes for name, arr in b.cols.items() if name in a.cols)
    for name in a.cols:
        if name in b.cols:
            assert b.cols[name] is a.cols[name], name
    _assert_same(b, stage_block(blk, second, groups, cache=False))
    reads.clear()
    s2 = _staging()

    # a third spelling of columns that are all resident: a hit, no read
    third = sorted(set(first) | set(extra), reverse=True)
    stage_block(blk, third, groups)
    s3 = _staging()
    assert not reads
    assert s3["cache_hits"] == s2["cache_hits"] + 1
    assert s3["transfer_bytes_total"] == s2["transfer_bytes_total"]


def test_shards_share_range_free_columns():
    """Trace-, res- and rattr-axis columns are one array for every
    range; span/sattr-axis columns and what is cut per slice are not."""
    _, _, blk = _open()
    needed, _ = _shape(blk, "tag_service")
    needed = needed + ["trace@gkey_s"]
    whole = stage_block(blk, needed, None)
    shard = stage_block(blk, needed, SHARD)
    for name in ("trace.start_ms", "trace@gkey_s", "res.service_id"):
        assert shard.cols[name] is whole.cols[name], name
    for name in ("span.trace_sid", "span.res_idx", "span@res.service_id",
                 "trace.span_off"):
        assert shard.cols[name] is not whole.cols[name], name
    _assert_same(shard, stage_block(blk, needed, SHARD, cache=False))
    keys = stage.column_keys(blk, needed + ["sattr.span", "rattr.res"], SHARD)
    assert keys["sattr.span"] == ("sattr.off", tuple(SHARD))
    assert keys["rattr.res"] == ("rattr.off", None)
    assert keys["span@res.service_id"] == ("span@res.service_id", tuple(SHARD))
    assert keys["trace@gkey_s"] == ("trace@gkey_s", None)
    # a span@ column is staged only beside its sources, as before
    assert "span@res.service_id" not in stage.column_keys(
        blk, ["span@res.service_id", "span.res_idx"], None)


# --------------------------------------------- (c) no admission caps


def test_wide_request_is_resident_on_second_call():
    """What the old per-entry cap refused (a request over four
    span-bucket columns, 256 MB at benchmark size) and what the old
    per-block cap of 32 entries pushed out both stay resident: the byte
    budget is the one policy."""
    assert not hasattr(stage, "_CACHE_MAX_ENTRY_BYTES")
    assert not hasattr(stage, "_CACHE_MAX_ENTRIES")
    _, _, blk = _open()
    needed, _ = _shape(blk, "struct_desc")
    view = stage_block(blk, needed)
    old_cap_equivalent = 4 * view.n_spans_b * 4
    assert sum(a.nbytes for a in view.cols.values()) > old_cap_equivalent
    assert is_staged(blk, needed)
    s0 = _staging()
    again = stage_block(blk, needed)
    s1 = _staging()
    assert s1["cache_hits"] == s0["cache_hits"] + 1
    assert s1["column_misses"] == s0["column_misses"]
    assert all(again.cols[n] is view.cols[n] for n in view.cols)
    n_groups = blk.pack.axes["span"].n_groups
    ranges = [[g] for g in range(n_groups)] + [
        [g, g + 1] for g in range(n_groups - 1)]
    for r in ranges:
        stage_block(blk, needed, r)
    stats = stage.staged_cache_stats(max_entries=4)
    assert stats["entries"] > 32
    assert len(stats["hottest"]) == 4
    assert set(stats["hottest"][0]) == {"block_id", "column", "groups", "nbytes"}
    assert is_staged(blk, needed) and all(is_staged(blk, needed, r) for r in ranges)


# ------------------------------------------------- (d) budget eviction


@pytest.mark.parametrize("pool", ["1", "0"], ids=["pool", "no-pool"])
def test_budget_eviction_drops_single_columns(pool, monkeypatch):
    """Over budget, the LRU drops the coldest COLUMNS; the next request
    restages exactly those -- from the host chunk pool when it admits
    them, else from the backend -- and equals an uncached staging."""
    monkeypatch.setenv("TEMPO_CHUNK_CACHE", pool)
    monkeypatch.setenv("TEMPO_CHUNK_CACHE_MIN_REUSE", "1")
    stage.set_staged_cache_budget(1)  # other tests' blocks: out of the way
    stage.set_staged_cache_budget(4 << 30)
    _, meta, blk = _open()
    needed, _ = _shape(blk, "attr_eq")
    view = stage_block(blk, needed)
    keys = stage.column_keys(blk, needed, None)
    assert is_staged(blk, needed)
    # touch two columns so that they are the warmest, then leave room
    # for just those two
    warm = ["sattr.key_id", "sattr.vtype"]
    stage_block(blk, warm)
    keep = sum(view.cols[keys[n][0]].nbytes for n in warm)
    base = stage.staged_cache_stats()["bytes"] - sum(
        a.nbytes for a in view.cols.values())
    d0 = chunkpool.stats()
    stage.set_staged_cache_budget(base + keep)
    assert is_staged(blk, warm) and not is_staged(blk, needed)
    evicted = [n for n in needed if not is_staged(blk, [n])]
    assert sorted(evicted) == sorted(set(needed) - set(warm))
    assert has_staged(blk)  # still a hot block: it is restaged, not sent cold
    pooled = chunkpool.stats()["demotions"] - d0["demotions"]
    assert pooled == (len(evicted) if pool == "1" else 0)

    stage.set_staged_cache_budget(4 << 30)
    reads = _record_reads(monkeypatch)
    s0, h0 = _staging(), chunkpool.stats()["hits"]
    again = stage_block(blk, needed)
    s1 = _staging()
    assert s1["column_hits"] - s0["column_hits"] == len(warm)
    assert s1["column_misses"] - s0["column_misses"] == len(evicted)
    # either way the columns cross the link again, and are counted
    assert s1["transfer_bytes_total"] - s0["transfer_bytes_total"] == sum(
        again.cols[keys[n][0]].nbytes for n in evicted)
    if pool == "1":
        assert not reads  # every evicted column came back from the pool
        assert chunkpool.stats()["hits"] - h0 == len(evicted)
    else:
        assert [sorted(r) for r in reads] == [sorted(evicted)]
    for n in warm:
        assert again.cols[keys[n][0]] is view.cols[keys[n][0]]
    _assert_same(again, stage_block(blk, needed, cache=False))
    assert is_staged(blk, needed)


def test_concurrent_lookups_keep_the_books():
    """More threads than cores stage overlapping requests of one block
    while the budget flips under them: every view is whole, and
    afterwards the LRU's bytes are the sum of its entries and each
    entry of this block is in the block's store."""
    import sys

    _, _, blk = _open(n_traces=100, seed=43)
    requests = [(_shape(blk, s)[0], g) for s in SHAPES for g in (None, SHARD)]
    want = {i: set(stage_block(blk, n, g, cache=False).cols)
            for i, (n, g) in enumerate(requests)}
    budget = stage.staged_cache_stats()["budget_bytes"]
    errors: list = []
    stop = threading.Event()

    def worker(k):
        try:
            for i in range(60):
                j = (i + k) % len(requests)
                view = stage_block(blk, *requests[j])
                assert set(view.cols) == want[j]
        except BaseException as e:  # noqa: BLE001 - reported below
            errors.append(e)

    def squeeze():
        while not stop.is_set():
            stage.set_staged_cache_budget(1 << 14)
            stage.set_staged_cache_budget(budget)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=worker, args=(k,), daemon=True)
               for k in range(16)]
    sq = threading.Thread(target=squeeze, daemon=True)
    try:
        sq.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        stop.set()
        sq.join(10)
        sys.setswitchinterval(old)
        stage.set_staged_cache_budget(budget)
    assert not errors, errors[:3]
    assert not any(t.is_alive() for t in threads) and not sq.is_alive()
    with stage._lru_lock:
        assert stage._lru_bytes == sum(e[1] for e in stage._lru.values())
        mine = {k[1] for k, e in stage._lru.items() if e[0]() is blk}
    for needed, groups in requests:  # the store and the LRU agree
        keys = stage.column_keys(blk, needed, groups).values()
        assert is_staged(blk, needed, groups) == all(k in mine for k in keys)


# --------------------------------- (e) one question, one function


@pytest.mark.parametrize("route", ["search_block", "search_fused", "batch"])
def test_is_staged_agrees_with_the_routes(route):
    """is_staged / has_staged answer what the three routes used to peek
    at: has the block been staged at all, and would THIS request (as the
    route spells it) be a full hit."""
    _, _, blk = _open()
    p = _plan_for_block(blk, _SEARCHES["duration_gt"])
    base = required_columns(p.conds) + list(p.extra_cols)
    needed, groups = {
        "search_block": (base + ["trace.start_ms"], SHARD),
        "search_fused": (tuple(base) + ("trace@gkey_s",), None),
        "batch": (base + ["trace.start_ms"], None),
    }[route]
    assert not has_staged(blk)
    assert not is_staged(blk, needed, groups)
    stage_block(blk, list(base), groups, cache=False)
    assert not has_staged(blk)  # an uncached staging leaves no trace
    stage_block(blk, list(base), groups)  # all but the route's last column
    assert has_staged(blk)
    assert not is_staged(blk, needed, groups)
    s0 = _staging()
    stage_block(blk, list(needed), groups)
    assert _staging()["column_misses"] - s0["column_misses"] == 1
    assert is_staged(blk, needed, groups)
    assert is_staged(blk, list(reversed(needed)), groups)  # any spelling
    other = [0] if groups is None else None
    assert not is_staged(blk, needed, other)
    assert not is_staged(blk, list(needed) + ["span.kind"], groups)
    if route == "batch":
        # the batch window's probe: a staged hit makes the block eligible
        # below the promotion threshold, under the key shape it had
        from types import SimpleNamespace

        from tempo_tpu.db.batchexec import batched_search_block_many

        taken = []
        window = SimpleNamespace(enabled=True, submit_many=lambda key, items: (
            taken.append((key, items)) or [None] * len(items)))
        blk.promote_touches = 99
        batched_search_block_many(window, [(blk, _SEARCHES["duration_gt"], None)])
        (key, (item,)), = taken
        assert key[3] is None and key[4] == tuple(needed)
        assert item.needed == base
        _, _, cold = _open(seed=44)
        cold.promote_touches = 99
        plans = []
        batched_search_block_many(
            window, [(cold, _SEARCHES["duration_gt"], None)],
            refused=lambda blk, req, groups, planned: plans.append(planned))
        assert len(taken) == 1 and plans[0].conds == item.planned.conds
