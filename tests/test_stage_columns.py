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
    assert keys["sattr.span"] == ("sattr.over", tuple(SHARD))
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


def test_staged_block_ids_follow_admit_and_eviction():
    """What a querier tells the frontend it holds (services/frontend
    `_claimer`): a block is in the set from its first admitted column to
    the eviction of its last, whatever the columns are."""
    stage.set_staged_cache_budget(1)  # other tests' blocks: out of the way
    stage.set_staged_cache_budget(4 << 30)
    _, meta_a, blk_a = _open(seed=41)
    _, meta_b, blk_b = _open(seed=43)
    assert not {meta_a.block_id, meta_b.block_id} & stage.staged_block_ids()
    stage_block(blk_a, ["sattr.key_id"])
    assert meta_a.block_id in stage.staged_block_ids()
    assert meta_b.block_id not in stage.staged_block_ids()
    needed, _ = _shape(blk_b, "attr_eq")
    view_b = stage_block(blk_b, needed)
    assert {meta_a.block_id, meta_b.block_id} <= stage.staged_block_ids()
    # room for b's columns alone: a's one column is the coldest and goes
    stage.set_staged_cache_budget(sum(a.nbytes for a in view_b.cols.values()))
    assert not is_staged(blk_a, ["sattr.key_id"]) and is_staged(blk_b, needed)
    assert meta_a.block_id not in stage.staged_block_ids()
    assert meta_b.block_id in stage.staged_block_ids()
    # a part of a block's columns still counts as holding it
    keys = stage.column_keys(blk_b, needed, None)
    one = min(view_b.cols[keys[n][0]].nbytes for n in needed)
    stage.set_staged_cache_budget(one)
    assert not is_staged(blk_b, needed)
    assert meta_b.block_id in stage.staged_block_ids()
    # a block that died leaves the set with its weakref
    del view_b, blk_b
    gc.collect()
    assert meta_b.block_id not in stage.staged_block_ids()


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


# ------------------- (f) sattr.* slot-major: K planes, then overflow rows

_ATTR_NEEDED = ["sattr.span", "sattr.key_id", "sattr.vtype", "sattr.str_id",
                "span.trace_sid", "trace.span_off"]
_SATTR_VALUES = [n for n in _ATTR_NEEDED if n.startswith("sattr.")][1:]


def _open_uniform(monkeypatch, attrs_per_span=2):
    """The benchmark corpus's shape (util/testdata.synth_block: every span
    owns `attrs_per_span` rows), cut into small row groups."""
    from tempo_tpu.block import schema as S
    from tempo_tpu.util.testdata import synth_block

    monkeypatch.setattr(S, "DEFAULT_ROW_GROUP_SPANS", 300)
    backend = MemBackend()
    meta, _ = synth_block(backend, TENANT, np.random.default_rng(7), 100, 12,
                          n_res=16, attrs_per_span=attrs_per_span)
    blk = open_block(backend, TENANT, meta.block_id)
    assert blk.pack.axes["span"].n_groups >= 4
    return blk


def _open_skewed():
    """make_traces' block with one span owning 40 attribute rows (in the
    shard's second row group)."""
    traces = make_traces(120, seed=41, n_spans=10)
    _, _, sp = list(traces[60][1].all_spans())[3]
    sp.attrs.update({f"extra{i}": f"v{i}" for i in range(35)})
    backend = MemBackend()
    meta = build_block_from_traces(backend, TENANT, traces, row_group_spans=256)
    blk = open_block(backend, TENANT, meta.block_id)
    owners = blk.pack.read_groups("sattr.span", SHARD)
    assert np.bincount(owners).max() == 40
    return blk


def _attr_reduce_rows():
    return {k[1:]: n for k, n in TEL.routing_counts().items()
            if k[0] == "attr_reduce"}


def _launch_attr_eq(blk, st, query=None):
    """`{ span.<k> = "<v>" }`, by default for the block's first string
    attribute row (util/testdata.synth_columns names its own keys)."""
    if query is None:
        row = int(np.flatnonzero(blk.pack.read("sattr.vtype") == 0)[0])
        k, v = (blk.dictionary.string(int(blk.pack.read(c)[row]))
                for c in ("sattr.key_id", "sattr.str_id"))
        query = f'{{ span.{k} = "{v}" }}'
    p = _plan_for_block(blk, SearchRequest(query=query))
    assert any(c.target == "sattr" for c in p.conds)
    return eval_block((p.tree, p.conds), st.cols,
                      Operands.build(p.rows, p.tables or None), st.n_spans,
                      st.n_traces, st.n_spans_b, st.n_res_b, st.n_traces_b,
                      span_out=False)


def _slice_rows(blk, groups):
    """-> (group list, owners rebased to the slice, its span count)."""
    span_ax = blk.pack.axes["span"]
    glist = groups or list(range(span_ax.n_groups))
    base = span_ax.offsets[glist[0]]
    return (glist, blk.pack.read_groups("sattr.span", glist) - base,
            span_ax.offsets[glist[-1] + 1] - base)


@pytest.mark.parametrize("groups", [None, SHARD], ids=["block", "shard"])
def test_uniform_counts_leave_no_overflow(groups, monkeypatch):
    """Two attributes on every span: two planes of n_spans_b, plane j =
    every span's j-th row, no overflow row and no offsets column staged
    or resident, no more bytes than the flat rows took, and the launch
    says `slots dense_counts`."""
    blk = _open_uniform(monkeypatch)
    view = stage_block(blk, _ATTR_NEEDED, groups)
    assert "sattr.off" not in view.cols and "sattr.span" not in view.cols
    assert view.cols["sattr.over"].shape == (0,)
    glist, owners, n_spans = _slice_rows(blk, groups)
    assert n_spans == view.n_spans
    for n in _SATTR_VALUES:
        raw = blk.pack.read_groups(n, glist)
        got = np.asarray(view.cols[n]).reshape(2, view.n_spans_b)
        np.testing.assert_array_equal(got[:, :n_spans], raw.reshape(-1, 2).T)
        assert (got[:, n_spans:] == stage.PAD_I32).all()
        assert got.size <= stage.bucket(raw.shape[0])
    gkey = tuple(groups) if groups else None
    keys = stage.column_keys(blk, _ATTR_NEEDED, groups)
    assert keys["sattr.span"] == ("sattr.over", gkey)
    assert all(keys[n] == (n, gkey) for n in _SATTR_VALUES)
    assert is_staged(blk, _ATTR_NEEDED, groups)
    before = _attr_reduce_rows()
    out = _launch_attr_eq(blk, view)
    after = _attr_reduce_rows()
    assert after.get(("slots", "dense_counts"), 0) == before.get(
        ("slots", "dense_counts"), 0) + 1
    assert sum(after.values()) == sum(before.values()) + 1
    # the same answer with every row an overflow row
    monkeypatch.setattr(stage, "_head_planes", lambda *a: 0)
    rows = stage_block(blk, _ATTR_NEEDED, groups, cache=False)
    assert rows.cols["sattr.over"].shape == rows.cols["sattr.key_id"].shape
    for a, b in zip(out, _launch_attr_eq(blk, rows)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("groups", [None, SHARD], ids=["block", "shard"])
def test_a_long_span_overflows_alone(groups):
    """One span with 40 attributes: the slice keeps as many planes as its
    flat rows' bucket holds, only the rows beyond a span's K-th are
    overflow rows (in row order, with their owners), and the launch says
    `slots skewed_counts` and answers as the host twin does."""
    from tempo_tpu.ops.hostfilter import eval_block_host

    blk = _open_skewed()
    view = stage_block(blk, _ATTR_NEEDED, groups)
    glist, owners, n_spans = _slice_rows(blk, groups)
    cnt = np.bincount(owners, minlength=n_spans)
    k = stage.bucket(owners.shape[0]) // view.n_spans_b
    assert 1 <= k < 40 == cnt.max()
    slot = np.arange(owners.shape[0]) - np.repeat(np.cumsum(cnt) - cnt, cnt)
    n_over = int((slot >= k).sum())
    over_b = stage.bucket(n_over)
    assert 40 - k <= n_over < owners.shape[0] // 2
    over = np.asarray(view.cols["sattr.over"])
    np.testing.assert_array_equal(over[:n_over], owners[slot >= k])
    assert over.shape == (over_b,) and (over[n_over:] == view.n_spans_b).all()
    for n in _SATTR_VALUES:
        raw = blk.pack.read_groups(n, glist)
        got = np.asarray(view.cols[n])
        assert got.shape == (k * view.n_spans_b + over_b,)
        planes = got[:k * view.n_spans_b].reshape(k, view.n_spans_b)
        for j in range(k):
            np.testing.assert_array_equal(planes[j, owners[slot == j]], raw[slot == j])
        assert (planes == stage.PAD_I32).sum() == planes.size - (slot < k).sum()
        np.testing.assert_array_equal(got[k * view.n_spans_b:][:n_over], raw[slot >= k])
        assert (got[k * view.n_spans_b + n_over:] == stage.PAD_I32).all()
    before = _attr_reduce_rows()
    p = _plan_for_block(blk, SearchRequest(query='{ span.extra34 = "v34" }'))
    operands = Operands.build(p.rows, p.tables or None)
    tm, cnt_t = eval_block((p.tree, p.conds), view.cols, operands, view.n_spans,
                           view.n_traces, view.n_spans_b, view.n_res_b,
                           view.n_traces_b, span_out=False)
    after = _attr_reduce_rows()
    assert after.get(("slots", "skewed_counts"), 0) == before.get(
        ("slots", "skewed_counts"), 0) + 1
    assert sum(after.values()) == sum(before.values()) + 1
    host = {n: blk.pack.read_groups(n, glist) for n in _ATTR_NEEDED
            if not n.startswith("trace.")}
    host["sattr.span"] = owners
    span_off = blk.pack.read("trace.span_off")
    base = blk.pack.axes["span"].offsets[glist[0]]
    host["trace.span_off"] = (np.clip(span_off, base, base + n_spans) - base).astype(np.int32)
    tm_h, cnt_h = eval_block_host((p.tree, p.conds), host, operands, n_spans,
                                  view.n_traces)
    assert tm_h.sum() == 1  # the long span's trace, through an overflow row
    np.testing.assert_array_equal(np.asarray(tm)[:view.n_traces], tm_h)
    np.testing.assert_array_equal(np.asarray(cnt_t)[:view.n_traces], cnt_h)


@pytest.mark.parametrize("counts", ["uniform", "skewed"])
def test_later_value_column_joins_the_slice_layout(counts, monkeypatch):
    """A value column staged when the others are resident is placed by
    the slice's owners again and lands in the same slots and overflow
    rows; the owners' own column is resident and is not sent again: the
    view equals staging everything at once."""
    blk = _open_uniform(monkeypatch) if counts == "uniform" else _open_skewed()
    first = stage_block(blk, _ATTR_NEEDED, SHARD)
    assert (first.cols["sattr.over"].shape[0] > 0) == (counts == "skewed")
    reads = _record_reads(monkeypatch)
    s0 = _staging()
    view = stage_block(blk, _ATTR_NEEDED + ["sattr.int32"], SHARD)
    s1 = _staging()
    assert [sorted(r) for r in reads] == [["sattr.int32", "sattr.span"]]
    assert s1["column_misses"] - s0["column_misses"] == 1
    assert s1["transfer_bytes_total"] - s0["transfer_bytes_total"] == (
        view.cols["sattr.int32"].nbytes)
    assert view.cols["sattr.int32"].shape == view.cols["sattr.key_id"].shape
    assert all(view.cols[n] is first.cols[n] for n in first.cols)
    _assert_same(view, stage_block(blk, _ATTR_NEEDED + ["sattr.int32"], SHARD,
                                   cache=False))
    assert is_staged(blk, _ATTR_NEEDED + ["sattr.int32"], SHARD)


def test_slot_major_columns_come_back_from_the_pool(monkeypatch):
    """Evicted slot-major columns, the empty and the filled overflow
    owners among them, are demoted to the host pool under the keys they
    were staged by and restaged from it as the arrays they were."""
    monkeypatch.setenv("TEMPO_CHUNK_CACHE", "1")
    monkeypatch.setenv("TEMPO_CHUNK_CACHE_MIN_REUSE", "1")
    for blk in (_open_uniform(monkeypatch), _open_skewed()):
        # asking about a slice nobody staged does no IO
        bytes_read = blk.pack.bytes_read
        assert not is_staged(blk, _ATTR_NEEDED, SHARD)
        assert blk.pack.bytes_read == bytes_read
        view = stage_block(blk, _ATTR_NEEDED, SHARD)
        d0 = chunkpool.stats()
        stage.set_staged_cache_budget(1)
        stage.set_staged_cache_budget(4 << 30)
        assert not is_staged(blk, _ATTR_NEEDED, SHARD)
        gone = [k for k in stage.column_keys(blk, _ATTR_NEEDED, SHARD).values()
                if k not in blk._staged_cache]  # an empty column costs nothing
        assert len(gone) >= len(view.cols) - 1
        assert chunkpool.stats()["demotions"] - d0["demotions"] >= len(gone)
        reads = _record_reads(monkeypatch)
        again = stage_block(blk, _ATTR_NEEDED, SHARD)
        assert not reads and chunkpool.stats()["hits"] - d0["hits"] == len(gone)
        _assert_same(again, view)


def _assemble_spans(body) -> list[dict]:
    """The attributes of every `stage:assemble` span `body` records in a
    self-trace, in order."""
    from tempo_tpu.services.selftrace import SelfTracer

    shipped: list = []
    tracer = SelfTracer(push=lambda tenant, rs: shipped.append(rs))
    with tracer.trace("frontend.search", {"tenant": TENANT}) as t:
        tok = TEL.set_active_trace(t)
        try:
            body()
        finally:
            TEL.reset_active_trace(tok)
    tracer.flush()
    spans = [sp for rs_list in shipped for rs in rs_list
             for ss in rs.scope_spans for sp in ss.spans
             if sp.name == "stage:assemble"]
    return [dict(sp.attrs) for sp in sorted(spans, key=lambda sp: sp.start_unix_nano)]


def test_assemble_span_says_what_it_placed():
    """A miss's `stage:assemble` span carries the generic-attribute rows
    it placed, their planes, the overflow rows, the columns and bytes it
    padded and which path placed them; a miss that stages no attribute
    column places none; a full hit's span carries rows=0 and nothing else
    of them."""
    from tempo_tpu import native

    blk = _open_skewed()
    _, owners, _ = _slice_rows(blk, SHARD)
    views = []
    miss, later, hit = _assemble_spans(lambda: views.extend([
        stage_block(blk, _ATTR_NEEDED, SHARD),
        stage_block(blk, _ATTR_NEEDED + ["span.dur_us"], SHARD),
        stage_block(blk, _ATTR_NEEDED, SHARD)]))
    view = views[0]
    n_over = int(np.count_nonzero(np.asarray(view.cols["sattr.over"]) < view.n_spans_b))
    k = (view.cols["sattr.key_id"].shape[0] - view.cols["sattr.over"].shape[0]) // view.n_spans_b
    assert n_over > 0 and k > 0
    assert (miss["rows"], miss["planes"], miss["overflow_rows"]) == (owners.shape[0], k, n_over)
    assert miss["path"] == ("native" if native.available() else "numpy")
    assert miss["columns"] == len(view.cols)
    assert miss["bytes"] == sum(int(a.nbytes) for a in view.cols.values())
    assert (later["rows"], later["planes"], later["overflow_rows"], later["columns"]) == (0, 0, 0, 1)
    assert later["bytes"] == views[1].cols["span.dur_us"].nbytes and later["path"] == miss["path"]
    assert hit["rows"] == 0 and not {"planes", "overflow_rows", "path", "bytes"} & set(hit)
