"""db/route decides for every block job; these tests hold it to what the
four call sites decided before it existed, and to planning a job once.

TABLE was generated at the parent commit (0c17b01, before db/route): the
same drivers as below -- search_block, search_blocks_fused, the batch
window's probe, metrics_block -- over the same block in each state, and
the rows the routing counter gained (one row differs since PR 34, which
priced a host-cached block of a fused group: `tres_cached_tiny_rtt/fused`
was `host_scan_cheaper` while a cached block cost nothing, and no scan is
cheaper than a round trip of 1e-9 ms). A key is "<state>/<caller>"; a value
lists the (layer, engine, reason) rows, then what else the caller did:
"accept" (the probe took the job), "returns_none" (the fused engine gave
the group back), "touches+1" (blk.search_touches moved). Counts only: CPU,
one tiny block.
"""

from __future__ import annotations

import tempfile
from types import SimpleNamespace

import pytest

from tempo_tpu.backend import MemBackend
from tempo_tpu.block import build_block_from_traces, open_block
from tempo_tpu.db import metrics_exec as mx
from tempo_tpu.db import route as route_mod
from tempo_tpu.db.batchexec import batched_search_block_many
from tempo_tpu.db.search import (
    SearchRequest,
    _plan_for_block,
    search_block,
    search_blocks_fused,
)
from tempo_tpu.ops.stage import stage_block
from tempo_tpu.util.kerneltel import TEL
from tempo_tpu.util.testdata import make_traces

TENANT = "t"
SHARD = [1, 2]
P = 3  # promote_touches of the readers below

TABLE = {
 "cold/batch": [["search_batch", "fallback", "cold_block"]],
 "cold/fused": [["search_fused", "host", "cold_block"], ["touches+1"]],
 "cold/metrics": [["metrics", "host", "cold_block"]],
 "cold/single": [["search_block", "host", "cold_block"]],
 "forced_device_cold/metrics": [["metrics", "device", "forced"]],
 "forced_device_cold/single": [["search_block", "device", "forced"]],
 "forced_host_pinned/metrics": [["metrics", "host", "forced"]],
 "forced_host_pinned/single": [["search_block", "host", "forced"]],
 "host_scan_cheaper/batch": [["search_batch", "fallback", "cold_block"]],
 "host_scan_cheaper/fused": [["search_fused", "host", "host_scan_cheaper"], ["touches+1"]],
 "host_scan_cheaper/metrics": [["metrics", "device", "hot_block"]],
 "host_scan_cheaper/single": [["search_block", "host", "host_scan_cheaper"]],
 "host_scan_cheaper_tres/batch": [["search_batch", "fallback", "tres_host"]],
 "host_scan_cheaper_tres/fused": [["search_fused", "host", "host_scan_cheaper"], ["touches+1"]],
 "host_scan_cheaper_tres/single": [["search_block", "host", "host_scan_cheaper"]],
 "metrics_exact_forced/metrics": [["metrics", "exact", "forced"]],
 "metrics_i32_range/metrics": [["metrics", "host", "i32_range"]],
 "metrics_i32_range_forced_device/metrics": [["metrics", "host", "i32_range"]],
 "metrics_i32_range_forced_host/metrics": [["metrics", "host", "forced"]],
 "metrics_unplannable_by/metrics": [["metrics", "exact", "unplannable_by"]],
 "over_stream_threshold/batch": [["search_batch", "fallback", "stream_scan"]],
 "over_stream_threshold/fused": [["search_fused", "host", "cold_block"], ["touches+1"]],
 "over_stream_threshold/metrics": [["metrics", "device", "hot_block"]],
 "over_stream_threshold/single": [["search_block", "device", "hot_block"], ["stream", "device", "chunked"]],
 "pinned/batch": [["search_batch", "fallback", "cold_block"]],
 "pinned/fused": [["search_fused", "host", "cold_block"], ["touches+1"]],
 "pinned/metrics": [["metrics", "device", "hot_block"]],
 "pinned/single": [["search_block", "device", "hot_block"]],
 "rtt_between_estimates/batch": [["search_batch", "fallback", "cold_block"]],
 "rtt_between_estimates/fused": [["search_fused", "host", "host_scan_cheaper"], ["touches+1"]],
 "rtt_between_estimates/metrics": [["metrics", "device", "hot_block"]],
 "rtt_between_estimates/single": [["search_block", "device", "hot_block"]],
 "shard_cold/batch": [["search_batch", "fallback", "cold_block"]],
 "shard_cold/single": [["search_block", "host", "cold_block"]],
 "shard_pinned/batch": [["accept"], ["touches+1"]],
 "shard_pinned/single": [["search_block", "device", "hot_block"]],
 "staged_other_cols/batch": [["search_batch", "fallback", "cold_block"]],
 "staged_other_cols/fused": [["search_fused", "host", "cold_block"], ["touches+1"]],
 "staged_other_cols/metrics": [["metrics", "device", "hot_block"]],
 "staged_other_cols/single": [["search_block", "device", "hot_block"]],
 "staged_over_budget/batch": [["search_batch", "fallback", "stream_scan"]],
 "staged_over_budget/fused": [["search_fused", "fallback", "pre_io_budget"], ["returns_none"], ["touches+1"]],
 "staged_over_budget/metrics": [["metrics", "device", "hot_block"]],
 "staged_over_budget/single": [["search_block", "device", "hot_block"], ["stream", "device", "chunked"]],
 "staged_request_cols/batch": [["accept"], ["touches+1"]],
 "staged_request_cols/fused": [["search_fused", "device", "staged_hit"], ["touches+1"]],
 "staged_request_cols/metrics": [["metrics", "device", "hot_block"]],
 "staged_request_cols/single": [["search_block", "device", "hot_block"]],
 "struct_on_shard/batch": [["search_batch", "fallback", "ineligible_plan"]],
 "struct_on_shard/single": [["search_block", "device", "hot_block"]],
 "touched_P-1/batch": [["accept"], ["touches+1"]],
 "touched_P-1/fused": [["search_fused", "device", "promoted"], ["touches+1"]],
 "touched_P-1/single": [["search_block", "host", "cold_block"]],
 "touched_P-2/batch": [["search_batch", "fallback", "cold_block"]],
 "touched_P-2/fused": [["search_fused", "host", "cold_block"], ["touches+1"]],
 "touched_P-2/single": [["search_block", "host", "cold_block"]],
 "touched_P/batch": [["accept"], ["touches+1"]],
 "touched_P/fused": [["search_fused", "device", "promoted"], ["touches+1"]],
 "touched_P/single": [["search_block", "host", "cold_block"]],
 "tres_cached_tiny_rtt/batch": [["search_batch", "fallback", "tres_host"]],
 "tres_cached_tiny_rtt/fused": [["search_fused", "host", "cold_block"], ["touches+1"]],  # PR 34, below
 "tres_cached_tiny_rtt/single": [["search_block", "device", "hot_block"]],
 "tres_on_shard/batch": [["search_batch", "fallback", "tres_host"]],
 "tres_on_shard/single": [["search_block", "device", "hot_block"]],
 "tres_plan/batch": [["search_batch", "fallback", "tres_host"]],
 "tres_plan/fused": [["search_fused", "host", "cold_block"], ["touches+1"]],
 "tres_plan/single": [["search_block", "device", "hot_block"]],
 "unlowerable_plan/batch": [["search_batch", "fallback", "ineligible_plan"]],
 "unlowerable_plan/fused": [["search_fused", "host", "cold_block"], ["touches+1"]],
 "unlowerable_plan/single": [["search_block", "device", "hot_block"]],
}

REQS = {
    "plain": SearchRequest(query="{ duration > 900ms }"),
    "tres": SearchRequest(tags={"service.name": "db"}),
    "unlowerable": SearchRequest(query='{ span.component = "grpc" }'),
    "struct": SearchRequest(
        query='{ span.component = "grpc" } >> { duration > 500ms }'),
}
MQ = '{ resource.service.name = "db" } | rate()'


def _st(pinned=False, touches=0, req="plain", groups=None, rtt=-1.0,
        budget0=False, staged=None, mode="auto", step=1000, mq=MQ):
    return dict(pinned=pinned, touches=touches, req=req, groups=groups,
                rtt=rtt, budget0=budget0, staged=staged, mode=mode,
                step=step, mq=mq)


# the block and the link in each state; the link's round trip is -1 ms
# (no host scan is cheaper) unless the state is about it
STATES = {
    "cold": _st(),
    "pinned": _st(pinned=True),
    "staged_request_cols": _st(staged="request"),
    "staged_other_cols": _st(staged="other"),
    "touched_P-2": _st(touches=P - 2),
    "touched_P-1": _st(touches=P - 1),
    "touched_P": _st(touches=P),
    "over_stream_threshold": _st(pinned=True, budget0=True),
    "staged_over_budget": _st(staged="request", budget0=True),
    "tres_plan": _st(pinned=True, req="tres"),
    "unlowerable_plan": _st(pinned=True, req="unlowerable"),
    "struct_on_shard": _st(pinned=True, req="struct", groups=SHARD),
    "shard_pinned": _st(pinned=True, groups=SHARD),
    "shard_cold": _st(groups=SHARD),
    "tres_on_shard": _st(pinned=True, req="tres", groups=SHARD),
    "host_scan_cheaper": _st(pinned=True, rtt=1e9),
    "host_scan_cheaper_tres": _st(pinned=True, req="tres", rtt=1e9),
    # the two places where the fused engine's host estimate differs from
    # search_block's (route.scan_bytes_est, ROADMAP C3)
    "tres_cached_tiny_rtt": _st(pinned=True, req="tres", rtt=1e-9,
                                staged="host_cached"),
    "rtt_between_estimates": _st(pinned=True, rtt="between"),
    "forced_device_cold": _st(mode="device"),
    "forced_host_pinned": _st(pinned=True, mode="host"),
    "metrics_exact_forced": _st(pinned=True, mode="exact"),
    "metrics_i32_range": _st(pinned=True, step=2**31),
    "metrics_i32_range_forced_device": _st(mode="device", step=2**31),
    "metrics_i32_range_forced_host": _st(mode="host", step=2**31),
    "metrics_unplannable_by": _st(pinned=True,
                                  mq="{ true } | rate() by (parent)"),
}
LAYERS = {"single": ("search_block", "stream"), "fused": ("search_fused",),
          "batch": ("search_batch",), "metrics": ("metrics",)}


@pytest.fixture(scope="module")
def stored():
    backend = MemBackend()
    meta = build_block_from_traces(
        backend, TENANT, make_traces(120, seed=41, n_spans=10),
        row_group_spans=256)
    return backend, meta


def _reader(stored, s):
    backend, meta = stored
    blk = open_block(backend, TENANT, meta.block_id)
    blk.promote_touches = P
    if s["pinned"]:
        blk.device_pinned = True
    if s["touches"]:
        blk.search_touches = s["touches"]
    return blk


def _stage_columns(blk, req):
    return route_mod.stage_columns(_plan_for_block(blk, req))


def _metrics_columns(blk):
    from tempo_tpu.ops.filter import required_columns
    from tempo_tpu.traceql.plan import plan_metrics_filter

    p = plan_metrics_filter(mx.parse_metrics_query(MQ), blk.dictionary)
    return [n for n in required_columns(p.conds)
            if n != "trace.span_off"] + ["span.start_ms"]


def _rows(layers):
    return {(r["layer"], r["engine"], r["reason"]): r["count"]
            for r in TEL.snapshot()["routing"] if r["layer"] in layers}


def _spy(monkeypatch, name, seen):
    orig = getattr(route_mod, name)

    def spy(*a, **kw):
        got = orig(*a, **kw)
        seen.append(got)
        return got

    monkeypatch.setattr(route_mod, name, spy)


@pytest.mark.parametrize("case", sorted(TABLE))
def test_decision_equals_the_parents(case, stored, monkeypatch):
    state, caller = case.split("/")
    s = STATES[state]
    blk = _reader(stored, s)
    req, groups = REQS[s["req"]], s["groups"]
    base = _stage_columns(blk, req)
    rtt = s["rtt"]
    if rtt == "between":
        # between the fused estimate (k span columns the host reads) and
        # search_block's (k + 1: span.trace_sid counted) at the seed rate
        k = sum(1 for n in base if n.startswith(("span.", "sattr."))
                and n != "span.trace_sid")
        rtt = route_mod.job_rows(blk, None) * 4 * (k + 0.5) / 1.5e9 * 1e3
    monkeypatch.setattr(route_mod, "link_rtt_ms", lambda: rtt)
    monkeypatch.setattr(route_mod, "_HOST_RATE_BPS", 1.5e9)
    if s["budget0"]:
        monkeypatch.setattr(route_mod, "JOB_STAGE_BUDGET_BYTES", 0)
    if s["staged"] == "request":  # as every caller spells its columns
        stage_block(blk, base + ["trace.start_ms"], groups)
        stage_block(blk, base + ["trace@gkey_s"])
        stage_block(blk, _metrics_columns(blk))
    elif s["staged"] == "other":
        stage_block(blk, _stage_columns(blk, REQS["unlowerable"])
                    + ["trace.start_ms"], groups)
    elif s["staged"] == "host_cached":
        search_block(blk, req, mode="host")  # fills the array cache only

    routes: list = []
    for fn in ("route_search", "route_fused", "route_batch",
               "route_metrics_exact", "route_metrics"):
        _spy(monkeypatch, fn, routes)
    before = _rows(LAYERS[caller])
    extra = []
    if caller == "single":
        search_block(blk, req, groups_range=groups, mode=s["mode"])
    elif caller == "fused":
        if search_blocks_fused([blk], req) is None:
            extra.append(["returns_none"])
    elif caller == "batch":
        window = SimpleNamespace(enabled=True,
                                 submit_many=lambda key, items: ["taken"])
        if batched_search_block_many(window, [(blk, req, groups)]) == ["taken"]:
            extra.append(["accept"])
    else:
        q = mx.parse_metrics_query(s["mq"])
        step = s["step"]
        t0 = blk.meta.start_time_unix_nano // 1_000_000 // step * step
        mreq = mx.MetricsRequest(s["mq"], t0, t0 + 2 * step, step)
        mx.metrics_block(
            blk, q, mreq,
            mx.MetricsResponse(q.agg.fn, t0, step, mreq.n_buckets),
            mode=s["mode"])
    after = _rows(LAYERS[caller])
    got = sorted(list(k) for k in after if after[k] - before.get(k, 0) == 1)
    assert sum(after.values()) - sum(before.values()) == len(got)  # one each
    if getattr(blk, "search_touches", 0) - s["touches"] == 1:
        extra.append(["touches+1"])
    assert got + extra == TABLE[case]

    # the record the caller was handed says what the counter says
    decided = [r for r in routes if r is not None]
    flat = [r for d in decided for r in (d if isinstance(d, list) else [d])]
    said = [(r.engine, r.reason) for r in flat]
    counted = [tuple(row[1:]) for row in got if row[0] != "stream"]
    if caller == "single":
        streamed = ["stream", "device", "chunked"] in got
        assert [r.engine for r in flat] == ["stream" if streamed else counted[0][0]]
        assert flat[0].reason == counted[0][1]
    elif ["accept"] in extra:
        assert flat[0].lowered is not None and not counted
    elif ["returns_none"] in extra:
        assert routes == [None]
    else:
        assert said == counted


# ------------------------------------------- a cached block is not free


@pytest.mark.parametrize("rtt_share,want", [
    (0.5, ("device", "staged_hit")),  # the scan costs two round trips
    (2.0, ("host", "host_scan_cheaper")),  # it costs half of one
], ids=["scan_dearer_than_rtt", "scan_cheaper_than_rtt"])
@pytest.mark.parametrize("req", ["plain", "tres"])
def test_a_staged_block_in_the_host_cache_is_priced_not_free(
        stored, monkeypatch, req, rtt_share, want):
    """A block of a fused group whose columns are staged AND sit in the
    host array cache: the parent estimated its host scan at 0 bytes, so one
    host scan sent it to the numpy engine for good (ROADMAP C3 ii). It is
    priced at its bytes over the memory-speed rate, and stays on the device
    while that is more than a link round trip."""
    blk = _reader(stored, _st())
    r = REQS[req]
    search_block(blk, r, mode="host")  # fills the array cache
    stage_block(blk, _stage_columns(blk, r) + ["trace@gkey_s"])
    p = _plan_for_block(blk, r)
    cols, tres = route_mod.host_plan(blk, p, None)
    assert tres == (req == "tres")
    assert all(blk.pack.has_cached_array(n) for n in cols if blk.pack.has(n))
    ms = route_mod.fused_host_ms(blk, p)
    assert ms > 0
    monkeypatch.setattr(route_mod, "link_rtt_ms", lambda: ms * rtt_share)
    [route] = route_mod.route_fused([(blk, p)])
    assert (route.engine, route.reason) == want


def test_a_cold_block_is_priced_at_the_cold_rate(stored, monkeypatch):
    """The same bytes cost more while they have to be read: the cold-scan
    EMA's rate, not the array cache's."""
    blk = _reader(stored, _st())
    p = _plan_for_block(blk, REQS["plain"])
    monkeypatch.setattr(route_mod, "_HOST_RATE_BPS", 1.0e9)
    cold = route_mod.fused_host_ms(blk, p)
    search_block(blk, REQS["plain"], mode="host")
    warm = route_mod.fused_host_ms(blk, p)
    assert cold == pytest.approx(
        warm * route_mod._HOST_CACHED_RATE_BPS / 1.0e9)


# ---------------------------------------------------------- planned once


@pytest.fixture(scope="module")
def db():
    from tempo_tpu.db.tempodb import TempoDB, TempoDBConfig

    db = TempoDB(TempoDBConfig(wal_path=tempfile.mkdtemp(prefix="tempo-route-wal"),
                               row_group_spans=256), backend=MemBackend())
    db.write_block(TENANT, make_traces(120, seed=41, n_spans=10))
    yield db
    db.close()


def _plan_compiles() -> int:
    return TEL.snapshot()["stages"].get("plan:compile", {}).get("count", 0)


@pytest.mark.parametrize("shape,probe,plans", [
    ("unlowerable", "ineligible_plan", 1),
    ("tres", "tres_host", 1),
    ("plain", None, 1),  # the window takes it; a lone item runs search_block
    ("struct", "ineligible_plan", 2),  # + the replan without the struct node
])
@pytest.mark.parametrize("entry", ["shard", "shard_multi", "block_set"])
def test_a_job_is_planned_once(db, entry, shape, probe, plans, monkeypatch):
    """Through TempoDB's job entries a one-block job records one
    plan:compile (two for a struct query on a shard) whether the batch
    window refuses it or takes it; the parent recorded 2 and 4."""
    monkeypatch.setattr(route_mod, "link_rtt_ms", lambda: -1.0)
    meta = db.blocklist.metas(TENANT)[0]
    db.open_block(meta).search_touches = P  # worth staging, whatever ran before
    req = REQS[shape]
    if entry == "block_set" and shape == "struct":
        plans = 1  # the whole block keeps its struct node
    p0 = _plan_compiles()
    before = _rows(("search_batch", "search_block", "search_fused"))
    if entry == "shard":
        resp = db.search_block_shard(TENANT, meta, req, SHARD)
    elif entry == "shard_multi":
        resp, = db.search_block_shard_multi([(TENANT, meta, req, SHARD)])
    else:
        resp = db.search_blocks(TENANT, [meta], req)
    assert _plan_compiles() - p0 == plans
    after = _rows(("search_batch", "search_block", "search_fused"))
    gained = {k: after[k] - before.get(k, 0) for k in after
              if after[k] - before.get(k, 0)}
    refusals = {k: n for k, n in gained.items() if k[0] == "search_batch"}
    assert refusals == ({("search_batch", "fallback", probe): 1} if probe else {})
    # the engine under the window's refusal: search_block for a shard (or
    # an accepted lone item), the fused engine for a block set
    layer = ("search_fused" if entry == "block_set" and probe
             else "search_block")
    assert [k[0] for k in gained if k[0] != "search_batch"] == [layer]
    ref = search_block(db.open_block(meta), req,
                       groups_range=None if entry == "block_set" else SHARD,
                       mode="host")
    # the newest `limit`, to the second: the fused engine selects on a
    # key of that grain, and engines break ties differently
    assert ([t.start_time_unix_nano // 10**9 for t in resp.traces]
            == [t.start_time_unix_nano // 10**9 for t in ref.traces]) and resp.traces
