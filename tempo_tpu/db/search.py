"""Search execution: tag / TraceQL queries -> filter plan -> results.

The per-block pipeline (analog of vparquet/block_search.go:78-116 +
block_traceql.go Fetch): the traceql planner resolves strings through
the block dictionary (a miss prunes the whole block -- the dictionary IS
the page-dictionary pre-filter of parquetquery predicates.go:38-89) and
emits a trace-level condition tree; the filter evaluates it over the
block's columns; the top `limit` candidates BY TRACE START TIME are
selected before any host materialization (ops/select.py), and only
those are settled exactly: by traceql.hosteval when a condition of the
query is conservative on the device (_verify_candidates), and always by
_candidates for the request's time window and duration bounds.

Two execution engines share the plan + verify contract:
  - device (ops/filter + ops/stage): staged padded columns, jit kernel,
    on-device top-k -- ONE small fetch per query. The production path
    for hot (cached/pinned) blocks; cost is O(limit), not O(matches).
  - host (ops/hostfilter): vectorized numpy over raw columns, for cold
    one-shot scans where upload + dispatch round trips exceed the scan.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np

_N_CPU = os.cpu_count() or 2

from ..block import schema as S
from ..block.reader import BackendBlock
from ..ops.filter import Operands, eval_block, required_columns
from ..ops.hostfilter import eval_block_host
from ..ops.device import bucket
from ..ops.select import (
    group_rung,
    k_bucket,
    select_topk_device,
    select_topk_device_multi,
    select_topk_host,
    select_topk_host_multi,
)
from ..ops.stage import stage_block
from ..traceql.plan import plan_search_request
from ..util.distinct import DistinctStringCollector
from . import route

DEFAULT_LIMIT = 20

_INTRINSIC_NAME = "name"
_WELL_KNOWN_RES = {
    "service.name": "res.service_id",
    "k8s.cluster.name": "res.cluster_id",
    "k8s.namespace.name": "res.namespace_id",
    "k8s.pod.name": "res.pod_id",
    "k8s.container.name": "res.container_id",
}

# column IO for the host evaluation path (reads overlap across columns;
# shared across queries -- each read is one ranged GET + zstd decode)
_host_io_pool = ThreadPoolExecutor(max_workers=8, thread_name_prefix="search-io")


@dataclass
class SearchRequest:
    tags: dict[str, str] = field(default_factory=dict)
    min_duration_ms: int = 0
    max_duration_ms: int = 0
    start: int = 0  # unix seconds, 0 = unbounded
    end: int = 0
    limit: int = DEFAULT_LIMIT
    query: str = ""  # TraceQL spanset filter


@dataclass
class SearchResult:
    trace_id: str  # hex
    root_service_name: str
    root_trace_name: str
    start_time_unix_nano: int
    duration_ms: int
    matched_spans: int = 0

    def to_dict(self) -> dict:
        return {
            "traceID": self.trace_id,
            "rootServiceName": self.root_service_name,
            "rootTraceName": self.root_trace_name,
            "startTimeUnixNano": str(self.start_time_unix_nano),
            "durationMs": self.duration_ms,
        }


@dataclass
class SearchResponse:
    traces: list[SearchResult] = field(default_factory=list)
    inspected_bytes: int = 0
    inspected_spans: int = 0

    def merge(self, other: "SearchResponse", limit: int) -> None:
        seen = {t.trace_id for t in self.traces}
        for t in other.traces:
            if t.trace_id not in seen and len(self.traces) < limit:
                self.traces.append(t)
                seen.add(t.trace_id)
        self.inspected_bytes += other.inspected_bytes
        self.inspected_spans += other.inspected_spans


# ---- wire forms (the internal-API serialization both the remote job
# plane and the ingester client speak)


def request_to_dict(req: SearchRequest) -> dict:
    return {
        "tags": req.tags,
        "query": req.query,
        "min_duration_ms": req.min_duration_ms,
        "max_duration_ms": req.max_duration_ms,
        "start": req.start,
        "end": req.end,
        "limit": req.limit,
    }


def request_from_dict(d: dict) -> SearchRequest:
    return SearchRequest(
        tags=d.get("tags", {}),
        query=d.get("query", ""),
        min_duration_ms=d.get("min_duration_ms", 0),
        max_duration_ms=d.get("max_duration_ms", 0),
        start=d.get("start", 0),
        end=d.get("end", 0),
        limit=d.get("limit", DEFAULT_LIMIT),
    )


def response_to_dict(resp: SearchResponse) -> dict:
    return {
        "traces": [
            {**t.to_dict(), "matchedSpans": t.matched_spans} for t in resp.traces
        ],
        "inspectedBytes": resp.inspected_bytes,
        "inspectedSpans": resp.inspected_spans,
    }


def response_from_dict(d: dict) -> SearchResponse:
    resp = SearchResponse()
    resp.inspected_bytes = d.get("inspectedBytes", 0)
    resp.inspected_spans = d.get("inspectedSpans", 0)
    for t in d.get("traces", []):
        resp.traces.append(
            SearchResult(
                trace_id=t["traceID"],
                root_service_name=t.get("rootServiceName", ""),
                root_trace_name=t.get("rootTraceName", ""),
                start_time_unix_nano=int(t.get("startTimeUnixNano", "0")),
                duration_ms=t.get("durationMs", 0),
                matched_spans=t.get("matchedSpans", 0),
            )
        )
    return resp


def _plan_for_block(blk: BackendBlock, req: SearchRequest, allow_struct: bool = True):
    """allow_struct=False is the replan of a struct query for a slice of
    the span axis (row-group shard, streamed chunk): the relation folds to
    a trace-AND that hosteval settles, and the plan says so
    (verify_reason) for the routing counter."""
    start_rel = None
    if req.start or req.end:
        base_ms = blk.meta.start_time_unix_nano // 1_000_000
        lo = (req.start * 1000 - base_ms - 1) if req.start else -(2**31)
        hi = (req.end * 1000 - base_ms + 1) if req.end else 2**31 - 1
        start_rel = (
            int(np.clip(lo, -(2**31), 2**31 - 1)),
            int(np.clip(hi, -(2**31), 2**31 - 1)),
        )
    # struct nodes need the block to carry the parent-row column
    # (pre-upgrade blocks don't)
    on_shard = not allow_struct
    allow_struct = allow_struct and blk.pack.has("span.parent_idx")
    from ..util.kerneltel import TEL

    with TEL.stage("plan:compile", block=blk.meta.block_id[:8]):  # parse + plan
        planned = plan_search_request(
            blk.dictionary,
            req.tags,
            query=req.query,
            min_duration_ms=req.min_duration_ms,
            max_duration_ms=req.max_duration_ms,
            start_rel_ms=start_rel,
            allow_struct=allow_struct,
        )
    if on_shard and planned.needs_verify:
        planned.verify_reason = "struct_on_shard"
    return planned


def plan_job(blk: BackendBlock, req: SearchRequest, groups_range=None):
    """The plan one block job runs under, made once and handed on; None
    when the answer is empty without a scan (out of the request's
    window, or pruned by the dictionary)."""
    if not blk.meta.overlaps_time(req.start, req.end):
        return None
    planned = _plan_for_block(blk, req)
    if not planned.prune and groups_range is not None and planned.has_struct:
        # struct nodes resolve parent links by GLOBAL row index; a
        # row-group slice would sever links across group boundaries, so
        # shards take the conservative plan (trace-AND + host verify),
        # whose fold may prove "no match"
        planned = _plan_for_block(blk, req, allow_struct=False)
    return None if planned.prune else planned


def _live_plans(blocks: list[BackendBlock], req: SearchRequest, pool=None):
    """(block, plan) of the blocks a multi-block engine has to scan: in
    the request's window, not pruned by their dictionary. Planning pulls
    each block's dictionary + footer: the pool overlaps the IO."""
    in_range = [b for b in blocks if b.meta.overlaps_time(req.start, req.end)]
    plans = (pool.map(lambda b: _plan_for_block(b, req), in_range)
             if pool is not None else (_plan_for_block(b, req) for b in in_range))
    return [(blk, p) for blk, p in zip(in_range, plans) if not p.prune]


# --------------------------------------------------- candidate selection


def _start_key_host(blk: BackendBlock) -> np.ndarray:
    """trace.start_ms column (the top-k selection key), cached on the
    immutable block."""
    key = getattr(blk, "_start_key_host", None)
    if key is None:
        key = blk._start_key_host = blk.pack.read("trace.start_ms")
    return key


def _start_key_dev(blk: BackendBlock, nb: int):
    key = getattr(blk, "_start_key_dev", None)
    if key is None or key.shape[0] != nb:
        import jax.numpy as jnp

        from ..ops.device import pad_rows

        key = jnp.asarray(pad_rows(_start_key_host(blk), nb, np.int32(0)))
        blk._start_key_dev = key
    return key


def _route_verify(req: SearchRequest, planned) -> bool:
    """Who settles one block's candidates: True when a condition OF THE
    QUERY is conservative (PlannedQuery.needs_verify) and hosteval must
    re-check them, False when the device proved every query condition
    exactly or there is no query to evaluate. Recorded as the routing
    decision ("verify", "hosteval" | "skip", reason), one per collected
    block. The request's window and duration bounds are never a reason
    to verify: _candidates owns their exact re-check."""
    from ..util.kerneltel import TEL

    if not req.query:
        engine, reason = "skip", "no_query"
    elif not planned.needs_verify:
        engine, reason = "skip", "exact_plan"
    else:
        engine, reason = "hosteval", planned.verify_reason or "lossy_cond"
    TEL.record_routing("verify", engine, reason)
    return engine == "hosteval"


def _verify_candidates(blk: BackendBlock, req: SearchRequest, sids, verify: bool):
    """Exact host re-check (traceql.hosteval) of TraceQL candidates when
    a condition of the query was conservative on the device (verify:
    what _route_verify returned). Bounded: callers pass at most the
    escalation k. The request's time window and duration bounds are NOT
    settled here -- hosteval does not evaluate them; _candidates does."""
    if not (verify and len(sids)):
        return sids
    import time as _time

    from ..traceql.hosteval import trace_matches
    from ..traceql.parser import parse
    from ..util.kerneltel import TEL

    t0_wall = _time.time()
    q = parse(req.query)
    traces = blk.materialize_traces([int(s) for s in sids])  # rows:materialize
    with TEL.stage("verify:eval", rows=int(len(sids))):
        out = np.asarray(
            [s for s, tr in zip(sids, traces) if tr is not None and trace_matches(q, tr)],
            dtype=np.int64,
        )
    # timeline + cost: the exact-verify leg (conservative device mask ->
    # host re-check) of this block's evaluation. A retroactive LEAF: the
    # two stages inside it are its siblings, so its self time stays the
    # whole leg (benchmarks' verify_ms_per_search)
    TEL.child_span("verify", t0_wall, _time.time(),
                   {"block": blk.meta.block_id[:8],
                    "rows": int(len(sids)), "kept": int(out.shape[0])})
    TEL.add_query_cost("rows_verified", int(len(sids)))
    return out


def _candidates(
    blk: BackendBlock, req: SearchRequest, sids: list[int], counts: dict[int, int]
) -> list[tuple]:
    """THE exact re-check of the request's time window and duration
    bounds + LIGHTWEIGHT candidate records (start_ns, trace_id hex,
    dur_ms, matched, blk, sid): everything the global merge sorts/dedupes
    on, with the dictionary lookups + SearchResult construction deferred
    to the winners (_materialize). O(len(sids)) -- callers cap it at the
    escalation k, never the full match count.

    Owner of the window: the device compares trace.start_ms against
    bounds widened by 1 ms (plan_search_request) and neither the plan's
    needs_verify nor hosteval covers that, so EVERY path that turns
    selected sids into results must come through here (the collects
    below do; tests/test_search_window.py drives each engine with a
    window that cuts a block). A row dropped here leaves the collect
    short of the limit, and the collect widens k."""
    ti = blk.search_index
    if not len(sids):
        return []
    # vectorized over the candidate set (up to the escalation k): the
    # per-sid scalar loop cost more than the selection it followed
    sa = np.asarray(sids, dtype=np.int64)
    start_ns = ti["trace.start_ns"][sa].astype(np.int64)
    end_ns = ti["trace.end_ns"][sa].astype(np.int64)
    dur_ms = np.maximum(0, (end_ns - start_ns) // 1_000_000)
    keep = np.ones(sa.shape[0], dtype=bool)
    if req.min_duration_ms:
        keep &= dur_ms >= req.min_duration_ms
    if req.max_duration_ms:
        keep &= dur_ms <= req.max_duration_ms
    if req.start:
        keep &= start_ns >= req.start * 1_000_000_000
    if req.end:
        keep &= start_ns <= req.end * 1_000_000_000
    ka = sa[keep]
    # one hex() over the packed id rows, sliced per 16-byte id
    blob = np.ascontiguousarray(ti["trace.id"][ka]).tobytes().hex()
    ids_hex = [blob[i * 32 : (i + 1) * 32] for i in range(ka.shape[0])]
    return [
        (s, h, d, int(counts.get(sid, 0)), blk, sid)
        for s, h, d, sid in zip(start_ns[keep].tolist(), ids_hex,
                                dur_ms[keep].tolist(), ka.tolist())
    ]


def _materialize(cand: tuple) -> SearchResult:
    """One candidate record -> wire SearchResult (the deferred
    dictionary/materialization half of _candidates)."""
    start_ns, tid_hex, dur_ms, cnt, blk, sid = cand
    ti = blk.search_index
    d = blk.dictionary
    return SearchResult(
        trace_id=tid_hex,
        root_service_name=d.string(int(ti["trace.root_service_id"][sid])),
        root_trace_name=d.string(int(ti["trace.root_name_id"][sid])),
        start_time_unix_nano=start_ns,
        duration_ms=dur_ms,
        matched_spans=cnt,
    )




def _collect_topk(blk: BackendBlock, req: SearchRequest, planned,
                  selector, limit: int, materialize: bool = True):
    """Escalating top-k collect: select k candidates (newest first),
    settle them exactly -- hosteval when the plan says a query condition
    is conservative (_route_verify), then always _candidates for the
    window and duration bounds -- and only widen k when either rejected
    enough to fall short of the limit. selector(k) -> (sids, counts,
    n_match). materialize=False returns candidate records (_candidates)
    for a caller doing its own global merge -- the fused engine
    materializes only the cross-block winners."""
    nt = blk.meta.total_traces
    if nt == 0:
        return []
    from ..util.kerneltel import TEL

    verify = _route_verify(req, planned)
    with TEL.stage("topk:collect", block=blk.meta.block_id[:8], limit=limit,
                   rounds=0) as st:
        k = min(k_bucket(max(2 * limit, 32)), nt)
        out: list = []
        seen: set[int] = set()
        while True:
            st.attrs["rounds"] += 1  # selects run: 1 + how often k escalated
            sids, cnts, n_match = selector(k)
            fresh = [(int(s), int(c)) for s, c in zip(sids, cnts) if int(s) not in seen]
            seen.update(s for s, _ in fresh)
            if fresh:
                ok = _verify_candidates(
                    blk, req, np.asarray([s for s, _ in fresh], dtype=np.int64), verify
                )
                okset = {int(s) for s in ok}
                out.extend(
                    _candidates(blk, req, [s for s, _ in fresh if s in okset], dict(fresh))
                )
            if len(out) >= limit or len(seen) >= n_match or k >= nt:
                return [_materialize(c) for c in out] if materialize else out
            k = min(k_bucket(k * 4), nt)


def collect_seeded(blk: BackendBlock, req: SearchRequest, planned, seed,
                   tm_row, counts_row, key_dev, limit: int):
    """_collect_topk with the FIRST selection pre-computed by the batch
    window's fused top-k (db/batchexec: the seed was sliced to exactly
    the k the collect loop asks for first); escalation falls back to
    per-query device selects on this query's mask row."""
    state = [seed]

    def selector(k):
        if state:
            return state.pop()
        return select_topk_device(tm_row, key_dev, counts_row, k)

    return _collect_topk(blk, req, planned, selector, limit)


# ---------------------------------------------------- per-block search


def _host_eval(blk: BackendBlock, p, operands, groups_range, plan=None):
    """Run the host engine under the chosen axis: returns
    (trace_mask, counts, cols_read). Covered spans are the caller's to
    report: tres mode still inspects every span's data (via its
    membership summary), so inspected_spans stays the span-axis count.
    plan: a precomputed route.host_plan result (callers that already
    built it for warm_columns pass it through)."""
    host_needed, tres = plan if plan is not None else route.host_plan(blk, p, groups_range)
    cols = _host_cols(blk, host_needed, groups_range)
    if tres:
        # evaluate the same condition tree over the tres axis: entries
        # play the role of spans (res conds LUT through tres.res), and
        # per-entry span counts weight the segment fold so matched-span
        # counts stay exact
        ecols = dict(cols)
        ecols["span.res_idx"] = cols["tres.res"]
        ecols["trace.span_off"] = cols["trace.tres_off"]
        ecols["@seg_weights"] = cols["tres.nspans"]
        tm, counts = eval_block_host(
            (p.tree, p.conds), ecols, operands,
            int(cols["tres.res"].shape[0]), blk.meta.total_traces,
        )
        return tm, counts, cols
    tm, counts = eval_block_host(
        (p.tree, p.conds), cols, operands, route.job_rows(blk, groups_range),
        blk.meta.total_traces
    )
    return tm, counts, cols


def _host_cols(blk: BackendBlock, needed: list[str], groups_range):
    """Raw (unpadded) host columns for the numpy evaluator; span/sattr
    axis columns cover only groups_range when given, with sattr owners
    rebased to the local span rows (same contract as ops/stage.py)."""
    pack = blk.pack
    span_ax = pack.axes.get(S.AX_SPAN)
    sliced = groups_range is not None and span_ax is not None and span_ax.n_groups > 0
    span_base = span_ax.offsets[groups_range[0]] if sliced and groups_range else 0

    def read(name):
        pref = name.split(".", 1)[0]
        if sliced and pref in ("span", "sattr"):
            return name, pack.read_groups(name, groups_range)
        return name, pack.read(name)

    wanted = [n for n in needed if not n.startswith("span@") and pack.has(n)]
    # warm blocks: every column is an array-cache hit, and pool dispatch
    # would cost more than the dict lookups it parallelizes. The check
    # races concurrent evictions (check-then-act): losing it only
    # degrades to serial re-reads of columns that were cached a moment
    # ago -- a cache already thrashing at that point.
    serial = all(pack.has_cached_array(n) for n in wanted) or (
        _N_CPU == 1 and not getattr(blk.backend, "is_remote", True)
    )
    if wanted and serial:
        cols = dict(read(n) for n in wanted)
    else:
        cols = dict(_host_io_pool.map(read, wanted))
    if "sattr.span" in cols and span_base:
        cols["sattr.span"] = cols["sattr.span"] - span_base
    if "trace.span_off" in cols and sliced:
        hi = span_ax.offsets[groups_range[-1] + 1] if groups_range else 0
        cols["trace.span_off"] = (
            np.clip(cols["trace.span_off"], span_base, hi) - span_base
        ).astype(np.int32)
    return cols


def search_block(
    blk: BackendBlock,
    req: SearchRequest,
    groups_range: list[int] | None = None,
    mode: str = "auto",
    planned=None,
) -> SearchResponse:
    """Search one block (optionally one row-group shard of it).

    mode: 'device' | 'host' | 'auto' (route.route_search picks).
    planned: the job's plan where the caller made it (plan_job)."""
    resp = SearchResponse()
    if planned is None:
        planned = plan_job(blk, req, groups_range)
        if planned is None:
            return resp
    limit = req.limit or DEFAULT_LIMIT
    operands = Operands.build(planned.rows, planned.tables or None)
    needed = route.stage_columns(planned)
    pack = blk.pack
    io0 = pack.bytes_read  # per-query IO delta (pack counts lifetime bytes)
    n_rows = route.job_rows(blk, groups_range)

    from ..util.kerneltel import TEL

    rt = route.route_search(blk, planned, groups_range, mode)
    use_device, reason = rt.engine != "host", rt.reason
    # per-block stage with kernel attrs: a slow query's flame view shows
    # which block ran where and whether it recompiled
    with TEL.stage("block:search", block=blk.meta.block_id[:8],
                   engine="device" if use_device else "host",
                   reason=reason) as st:
        compiles0 = TEL.totals()[0]  # delta covers every chunk of a streamed eval

        if use_device:
            if rt.engine == "stream":
                # large scan: stream row-group chunks, prefetching the next
                # chunk's IO while the device filters the current one
                if planned.has_struct:  # streaming slices the span axis too
                    planned = _plan_for_block(blk, req, allow_struct=False)
                    if planned.prune:
                        return resp
                    operands = Operands.build(planned.rows, planned.tables or None)
                    needed = required_columns(planned.conds)
                from ..ops.stream import eval_block_streamed

                tm, counts, n_spans_seen = eval_block_streamed(
                    blk, needed, (planned.tree, planned.conds), operands,
                    groups=groups_range, return_device=True,
                )
                key = _start_key_dev(blk, tm.shape[0])
            else:
                staged = stage_block(blk, needed + ["trace.start_ms"], groups=groups_range)
                tm, counts = eval_block(
                    (planned.tree, planned.conds),
                    staged.cols,
                    operands,
                    staged.n_spans,
                    staged.n_traces,
                    staged.n_spans_b,
                    staged.n_res_b,
                    staged.n_traces_b,
                    span_out=False,
                )
                key = staged.cols["trace.start_ms"]
                n_spans_seen = staged.n_spans

            def selector(k):
                return select_topk_device(tm, key, counts, k)
        else:
            # span_off carries the span->trace grouping: the full-length
            # trace_sid column never needs to leave disk on the host path
            plan = None
            if groups_range is None:
                from ..ops.stream import staged_warm

                plan = route.host_plan(blk, planned, None)
                # single-unit form of the cold pipeline: coalesced ranged
                # fetch + one threaded decode, with per-stage kerneltel
                staged_warm(
                    blk, plan[0] + list(blk.SEARCH_TRACE_COLS) + ["trace.start_ms"])
            tm, counts, _ = _host_eval(blk, planned, operands, groups_range, plan=plan)
            n_spans_seen = n_rows
            key = _start_key_host(blk)

            def selector(k):
                return select_topk_host(tm, key, counts, k)

        info = TEL.last_launch() if use_device else None
        st.attrs.update(
            bucket=(int(info[1]) if info and info[0] == "filter" else n_rows),
            compile=use_device and TEL.totals()[0] > compiles0)
    results = _collect_topk(blk, req, planned, selector, limit)
    results.sort(key=lambda r: -r.start_time_unix_nano)
    resp.traces = results[:limit]
    resp.inspected_spans = n_spans_seen
    resp.inspected_bytes = pack.bytes_read - io0
    return resp


# ---- fused multi-block device search (single chip)
# (the cross-block ordering key trace@gkey_s is a derived staged column;
# its origin constant lives in ops/stage.GKEY_ORIGIN_S)


def search_blocks_fused(
    blocks: list[BackendBlock],
    req: SearchRequest,
    pool=None,
    default_limit: int = DEFAULT_LIMIT,
    plans: list | None = None,
) -> SearchResponse | None:
    """Search many blocks with at most ONE device sync.

    Engine choice is per block, by temperature (route.route_fused): a
    block worth staging evaluates on device; everything colder evaluates
    on host with the vectorized numpy engine, which costs ZERO device
    round trips and no staging upload. Device blocks share one fused
    cross-block top-k (one sync covers the whole group); host blocks run
    per-block top-k collects in the IO pool. A cold one-shot scan therefore never
    touches the device, and a hot working set costs ~one RTT per query
    regardless of block count -- the single-chip counterpart of the mesh
    program in parallel/search.py, and the production engine behind
    TempoDB.search_blocks / the frontend's block-batch jobs. The
    benchmark's `chip1-range-mix` cell (32 one-hour blocks, ranges of
    1-24) runs it served: a `search_fused` routing row a block
    (`fused_device_share`), stages `search:fused` and `topk:collect`
    (`merge_ms_per_search`), the `select` launch (`select_ms_per_launch`).

    plans: one a block where the caller has made them (plan_job).
    Returns None only when the combined staged footprint of the
    device-eligible blocks exceeds the device budget -- the caller
    falls back to per-block (streamed) search."""
    resp = SearchResponse()
    limit = req.limit or default_limit
    # TempoDB already gates its io_pool on core count + backend locality;
    # this covers direct callers handing in an ungated pool
    if (pool is not None and _N_CPU == 1 and blocks
            and not getattr(blocks[0].backend, "is_remote", True)):
        pool = None
    live = (list(zip(blocks, plans)) if plans is not None
            else _live_plans(blocks, req, pool))
    if not live:
        return resp

    from ..util.kerneltel import TEL

    routes = route.route_fused(live)
    if routes is None:
        return None
    dev_items = [it for it, r in zip(live, routes) if r.engine == "device"]
    host_items = [it for it, r in zip(live, routes) if r.engine == "host"]

    with TEL.stage("search:fused", blocks=len(live), device=len(dev_items),
                   host=len(host_items)):
        return _fused_eval(live, dev_items, host_items, req, pool, limit, resp)


def _fused_eval(live, dev_items, host_items, req: SearchRequest, pool,
                limit: int, resp: SearchResponse) -> SearchResponse:
    """search_blocks_fused past its routing: the device blocks' kernels
    and the host blocks' scans in one pool pass, the cross-block selects,
    the merge."""
    from ..util.kerneltel import TEL

    io0 = {id(blk): blk.pack.bytes_read for blk, _ in live}
    results: list[tuple] = []  # _candidates records until the final merge

    # cold host blocks run through the streaming read pipeline: block
    # N+1's ranged reads and threaded decompress are in flight while
    # block N's host engine evaluates -- the read-side analog of the
    # compaction pipeline's input prefetch, depth/byte-budget bounded
    # (ops/stream). Results are unaffected: the pipeline only moves the
    # fetch+decode of exactly the columns host_eval_collect would read.
    host_plans: dict[int, tuple] = {}
    cold_ids: set[int] = set()
    cold_wants: list[tuple[BackendBlock, list[str]]] = []
    for blk, p in host_items:
        plan = route.host_plan(blk, p, None)
        host_plans[id(blk)] = plan
        if not all(blk.pack.has_cached_array(n) for n in plan[0]
                   if blk.pack.has(n)):
            cold_ids.add(id(blk))
            cold_wants.append((blk, plan[0] + list(blk.SEARCH_TRACE_COLS)
                               + ["trace.start_ms"]))
    prefetch = None
    if len(cold_wants) > 1:  # a lone cold block has nothing to overlap
        from ..ops.stream import HostPrefetch

        prefetch = HostPrefetch(cold_wants)

    def stage_and_eval(item):
        blk, p = item
        with TEL.stage("block:search", block=blk.meta.block_id[:8],
                       engine="device") as st:
            operands = Operands.build(p.rows, p.tables or None)
            needed = required_columns(p.conds) + list(p.extra_cols) + ["trace@gkey_s"]
            staged = stage_block(blk, needed)
            tm, counts = eval_block(
                (p.tree, p.conds), staged.cols, operands,
                staged.n_spans, staged.n_traces,
                staged.n_spans_b, staged.n_res_b, staged.n_traces_b,
                span_out=False,
            )
            info = TEL.last_launch()
            st.attrs.update(
                bucket=staged.n_spans_b,
                compile=bool(info and info[0] == "filter" and info[2]))
        return tm, counts, staged.cols["trace@gkey_s"], staged.n_spans

    def host_eval_collect(item):
        import time as _time

        blk, p = item
        # cold-scan detection from the PRE-prefetch snapshot (a pipeline
        # hit still runs the host engine as a cold scan), but the rate
        # EMA only learns from scans that paid their own IO: a block the
        # prefetch served (fully or partly) times at somewhere between
        # memory and IO speed and would inflate route's host-rate EMA,
        # misrouting the next lone cold block toward the host engine
        plan = host_plans[id(blk)]
        host_needed = plan[0]
        cold = id(blk) in cold_ids
        n_spans = blk.pack.axes[S.AX_SPAN].n_rows
        with TEL.stage("block:search", block=blk.meta.block_id[:8],
                       engine="host", bucket=int(n_spans), compile=False,
                       cold=cold):
            operands = Operands.build(p.rows, p.tables or None)
            paid_io = False
            t0 = _time.perf_counter()
            if cold:
                # one coalesced ranged read + one threaded decompress batch
                # for EVERYTHING this query touches (eval columns + the
                # candidate/result trace columns): a cold scan's cost is
                # per-column fixed overheads, not bytes. The pipeline ran
                # (or is running) those stages ahead; wait for them, and do
                # the read here only if the pipeline was skipped/cancelled.
                if prefetch is None or not prefetch.wait(blk):
                    paid_io = True
                    blk.pack.warm_columns(
                        host_needed + list(blk.SEARCH_TRACE_COLS) + ["trace.start_ms"])
            tm, counts, cols = _host_eval(blk, p, operands, None, plan=plan)
            if paid_io:
                route.note_host_rate(sum(a.nbytes for a in cols.values()),
                                _time.perf_counter() - t0)
            key = _start_key_host(blk)

        if not p.needs_verify:
            # exact plans skip the per-block escalating collect: ONE
            # global host selection covers every such block (the host
            # twin of the fused device select). Key = the cross-block
            # seconds-granularity gkey (shared definition with the
            # staged device column); the final merge sorts winners by
            # exact start_ns anyway.
            from ..ops.stage import gkey_from_start_ms

            return ("raw", tm, counts, gkey_from_start_ms(blk.meta, key), n_spans)

        def selector(k):
            return select_topk_host(tm, key, counts, k)

        return ("cand", _collect_topk(blk, req, p, selector, limit,
                                      materialize=False), n_spans)

    # device staging IO + host scans overlap across one pool pass;
    # device kernel dispatches are async, so nothing blocks until the
    # fused select's single fetch
    tagged = [("dev", it) for it in dev_items] + [("host", it) for it in host_items]

    def run_item(t):
        tag, item = t
        try:
            return tag, (stage_and_eval(item) if tag == "dev" else host_eval_collect(item))
        except Exception as e:
            # pool futures re-raise with the OUTER stack; carry the real
            # one along so truncated logs still show the root cause
            import traceback

            e.add_note(f"search {tag} item on block "
                       f"{item[0].meta.block_id}: {traceback.format_exc()}")
            raise

    try:
        if pool is not None:
            # pool threads lose the contextvars: each item runs in a copy
            # of this context so its stages land on the query's self-trace
            import contextvars

            ctxs = [contextvars.copy_context() for _ in tagged]
            outs = list(pool.map(lambda c, t: c.run(run_item, t), ctxs, tagged))
        else:
            outs = [run_item(t) for t in tagged]
    finally:
        if prefetch is not None:
            prefetch.close()  # an errored item mustn't leak pipeline work
    evald = [o for tag, o in outs if tag == "dev"]
    host_out = [(o, it) for (tag, o), (htag, it) in zip(outs, tagged) if tag == "host"]

    host_raw: list[tuple] = []
    for (o, item) in host_out:
        if o[0] == "cand":
            _, out, n_spans = o
            results.extend(out)
        else:
            _, tm, counts, gkey, n_spans = o
            host_raw.append((item[0], item[1], tm, counts, gkey))
        resp.inspected_spans += int(n_spans)
    if host_raw:
        h_blocks = [b for b, _, _, _, _ in host_raw]
        h_plans = [p for _, p, _, _, _ in host_raw]
        h_tms = [t for _, _, t, _, _ in host_raw]
        h_cnts = [c for _, _, _, c, _ in host_raw]
        h_keys = [k for _, _, _, _, k in host_raw]
        h_offsets = np.cumsum([0] + [int(t.shape[0]) for t in h_tms])

        def h_selector(k):
            return select_topk_host_multi(h_tms, h_keys, h_cnts, k)

        results.extend(_collect_topk_multi(
            h_blocks, h_plans, h_offsets, req, h_selector, limit,
            materialize=False,
        ))

    if evald:
        tms = [e[0] for e in evald]
        cnts = [e[1] for e in evald]
        keys = [e[2] for e in evald]
        resp.inspected_spans += int(sum(e[3] for e in evald))
        # the select's shape is the job's (its block count and largest
        # trace bucket), never the router's split of it: a compile a
        # blocklist reaches is one warm-up reaches (ops/select)
        part_len = max(bucket(max(blk.meta.total_traces, 1)) for blk, _ in live)
        offsets = np.arange(len(tms) + 1) * part_len

        def selector(k):
            return select_topk_device_multi(tms, keys, cnts, k, len(live), part_len)

        results.extend(_collect_topk_multi(
            [blk for blk, _ in dev_items], [p for _, p in dev_items],
            offsets, req, selector, limit, materialize=False,
            total=group_rung(len(live)) * part_len,
        ))

    # global merge over lightweight candidates; only the winning `limit`
    # pay dictionary lookups + SearchResult construction
    results.sort(key=lambda c: -c[0])
    seen: set[str] = set()
    resp.traces = []
    for c in results:
        if c[1] in seen:
            continue
        seen.add(c[1])
        resp.traces.append(_materialize(c))
        if len(resp.traces) >= limit:
            break
    resp.inspected_bytes = sum(
        blk.pack.bytes_read - io0[id(blk)] for blk, _ in live
    )
    return resp


def _collect_topk_multi(blocks, plans, offsets, req: SearchRequest, selector,
                        limit: int, materialize: bool = True,
                        total: int | None = None):
    """Escalating cross-block top-k collect: global winners map back to
    (block, sid) via the padded part offsets, then per-block exact
    verification + result building -- the multi-block twin of
    _collect_topk (same materialize contract). total: the rows the
    selector ranks where that is more than the parts' (a device group
    padded to its rung); it caps k."""
    total = int(offsets[-1]) if total is None else total
    if total == 0:
        return []
    from ..util.kerneltel import TEL

    verify = [_route_verify(req, p) for p in plans]
    with TEL.stage("topk:collect", blocks=len(blocks), limit=limit,
                   rounds=0) as st:
        k = min(k_bucket(max(2 * limit, 32)), total)
        out: list = []
        seen: set[int] = set()
        while True:
            st.attrs["rounds"] += 1  # selects run: 1 + how often k escalated
            gids, gcnts, n_match = selector(k)
            per_block: dict[int, list[tuple[int, int]]] = {}
            fresh = 0
            for g, c in zip(gids, gcnts):
                g = int(g)
                if g in seen:
                    continue
                seen.add(g)
                fresh += 1
                bi = int(np.searchsorted(offsets, g, side="right")) - 1
                per_block.setdefault(bi, []).append((g - int(offsets[bi]), int(c)))
            for bi, pairs in per_block.items():
                blk = blocks[bi]
                sids = np.asarray([s for s, _ in pairs], dtype=np.int64)
                ok = _verify_candidates(blk, req, sids, verify[bi])
                okset = {int(s) for s in ok}
                out.extend(
                    _candidates(blk, req, [s for s, c in pairs if s in okset], dict(pairs))
                )
            if len(out) >= limit or len(seen) >= n_match or k >= total or fresh == 0:
                return [_materialize(c) for c in out] if materialize else out
            k = min(k_bucket(k * 4), total)


# ---- stacked multi-block device search (parallel/search.py)


def _count_struct_nodes(tree) -> int:
    """Struct ('>' / '>>' / '~') nodes in a condition tree. Each one
    costs its own span-axis lhs-mask all_gather on the mesh, so the
    pre-IO budget estimate must scale with the count, not a boolean.
    ('struct', op, lhs, rhs): t[1] is the op STRING, never recursed."""
    if not isinstance(tree, tuple):
        return 0
    n = 1 if tree[0] == "struct" else 0
    children = tree[2:] if tree[0] == "struct" else tree[1:]
    return n + sum(_count_struct_nodes(ch) for ch in children
                   if isinstance(ch, tuple))


def _has_deep_struct(tree) -> bool:
    """True when any '>>' or '~' node is present: those relations walk
    the REPLICATED parent table, so the mesh program hoists one
    parent/validity gather per launch on top of the per-node masks
    ('>' runs off the local parent column and needs neither)."""
    if not isinstance(tree, tuple):
        return False
    if tree[0] == "struct" and tree[1] in (">>", "~"):
        return True
    children = tree[2:] if tree[0] == "struct" else tree[1:]
    return any(_has_deep_struct(ch) for ch in children
               if isinstance(ch, tuple))


def _stacked_words_est(items, needed: list[str], tree, sp: int,
                       S_b: int, NT_b: int, attr_b: dict[str, int]) -> int:
    """Per-block stacked-column words the mesh program will hold on
    device, estimated BEFORE any column IO (an over-budget group must
    fall back without paying the cold reads). Per-axis products plus
    the struct-node replication, priced to the SHRUNK mesh program
    (parallel/search): each node replicates its (bit-packed on the
    wire, unpacked bool on device) lhs mask onto every chip, and a
    tree with any '>>' / '~' node additionally hoists ONE
    parent/validity gather (+ pointer-doubling temps) per launch --
    the costmodel comm walker prices the same collectives on the wire
    and tests cross-check the two counts."""
    span_cols = [n for n in needed if n.startswith("span.")]
    est = S_b * max(1, len(span_cols))
    # trace-axis tables (span_off at NT_b+1 plus any trace.* conds) and
    # res-axis columns ride every block too; their row counts come from
    # footer metadata (pack.n_rows_of), so trace-heavy groups near the
    # budget are no longer understated (ADVICE round 5)
    n_trace_cols = sum(1 for n in needed if n.startswith("trace."))
    est += NT_b * n_trace_cols
    res_cols = [n for n in needed if n.startswith("res.")]
    if res_cols:
        r_rows = max((blk.pack.n_rows_of(n) for blk, _ in items for n in res_cols),
                     default=1)
        est += bucket(max(r_rows, 1)) * len(res_cols)
    for pre, a_b in attr_b.items():
        n_val_cols = sum(
            1 for n in needed if n.startswith(f"{pre}.") and not n.endswith((".span", ".res"))
        )
        # values + off: the stacked program's own columns (flat rows and
        # offsets, built below), not ops/stage's slot-major form
        est += a_b * n_val_cols + (S_b + 1 if pre == "sattr" else 0)
    from ..parallel.search import struct_pack_enabled

    if struct_pack_enabled():
        est += S_b * sp * _count_struct_nodes(tree)  # per-node replicated mask
        if _has_deep_struct(tree):
            est += 4 * S_b * sp  # hoisted pid/valid + closure temps, once
    else:
        # legacy escape hatch (TEMPO_STRUCT_PACK=0): every node gathers
        # lm/pid/valid + temps -- the budget must price what will run
        est += 6 * S_b * sp * _count_struct_nodes(tree)
    return est


def search_blocks_device(
    blocks: list[BackendBlock],
    req: SearchRequest,
    mesh,
    default_limit: int = DEFAULT_LIMIT,
    pool=None,
) -> SearchResponse | None:
    """Search many blocks as ONE stacked mesh program: blocks shard over
    'dp', span rows AND generic-attr rows over 'sp', per-block operands
    resolved through each block's dictionary (parallel/search.py). The
    multi-chip analog of the reference's per-block job fan-out
    (modules/frontend/searchsharding.go + tempodb/pool), including the
    generic attribute iterators (vparquet/block_traceql.go:682-763) and
    structural ops (>, >>, ~: parent tables all_gather along sp).
    Pre-upgrade blocks without span.parent_idx never reach a struct
    tree -- their planner falls back to the conservative force-verify
    plan, which runs on the mesh like any other. Returns None only when
    the stacked columns (plus struct all_gather replication) exceed the
    device budget -- the caller falls back to per-block search_block."""
    resp = SearchResponse()
    live = _live_plans(blocks, req, pool)
    if not live:
        return resp

    # identical plan structure -> one compiled mesh program per group
    groups: dict[tuple, list[tuple[BackendBlock, object]]] = {}
    for blk, p in live:
        groups.setdefault((p.tree, p.conds), []).append((blk, p))

    limit = req.limit or default_limit
    results: list[SearchResult] = []
    for (tree, conds), items in groups.items():
        got = _search_group_device(items, tree, conds, req, mesh, resp, pool)
        if got is None:
            return None
        results.extend(got)
    results.sort(key=lambda r: -r.start_time_unix_nano)
    # replicated partials hit in several blocks: dedupe by trace id, same
    # as the per-block path's SearchResponse.merge
    seen: set[str] = set()
    deduped = []
    for r in results:
        if r.trace_id not in seen:
            seen.add(r.trace_id)
            deduped.append(r)
    resp.traces = deduped[:limit]
    return resp


def _search_group_device(items, tree, conds, req: SearchRequest, mesh, resp: SearchResponse,
                         pool=None):
    from ..ops.device import PAD_I32, bucket
    from ..parallel.search import sharded_search

    dp, sp = mesh.shape["dp"], mesh.shape["sp"]
    # span@ materialization is a staged-cache concept; the stacked path
    # reads and stacks raw columns only. extra_cols carries tree-level
    # needs (span.parent_idx for struct nodes).
    needed = [n for n in required_columns(conds) + list(items[0][1].extra_cols)
              if not n.startswith("span@")]
    B = len(items)
    Bp = ((B + dp - 1) // dp) * dp
    s_max = max(blk.pack.axes[S.AX_SPAN].n_rows for blk, _ in items)
    S_b = sp * bucket(max(1, -(-max(s_max, 1) // sp)))
    NT_b = bucket(max(max(blk.meta.total_traces for blk, _ in items), 1))
    # generic-attr rows ride the sp axis like span rows; their buckets
    # come from the widest block in the group (axis metadata -- no IO)
    attr_b: dict[str, int] = {}
    for pre, ax in (("sattr", S.AX_SATTR), ("rattr", S.AX_RATTR)):
        if f"{pre}.key_id" in needed:
            a_max = max(
                blk.pack.axes[ax].n_rows if ax in blk.pack.axes else 0 for blk, _ in items
            )
            attr_b[pre] = sp * bucket(max(1, -(-max(a_max, 1) // sp)))
    est = _stacked_words_est(items, needed, tree, sp, S_b, NT_b, attr_b)
    if Bp * est * 4 > route.JOB_STAGE_BUDGET_BYTES:
        from ..util.kerneltel import TEL

        TEL.record_routing("search_mesh", "fallback", "pre_io_budget",
                           n=len(items))
        return None

    host: dict[str, np.ndarray] = {}
    io0 = [blk.pack.bytes_read for blk, _ in items]

    def read_block_cols(blk):
        return {n: blk.pack.read(n) for n in needed}

    if pool is not None:  # overlap per-block column IO, like the host path
        per_block = list(pool.map(read_block_cols, [blk for blk, _ in items]))
    else:
        per_block = [read_block_cols(blk) for blk, _ in items]
    n_res_per = [
        max((a.shape[0] for n, a in cols.items() if n.startswith("res.")), default=1)
        for cols in per_block
    ]
    R_b = bucket(max(max(n_res_per), 1))
    for n in needed:
        pre = n.split(".", 1)[0]
        if n == "trace.span_off":
            # (NT_b+1,) offsets per block; padded trace rows collapse to
            # empty segments by repeating the final offset
            out = np.zeros((Bp, NT_b + 1), dtype=np.int32)
            for bi, cols in enumerate(per_block):
                a = cols[n]
                out[bi, : a.shape[0]] = a
                out[bi, a.shape[0]:] = a[-1] if a.size else 0
            host[n] = out
            continue
        if n in ("sattr.span", "rattr.res"):
            # owner rows (grouped by owner) -> per-owner offset column,
            # replicated along sp; the kernel aggregates with cumsum +
            # offset gathers (parallel/search.owner_counts). Mirrors
            # ops/stage.py's single-device offsetting of `rattr.res`.
            n_seg_b = S_b if n == "sattr.span" else R_b
            out = np.zeros((Bp, n_seg_b + 1), dtype=np.int32)
            for bi, cols in enumerate(per_block):
                owners = cols[n]
                n_seg = (
                    items[bi][0].pack.axes[S.AX_SPAN].n_rows
                    if n == "sattr.span"
                    else n_res_per[bi]
                )
                cnt = np.bincount(
                    np.clip(owners, 0, max(n_seg, 1) - 1), minlength=max(n_seg, 1)
                ) if owners.size else np.zeros(max(n_seg, 1), dtype=np.int64)
                off = np.concatenate([[0], np.cumsum(cnt)]).astype(np.int32)
                out[bi, : off.shape[0]] = off
                out[bi, off.shape[0]:] = off[-1]
            host[f"{pre}.off"] = out
            continue
        if n.startswith("span."):
            shape, fill = (Bp, S_b), PAD_I32
        elif pre in attr_b:
            shape, fill = (Bp, attr_b[pre]), PAD_I32
        elif n.startswith("res."):
            shape, fill = (Bp, R_b), PAD_I32
        elif n.startswith("trace."):
            shape, fill = (Bp, NT_b), PAD_I32
        else:
            return None
        first = per_block[0][n]
        if first.dtype not in (np.int32, np.float32):
            return None
        out = np.full(shape, fill if first.dtype == np.int32 else np.float32(0), dtype=first.dtype)
        for bi, cols in enumerate(per_block):
            a = cols[n]
            out[bi, : a.shape[0]] = a
        host[n] = out

    n_spans = np.zeros((Bp,), dtype=np.int32)
    for bi, (blk, _) in enumerate(items):
        n_spans[bi] = blk.pack.axes[S.AX_SPAN].n_rows
    operands = [Operands.build(p.rows, p.tables or None) for _, p in items]
    operands += [Operands.build([(0, 0, 0, 0.0, 0.0)] * len(conds))] * (Bp - B)
    tm, sc = sharded_search(mesh, tree, conds, operands, host, n_spans, nt=NT_b)

    limit = req.limit or DEFAULT_LIMIT
    results: list[SearchResult] = []
    for bi, (blk, p) in enumerate(items):
        nt = blk.meta.total_traces
        mask, cnt = tm[bi][:nt], sc[bi][:nt]
        key = _start_key_host(blk)[:nt]

        def selector(k, mask=mask, cnt=cnt, key=key):
            return select_topk_host(mask, key, cnt, k)

        results.extend(_collect_topk(blk, req, p, selector, limit))
        resp.inspected_spans += int(n_spans[bi])
        resp.inspected_bytes += blk.pack.bytes_read - io0[bi]
    return results


# ---- tag name/value discovery (reference: /api/search/tags endpoints)


def search_tags(blk: BackendBlock, collector: DistinctStringCollector) -> None:
    d = blk.dictionary
    for col in ("sattr.key_id", "rattr.key_id"):
        codes = np.unique(blk.pack.read(col))
        for c in codes:
            if c >= 0:
                collector.collect(d.string(int(c)))
    # well-known resource attrs live only in dedicated columns
    for tag, col in _WELL_KNOWN_RES.items():
        if blk.pack.has(col) and (blk.pack.read(col) >= 0).any():
            collector.collect(tag)


def search_tag_values(blk: BackendBlock, tag: str, collector: DistinctStringCollector) -> None:
    d = blk.dictionary
    kcode = d.lookup(tag)
    if tag == _INTRINSIC_NAME:
        for c in np.unique(blk.pack.read("span.name_id")):
            if c >= 0:
                collector.collect(d.string(int(c)))
        return
    ded = _WELL_KNOWN_RES.get(tag)
    if ded and blk.pack.has(ded):
        for c in np.unique(blk.pack.read(ded)):
            if c >= 0:
                collector.collect(d.string(int(c)))
    if kcode < 0:
        return
    for pre in ("sattr", "rattr"):
        keys = blk.pack.read(f"{pre}.key_id")
        mask = keys == kcode
        if not mask.any():
            continue
        vt = blk.pack.read(f"{pre}.vtype")[mask]
        sid = blk.pack.read(f"{pre}.str_id")[mask]
        i64 = blk.pack.read(f"{pre}.int64")[mask]
        for j in range(len(vt)):
            if vt[j] == 0:
                collector.collect(d.string(int(sid[j])))
            elif vt[j] == 1:
                collector.collect(str(int(i64[j])))
            elif vt[j] == 3:
                collector.collect("true" if i64[j] else "false")
