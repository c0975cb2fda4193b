"""Cross-query batching executor: coalesce concurrent jobs into fused
multi-query kernel launches.

Under concurrency every in-flight query used to dispatch its own kernel
sequence over the SAME staged block -- Q small launches paying Q
dispatch round trips. This module is the scheduling half of the fix
(ops/multiquery.py is the kernel half), the trace-search analog of
continuous batching in inference serving (Orca, OSDI '22: merge
concurrent requests into one device step):

  * a short admission window (TEMPO_BATCH_WINDOW_MS, default 3 ms)
    opens when the first eligible job arrives; jobs submitted inside it
    group by *coalesce key* -- (block, row-group range, staged column
    set, program-shape bucket) -- so every member lowers onto the SAME
    compiled program;
  * each group executes as ONE fused launch pair (multi-query filter +
    batched top-k) and the per-query results demux back to their
    submitters, exact-verify fallback preserved per query;
  * a lone query never waits past the window, and skips it entirely
    when nobody else is inside the executor (the in-flight fast path);
  * ineligible plans (regex tables, generic attr conds, struct
    relations, cold blocks) never enter the window: callers fall back
    to the single-query path unchanged.

Two executors share the machinery: `search` fuses TraceQL/tag search
jobs through the predicate-program kernel; `find` fuses trace-by-ID
lookups through the batched bisection kernel (ops/find already takes a
(Q, 4) query block -- the batcher just forms the Q axis).

Occupancy, coalesce ratio, window waits and demux counts flow through
util/kerneltel into /metrics and /status/kernels.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field

import numpy as np

from ..util.profiler import timed_lock
from . import route

DEFAULT_WINDOW_MS = 3.0
DEFAULT_MAX_BATCH = 16
_FOLLOWER_TIMEOUT_S = 600.0


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _mq_budget_bytes() -> int:
    """Fused-launch intermediate budget ((Q, P, S) cond masks + cumsums
    in HBM): a group estimated past it runs its members sequentially
    instead. TEMPO_BATCH_MQ_BUDGET overrides (bytes)."""
    return int(_env_float("TEMPO_BATCH_MQ_BUDGET", float(1 << 30)))


def resolve_batch_config(enabled=None, window_ms=None, max_batch=None):
    """(enabled, window_s, max_batch) from explicit config, falling back
    to env knobs: TEMPO_BATCH=0 disables, TEMPO_BATCH_WINDOW_MS,
    TEMPO_BATCH_MAX."""
    if enabled is None:
        enabled = os.environ.get("TEMPO_BATCH", "1") not in ("0", "false")
    if window_ms is None:
        window_ms = _env_float("TEMPO_BATCH_WINDOW_MS", DEFAULT_WINDOW_MS)
    if max_batch is None:
        max_batch = int(_env_float("TEMPO_BATCH_MAX", DEFAULT_MAX_BATCH))
    return bool(enabled), max(0.0, window_ms) / 1e3, max(1, max_batch)


class _Group:
    __slots__ = ("items", "done", "full", "closed", "results")

    def __init__(self):
        self.items: list = []
        self.done = threading.Event()
        self.full = threading.Event()
        self.closed = False
        self.results: list | None = None


class BatchExecutor:
    """Leader/follower admission-window batcher. The first submitter
    for a key becomes the group leader: it holds the window open (or
    until the group fills), then runs `runner(key, items)` and fans the
    per-item results (or per-item exceptions) back out. Followers that
    land inside the window just wait for demux."""

    def __init__(self, name: str, runner, window_s: float = DEFAULT_WINDOW_MS / 1e3,
                 max_batch: int = DEFAULT_MAX_BATCH, enabled: bool = True):
        self.name = name
        self.runner = runner  # (key, items) -> list of results/Exceptions
        self.window_s = window_s
        self.max_batch = max_batch
        self.enabled = enabled
        # cataloged hot lock: every submitter serializes through the
        # admission window here (TEMPO_LOCK_PROFILE arms wait timing)
        self._lock = timed_lock(f"batchexec_{name}")
        self._groups: dict = {}
        self._inflight = 0  # submitters currently inside submit_many

    def submit(self, key, item):
        out = self.submit_many(key, [item])[0]
        if isinstance(out, Exception):
            raise out
        return out

    def submit_many(self, key, items: list) -> list:
        """Submit items under one coalesce key; blocks until the fused
        group (this thread's and any window-mates') executes. Returns
        per-item results; a failed item comes back as its Exception so
        one poisoned query never discards its siblings' results (multi
        callers route per-item failures through per-job error paths)."""
        if len(items) > self.max_batch:  # a single oversized submission
            out: list = []  # still respects the configured group cap
            for i in range(0, len(items), self.max_batch):
                out.extend(self.submit_many(key, items[i:i + self.max_batch]))
            return out
        with self._lock:
            self._inflight += 1
            g = self._groups.get(key)
            if (g is None or g.closed
                    or len(g.items) + len(items) > self.max_batch):
                g = _Group()
                self._groups[key] = g
                leader = True
            else:
                leader = False
            lo = len(g.items)
            g.items.extend(items)
            if not leader and len(g.items) >= self.max_batch:
                g.full.set()
        try:
            if leader:
                self._lead(key, g)
            elif not g.done.wait(_FOLLOWER_TIMEOUT_S):
                raise TimeoutError(
                    f"batch group leader stalled ({self.name})")
        finally:
            with self._lock:
                self._inflight -= 1
        return g.results[lo:lo + len(items)]

    def _lead(self, key, g: _Group) -> None:
        from ..util.kerneltel import TEL

        # timeline: the admission window this leader held open (zero-
        # length on the lone-query fast path), with its final occupancy
        with TEL.stage("batch-window", executor=self.name) as win:
            # lone-query fast path: only hold the window open when another
            # SUBMITTER is inside the executor (each counts once in
            # _inflight no matter how many items it carries; the leader
            # itself is one). Purely sequential traffic therefore never
            # pays the window; a concurrent burst's stragglers group with
            # each other while the first arrival's launch is in flight.
            if self.window_s > 0:
                with self._lock:
                    others = self._inflight > 1
                if others:
                    g.full.wait(self.window_s)
            with self._lock:
                g.closed = True
                if self._groups.get(key) is g:
                    del self._groups[key]
                items = list(g.items)
            win.attrs["occupancy"] = len(items)
        wait_s = win.seconds
        try:
            with TEL.stage("batch:launch", executor=self.name,
                           occupancy=len(items)):
                results = self.runner(key, items)
            if not isinstance(results, list) or len(results) != len(items):
                raise RuntimeError(
                    f"batch runner returned {len(results) if isinstance(results, list) else type(results)} "
                    f"results for {len(items)} items")
            g.results = results
        except Exception as e:  # group-level failure: every member sees it
            g.results = [e] * len(items)
        finally:
            g.done.set()
        TEL.record_batch(self.name, len(items), wait_s)


# ------------------------------------------------------------- search path


@dataclass
class _SearchItem:
    blk: object
    req: object
    planned: object
    lowered: object
    needed: list
    groups_range: object
    limit: int


def _seq_or_exc(it: _SearchItem):
    """One item on the single-job path, under the plan the probe made."""
    from dataclasses import replace

    from .search import search_block

    # honor the route's default limit (search_blocks passes the config
    # default; search_block alone would fall back to the module default)
    req = it.req if it.req.limit else replace(it.req, limit=it.limit)
    try:
        return search_block(it.blk, req, groups_range=it.groups_range,
                            planned=it.planned)
    except Exception as e:
        return e


def _run_search_group(key, items: list, mesh_fn=None) -> list:
    """Execute one coalesced search group: stage once, ONE fused
    multi-query filter launch, ONE batched top-k launch, per-query
    verify + materialize. Any fused-path failure degrades to per-item
    single-query execution (never to an error the sequential path would
    not have raised)."""
    from ..util.kerneltel import TEL

    if len(items) == 1:
        return [_seq_or_exc(items[0])]
    try:
        return _run_search_group_fused(items, mesh_fn)
    except Exception as e:
        TEL.record_routing("search_batch", "fallback", "fused_error",
                           n=len(items))
        _log_fused_error("search", e)
        return [_seq_or_exc(it) for it in items]


def _log_fused_error(kind: str, e: Exception) -> None:
    """A fused launch that fails (a compile refusal included) still
    degrades to per-item execution, but never silently: the error and
    its traceback are logged, each distinct error once per log window
    (the error text is the log shim's repeat key; repeats are counted)."""
    import traceback

    from ..util.log import get_logger

    get_logger("batchexec").error(
        f"fused {kind} launch failed, running items one by one: "
        f"{type(e).__name__}: {e}",
        traceback="".join(traceback.format_exception(e)))


def _mesh_batch_enabled() -> bool:
    """TEMPO_MESH_BATCH=0 pins window leaders to the single-chip fused
    launch even on a multi-device mesh (the legacy-path escape hatch the
    differential suite also uses)."""
    return os.environ.get("TEMPO_MESH_BATCH", "1") not in ("0", "false")


def _run_search_group_fused(items: list, mesh_fn=None) -> list:

    from ..ops.multiquery import (
        _p2,
        eval_multiquery,
        mq_bytes_estimate,
        pack_queries,
        select_multiquery,
    )
    from ..ops.select import k_bucket
    from ..ops.stage import stage_block
    from ..util.kerneltel import TEL
    from .search import SearchResponse, collect_seeded

    blk = items[0].blk
    shape = items[0].lowered.shape
    q_b = _p2(len(items), lo=1)
    io0 = blk.pack.bytes_read
    with TEL.stage("batch:eval", block=blk.meta.block_id[:8],
                   occupancy=len(items)) as st:
        staged = stage_block(blk, items[0].needed + ["trace.start_ms"],
                             groups=items[0].groups_range)
        if mq_bytes_estimate(shape, q_b, staged.n_spans_b) > _mq_budget_bytes():
            TEL.record_routing("search_batch", "fallback", "mq_budget",
                               n=len(items))
            return [_seq_or_exc(it) for it in items]
        progs = pack_queries([it.lowered for it in items], q_b)
        lowered = [it.lowered for it in items]
        # >1 chip attached: the window leader lowers the whole group to ONE
        # Q-programs x sharded-rows mesh launch (parallel/multiquery), so
        # the admission window amortizes across every chip instead of
        # competing with sp-sharding for the executor. Shape-ineligible
        # buckets and TEMPO_MESH_BATCH=0 keep the single-chip fused launch.
        mesh = mesh_fn() if mesh_fn is not None else None
        engine = "device"
        if mesh is not None and _mesh_batch_enabled():
            from ..parallel.multiquery import mesh_batch_eligible, mesh_eval_multiquery

            if mesh_batch_eligible(mesh, staged):
                tm, counts = mesh_eval_multiquery(mesh, lowered, staged, progs)
                engine = "mesh"
            else:
                tm, counts = eval_multiquery(lowered, staged, progs)
        else:
            tm, counts = eval_multiquery(lowered, staged, progs)
        st.attrs.update(engine=engine, bucket=staged.n_spans_b)
    key_dev = staged.cols["trace.start_ms"]
    nt = blk.meta.total_traces
    TEL.record_routing("search_batch", engine,
                       "mesh_batched" if engine == "mesh" else "coalesced",
                       n=len(items))

    responses: list = []
    if nt == 0:
        for it in items:
            r = SearchResponse()
            r.inspected_spans = staged.n_spans
            responses.append(r)
        responses[0].inspected_bytes = blk.pack.bytes_read - io0
        return responses
    ks = [min(k_bucket(max(2 * it.limit, 32)), nt) for it in items]
    rows = select_multiquery(tm, key_dev, counts, max(ks))
    for qi, it in enumerate(items):
        try:
            sids_k, cnts_k, valid_k, n_match = rows[qi]
            kq = ks[qi]
            seed = (sids_k[:kq][valid_k[:kq]], cnts_k[:kq][valid_k[:kq]],
                    n_match)
            results = collect_seeded(blk, it.req, it.planned, seed,
                                     tm[qi], counts[qi], key_dev, it.limit)
            results.sort(key=lambda r: -r.start_time_unix_nano)
            resp = SearchResponse()
            resp.traces = results[:it.limit]
            resp.inspected_spans = staged.n_spans
            responses.append(resp)
        except Exception as e:  # verify/materialize is per-query: isolate
            responses.append(e)
    # IO attribution mirrors the sequential hot path: only the query
    # that triggered reads pays them (here, the group's one staging
    # pass), so the first response carries the delta and its mates
    # report 0 -- same as cache-hit queries on the sequential engine
    for r in responses:
        if not isinstance(r, Exception):
            r.inspected_bytes = blk.pack.bytes_read - io0
            break
    TEL.record_demux("search", len(items))
    return responses


# --------------------------------------------------------------- find path


@dataclass
class _FindItem:
    metas: list
    trace_id: bytes
    db: object = field(repr=False, default=None)


def _find_seq_or_exc(it: _FindItem):
    """Sequential twin of one find item (the pre-batching path)."""
    from ..wire.combine import combine_traces

    try:
        found = it.db._device_find(it.metas, it.trace_id)
        return combine_traces(found) if found else None
    except Exception as e:
        return e


def _run_find_group(key, items: list) -> list:
    """One coalesced trace-by-ID group: bloom-gate per (block, id) on
    host, then ONE batched bisection over every surviving block for ALL
    Q ids, per-id hit rows materialized and combined. Engine choice
    mirrors TempoDB._device_find: the sharded mesh program when >1 chip
    is attached, the fused single-chip batch (auto host/device) else.
    Any fused-path failure degrades to per-item sequential lookups so
    one bad block never fails the whole window's queries."""
    from ..util.kerneltel import TEL

    if len(items) == 1:
        return [_find_seq_or_exc(items[0])]
    try:
        return _run_find_group_fused(items)
    except Exception as e:
        TEL.record_routing("find_batch", "fallback", "fused_error",
                           n=len(items))
        _log_fused_error("find", e)
        return [_find_seq_or_exc(it) for it in items]


def _run_find_group_fused(items: list) -> list:
    from ..block import schema as S
    from ..ops.find import lookup_ids_blocks_cached
    from ..util.kerneltel import TEL
    from ..wire.combine import combine_traces

    db = items[0].db
    metas, pool = items[0].metas, db.pool
    ids = [it.trace_id.rjust(16, b"\x00") for it in items]
    # a block survives the gate if ANY id in the window may be present;
    # the bisection compare is exact, so ids the bloom would have pruned
    # for a given block simply miss (-1) there
    with TEL.stage("find:bloom", blocks=len(metas), ids=len(items)):
        blocks = [db.open_block(m) for m in metas]
        if pool is not None:
            gates = list(pool.map(
                lambda b: any(b.bloom_test(it.trace_id) for it in items), blocks))
        else:
            gates = [any(b.bloom_test(it.trace_id) for it in items)
                     for b in blocks]
        keep = [b for b, ok in zip(blocks, gates) if ok]
    if not keep:
        return [None] * len(items)
    query = np.asarray([S.trace_id_to_codes(i) for i in ids], dtype=np.int32)
    with TEL.stage("find:lookup", blocks=len(keep), ids=len(items)):
        if db.mesh.devices.size > 1:
            from ..parallel.find import sharded_find_rows

            codes = (list(pool.map(lambda b: b.trace_index["trace.id_codes"], keep))
                     if pool is not None
                     else [b.trace_index["trace.id_codes"] for b in keep])
            sids = sharded_find_rows(db.mesh, codes, query)  # (B, Q)
        else:
            if pool is not None:  # overlap the id-index reads
                list(pool.map(lambda b: b.trace_index, keep))
            sids = lookup_ids_blocks_cached(keep, query)  # (B, Q)
    per_block: dict[int, list[tuple[int, int]]] = {}
    for bi in range(sids.shape[0]):
        for qi in range(sids.shape[1]):
            if sids[bi, qi] >= 0:
                per_block.setdefault(bi, []).append((qi, int(sids[bi, qi])))
    found: list[list] = [[] for _ in items]
    with TEL.stage("find:fetch", hits=sum(len(p) for p in per_block.values())):
        for bi, pairs in per_block.items():
            traces = keep[bi].materialize_traces([row for _, row in pairs])
            for (qi, _), tr in zip(pairs, traces):
                if tr is not None:
                    found[qi].append(tr)
    TEL.record_demux("find", len(items))
    return [combine_traces(f) if f else None for f in found]


def batched_find(batcher: BatchExecutor, db, metas: list, trace_id: bytes):
    """Trace-by-ID lookup through the find batcher: concurrent lookups
    against the same candidate partition share one bisection batch."""
    key = ("find", metas[0].tenant_id, tuple(m.block_id for m in metas))
    item = _FindItem(metas=metas, trace_id=trace_id, db=db)
    return batcher.submit(key, item)


# ------------------------------------------------------------- aggregates


class QueryBatchers:
    """The per-TempoDB pair of batching executors (search + find) under
    one resolved config. `mesh_fn` (lazy: the mesh is built on first
    use) lets window leaders lower a whole group onto the device mesh
    when more than one chip is attached."""

    def __init__(self, enabled=None, window_ms=None, max_batch=None,
                 mesh_fn=None):
        on, window_s, max_b = resolve_batch_config(enabled, window_ms, max_batch)
        self.enabled = on

        def search_runner(key, items):
            return _run_search_group(key, items, mesh_fn)

        self.search = BatchExecutor("search", search_runner,
                                    window_s=window_s, max_batch=max_b,
                                    enabled=on)
        self.find = BatchExecutor("find", _run_find_group,
                                  window_s=window_s, max_batch=max_b,
                                  enabled=on)


def batched_search_block_many(batcher: BatchExecutor, entries: list,
                              default_limit: int | None = None,
                              refused=None) -> list:
    """Many (blk, req, groups_range) searches from ONE caller thread,
    each planned here, once. Those db/route finds batchable group by
    coalesce key and submit together, so a single worker draining a
    burst still forms full batches (the frontend's batch-aware dequeue
    lands here). Returns per entry a SearchResponse, the entry's own
    Exception (caller routes it through its per-job error path), or for
    an entry the window refuses what refused(blk, req, groups_range,
    planned) returns: the caller's single-job path under the plan made
    here (None without one). default_limit: for limit-less requests
    (search_default_limit parity on the search_blocks route)."""
    from .search import DEFAULT_LIMIT, SearchResponse, plan_job

    out: list = [None] * len(entries)
    staged: dict = {}
    turned_away: list = []
    for i, (blk, req, groups_range) in enumerate(entries):
        planned = plan_job(blk, req, groups_range)
        if planned is None:  # out of the window, or pruned
            out[i] = SearchResponse()
            continue
        lowered = route.route_batch(blk, planned, groups_range).lowered
        if lowered is None:
            turned_away.append((i, planned))
            continue
        needed = route.stage_columns(planned)
        item = _SearchItem(
            blk=blk, req=req, planned=planned, lowered=lowered, needed=needed,
            groups_range=list(groups_range) if groups_range is not None else None,
            limit=req.limit or default_limit or DEFAULT_LIMIT,
        )
        key = ("search", blk.meta.tenant_id, blk.meta.block_id,
               tuple(groups_range) if groups_range is not None else None,
               tuple(needed + ["trace.start_ms"]), lowered.shape)
        staged.setdefault(key, []).append((i, item))
    for key, pairs in staged.items():
        results = batcher.submit_many(key, [it for _, it in pairs])
        for (i, _), r in zip(pairs, results):
            out[i] = r
    if refused is not None:
        for i, planned in turned_away:
            out[i] = refused(*entries[i], planned)
    return out
