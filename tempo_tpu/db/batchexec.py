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


def _collect_seeded(blk, req, planned, seed, tm_row, counts_row, key_dev,
                    limit: int):
    """db/search._collect_topk with the FIRST selection pre-computed by
    the fused batched top-k (the seed was sliced to exactly the k the
    collect loop asks for first); escalation (verification rejected
    enough candidates) falls back to per-query device selects on this
    query's mask row. Returns candidate records (materialize=False)."""
    from ..ops.select import select_topk_device
    from .search import _collect_topk

    state = [seed]

    def selector(k):
        if state:
            return state.pop()
        return select_topk_device(tm_row, key_dev, counts_row, k)

    return _collect_topk(blk, req, planned, selector, limit,
                         materialize=False)


def _sequential_search(it: _SearchItem):
    from dataclasses import replace

    from .search import search_block

    # honor the route's default limit (search_blocks passes the config
    # default; search_block alone would fall back to the module default)
    req = it.req if it.req.limit else replace(it.req, limit=it.limit)
    return search_block(it.blk, req, groups_range=it.groups_range)


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


def _seq_or_exc(it: _SearchItem):
    try:
        return _sequential_search(it)
    except Exception as e:
        return e


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
    from .search import SearchResponse, _materialize

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
            out = _collect_seeded(blk, it.req, it.planned, seed,
                                  tm[qi], counts[qi], key_dev, it.limit)
            results = [_materialize(c) for c in out]
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


def batched_search_block(batcher: BatchExecutor, blk, req,
                         groups_range=None, promote_touches: int = 2,
                         default_limit: int | None = None):
    """Route one block search through the batching executor when
    eligible; None means "take today's path unchanged":

      * the plan must lower to a predicate program (ops/multiquery);
      * the block must be warm -- staged columns resident, or touched
        promote_touches times (search_blocks_fused's promotion rule), or
        device-pinned for row-group shard jobs (search_block's rule);
      * tres-eligible plans keep the cheaper host membership scan, and
        stream-sized scans keep the chunked path.

    The sequential engine's per-query host_scan_cheaper estimate is
    deliberately NOT mirrored: it weighs one host scan against one
    device round trip, but under the batcher the round trip amortizes
    over the window (RTT/occupancy), which is the point of the
    subsystem -- a lone query on a warm block pays at most one RTT over
    the host estimate, bounded by the admission window policy."""
    probe = _probe_search_entry(batcher, blk, req, groups_range,
                                promote_touches, default_limit)
    if probe is None or not isinstance(probe, tuple):
        return probe  # ineligible (None) or a static empty response
    key, item = probe
    return batcher.submit(key, item)


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
                              promote_touches: int = 2,
                              default_limit: int | None = None) -> list:
    """Many (blk, req, groups_range) searches from ONE caller thread,
    grouped by coalesce key and submitted together so a single worker
    draining a burst still forms full batches (the frontend's
    batch-aware dequeue lands here). Returns per-entry SearchResponse,
    None where the entry was ineligible (caller falls back), or the
    entry's own Exception (caller routes it through its per-job error
    path)."""
    out: list = [None] * len(entries)
    # batched_search_block with a one-item window would lose the mates;
    # instead lower each entry, bucket by key, and submit_many per key
    staged: dict = {}
    for i, (blk, req, groups_range) in enumerate(entries):
        probe = _probe_search_entry(batcher, blk, req, groups_range,
                                    promote_touches, default_limit)
        if probe is None:
            continue
        if isinstance(probe, tuple):
            key, item = probe
            staged.setdefault(key, []).append((i, item))
        else:  # an immediate empty response (prune / out of range)
            out[i] = probe
    for key, pairs in staged.items():
        results = batcher.submit_many(key, [it for _, it in pairs])
        for (i, _), r in zip(pairs, results):
            out[i] = r
    return out


def _probe_search_entry(batcher, blk, req, groups_range, promote_touches,
                        default_limit: int | None = None):
    """Eligibility probe shared with batched_search_block: returns
    (key, item) when batchable, a SearchResponse for static empties,
    or None to fall back. default_limit overrides db/search's module
    default for limit-less requests (TempoDBConfig.search_default_limit
    parity on the search_blocks route)."""
    from ..ops.filter import required_columns
    from ..ops.multiquery import lower_plan
    from ..util.kerneltel import TEL
    from .search import (
        _STREAM_MIN_STAGE_BYTES,
        DEFAULT_LIMIT,
        SearchResponse,
        _plan_for_block,
        _tres_eligible,
    )

    if batcher is None or not batcher.enabled:
        return None
    if not blk.meta.overlaps_time(req.start, req.end):
        return SearchResponse()
    planned = _plan_for_block(blk, req)
    if not planned.prune and groups_range is not None and planned.has_struct:
        planned = _plan_for_block(blk, req, allow_struct=False)
    if planned.prune:
        return SearchResponse()
    lowered = lower_plan(planned)
    if lowered is None:
        TEL.record_routing("search_batch", "fallback", "ineligible_plan")
        return None
    if _tres_eligible(blk, planned):
        TEL.record_routing("search_batch", "fallback", "tres_host")
        return None
    needed = required_columns(planned.conds) + list(planned.extra_cols)
    from ..block import schema as S

    span_ax = blk.pack.axes.get(S.AX_SPAN)
    n_rows = span_ax.n_rows if span_ax else 0
    n_span_cols = max(1, sum(
        1 for n in needed if n.startswith(("span.", "sattr."))))
    if n_rows * 4 * n_span_cols > _STREAM_MIN_STAGE_BYTES:
        TEL.record_routing("search_batch", "fallback", "stream_scan")
        return None
    from ..ops.stage import is_staged

    stage_cols = needed + ["trace.start_ms"]
    staged_hit = is_staged(blk, stage_cols, groups_range)
    touches = getattr(blk, "search_touches", 0)
    hot = (staged_hit
           or (groups_range is not None and getattr(blk, "device_pinned", False))
           or touches + 1 >= promote_touches)
    if not hot:
        TEL.record_routing("search_batch", "fallback", "cold_block")
        return None
    blk.search_touches = touches + 1
    item = _SearchItem(
        blk=blk, req=req, planned=planned, lowered=lowered, needed=needed,
        groups_range=list(groups_range) if groups_range is not None else None,
        limit=req.limit or default_limit or DEFAULT_LIMIT,
    )
    key = ("search", blk.meta.tenant_id, blk.meta.block_id,
           tuple(groups_range) if groups_range is not None else None,
           tuple(stage_cols), lowered.shape)
    return key, item
