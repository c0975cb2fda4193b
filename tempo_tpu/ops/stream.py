"""Cold-read streaming pipeline: overlap ranged IO, native decompress,
pad/assemble and device upload across the units of a scan.

The long-context story (SURVEY.md 5.7): a block's span axis is the
"sequence", row groups are its chunks. Like ring attention streams KV
blocks through device memory while the next block prefetches, the
pipeline keeps every stage of the cold path busy at once -- while unit
N's filter kernel runs on device, unit N+1 is uploading from the
double buffer, unit N+2 is decompressing on native threads, and unit
N+3's ranged reads are in flight. Units are row-group chunks of one
block (the streamed device eval) or whole cold blocks of one query
(the fused search/metrics host engines) -- the role of the reference's
prefetch iterators (vparquet/prefetch_iterator.go,
v2/iterator_prefetch.go), with the stages made explicit so each shows
up in kerneltel (tempo_stream_stage_seconds{stage}) and the overlap
ratio is measurable in /status/kernels.

Scheduling is budgeted, not best-effort:

  * TEMPO_STREAM_PREFETCH_DEPTH (default 3) bounds how many units run
    ahead of the consumer; depth 0 is the serial kill switch (same
    stages, inline -- the differential tests' oracle).
  * TEMPO_STREAM_MEM_BUDGET (default 256 MiB) gates admission on each
    unit's estimated host bytes (compressed fetch + decode output,
    known from footer metadata before any IO). Admission is strictly
    in unit order per pipeline and one unit always admits, so an
    oversized unit stalls its pipeline instead of deadlocking it --
    the compact_pipeline admission-gate shape on the read side.
  * TEMPO_STREAM_WORKERS sizes the shared stage executor (default
    max(4, cpu/2)). The pool is process-wide; fairness across
    concurrent pipelines comes from the per-pipeline depth bound and
    the byte gate, not from pool ownership -- this replaces the old
    module-global unbounded-fairness prefetch pool.
  * uploads are double-buffered IN ORDER: unit i uploads only once the
    consumer is within _UPLOAD_BUFFERS units of it, so at most two
    staged-but-unconsumed uploads hold device memory.

Cross-chunk correctness (the streamed device eval): a trace's spans can
straddle chunk boundaries, so evaluating the FULL trace-level tree per
chunk and OR-ing masks would drop traces whose AND-of-tracify legs hit
in different chunks. Instead each trace-level LEAF (a tracify subtree
or a trace-axis cond) aggregates across chunks first -- tracify leaves
OR their per-chunk trace hits, trace-cond leaves are chunk-invariant --
and the boolean skeleton combines the aggregated leaf vectors on host.
"""

from __future__ import annotations

import os
import threading
import time as _time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from ..block import schema as S
from ..block.reader import BackendBlock
from ..util.kerneltel import TEL
from .filter import Operands, eval_block, normalize_tree
from .stage import (
    assemble_stage,
    plan_stage,
    pool_holds,
    read_stage_columns,
    restage_from_pool,
    stage_fetch_wants,
    upload_stage,
)

DEFAULT_GROUPS_PER_CHUNK = 4
_UPLOAD_BUFFERS = 2  # staged-but-unconsumed uploads allowed (double buffer)

_DEFAULT_DEPTH = 3
_DEFAULT_MEM_BUDGET = 256 << 20


def _env_int(name: str, default: int) -> int:
    try:
        v = os.environ.get(name, "")
        return int(v) if v else default
    except ValueError:
        return default


def prefetch_depth() -> int:
    """Units the pipeline runs ahead of the consumer; 0 = serial."""
    return max(0, _env_int("TEMPO_STREAM_PREFETCH_DEPTH", _DEFAULT_DEPTH))


def mem_budget() -> int:
    return max(1, _env_int("TEMPO_STREAM_MEM_BUDGET", _DEFAULT_MEM_BUDGET))


_pool: ThreadPoolExecutor | None = None
_pool_lock = threading.Lock()


def _executor() -> ThreadPoolExecutor:
    """The shared stage executor, sized once (TEMPO_STREAM_WORKERS).
    Context-propagating (util/ctxpool): stage timings/spans recorded on
    pool threads keep the submitting query's ambient self-trace +
    affinity placement."""
    global _pool
    with _pool_lock:
        if _pool is None:
            from ..util.ctxpool import ContextThreadPool

            workers = _env_int("TEMPO_STREAM_WORKERS", 0)
            if workers <= 0:
                workers = max(4, (os.cpu_count() or 8) // 2)
            _pool = ContextThreadPool(
                max_workers=workers, thread_name_prefix="stream-stage")
        return _pool


class _ByteGate:
    """Process-wide admission budget over every stream pipeline's
    in-flight units. A unit holds its estimate from admission until its
    stages finish (fetched bytes + decode buffers are host RAM for
    exactly that window). Admission order within a pipeline is strictly
    unit order (_PipeState.wait_admit_turn), so a pipeline's later
    units can never hold budget while its head waits -- the classic
    inversion deadlock. A unit always admits when nothing is in flight,
    so one oversized unit stalls, never deadlocks."""

    def __init__(self):
        self._cv = threading.Condition()
        self._bytes = 0
        self._holders = 0
        self.peak_bytes = 0  # high-water mark (tests + /status)

    def acquire(self, n: int, cancelled: threading.Event | None) -> bool:
        with self._cv:
            while True:
                if cancelled is not None and cancelled.is_set():
                    return False
                if self._holders == 0 or self._bytes + n <= mem_budget():
                    self._bytes += n
                    self._holders += 1
                    if self._bytes > self.peak_bytes:
                        self.peak_bytes = self._bytes
                    TEL.stream_inflight(self._bytes)
                    return True
                # re-check on release notifications; the timeout only
                # guards against a lost cancellation wakeup
                self._cv.wait(0.05)

    def release(self, n: int) -> None:
        with self._cv:
            self._bytes -= n
            self._holders -= 1
            TEL.stream_inflight(self._bytes)
            self._cv.notify_all()

    def inflight_bytes(self) -> int:
        with self._cv:
            return self._bytes


_GATE = _ByteGate()


@dataclass
class StreamUnit:
    """One pipeline unit: a (block, columns, row-group slice) read.
    upload=True stages padded device columns (the streamed device eval);
    upload=False stops after fetch+decompress, leaving the columns
    cache-resident for a host engine (the cold fused-search path)."""

    blk: BackendBlock
    needed: list[str]
    groups: list[int] | None = None  # None = whole block
    upload: bool = True
    est_bytes: int = 0  # filled at plan time (admission gate)
    index: int = 0  # position in its pipeline (set by _run_unit; the
    # upload turnstile orders the double buffer by it)
    pool_hit: bool = False  # plan-time host chunk-pool probe hit: the
    # fetch/decompress/assemble stages are skipped (ops/chunkpool)


class _PipeState:
    """Per-pipeline coordination: ordered admission, ordered
    double-buffered upload, consumer progress, cancellation."""

    def __init__(self):
        self._cv = threading.Condition()
        self._admitted = 0  # units past the admission turnstile
        self._consumed = 0  # units the consumer is done with
        self.cancelled = threading.Event()

    def wait_admit_turn(self, i: int) -> bool:
        with self._cv:
            while not self.cancelled.is_set() and i != self._admitted:
                self._cv.wait(0.05)
            return not self.cancelled.is_set()

    def admit_done(self) -> None:
        with self._cv:
            self._admitted += 1
            self._cv.notify_all()

    def wait_upload_turn(self, i: int) -> bool:
        """Unit i may upload once the consumer is within
        _UPLOAD_BUFFERS units: device memory holds at most two staged
        uploads the filter hasn't consumed yet."""
        with self._cv:
            while (not self.cancelled.is_set()
                   and i >= self._consumed + _UPLOAD_BUFFERS):
                self._cv.wait(0.05)
            return not self.cancelled.is_set()

    def advance(self) -> None:
        with self._cv:
            self._consumed += 1
            self._cv.notify_all()

    def cancel(self) -> None:
        self.cancelled.set()
        with self._cv:
            self._cv.notify_all()


def _unit_groups(u: StreamUnit) -> list[int]:
    span_ax = u.blk.pack.axes.get(S.AX_SPAN)
    if u.groups is not None:
        return u.groups
    return list(range(span_ax.n_groups)) if span_ax else []


def _plan_unit(u: StreamUnit):
    """(stage plan, column-fetch plan) for a unit -- footer metadata
    only, no IO; fills u.est_bytes for the admission gate. Upload units
    probe the host chunk pool (ops/chunkpool) first: a warm entry means
    no backend ranged read to plan and no admission bytes to hold."""
    if u.upload:
        plan = plan_stage(u.needed)
        # ONE key shape shared with ops/stage (a pool entry is a device
        # column), so columns its evictions demoted restage here
        if pool_holds(u.blk, u.needed, u.groups):
            u.pool_hit = True
            u.est_bytes = 0
            return plan, None
        wants = stage_fetch_wants(u.blk, plan, u.groups)
    else:
        plan = None
        wants = [(n, None) for n in u.needed]
    cf = u.blk.pack.plan_fetch(wants)
    u.est_bytes = cf.est_bytes if cf is not None else 0
    return plan, cf


def _run_stages(u: StreamUnit, plan, cf, state: _PipeState | None):
    """fetch -> decompress -> assemble -> upload for one unit, with
    per-stage kerneltel timings. state=None runs without cancellation
    checks (the serial path)."""
    pack = u.blk.pack
    if u.upload and u.pool_hit:
        if state is not None and not state.wait_upload_turn(u.index):
            return None  # cancelled before the restage upload
        with TEL.stage("stream:upload") as up:
            staged = restage_from_pool(u.blk, u.needed, u.groups)
            up.counted = staged is not None
        if staged is not None:
            return staged
        # evicted between plan and run: late-plan the cold fetch and
        # fall through to the normal stages (est_bytes stays 0 -- the
        # gate's one-always-admits rule bounds the raced unit)
        u.pool_hit = False
        cf = pack.plan_fetch(stage_fetch_wants(u.blk, plan, u.groups))
    with TEL.stage("stream:fetch"):
        if cf is not None:
            pack.fetch_ranges(cf)
    if state is not None and state.cancelled.is_set():
        return None
    with TEL.stage("stream:decompress"):
        if cf is not None:
            pack.decode_fetched(cf)
        if not u.upload:
            return True  # columns are cache-resident; host engines read them
        groups = _unit_groups(u)
        host, n_res = read_stage_columns(u.blk, plan, groups)
    if state is not None and state.cancelled.is_set():
        return None
    with TEL.stage("stream:assemble"):
        staged, padded, real_rows = assemble_stage(u.blk, plan, groups, host, n_res)
    if state is not None and not state.wait_upload_turn(u.index):
        return None  # cancelled: no device work for abandoned units
    with TEL.stage("stream:upload"):
        upload_stage(u.blk, plan, staged, padded, real_rows)
    return staged


def _run_unit(u: StreamUnit, i: int, state: _PipeState):
    """One unit through admission + stages on a pool worker."""
    u.index = i
    if not state.wait_admit_turn(i):
        TEL.record_stream_unit("cancelled")
        return None
    ok = False
    try:
        plan, cf = _plan_unit(u)
        ok = _GATE.acquire(u.est_bytes, state.cancelled)
    except BaseException:
        TEL.record_stream_unit("error")
        raise
    finally:
        # unblock the next unit's turnstile on EVERY exit -- a planning
        # error here must fail this unit, not stall the whole pipeline
        # (HostPrefetch callers wait() with no timeout)
        state.admit_done()
    if not ok:
        TEL.record_stream_unit("cancelled")
        return None
    try:
        out = _run_stages(u, plan, cf, state)
        TEL.record_stream_unit(
            "cancelled" if state.cancelled.is_set() and out is None else "ok")
        return out
    except BaseException:
        TEL.record_stream_unit("error")
        raise
    finally:
        _GATE.release(u.est_bytes)


def stream_staged(units: list[StreamUnit], depth: int | None = None):
    """THE pipelined iterator: yields (unit, result) strictly in unit
    order while later units' stages run ahead. result is a StagedBlock
    for upload units, True for host units (their columns are left
    cache-resident). Results are bit-identical to running the same
    units serially -- the pipeline reorders WORK, never data.

    On error or early close, every in-flight future is cancelled or
    drained and admission bytes return to the gate: no leaked device
    work, no leaked budget."""
    if depth is None:
        depth = prefetch_depth()
    t_run = _time.perf_counter()
    if depth <= 0 or len(units) <= 1:
        # serial kill switch / degenerate pipeline: same stages, inline
        try:
            for u in units:
                plan, cf = _plan_unit(u)
                try:
                    out = _run_stages(u, plan, cf, None)
                except BaseException:
                    TEL.record_stream_unit("error")
                    raise
                TEL.record_stream_unit("ok")
                yield u, out
        finally:
            TEL.record_stream_run(_time.perf_counter() - t_run)
        return
    state = _PipeState()
    pool = _executor()
    futures = []

    def submit(i: int) -> None:
        futures.append(pool.submit(_run_unit, units[i], i, state))

    try:
        for i in range(min(depth + 1, len(units))):
            submit(i)
        for i in range(len(units)):
            res = futures[i].result()
            yield units[i], res
            state.advance()  # consumer done with unit i
            nxt = i + depth + 1
            if nxt < len(units):
                submit(nxt)
    finally:
        state.cancel()
        for f in futures:
            f.cancel()
        for f in futures:
            if not f.cancelled():
                try:
                    f.exception()  # drain started futures; nothing leaks
                except BaseException:  # noqa: BLE001 - already surfaced
                    pass
        TEL.record_stream_run(_time.perf_counter() - t_run)


class HostPrefetch:
    """Handle over a host-flavor pipeline run (upload=False units): the
    cold blocks' fetch+decompress stages run ahead on the stream
    executor while the caller's host engines evaluate blocks as their
    columns land. wait(blk) returns True once that block's columns are
    cache-resident, False if the unit errored or was cancelled first
    (callers then read the normal way, which surfaces any real error
    itself). Host units never touch the device and never wait on the
    consumer, so every unit is submitted up front -- the admission
    turnstile + byte gate bound the actual in-flight work."""

    def __init__(self, items: list[tuple[BackendBlock, list[str]]]):
        self._state = _PipeState()
        self._lock = threading.Lock()
        self._done: dict[int, threading.Event] = {}
        self._ok: dict[int, bool] = {}
        self._t0 = _time.perf_counter()
        self._futures: list = []
        self._remaining = 0
        if prefetch_depth() <= 0:
            # serial kill switch: every wait() misses, so callers run
            # their own inline reads -- the differential tests' oracle
            return
        units = []
        for blk, names in items:
            if id(blk) in self._done:
                continue
            units.append(StreamUnit(blk, list(names), None, upload=False))
            self._done[id(blk)] = threading.Event()
            self._ok[id(blk)] = False
        self._remaining = len(units)
        pool = _executor()
        self._futures = [pool.submit(self._run, u, i)
                         for i, u in enumerate(units)]

    def _run(self, u: StreamUnit, i: int) -> None:
        ok = False
        try:
            ok = _run_unit(u, i, self._state) is not None
        except BaseException:  # noqa: BLE001 - the caller's own read re-raises
            ok = False
        finally:
            self._ok[id(u.blk)] = ok
            self._done[id(u.blk)].set()
            with self._lock:
                self._remaining -= 1
                last = self._remaining == 0
            if last:
                TEL.record_stream_run(_time.perf_counter() - self._t0)

    def wait(self, blk: BackendBlock, timeout: float | None = None) -> bool:
        ev = self._done.get(id(blk))
        if ev is None:
            return False
        ev.wait(timeout)
        return self._ok.get(id(blk), False)

    def close(self) -> None:
        """Cancel outstanding work (idempotent); never strands a
        waiter."""
        self._state.cancel()
        cancelled = sum(1 for f in self._futures if f.cancel())
        self._futures = []
        for ev in self._done.values():
            ev.set()
        if cancelled:
            # queued units whose _run will never execute still owe
            # their _remaining decrement, else the run is never
            # recorded and overlap ratio drifts up after errored runs
            with self._lock:
                self._remaining -= cancelled
                last = self._remaining == 0
            if last:
                TEL.record_stream_run(_time.perf_counter() - self._t0)


def staged_warm(blk: BackendBlock, names: list[str]) -> None:
    """Single-unit inline form of the pipeline's fetch+decompress
    stages: one coalesced ranged read + one threaded decode into the
    pack's caches, with the stage timings recorded (colio._run_plan).
    The cold path of callers that handle one block at a time (per-block
    search shards, the metrics executor)."""
    blk.pack.warm_columns(names)


def _chunks(n: int, per: int) -> list[list[int]]:
    return [list(range(i, min(i + per, n))) for i in range(0, n, per)]


def _split_leaves(tree):
    """Trace-level tree -> (skeleton, leaves). Leaves are tracify
    subtrees or trace-cond nodes; skeleton nodes are ('and'|'or', ...)
    over ('leaf', j)."""
    leaves: list = []

    def walk(t):
        if t[0] in ("tracify", "cond"):
            leaves.append(t)
            return ("leaf", len(leaves) - 1)
        return (t[0],) + tuple(walk(ch) for ch in t[1:])

    return walk(tree), leaves


def eval_block_streamed(
    blk: BackendBlock,
    needed: list[str],
    tree_conds,
    operands: Operands,
    groups: list[int] | None = None,
    groups_per_chunk: int = DEFAULT_GROUPS_PER_CHUNK,
    return_device: bool = False,
):
    """Evaluate a condition tree over a block by streaming row-group
    chunks through the device pipeline. Returns (trace_mask (n_traces,),
    span_count (n_traces,), n_spans_seen) as numpy -- or, with
    return_device, (trace_mask_dev, counts_dev, n_spans_seen) as PADDED
    device arrays with no host sync at all: the caller's top-k selector
    (ops/select.py) does the single fetch."""
    tree, conds = tree_conds
    if tree is not None:
        tree = normalize_tree(tree, conds)
        skeleton, leaves = _split_leaves(tree)
        # union-of-span-subtrees tree for per-trace matched-span counts
        span_subs = [lf[1] for lf in leaves if lf[0] == "tracify"]
        if span_subs:
            count_tree = ("tracify", span_subs[0] if len(span_subs) == 1
                          else ("or",) + tuple(span_subs))
        else:
            count_tree = None
    else:
        skeleton, leaves, count_tree = None, [], None

    span_ax = blk.pack.axes.get("span")
    all_groups = groups if groups is not None else list(
        range(span_ax.n_groups if span_ax else 1)
    )
    chunk_groups = [[all_groups[i] for i in c]
                    for c in _chunks(len(all_groups), groups_per_chunk)]

    n_traces = blk.meta.total_traces
    # accumulate ON DEVICE: per-chunk results stay resident and fold with
    # async device ops; the host syncs exactly once at the end. Pulling
    # each chunk's mask back would cost a device->host round trip per
    # chunk, which dominates when the interconnect has high latency.
    leaf_hits: list = [None for _ in leaves]
    counts_dev = None
    n_spans_seen = 0

    def run_tree(t, staged):
        tm, sc = eval_block(
            (t, conds), staged.cols, operands,
            staged.n_spans, staged.n_traces,
            staged.n_spans_b, staged.n_res_b, staged.n_traces_b,
            span_out=False,
        )
        return tm, sc  # device arrays, padded (n_traces_b,)

    TEL.record_routing("stream", "device", "chunked")
    t0_stream = _time.perf_counter()

    single_tracify = sum(1 for lf in leaves if lf[0] == "tracify") == 1
    # the streamed path exists because staging the whole block exceeds
    # the device budget, so chunks never enter the staged cache (the
    # byte-budget LRU would evict them before reuse); the pipeline's own
    # double buffer bounds device memory instead
    units = [StreamUnit(blk, needed, cg, upload=True) for cg in chunk_groups]
    it = stream_staged(units)
    try:
        for ci, (_unit, staged) in enumerate(it):
            if tree is None:
                tm, sc = run_tree(None, staged)
                counts_dev = sc if counts_dev is None else counts_dev + sc
            else:
                for j, leaf in enumerate(leaves):
                    if leaf[0] == "cond" and ci > 0:
                        continue  # trace-axis conds are chunk-invariant
                    tm, sc = run_tree(leaf, staged)
                    leaf_hits[j] = tm if leaf_hits[j] is None else leaf_hits[j] | tm
                    if single_tracify and leaf[0] == "tracify":
                        counts_dev = sc if counts_dev is None else counts_dev + sc
                if not single_tracify:
                    _, sc = run_tree(count_tree, staged)
                    counts_dev = sc if counts_dev is None else counts_dev + sc
            n_spans_seen += staged.n_spans
    finally:
        it.close()  # abandoned prefetch on error mustn't leak device work
    # whole-pipeline window (IO overlap included): the per-chunk filter
    # kernels already record their own launches/compiles via eval_block
    TEL.observe_device("stream", len(chunk_groups), t0_stream)

    if return_device:
        import jax.numpy as jnp

        if counts_dev is None:
            counts_dev = jnp.zeros(max(n_traces, 1), dtype=jnp.int32)
        nb = counts_dev.shape[0]
        valid = jnp.arange(nb, dtype=jnp.int32) < n_traces
        if tree is None:
            tm_dev = (counts_dev > 0) & valid
        else:
            def evd(sk):
                if sk[0] == "leaf":
                    h = leaf_hits[sk[1]]
                    return h if h is not None else jnp.zeros(nb, dtype=bool)
                vals = [evd(ch) for ch in sk[1:]]
                out = vals[0]
                for v in vals[1:]:
                    out = (out & v) if sk[0] == "and" else (out | v)
                return out

            tm_dev = evd(skeleton) & valid
        return tm_dev, counts_dev, n_spans_seen

    counts = (
        np.asarray(counts_dev)[:n_traces].astype(np.int64)
        if counts_dev is not None
        else np.zeros(n_traces, dtype=np.int64)
    )
    if tree is None:
        trace_mask = counts > 0
    else:
        hits_np = [
            np.asarray(h)[:n_traces] if h is not None else np.zeros(n_traces, bool)
            for h in leaf_hits
        ]

        def ev(sk):
            if sk[0] == "leaf":
                return hits_np[sk[1]]
            vals = [ev(ch) for ch in sk[1:]]
            out = vals[0]
            for v in vals[1:]:
                out = (out & v) if sk[0] == "and" else (out | v)
            return out

        trace_mask = ev(skeleton)
    return trace_mask, np.where(trace_mask, counts, 0), n_spans_seen
