"""Stage vtpu block columns onto the device for filtering.

Reads only the columns a condition set needs (ops.filter.required_columns),
optionally only a row-group range (the unit of search-job sharding,
mirroring the reference's StartPage/TotalPages jobs,
modules/frontend/searchsharding.go), pads every axis to its power-of-two
bucket, and uploads. Staged device arrays are cached on the (immutable)
block object keyed by (column set, group range), so repeated queries
against a hot block skip IO, decompression, AND the host->device
transfer -- the device-memory analog of the reference's page cache +
memcached layers, and the biggest win when the host<->device link has
high latency."""

from __future__ import annotations

import weakref
from collections import OrderedDict
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..block import schema as S
from ..block.reader import BackendBlock
from ..util.profiler import timed_lock
from .device import PAD_I32, bucket, pad_rows, scoped

_CACHE_MAX_ENTRIES = 32  # per block
_CACHE_MAX_ENTRY_BYTES = 256 << 20

# aggregate device-memory budget across EVERY block's staged cache: an
# LRU over (block, entry) pairs, so a wide working set evicts the
# coldest block's columns instead of growing until HBM OOMs
_GLOBAL_CACHE_BUDGET = 4 << 30
# a cataloged hot lock: TEMPO_LOCK_PROFILE arms contention timing
# (tempo_lock_wait_seconds{lock="stage_lru"}); off = a raw Lock
_lru_lock = timed_lock("stage_lru")
_lru: OrderedDict[tuple[int, tuple], tuple] = OrderedDict()  # -> (blk weakref, nbytes)
_lru_bytes = 0

# HBM-evicted entries awaiting demotion into the host chunk pool
# (ops/chunkpool): collected under _lru_lock, compressed OUTSIDE it --
# the D2H pull + codec work is milliseconds, the lock guards
# microsecond bookkeeping
_pending_demote: list[tuple[str, tuple, object]] = []


def staged_cache_stats(max_entries: int = 32) -> dict:
    """Point-in-time view of the device staged-column cache for
    /status/kernels: aggregate occupancy plus the hottest (most recently
    touched) entries' shape."""
    with _lru_lock:
        items = list(_lru.items())
        total = _lru_bytes
        budget = _GLOBAL_CACHE_BUDGET
    entries = []
    for (_bid, key), (wr, nbytes) in reversed(items[-max_entries:]):
        blk = wr()
        cols, groups = key
        entries.append({
            "block_id": getattr(getattr(blk, "meta", None), "block_id", "")[:8],
            "columns": len(cols),
            "groups": list(groups) if groups is not None else None,
            "nbytes": int(nbytes),
        })
    return {"entries": len(items), "bytes": int(total),
            "budget_bytes": int(budget), "hottest": entries}


def set_staged_cache_budget(n_bytes: int) -> None:
    global _GLOBAL_CACHE_BUDGET
    with _lru_lock:
        # budget write must be inside the lock: an eviction pass racing
        # an unlocked shrink could evict against the stale budget and
        # leave the cache over the new one
        _GLOBAL_CACHE_BUDGET = n_bytes
        _evict_over_budget_locked()
    _drain_demotions()


def _sweep_dead_locked() -> None:
    """Drop entries whose block weakref has died: their device arrays
    are gone, so leaving their nbytes in _lru_bytes would make the HBM
    budget evict live columns to pay for freed ones. Called under the
    lock on every insert and eviction pass."""
    global _lru_bytes
    dead = [k for k, (wr, _) in _lru.items() if wr() is None]
    for k in dead:
        _lru_bytes -= _lru.pop(k)[1]


def _lru_touch(blk, key: tuple, nbytes: int) -> None:
    global _lru_bytes
    k = (id(blk), key)
    with _lru_lock:
        existing = _lru.get(k)
        if existing is not None:
            if existing[0]() is blk:
                _lru.move_to_end(k)
                return
            # id() reuse after the old block was GC'd: replace the stale
            # entry and its accounting
            _lru_bytes -= existing[1]
            del _lru[k]
        _lru[k] = (weakref.ref(blk), nbytes)
        _lru_bytes += nbytes
        # the eviction pass sweeps dead weakrefs first, so every insert
        # restores the accounting invariant in one O(n) scan
        _evict_over_budget_locked()
    _drain_demotions()


def _lru_drop(blk, key: tuple) -> None:
    """Per-block cap evictions must release their global accounting."""
    global _lru_bytes
    k = (id(blk), key)
    with _lru_lock:
        entry = _lru.pop(k, None)
        if entry is not None:
            _lru_bytes -= entry[1]


def _evict_over_budget_locked() -> None:
    global _lru_bytes
    _sweep_dead_locked()  # freed arrays must not force live evictions
    while _lru_bytes > _GLOBAL_CACHE_BUDGET and len(_lru) > 1:
        (_bid, key), (wr, nbytes) = _lru.popitem(last=False)
        _lru_bytes -= nbytes
        blk = wr()
        if blk is not None:
            store = getattr(blk, "_staged_cache", None)
            if store is not None:
                staged = store.pop(key, None)
                if staged is not None:
                    # Tier B demotion candidate: the padded device
                    # arrays still exist here -- park them for the
                    # post-lock compress instead of discarding
                    block_id = getattr(
                        getattr(blk, "meta", None), "block_id", "") or ""
                    if block_id:
                        _pending_demote.append((block_id, key, staged))


def _miss_reason(store: dict | None, key: tuple) -> str:
    """Why a staged-cache lookup missed, from what the block holds now:
    everything asked for is staged under another key; some of it is;
    none of it is (never was, or was evicted). Other threads insert and
    evict while this reads, so it works on a copy of the keys and gives
    "" rather than raise into the query path."""
    try:
        cols, groups = set(key[0]), key[1]
        have = [set(k[0]) for k in list(store or ()) if k[1] == groups]
    except Exception:
        return ""
    if any(cols <= h for h in have):
        return "key_mismatch"
    if any(cols & h for h in have):
        return "partial_columns"
    return "not_staged"


def _drain_demotions() -> None:
    """Compress HBM-evicted entries into the host chunk pool. Called by
    every path that may have run an eviction pass, AFTER _lru_lock is
    released. With TEMPO_CHUNK_CACHE=0 the pool refuses every entry and
    eviction degrades to exactly the old discard."""
    if not _pending_demote:
        return
    with _lru_lock:
        victims = list(_pending_demote)
        _pending_demote.clear()
    if not victims:
        return
    from . import chunkpool

    for block_id, key, staged in victims:
        chunkpool.demote(block_id, key, staged)

# absolute-seconds origin (2020-01-01 UTC) for the derived trace@gkey_s
# column: a global trace start time in int32 seconds (valid until 2088)
# that orders traces ACROSS blocks -- per-block relative ms don't
GKEY_ORIGIN_S = 1_577_836_800


def gkey_from_start_ms(meta, start_ms):
    """The cross-block top-k ordering key (trace@gkey_s convention):
    absolute seconds since GKEY_ORIGIN_S, derived from a block's
    relative start_ms column. ONE definition -- the staged device
    column and the host raw-select path must order identically."""
    import numpy as np

    base_s = meta.start_time_unix_nano // 1_000_000_000 - GKEY_ORIGIN_S
    return np.asarray(start_ms).astype(np.int64) // 1000 + base_s

@jax.jit
@scoped("res_to_span")
def _res_to_span(res_vals, res_idx):
    """Broadcast a res-axis column to span rows; PAD where no resource."""
    out = res_vals[jnp.clip(res_idx, 0, res_vals.shape[0] - 1)]
    return jnp.where(res_idx >= 0, out, PAD_I32)


_AXIS_OF = {
    "span": S.AX_SPAN,
    "sattr": S.AX_SATTR,
    "rattr": None,  # res-axis tables are small: always loaded whole
    "res": None,
    "trace": None,
}


@dataclass
class StagedBlock:
    n_spans: int
    n_traces: int
    n_res: int
    n_spans_b: int
    n_traces_b: int
    n_res_b: int
    span_base: int  # global row of first staged span (group-range staging)
    cols: dict[str, jnp.ndarray] = field(default_factory=dict)


@dataclass
class StagePlan:
    """The name bookkeeping stage_block used to do inline, precomputed
    so the pipeline can run the read / assemble / upload phases on
    different schedules."""

    read_names: list[str]  # real pack columns to read
    materialize: list[str]  # res columns to broadcast to span level
    want_gkey: bool
    start_ms_for_gkey_only: bool


def plan_stage(needed: list[str]) -> StagePlan:
    materialize = [n.split("@", 1)[1] for n in needed if n.startswith("span@")]
    want_gkey = "trace@gkey_s" in needed
    read_names = [n for n in needed if not n.startswith(("span@", "trace@"))]
    start_ms_for_gkey_only = want_gkey and "trace.start_ms" not in read_names
    if start_ms_for_gkey_only:
        read_names = read_names + ["trace.start_ms"]
    return StagePlan(read_names, materialize, want_gkey, start_ms_for_gkey_only)


def stage_fetch_wants(blk: BackendBlock, plan: StagePlan,
                      groups: list[int] | None) -> list[tuple[str, list[int] | None]]:
    """The (column, groups) set the read phase will touch, in
    ColumnPack.plan_fetch form -- the pipeline's fetch/decompress stages
    warm exactly these so read_stage_columns is pure cache assembly."""
    span_ax = blk.pack.axes.get(S.AX_SPAN)
    sliced = span_ax is not None and span_ax.n_groups > 0 and groups is not None
    wants: list[tuple[str, list[int] | None]] = []
    for name in plan.read_names:
        ax = _AXIS_OF.get(name.split(".", 1)[0])
        wants.append((name, list(groups) if (ax is not None and sliced) else None))
    return wants


def read_stage_columns(blk: BackendBlock, plan: StagePlan,
                       groups: list[int]) -> tuple[dict, int]:
    """The host-read phase: raw columns (sliced to `groups` on their
    axis) + the res-axis row count."""
    from ..util.kerneltel import TEL

    pack = blk.pack
    span_ax = pack.axes[S.AX_SPAN]
    host: dict[str, np.ndarray] = {}
    n_res = 0
    with TEL.stage("stage:read_columns", columns=len(plan.read_names)):
        for name in plan.read_names:
            pref = name.split(".", 1)[0]
            ax = _AXIS_OF.get(pref)
            if ax is None:
                arr = pack.read(name)
            else:
                arr = pack.read_groups(name, groups) if span_ax.n_groups else pack.read(name)
            host[name] = arr
    for name, arr in host.items():
        if name.startswith("res."):
            n_res = max(n_res, arr.shape[0])
    return host, n_res


def stage_block(
    blk: BackendBlock,
    needed: list[str],
    groups: list[int] | None = None,
    cache: bool = True,
) -> StagedBlock:
    """Load `needed` columns (padded, on device). If `groups` is given,
    span/sattr-axis columns cover only those contiguous row groups.
    Results cache on the block object (blocks are immutable)."""
    from ..util.kerneltel import TEL

    key = (tuple(needed), tuple(groups) if groups is not None else None)
    store: dict | None = getattr(blk, "_staged_cache", None) if cache else None
    hit = store.get(key) if store is not None else None
    reason = "" if hit is not None or not cache else _miss_reason(store, key)
    with TEL.stage("stage:lookup", hit=hit is not None, reason=reason):
        if hit is not None:
            TEL.staged_cache_hits.inc()
            # attribute the hit to the dequeue placement of the job
            # asking (own/steal/unowned): the affinity scheduler's
            # whole point is moving this ratio
            TEL.record_staged_lookup(True)
            _lru_touch(blk, key, sum(a.nbytes for a in hit.cols.values()))
            return hit
    if cache:
        TEL.staged_cache_misses.inc()
        TEL.record_staged_lookup(False)
        # Tier B probe: a previous HBM eviction may have demoted exactly
        # this (block, columns, groups) entry into the host chunk pool
        # -- restaging from there skips the backend ranged read, the
        # column decode AND the pad/assemble phase
        block_id = getattr(blk.meta, "block_id", "") or ""
        if block_id:
            from . import chunkpool

            chunkpool.note_stage(block_id, key)
            warm = chunkpool.restage(block_id, key)
            if warm is not None:
                _cache_insert(blk, key, warm)
                return warm
    plan = plan_stage(needed)
    span_ax = blk.pack.axes[S.AX_SPAN]
    if groups is None:
        groups = list(range(span_ax.n_groups))
    host, n_res = read_stage_columns(blk, plan, groups)
    staged, padded, real_rows = assemble_stage(blk, plan, groups, host, n_res)
    upload_stage(blk, plan, staged, padded, real_rows)
    if cache:
        _cache_insert(blk, key, staged)
    return staged


def _cache_insert(blk: BackendBlock, key: tuple, staged: StagedBlock) -> None:
    """Admit a freshly staged (or pool-restaged) entry into the
    per-block store + global LRU; a per-block cap victim demotes into
    the host chunk pool the same way budget evictions do."""
    nbytes = sum(a.nbytes for a in staged.cols.values())
    if nbytes > _CACHE_MAX_ENTRY_BYTES:
        return
    store = getattr(blk, "_staged_cache", None)
    if store is None:
        store = {}
        blk._staged_cache = store
    if len(store) >= _CACHE_MAX_ENTRIES:
        victim = next(iter(store))
        vstaged = store.pop(victim)
        _lru_drop(blk, victim)
        block_id = getattr(blk.meta, "block_id", "") or ""
        if block_id and vstaged is not None:
            from . import chunkpool

            chunkpool.demote(block_id, victim, vstaged)
    store[key] = staged
    _lru_touch(blk, key, nbytes)


def assemble_stage(blk: BackendBlock, plan: StagePlan, groups: list[int],
                   host: dict, n_res: int) -> tuple[StagedBlock, dict, dict]:
    """The pad/assemble phase: owner-offset transforms, derived columns,
    bucket padding. Pure host numpy -- no IO, no device."""
    from ..util.kerneltel import TEL

    with TEL.stage("stage:assemble", block=blk.meta.block_id[:8]):
        return _assemble(blk, plan, groups, host, n_res)


def _assemble(blk, plan, groups, host, n_res):
    host = dict(host)  # owner-offset transforms mutate; callers may retry
    pack = blk.pack
    span_ax = pack.axes[S.AX_SPAN]
    span_base = span_ax.offsets[groups[0]] if groups else 0
    span_hi = span_ax.offsets[groups[-1] + 1] if groups else 0
    n_spans = span_hi - span_base
    n_traces = blk.meta.total_traces

    n_spans_b = bucket(max(n_spans, 1))
    n_traces_b = bucket(max(n_traces, 1))
    n_res_b = bucket(max(n_res, 1))

    want_gkey = plan.want_gkey
    start_ms_for_gkey_only = plan.start_ms_for_gkey_only

    staged = StagedBlock(
        n_spans=n_spans,
        n_traces=n_traces,
        n_res=n_res,
        n_spans_b=n_spans_b,
        n_traces_b=n_traces_b,
        n_res_b=n_res_b,
        span_base=span_base,
    )
    # owner-offset columns: rows of every child table are grouped by
    # owner, so the kernel aggregates with cumsum + offset gathers
    # (ops/filter._offset_counts) -- the owner row columns themselves
    # never need to reach the device.
    real_rows: dict[str, int] = {}  # pre-padding lengths (telemetry)
    if "sattr.span" in host:
        owners = np.clip(host["sattr.span"] - span_base, 0, max(n_spans, 1) - 1)
        cnt = np.bincount(owners, minlength=max(n_spans, 1)) if owners.size else np.zeros(
            max(n_spans, 1), dtype=np.int64
        )
        off = np.concatenate([[0], np.cumsum(cnt)]).astype(np.int32)
        real_rows["sattr.off"] = int(off.shape[0])
        host["sattr.off"] = pad_rows(off, n_spans_b + 1, off[-1] if off.size else 0)
        del host["sattr.span"]
    if "rattr.res" in host:
        owners = np.clip(host["rattr.res"], 0, max(n_res, 1) - 1)
        cnt = np.bincount(owners, minlength=max(n_res, 1)) if owners.size else np.zeros(
            max(n_res, 1), dtype=np.int64
        )
        off = np.concatenate([[0], np.cumsum(cnt)]).astype(np.int32)
        real_rows["rattr.off"] = int(off.shape[0])
        host["rattr.off"] = pad_rows(off, n_res_b + 1, off[-1] if off.size else 0)
        del host["rattr.res"]  # superseded on device by the offsets

    if want_gkey:
        # derived column: the cross-block top-k ordering key
        host["trace@gkey_s"] = gkey_from_start_ms(
            blk.meta, host["trace.start_ms"]).astype(np.int32)
        if start_ms_for_gkey_only:
            host.pop("trace.start_ms", None)  # read only to derive the key

    padded: dict[str, np.ndarray] = {}
    for name, arr in host.items():
        pref = name.split(".", 1)[0].split("@", 1)[0]
        if name == "trace.span_off":
            # rebase global span rows to the staged slice; padded trace
            # rows collapse to empty segments (count 0)
            arr = (np.clip(arr, span_base, span_hi) - span_base).astype(np.int32)
            arr = pad_rows(arr, n_traces_b + 1, arr[-1] if arr.size else 0)
        elif name in ("sattr.off", "rattr.off"):
            pass  # already padded above
        elif name == "trace@gkey_s":
            arr = pad_rows(arr, n_traces_b, np.int32(-(2**31)))
        elif pref == "span":
            arr = pad_rows(arr, n_spans_b, PAD_I32)
        elif pref == "sattr":
            arr = pad_rows(arr, bucket(max(arr.shape[0], 1)), PAD_I32)
        elif pref == "rattr":
            arr = pad_rows(arr, bucket(max(arr.shape[0], 1)), PAD_I32)
        elif pref == "res":
            arr = pad_rows(arr, n_res_b, PAD_I32)
        elif pref == "trace":
            if arr.dtype in (np.int32, np.float32):
                arr = pad_rows(arr, n_traces_b, PAD_I32 if arr.dtype == np.int32 else np.float32(0))
            else:
                continue  # host-only trace columns are not staged
        padded[name] = arr
    # complete the per-column real (pre-padding) row counts for the
    # upload phase's padding-waste telemetry
    real_full = {n: real_rows.get(n, int(host[n].shape[0])) for n in padded}
    return staged, padded, real_full


def upload_stage(blk: BackendBlock, plan: StagePlan, staged: StagedBlock,
                 padded: dict, real_rows: dict) -> StagedBlock:
    """The host->device phase: one batched transfer + the query-
    independent res->span materialization."""
    from ..util.kerneltel import TEL

    nbytes = sum(int(a.nbytes) for a in padded.values())
    # THE host->device transfer, whether a warm staging miss or a
    # stream-pipeline unit (whose `stream:upload` stage is around this
    # whole call: ops/stream)
    with TEL.stage("stage:upload", bytes=nbytes, block=blk.meta.block_id[:8]):
        # ONE batched transfer for the whole block: per-array device_puts
        # each pay their own dispatch + link round trip
        staged.cols = dict(zip(padded, jax.device_put(list(padded.values()))))
    # telemetry: upload volume + padding waste (padded vs real rows
    # summed per column -- columns live on different axes)
    TEL.record_transfer(
        nbytes,
        sum(real_rows.values()),
        sum(int(a.shape[0]) for a in padded.values()),
    )

    # materialize requested res columns at SPAN level: the res->span
    # broadcast gather is query-independent, so paying it once here
    # (cached with the staged entry) removes a span-length random gather
    # -- one of the most expensive TPU ops -- from every query's kernel
    if plan.materialize and "span.res_idx" in staged.cols:
        for name in plan.materialize:
            if name in staged.cols:
                staged.cols[f"span@{name}"] = _res_to_span(
                    staged.cols[name], staged.cols["span.res_idx"]
                )
    return staged
