"""Stage vtpu block columns onto the device for filtering.

Reads only the columns a condition set needs (ops.filter.required_columns),
optionally only a row-group range (the unit of search-job sharding,
mirroring the reference's StartPage/TotalPages jobs,
modules/frontend/searchsharding.go), pads every axis to its power-of-two
bucket, and uploads.

The staged cache holds device COLUMNS, not column sets. Each (immutable)
block object carries a store keyed by (device column name, group range
or None) -> one padded device array. Span- and sattr-axis columns and
what is derived from them per slice (`sattr.over`, the rebased
`trace.span_off`, `span@<res column>`) key on the group range; trace-,
res- and rattr-axis columns and `trace@gkey_s` do not depend on the
range and key on None, so row-group shards and whole-block requests
share them. `column_keys` is the one place a request name becomes a
device name. A slice's generic span-attribute columns (`sattr.*`) reach
the device slot-major (`_SlotLayout`): one array a column, `K` planes of
`n_spans_b` (plane `j` = every span's `j`-th attribute row) and then
the rows beyond a span's `K`-th, whose owners are `sattr.over`; the
kernel ORs over the planes and scatters only the overflow rows
(ops/filter._cond_mask). A request resolves every column it names against the
store, stages only the missing ones (host chunk pool first, then the
backend) and gets a fresh StagedBlock view over the shared arrays: a
column is read, assembled and uploaded once however the routes spell
their column lists, and repeated queries against a hot block skip IO,
decompression AND the host->device transfer -- the device-memory analog
of the reference's page cache + memcached layers, and the biggest win
when the host<->device link has high latency. One policy bounds it: a
byte-budget LRU over every (block, column)."""

from __future__ import annotations

import weakref
from collections import OrderedDict
from dataclasses import dataclass, field
from functools import cached_property, lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from ..block import schema as S
from ..block.reader import BackendBlock
from ..util.profiler import timed_lock
from .device import PAD_I32, bucket, pad_rows, scoped

# aggregate device-memory budget across EVERY block's staged columns: an
# LRU over (block, column key) pairs, so a wide working set evicts the
# coldest columns instead of growing until HBM OOMs
_GLOBAL_CACHE_BUDGET = 4 << 30
# a cataloged hot lock: TEMPO_LOCK_PROFILE arms contention timing
# (tempo_lock_wait_seconds{lock="stage_lru"}); off = a raw Lock
_lru_lock = timed_lock("stage_lru")
_lru: OrderedDict[tuple[int, tuple], tuple] = OrderedDict()  # -> (blk weakref, nbytes)
_lru_bytes = 0

# HBM-evicted columns awaiting demotion into the host chunk pool
# (ops/chunkpool): collected under _lru_lock, compressed OUTSIDE it --
# the D2H pull + codec work is ~0.14 s a column at the cells' sizes
# (`demote_ms_per_eviction`), the lock guards microsecond bookkeeping
_pending_demote: list[tuple[str, tuple, object]] = []


def staged_cache_stats(max_entries: int = 32) -> dict:
    """Point-in-time view of the device staged-column cache for
    /status/kernels: aggregate occupancy plus the hottest (most recently
    touched) columns."""
    with _lru_lock:
        items = list(_lru.items())
        total = _lru_bytes
        budget = _GLOBAL_CACHE_BUDGET
    hottest = []
    for (_bid, (name, groups)), (wr, nbytes) in reversed(items[-max_entries:]):
        hottest.append({
            "block_id": getattr(getattr(wr(), "meta", None), "block_id", "")[:8],
            "column": name,
            "groups": list(groups) if groups is not None else None,
            "nbytes": int(nbytes),
        })
    from ..util.kerneltel import TEL

    # evictions / evicted_bytes: cumulative, one a column the LRU popped
    # to get back under the budget (a column of a dead reader is swept,
    # not evicted, and counts nowhere)
    return {"entries": len(items), "bytes": int(total),
            "budget_bytes": int(budget),
            "evictions": int(TEL.staged_cache_evictions.get()),
            "evicted_bytes": int(TEL.staged_cache_evicted_bytes.get()),
            "hottest": hottest}


def staged_block_ids() -> frozenset[str]:
    """The blocks with at least one column resident in this process's
    staged cache: what a querier tells the frontend it holds with every
    poll, so that a job for one of them need not wait out the steal
    clock (services/frontend `_claimer`)."""
    with _lru_lock:
        blocks = {bid: wr for (bid, _key), (wr, _n) in _lru.items()}
    ids = (getattr(getattr(wr(), "meta", None), "block_id", "")
           for wr in blocks.values())
    return frozenset(i for i in ids if i)


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


def _lru_touch(blk, keys) -> int:
    """Re-rank the resident columns a lookup just used -> their bytes."""
    bid, nbytes = id(blk), 0
    with _lru_lock:
        for key in keys:
            entry = _lru.get((bid, key))
            if entry is not None and entry[0]() is blk:
                _lru.move_to_end((bid, key))
                nbytes += entry[1]
    return nbytes


def _admit(blk, fresh: dict) -> None:
    """Put freshly staged (or pool-restaged) columns into the block's
    store and the global LRU. Store and accounting change together under
    the lock, so an eviction pass never sees one without the other."""
    global _lru_bytes
    bid = id(blk)
    with _lru_lock:
        store = getattr(blk, "_staged_cache", None)
        if store is None:
            store = blk._staged_cache = {}
        for key, arr in fresh.items():
            # already accounted: a concurrent miss staged the same
            # column, or id() was reused after the old block was GC'd --
            # either way the new array replaces it
            old = _lru.pop((bid, key), None)
            if old is not None:
                _lru_bytes -= old[1]
            store[key] = arr
            _lru[(bid, key)] = (weakref.ref(blk), int(arr.nbytes))
            _lru_bytes += int(arr.nbytes)
        # the eviction pass sweeps dead weakrefs first, so every insert
        # restores the accounting invariant in one O(n) scan
        _evict_over_budget_locked()
    _drain_demotions()


def _evict_over_budget_locked() -> None:
    global _lru_bytes
    from ..util.kerneltel import TEL

    _sweep_dead_locked()  # freed arrays must not force live evictions
    before = _lru_bytes
    evicted = 0
    while _lru_bytes > _GLOBAL_CACHE_BUDGET and len(_lru) > 1:
        (_bid, key), (wr, nbytes) = _lru.popitem(last=False)
        _lru_bytes -= nbytes
        evicted += 1
        blk = wr()
        if blk is None:
            continue
        arr = (getattr(blk, "_staged_cache", None) or {}).pop(key, None)
        block_id = getattr(getattr(blk, "meta", None), "block_id", "") or ""
        if arr is not None and block_id:
            # Tier B demotion candidate: the padded device array still
            # exists here -- park it for the post-lock compress instead
            # of discarding
            _pending_demote.append((block_id, key, arr))
    if evicted:
        TEL.staged_cache_evictions.inc(evicted)
        TEL.staged_cache_evicted_bytes.inc(before - _lru_bytes)


def _pool_key(key: tuple) -> tuple:
    """A store key as the host chunk pool spells it: a one-column entry
    of the (column tuple, groups) shape the pool has always had."""
    return ((key[0],), key[1])


def _drain_demotions() -> None:
    """Compress HBM-evicted columns into the host chunk pool. Called by
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
    from ..util.kerneltel import TEL
    from . import chunkpool

    # the device -> host pull and the codec of every victim, on the thread
    # of whichever lookup admitted a column (a hit's neighbour pays it)
    with TEL.stage("stage:demote", columns=len(victims),
                   bytes=sum(int(arr.nbytes) for _, _, arr in victims)):
        for block_id, key, arr in victims:
            chunkpool.demote(block_id, _pool_key(key), arr)

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
    # a generic-attribute value column is placed by its owners (_assemble),
    # so the owner rows are read beside it even when only it is missing
    if "sattr.span" not in read_names and any(
            n.startswith("sattr.") for n in read_names):
        read_names = read_names + ["sattr.span"]
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
    with TEL.stage("stage:read_columns", columns=len(plan.read_names)):
        for name in plan.read_names:
            pref = name.split(".", 1)[0]
            ax = _AXIS_OF.get(pref)
            if ax is None:
                arr = pack.read(name)
            else:
                arr = pack.read_groups(name, groups) if span_ax.n_groups else pack.read(name)
            host[name] = arr
    return host, _n_res(blk, plan.read_names)


# request names whose device column has another name: of the owner-row
# columns the device gets what _assemble derives -- the owners of a
# slice's overflow rows, and cumulative offsets on the resource axis
_DEVICE_NAME = {"sattr.span": "sattr.over", "rattr.res": "rattr.off"}


def column_keys(blk: BackendBlock, needed, groups) -> dict[str, tuple]:
    """Request name -> store key (device column name, group range or
    None) for every column a request of `needed` gets staged: THE place
    a request name becomes a device name. Columns read per row-group
    range (span / sattr axis) and what is rebased or gathered per slice
    (`trace.span_off`, `span@X`) key on the range; everything else is
    the same array for every range and keys on None. Left out, as
    _assemble and upload_stage leave them out: a `span@X` whose sources
    (`X`, `span.res_idx`) the request does not name, and trace columns
    of a host-only dtype. (`rattr.off` is cut to the res axis, which
    every request that names `rattr.res` sizes alike: required_columns
    sends `res.service_id` along.)"""
    gkey = tuple(groups) if groups is not None else None
    keys: dict[str, tuple] = {}
    for n in needed:
        device_name, ranged, source = _device_column(n)
        if source is not None:  # span@X
            if source not in needed or "span.res_idx" not in needed:
                continue
        elif (n.startswith("trace.") and not ranged
              and blk.pack.dtype_of(n) not in (None, np.int32, np.float32)):
            continue
        keys[n] = (device_name, gkey if ranged else None)
    return keys


def _head_planes(cnt: np.ndarray, n_rows: int, n_spans_b: int) -> int:
    """K, the dense planes a slice's generic attributes get: as many as
    its longest span needs, but no more than the flat rows' bucket would
    hold -- so the planes never cost more device memory than the rows
    did flat, however long the tail. Rows beyond a span's K-th are the
    slice's overflow (_assemble). From the slice's own counts: uniform
    counts leave no overflow, one span with 40 attributes a few rows of
    it, attributes rarer than spans (K = 0) all of them."""
    return min(int(cnt.max()), bucket(max(n_rows, 1)) // n_spans_b)


class _SlotLayout:
    """Where the generic-attribute rows of one staged slice go, worked
    out once a miss from the slice's per-span counts and shared by every
    value column. Slot-major: one array a value column -- K planes of
    n_spans_b, plane j holding every span's j-th attribute row and
    PAD_I32 where a span has fewer (a PAD key matches no key code, so an
    empty slot can never hit), then the overflow rows (a span's rows
    from its K-th on, in row order) padded to their bucket, their owners
    in `over_owners` (padded with n_spans_b = no span: the kernel drops
    it). The kernel ORs over the planes and scatters only the overflow
    (ops/filter._cond_mask): no cumsum over attribute rows, no
    span-length gather. Rows are grouped by owner in span order (the
    j-th row of a span's run is slot j); a row whose owner lies outside
    the slice belongs to the edge span."""

    def __init__(self, owners: np.ndarray, span_base: int, n_spans: int,
                 n_spans_b: int):
        self.owners, self.span_base, self.n_spans = owners, span_base, n_spans
        self.n_spans_b = n_spans_b
        self.n_rows = int(owners.shape[0])
        hi = max(n_spans, 1) - 1
        rel = owners - span_base
        self.cnt = cnt = np.bincount(np.clip(rel, 0, hi, out=rel), minlength=hi + 1)
        self.k = k = _head_planes(cnt, self.n_rows, n_spans_b)
        # the spans with rows beyond their K-th, and how many each
        self._long = long = np.flatnonzero(cnt > k)
        self._extra = extra = cnt[long] - k
        self.n_over = int(extra.sum())
        self.over_b = bucket(self.n_over) if self.n_over else 0
        self.over_owners = np.repeat(long, extra).astype(np.int32)
        self.numpy_columns = 0  # value columns the native pass did not place

    def place(self, arr: np.ndarray) -> np.ndarray:
        """One value column in the slice's layout: every element of the
        result written once."""
        from .. import native

        out = np.empty(self.k * self.n_spans_b + self.over_b, dtype=arr.dtype)
        pad = arr.dtype.type(PAD_I32)
        if not native.slot_place(self.owners, self.span_base, self.n_spans,
                                 self.n_spans_b, self.k, arr, out, pad):
            self.numpy_columns += 1
            self._place_numpy(arr, out, pad)
        return out

    @cached_property
    def _starts(self) -> np.ndarray:
        return np.cumsum(self.cnt) - self.cnt

    @cached_property
    def _plane_rows(self) -> list[tuple]:
        """Per plane j: (the spans that own a j-th row, or None when
        every span does; the rows that hold them)."""
        planes, fewest = [], int(self.cnt.min())
        for j in range(self.k):
            if fewest > j:
                planes.append((None, self._starts + j))
            else:
                has = np.flatnonzero(self.cnt > j)
                planes.append((has, self._starts[has] + j))
        return planes

    @cached_property
    def _over_rows(self) -> np.ndarray:
        """The overflow rows, in row order."""
        first = np.cumsum(self._extra) - self._extra
        return (np.repeat(self._starts[self._long] + self.k - first, self._extra)
                + np.arange(self.n_over))

    def _place_numpy(self, arr, out, pad) -> None:
        """place() without the library, and its twin in the tests: plane
        by plane from the n_spans-long starts."""
        n, nsb = self.cnt.shape[0], self.n_spans_b
        for j, (has, rows) in enumerate(self._plane_rows):
            plane = out[j * nsb:(j + 1) * nsb]
            if has is None:
                np.take(arr, rows, out=plane[:n], mode="clip")
            else:
                plane[:n] = pad
                plane[has] = arr[rows]
            plane[n:] = pad
        tail = out[self.k * nsb:]
        tail[:self.n_over] = arr[self._over_rows]
        tail[self.n_over:] = pad


@lru_cache(maxsize=1024)
def _device_column(name: str) -> tuple[str, bool, str | None]:
    """What column_keys knows from a request name alone -> (device
    column name, whether it depends on the row-group range, the res
    column a `span@X` is gathered from)."""
    if name.startswith("span@"):
        return name, True, name.split("@", 1)[1]
    ranged = (name == "trace.span_off"
              or _AXIS_OF.get(name.split(".", 1)[0]) is not None)
    return _DEVICE_NAME.get(name, name), ranged, None


def has_staged(blk: BackendBlock) -> bool:
    """The routes' temperature test: has this block object ever had a
    column admitted to the staged cache (it may since have been
    evicted; a block that was hot once is restaged, not sent cold)."""
    return getattr(blk, "_staged_cache", None) is not None


def is_staged(blk: BackendBlock, needed, groups=None) -> bool:
    """Would stage_block(blk, needed, groups) be a full hit now: every
    column the request names is resident."""
    store = getattr(blk, "_staged_cache", None)
    return store is not None and all(
        k in store for k in column_keys(blk, needed, groups).values())


def _group_list(blk: BackendBlock, groups) -> list[int]:
    if groups is not None:
        return list(groups)
    return list(range(blk.pack.axes[S.AX_SPAN].n_groups))


def _n_res(blk: BackendBlock, needed) -> int:
    """Rows of the res axis as the request sizes it: from the `res.*`
    columns it names (footer metadata; what read_stage_columns counts
    from the arrays)."""
    return max((blk.pack.n_rows_of(n) for n in needed
                if n.startswith("res.")), default=0)


def stage_block(
    blk: BackendBlock,
    needed: list[str],
    groups: list[int] | None = None,
    cache: bool = True,
) -> StagedBlock:
    """Load `needed` columns (padded, on device). If `groups` is given,
    span/sattr-axis columns cover only those contiguous row groups.
    Columns cache on the block object (blocks are immutable), one entry
    each: whatever is resident is shared, only the rest is staged, and
    the result is a fresh view holding exactly the columns asked for."""
    from ..util.kerneltel import TEL

    keys = column_keys(blk, needed, groups)
    store = (getattr(blk, "_staged_cache", None) or {}) if cache else {}
    cols: dict[str, jnp.ndarray] = {}  # device name -> array: the view's
    resident: list[tuple] = []
    missing: list[str] = []  # request names still to stage
    for n, key in keys.items():
        arr = store.get(key)
        if arr is None:
            missing.append(n)
        else:
            cols[key[0]] = arr
            resident.append(key)
    fresh: dict[tuple, jnp.ndarray] = {}
    if cache:
        reason = "" if not missing else (
            "partial_columns" if cols else "not_staged")
        # resident / pool / backend: where the lookup's columns are when
        # it asks -- on the device, in the host chunk pool, or nowhere
        # (read, assemble, upload); the two on a miss are filled below
        with TEL.stage("stage:lookup", hit=not missing, reason=reason,
                       missing=len(missing), resident=len(resident),
                       pool=0, backend=0) as lookup:
            (TEL.staged_cache_misses if missing else TEL.staged_cache_hits).inc()
            # attribute the lookup to the dequeue placement of the job
            # asking (own/steal/unowned): the affinity scheduler's
            # whole point is moving this ratio
            TEL.record_staged_lookup(not missing)
            TEL.record_staged_columns(
                len(resident), len(missing), _lru_touch(blk, resident))
            block_id = getattr(blk.meta, "block_id", "") or ""
            if missing and block_id:
                # Tier B probe, per column: a previous HBM eviction may have
                # demoted it into the host chunk pool -- restaging from there
                # skips the backend ranged read, the column decode AND the
                # pad/assemble phase
                from . import chunkpool

                pool_keys = {_pool_key(keys[n]): n for n in missing}
                for pk in pool_keys:
                    chunkpool.note_stage(block_id, pk)
                for pk, arr in chunkpool.restage(block_id, list(pool_keys)).items():
                    n = pool_keys[pk]
                    fresh[keys[n]] = cols[keys[n][0]] = arr
                    missing.remove(n)
                lookup.attrs.update(pool=len(fresh), backend=len(missing))
    glist = _group_list(blk, groups)
    n_res = _n_res(blk, needed)
    if missing:
        plan = plan_stage(missing)
        host, _ = read_stage_columns(blk, plan, glist)
        view, padded, real_rows = assemble_stage(blk, plan, glist, host, n_res)
        view.cols = cols  # what is resident: upload_stage adds the rest
        upload_stage(blk, plan, view, padded, real_rows)
        for n in missing:
            if keys[n][0] in cols:
                fresh[keys[n]] = cols[keys[n][0]]
    else:
        # nothing to read or upload: assembling the view is all the
        # staging this request costs, and it is timed as that (a traced
        # search's staging time then reads ~0 rather than not at all)
        with TEL.stage("stage:assemble", block=blk.meta.block_id[:8], rows=0):
            view = _dims(blk, glist, n_res)
            view.cols = cols
    if fresh and cache:
        _admit(blk, fresh)
    return view


def restage_from_pool(blk: BackendBlock, needed: list[str],
                      groups: list[int] | None) -> StagedBlock | None:
    """The whole request rebuilt from the host chunk pool (one batched
    upload), or None unless every column is there: the streamed cold
    path's unit restage (ops/stream), whose units never enter the
    staged cache."""
    from . import chunkpool

    block_id = getattr(blk.meta, "block_id", "") or ""
    keys = column_keys(blk, needed, groups)
    if not block_id or not keys:
        return None
    warm = chunkpool.restage(block_id, [_pool_key(k) for k in keys.values()])
    if len(warm) < len(keys):  # pool_holds said yes, an eviction since
        return None
    view = _dims(blk, _group_list(blk, groups), _n_res(blk, needed))
    view.cols = {pk[0][0]: arr for pk, arr in warm.items()}
    return view


def pool_holds(blk: BackendBlock, needed: list[str],
               groups: list[int] | None) -> bool:
    """Plan-time form of restage_from_pool: would it hit."""
    from . import chunkpool

    block_id = getattr(blk.meta, "block_id", "") or ""
    keys = column_keys(blk, needed, groups)
    return bool(block_id and keys) and all(
        chunkpool.probe(block_id, _pool_key(k)) for k in keys.values())


def assemble_stage(blk: BackendBlock, plan: StagePlan, groups: list[int],
                   host: dict, n_res: int) -> tuple[StagedBlock, dict, dict]:
    """The pad/assemble phase: owner-offset transforms, derived columns,
    bucket padding. Pure host numpy -- no IO, no device."""
    from ..util.kerneltel import TEL

    with TEL.stage("stage:assemble", block=blk.meta.block_id[:8]) as span:
        return _assemble(blk, plan, groups, host, n_res, span.attrs)


def _dims(blk: BackendBlock, groups: list[int], n_res: int) -> StagedBlock:
    """The shape fields of a staging of `groups` (no columns yet): what
    the kernels take as static arguments."""
    span_ax = blk.pack.axes[S.AX_SPAN]
    span_base = span_ax.offsets[groups[0]] if groups else 0
    span_hi = span_ax.offsets[groups[-1] + 1] if groups else 0
    n_spans = span_hi - span_base
    n_traces = blk.meta.total_traces
    return StagedBlock(
        n_spans=n_spans,
        n_traces=n_traces,
        n_res=n_res,
        n_spans_b=bucket(max(n_spans, 1)),
        n_traces_b=bucket(max(n_traces, 1)),
        n_res_b=bucket(max(n_res, 1)),
        span_base=span_base,
    )


def _assemble(blk, plan, groups, host, n_res, attrs: dict | None = None):
    """-> (the slice's dims, the padded host columns, their real rows);
    `attrs` (the `stage:assemble` span's) is told what was placed."""
    host = dict(host)  # owner-offset transforms mutate; callers may retry
    staged = _dims(blk, groups, n_res)
    span_base, n_spans = staged.span_base, staged.n_spans
    span_hi = span_base + n_spans
    n_spans_b, n_traces_b, n_res_b = (
        staged.n_spans_b, staged.n_traces_b, staged.n_res_b)

    want_gkey = plan.want_gkey
    start_ms_for_gkey_only = plan.start_ms_for_gkey_only

    # rows of every child table are grouped by owner; the owner row
    # columns themselves never need to reach the device
    real_rows: dict[str, int] = {}  # pre-padding lengths (telemetry)
    layout = None
    if "sattr.span" in host:
        layout = _SlotLayout(host.pop("sattr.span"), span_base, n_spans, n_spans_b)
        for name in [n for n in host if n.startswith("sattr.")]:
            host[name] = layout.place(host[name])
            real_rows[name] = layout.n_rows
        real_rows["sattr.over"] = layout.n_over
        host["sattr.over"] = pad_rows(layout.over_owners, layout.over_b, n_spans_b)
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
        elif pref == "sattr" or name == "rattr.off":
            pass  # already padded above
        elif name == "trace@gkey_s":
            arr = pad_rows(arr, n_traces_b, np.int32(-(2**31)))
        elif pref == "span":
            arr = pad_rows(arr, n_spans_b, PAD_I32)
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
    if attrs is not None:
        from .. import native

        # rows / planes / overflow_rows: the generic-attribute rows placed
        # slot-major, the K they got and the rows beyond it; path: what
        # placed them (`numpy` once any column took the fallback)
        rows, planes, n_over, fell_back = (
            (layout.n_rows, layout.k, layout.n_over, layout.numpy_columns)
            if layout else (0, 0, 0, 0))
        attrs.update(
            rows=rows, planes=planes, overflow_rows=n_over, columns=len(padded),
            bytes=sum(int(a.nbytes) for a in padded.values()),
            path="native" if native.available() and not fell_back else "numpy")
    return staged, padded, real_full


def upload_stage(blk: BackendBlock, plan: StagePlan, staged: StagedBlock,
                 padded: dict, real_rows: dict) -> StagedBlock:
    """The host->device phase: one batched transfer + the query-
    independent res->span materialization. Adds to what `staged.cols`
    already holds (stage_block seeds it with the resident columns, so a
    missing `span@X` gathers from a resident `X` or `span.res_idx`, and
    what is resident is not sent again: the `sattr.over` of owners read
    only to place a value column staged later)."""
    from ..util.kerneltel import TEL

    padded = {n: a for n, a in padded.items() if n not in staged.cols}
    nbytes = sum(int(a.nbytes) for a in padded.values())
    # THE host->device transfer, whether a warm staging miss or a
    # stream-pipeline unit (whose `stream:upload` stage is around this
    # whole call: ops/stream)
    with TEL.stage("stage:upload", bytes=nbytes, block=blk.meta.block_id[:8]):
        # ONE batched transfer for the whole block: per-array device_puts
        # each pay their own dispatch + link round trip
        staged.cols.update(zip(padded, jax.device_put(list(padded.values()))))
    # telemetry: upload volume + padding waste (padded vs real rows
    # summed per column -- columns live on different axes)
    TEL.record_transfer(
        nbytes,
        sum(real_rows[n] for n in padded),
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
