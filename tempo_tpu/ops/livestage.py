"""Live-head staging: incremental device columns for WAL/live traces.

The ingester's live/cut/flushing traces used to be searchable only
through a host-side per-trace index walk (services/ingester.py
_SearchEntry) while complete blocks run the fused device engine -- the
hottest data got the slowest engine. This module maintains per-tenant
APPEND-ONLY columnar tails for the live head so the same fused
filter->top-k shape (segment-membership masks + ops/select top-k)
covers live traces too:

  * one SLOT per live trace id (merged across the live/cut/flushing
    lifecycle states) carrying the filterable per-trace aggregates:
    push-metadata time bounds, the exact span-time selection key
    (seconds since ops/stage.GKEY_ORIGIN_S), a conservative duration,
    an alive flag, and the 4x int32 trace-id codes for find;
  * append-only ROW tails for tag membership: (owner slot, code) rows
    for every (key, lowered-str-value) attr pair and every span name,
    through an append-only dictionary whose codes never remap.

New segments are delta-encoded into the host tails off the push lock
(the ingester only marks trace ids dirty at push time; the decode
amortizes into the next refresh), and refreshes delta-upload: when the
row bucket is unchanged only the NEW rows cross the host->device link
(jax.lax.dynamic_update_slice builds the next generation's array from
the resident one -- a device-side copy, not a PCIe transfer), while the
tiny slot columns re-upload whole. Every refresh stamps a new
generation and returns an immutable LiveSnapshot, so an in-flight query
keeps a consistent view while later refreshes build new generations;
cut/flush retiring a trace only flips its slot's alive flag (no row
re-staging), and a compaction pass rebuilds the tails from the
per-trace fragments once dead slots / garbage rows pass a threshold.

Conservative-filter contract (same as ops/filter): the device mask may
over-match but never under-match the host oracle (_SearchEntry
semantics) -- tag/name membership and the time prefilter are exact,
min-duration filters on the per-segment-union duration (>= the
combined-trace duration combine_traces dedupe can shrink), and
max-duration / TraceQL are settled ONLY by the exact host verification
of the selected candidates (db/live_engine).
"""

from __future__ import annotations

import threading
import weakref
from dataclasses import dataclass, field
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

# LiveDict / kv_pair_key moved to the columnar ingest plane (ISSUE 16)
# so WAL feature checkpoints and staging share one dictionary; re-
# exported here for existing importers
from ..ingest.columnar import LiveDict, compute_features, kv_pair_key  # noqa: F401
from ..util.profiler import timed_rlock
from .device import PAD_I32, bucket, pad_rows, scoped
from .stage import GKEY_ORIGIN_S

_I32_MIN = -(2**31)
_I32_MAX = 2**31 - 1

# live stagers (one per ingester instance/tenant), weakly held so the
# HBM ledger (util/costmodel) can account their resident device tails
# without keeping drained instances alive
_registry_lock = threading.Lock()
_stagers: "weakref.WeakSet" = weakref.WeakSet()


def stager_device_bytes() -> tuple[int, int]:
    """(total device bytes of all live stagers' resident columns,
    stager count) -- the livestage component of the HBM ledger."""
    with _registry_lock:
        stagers = list(_stagers)
    return sum(s.device_bytes() for s in stagers), len(stagers)


def _clip_i32(v: int) -> int:
    return int(min(max(v, _I32_MIN + 1), _I32_MAX))


def _delta_bucket(n: int, floor: int = 64) -> int:
    """Small power-of-two bucket for delta-row uploads (no MIN_BUCKET
    floor: a 50-row delta must not pad to 1024 rows or the in-place
    append could not fit before the full bucket does)."""
    b = floor
    while b < n:
        b <<= 1
    return b


@dataclass
class _TraceTail:
    """Host-side per-trace fragment: which segments are staged and the
    rows/aggregates they contributed. Fragments survive until the trace
    retires so a compaction rebuild never re-decodes segments."""

    slot: int
    staged_segs: list = field(default_factory=list)  # segment refs
    kv_codes: list = field(default_factory=list)
    name_codes: list = field(default_factory=list)
    kv_seen: set = field(default_factory=set)  # staged kv CODES
    name_seen: set = field(default_factory=set)  # staged name CODES
    min_start_ns: int | None = None
    max_end_ns: int | None = None
    state: str = "live"


@dataclass(frozen=True)
class LiveSnapshot:
    """One consistent, immutable view of the staged live head. Slot
    arrays are copies (they mutate in place across refreshes); row
    arrays are views into append-only storage (rows below the recorded
    counts are never rewritten; growth reallocates, compaction swaps in
    fresh arrays -- either way this snapshot's references stay valid)."""

    generation: int
    n_slots: int
    n_kv: int
    n_name: int
    slot_b: int
    kv_b: int
    name_b: int
    # host columns (numpy)
    start_s: np.ndarray
    end_s: np.ndarray
    dur_ms: np.ndarray
    key_s: np.ndarray
    alive: np.ndarray
    id_codes: np.ndarray  # (n_slots, 4)
    kv_owner: np.ndarray
    kv_code: np.ndarray
    name_owner: np.ndarray
    name_code: np.ndarray
    # device columns (None until the device path first stages)
    dev: dict | None
    # slot -> trace id (the collect step maps winners back through the
    # caller's own groups snapshot for segments/verification)
    slot_tid: dict


# ------------------------------------------------------------ kernels


@lru_cache(maxsize=128)
def _compiled_live_filter(n_tags: int, n_names: int, f_start: bool, f_end: bool,
                          f_min: bool, slot_b: int, kv_b: int, name_b: int):
    """Structure (tag/name counts, which scalar prefilters exist,
    buckets) keys the compile; codes and thresholds are traced, so
    every live query with the same shape shares one program (the
    ops/filter launch-key contract)."""

    @jax.jit
    @scoped("live_filter")
    def run(start_s, end_s, dur_ms, alive, kv_owner, kv_code,
            name_owner, name_code, tag_codes, name_qcodes,
            t0, t1, dmin, n_slots):
        valid = jnp.arange(slot_b, dtype=jnp.int32) < n_slots
        mask = (alive > 0) & valid
        if f_start:
            mask = mask & (end_s >= t0)
        if f_end:
            mask = mask & (start_s <= t1)
        if f_min:
            # conservative: staged dur is the per-segment-union duration,
            # >= the exact combined duration, so >= dmin never
            # under-matches (exact check happens in host verification)
            mask = mask & (dur_ms >= dmin)
        kv_own = jnp.clip(kv_owner, 0, slot_b - 1)
        for i in range(n_tags):
            hit = (kv_code == tag_codes[i]).astype(jnp.int32)
            mask = mask & (jax.ops.segment_max(hit, kv_own, num_segments=slot_b) > 0)
        nm_own = jnp.clip(name_owner, 0, slot_b - 1)
        for i in range(n_names):
            hit = (name_code == name_qcodes[i]).astype(jnp.int32)
            mask = mask & (jax.ops.segment_max(hit, nm_own, num_segments=slot_b) > 0)
        return mask

    return run


def eval_live_device(snap: LiveSnapshot, tag_codes: list[int],
                     name_codes: list[int], t0: int, t1: int, dmin: int):
    """Fused live-head filter on device: slot mask over the staged
    columns. t0/t1/dmin <= 0 mean 'no filter' (matching SearchRequest's
    zero-is-unset convention). Returns the device mask (slot_b,)."""
    from ..util.kerneltel import TEL

    d = snap.dev
    key = (len(tag_codes), len(name_codes), t0 > 0, t1 > 0, dmin > 0,
           snap.slot_b, snap.kv_b, snap.name_b)
    fn = _compiled_live_filter(*key)
    args = (
        d["start_s"], d["end_s"], d["dur_ms"], d["alive"],
        d["kv_owner"], d["kv_code"], d["name_owner"], d["name_code"],
        np.asarray(tag_codes or [0], dtype=np.int32),
        np.asarray(name_codes or [0], dtype=np.int32),
        np.int32(_clip_i32(t0)), np.int32(_clip_i32(t1)),
        np.int32(_clip_i32(dmin)), np.int32(snap.n_slots),
    )
    from ..util import costmodel

    with TEL.launch("live_filter", ("live_filter",) + key, snap.slot_b,
                    cost=lambda: costmodel.spec(fn, *args)) as ln:
        return ln.sync(fn(*args))


def eval_live_host(snap: LiveSnapshot, tag_codes: list[int],
                   name_codes: list[int], t0: int, t1: int, dmin: int) -> np.ndarray:
    """Numpy twin of eval_live_device over the snapshot's host columns:
    identical mask semantics with zero device round trips -- the
    tiny-head engine below the measured row-count crossover."""
    n = snap.n_slots
    mask = snap.alive[:n] > 0
    if t0 > 0:
        mask &= snap.end_s[:n] >= _clip_i32(t0)
    if t1 > 0:
        mask &= snap.start_s[:n] <= _clip_i32(t1)
    if dmin > 0:
        mask &= snap.dur_ms[:n] >= _clip_i32(dmin)
    kv_owner = snap.kv_owner[: snap.n_kv]
    kv_code = snap.kv_code[: snap.n_kv]
    for c in tag_codes:
        hit = np.zeros(max(n, 1), dtype=bool)
        owners = kv_owner[kv_code == c]
        hit[owners[(owners >= 0) & (owners < n)]] = True
        mask &= hit[:n]
    nm_owner = snap.name_owner[: snap.n_name]
    nm_code = snap.name_code[: snap.n_name]
    for c in name_codes:
        hit = np.zeros(max(n, 1), dtype=bool)
        owners = nm_owner[nm_code == c]
        hit[owners[(owners >= 0) & (owners < n)]] = True
        mask &= hit[:n]
    return mask


@lru_cache(maxsize=32)
def _compiled_find(slot_b: int):
    @jax.jit
    @scoped("live_find")
    def run(id_codes, alive, q, n_slots):
        valid = jnp.arange(slot_b, dtype=jnp.int32) < n_slots
        m = jnp.all(id_codes == q[None, :], axis=1) & (alive > 0) & valid
        return jnp.where(jnp.any(m), jnp.argmax(m), -1)

    return run


def find_slot_device(snap: LiveSnapshot, trace_id: bytes) -> int:
    """Locate a live trace's slot on device by its 4x int32 id codes;
    -1 = not staged/alive. One tiny fetch."""
    from ..block import schema as S
    from ..util.kerneltel import TEL

    d = snap.dev
    fn = _compiled_find(snap.slot_b)
    q = np.asarray(S.trace_id_to_codes(trace_id.rjust(16, b"\x00")), dtype=np.int32)
    ns = np.int32(snap.n_slots)
    from ..util import costmodel

    with TEL.launch(
            "live_find", ("live_find", snap.slot_b), snap.slot_b,
            cost=lambda: costmodel.spec(fn, d["id_codes"], d["alive"], q, ns)):
        return int(np.asarray(fn(d["id_codes"], d["alive"], q, ns)))


def find_slot_host(snap: LiveSnapshot, trace_id: bytes) -> int:
    """Numpy twin of find_slot_device."""
    from ..block import schema as S

    n = snap.n_slots
    if n == 0:
        return -1
    q = np.asarray(S.trace_id_to_codes(trace_id.rjust(16, b"\x00")), dtype=np.int32)
    m = np.all(snap.id_codes[:n] == q[None, :], axis=1) & (snap.alive[:n] > 0)
    idx = int(np.argmax(m))
    return idx if m[idx] else -1


@jax.jit
@scoped("live_append")
def _append_rows_device(dst, src, start):
    """Delta append: next generation's column = resident array with the
    new rows written at `start`. The copy is device-side; only `src`
    (the padded delta) crosses the host->device link."""
    return jax.lax.dynamic_update_slice(dst, src, (start,))


@jax.jit
@scoped("live_patch")
def _patch_slots_device(dst, idx, vals):
    """Dirty-slot patch: scatter the changed slot values into the
    resident column. idx is padded by REPEATING real indices (the
    overwrite is idempotent), so pad lanes never touch foreign rows."""
    return dst.at[idx].set(vals)


# ------------------------------------------------------------- stager


class LiveStager:
    """Per-tenant live-head staging state. All mutation happens under
    self.lock (refresh/retire/compact); queries run lock-free against
    the immutable LiveSnapshot a refresh returns."""

    # rebuild the tails once dead slots or dead rows dominate
    COMPACT_DEAD_FRACTION = 0.5

    def __init__(self, dictionary: LiveDict | None = None, features_fn=None):
        # cataloged hot lock: pushes, refreshes and retirements all
        # serialize on the tail here (TEMPO_LOCK_PROFILE arms timing;
        # the wrapper's RLock keeps refresh->retire recursion legal)
        self.lock = timed_rlock("livestage_tail")
        self.dict = dictionary or LiveDict()
        # seg -> SegFeatures source: the instance's ColumnarIngest cache
        # when wired (decode once per segment across consumers), else a
        # direct compute against this stager's own dictionary
        self._features = features_fn or (lambda seg: compute_features(seg, self.dict))
        self.tails: dict[bytes, _TraceTail] = {}
        self.generation = 0
        # slot columns (numpy, capacity-grown; n_slots is the high-water)
        self.n_slots = 0
        self.dead_slots = 0
        self._slot_cap = 0
        self.start_s = np.empty(0, np.int32)
        self.end_s = np.empty(0, np.int32)
        self.dur_ms = np.empty(0, np.int32)
        self.key_s = np.empty(0, np.int32)
        self.alive = np.empty(0, np.int32)
        self.id_codes = np.empty((0, 4), np.int32)
        # append-only row tails
        self.n_kv = 0
        self.dead_kv = 0
        self.kv_owner = np.empty(0, np.int32)
        self.kv_code = np.empty(0, np.int32)
        self.n_name = 0
        self.dead_name = 0
        self.name_owner = np.empty(0, np.int32)
        self.name_code = np.empty(0, np.int32)
        # device generation (arrays + the row counts they cover)
        self._dev: dict | None = None
        self._dev_rows: tuple[int, int, int] | None = None  # slots, kv, name
        self._dirty_slots: set[int] = set()  # slots changed since last upload
        self._snap: LiveSnapshot | None = None
        with _registry_lock:
            _stagers.add(self)

    def device_bytes(self) -> int:
        """Resident device bytes of the staged tails (HBM ledger)."""
        with self.lock:
            dev = self._dev
            return sum(int(a.nbytes) for a in dev.values()) if dev else 0

    # ------------------------------------------------------ host tails
    def _grow_slots_locked(self, need: int) -> None:
        if need <= self._slot_cap:
            return
        cap = max(64, self._slot_cap * 2, need)
        for name in ("start_s", "end_s", "dur_ms", "key_s", "alive"):
            old = getattr(self, name)
            new = np.zeros(cap, np.int32)
            new[: old.shape[0]] = old
            setattr(self, name, new)
        old = self.id_codes
        new = np.zeros((cap, 4), np.int32)
        new[: old.shape[0]] = old
        self.id_codes = new
        self._slot_cap = cap

    @staticmethod
    def _append_rows(arr: np.ndarray, n: int, vals: list) -> np.ndarray:
        """Append vals at arr[n:]; grows by reallocation (old arrays --
        and any snapshot views into them -- stay intact)."""
        need = n + len(vals)
        if need > arr.shape[0]:
            cap = max(256, arr.shape[0] * 2, need)
            new = np.full(cap, PAD_I32, np.int32)
            new[: arr.shape[0]] = arr
            arr = new
        arr[n:need] = vals
        return arr

    def note_rows(self) -> tuple[int, int, int]:
        """(slots, kv rows, name rows) -- the engine's routing input."""
        with self.lock:
            return self.n_slots, self.n_kv, self.n_name

    def _alloc_slot_locked(self, tid: bytes) -> _TraceTail:
        from ..block import schema as S

        slot = self.n_slots
        self._grow_slots_locked(slot + 1)
        self.n_slots += 1
        self.alive[slot] = 1
        self.id_codes[slot] = np.asarray(
            S.trace_id_to_codes(tid.rjust(16, b"\x00")), dtype=np.int32)
        tail = _TraceTail(slot=slot)
        self.tails[tid] = tail
        self._dirty_slots.add(slot)
        return tail

    def _retire_locked(self, tid: bytes, tail: _TraceTail) -> None:
        self.alive[tail.slot] = 0
        self._dirty_slots.add(tail.slot)
        self.dead_slots += 1
        self.dead_kv += len(tail.kv_codes)
        self.dead_name += len(tail.name_codes)
        del self.tails[tid]

    def _stage_trace_locked(self, tid: bytes, segs: list,
                            start_s: int, end_s: int, state: str) -> bool:
        """Bring one trace's tail up to `segs`; returns True when slot
        or row state changed. Segment identity is the staleness check:
        the lifecycle keeps a trace's merged segment list prefix-stable
        (cut extends, flush snapshots, failed flushes restore in order),
        and any violation simply restages the trace on a fresh slot."""
        tail = self.tails.get(tid)
        if tail is not None:
            ns = len(tail.staged_segs)
            if any(a is not b for a, b in zip(tail.staged_segs, segs)):
                # reordered merge (or reborn id): the old rows are
                # garbage now -- kill the slot, restage whole
                self._retire_locked(tid, tail)
                tail = None
            elif len(segs) < ns:
                # a strict prefix of what is already staged: a stale
                # snapshot racing a newer refresh (the engine serializes
                # these, but stay safe) -- staged state is newer, no-op
                return False
        if tail is None:
            tail = self._alloc_slot_locked(tid)
        dirty = False
        for seg in segs[len(tail.staged_segs):]:
            feat = self._features(seg)
            lo, hi = feat.lo_ns, feat.hi_ns
            kv_add = [c for c in feat.kv_codes if c not in tail.kv_seen]
            tail.kv_seen.update(kv_add)
            nm_add = [c for c in feat.name_codes if c not in tail.name_seen]
            tail.name_seen.update(nm_add)
            if kv_add:
                self.kv_owner = self._append_rows(
                    self.kv_owner, self.n_kv, [tail.slot] * len(kv_add))
                self.kv_code = self._append_rows(self.kv_code, self.n_kv, kv_add)
                self.n_kv += len(kv_add)
                tail.kv_codes.extend(kv_add)
            if nm_add:
                self.name_owner = self._append_rows(
                    self.name_owner, self.n_name, [tail.slot] * len(nm_add))
                self.name_code = self._append_rows(self.name_code, self.n_name, nm_add)
                self.n_name += len(nm_add)
                tail.name_codes.extend(nm_add)
            if lo is not None and (tail.min_start_ns is None or lo < tail.min_start_ns):
                tail.min_start_ns = lo
            if hi is not None and (tail.max_end_ns is None or hi > tail.max_end_ns):
                tail.max_end_ns = hi
            tail.staged_segs.append(seg)
            dirty = True
        slot = tail.slot
        lo_ns = tail.min_start_ns or 0
        hi_ns = tail.max_end_ns or 0
        dur = _clip_i32(max(0, (hi_ns - lo_ns) // 1_000_000))
        key = _clip_i32(lo_ns // 1_000_000_000 - GKEY_ORIGIN_S) if lo_ns else _I32_MIN + 1
        vals = (int(np.int32(_clip_i32(start_s))), int(np.int32(_clip_i32(end_s))),
                dur, key)
        cur = (int(self.start_s[slot]), int(self.end_s[slot]),
               int(self.dur_ms[slot]), int(self.key_s[slot]))
        if dirty or cur != vals or tail.state != state:
            if cur != vals or dirty:
                self._dirty_slots.add(slot)
            self.start_s[slot], self.end_s[slot] = vals[0], vals[1]
            self.dur_ms[slot], self.key_s[slot] = vals[2], vals[3]
            tail.state = state
            dirty = True
        return dirty

    def _compact_locked(self) -> None:
        """Rebuild slots + row tails from the live per-trace fragments:
        dead slots and their rows vanish, fragments re-own fresh
        contiguous slots. Rebuilt arrays are NEW objects, so earlier
        snapshots keep their old views."""
        tails = sorted(self.tails.items(), key=lambda kv: kv[1].slot)
        n = len(tails)
        cap = max(64, n)
        start_s = np.zeros(cap, np.int32)
        end_s = np.zeros(cap, np.int32)
        dur_ms = np.zeros(cap, np.int32)
        key_s = np.zeros(cap, np.int32)
        alive = np.zeros(cap, np.int32)
        id_codes = np.zeros((cap, 4), np.int32)
        kv_owner: list[int] = []
        kv_code: list[int] = []
        nm_owner: list[int] = []
        nm_code: list[int] = []
        for new_slot, (tid, tail) in enumerate(tails):
            old = tail.slot
            start_s[new_slot] = self.start_s[old]
            end_s[new_slot] = self.end_s[old]
            dur_ms[new_slot] = self.dur_ms[old]
            key_s[new_slot] = self.key_s[old]
            alive[new_slot] = 1
            id_codes[new_slot] = self.id_codes[old]
            kv_owner.extend([new_slot] * len(tail.kv_codes))
            kv_code.extend(tail.kv_codes)
            nm_owner.extend([new_slot] * len(tail.name_codes))
            nm_code.extend(tail.name_codes)
            tail.slot = new_slot
        self.start_s, self.end_s = start_s, end_s
        self.dur_ms, self.key_s, self.alive = dur_ms, key_s, alive
        self.id_codes = id_codes
        self._slot_cap = cap
        self.n_slots, self.dead_slots = n, 0
        self.kv_owner = np.asarray(kv_owner or [], dtype=np.int32)
        self.kv_code = np.asarray(kv_code or [], dtype=np.int32)
        self.n_kv, self.dead_kv = len(kv_code), 0
        self.name_owner = np.asarray(nm_owner or [], dtype=np.int32)
        self.name_code = np.asarray(nm_code or [], dtype=np.int32)
        self.n_name, self.dead_name = len(nm_code), 0
        self._dev = None  # buckets/ownership changed: next upload is full
        self._dev_rows = None

    # ---------------------------------------------------------- refresh
    def refresh(self, items: dict, stage_device: bool = True) -> LiveSnapshot:
        """Reconcile the tails against `items` ({tid: (segments, state,
        start_s, end_s)} -- the caller's consistent instance-lock
        snapshot, segments merged flushing+cut+live per tid) and return
        the new generation's snapshot. stage_device=False keeps the
        refresh host-only (the tiny-head path pays no upload)."""

        from ..util.kerneltel import TEL

        with self.lock:
            # ingest-stage ledger: the host delta encode (includes any
            # segment decodes the columnar cache had not absorbed)
            with TEL.stage("ingest:stage_delta", traces=len(items)) as delta:
                dirty = False
                for tid in [t for t in self.tails if t not in items]:
                    self._retire_locked(tid, self.tails[tid])
                    dirty = True
                for tid, (segs, state, start_s, end_s) in items.items():
                    dirty |= self._stage_trace_locked(tid, segs, start_s, end_s, state)
                delta.counted = dirty  # a clean refresh encoded nothing
            total_rows = self.n_kv + self.n_name
            dead_rows = self.dead_kv + self.dead_name
            if self.n_slots and (
                self.dead_slots > self.COMPACT_DEAD_FRACTION * self.n_slots
                or (total_rows and dead_rows > self.COMPACT_DEAD_FRACTION * total_rows)
            ):
                self._compact_locked()
                dirty = True
            snap = self._snap
            if (not dirty and snap is not None
                    and (not stage_device or snap.dev is not None)):
                return snap  # same generation still describes the tails
            dev = None
            if stage_device:
                # the live head's stage + upload: delta appends and
                # dirty-slot patches are jitted programs of their own
                with TEL.stage("live:upload", slots=self.n_slots):
                    dev = self._upload_locked()
            self.generation += 1
            n = self.n_slots
            states: dict[str, int] = {"dead": self.dead_slots}
            for tail in self.tails.values():
                states[tail.state] = states.get(tail.state, 0) + 1
            TEL.set_livestage_rows(states, self.n_kv + self.n_name,
                                   self.generation)
            snap = LiveSnapshot(
                generation=self.generation,
                n_slots=n, n_kv=self.n_kv, n_name=self.n_name,
                slot_b=bucket(max(n, 1)),
                kv_b=bucket(max(self.n_kv, 1)),
                name_b=bucket(max(self.n_name, 1)),
                start_s=self.start_s[:n].copy(),
                end_s=self.end_s[:n].copy(),
                dur_ms=self.dur_ms[:n].copy(),
                key_s=self.key_s[:n].copy(),
                alive=self.alive[:n].copy(),
                id_codes=self.id_codes[:n].copy(),
                kv_owner=self.kv_owner[: self.n_kv],
                kv_code=self.kv_code[: self.n_kv],
                name_owner=self.name_owner[: self.n_name],
                name_code=self.name_code[: self.n_name],
                dev=dev,
                slot_tid={tail.slot: tid for tid, tail in self.tails.items()},
            )
            self._snap = snap
            return snap

    def _upload_locked(self) -> dict:
        """Bring the device columns up to the host tails. Slot columns
        re-upload whole (tiny); row tails append in place via
        dynamic_update_slice when they fit under the resident bucket,
        else re-upload full. Returns the device column dict."""
        from ..util.kerneltel import TEL

        n = self.n_slots
        slot_b = bucket(max(n, 1))
        kv_b = bucket(max(self.n_kv, 1))
        name_b = bucket(max(self.n_name, 1))
        dev = dict(self._dev) if self._dev is not None else None
        prev = self._dev_rows
        full = (
            dev is None or prev is None
            or dev["start_s"].shape[0] != slot_b
            or dev["kv_owner"].shape[0] != kv_b
            or dev["name_owner"].shape[0] != name_b
        )
        sent = 0
        rows_sent = 0
        if full:
            host = {
                "start_s": pad_rows(self.start_s[:n], slot_b, np.int32(0)),
                "end_s": pad_rows(self.end_s[:n], slot_b, np.int32(0)),
                "dur_ms": pad_rows(self.dur_ms[:n], slot_b, np.int32(0)),
                "key_s": pad_rows(self.key_s[:n], slot_b, np.int32(_I32_MIN)),
                "alive": pad_rows(self.alive[:n], slot_b, np.int32(0)),
                "id_codes": pad_rows(self.id_codes[:n], slot_b, PAD_I32),
                "kv_owner": pad_rows(self.kv_owner[: self.n_kv], kv_b, np.int32(0)),
                "kv_code": pad_rows(self.kv_code[: self.n_kv], kv_b, PAD_I32),
                "name_owner": pad_rows(self.name_owner[: self.n_name], name_b,
                                       np.int32(0)),
                "name_code": pad_rows(self.name_code[: self.n_name], name_b,
                                      PAD_I32),
            }
            dev = dict(zip(host, jax.device_put(list(host.values()))))
            sent = sum(int(a.nbytes) for a in host.values())
            rows_sent = n + self.n_kv + self.n_name
        else:
            # slot columns: scatter-patch only the DIRTY slots (idx
            # lanes pad by repeating a real index -- idempotent), so a
            # 2-trace push moves tens of bytes, not the padded columns
            dirty = sorted(s for s in self._dirty_slots if s < slot_b)
            if dirty:
                db_ = _delta_bucket(len(dirty), 16)
                idx = np.asarray(dirty + [dirty[0]] * (db_ - len(dirty)),
                                 dtype=np.int32)
                for name_ in ("start_s", "end_s", "dur_ms", "key_s", "alive",
                              "id_codes"):
                    src = getattr(self, name_)[idx]
                    dev[name_] = _patch_slots_device(dev[name_], idx, src)
                    sent += int(idx.nbytes + src.nbytes)
                rows_sent += len(dirty)
            for owner_name, code_name, n_new, fill_owner in (
                ("kv_owner", "kv_code", self.n_kv, 0),
                ("name_owner", "name_code", self.n_name, 0),
            ):
                n_old = prev[1] if owner_name == "kv_owner" else prev[2]
                if n_new == n_old:
                    continue
                delta = n_new - n_old
                db = _delta_bucket(delta)
                bkt = dev[owner_name].shape[0]
                owner_src = getattr(self, owner_name)[n_old:n_new]
                code_src = getattr(self, code_name)[n_old:n_new]
                if n_old + db <= bkt:
                    owner_p = pad_rows(owner_src, db, np.int32(fill_owner))
                    code_p = pad_rows(code_src, db, PAD_I32)
                    dev[owner_name] = _append_rows_device(
                        dev[owner_name], owner_p, np.int32(n_old))
                    dev[code_name] = _append_rows_device(
                        dev[code_name], code_p, np.int32(n_old))
                    sent += int(owner_p.nbytes + code_p.nbytes)
                else:  # padded delta would clip: full column re-upload
                    owner_p = pad_rows(getattr(self, owner_name)[:n_new], bkt,
                                       np.int32(fill_owner))
                    code_p = pad_rows(getattr(self, code_name)[:n_new], bkt, PAD_I32)
                    dev[owner_name], dev[code_name] = jax.device_put(
                        [owner_p, code_p])
                    sent += int(owner_p.nbytes + code_p.nbytes)
                rows_sent += delta
        self._dev = dev
        self._dev_rows = (n, self.n_kv, self.n_name)
        self._dirty_slots.clear()
        if sent:
            TEL.record_livestage_upload(sent, rows_sent, full)
        return dev
