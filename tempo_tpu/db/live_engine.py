"""Live-head device engine: the ingester's live/cut/flushing traces
searched through the same fused filter->top-k shape as complete blocks.

Execution contract (mirrors db/search.py): the staged device (or numpy
twin) mask is CONSERVATIVE -- tag/name membership and the push-metadata
time prefilter are exact, min-duration filters on a >= bound, and
max-duration / TraceQL are not filtered at all -- then the top-k
selection (ops/select, newest first by the seconds-granularity start
key) feeds an escalating collect whose candidates are re-verified
bit-exactly through the SAME per-trace index the host oracle
(Instance.search_live_index) uses. The escalation widens k until either
every masked slot has been seen or the limit-th verified result's key
is STRICTLY newer than the selection boundary -- at that point no
unseen slot can displace a winner even under second-granularity ties,
so the result set is bit-identical to the oracle by construction.

Engine routing is a measured row-count crossover: the host twin costs
~rows/host_rate with zero device round trips, the device path costs a
~fixed dispatch+sync; both rates are EMA-learned from this process's
own queries, so the threshold tracks the actual link instead of an
assumption. Tiny heads (the common single-tenant dev case) therefore
keep running on host arithmetic, and the device engine takes over
exactly when it starts winning.

Env knobs: TEMPO_LIVE_STAGE=0 kills staging entirely (the legacy index
walk serves everything); TEMPO_LIVE_ENGINE=device|host|index forces a
path (tests / differential harnesses); TEMPO_LIVE_CROSSOVER_ROWS seeds
the crossover before measurements exist; TEMPO_LIVE_FIND_DEVICE=1
routes find-by-id through the staged id-code kernel (the hash-map
lookup measures faster, so it stays the default)."""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from ..ops.livestage import (
    LiveStager,
    eval_live_device,
    eval_live_host,
    find_slot_device,
    find_slot_host,
    kv_pair_key,
)
from ..ops.select import k_bucket, select_topk_device, select_topk_host
from .search import DEFAULT_LIMIT, SearchRequest, SearchResponse, SearchResult

_I32_MIN = -(2**31)


def _env_flag(name: str, default: str = "") -> str:
    return os.environ.get(name, default)


class LiveEngine:
    """One ingester Instance's staged live-head engine. Query execution
    never holds the instance lock past the snapshot; staging mutation
    serializes on the stager's own lock."""

    def __init__(self, instance):
        self.inst = instance
        # share the instance's columnar-ingest plane when present: one
        # LiveDict for staging + WAL feature checkpoints, and staging
        # reads decoded features from the shared cache (decode once)
        col = getattr(instance, "columnar", None)
        if col is not None:
            self.stager = LiveStager(dictionary=col.dict,
                                     features_fn=col.features_for)
        else:
            self.stager = LiveStager()
        self._pending_lock = threading.Lock()
        self._pending_push: dict[bytes, float] = {}  # tid -> first unstaged push
        self.enabled = _env_flag("TEMPO_LIVE_STAGE", "1") != "0"
        try:
            self._crossover_seed = float(
                _env_flag("TEMPO_LIVE_CROSSOVER_ROWS", "4096"))
        except ValueError:
            self._crossover_seed = 4096.0
        # measured engine rates (EMAs over this process's own queries):
        # host twin scans at s/row, the device path pays ~fixed seconds
        self._host_s_per_row: float | None = None
        self._dev_fixed_s: float | None = None
        self._measured = False  # did THIS process observe an engine run?
        # seed the EMAs from the persisted CostLedger (a PREVIOUS
        # process's measurements) so routing starts measured instead of
        # re-learning from scratch every restart. The env seed still
        # wins when set -- the operator aimed the crossover on purpose.
        if not _env_flag("TEMPO_LIVE_CROSSOVER_ROWS"):
            try:
                from ..util.costledger import KEY_LIVE_SEARCH, ledger

                entry = ledger().get(KEY_LIVE_SEARCH)
                if entry:
                    h = float(entry.get("host_s_per_row", 0.0) or 0.0)
                    d = float(entry.get("device_fixed_s", 0.0) or 0.0)
                    if h > 0:
                        self._host_s_per_row = h
                    if d > 0:
                        self._dev_fixed_s = d
            except Exception:
                pass  # routing falls back to the seed constant

    # ------------------------------------------------------------- push
    def note_push(self, tids, now: float) -> None:
        """Stamp the staging-lag clock for freshly pushed trace ids --
        O(1) per id, called OFF the instance push lock."""
        if not self.enabled:
            return
        with self._pending_lock:
            for tid in tids:
                self._pending_push.setdefault(tid, now)

    def _note_staged(self, staged_tids) -> None:
        from ..util.kerneltel import TEL

        now = time.time()
        with self._pending_lock:
            lags = [now - self._pending_push.pop(tid)
                    for tid in staged_tids if tid in self._pending_push]
        for lag in lags:
            TEL.record_staging_lag(max(0.0, lag))

    # ---------------------------------------------------------- routing
    def crossover_rows(self) -> float:
        """Rows above which the device path is expected to win, from the
        measured EMAs (seeded by TEMPO_LIVE_CROSSOVER_ROWS until both
        engines have run at least once)."""
        if self._host_s_per_row and self._dev_fixed_s:
            est = self._dev_fixed_s / self._host_s_per_row
            return float(min(max(est, 256.0), float(1 << 22)))
        return self._crossover_seed

    def _observe_engine(self, engine: str, rows: int, seconds: float) -> None:
        if seconds <= 0:
            return
        self._measured = True
        if engine == "host":
            per_row = seconds / max(rows, 1)
            cur = self._host_s_per_row
            self._host_s_per_row = (per_row if cur is None
                                    else 0.7 * cur + 0.3 * per_row)
        else:
            cur = self._dev_fixed_s
            self._dev_fixed_s = (seconds if cur is None
                                 else 0.7 * cur + 0.3 * seconds)

    def _route(self, rows: int) -> tuple[str, str]:
        forced = _env_flag("TEMPO_LIVE_ENGINE")
        if forced in ("device", "host", "index"):
            return forced, "forced"
        if rows >= self.crossover_rows():
            return "device", ("measured_crossover"
                              if self._host_s_per_row and self._dev_fixed_s
                              else "seeded_crossover")
        return "host", "tiny_head"

    # --------------------------------------------------------- lifecycle
    def maybe_refresh(self) -> None:
        """Sweeper hook: bound the staging lag without waiting for a
        query. Only refreshes when pushes are pending or traces retired
        since the last generation."""
        if not self.enabled:
            return
        rows = sum(self.stager.note_rows())
        engine, _ = self._route(rows)
        # snapshot + reconcile are atomic under the stager lock (lock
        # order: stager outer, instance inner -- everywhere): a stale
        # groups snapshot must never reach refresh after a newer one,
        # or it would retire-and-restage traces the newer one staged
        with self.stager.lock:
            groups = self.inst._live_groups()
            if not groups and not self.stager.tails:
                return
            items = {tid: (g[0], g[1], g[2], g[3]) for tid, g in groups.items()}
            self.stager.refresh(items, stage_device=engine == "device")
        self._note_staged(list(items))

    # ------------------------------------------------------------ search
    def search(self, req: SearchRequest) -> SearchResponse:
        from ..util.kerneltel import TEL

        inst = self.inst
        if not self.enabled:
            TEL.record_routing("search_live", "index", "kill_switch")
            return inst.search_live_index(req)
        rows = sum(self.stager.note_rows())
        engine, reason = self._route(rows)
        if engine == "index":
            TEL.record_routing("search_live", "index", reason)
            return inst.search_live_index(req)

        from ..traceql.parser import parse

        q = parse(req.query) if req.query else None
        # snapshot + reconcile atomically (see maybe_refresh): stale
        # snapshots reaching refresh out of order would thrash slots
        with self.stager.lock:
            groups = inst._live_groups()
            if not groups:
                if self.stager.tails:  # fully drained head: retire slots
                    self.stager.refresh({}, stage_device=False)
                return SearchResponse()
            items = {tid: (g[0], g[1], g[2], g[3]) for tid, g in groups.items()}
            snap = self.stager.refresh(items, stage_device=engine == "device")
        self._note_staged(list(items))

        # resolve tag strings through the append-only dictionary: a miss
        # proves no staged row carries the pair -> exact empty result
        tag_codes: list[int] = []
        name_codes: list[int] = []
        for k, v in (req.tags or {}).items():
            if k == "name":
                c = self.stager.dict.lookup(v)
                if c < 0:
                    TEL.record_routing("search_live", engine, "dict_prune")
                    return SearchResponse()
                name_codes.append(c)
            else:
                c = self.stager.dict.lookup(kv_pair_key(k, str(v).lower()))
                if c < 0:
                    TEL.record_routing("search_live", engine, "dict_prune")
                    return SearchResponse()
                tag_codes.append(c)

        TEL.record_routing("search_live", engine, reason)
        # timeline: the ingester live-head leg with its routing verdict
        with TEL.stage("live:search", engine=engine, reason=reason,
                       rows=rows) as st:
            if engine == "device":
                mask = eval_live_device(snap, tag_codes, name_codes,
                                        req.start, req.end, req.min_duration_ms)

                def selector(k):
                    sids, _, n_match = select_topk_device(
                        mask, snap.dev["key_s"], mask, k)
                    return sids, n_match
            else:
                hmask = eval_live_host(snap, tag_codes, name_codes,
                                       req.start, req.end, req.min_duration_ms)

                def selector(k):
                    sids, _, n_match = select_topk_host(
                        hmask, snap.key_s, np.zeros_like(snap.key_s), k)
                    return sids, n_match

            resp = self._collect(snap, groups, req, q, selector)
        self._observe_engine(engine, rows, st.seconds)
        return resp

    def _collect(self, snap, groups, req: SearchRequest, q, selector) -> SearchResponse:
        """Escalating top-k collect with exact host verification: the
        device/host-twin mask proposes newest-first candidates, the
        per-trace index (the oracle's own entry) settles them."""
        inst = self.inst
        resp = SearchResponse()
        n = snap.n_slots
        if n == 0:
            return resp
        limit = req.limit or DEFAULT_LIMIT
        slot_tid = snap.slot_tid
        k = min(k_bucket(max(2 * limit, 32)), n)
        out: list[tuple[int, str, object]] = []
        seen: set[int] = set()
        while True:
            sids, n_match = selector(k)
            boundary_key = (int(snap.key_s[int(sids[-1])])
                            if len(sids) == k else None)
            for s in sids:
                s = int(s)
                if s in seen:
                    continue
                seen.add(s)
                tid = slot_tid.get(s)
                g = groups.get(tid) if tid is not None else None
                if g is None:
                    continue  # retired between snapshot and collect
                idx, decoded = inst._live_entry(tid, g[4], g[0])
                if req.tags and not idx.matches_tags(req.tags):
                    continue
                if req.min_duration_ms and idx.dur_ms < req.min_duration_ms:
                    continue
                if req.max_duration_ms and idx.dur_ms > req.max_duration_ms:
                    continue
                if q is not None:
                    from ..traceql.hosteval import trace_matches

                    if not trace_matches(q, decoded):
                        continue
                out.append((idx.start_ns, tid.hex(), idx))
            out.sort(key=lambda c: (-c[0], c[1]))
            done = len(seen) >= n_match or k >= n
            if not done and len(out) >= limit and boundary_key is not None:
                # exact-stop: the limit-th verified result is strictly
                # newer (at key granularity) than anything unseen
                from ..ops.livestage import _clip_i32
                from ..ops.stage import GKEY_ORIGIN_S

                cutoff = _clip_i32(
                    out[limit - 1][0] // 1_000_000_000 - GKEY_ORIGIN_S)
                done = cutoff > boundary_key
            if done:
                break
            k = min(k_bucket(k * 4), n)
        for start_ns, tid_hex, idx in out[:limit]:
            resp.traces.append(SearchResult(
                trace_id=tid_hex,
                root_service_name=idx.root_service,
                root_trace_name=idx.root_name,
                start_time_unix_nano=idx.start_ns,
                duration_ms=idx.dur_ms,
            ))
        resp.inspected_spans = snap.n_kv + snap.n_name
        return resp

    # -------------------------------------------------------------- find
    def find(self, trace_id: bytes):
        """Find-by-id through the live head. The hash-map lookup is the
        measured winner (O(1) host, no staging requirement), so it is
        the default; TEMPO_LIVE_FIND_DEVICE=1 (or the forced-engine env)
        routes through the staged id-code kernel instead -- both
        materialize through the same segment-combine, so results are
        bit-identical by construction."""
        from ..util.kerneltel import TEL

        inst = self.inst
        forced = _env_flag("TEMPO_LIVE_ENGINE")
        device_find = (_env_flag("TEMPO_LIVE_FIND_DEVICE") == "1"
                       or forced in ("device", "host"))
        if not self.enabled or not device_find:
            TEL.record_routing("find_live", "map",
                               "kill_switch" if not self.enabled
                               else "host_map_cheaper")
            return inst._find_live_map(trace_id)
        engine = "host" if forced == "host" else "device"
        with self.stager.lock:
            groups = inst._live_groups()
            items = {tid: (g[0], g[1], g[2], g[3]) for tid, g in groups.items()}
            snap = self.stager.refresh(items, stage_device=engine == "device")
        self._note_staged(list(items))
        TEL.record_routing("find_live", engine, "forced" if forced else "env")
        if engine == "device":
            slot = find_slot_device(snap, trace_id)
        else:
            slot = find_slot_host(snap, trace_id)
        if slot < 0:
            return None
        return inst._find_live_map(trace_id)

    def persist_crossover(self) -> None:
        """Commit this process's measured engine rates to the
        CostLedger so the NEXT process starts from them (ingester stop
        hook). Writes ONLY when this process actually observed an
        engine run: ledger-seeded values that never updated are not
        re-written (a restart loop would otherwise keep refreshing
        measured_at_unix on stale rates forever). Multi-tenant
        ingesters persist per instance; instances that measured nothing
        skip, so the last real measurement wins."""
        if not self._measured:
            return
        if self._host_s_per_row is None and self._dev_fixed_s is None:
            return
        try:
            from ..util.costledger import KEY_LIVE_SEARCH, ledger

            fields = {"crossover_rows": round(self.crossover_rows(), 1)}
            if self._host_s_per_row is not None:
                fields["host_s_per_row"] = self._host_s_per_row
            if self._dev_fixed_s is not None:
                fields["device_fixed_s"] = self._dev_fixed_s
            ledger().update(KEY_LIVE_SEARCH, **fields)
            ledger().publish()
        except Exception:
            pass  # persistence is advisory; next process re-learns

    # --------------------------------------------------------------- ops
    def stats(self) -> dict:
        """Per-instance staging state (debug/status surfaces)."""
        slots, kv, name = self.stager.note_rows()
        return {
            "enabled": self.enabled,
            "generation": self.stager.generation,
            "slots": slots, "kv_rows": kv, "name_rows": name,
            "dead_slots": self.stager.dead_slots,
            "crossover_rows": round(self.crossover_rows(), 1),
        }
