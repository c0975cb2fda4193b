"""BackendBlock: the read side of a vtpu block.

Find-by-ID pipeline (analog of vparquet/block_findtracebyid.go:56-203):
bloom shard test -> binary search sorted trace.id -> span range from
trace.span_off -> range-read ONLY the row-group chunks covering that
span range -> materialize the trace back to the wire model. All host
control-plane; the batched/device lookup path lives in ops/find.py and
the search path in db/search.py.

All child tables (attrs, events, links and their attrs) have sorted
owner columns, so per-span slices are searchsorted ranges, not scans.
"""

from __future__ import annotations

import bisect
from functools import cached_property

import numpy as np

from ..backend.base import RawBackend
from ..wire.model import Event, Link, Resource, ResourceSpans, Scope, ScopeSpans, Span, Trace
from . import schema as S
from .bloom import ShardedBloom, shard_for_trace_id
from .builder import BLOOM_PREFIX, DATA_NAME, DICT_NAME, decode_attr_value
from .colio import ColumnPack
from .dictionary import Dictionary
from .meta import BlockMeta

_MAT_SPAN_COLS = [
    "span.trace_sid",
    "span.name_id",
    "span.kind",
    "span.status",
    "span.start_ns",
    "span.end_ns",
    "span.id",
    "span.parent_id",
    "span.trace_state_id",
    "span.status_msg_id",
    "span.dropped_attrs",
    "span.res_idx",
    "span.scope_idx",
]

_ATTR_FIELDS = ("key_id", "vtype", "str_id", "int32", "int64", "f64")


class _ChildRows:
    """Rows of a child table belonging to a contiguous global owner range,
    loaded from the row-group chunks covering it."""

    def __init__(self, pack: ColumnPack, prefix: str, owner_col: str, axis: str,
                 groups: list[int], fields: tuple[str, ...]):
        ax = pack.axes[axis]
        self.global_base = ax.offsets[groups[0]] if ax.n_rows else 0
        names = [f"{prefix}.{owner_col}"] + [f"{prefix}.{f}" for f in fields]
        if ax.n_rows == 0:
            self.owner = np.empty(0, dtype=np.int32)
            self.cols = {n: np.empty(0) for n in names}
        else:
            self.cols = {n: pack.read_groups(n, groups) for n in names}
            self.owner = self.cols[f"{prefix}.{owner_col}"]
        self.prefix = prefix

    def ranges(self, owner_rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Vectorized per-owner [lo, hi) ranges: two batched searchsorted
        calls for ALL owner rows instead of two scalar calls per row."""
        lo = np.searchsorted(self.owner, owner_rows, side="left")
        hi = np.searchsorted(self.owner, owner_rows, side="right")
        return lo, hi

    def field(self, name: str, j: int):
        return self.cols[f"{self.prefix}.{name}"][j]

    def global_row(self, j: int) -> int:
        return self.global_base + j


def _attrs_from(child: _ChildRows, jlo: int, jhi: int, d: Dictionary) -> dict:
    out = {}
    for j in range(jlo, jhi):
        out[d.string(int(child.field("key_id", j)))] = decode_attr_value(
            int(child.field("vtype", j)),
            int(child.field("str_id", j)),
            int(child.field("int32", j)),
            int(child.field("int64", j)),
            float(child.field("f64", j)),
            d,
        )
    return out


class BackendBlock:
    def __init__(self, backend: RawBackend, meta: BlockMeta):
        self.backend = backend
        self.meta = meta
        self._data_size = meta.size_bytes
        self._pack: ColumnPack | None = None
        self.bytes_read = 0

    # ------------------------------------------------------------- IO
    @property
    def pack(self) -> ColumnPack:
        if self._pack is None:
            t, b = self.meta.tenant_id, self.meta.block_id
            size = self._data_size
            if not size:
                size = len(self.backend.read(t, b, DATA_NAME))  # fallback: full read
            self._pack = ColumnPack(
                lambda off, ln: self.backend.read_range(t, b, DATA_NAME, off, ln), size
            )
        return self._pack

    @cached_property
    def dictionary(self) -> Dictionary:
        return Dictionary.from_bytes(
            self.backend.read(self.meta.tenant_id, self.meta.block_id, DICT_NAME)
        )

    def bloom_shard(self, shard: int) -> np.ndarray:
        cache = getattr(self, "_bloom_cache", None)
        if cache is None:
            cache = self._bloom_cache = {}
        hit = cache.get(shard)
        if hit is not None:
            return hit
        data = self.backend.read(self.meta.tenant_id, self.meta.block_id, f"{BLOOM_PREFIX}{shard}")
        self.bytes_read += len(data)
        words = ShardedBloom.shard_from_bytes(data)
        cache[shard] = words  # blocks are immutable; shards are ~100 KiB
        return words

    @cached_property
    def trace_index(self) -> dict[str, np.ndarray]:
        """Trace-level columns (small; cached for the block's lifetime)."""
        return self.pack.read_many(
            [
                "trace.id",
                "trace.id_codes",
                "trace.span_off",
                "trace.start_ns",
                "trace.end_ns",
                "trace.root_service_id",
                "trace.root_name_id",
                "trace.dur_us",
            ]
        )

    # the contract with db/search._candidates/_materialize: every
    # trace-axis column they index. Extend HERE when they read more.
    SEARCH_TRACE_COLS = (
        "trace.id",
        "trace.start_ns",
        "trace.end_ns",
        "trace.root_service_id",
        "trace.root_name_id",
    )

    @cached_property
    def search_index(self) -> dict[str, np.ndarray]:
        """The trace_index subset search-result building touches
        (SEARCH_TRACE_COLS). Cold one-shot readers decode ~45% fewer
        trace-axis bytes than the full index (id_codes/span_off/dur_us
        are find-path columns)."""
        return self.pack.read_many(list(self.SEARCH_TRACE_COLS))

    # ------------------------------------------------------ find by id
    def bloom_test(self, trace_id: bytes) -> bool:
        if not self.meta.bloom_shards:
            return True
        shard = shard_for_trace_id(trace_id, self.meta.bloom_shards)
        words = self.bloom_shard(shard)
        for pos in ShardedBloom.positions(trace_id, self.meta.bloom_shard_bits):
            if not (int(words[pos // 32]) >> (pos % 32)) & 1:
                return False
        return True

    def find_trace_sid(self, trace_id: bytes) -> int:
        """Binary search the sorted trace-id index; -1 if absent.
        Shares the cached void16 view with the batched host engine
        (ops/find.lookup_ids_blocks_host)."""
        from ..ops.find import _ids_void

        iv = _ids_void(self)
        n = iv.shape[0]
        padded = trace_id.rjust(16, b"\x00")
        if n == 0 or len(padded) != 16:  # oversize ids can match nothing
            return -1
        tid = np.frombuffer(padded, dtype=np.uint8).view("V16")
        pos = int(np.searchsorted(iv, tid[0]))
        if pos < n and iv[pos] == tid[0]:
            return pos
        return -1

    def find_trace_by_id(self, trace_id: bytes) -> Trace | None:
        if not self.meta.may_contain_id(trace_id.rjust(16, b"\x00").hex()):
            return None
        if not self.bloom_test(trace_id):
            return None
        sid = self.find_trace_sid(trace_id)
        if sid < 0:
            return None
        return self.materialize_traces([sid])[0]

    # --------------------------------------------------- materialization
    def _groups_for_span_range(self, lo: int, hi: int) -> list[int]:
        offs = self.pack.axes[S.AX_SPAN].offsets
        g_lo = bisect.bisect_right(offs, lo) - 1
        g_hi = bisect.bisect_left(offs, hi)
        return list(range(max(0, g_lo), max(g_lo + 1, g_hi)))

    @cached_property
    def _res_tables(self):
        d_cols = sorted(set(S.WELL_KNOWN_RES_ATTRS.values()))
        res_ded = {c: self.pack.read(c) for c in d_cols if self.pack.has(c)}
        ded_key = {}
        for key, col in S.WELL_KNOWN_RES_ATTRS.items():
            ded_key.setdefault(col, key)
        rattr = self.pack.read_many(
            ["rattr.res"] + [f"rattr.{f}" for f in _ATTR_FIELDS]
        )
        scope_name = self.pack.read("scope.name_id")
        scope_version = self.pack.read("scope.version_id")
        return res_ded, ded_key, rattr, scope_name, scope_version

    def _resource_attrs(self, res_idx: int, d: Dictionary,
                        rrange: tuple[int, int] | None = None) -> dict:
        res_ded, ded_key, rattr, _, _ = self._res_tables
        attrs: dict = {}
        for col, arr in res_ded.items():
            code = int(arr[res_idx])
            if code >= 0:
                attrs[ded_key[col]] = d.string(code)
        owner = rattr.get("rattr.res")
        if owner is not None and len(owner):
            if rrange is not None:
                lo, hi = rrange
            else:
                lo = int(np.searchsorted(owner, res_idx, side="left"))
                hi = int(np.searchsorted(owner, res_idx, side="right"))
            for j in range(lo, hi):
                attrs[d.string(int(rattr["rattr.key_id"][j]))] = decode_attr_value(
                    int(rattr["rattr.vtype"][j]),
                    int(rattr["rattr.str_id"][j]),
                    int(rattr["rattr.int32"][j]),
                    int(rattr["rattr.int64"][j]),
                    float(rattr["rattr.f64"][j]),
                    d,
                )
        return attrs

    def materialize_traces(self, sids: list[int]) -> list[Trace]:
        """Reconstruct full wire traces for the given trace indexes,
        reading only the row-group chunks that cover their span rows.
        The one site that serves search verify, find and metrics."""
        from ..util.kerneltel import TEL

        with TEL.stage("rows:materialize", rows=len(sids),
                       block=self.meta.block_id[:8]) as st:
            out = self._materialize_traces(sids)
            st.attrs["spans"] = sum(t.span_count() for t in out if t is not None)
        return out

    def _materialize_traces(self, sids: list[int]) -> list[Trace]:
        span_off = self.trace_index["trace.span_off"]
        d = self.dictionary
        _, _, _, scope_name, scope_version = self._res_tables
        # global-attr tables for events/links (owner = global ev/ln row)
        evattr_all = self.pack.read_many(["evattr.ev"] + [f"evattr.{f}" for f in _ATTR_FIELDS])
        lnattr_all = self.pack.read_many(["lnattr.ln"] + [f"lnattr.{f}" for f in _ATTR_FIELDS])

        def global_attrs(table: dict, owner_name: str, global_row: int) -> dict:
            owner = table.get(owner_name)
            out: dict = {}
            if owner is None or not len(owner):
                return out
            lo = int(np.searchsorted(owner, global_row, side="left"))
            hi = int(np.searchsorted(owner, global_row, side="right"))
            pre = owner_name.split(".")[0]
            for j in range(lo, hi):
                out[d.string(int(table[f"{pre}.key_id"][j]))] = decode_attr_value(
                    int(table[f"{pre}.vtype"][j]),
                    int(table[f"{pre}.str_id"][j]),
                    int(table[f"{pre}.int32"][j]),
                    int(table[f"{pre}.int64"][j]),
                    float(table[f"{pre}.f64"][j]),
                    d,
                )
            return out

        out: list[Trace] = []
        for sid in sids:
            lo, hi = int(span_off[sid]), int(span_off[sid + 1])
            groups = self._groups_for_span_range(lo, hi)
            base = self.pack.axes[S.AX_SPAN].offsets[groups[0]]
            sl = slice(lo - base, hi - base)
            # one threaded decode for EVERY chunk this trace touches
            # (span cols + child tables); the reads below then hit the
            # pack's decompressed-chunk cache
            wants = [(c, groups) for c in _MAT_SPAN_COLS]
            for pre, fields in (("sattr", ("span",) + _ATTR_FIELDS),
                                ("ev", ("span", "time_ns", "name_id", "dropped")),
                                ("ln", ("span", "trace_id", "span_id", "state_id"))):
                wants += [(f"{pre}.{f}", groups) for f in fields]
            self.pack.warm(wants)
            sp_cols = {c: self.pack.read_groups(c, groups)[sl] for c in _MAT_SPAN_COLS}

            sat = _ChildRows(self.pack, "sattr", "span", S.AX_SATTR, groups, _ATTR_FIELDS)
            evs = _ChildRows(self.pack, "ev", "span", S.AX_EVENT, groups, ("time_ns", "name_id", "dropped"))
            lns = _ChildRows(self.pack, "ln", "span", S.AX_LINK, groups, ("trace_id", "span_id", "state_id"))

            # batched child-table ranges: one searchsorted pair per table
            # for the whole trace, not per span
            rows = np.arange(lo, hi, dtype=np.int64)
            sat_lo, sat_hi = sat.ranges(rows)
            ev_lo, ev_hi = evs.ranges(rows)
            ln_lo, ln_hi = lns.ranges(rows)
            res_u = np.unique(sp_cols["span.res_idx"])
            rowner = self._res_tables[2].get("rattr.res")
            if rowner is not None and len(rowner):
                r_lo = np.searchsorted(rowner, res_u, side="left")
                r_hi = np.searchsorted(rowner, res_u, side="right")
                res_ranges = {int(r): (int(a), int(b)) for r, a, b in zip(res_u, r_lo, r_hi)}
            else:
                res_ranges = {int(r): (0, 0) for r in res_u}

            tid_bytes = self.trace_index["trace.id"][sid].tobytes()
            t = Trace()
            batches: dict[int, ResourceSpans] = {}
            scopes: dict[tuple[int, int], ScopeSpans] = {}
            for i in range(hi - lo):
                res_idx = int(sp_cols["span.res_idx"][i])
                scope_idx = int(sp_cols["span.scope_idx"][i])
                rs = batches.get(res_idx)
                if rs is None:
                    rs = ResourceSpans(resource=Resource(attrs=self._resource_attrs(
                        res_idx, d, res_ranges.get(res_idx))))
                    batches[res_idx] = rs
                    t.resource_spans.append(rs)
                skey = (res_idx, scope_idx)
                ss = scopes.get(skey)
                if ss is None:
                    ss = ScopeSpans(
                        scope=Scope(
                            name=d.string(int(scope_name[scope_idx])),
                            version=d.string(int(scope_version[scope_idx])),
                        )
                    )
                    scopes[skey] = ss
                    rs.scope_spans.append(ss)

                parent = sp_cols["span.parent_id"][i].tobytes()
                sp = Span(
                    trace_id=tid_bytes,
                    span_id=sp_cols["span.id"][i].tobytes(),
                    parent_span_id=b"" if parent == b"\x00" * 8 else parent,
                    trace_state=d.string(int(sp_cols["span.trace_state_id"][i])),
                    name=d.string(int(sp_cols["span.name_id"][i])),
                    kind=int(sp_cols["span.kind"][i]),
                    start_unix_nano=int(sp_cols["span.start_ns"][i]),
                    end_unix_nano=int(sp_cols["span.end_ns"][i]),
                    status_code=int(sp_cols["span.status"][i]),
                    status_message=d.string(int(sp_cols["span.status_msg_id"][i])),
                    dropped_attributes_count=int(sp_cols["span.dropped_attrs"][i]),
                    attrs=_attrs_from(sat, int(sat_lo[i]), int(sat_hi[i]), d),
                )
                for j in range(int(ev_lo[i]), int(ev_hi[i])):
                    e = Event(
                        time_unix_nano=int(evs.field("time_ns", j)),
                        name=d.string(int(evs.field("name_id", j))),
                        dropped_attributes_count=int(evs.field("dropped", j)),
                        attrs=global_attrs(evattr_all, "evattr.ev", evs.global_row(j)),
                    )
                    sp.events.append(e)
                for j in range(int(ln_lo[i]), int(ln_hi[i])):
                    link = Link(
                        trace_id=lns.field("trace_id", j).tobytes(),
                        span_id=lns.field("span_id", j).tobytes(),
                        trace_state=d.string(int(lns.field("state_id", j))),
                        attrs=global_attrs(lnattr_all, "lnattr.ln", lns.global_row(j)),
                    )
                    sp.links.append(link)
                ss.spans.append(sp)
            out.append(t)
        self.bytes_read = self.pack.bytes_read
        return out


def open_block(backend: RawBackend, tenant: str, block_id: str) -> BackendBlock:
    meta = BlockMeta.from_json(backend.read(tenant, block_id, "meta.json"))
    from .versioned import open_block_versioned

    return open_block_versioned(backend, meta)
