"""Sharded predicate search: blocks over dp, span rows over sp.

The multi-chip analog of the reference's two-level search sharding --
blocks to jobs (modules/frontend/searchsharding.go:266-310) and pages
within a block (SearchOptions.StartPage/TotalPages) -- as one mesh
program: the span axis is sharded over 'sp' (each chip filters its row
slice), per-trace aggregation is a segment reduce + `psum` over 'sp'
(the combiner collective), and independent blocks ride 'dp'.

Operands are PER BLOCK: every block resolves strings through its own
dictionary, so the same query yields different int codes (and different
regex-match tables) per block. ops_i/ops_f/tables carry a leading block
axis sharded over 'dp'; condition compares broadcast the per-block
operand over that block's rows. Operand values are traced, and the mesh
programs are memoized, so different constants with the same structure
share one compiled program.

Mirrors ops/filter.py's trace-level tree semantics: span subtrees
aggregate through ('tracify', t) nodes, trace-axis conds compare
per-block (B, NT) columns.

Generic-attr conds (sattr/rattr -- the reference's first-class generic
attribute iterators, tempodb/encoding/vparquet/block_traceql.go:682-763)
run on the mesh too: attr VALUE rows shard over 'sp' exactly like span
rows, the per-owner aggregation is a local cumsum + gathers at the
(replicated) owner-offset column, and the cross-shard stitch is a
`psum_scatter` over 'sp' -- a reduce-scatter that lands each chip
precisely its own span slice of the per-span hit counts, so an
arbitrary `{ span.foo = "bar" }` costs one collective the size of the
span axis. rattr rows aggregate to the small replicated resource axis
with a plain `psum` and gather through span.res_idx. Padded attr rows
carry key_id = PAD (< 0); planner key codes are always >= 0, so
validity needs no extra operand.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.device import bucket, scoped
from ..ops.filter import (
    _ATTR_VALUE_COL,
    _VT_CODE,
    Cond,
    Operands,
    T_RATTR,
    T_RES,
    T_SATTR,
    T_SPAN,
    T_TRACE,
    normalize_tree,
)
from .mesh import smap


def _cmp_b(op: str, x, v0, v1, f0, f1, is_float: bool, table):
    """Per-block compare: x (Bl, N); v0/v1/f0/f1 (Bl,) per-block operands;
    table (Bl, L) per-block dictionary-match table."""
    a = (f0 if is_float else v0)[:, None]
    b = (f1 if is_float else v1)[:, None]
    if op == "eq":
        return x == a
    if op == "ne":
        return x != a
    if op == "ne_present":
        return (x != a) & (x >= 0)
    if op == "ne_clamped":
        return (x != a) | (x == 2**31 - 1) | (x == -(2**31) + 1)
    if op == "lt":
        return x < a
    if op == "le":
        return x <= a
    if op == "gt":
        return x > a
    if op == "ge":
        return x >= a
    if op == "range":
        return (x >= a) & (x <= b)
    if op == "exists":
        return jnp.ones_like(x, dtype=bool)
    if op in ("intable", "notintable"):
        hit = jnp.take_along_axis(table, jnp.clip(x, 0, table.shape[1] - 1), axis=1) > 0
        if op == "notintable":
            hit = ~hit
        return hit & (x >= 0)
    raise ValueError(f"unknown op {op}")


@lru_cache(maxsize=128)
def make_sharded_search(mesh, tree, conds: tuple[Cond, ...], col_names: tuple[str, ...],
                        B: int, S: int, R: int, NT: int, table_idxs: tuple[int, ...] = (),
                        pack: bool = True):
    """Jitted mesh program over stacked blocks.

    ops_i: (B, C, 3) int32, ops_f: (B, C, 2) f32, tables: (B, L) u8 --
    all sharded over dp. cols[name]: (B, S) span-axis int32
    (trace_sid included), or (B, R) res-axis, or (B, NT) trace-axis.
    n_spans: (B,). `tree` must be trace-level (normalize_tree applied).
    Returns (trace_mask (B, NT) bool, span_count (B, NT) int32),
    sharded over dp.
    """

    def local(ops_i, ops_f, n_spans_l, *arrays):
        n_tab = len(table_idxs)
        tables = dict(zip(table_idxs, arrays[:n_tab]))
        cols = dict(zip(col_names, arrays[n_tab:]))
        Sl = cols["span.trace_sid"].shape[1]
        row0 = jax.lax.axis_index("sp") * Sl
        valid = (jnp.arange(Sl, dtype=jnp.int32)[None, :] + row0) < n_spans_l[:, None]
        span_masks: list = []

        def cond_cmp(i, x):
            c = conds[i]
            return _cmp_b(c.op, x, ops_i[:, i, 1], ops_i[:, i, 2],
                          ops_f[:, i, 0], ops_f[:, i, 1], c.is_float, tables.get(i))

        def owner_counts(row_hit, off):
            """Per-owner True counts when rows are GROUPED by owner and
            sharded over 'sp': local exclusive cumsum + gathers at the
            global offsets, each shard contributing only the slice of
            every segment it holds. off: (Bl, n_seg+1) global attr rows,
            replicated along sp. Returns (Bl, n_seg) PARTIAL counts --
            the caller sums over 'sp'."""
            Al = row_hit.shape[1]
            arow0 = jax.lax.axis_index("sp") * Al
            ecs = jnp.concatenate(
                [jnp.zeros((row_hit.shape[0], 1), jnp.int32),
                 jnp.cumsum(row_hit.astype(jnp.int32), axis=1)], axis=1)
            lo = jnp.clip(off[:, :-1] - arow0, 0, Al)
            hi = jnp.clip(off[:, 1:] - arow0, 0, Al)
            return jnp.take_along_axis(ecs, hi, 1) - jnp.take_along_axis(ecs, lo, 1)

        def attr_mask(i):
            """Span-level mask for a generic-attr cond: hit rows in the
            sharded attr table, aggregated to their owner axis."""
            c = conds[i]
            pre = c.target  # 'sattr' | 'rattr'
            key_match = cols[f"{pre}.key_id"] == ops_i[:, i, 0][:, None]
            if c.col == "any":
                row_hit = key_match
            else:
                vcol = cols[f"{pre}.{_ATTR_VALUE_COL[c.col]}"]
                vt_ok = cols[f"{pre}.vtype"] == _VT_CODE[c.col]
                row_hit = key_match & vt_ok & cond_cmp(i, vcol)
            cnt = owner_counts(row_hit, cols[f"{pre}.off"])
            if pre == T_SATTR:
                # reduce-scatter: chip k receives the summed counts for
                # exactly its span columns [k*Sl, (k+1)*Sl)
                cnt = jax.lax.psum_scatter(cnt, "sp", scatter_dimension=1,
                                           tiled=True)  # (Bl, Sl)
                return (cnt > 0) & valid
            rm = jax.lax.psum(cnt, "sp") > 0  # (Bl, R) -- small, replicated
            idx = jnp.clip(cols["span.res_idx"], 0, rm.shape[1] - 1)
            rm_g = jnp.take_along_axis(rm, idx, axis=1)
            return rm_g & (cols["span.res_idx"] >= 0) & valid

        def cond_mask(i):
            c = conds[i]
            if c.target == T_SPAN:
                return cond_cmp(i, cols[c.col]) & valid
            if c.target == T_RES:
                rm = cond_cmp(i, cols[c.col])  # (Bl, R)
                idx = jnp.clip(cols["span.res_idx"], 0, rm.shape[1] - 1)
                rm_g = jnp.take_along_axis(rm, idx, axis=1)
                return rm_g & (cols["span.res_idx"] >= 0) & valid
            if c.target in (T_SATTR, T_RATTR):
                return attr_mask(i)
            raise ValueError(f"sharded search: unsupported target {c.target}")

        def gather_mask(m):
            """all_gather a boolean row mask along 'sp', bit-packed into
            uint8 lanes before the collective and unpacked after: x8
            fewer wire bytes than gathering the bool array, with an
            exact pack/unpack round trip (Sl is a power-of-two bucket,
            always 8-aligned). pack=False keeps the legacy unpacked
            gather (the before/after comm bench and the differential
            suite's byte-identity anchor)."""
            if not pack or m.shape[1] % 8:
                return jax.lax.all_gather(m, "sp", axis=1, tiled=True)
            pk = jnp.packbits(m, axis=1)  # (Bl, Sl/8) uint8
            pk_g = jax.lax.all_gather(pk, "sp", axis=1, tiled=True)
            return jnp.unpackbits(pk_g, axis=1).astype(bool)  # (Bl, S)

        hoisted: dict = {}

        def parent_tables():
            """The predicate-independent struct operands -- the parent
            index table and row validity, replicated along 'sp' --
            gathered ONCE per launch (lazily, at the first '>>' or '~'
            node) and shared by every struct node of the query: only
            the per-node lhs mask rides a per-node collective."""
            if "pid" not in hoisted:
                hoisted["pid"] = jax.lax.all_gather(
                    cols["span.parent_idx"], "sp", axis=1, tiled=True)
                hoisted["val"] = gather_mask(valid)
            return hoisted["pid"], hoisted["val"]

        def ev_struct(op, lm, rm):
            """Structural relation on the mesh. The '>' relation needs
            only the REPLICATED lhs mask (each row's parent index is in
            the local shard already), so its per-node collective is one
            bit-packed span-axis gather; '>>' and '~' additionally read
            the hoisted parent/validity tables (parent_tables, once per
            launch) and run the single-chip relation (ops/filter
            ev_struct) on the replicated (Bl, S) tables, each chip
            slicing its own span range back out to AND with the local
            rhs."""
            Sl = lm.shape[1]
            if pack and op == ">":
                lm_g = gather_mask(lm)  # lm is valid-masked at the leaves
                pid_l = cols["span.parent_idx"]
                has_p_l = (pid_l >= 0) & valid
                hit = jnp.take_along_axis(
                    lm_g, jnp.clip(pid_l, 0, lm_g.shape[1] - 1), 1)
                return rm & has_p_l & hit & valid
            lm_g = gather_mask(lm)  # (Bl, S)
            if pack:
                pid_g, val_g = parent_tables()
            else:  # legacy: every node gathers all three tables
                pid_g = jax.lax.all_gather(cols["span.parent_idx"], "sp",
                                           axis=1, tiled=True)
                val_g = jax.lax.all_gather(valid, "sp", axis=1, tiled=True)
            Sg = lm_g.shape[1]
            has_p = (pid_g >= 0) & val_g
            safe = jnp.clip(pid_g, 0, Sg - 1)
            if op == ">":
                out = has_p & jnp.take_along_axis(lm_g, safe, 1)
            elif op == ">>":
                acc = has_p & jnp.take_along_axis(lm_g, safe, 1)
                ptr = jnp.where(has_p, safe, -1)
                for _ in range(max(1, (Sg - 1).bit_length())):
                    psafe = jnp.clip(ptr, 0, Sg - 1)
                    alive = ptr >= 0
                    acc = acc | (alive & jnp.take_along_axis(acc, psafe, 1))
                    nxt = jnp.take_along_axis(ptr, psafe, 1)
                    ptr = jnp.where(alive, jnp.where(nxt >= 0, nxt, -1), -1)
                out = acc
            else:  # '~': sibling with a DIFFERENT lhs span under one parent
                lhs_child = (lm_g & has_p).astype(jnp.int32)
                owner = jnp.where(has_p & lm_g, safe, Sg)
                cnt = jax.vmap(
                    lambda o, w: jax.ops.segment_sum(w, o, num_segments=Sg + 1)[:Sg]
                )(owner, lhs_child)
                sibs = jnp.take_along_axis(cnt, safe, 1) - lhs_child
                orphan = (pid_g == -2) & val_g
                any_lhs_orphan = jnp.any(lm_g & orphan, axis=1, keepdims=True)
                out = (has_p & (sibs > 0)) | (orphan & any_lhs_orphan)
            row0_ = jax.lax.axis_index("sp") * Sl
            out_local = jax.lax.dynamic_slice_in_dim(out, row0_, Sl, axis=1)
            return rm & out_local & valid

        def ev_span(t):
            if t == ("true",):
                return valid
            if t == ("false",):
                return jnp.zeros_like(valid)
            if t[0] == "cond":
                return cond_mask(t[1])
            if t[0] == "struct":
                return ev_struct(t[1], ev_span(t[2]), ev_span(t[3]))
            ms = [ev_span(ch) for ch in t[1:]]
            out = ms[0]
            for m in ms[1:]:
                out = (out & m) if t[0] == "and" else (out | m)
            return out

        def seg_reduce(mask):
            if "trace.span_off" in cols:
                # grouped layout: per-shard cumsum + offset gathers, then
                # psum over 'sp' stitches traces straddling shard cuts --
                # no scatter anywhere (see ops/filter._offset_counts)
                off = cols["trace.span_off"]  # (Bl, NT+1) global span rows
                ecs = jnp.concatenate(
                    [jnp.zeros((mask.shape[0], 1), jnp.int32),
                     jnp.cumsum(mask.astype(jnp.int32), axis=1)], axis=1)
                lo = jnp.clip(off[:, :-1] - row0, 0, Sl)
                hi = jnp.clip(off[:, 1:] - row0, 0, Sl)
                local_c = jnp.take_along_axis(ecs, hi, 1) - jnp.take_along_axis(ecs, lo, 1)
            else:
                sid = jnp.clip(jnp.where(mask, cols["span.trace_sid"], NT), 0, NT)
                local_c = jax.vmap(
                    lambda m, s: jax.ops.segment_sum(m.astype(jnp.int32), s,
                                                     num_segments=NT + 1)[:NT]
                )(mask, sid)
            return jax.lax.psum(local_c, "sp")  # (Bl, NT)

        def ev_trace(t):
            if t[0] == "tracify":
                sm = ev_span(t[1])
                span_masks.append(sm)
                return seg_reduce(sm) > 0
            if t == ("true",):
                return jnp.ones((n_spans_l.shape[0], NT), dtype=bool)
            if t == ("false",):
                return jnp.zeros((n_spans_l.shape[0], NT), dtype=bool)
            if t[0] == "cond":
                return cond_cmp(t[1], cols[conds[t[1]].col])
            ms = [ev_trace(ch) for ch in t[1:]]
            out = ms[0]
            for m in ms[1:]:
                out = (out & m) if t[0] == "and" else (out | m)
            return out

        if tree is None:
            span_mask = valid
            count = seg_reduce(span_mask)
            trace_mask = count > 0
        else:
            trace_mask = ev_trace(tree)
            if span_masks:
                span_mask = span_masks[0]
                for m in span_masks[1:]:
                    span_mask = span_mask | m
            else:
                span_mask = valid
            count = seg_reduce(span_mask)
        return trace_mask, jnp.where(trace_mask, count, 0)

    in_specs = [P("dp"), P("dp"), P("dp")] + [P("dp")] * len(table_idxs)
    for n in col_names:
        if n.endswith(".off"):
            in_specs.append(P("dp"))  # owner offsets: replicated along sp
        elif n.startswith(("span.", "sattr.", "rattr.")):
            in_specs.append(P("dp", "sp"))  # row axes shard over sp
        else:
            in_specs.append(P("dp"))
    fn = smap(scoped("mesh_search")(local), mesh, in_specs=tuple(in_specs),
              out_specs=(P("dp"), P("dp")))
    return jax.jit(fn)


def _stack_operands(operands, B: int, n_conds: int):
    """Accept one Operands (replicated to every block) or a per-block
    list (padded with zero rows to B). Returns (ints (B,C,3),
    floats (B,C,2), tables {i: (B, L) u8})."""
    if isinstance(operands, Operands):
        ints = np.broadcast_to(operands.ints[None], (B,) + operands.ints.shape).copy()
        floats = np.broadcast_to(operands.floats[None], (B,) + operands.floats.shape).copy()
        tabs = {}
        for i, t in (operands.tables or {}).items():
            t8 = np.asarray(t, dtype=np.uint8)
            tabs[i] = np.broadcast_to(t8[None], (B,) + t8.shape).copy()
        return ints, floats, tabs
    ints = np.zeros((B, n_conds, 3), dtype=np.int32)
    floats = np.zeros((B, n_conds, 2), dtype=np.float32)
    idxs = set()
    for o in operands:
        idxs.update(o.tables or {})
    tabs = {}
    for i in sorted(idxs):
        L = bucket(max(max(len(o.tables[i]) for o in operands if o.tables and i in o.tables), 1))
        tabs[i] = np.zeros((B, L), dtype=np.uint8)
    for bi, o in enumerate(operands):
        ints[bi, : o.ints.shape[0]] = o.ints
        floats[bi, : o.floats.shape[0]] = o.floats
        for i, t in (o.tables or {}).items():
            tabs[i][bi, : len(t)] = np.asarray(t, dtype=np.uint8)
    return ints, floats, tabs


def struct_pack_enabled() -> bool:
    """TEMPO_STRUCT_PACK=0 reverts struct nodes to the legacy
    per-node unpacked triple gather -- the before/after leg of the
    comm-shrink bench and the differential suite's byte-identity
    anchor. Default: hoisted + bit-packed collectives."""
    import os

    return os.environ.get("TEMPO_STRUCT_PACK", "1") not in ("0", "false")


def sharded_search(mesh, tree, conds, operands, cols: dict[str, np.ndarray],
                   n_spans: np.ndarray, nt: int | None = None):
    """Host entry. `operands`: one Operands (same codes for every block:
    the synthetic-bench path) or a list of per-block Operands (the
    service path -- per-block dictionary codes). cols must already be
    stacked/padded: span-axis (B, S) with S % sp == 0 and B % dp == 0;
    res/trace axis (B, R)/(B, NT) replicated along sp. Returns
    (trace_mask, span_count) as numpy, (B, NT)."""
    names = tuple(sorted(cols))
    NT = nt
    if NT is None and any(n.startswith("trace.") for n in names):
        NT = cols[[n for n in names if n.startswith("trace.")][0]].shape[1]
    if NT is None:
        NT = bucket(int(cols["span.trace_sid"].max(initial=0)) + 1)
    B, S = cols["span.trace_sid"].shape
    R = next((cols[n].shape[1] for n in names if n.startswith("res.")), 1)
    conds = tuple(conds)
    if tree is not None:
        tree = normalize_tree(tree, conds)
    ints, floats, tabs = _stack_operands(operands, B, len(conds))
    table_idxs = tuple(sorted(tabs))
    pack = struct_pack_enabled()
    fn = make_sharded_search(mesh, tree, conds, names, B, S, R, NT, table_idxs,
                             pack=pack)
    arrays = [jnp.asarray(tabs[i]) for i in table_idxs] + [jnp.asarray(cols[n]) for n in names]

    from ..util import costmodel
    from ..util.kerneltel import TEL

    ints_j = jnp.asarray(ints)
    floats_j = jnp.asarray(floats)
    nsp_j = jnp.asarray(n_spans, dtype=np.int32)
    # the legacy (unpacked) program keeps its own costmodel op label so
    # the comm-shrink bench can read both variants' walker prices
    op = "mesh_search" if pack else "mesh_search_nopack"
    with TEL.launch(
        op, ("search", tree, conds, names, B, S, R, NT, table_idxs, pack), S,
        cost=lambda: costmodel.spec(fn, ints_j, floats_j, nsp_j, *arrays,
                                    mesh=mesh),
            blocks=B) as ln:
        from .mesh import DISPATCH_LOCK

        with DISPATCH_LOCK:  # collective programs must not interleave enqueues
            tm, sc = fn(ints_j, floats_j, nsp_j, *arrays)
            out = np.asarray(tm), np.asarray(sc)
        # timeline: the mesh leg with its statically-priced collective bytes
        # (costmodel comm walker; zeros until the background capture lands)
        comm = costmodel.COST.comm_for(op, str(S))
        ln.attrs.update({"comm_bytes": int(sum(comm.values())),
                         **{f"comm.{c}": int(b) for c, b in sorted(comm.items())}})
    return out
